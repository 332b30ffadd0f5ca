"""Every served token says when a client could read it and what it waited
for (ISSUE 40): one `serving.token` span a token on its request's trace.

Load-bearing claims: (a) a request's records are a gapless chain, one a
served token and the prefill's first among them, none for a row dropped at
the collect, one a token of a speculative burst; (b) a record's `dur` is the
number `serving_itl_seconds` observed for that token; (c) the engine names
the cause by what it ran between two tokens of one sequence: an admission
between two steps marks the NEXT token of exactly the rows then running;
(d) a failover's replay continues the chain from the victim's stamp; (e) it
is the record the program already made, under another name: as many a step
as rows advanced, none with telemetry off, and `serving.decode` names the
batch-level step alone; (f) the stamps `tpot_p90_ms` reads are taken where
they were; (g) a whole-prompt prefill whose first token the next step takes
on the device (ISSUE 46) keeps a `serving.prefill` span from its dispatch to
the read of that token, behind the pass's launch, and counts as run from
that read on.
"""
import re
import time

import pytest

import jax

from mxnet_tpu import serving, telemetry
from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params)
from mxnet_tpu.serving.engine import pow2_bucket
from mxnet_tpu.serving.spec import self_draft

from test_serving_failover import fail_read

BS = 8


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = TransformerConfig(vocab=48, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=64)
    return init_transformer_params(jax.random.PRNGKey(0), cfg), cfg


def prompt(start, n, vocab=48):
    return [(start + 5 * t) % vocab for t in range(n)]


def options_of(config, tiny_lm):
    return {"gather": dict(),
            # a 20-token prompt streams in three chunks beside the decoding
            "paged": dict(paged=True, prefill_chunk=8),
            "spec": dict(paged=True, spec_k=3,
                         draft=self_draft(*tiny_lm, 1))}[config]


def end_us(span):
    return span["ts"] + span["dur"]


def timeline(handle):
    """A request's `serving.token` records, in the order they were made."""
    return [s for s in telemetry.spans(handle.trace)
            if s["name"] == "serving.token"]


def gaps_of(handles):
    return [s for h in handles for s in timeline(h)
            if "first" not in s["attrs"]]


def assert_chain(handle, n_prompt):
    """One record a served token at consecutive positions, each starting
    where the one before it ended."""
    line = timeline(handle)
    served = handle.tokens[n_prompt:]
    assert [s["attrs"]["position"] for s in line] \
        == list(range(n_prompt, n_prompt + len(served)))
    assert all(s["dur"] >= 0 for s in line)
    for before, after in zip(line, line[1:]):
        assert after["ts"] == end_us(before)
    assert sum(s["dur"] for s in line) == end_us(line[-1]) - line[0]["ts"]
    return line


REQUESTS = [(prompt(2, 20), 9), (prompt(4, 12), 7), (prompt(6, 10), 5)]


@pytest.mark.parametrize("config", ["gather", "paged", "spec"])
def test_a_requests_tokens_are_a_gapless_chain_from_the_first(tiny_lm,
                                                              config):
    telemetry.tracing.clear()
    srv = serving.serve(tiny_lm, max_batch=4, block_size=BS,
                        **options_of(config, tiny_lm))
    try:
        assert bool(srv.engine.spec) == (config == "spec")
        handles = [srv.submit(p, max_new_tokens=n) for p, n in REQUESTS]
        for h in handles:
            h.result(timeout=300)
        observed = srv.metrics._h_itl
    finally:
        srv.close()
    spans = {s["id"]: s for s in telemetry.spans()}
    for h, (p, n) in zip(handles, REQUESTS):
        line = assert_chain(h, len(p))
        assert len(line) == n
        first, rest = line[0], line[1:]
        # the first spans the prefill and ends before the stamp the metrics
        # took for it, by as much as it says
        assert first["attrs"]["first"] == 1
        assert first["attrs"]["prefills"] >= (3 if config != "gather"
                                              and len(p) == 20 else 1)
        assert first["attrs"]["stamp_lag_us"] == pytest.approx(
            h.t_first_token * 1e6 - end_us(first), abs=2)
        assert first["attrs"]["stamp_lag_us"] >= 0
        assert first["ts"] >= h.t_admit * 1e6 - 1
        # read inside the admission, or (a whole prompt's, carried on the
        # device) behind the launch of the pass's step
        prefill = [s for s in telemetry.spans(h.trace)
                   if s["name"] == "serving.prefill"][-1]
        assert spans[first["parent"]]["name"] == (
            "serving.loop" if prefill["attrs"]["ahead"] else "serving.admit")
        assert not prefill["attrs"]["ahead"] or config == "gather"
        for s in rest:
            assert "first" not in s["attrs"] and "ahead" in s["attrs"]
            assert spans[s["parent"]]["name"] == (
                "serving.spec" if config == "spec" else "serving.decode")
        if config == "spec":        # a burst reaches the client at once
            assert all(s["attrs"]["drains"] == "spec" for s in rest)
            assert any(s["dur"] == 0 for s in rest)
        else:
            assert all("batch" in spans[s["parent"]]["attrs"] for s in rest)
    gaps = gaps_of(handles)
    if config != "spec":
        # a step launched with nothing in flight, or collected in the pass
        # that launched it, says why on its tokens; the rest ran ahead
        assert {"first_step", "last_step"} <= {
            d for s in gaps for d in s["attrs"].get("drains", "").split(",")}
        assert "last_step" in timeline(handles[0])[-1]["attrs"]["drains"]
        assert all((s["attrs"]["ahead"] == 0) == (
            "first_step" in s["attrs"].get("drains", "")) for s in gaps)
        assert any(s["attrs"]["ahead"] and "drains" not in s["attrs"]
                   for s in gaps)
    # (b) the same count and, a microsecond's rounding a token, the same sum
    assert observed.count == len(gaps) == sum(n - 1 for _, n in REQUESTS)
    assert sum(s["dur"] for s in gaps) == pytest.approx(
        observed.sum * 1e6, abs=len(gaps))
    # (e) the step's name is the step's alone
    assert all("batch" in s["attrs"] for s in telemetry.spans()
               if s["name"] == "serving.decode")


def test_a_requests_row_in_the_export_is_one_chain_of_its_tokens(tiny_lm,
                                                                 tmp_path):
    telemetry.tracing.clear()
    srv = serving.serve(tiny_lm, max_batch=2, block_size=BS)
    try:
        h = srv.submit(prompt(3, 9), max_new_tokens=6)
        h.result(timeout=120)
    finally:
        srv.close()
    doc = telemetry.export_perfetto(str(tmp_path / "row.json"))
    row = sorted((e for e in doc["traceEvents"] if e["ph"] == "X"
                  and e["args"].get("trace") == h.trace),
                 key=lambda e: (e["ts"], -e["dur"]))
    assert len({e["tid"] for e in row}) == 1
    names = [e["name"] for e in row if e["name"].startswith("serving.")]
    # submit -> queue -> prefill, under its first token -> the other tokens
    assert set(names[:2]) == {"serving.submit", "serving.queue"}
    assert names[2:4] == ["serving.token", "serving.prefill"]
    assert set(names[4:]) == {"serving.token"}
    tokens = [e for e in row if e["name"] == "serving.token"]
    assert len(tokens) == 6
    for before, after in zip(tokens, tokens[1:]):       # no overlap, no hole
        assert after["ts"] == before["ts"] + before["dur"]


def test_a_row_dropped_at_the_collect_gets_no_record(tiny_lm):
    """It met its `eos_id` in a step the host had not read when the next was
    launched: that step's token for it is nobody's."""
    srv = serving.serve(tiny_lm, max_batch=4, block_size=BS)
    try:
        free = list(srv.generate(prompt(2, 20), max_new_tokens=16,
                                 timeout=120))
        j = next(j for j in range(2, len(free) - 2)
                 if free[j] not in free[:j])
        telemetry.tracing.clear()
        ended = srv.submit(prompt(2, 20), max_new_tokens=16, eos_id=free[j])
        other = srv.submit(prompt(6, 10), max_new_tokens=14)
        assert list(ended.result(timeout=120)) == free[:j + 1]
        other.result(timeout=120)
    finally:
        srv.close()
    assert len(assert_chain(ended, 20)) == j + 1
    assert len(assert_chain(other, 10)) == 14
    # the dropped row was launched all the same
    rows = sum(s["attrs"]["batch"] for s in telemetry.spans()
               if s["name"] == "serving.decode.dispatch")
    assert rows == len(gaps_of([ended, other])) + 1


def test_an_admission_between_two_steps_marks_the_next_token_of_the_rows_running():
    # room for long answers: the late request arrives while both still run,
    # however this machine schedules the test's thread
    cfg = TransformerConfig(vocab=48, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=256)
    model = init_transformer_params(jax.random.PRNGKey(0), cfg), cfg
    telemetry.tracing.clear()
    srv = serving.serve(model, max_batch=4, block_size=BS)
    try:
        running = [srv.submit(prompt(2, 9), max_new_tokens=200),
                   srv.submit(prompt(4, 12), max_new_tokens=220)]
        deadline = time.monotonic() + 120
        while srv.metrics.tokens_generated < 12:    # both are decoding
            assert time.monotonic() < deadline
            time.sleep(0.001)
        late = srv.submit(prompt(6, 20), max_new_tokens=4)
        for h in running + [late]:
            h.result(timeout=120)
    finally:
        srv.close()
    bucket = pow2_bucket(20, lo=8)
    first, *rest = assert_chain(late, 20)
    assert first["attrs"]["first"] == 1
    assert (first["attrs"]["prefills"], first["attrs"]["prefill_tokens"]) \
        == (1, bucket)
    assert all(s["attrs"]["prefills"] == 0 for s in rest)
    for h, n in zip(running, (9, 12)):
        line = assert_chain(h, n)
        since = [s for s in line if end_us(s) > late.t_submit * 1e6]
        marked = [s for s in since if s["attrs"]["prefills"]]
        # exactly one, the next: the first to end after that prefill did,
        # the token of the step that ran BEHIND the prefill on the device
        assert len(marked) == 1 and len(since) < len(line) - 1
        assert (marked[0]["attrs"]["prefills"],
                marked[0]["attrs"]["prefill_tokens"]) == (1, bucket)
        assert marked[0]["ts"] <= end_us(first) <= end_us(marked[0])
        assert marked[0] is min(
            (s for s in line if end_us(s) >= end_us(first)), key=end_us)
        # the token collected before the prefill's in the same pass (the
        # step in flight when the prompt was admitted) did not wait for it
        before = line[line.index(marked[0]) - 1]
        assert first["ts"] <= end_us(before) <= end_us(first)
        assert before["attrs"]["prefills"] == 0


def test_a_failovers_replay_keeps_the_chain(tiny_lm):
    """The replay's prefill yields the next token the client reads: its
    record starts at the victim's last stamp, is no `first`, and its gap is
    the one `serving_itl_seconds` was given for the hop."""
    srv = serving.serve(tiny_lm, max_batch=4, block_size=BS)
    try:
        want = list(srv.generate(prompt(2, 9), max_new_tokens=12,
                                 timeout=120))
        telemetry.tracing.clear()
        base = srv.metrics._h_itl.count, srv.metrics._h_itl.sum
        fail_read(srv.engine, 4)
        h = srv.submit(prompt(2, 9), max_new_tokens=12)
        assert list(h.result(timeout=120)) == want
        assert srv.metrics.failovers == 1
        count = srv.metrics._h_itl.count - base[0]
        total = srv.metrics._h_itl.sum - base[1]
    finally:
        srv.close()
    line = assert_chain(h, 9)
    assert [s["attrs"].get("first") for s in line] == [1] + [None] * 11
    hops = [s for s in line if "stamp_lag_us" in s["attrs"]]
    assert len(hops) == 2           # the two tokens that came out of a prefill
    replayed = hops[1]
    assert replayed["attrs"]["prefills"] == 1
    assert replayed["attrs"]["prefill_tokens"] == pow2_bucket(
        replayed["attrs"]["position"], lo=8)
    assert count == 11
    assert sum(s["dur"] for s in line[1:]) == pytest.approx(total * 1e6,
                                                            abs=11)


def serve_and_count(tiny_lm):
    telemetry.tracing.clear()
    srv = serving.serve(tiny_lm, max_batch=4, block_size=BS)
    try:
        handles = [srv.submit(p, max_new_tokens=n) for p, n in REQUESTS]
        tokens = [list(h.result(timeout=120)) for h in handles]
    finally:
        srv.close()
    return handles, tokens


def test_as_many_records_a_step_as_rows_advanced(tiny_lm):
    """No new record: what the per-request copies of the step's span were."""
    handles, _ = serve_and_count(tiny_lm)
    spans = telemetry.spans()
    tokens = [s for s in spans if s["name"] == "serving.token"]
    accounts = [s for s in spans if s["name"] == "serving.account"]
    steps = {s["id"] for s in spans if s["name"] == "serving.decode"}
    by_step = {}
    for t in tokens:
        if "first" not in t["attrs"]:
            assert t["parent"] in steps
            by_step[t["parent"]] = by_step.get(t["parent"], 0) + 1
    assert sum(by_step.values()) \
        == sum(s["attrs"]["batch"] for s in accounts) \
        == sum(n - 1 for _, n in REQUESTS)
    assert len(tokens) == sum(n for _, n in REQUESTS)
    assert all(s["trace"] in {h.trace for h in handles} for s in tokens)
    # ring only: neither the flight recorder nor the chrome trace has one
    assert "serving.token" not in {e["name"]
                                   for e in telemetry.flight().events()}


def test_with_telemetry_off_no_record_is_made_and_the_tokens_are_the_same(
        tiny_lm, monkeypatch):
    _, want = serve_and_count(tiny_lm)
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    handles, got = serve_and_count(tiny_lm)
    monkeypatch.delenv("MXNET_TELEMETRY")
    assert got == want
    assert telemetry.spans() == []


def test_the_stamps_tpot_reads_are_taken_where_they_were(tiny_lm):
    """`t_first_token` (and the client's, the same for a fresh request) in
    the admitting pass after the prefill's span has closed, which is after
    the pass's step was launched; `t_done` in the account of the pass that
    read the last token, after it."""
    telemetry.tracing.clear()
    srv = serving.serve(tiny_lm, max_batch=2, block_size=BS)
    try:
        h = srv.submit(prompt(3, 9), max_new_tokens=6)
        h.result(timeout=120)
    finally:
        srv.close()
    spans = telemetry.spans()
    line = assert_chain(h, 9)
    prefill, = [s for s in spans if s["name"] == "serving.prefill"]
    admit, = [s for s in spans if s["name"] == "serving.admit"
              and s["ts"] <= prefill["ts"] < end_us(s)]
    loop, = [s for s in spans if s["id"] == admit["parent"]]
    sent, = [s for s in spans if s["name"] == "serving.decode.dispatch"
             and loop["ts"] <= s["ts"] < end_us(loop)]
    assert h.t_client_first_token == h.t_first_token
    assert end_us(admit) <= end_us(sent) <= end_us(prefill)
    assert end_us(prefill) <= h.t_first_token * 1e6 <= end_us(loop)
    # the first token could be read before the prefill's span closed
    assert prefill["ts"] < end_us(line[0]) <= end_us(prefill)
    account = max((s for s in spans if s["name"] == "serving.account"),
                  key=end_us)
    assert account["ts"] <= h.t_done * 1e6 <= end_us(account) + 1
    assert end_us(line[-1]) <= account["ts"]


# -- (g) -----------------------------------------------------------------------

def carried(config, tiny_lm):
    """A family that prefills whole prompts: the dense one, or one with
    experts, whose prefill counts the pairs it routed."""
    if config == "gather":
        return tiny_lm
    import test_serving_ahead as ahead
    weights = ahead.latent_family.make_weights(ahead.LATENT, 11)
    return (ahead.latent_family.program_params(weights),
            ahead.latent_family.program_config(ahead.LATENT, 64))


@pytest.mark.parametrize("config", ["gather", "experts"])
def test_a_carried_prefills_span_runs_from_its_dispatch_to_the_read_of_its_token(
        tiny_lm, config):
    model, requests = carried(config, tiny_lm), REQUESTS
    telemetry.tracing.clear()
    srv = serving.serve(model, max_batch=4, block_size=BS)
    try:
        keep = srv.submit(prompt(1, 7), max_new_tokens=40)
        deadline = time.monotonic() + 120
        while srv.metrics.tokens_generated < 3:     # a step is in flight
            assert time.monotonic() < deadline
            time.sleep(0.001)
        handles = []
        for p, n in requests:           # one a pass: each is its pass's last
            handles.append(srv.submit(p, max_new_tokens=n))
            while handles[-1].t_first_token is None:
                assert time.monotonic() < deadline
                time.sleep(0.001)
        for h in handles + [keep]:
            h.result(timeout=300)
        snap = srv.snapshot()["throughput"]
        ran = srv.engine.prefills_run
        assert re.search(r"^serving_prefills_ahead_total\S* %d$" % ran,
                         srv.prometheus_text(), re.M)
    finally:
        srv.close()
    spans = telemetry.spans()
    by_id = {s["id"]: s for s in spans}
    for h, (p, _) in zip(handles, requests):
        prefill, = [s for s in telemetry.spans(h.trace)
                    if s["name"] == "serving.prefill"]
        attrs = prefill["attrs"]
        assert (attrs["ahead"], attrs["bucket"], attrs["length"]) == (
            1, pow2_bucket(len(p), lo=8), len(p))
        assert (attrs.get("moe_pairs", 0) > 0) == (config == "experts")
        # under the pass, not under its admission, which it outlives: it
        # ends after the pass's step was launched, where the host read its
        # token, which is where the first token's record ends
        loop = by_id[prefill["parent"]]
        assert loop["name"] == "serving.loop"
        admit, = [s for s in spans if s["name"] == "serving.admit"
                  and s["parent"] == loop["id"]]
        sent, = [s for s in spans if s["name"] == "serving.decode.dispatch"
                 and loop["ts"] <= s["ts"] < end_us(loop)]
        assert admit["ts"] <= prefill["ts"] < end_us(admit) <= sent["ts"]
        assert end_us(sent) <= end_us(prefill) <= end_us(loop)
        first = timeline(h)[0]
        assert first["attrs"]["first"] == 1
        assert end_us(first) == end_us(prefill)
        assert first["attrs"]["prefills"] >= 1
        # the pass's own step span closed with the read of the step before:
        # the prefill's wait is not under it
        step, = [s for s in spans if s["name"] == "serving.decode"
                 and s["parent"] == loop["id"]]
        assert end_us(step) <= end_us(prefill)
    # the counters say how often it engaged: every prefill run gave a first
    # token, each carried or read inside its pass for a reason
    assert snap["prefills_ahead"] + sum(snap["prefill_syncs"].values()) \
        == ran == len(requests) + 1
    assert snap["prefill_syncs"] == {}
