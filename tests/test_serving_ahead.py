"""The serving loop keeps one decode step in flight (ISSUE 30): a pass
launches the next step from the last step's tokens on the device, then
collects the last one.

Load-bearing claims: (a) what is served is, token for token, what the
synchronous `Engine.decode_step` gives, on every configuration that
launches ahead, through the events that change a batch between two steps
(a row ending by length, an admission while a step is in flight, a change
of batch bucket); (b) a row that met its `eos_id` in a step the host had
not read when the next was launched is dropped there: no token after the
end is appended, counted or recorded; (c) an engine whose next input
exists on the host alone never launches ahead and says why; (d) the
window costs no program: one decode compilation a signature after the
benchmark's warm-up, and none, by the watchdog and by jax's own compile
log, in a churned window after it; (e) a whole-prompt prefill's first token
takes the same road (ISSUE 46): chosen on the device, taken there by the
step launched behind the prefill, read on the host after that launch; one
that ends its request (its `eos_id`, `max_new_tokens` 1) ends it with that
one token, and the row launched from the former is dropped; where something
needs the token at once it is read inside the pass, and counted by why.
"""
import logging
import re
import threading
import time

import pytest

import numpy as np

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params)
from mxnet_tpu.serving.spec import self_draft
from mxnet_tpu.telemetry import introspect

from chipbench.families import afmoe_lm as kinds_family
from chipbench.families import falcon_h1_lm as state_family
from chipbench.families import latent_moe_lm as latent_family
from chipbench.families import nemotron_h_lm as pattern_family
from chipbench.generators import serving as bench_serving

from test_nemotron_h import TOY as PATTERN

BS, MAX_BATCH = 8, 4

LATENT = {
    "hidden_size": 48, "num_attention_heads": 4, "q_lora_rank": 20,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "intermediate_size": 96, "moe_intermediate_size": 24,
    "n_shared_experts": 1, "n_routed_experts": 4,
    "n_routed_experts_published": 16, "expert_parallel": 4, "expert_rank": 2,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 2.5, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "vocab_size": 96, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "dtype": "float32"}

#: window and full layers over a cache of two kinds, two KV heads under six
#: query heads; a window of 8 at a block of 8 is a ring of two blocks, which
#: a 20-token prompt and its 16 tokens wrap twice
KINDS = {
    "hidden_size": 48, "num_attention_heads": 6, "num_key_value_heads": 2,
    "head_dim": 8, "intermediate_size": 96, "moe_intermediate_size": 24,
    "num_shared_experts": 1, "num_experts": 2, "num_experts_published": 8,
    "expert_parallel": 4, "expert_rank": 2, "num_experts_per_tok": 4,
    "route_scale": 2.448, "num_hidden_layers": 3, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "full_attention",
                    "sliding_attention"],
    "sliding_window": 8, "vocab_size": 96, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "mup_enabled": True, "dtype": "float32"}

#: attention heads and state-space heads side by side in every layer: a
#: recurrent state a sequence beside its keys and values, found through the
#: row's table as rows change place (the published multipliers are the
#: 5,120-wide model's: these keep a 32-wide one's rows of the order of 1)
STATE = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "intermediate_size": 64, "mamba_d_ssm": 32,
    "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "num_hidden_layers": 2, "vocab_size": 96, "rms_norm_eps": 1e-5,
    "rope_theta": 1e11, "attention_in_multiplier": 1,
    "attention_out_multiplier": 8.0, "embedding_multiplier": 50.0,
    "key_multiplier": 1.0, "lm_head_multiplier": 8.0,
    "mlp_multipliers": [8.0, 8.0], "ssm_in_multiplier": 1.0,
    "ssm_out_multiplier": 8.0, "ssm_multipliers": [8.0, 8.0, 8.0, 8.0, 4.0],
    "dtype": "float32", "state_dtype": "float32"}

#: configuration -> (model family, what `serve` and `Engine` are told)
CONFIGS = {
    "gather": ("dense", dict()),
    # chunked prefill co-scheduled with the decode steps: a 20-token prompt
    # streams in three chunks while the others decode
    "paged": ("dense", dict(paged=True, prefill_chunk=8)),
    "paged_q8": ("dense", dict(paged=True, kv_quant=True)),
    "tp2": ("dense", dict(paged=True, tp=2)),
    "latent": ("latent", dict()),
    "kinds": ("kinds", dict()),
    "state": ("state", dict()),
    # a layer is ONE mixer by a pattern's letter: a state alone, keys and
    # values alone, or experts and no cache
    "pattern": ("pattern", dict()),
}
#: those that prefill a whole prompt in one program, whose first token the
#: next decode step takes on the device
WHOLE_PROMPT = sorted(c for c, (_, o) in CONFIGS.items() if not o.get("paged"))


@pytest.fixture(scope="module")
def models():
    cfg = TransformerConfig(vocab=48, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=64)
    weights = latent_family.make_weights(LATENT, 11)
    kinds = kinds_family.make_weights(KINDS, 11)
    state = state_family.make_weights(STATE, 11)
    pattern = pattern_family.make_weights(PATTERN, 11)
    return {"pattern": (pattern_family.program_params(pattern),
                        pattern_family.program_config(PATTERN, 64)),
            "dense": (init_transformer_params(jax.random.PRNGKey(0), cfg),
                      cfg),
            "latent": (latent_family.program_params(weights),
                       latent_family.program_config(LATENT, 64)),
            "kinds": (kinds_family.program_params(kinds),
                      kinds_family.program_config(KINDS, 64)),
            "state": (state_family.program_params(state),
                      state_family.program_config(STATE, 64))}


def prompt(start, n, vocab=48):
    return [(start + 5 * t) % vocab for t in range(n)]


def oracle(model, options, requests):
    """Each request alone through the synchronous door: `start`, then
    `decode_step` until it is done. Returns the generated tokens of each."""
    eng = serving.Engine(serving.server._resolve_model(model),
                         max_batch=MAX_BATCH, block_size=BS, **options)
    out = []
    try:
        for tokens, max_new, eos in requests:
            seq = eng.start(tokens, max_new, eos_id=eos)
            while not seq.done:
                assert eng.decode_step([seq]) == [seq]
            out.append(list(seq.generated))
            eng.release(seq, reusable=False)
    finally:
        eng.close()
    return out


def carried(snap):
    """Whole-prompt prefills a server launched to carry their first token:
    those it did, and those with another prompt admitted behind them in the
    same pass (one first token is in flight at a time)."""
    return snap["prefills_ahead"] + snap["prefill_syncs"].get(
        "more_admitted", 0)


def serve_churned(srv, first, later):
    """`first` at once (a full batch and a queue behind it), `later` once
    steps are under way: admissions land while a step is in flight, rows
    end by length in the middle of a batch, and the batch shrinks through
    its buckets as the last requests run out."""
    handles = [srv.submit(p, max_new_tokens=n, eos_id=e) for p, n, e in first]
    deadline = time.perf_counter() + 120
    while srv.metrics.tokens_generated < 3:
        assert time.perf_counter() < deadline
        time.sleep(0.002)
    handles += [srv.submit(p, max_new_tokens=n, eos_id=e)
                for p, n, e in later]
    return [list(h.result(timeout=300)) for h in handles]


FIRST = [(prompt(1, 9), 3, None), (prompt(2, 20), 12, None),
         (prompt(3, 5), 6, None), (prompt(4, 12), 16, None),
         (prompt(5, 7), 2, None), (prompt(6, 10), 9, None)]
LATER = [(prompt(7, 6), 5, None), (prompt(8, 11), 1, None),
         (prompt(9, 9), 7, None)]


# -- (a) -----------------------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="tp steps need >= 2 (emulated) devices")
@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_served_tokens_are_the_synchronous_steps_tokens_under_churn(
        models, config):
    family, options = CONFIGS[config]
    model = models[family]
    want = oracle(model, options, FIRST + LATER)
    srv = serving.serve(model, max_batch=MAX_BATCH, block_size=BS, **options)
    try:
        assert srv.engine.sync_reason is None
        assert bool(srv.engine.paged) == bool(options.get("paged"))
        assert srv.engine.tp == options.get("tp", 1)
        got = serve_churned(srv, FIRST, LATER)
        snap = srv.snapshot()["throughput"]
    finally:
        srv.close()
    assert got == want
    # a request's first token is the prefill's; every other is a step's
    assert snap["tokens_generated"] == sum(len(g) - 1 for g in want)
    assert snap["decode_steps_ahead"] > 0.8 * snap["decode_steps"]
    assert set(snap["decode_drains"]) <= {"first_step", "last_step"}
    # every whole-prompt prefill's first token was carried, the one with
    # nothing to launch too, but for the prompts with another admitted
    # behind them in one pass; a prompt that came in chunks says why not
    n = len(FIRST + LATER)
    if options.get("paged"):
        assert (snap["prefills_ahead"], snap["prefill_syncs"]) == (
            0, {"paged": n})
    else:
        assert set(snap["prefill_syncs"]) <= {"more_admitted"}
        assert carried(snap) == n and snap["prefills_ahead"] >= 4
    # a full batch, and smaller ones down through the buckets as it drained
    batches = {s["attrs"]["batch"] for s in telemetry.spans()
               if s["name"] == "serving.decode.dispatch"}
    assert 4 in batches and len(batches) >= 3, batches


def test_a_request_that_ends_in_its_prefill_is_finished_with_no_step(models):
    """Nothing to launch and nothing to collect: the pass still evicts."""
    srv = serving.serve(models["dense"], max_batch=2, block_size=BS)
    try:
        assert len(srv.generate(prompt(2, 9), max_new_tokens=1,
                                timeout=60)) == 1
        snap = srv.snapshot()
        assert snap["throughput"]["decode_steps"] == 0
        assert snap["requests"]["completed"] == 1
        assert srv.engine.cache.pool.in_use == 0
    finally:
        srv.close()


# -- (b) -----------------------------------------------------------------------

def mid_stream_eos(generated):
    """(index, token) of a generated token, not the first nor the last two,
    that no earlier generated token equals: declared `eos_id`, the request
    ends there, in a step the host reads after the next was launched."""
    for j in range(2, len(generated) - 2):
        if generated[j] not in generated[:j]:
            return j, generated[j]
    raise AssertionError("no usable token in %r" % (generated,))


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="tp steps need >= 2 (emulated) devices")
@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_a_row_that_met_its_eos_is_dropped_from_the_step_launched_ahead(
        models, config):
    family, options = CONFIGS[config]
    model = models[family]
    plain = [(prompt(2, 20), 16, None), (prompt(4, 12), 14, None),
             (prompt(6, 10), 12, None)]
    free = oracle(model, options, plain)
    ends = [mid_stream_eos(g) for g in free[:2]]
    # two requests end by their eos, at different steps; the third runs on
    requests = [(p, n, eos) for (p, n, _), (_, eos) in zip(plain, ends)] \
        + plain[2:]
    want = [g[:j + 1] for g, (j, _) in zip(free, ends)] + free[2:]
    assert oracle(model, options, requests) == want
    telemetry.tracing.clear()
    srv = serving.serve(model, max_batch=MAX_BATCH, block_size=BS, **options)
    try:
        handles = [srv.submit(p, max_new_tokens=n, eos_id=e)
                   for p, n, e in requests]
        got = [list(h.result(timeout=300)) for h in handles]
        met = srv.metrics
        snap = srv.snapshot()["throughput"]
        assert got == want
        # never counted ...
        assert snap["tokens_generated"] == sum(len(g) - 1 for g in want)
        assert snap["decode_steps_ahead"] > 0
        # ... never given a `token_generated` record (one a decode token:
        # the gap from the token before it) ...
        assert met._h_itl.count == sum(len(g) - 1 for g in want)
        # ... nor a `serving.token` record on its request's row: one a
        # served token, the prefill's first, the last at the request's last
        # position
        for h, g, (p, _, _) in zip(handles, want, requests):
            assert h.tokens == p + g
            served = [s["attrs"]["position"] for s in telemetry.spans(h.trace)
                      if s["name"] == "serving.token"]
            assert served == list(range(len(p), len(p) + len(g)))
        # the rows that ran past their end were launched all the same: the
        # steps dispatched hold more rows than the tokens appended
        rows = sum(s["attrs"]["batch"] for s in telemetry.spans()
                   if s["name"] == "serving.decode.dispatch")
        assert rows == snap["tokens_generated"] + len(ends)
    finally:
        srv.close()         # the pool's audit: no block leaked


# -- (c) -----------------------------------------------------------------------

def word_lm():
    net = mx.models.RNNModel(mode="lstm", vocab_size=32, num_embed=16,
                             num_hidden=16, num_layers=1, dropout=0.0)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((4, 2)))                 # materialize params
    return net


SYNC = {
    "spec": lambda m: (m["dense"], dict(
        paged=True, draft=self_draft(*m["dense"], 1), spec_k=3)),
    "no_cache": lambda m: (word_lm(), dict(vocab=32, max_len=32,
                                           time_major=True)),
    "keep_logits": lambda m: (m["dense"], dict(keep_logits=True)),
}


@pytest.mark.parametrize("reason", sorted(SYNC))
def test_an_engine_whose_next_input_is_on_the_host_never_launches_ahead(
        models, reason):
    model, options = SYNC[reason](models)
    srv = serving.serve(model, max_batch=2, **options)
    try:
        assert srv.engine.sync_reason == reason
        handles = [srv.submit(prompt(1 + i, 5, vocab=32), max_new_tokens=6 + i)
                   for i in range(3)]
        assert [len(h.result(timeout=300)) for h in handles] == [6, 7, 8]
        snap = srv.snapshot()["throughput"]
        assert snap["decode_steps_ahead"] == 0
        assert snap["decode_drains"] == {reason: snap["decode_steps"]}
        assert srv._flight is None
        # nor is a first token carried: each is read inside its pass
        assert (snap["prefills_ahead"], snap["prefill_syncs"]) == (
            0, {reason: 3})
    finally:
        srv.close()


# -- (d) -----------------------------------------------------------------------

class Door:
    """What the benchmark's warm-up drives (`chipbench/families`)."""

    def __init__(self, srv):
        self.srv, self.max_batch = srv, srv.engine.max_batch

    def submit(self, tokens, max_new):
        return self.srv.submit(tokens, max_new_tokens=max_new)


class CompileLog(logging.Handler):
    """jax's own word on every program it compiles, instrumented or not."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.compiled = []

    def emit(self, record):
        text = record.getMessage()
        if text.startswith(("Compiling ", "Finished XLA compilation")):
            self.compiled.append(text[:120])

    def __enter__(self):
        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)
        return self

    def __exit__(self, *exc):
        logging.getLogger("jax").removeHandler(self)
        jax.config.update("jax_log_compiles", False)


#: decode signatures the waves of 1 .. 4 requests of three tokens compile:
#: one a batch bucket (and, paged, the one table width these lengths have),
#: which is what the engine that read every step before the next compiled
BUCKETS = 3


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="tp steps need >= 2 (emulated) devices")
@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_the_window_compiles_nothing_after_the_benchmarks_warm_up(
        models, config):
    family, options = CONFIGS[config]
    # prompts of 9 and 10 tokens, at most 6 more: two blocks throughout
    window = [(prompt(1 + i, 9 + i % 2), n, None)
              for i, n in enumerate([3, 6, 4, 5, 2, 6, 3, 5, 4])]
    srv = serving.serve(models[family], max_batch=MAX_BATCH, block_size=BS,
                        **options)
    try:
        eng = srv.engine
        bench_serving.warm_up(Door(srv), [p for p, _, _ in window])
        sigs = {sig for kind, sig in eng._sigs if kind == "decode"}
        assert len(sigs) == BUCKETS, sorted(eng._sigs)
        # a step launched with none in flight and a step launched ahead are
        # one signature: no bucket was compiled twice
        assert eng.decode_compilations == BUCKETS, sorted(eng._sigs)
        # a wave is two steps, the second launched from the first's tokens
        ahead = srv.snapshot()["throughput"]["decode_steps_ahead"]
        assert ahead >= MAX_BATCH
        # ... and every whole-prompt prefill's first token was carried: the
        # two small programs of that are compiled too
        firsts = srv.snapshot()["throughput"]["prefills_ahead"]
        assert (firsts > 0) == (config in WHOLE_PROMPT)
        launched = carried(srv.snapshot()["throughput"])
        mark = introspect.watchdog().mark()
        compiled = eng.decode_compilations, eng.prefill_compilations
        with CompileLog() as log:
            got = serve_churned(srv, window[:6], window[6:])
        assert [len(g) for g in got] == [n for _, n, _ in window]
        assert (eng.decode_compilations, eng.prefill_compilations) == compiled
        assert [e for e in introspect.watchdog().events()
                if e["seq"] > mark] == []
        assert log.compiled == []
        snap = srv.snapshot()["throughput"]
        assert snap["decode_steps_ahead"] > ahead
        assert snap["prefills_ahead"] > firsts or config not in WHOLE_PROMPT
        assert carried(snap) == (
            launched + len(window) if config in WHOLE_PROMPT else 0)
    finally:
        srv.close()


# -- (e) -----------------------------------------------------------------------

def admitted_in_flight(srv, requests):
    """`requests` one by one, each submitted once steps are under way and
    the one before it has its first token: every admission lands with a
    step in flight and rows that go on."""
    keep = [srv.submit(prompt(1, 9), max_new_tokens=40),
            srv.submit(prompt(3, 12), max_new_tokens=40)]
    deadline = time.perf_counter() + 120
    while srv.metrics.tokens_generated < 3:
        assert time.perf_counter() < deadline
        time.sleep(0.002)
    handles = []
    for p, n, e in requests:
        handles.append(srv.submit(p, max_new_tokens=n, eos_id=e))
        while handles[-1].t_first_token is None:
            assert time.perf_counter() < deadline
            time.sleep(0.002)
    got = [list(h.result(timeout=300)) for h in handles]
    for h in keep:
        h.result(timeout=300)
    return handles, got


@pytest.mark.parametrize("config", WHOLE_PROMPT)
def test_a_first_token_that_ends_its_request_ends_it_with_that_one_token(
        models, config):
    family, options = CONFIGS[config]
    model = models[family]
    plain = [(prompt(2, 20), 6, None), (prompt(4, 12), 5, None)]
    free = oracle(model, options, plain)
    # the first ends by its eos in its prefill, the second by its length
    # there; the third goes on, and its first token is nobody's eos
    requests = [(plain[0][0], 6, free[0][0]), (plain[1][0], 1, None),
                (prompt(6, 10), 4, None)]
    want = oracle(model, options, requests)
    assert [len(g) for g in want] == [1, 1, 4]
    assert want[0] == free[0][:1] and want[1] == free[1][:1]
    telemetry.tracing.clear()
    srv = serving.serve(model, max_batch=MAX_BATCH, block_size=BS, **options)
    try:
        handles, got = admitted_in_flight(srv, requests)
        assert got == want
        snap = srv.snapshot()["throughput"]
        assert snap["prefills_ahead"] >= len(requests)
        assert carried(snap) == len(requests) + 2
        spans = telemetry.spans()
        # the row of the first was launched from its token all the same (the
        # host had not read it), and dropped at that step's collect: never
        # appended, counted or recorded. The second was never launched.
        rows = sum(s["attrs"]["batch"] for s in spans
                   if s["name"] == "serving.decode.dispatch")
        assert rows == snap["tokens_generated"] + 1
        assert snap["tokens_generated"] == 2 * 39 + 3
        for h, g, (p, _, _) in zip(handles, want, requests):
            assert h.tokens == p + g
            served = [s["attrs"]["position"] for s in telemetry.spans(h.trace)
                      if s["name"] == "serving.token"]
            assert served == list(range(len(p), len(p) + len(g)))
    finally:
        srv.close()         # the pool's audit: no block leaked
    assert srv.engine.cache.pool.in_use == 0


@pytest.mark.parametrize("config", WHOLE_PROMPT)
def test_two_first_tokens_are_carried_into_one_step_beside_the_rows_that_go_on(
        models, config):
    """The engine's half alone, pass by pass: two prompts admitted in one
    pass while a step is in flight; the step launched behind them takes both
    tokens on the device, each in a place of the carry no other row has."""
    family, options = CONFIGS[config]
    model = models[family]
    requests = [(prompt(1, 9), 8, None), (prompt(2, 20), 8, None),
                (prompt(3, 5), 8, None), (prompt(4, 12), 8, None)]
    want = oracle(model, options, requests)
    eng = serving.Engine(serving.server._resolve_model(model),
                         max_batch=MAX_BATCH, block_size=BS, **options)
    try:
        assert eng.first_sync_reason is None
        seqs = [eng.start(p, n, eos_id=e) for p, n, e in requests[:2]]
        collected, flight = eng.decode_pass(seqs)
        assert collected == [] and flight.drains == ["first_step"]
        late = [eng.start(p, n, eos_id=e, hold=True)
                for p, n, e in requests[2:]]
        # in flight: nothing of either prefill has been read
        assert [len(s.tokens) for s in late] == [5, 12]
        assert all(s.first is not None and s.first.ahead for s in late)
        ran = eng.prefills_run
        seqs += late
        collected, flight = eng.decode_pass(seqs, after=flight)
        assert len(flight.seqs) == 4 and flight.ahead
        assert [len(s.tokens) for s in late] == [5, 12]
        # the step collected before the read did not wait for them
        assert eng.prefills_run == ran
        assert list(eng.collect_firsts()) == late
        assert eng.prefills_run == ran + 2
        assert [len(s.tokens) for s in late] == [6, 13]
        assert all(s.first is None for s in late)
        while flight is not None:
            collected, flight = eng.decode_pass(seqs, after=flight)
        assert [list(s.generated) for s in seqs] == want
    finally:
        for s in seqs:
            eng.release(s, reusable=False)
        eng.close()


def test_of_the_prompts_admitted_in_one_pass_the_last_is_the_one_carried(
        models):
    """One first token is in flight at a time: a `serving.prefill` span
    then holds its own program on the device and no other prompt's."""
    requests = FIRST[:3]
    want = oracle(models["dense"], {}, requests)
    telemetry.tracing.clear()
    srv = serving.serve(models["dense"], max_batch=MAX_BATCH, block_size=BS)
    try:
        queued, real = threading.Event(), srv.scheduler.admit

        def admit(*args, **kw):         # the pass waits for all three
            queued.wait(60)
            return real(*args, **kw)

        srv.scheduler.admit = admit
        handles = [srv.submit(p, max_new_tokens=n) for p, n, _ in requests]
        queued.set()
        assert [list(h.result(timeout=300)) for h in handles] == want
        snap = srv.snapshot()["throughput"]
        assert (snap["prefills_ahead"], snap["prefill_syncs"]) == (
            1, {"more_admitted": 2})
        prefills = sorted((s for s in telemetry.spans()
                           if s["name"] == "serving.prefill"),
                          key=lambda s: s["ts"])
        assert [s["attrs"]["ahead"] for s in prefills] == [0, 0, 1]
        for before, after in zip(prefills, prefills[1:]):   # one at a time
            assert before["ts"] + before["dur"] <= after["ts"]
    finally:
        srv.close()


def test_the_synchronous_step_reads_a_first_token_in_flight_before_it_launches(
        models):
    """`decode_step` leaves nothing in flight: not a first token either."""
    request = (prompt(2, 9), 5, None)
    want, = oracle(models["dense"], {}, [request])
    eng = serving.Engine(serving.server._resolve_model(models["dense"]),
                         max_batch=2, block_size=BS)
    try:
        seq = eng.start(*request[:2], hold=True)
        assert seq.first is not None and len(seq.tokens) == 9
        while not seq.done:
            assert eng.decode_step([seq]) == [seq]
            assert seq.first is None
        assert list(seq.generated) == want
        eng.release(seq, reusable=False)
    finally:
        eng.close()


def test_the_device_chooses_the_token_numpy_would(models):
    """Among equal logits the lowest index, as `np.argmax` on the host."""
    from mxnet_tpu.serving.engine import carry_first, first_token
    logits = np.zeros((48,), np.float32)
    logits[[7, 19, 30]] = 3.5
    assert int(first_token(jnp.asarray(logits))) == np.argmax(logits) == 7
    carry = carry_first(jnp.arange(4, dtype=jnp.int32), jnp.int32(9),
                        jnp.int32(2))
    assert carry.tolist() == [0, 1, 9, 3]
    # and through an engine that keeps its logits: the token served is the
    # best of the logits the prefill's program returned
    eng = serving.Engine(serving.server._resolve_model(models["dense"]),
                         max_batch=2, block_size=BS, keep_logits=True)
    try:
        seq = eng.start(prompt(2, 9), 2)
        assert seq.tokens[9] == int(np.argmax(seq.token_logits[0]))
        eng.release(seq, reusable=False)
    finally:
        eng.close()


READ_INSIDE = {
    "paged": lambda srv: None,
    # a prefill replica's hook, here one that finds no decode replica: the
    # sequence stays, and its first token was read before the hook was asked
    "hand_off": lambda srv: setattr(srv, "on_prefill_done",
                                    lambda srv, req, tokens: False),
}


@pytest.mark.parametrize("reason", sorted(READ_INSIDE))
def test_a_first_token_needed_at_once_is_read_inside_the_pass(models, reason):
    options = dict(paged=True, prefill_chunk=8) if reason == "paged" else {}
    want = oracle(models["dense"], options, FIRST[:4])
    telemetry.tracing.clear()
    srv = serving.serve(models["dense"], max_batch=2, block_size=BS,
                        **options)
    try:
        READ_INSIDE[reason](srv)
        assert srv.engine.sync_reason is None
        assert srv._first_sync_reason() == reason
        handles = [srv.submit(p, max_new_tokens=n) for p, n, _ in FIRST[:4]]
        assert [list(h.result(timeout=300)) for h in handles] == want
        snap = srv.snapshot()["throughput"]
        assert (snap["prefills_ahead"], snap["prefill_syncs"]) == (
            0, {reason: 4})
        assert snap["decode_steps_ahead"] > 0       # the steps still are
        assert re.search(r"^serving_prefill_syncs_%s_total\S* 4$" % reason,
                         srv.prometheus_text(), re.M)
        spans = {s["id"]: s for s in telemetry.spans()}
        prefills = [s for s in spans.values() if s["name"] == "serving.prefill"]
        assert prefills and all(s["attrs"]["ahead"] == 0 for s in prefills)
        assert all(spans[s["parent"]]["name"] == "serving.admit"
                   for s in prefills)
    finally:
        srv.close()
