"""Spans with parents (ISSUE 24): the stack behind `parent`, the tree one
serving-loop iteration records, `TrainStep`'s `train.dispatch`, and the names
the jitted step programs carry into a device trace."""
import collections
import threading

import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import profiler, serving, telemetry
from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params)


@pytest.fixture(autouse=True)
def _clean_rings():
    telemetry.tracing.clear()
    telemetry.flight().clear()
    yield
    telemetry.tracing.clear()
    telemetry.flight().clear()


def by_name(spans=None):
    out = collections.defaultdict(list)
    for s in telemetry.spans() if spans is None else spans:
        out[s["name"]].append(s)
    return out


# -- parents -----------------------------------------------------------------


def test_a_span_is_the_parent_of_what_opens_inside_it():
    with telemetry.span("outer") as outer:
        with telemetry.span("inner") as inner:
            telemetry.record_span("timed", 0, 1)
        with telemetry.span("second"):
            pass
    got = by_name()
    assert got["outer"][0]["parent"] is None
    assert got["outer"][0]["id"] == outer.id
    assert got["inner"][0]["parent"] == outer.id
    assert got["timed"][0]["parent"] == inner.id
    assert got["second"][0]["parent"] == outer.id      # `inner` was popped
    assert all("parent" in s for s in telemetry.spans())
    args = {e["name"]: e["args"] for e in
            telemetry.export_perfetto()["traceEvents"] if e["ph"] == "X"}
    assert args["inner"]["parent"] == outer.id
    assert args["outer"]["parent"] is None


def test_a_copy_names_its_parent_whatever_is_open():
    with telemetry.span("step") as step:
        pass
    with telemetry.span("bookkeeping") as later:
        telemetry.record_span("copy", 0, 1, parent=step.id)
        telemetry.record_span("plain", 0, 1)
    got = by_name()
    assert got["copy"][0]["parent"] == step.id
    assert got["plain"][0]["parent"] == later.id


def test_two_threads_do_not_share_a_stack():
    inside, release = threading.Event(), threading.Event()

    def other():
        with telemetry.span("other.outer"):
            inside.set()
            assert release.wait(30)

    t = threading.Thread(target=other)
    t.start()
    assert inside.wait(30)
    with telemetry.span("mine"):            # `other.outer` is open meanwhile
        pass
    release.set()
    t.join(30)
    assert not t.is_alive()
    got = by_name()
    assert got["mine"][0]["parent"] is None
    assert got["other.outer"][0]["parent"] is None


def test_an_exception_pops_the_stack():
    with pytest.raises(ValueError):
        with telemetry.span("outer"):
            with telemetry.span("failing"):
                raise ValueError("boom")
    with telemetry.span("after"):
        pass
    got = by_name()
    assert got["failing"][0]["attrs"]["error"] == "ValueError"
    assert got["after"][0]["parent"] is None
    assert telemetry.tracing._open_spans() == []


def test_a_cancelled_span_leaves_no_record_and_no_parent_behind():
    with telemetry.span("idle") as idle:
        idle.cancel()
    with telemetry.span("after"):
        pass
    got = by_name()
    assert "idle" not in got and got["after"][0]["parent"] is None


def test_telemetry_off_records_and_pushes_nothing(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    with telemetry.span("dead") as dead:
        assert dead.id is None
        assert telemetry.tracing._open_spans() == []
        assert telemetry.record_span("copy", 0, 1, parent=dead.id) is None
    assert telemetry.spans() == []


def test_the_ring_counts_a_drop_by_appends_not_by_ids(monkeypatch):
    """A parent's id is older than its children's and it is appended after
    them: an export must bless every span it wrote, the children too."""
    monkeypatch.setattr(telemetry.tracing, "_spans",
                        collections.deque(maxlen=3))
    dropped = telemetry.default_registry().counter("spans_dropped_total")
    with telemetry.span("parent"):
        telemetry.record_span("child1", 0, 1)
        telemetry.record_span("child2", 0, 1)
    base = dropped.value
    telemetry.export_perfetto()
    for i in range(3):                      # overwrite what was exported
        telemetry.record_span("next%d" % i, 0, 1)
    assert dropped.value == base
    telemetry.record_span("unseen", 0, 1)   # overwrites `next0`, never seen
    assert dropped.value == base + 1


# -- the serving thread ------------------------------------------------------

TREE = {"serving.admit": "serving.loop", "serving.decode": "serving.loop",
        "serving.decode.build": "serving.decode",
        "serving.decode.dispatch": "serving.decode",
        "serving.decode.readback": "serving.decode",
        "serving.decode.append": "serving.loop",
        "serving.account": "serving.loop"}


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = TransformerConfig(vocab=48, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=64)
    return init_transformer_params(jax.random.PRNGKey(0), cfg), cfg


def serve_three(tiny_lm, **options):
    srv = serving.serve(tiny_lm, max_batch=4, num_blocks=64, **options)
    try:
        handles = [srv.submit([1 + i, 2, 3, 4, 5], max_new_tokens=5 + i)
                   for i in range(3)]
        tokens = [list(h.result(timeout=120)) for h in handles]
        thread = srv._thread.ident
    finally:
        srv.close()
    return tokens, thread


def inside(child, parent):
    return parent["ts"] <= child["ts"] and \
        child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]


@pytest.mark.parametrize("paged", [None, True])
def test_every_decoding_iteration_records_one_tree(tiny_lm, paged):
    """A pass launches a step and collects the one the pass before launched
    (ISSUE 30): its `serving.decode` span holds the build and the dispatch of
    the first and the readback of the second; the append and the account of
    the collected step follow it. The first pass collects nothing; the last
    collects its own step too."""
    _, thread = serve_three(tiny_lm, paged=paged)
    spans = [s for s in telemetry.spans() if s["tid"] == thread]
    ids = {s["id"]: s for s in spans}
    assert spans and all("parent" in s for s in spans)
    roots = {s["name"] for s in spans if s["parent"] is None}
    assert roots == {"serving.loop"}
    loops = sorted((s for s in spans if s["name"] == "serving.loop"),
                   key=lambda s: s["attrs"]["it"])
    assert len({s["attrs"]["it"] for s in loops}) == len(loops)
    passes = []
    for loop in loops:
        under = by_name(s for s in spans if s["parent"] == loop["id"]
                        or ids.get(s["parent"], {}).get("parent") == loop["id"])
        assert len(under["serving.admit"]) == 1        # every working pass
        if "serving.decode" in under:
            passes.append(under)
    assert len(passes) >= 6         # the longest request decodes 6 tokens
    launched, appended = None, 0    # the batch of the step in flight
    for n, under in enumerate(passes):
        first, last = n == 0, n == len(passes) - 1
        # the name is the batch-level step's alone (ISSUE 40): a request's
        # tokens are `serving.token` records under it
        step, = under["serving.decode"]
        assert "batch" in step["attrs"]
        tokens = [s for s in under["serving.token"]
                  if "first" not in s["attrs"]]
        collected = ([] if first else [launched]) \
            + ([step["attrs"]["batch"]] if last else [])
        # a record a request for every token this pass appended, ending
        # where the host held the step's tokens: inside the pass's span
        assert len(tokens) == sum(collected)
        appended += len(tokens)
        assert all(t["parent"] == step["id"] and t["trace"] is not None
                   and step["ts"] <= t["ts"] + t["dur"]
                   <= step["ts"] + step["dur"] for t in tokens)
        found = {name: [s for s in under[name] if "batch" in s["attrs"]]
                 for name in TREE}
        for name, parent in TREE.items():
            for s in found[name]:
                assert ids[s["parent"]]["name"] == parent
                assert inside(s, ids[s["parent"]])
        build, = found["serving.decode.build"]
        sent, = found["serving.decode.dispatch"]
        assert sent["attrs"]["ahead"] == (0 if first else 1)
        assert build["attrs"]["batch"] == sent["attrs"]["batch"] \
            == step["attrs"]["batch"]
        for name in ("serving.decode.readback", "serving.decode.append",
                     "serving.account"):
            assert [s["attrs"]["batch"] for s in found[name]] == collected
        # the interval: opens before the arrays are built, launches, then
        # closes with the (last) readback, and does not cover what follows
        assert step["ts"] <= build["ts"]
        assert build["ts"] + build["dur"] <= sent["ts"]
        for back in found["serving.decode.readback"]:
            assert back["ts"] >= sent["ts"] + sent["dur"]
            assert back["ts"] + back["dur"] <= step["ts"] + step["dur"]
        for append in found["serving.decode.append"]:
            assert append["ts"] >= step["ts"] + step["dur"]
        for account in found["serving.account"]:
            assert account["ts"] >= found["serving.decode.append"][-1]["ts"]
        launched = step["attrs"]["batch"]
    # requests of 5, 6 and 7 tokens: one from the prefill, the rest a step
    assert appended == 4 + 5 + 6
    admits = [s for s in spans if s["name"] == "serving.admit"]
    assert sum(s["attrs"]["admitted"] for s in admits) == 3
    for s in spans:
        # queue waits hang under admit, and so does a prefill read there (a
        # chunk); a whole prompt's outlives the admission, its first token
        # read behind the pass's launch (ISSUE 46): it hangs under the pass
        if s["name"] in ("serving.prefill", "serving.queue"):
            carried = s["name"] == "serving.prefill" and s["attrs"]["ahead"]
            assert ids[s["parent"]]["name"] == (
                "serving.loop" if carried else "serving.admit")
    # the last prompt of every pass that admitted whole prompts is carried
    assert any(s["name"] == "serving.prefill" and s["attrs"]["ahead"]
               for s in spans) == (not paged)
    # the fine-grained spans stay out of the flight ring
    flown = {e["name"] for e in telemetry.flight().events()}
    assert {"serving.loop", "serving.admit", "serving.decode"} <= flown
    assert not flown & {"serving.decode.build", "serving.decode.dispatch",
                        "serving.decode.readback", "serving.decode.append",
                        "serving.account", "serving.token"}


def test_a_pass_that_only_waits_records_nothing(tiny_lm):
    import time
    srv = serving.serve(tiny_lm, max_batch=4, num_blocks=64)
    try:
        time.sleep(0.3)         # the loop wakes a few times and finds nothing
    finally:
        srv.close()
    assert telemetry.spans() == []


def test_served_tokens_do_not_depend_on_telemetry(tiny_lm, monkeypatch):
    on, _ = serve_three(tiny_lm)
    assert telemetry.spans()
    telemetry.tracing.clear()
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    off, _ = serve_three(tiny_lm)
    assert telemetry.spans() == []
    assert on == off and [len(t) for t in on] == [5, 6, 7]


# -- the trainer ---------------------------------------------------------------


def test_trainstep_records_train_dispatch_and_keeps_the_profilers_names():
    import mxnet_tpu.gluon as gluon
    from mxnet_tpu.parallel.trainer import TrainStep
    net = gluon.nn.Dense(2, in_units=3)
    net.initialize()
    step = TrainStep(net, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.1})
    x, y = mx.nd.ones((4, 3)), mx.nd.zeros((4, 2))
    profiler._state["events"] = []
    profiler._state["flushed"] = []
    profiler.set_state("run")
    try:
        with telemetry.span("train.device_step") as outer:
            step(x, y)
        step(x, y)
    finally:
        profiler.set_state("stop")
    first, second = by_name()["train.dispatch"]
    assert first["attrs"] == {"step": 1, "first_call": True}
    assert second["attrs"] == {"step": 2, "first_call": False}
    assert first["parent"] == outer.id and second["parent"] is None
    table = profiler.dumps()
    assert "TrainStep::compile" in table and "TrainStep::run" in table
    assert "train.dispatch" not in table
    import inspect
    from mxnet_tpu.parallel import trainer
    assert "profiler.scope" not in inspect.getsource(trainer)


# -- program names -------------------------------------------------------------


#: what `TransformerLM.bind` registers for each configuration: operation ->
#: (program name, watchdog site, phase, AOT variant; `@` stands for the tp
#: mesh's device window). The benchmark's readers, the AOT cache and the
#: watchdog key on every one of them.
PROGRAMS = {
    "gather": (dict(), {
        "prefill": ("serving_prefill", "serving.prefill", "prefill",
                    "prefill_dense"),
        "decode": ("serving_decode", "serving.decode", "decode",
                   "decode_gather")}),
    "paged": (dict(paged=True), {
        "decode": ("serving_decode_paged", "serving.decode", "decode",
                   "decode_paged"),
        "prefill_chunk": ("serving_prefill_chunk", "serving.prefill",
                          "prefill", "prefill_chunk"),
        "spec_score": ("serving_spec_score", "serving.spec_score", "decode",
                       "spec_score")}),
    "paged_q8": (dict(paged=True, kv_quant=True), {
        "decode": ("serving_decode_paged_q8", "serving.decode", "decode",
                   "decode_paged_q8"),
        "prefill_chunk": ("serving_prefill_chunk_q8", "serving.prefill",
                          "prefill", "prefill_chunk_q8"),
        "spec_score": ("serving_spec_score_q8", "serving.spec_score",
                       "decode", "spec_score_q8")}),
    "tp": (dict(paged=True, tp=2), {
        "decode": ("serving_decode_tp", "serving.decode", "decode",
                   "decode_tp:@"),
        "prefill_chunk": ("serving_prefill_chunk_tp", "serving.prefill",
                          "prefill", "prefill_chunk_tp:@"),
        "spec_score": ("serving_spec_score_tp", "serving.spec_score",
                       "decode", "spec_score_tp:@")}),
    "tp_q8": (dict(paged=True, kv_quant=True, tp=2), {
        "decode": ("serving_decode_tp_q8", "serving.decode", "decode",
                   "decode_tp_q8:@"),
        "prefill_chunk": ("serving_prefill_chunk_tp_q8", "serving.prefill",
                          "prefill", "prefill_chunk_tp_q8:@"),
        "spec_score": ("serving_spec_score_tp_q8", "serving.spec_score",
                       "decode", "spec_score_tp_q8:@")}),
}


@pytest.mark.parametrize("config", sorted(PROGRAMS))
def test_the_step_programs_carry_their_own_names(tiny_lm, config):
    """A device trace names a program after the function handed to
    `jax.jit`: every serving step and the train step can be told apart.
    A configuration binds its own programs and no other's."""
    from mxnet_tpu.serving import tp as tp_mod
    params, cfg = tiny_lm
    opts, want = PROGRAMS[config]
    opts = dict(opts)
    mesh = tp_mod.build_tp_mesh(opts.pop("tp"), None) if "tp" in opts else None
    model = serving.TransformerLM(params, cfg)
    model.bind(8, mesh=mesh, **opts)
    where = tp_mod.tp_cache_variant(mesh) if mesh is not None else ""
    assert {op: (jit.__wrapped__.__name__, jit.site, jit._phase, jit._variant)
            for op, jit in model.programs.items()} \
        == {op: (name, site, phase, variant.replace("@", where))
            for op, (name, site, phase, variant) in want.items()}
    pools = ("k_pool", "v_pool", "k_scale", "v_scale")[
        :4 if opts.get("kv_quant") else 2]
    for jit in model.programs.values():         # the pools first, donated
        assert jit._argnames[:1 + len(pools)] == ("params",) + pools
    if config != "gather":
        return
    import jax.numpy as jnp
    text = model.programs["decode"].lower(
        params, *(jnp.zeros((cfg.n_layers, 4, cfg.n_heads, 8,
                             cfg.d_model // cfg.n_heads)),) * 2,
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2, 3), jnp.int32)).as_text()
    assert "module @jit_serving_decode " in text
