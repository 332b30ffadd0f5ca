"""Compile watchdog, executable memory accounting, and the bench
regression sentinel (ISSUE 9).

Load-bearing claims: (1) every compilation at a watchdog site is an
attributed event naming the ARGUMENT (and axis) whose signature
changed — including the acceptance case: a decode-bucket shape change
in the serving engine; (2) a tp-sharded engine restart over unchanged
shapes is attributed to the sharding diff, not misread as new traffic
shapes; (3) `memory_analysis()` gauges land in the Prometheus
exposition (gracefully absent where jax doesn't expose them); (4)
`MXNET_TELEMETRY=0` makes every introspect recording site a no-op while
the FUNCTIONAL counters (the engine's recompile bounds) keep working;
(5) `MXNET_COMPILE_BUDGET` / `MXNET_HBM_BUDGET_GB` budget policies;
(6) `tools/bench_sentinel.py` reproduces the known r5 trajectory
verdicts from the committed fixtures and exits nonzero on a synthetic
20% tok/s regression.
"""
import json
import os
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, serving, telemetry
from mxnet_tpu.telemetry import introspect
from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SENTINEL = os.path.join(REPO, "tools", "bench_sentinel.py")


@pytest.fixture(autouse=True)
def _fresh_watchdog():
    """Each test gets its own watchdog + default registry (sites are
    process-global by design, so tests must not see each other's)."""
    introspect.reset()
    telemetry.default_registry().reset()
    telemetry.tracing.clear()
    telemetry.flight().clear()
    yield
    introspect.reset()
    telemetry.default_registry().reset()
    telemetry.tracing.clear()
    telemetry.flight().clear()


def tiny_lm():
    cfg = TransformerConfig(vocab=48, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=64)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


def arith_prompt(seed, lo, n):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(lo, 40, n)]


def _has_memory_analysis():
    compiled = jax.jit(lambda a: a + 1).lower(jnp.ones((2,))).compile()
    memory, _flops, _bytes = introspect._analyses(compiled)
    return memory is not None


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------


def test_attribution_names_argument_and_axis():
    f = introspect.instrument(jax.jit(lambda a, b: a @ b),
                              site="probe.mm", argnames=("lhs", "rhs"))
    f(jnp.ones((4, 8)), jnp.ones((8, 2)))
    f(jnp.ones((4, 16)), jnp.ones((16, 2)))
    evs = introspect.compile_events("probe.mm")
    assert len(evs) == 2
    assert evs[0]["reason"] == "first compilation at this site"
    assert "lhs: shape (4, 8) -> (4, 16) (axis 1)" in evs[1]["reason"]
    assert "rhs: shape (8, 2) -> (16, 2) (axis 0)" in evs[1]["reason"]
    # same-signature calls dispatch the cached executable: no new event
    f(jnp.ones((4, 16)), jnp.ones((16, 2)))
    assert len(introspect.compile_events("probe.mm")) == 2
    assert f.compiles == 2 and f._cache_size() == 2


def test_attribution_dtype_and_static():
    f = introspect.instrument(jax.jit(lambda a, flag: a * (2 if flag else 3),
                                      static_argnums=(1,)),
                              site="probe.static", argnames=("a", "flag"),
                              static_argnums=(1,))
    f(jnp.ones((4,), jnp.float32), True)
    f(jnp.ones((4,), jnp.bfloat16), True)
    f(jnp.ones((4,), jnp.bfloat16), False)
    evs = introspect.compile_events("probe.static")
    assert "a: dtype float32 -> bfloat16" in evs[1]["reason"]
    assert "flag: static True -> False" in evs[2]["reason"]


def test_decode_bucket_change_attributed(monkeypatch):
    """The acceptance case: the serving decode batch crossing a bucket
    (1 -> 2 live sequences) emits a compile event naming the changed
    argument and axis — not just 'something recompiled'."""
    monkeypatch.delenv("MXNET_PAGED_ATTENTION", raising=False)
    params, cfg = tiny_lm()
    srv = serving.serve((params, cfg), max_batch=4, block_size=8)
    try:
        results = {}

        def client(i, delay, plen):
            time.sleep(delay)
            results[i] = srv.generate(arith_prompt(i, 1, plen),
                                      max_new_tokens=8, timeout=120)

        threads = [threading.Thread(target=client, args=(i, 0.15 * i, p))
                   for i, p in enumerate((5, 9))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(len(results[i]) == 8 for i in range(2))
        evs = introspect.compile_events("serving.decode")
        assert evs, "no decode compile events recorded"
        assert evs[0]["reason"] == "first compilation at this site"
        bucket = [e for e in evs[1:]
                  if "tokens" in e["reason"] and "axis 0" in e["reason"]]
        assert bucket, ("decode bucket 1 -> 2 not attributed to the "
                        "batch axis: %r" % [e["reason"] for e in evs])
        assert "tokens: shape (1,) -> (2,) (axis 0)" in bucket[0]["reason"]
        assert all(e["phase"] == "decode" for e in evs)
        # the migrated counters read the same watchdog seam
        assert srv.engine.decode_compilations == len(evs)
    finally:
        srv.close()


def test_engine_restart_attributed_as_duplicate(monkeypatch):
    """A second engine over the SAME shapes recompiles (cold per-instance
    executable cache) but the watchdog attributes it as a duplicate of a
    process-seen signature — the gap the ROADMAP item-5 AOT cache will
    close — while the per-engine recompile-bound counters still work."""
    monkeypatch.delenv("MXNET_PAGED_ATTENTION", raising=False)
    params, cfg = tiny_lm()
    prompt = arith_prompt(0, 1, 5)
    for round_ in range(2):
        srv = serving.serve((params, cfg), max_batch=2, block_size=8)
        try:
            out = srv.generate(prompt, max_new_tokens=4, timeout=120)
            assert len(out) == 4
            assert srv.engine.decode_compilations >= 1
            assert srv.engine.prefill_compilations >= 1
        finally:
            srv.close()
    evs = introspect.compile_events("serving.decode")
    first = [e for e in evs if not e["duplicate"]]
    dups = [e for e in evs if e["duplicate"]]
    assert first and dups, evs
    assert all("cold" in e["reason"] for e in dups)
    site = introspect.watchdog().site("serving.decode")
    assert site.duplicates == len(dups)


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="tp attribution needs >= 4 emulated devices")
def test_tp_restart_attributed_to_sharding(monkeypatch):
    """A tp-sharded engine after a single-device run over the SAME
    traffic shapes: its decode compiles must be attributed to the
    params/pool sharding diff, not to a shape change."""
    monkeypatch.delenv("MXNET_PAGED_ATTENTION", raising=False)
    params, cfg = tiny_lm()
    prompt = arith_prompt(3, 1, 9)
    srv = serving.serve((params, cfg), max_batch=2, block_size=8,
                        paged=True)
    try:
        srv.generate(prompt, max_new_tokens=4, timeout=120)
    finally:
        srv.close()
    mark = introspect.watchdog().mark()
    srv = serving.serve((params, cfg), max_batch=2, block_size=8,
                        paged=True, tp=2)
    try:
        assert srv.engine.tp == 2, getattr(srv.engine, "tp_fallback", None)
        srv.generate(prompt, max_new_tokens=4, timeout=120)
    finally:
        srv.close()
    evs = [e for e in introspect.compile_events("serving.decode")
           if e["seq"] > mark and not e["duplicate"]]
    assert evs, "tp engine triggered no fresh decode compilations"
    for e in evs:
        assert "sharding" in e["reason"], e["reason"]
        assert "shape" not in e["reason"], e["reason"]


def test_numpy_and_uncommitted_device_args_share_signature():
    """jax's own cache reuses one executable for a numpy arg and an
    uncommitted device array of the same aval — the watchdog must not
    split them (the engine feeds jnp prefill args but numpy decode
    batches through the same step jits)."""
    f = introspect.instrument(jax.jit(lambda a: a * 2), site="probe.mix",
                              argnames=("a",))
    x = np.ones((4, 4), np.float32)
    f(jnp.asarray(x))
    f(x)
    assert len(introspect.compile_events("probe.mix")) == 1
    assert f.compiles == 1
    # an explicitly placed (committed) array IS a different placement
    committed = jax.device_put(jnp.asarray(x), jax.devices()[0])
    f(committed)
    assert len(introspect.compile_events("probe.mix")) == 2


def test_concurrent_first_calls_compile_once():
    """Two threads sharing one instrumented jit racing on a fresh
    signature must pay ONE XLA compile (plain jax.jit was internally
    thread-safe here; the owned cache must be too)."""
    f = introspect.instrument(jax.jit(lambda a: a @ a.T),
                              site="probe.race")
    x = jnp.ones((64, 64))
    errs = []

    def call():
        try:
            f(x)
        except Exception as e:                   # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=call) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert f.compiles == 1
    assert len(introspect.compile_events("probe.race")) == 1


def test_shared_adapter_counters_stay_per_engine():
    """Two engines over the SAME BlockLM adapter (no rebind — the jits
    persist on the adapter): counters attribute each compile to the
    engine whose call PAID it, so an idle sibling reads 0 even while the
    adapter compiles for the other engine's traffic — and a warm shared
    cache truthfully reads as zero new compilations."""
    from mxnet_tpu.serving.engine import BlockLM, Engine
    net = mx.models.RNNModel(mode="lstm", vocab_size=16, num_embed=8,
                             num_hidden=8, num_layers=1, dropout=0.0)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((4, 2)))
    adapter = BlockLM(net, vocab=16, max_len=8, time_major=True)
    e1 = Engine(adapter, max_batch=2)
    e2 = Engine(adapter, max_batch=2)
    # max_new=4 walks the decode length across the 4 -> 8 pad bucket, so
    # the shared step jit really compiles a decode signature (the first
    # decode step reuses the (1, 4) prefill signature warm)
    seq = e1.start([1, 2, 3], max_new=4)
    while not seq.done:
        e1.decode_step([seq])
    assert e1.prefill_compilations >= 1
    assert e1.decode_compilations >= 1
    # e2 served nothing: the shared adapter's compiles are e1's, not its
    assert e2.prefill_compilations == 0
    assert e2.decode_compilations == 0
    p1, d1 = e1.prefill_compilations, e1.decode_compilations
    # same shapes through e2: warm shared cache — zero new compiles,
    # and e1's tally is untouched by e2's traffic
    seq = e2.start([1, 2, 3], max_new=4)
    while not seq.done:
        e2.decode_step([seq])
    assert e2.prefill_compilations == 0
    assert e2.decode_compilations == 0
    assert (e1.prefill_compilations, e1.decode_compilations) == (p1, d1)


def test_shared_transformer_adapter_rebind_counts_stay_per_engine():
    """A second engine over a shared TransformerLM adapter RE-BINDS it
    (fresh jits, cold executable caches): the second engine's warm-up
    recompiles land on ITS counters, and the first engine's tally is
    unchanged by them."""
    from mxnet_tpu.serving.engine import TransformerLM, Engine
    params, cfg = tiny_lm()
    adapter = TransformerLM(params, cfg)
    e1 = Engine(adapter, max_batch=2, block_size=8)
    seq = e1.start(arith_prompt(0, 1, 5), max_new=2)
    while not seq.done:
        e1.decode_step([seq])
    assert e1.prefill_compilations >= 1
    p1, d1 = e1.prefill_compilations, e1.decode_compilations
    e2 = Engine(adapter, max_batch=2, block_size=8)   # re-binds: new jits
    assert e2.prefill_compilations == 0
    seq = e2.start(arith_prompt(0, 1, 5), max_new=2)
    while not seq.done:
        e2.decode_step([seq])
    assert e2.prefill_compilations >= 1   # its own cold-cache compiles
    assert (e1.prefill_compilations, e1.decode_compilations) == (p1, d1)


def test_compile_region_failure_not_recorded():
    """A region that raises produced no executable: no event, no
    budget consumption, no compile_s pollution — the exception is the
    signal."""
    with pytest.raises(RuntimeError, match="boom"):
        with introspect.compile_region("probe.fail"):
            raise RuntimeError("boom")
    assert introspect.compile_events("probe.fail") == []
    assert introspect.watchdog().site("probe.fail").compiles == 0


# ---------------------------------------------------------------------------
# metrics / spans / flight / kill switch
# ---------------------------------------------------------------------------


def test_memory_gauges_in_prometheus_exposition():
    if not _has_memory_analysis():
        pytest.skip("backend doesn't expose memory_analysis")
    f = introspect.instrument(jax.jit(lambda a: a @ a.T),
                              site="probe.mem", argnames=("a",))
    f(jnp.ones((8, 16)))
    text = telemetry.default_registry().prometheus_text()
    for name in ("exec_probe_mem_argument_bytes",
                 "exec_probe_mem_output_bytes",
                 "exec_probe_mem_temp_bytes",
                 "exec_probe_mem_code_bytes",
                 "exec_probe_mem_hbm_bytes"):
        assert name in text, text
    assert "compile_seconds_bucket" in text
    assert "compile_probe_mem_total" in text
    ev = introspect.compile_events("probe.mem")[-1]
    assert ev["hbm_bytes"] > 0
    mem = ev["memory"]
    assert ev["hbm_bytes"] == (mem["argument_bytes"] + mem["output_bytes"]
                               - mem["alias_bytes"] + mem["temp_bytes"]
                               + mem["code_bytes"])


def test_compile_recorded_as_span_and_flight_event():
    f = introspect.instrument(jax.jit(lambda a: a + 1), site="probe.rec")
    f(jnp.ones((4,)))
    spans = [s for s in telemetry.spans() if s["name"] == "compile"]
    assert spans and spans[0]["attrs"]["site"] == "probe.rec"
    assert spans[0]["cat"] == "compile"
    flight = [e for e in telemetry.flight().events()
              if e["name"] == "compile"]
    assert flight and flight[0]["site"] == "probe.rec"
    assert flight[0]["reason"] == "first compilation at this site"


def test_train_step_site_records():
    net = gluon.nn.Dense(2, in_units=3)
    net.initialize()
    from mxnet_tpu.parallel.trainer import TrainStep
    step = TrainStep(net, gluon.loss.L2Loss(), "sgd",
                     {"learning_rate": 0.1})
    float(step(mx.nd.ones((4, 3)), mx.nd.zeros((4, 2))))
    evs = introspect.compile_events("train.step")
    assert len(evs) == 1 and evs[0]["phase"] == "train"
    float(step(mx.nd.ones((4, 3)), mx.nd.zeros((4, 2))))
    assert len(introspect.compile_events("train.step")) == 1


def test_export_region_records(tmp_path):
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize()
    net(mx.nd.ones((1, 8)))
    mx.predict.export_model(net, [("data", (1, 8))],
                            str(tmp_path / "m.mxtpu"))
    evs = introspect.compile_events("predict.export")
    assert len(evs) == 1
    assert evs[0]["phase"] == "export"
    assert "explicit compile region" in evs[0]["reason"]


def test_telemetry_kill_switch_makes_recording_noop(monkeypatch):
    """MXNET_TELEMETRY=0: no metrics, spans, or flight events from any
    introspect site — but the FUNCTIONAL side (signature caching, the
    engine's recompile counters) keeps working: it is behavior, not
    telemetry."""
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    f = introspect.instrument(jax.jit(lambda a: a * 2), site="probe.off")
    f(jnp.ones((4,)))
    f(jnp.ones((8,)))
    reg = telemetry.default_registry()
    assert "compile_total" not in reg.prometheus_text()
    assert telemetry.spans() == []
    assert telemetry.flight().events() == []
    assert f.compiles == 2 and f._cache_size() == 2
    assert len(introspect.compile_events("probe.off")) == 2
    monkeypatch.delenv("MXNET_TELEMETRY")
    f(jnp.ones((16,)))
    assert "compile_total" in reg.prometheus_text()


# ---------------------------------------------------------------------------
# budgets
# ---------------------------------------------------------------------------


def test_compile_budget_warn_then_raise(monkeypatch):
    monkeypatch.setenv("MXNET_COMPILE_BUDGET", "1")
    f = introspect.instrument(jax.jit(lambda a: a + 1), site="probe.bud")
    f(jnp.ones((2,)))
    with pytest.warns(RuntimeWarning, match="compile budget overrun"):
        f(jnp.ones((3,)))
    reg = telemetry.default_registry()
    assert reg.counter("compile_budget_overruns_total").value >= 1
    monkeypatch.setenv("MXNET_COMPILE_BUDGET", "1:raise")
    with pytest.raises(introspect.CompileBudgetExceeded):
        f(jnp.ones((4,)))
    # same-signature dispatch of an already-cached executable stays free
    f(jnp.ones((3,)))


def test_hbm_budget_preflight(monkeypatch):
    if not _has_memory_analysis():
        pytest.skip("backend doesn't expose memory_analysis")
    monkeypatch.setenv("MXNET_HBM_BUDGET_GB", "1e-9")
    f = introspect.instrument(jax.jit(lambda a: a @ a.T),
                              site="probe.hbm")
    with pytest.raises(introspect.HbmBudgetExceeded):
        f(jnp.ones((64, 64)))
    # a same-sig retry is refused WITHOUT paying the compile again and
    # without reading as a duplicate (the engine-restart signal) ...
    with pytest.raises(introspect.HbmBudgetExceeded):
        f(jnp.ones((64, 64)))
    assert f.compiles == 1
    assert len(introspect.compile_events("probe.hbm")) == 1
    assert not introspect.compile_events("probe.hbm")[0]["duplicate"]
    # ... and lifting the budget re-admits the already-built executable
    monkeypatch.setenv("MXNET_HBM_BUDGET_GB", "64")
    out = f(jnp.ones((64, 64)))
    assert out.shape == (64, 64)
    assert f.compiles == 1
    monkeypatch.setenv("MXNET_HBM_BUDGET_GB", "1e-9:warn")
    g = introspect.instrument(jax.jit(lambda a: a @ a.T),
                              site="probe.hbm2")
    with pytest.warns(RuntimeWarning, match="MXNET_HBM_BUDGET_GB"):
        out = g(jnp.ones((64, 64)))
    assert out.shape == (64, 64)
    # a generous budget admits the executable silently
    monkeypatch.setenv("MXNET_HBM_BUDGET_GB", "64")
    h = introspect.instrument(jax.jit(lambda a: a * 2),
                              site="probe.hbm3")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        h(jnp.ones((4,)))


# ---------------------------------------------------------------------------
# bench integration
# ---------------------------------------------------------------------------


def test_bench_check_line_compile_fields():
    import bench
    base = {"metric": "m_img_per_sec", "unit": "img/s", "value": 1.0,
            "device": "cpu"}
    assert bench.check_line({**base, "compile_s": 0.5,
                             "exec_hbm_bytes": 1024})
    assert bench.check_line({**base, "compile_s": 0.0,
                             "exec_hbm_bytes": None})
    with pytest.raises(ValueError):
        bench.check_line({**base, "compile_s": -1.0})
    with pytest.raises(ValueError):
        bench.check_line({**base, "compile_s": float("nan")})
    with pytest.raises(ValueError):
        bench.check_line({**base, "compile_s": 1.0, "exec_hbm_bytes": 0})
    with pytest.raises(ValueError):
        # a footprint can only come from a compile event
        bench.check_line({**base, "compile_s": 0.0,
                          "exec_hbm_bytes": 4096})


def test_watchdog_mark_since_brackets_one_config():
    wd = introspect.watchdog()
    f = introspect.instrument(jax.jit(lambda a: a + 1), site="probe.seq")
    f(jnp.ones((2,)))
    mark = wd.mark()
    f(jnp.ones((4,)))
    f(jnp.ones((4,)))                      # cached: contributes nothing
    seconds, peak = wd.since(mark)
    assert seconds > 0
    evs = [e for e in introspect.compile_events() if e["seq"] > mark]
    assert len(evs) == 1
    if evs[0].get("hbm_bytes"):
        assert peak == evs[0]["hbm_bytes"]


# ---------------------------------------------------------------------------
# the regression sentinel (stdlib-only subprocess)
# ---------------------------------------------------------------------------


def _run_sentinel(*args):
    out = subprocess.run([sys.executable, SENTINEL] + list(args),
                         capture_output=True, text=True, timeout=120)
    verdicts = [json.loads(ln) for ln in out.stdout.splitlines()
                if ln.strip().startswith("{")]
    summary = [v for v in verdicts if "sentinel_summary" in v]
    assert summary, (out.stdout, out.stderr)
    return out.returncode, verdicts, summary[-1]["sentinel_summary"]


def test_sentinel_synthetic_regression_exits_nonzero(tmp_path):
    """A 20% tok/s drop against the committed lstm word-LM trajectory
    must come back `regressed` with exit 1."""
    with open(os.path.join(REPO, "BENCH_r04.json")) as f:
        blob = json.load(f)
    lines = [json.loads(ln) for ln in blob["tail"].splitlines()
             if ln.strip().startswith("{")]
    ref = [r for r in lines
           if r.get("metric") == "lstm_word_lm_train_tok_per_sec"][0]
    fresh = dict(ref, value=round(ref["value"] * 0.8, 2))
    path = tmp_path / "fresh.jsonl"
    path.write_text(json.dumps(fresh) + "\n")
    rc, verdicts, summary = _run_sentinel(str(path))
    assert rc == 1
    v = [x for x in verdicts if x.get("metric") == fresh["metric"]][0]
    assert v["verdict"] == "regressed"
    assert v["delta_pct"] == -20.0
    assert summary["regressed"] == [fresh["metric"]]
    # the same value restated verbatim is within noise, exit 0
    path.write_text(json.dumps(ref) + "\n")
    rc, verdicts, _ = _run_sentinel(str(path))
    assert rc == 0
    v = [x for x in verdicts if x.get("metric") == ref["metric"]][0]
    assert v["verdict"] in ("within-noise", "improved")


def test_sentinel_new_metric_and_config_error(tmp_path):
    fresh = [
        {"metric": "brand_new_tok_per_sec", "unit": "tok/s",
         "value": 10.0, "device": "cpu"},
        {"metric": "broken_config_error", "value": None, "unit": "",
         "error": "ValueError: boom"},
    ]
    path = tmp_path / "fresh.jsonl"
    path.write_text("\n".join(json.dumps(r) for r in fresh) + "\n")
    rc, verdicts, summary = _run_sentinel(str(path))
    assert rc == 1                       # the crashed config fails the run
    by_metric = {v["metric"]: v for v in verdicts if "metric" in v}
    assert by_metric["brand_new_tok_per_sec"]["verdict"] == "new"
    assert by_metric["broken_config_error"]["verdict"] == "config-error"


def test_sentinel_regime_band_not_widened_by_past_improvement(tmp_path):
    """After a committed 5x improvement the raw series spread is ~400% —
    the band must come from the current regime only, so a 70% collapse
    back toward the old level still reads `regressed` (exit 1)."""
    hist = tmp_path / "hist"
    hist.mkdir()
    for i, value in enumerate((100.0, 100.0, 500.0), start=1):
        line = {"metric": "regime_tok_per_sec", "unit": "tok/s",
                "value": value, "device": "cpu"}
        (hist / ("BENCH_r%02d.json" % i)).write_text(json.dumps(
            {"rc": 0, "tail": json.dumps(line)}))
    fresh = {"metric": "regime_tok_per_sec", "unit": "tok/s",
             "value": 150.0, "device": "cpu"}
    path = tmp_path / "fresh.jsonl"
    path.write_text(json.dumps(fresh) + "\n")
    rc, verdicts, summary = _run_sentinel(str(path), "--repo", str(hist))
    assert rc == 1
    v = [x for x in verdicts if x.get("metric") == fresh["metric"]][0]
    assert v["verdict"] == "regressed" and v["ref"] == 500.0
    assert v["band_pct"] == 10.0      # floor, not the 400% raw spread
    # holding the improved level stays within noise
    path.write_text(json.dumps(dict(fresh, value=495.0)) + "\n")
    rc, _, _ = _run_sentinel(str(path), "--repo", str(hist))
    assert rc == 0


def test_sentinel_band_anchors_at_ref_not_median(tmp_path):
    """The abandoned regime's wobble must not set the band: with history
    [80, 100, 120, 500, 510] the median (100) still sits in the old
    regime, whose 40% spread would swallow a one-third collapse of the
    new level. Anchored at the ref (510), the band is the new regime's
    2% wobble (floored to 10%) and 340 reads `regressed`."""
    hist = tmp_path / "hist"
    hist.mkdir()
    for i, value in enumerate((80.0, 100.0, 120.0, 500.0, 510.0),
                              start=1):
        line = {"metric": "anchor_tok_per_sec", "unit": "tok/s",
                "value": value, "device": "cpu"}
        (hist / ("BENCH_r%02d.json" % i)).write_text(json.dumps(
            {"rc": 0, "tail": json.dumps(line)}))
    fresh = {"metric": "anchor_tok_per_sec", "unit": "tok/s",
             "value": 340.0, "device": "cpu"}
    path = tmp_path / "fresh.jsonl"
    path.write_text(json.dumps(fresh) + "\n")
    rc, verdicts, _ = _run_sentinel(str(path), "--repo", str(hist))
    assert rc == 1
    v = [x for x in verdicts if x.get("metric") == fresh["metric"]][0]
    assert v["verdict"] == "regressed" and v["ref"] == 510.0
    assert v["band_pct"] == 10.0


def test_sentinel_zero_valued_history_is_unjudgeable(tmp_path):
    """A committed line with value exactly 0 can't anchor a relative
    delta — it must be skipped as history (verdict `new`), not crash
    the sentinel with a ZeroDivisionError mid-outage-triage."""
    hist = tmp_path / "hist"
    hist.mkdir()
    line = {"metric": "zeroed_tok_per_sec", "unit": "tok/s",
            "value": 0.0, "device": "cpu"}
    (hist / "BENCH_r01.json").write_text(json.dumps(
        {"rc": 0, "tail": json.dumps(line)}))
    path = tmp_path / "fresh.jsonl"
    path.write_text(json.dumps(dict(line, value=10.0)) + "\n")
    rc, verdicts, _ = _run_sentinel(str(path), "--repo", str(hist))
    assert rc == 0
    v = [x for x in verdicts if x.get("metric") == line["metric"]][0]
    assert v["verdict"] == "new" and v["n_history"] == 0


def test_sentinel_compile_fields_warn_only(tmp_path):
    """compile_s / exec_hbm_bytes blowups are reported as warnings but
    never decide the exit code — only the measured value does."""
    with open(os.path.join(REPO, "BENCH_r04.json")) as f:
        blob = json.load(f)
    lines = [json.loads(ln) for ln in blob["tail"].splitlines()
             if ln.strip().startswith("{")]
    ref = [r for r in lines
           if r.get("metric") == "lstm_word_lm_train_tok_per_sec"][0]
    hist = tmp_path / "hist"
    hist.mkdir()
    with_compile = dict(ref, compile_s=1.0, exec_hbm_bytes=1000)
    (hist / "BENCH_r01.json").write_text(json.dumps(
        {"rc": 0, "tail": json.dumps(with_compile)}))
    fresh = dict(with_compile, compile_s=10.0, exec_hbm_bytes=5000)
    path = tmp_path / "fresh.jsonl"
    path.write_text(json.dumps(fresh) + "\n")
    rc, verdicts, _ = _run_sentinel(str(path), "--repo", str(hist))
    assert rc == 0
    v = [x for x in verdicts if x.get("metric") == ref["metric"]][0]
    assert v["verdict"] == "within-noise"
    warns = " ".join(v.get("warnings", []))
    assert "compile_s" in warns and "exec_hbm_bytes" in warns
