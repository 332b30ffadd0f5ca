"""Training-fleet observability tests (ISSUE 14).

Load-bearing claims:

* the collective-comms ledger is pinned against THEORY: an explicit
  ZeRO-1 shard_map program has hand-computable reduce-scatter /
  all-gather sizes (== param bytes each), and the ledger must land
  within 10% of them — never against its own output;
* the real `TrainStep(sharded_update=True)` ledger covers the update's
  irreducible collectives, and a tensor-parallel serving decode shows
  its two psums per layer;
* straggler detection flags EXACTLY the slow host, after
  MXNET_STRAGGLER_PATIENCE windows, once per episode, through both the
  synthetic gather and the shared-directory exchange the emulated pod
  uses;
* the anomaly detector's EWMA mean/variance/z math matches
  hand-computed sequences, and a finite chaos grad-spike trips it while
  the NaN/Inf guard stays green;
* the train console serves /metrics + /statusz + /healthz read-only,
  and tools/train_top.py renders live, degraded, and unreachable pods;
* tools/postmortem.py calls out detector events, appends the per-host
  skew table, and keeps per-host Perfetto rows distinct (the
  multi-host row-collision fix);
* MXNET_TELEMETRY=0 keeps every new seam a no-op.
"""
import importlib.util
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import gluon, telemetry
from mxnet_tpu.telemetry import introspect
from mxnet_tpu.telemetry.anomaly import AnomalyDetector, EwmaDetector
from mxnet_tpu.parallel import ResilientLoop, StragglerMonitor, TrainStep
from mxnet_tpu.parallel.resilient import _FileTimeExchange
from mxnet_tpu.utils import chaos
from mxnet_tpu.utils.recovery import CheckpointManager


def _tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(__file__), "..", "tools",
                           name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_slate():
    introspect.reset()
    telemetry.default_registry().reset()
    telemetry.flight().clear()
    chaos.reset()
    yield
    chaos.reset()
    telemetry.default_registry().reset()


def _mlp(hidden=16, n_in=8, n_out=4):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(hidden, in_units=n_in, activation="relu"))
    net.add(gluon.nn.Dense(n_out, in_units=hidden))
    net.initialize(mx.init.Xavier())
    return net


def _loop(tmp_path, net=None, **kw):
    net = net or _mlp()
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.01}, guard=True)
    kw.setdefault("policy", "skip")
    kw.setdefault("watch_preemption", False)
    kw.setdefault("verbose", False)
    return ResilientLoop(step, CheckpointManager(str(tmp_path)),
                         save_every=0, **kw)


def _batch(n=8, n_in=8, n_out=4, seed=0):
    r = np.random.RandomState(seed)
    return (r.randn(n, n_in).astype(np.float32),
            r.randint(0, n_out, (n,)).astype(np.float32))


# ---------------------------------------------------------------------------
# comms ledger: the HLO walk itself, pinned on synthetic text
# ---------------------------------------------------------------------------


def test_comms_from_hlo_synthetic_pin():
    """Hand-computed bytes/ops for every parse shape the walker must
    handle: plain, named-lhs, tuple results, async -start (counted)
    and -done (NOT double-counted), and the max(in, out) convention."""
    hlo = "\n".join([
        # all-gather: out 4*64*4 = 1024B > in 256B -> 1024
        "  %all-gather = f32[4,64]{1,0} all-gather(f32[1,64]{1,0} %p),"
        " replica_groups={}",
        # reduce-scatter: in 1024B > out 256B -> 1024
        "  %reduce-scatter.3 = f32[1,64]{0,1} reduce-scatter("
        "f32[4,64]{1,0} %q), dimensions={0}",
        # all-reduce, bf16: 2 * 8 * 2 = 32B in == out -> 32
        "  %ar = bf16[2,8]{1,0} all-reduce(bf16[2,8]{1,0} %r)",
        # async pair: -start counts once at max(operand, result minus
        # the aliased operand) — for all-reduce both sides are the full
        # payload (64B); -done must NOT count again
        "  %ars = (f32[16]{0}, f32[16]{0}) all-reduce-start("
        "f32[16]{0} %s)",
        # async all-gather: operand is the 1/4 SHARD (256B), result
        # tuple is (aliased shard, full 1024B output) -> payload must
        # be the full output, not the shard
        "  %ags = (f32[1,64]{1,0}, f32[4,64]{1,0}) all-gather-start("
        "f32[1,64]{1,0} %u), dimensions={0}",
        "  %agd = f32[4,64]{1,0} all-gather-done((f32[1,64]{1,0}, "
        "f32[4,64]{1,0}) %ags)",
        "  %ard = f32[16]{0} all-reduce-done((f32[16]{0}, f32[16]{0})"
        " %ars)",
        # collective-permute, scalar-free shape: 2*2*4 = 16B
        "  %cp = f32[2,2]{1,0} collective-permute(f32[2,2]{1,0} %t)",
        # not collectives: must not match
        "  %add = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)",
    ])
    kinds = introspect.comms_from_hlo(hlo)
    # sync 1024B + async full-output 1024B (NOT the 256B shard)
    assert kinds["all_gather"] == {"bytes": 1024 + 1024, "ops": 2}
    assert kinds["reduce_scatter"] == {"bytes": 1024, "ops": 1}
    # plain 32B + async max(in 64, tuple 128 - aliased 64) = 64 -> 96
    assert kinds["all_reduce"] == {"bytes": 32 + 64, "ops": 2}
    assert kinds["collective_permute"] == {"bytes": 16, "ops": 1}
    assert set(kinds) <= set(introspect.COLLECTIVE_KINDS)


# ---------------------------------------------------------------------------
# comms ledger vs THEORY: the analytic ZeRO-1 pin
# ---------------------------------------------------------------------------


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs >= 4 (emulated) devices")
def test_comms_ledger_analytic_zero1_pin():
    """The ISSUE 14 acceptance pin: an EXPLICIT ZeRO-1 program —
    psum_scatter(grads) -> local shard update -> all_gather(params) —
    has hand-computable collective sizes (reduce-scatter input and
    all-gather output are each exactly param bytes), and the ledger
    must report them within 10%. The ledger is tested against theory,
    not against itself."""
    from jax.sharding import Mesh, PartitionSpec as P

    n_dp = 4
    mesh = Mesh(np.array(jax.devices()[:n_dp]), ("dp",))
    rows, cols = 1024, 64
    param_bytes = rows * cols * 4

    def zero1(g, w):
        gs = jax.lax.psum_scatter(g, "dp", scatter_dimension=0,
                                  tiled=True)
        i = jax.lax.axis_index("dp")
        ws = jax.lax.dynamic_slice_in_dim(w, i * gs.shape[0],
                                          gs.shape[0], 0)
        return jax.lax.all_gather(ws - 0.1 * gs, "dp", tiled=True)

    fn = introspect.instrument(
        jax.jit(jax.shard_map(zero1, mesh=mesh, in_specs=(P(), P()),
                          out_specs=P(), check_vma=False)),
        site="test.zero1")
    g = np.random.randn(rows, cols).astype(np.float32)
    w = np.random.randn(rows, cols).astype(np.float32)
    fn(g, w)

    ledger = telemetry.site_comms("test.zero1")
    assert ledger is not None
    rs = ledger["kinds"]["reduce_scatter"]
    ag = ledger["kinds"]["all_gather"]
    assert rs["ops"] == 1 and ag["ops"] == 1
    assert abs(rs["bytes"] - param_bytes) <= 0.10 * param_bytes
    assert abs(ag["bytes"] - param_bytes) <= 0.10 * param_bytes
    assert ledger["total_bytes"] == rs["bytes"] + ag["bytes"]
    # fraction: a real fraction of the executable's total traffic
    assert ledger["fraction"] is None or 0.0 < ledger["fraction"] <= 1.0
    # ... and the gauges made it onto the registry under the template
    snap = telemetry.snapshot()["metrics"]
    assert snap[introspect.COMMS_BYTES % ("test_zero1",
                                          "reduce_scatter")]["value"] \
        == rs["bytes"]
    assert snap[introspect.COMMS_OPS % ("test_zero1",
                                        "all_gather")]["value"] == 1


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs >= 4 (emulated) devices")
def test_comms_ledger_on_sharded_train_step():
    """The real `TrainStep(sharded_update=True)` on a dp=4 mesh: the
    compiled update cannot move fewer collective bytes than the
    irreducible minimum — the grads must be globally reduced (>= param
    bytes of reduce payload) and the updated params must come back
    (>= param bytes of gather payload) — however XLA chose to lower the
    reduce-scatter (CPU may emit all-reduce + slice; the ledger reports
    the compiled truth)."""
    from mxnet_tpu.parallel.mesh import build_mesh

    net = _mlp(hidden=64, n_in=64, n_out=12)
    mesh = build_mesh({"dp": 4}, jax.devices()[:4])
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.01}, mesh=mesh,
                     sharded_update=True)
    x = np.random.randn(256, 64).astype(np.float32)
    y = np.random.randint(0, 12, (256,)).astype(np.float32)
    step(x, y)

    dp_divisible_bytes = sum(
        int(np.prod(p.shape)) * 4
        for p in net.collect_params().values() if p.shape[0] % 4 == 0)
    ledger = telemetry.site_comms("train.step")
    assert ledger is not None and ledger["kinds"], ledger
    reduce_like = sum(ledger["kinds"].get(k, {}).get("bytes", 0)
                      for k in ("reduce_scatter", "all_reduce"))
    gather = ledger["kinds"].get("all_gather", {}).get("bytes", 0)
    assert reduce_like >= 0.9 * dp_divisible_bytes, ledger
    assert gather >= 0.9 * dp_divisible_bytes, ledger
    if ledger["bytes_accessed"]:
        assert ledger["total_bytes"] <= ledger["bytes_accessed"]
        assert 0.0 < ledger["fraction"] <= 1.0
    # the fraction gauge rides the registry under the %s template
    snap = telemetry.snapshot()["metrics"]
    assert introspect.COMMS_FRACTION % "train_step" in snap


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 (emulated) devices")
def test_comms_ledger_tp_two_psums_per_layer():
    """The serving tp site's free check: a Megatron-style block is one
    psum after attention's row-parallel wo and one after the FFN's
    row-parallel w2 — TWO all-reduces per layer, no more, and each
    moves exactly the activation bytes."""
    from jax.sharding import Mesh, PartitionSpec as P

    n_layers, batch, d = 3, 4, 32
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))

    def block(x, w):
        for _ in range(n_layers):
            x = jax.lax.psum(x @ w, "tp")          # attention wo psum
            x = jax.lax.psum(jax.nn.relu(x) @ w, "tp")   # FFN w2 psum
        return x

    fn = introspect.instrument(
        jax.jit(jax.shard_map(block, mesh=mesh, in_specs=(P(), P()),
                          out_specs=P(), check_vma=False)),
        site="test.tp_block")
    fn(np.random.randn(batch, d).astype(np.float32),
       np.random.randn(d, d).astype(np.float32))
    ledger = telemetry.site_comms("test.tp_block")
    ar = ledger["kinds"]["all_reduce"]
    assert ar["ops"] == 2 * n_layers
    assert ar["bytes"] == 2 * n_layers * batch * d * 4


def test_comms_gauges_zeroed_when_a_recompile_drops_a_kind():
    """The per-kind gauges claim "latest executable": a recompile whose
    lowering dropped a collective kind must ZERO that kind's existing
    gauges, never leave them advertising stale collectives."""
    wd = introspect.watchdog()
    site = wd.site("test.kindswap")
    wd.record(site, None, "first", 0.01, comms={
        "kinds": {"reduce_scatter": {"bytes": 1024, "ops": 1},
                  "all_gather": {"bytes": 1024, "ops": 1}},
        "total_bytes": 2048, "bytes_accessed": 4096.0,
        "fraction": 0.5})
    wd.record(site, None, "relowered", 0.01, comms={
        "kinds": {"all_reduce": {"bytes": 512, "ops": 1}},
        "total_bytes": 512, "bytes_accessed": 4096.0,
        "fraction": 0.125})
    snap = telemetry.snapshot()["metrics"]
    sane = site.sane
    assert snap[introspect.COMMS_BYTES % (sane, "all_reduce")][
        "value"] == 512
    assert snap[introspect.COMMS_BYTES % (sane, "reduce_scatter")][
        "value"] == 0
    assert snap[introspect.COMMS_OPS % (sane, "all_gather")][
        "value"] == 0
    # ... and a kind that NEVER appeared has no gauge at all
    assert introspect.COMMS_BYTES % (sane, "all_to_all") not in snap
    assert site.comms["kinds"] == {"all_reduce": {"bytes": 512,
                                                  "ops": 1}}


def test_comms_ledger_telemetry_off_noop(tmp_path, monkeypatch):
    """MXNET_TELEMETRY=0: the HLO walk never runs — no site ledger, no
    comms gauges — while the jit still compiles and dispatches."""
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    fn = introspect.instrument(jax.jit(lambda x: x * 2),
                               site="test.off")
    out = fn(np.arange(4, dtype=np.float32))
    assert np.allclose(np.asarray(out), [0, 2, 4, 6])
    assert telemetry.site_comms("test.off") is None
    monkeypatch.delenv("MXNET_TELEMETRY")
    assert telemetry.snapshot()["metrics"] == {}


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------


def test_straggler_monitor_flags_exactly_the_slow_host():
    """Synthetic pod of 3 hosts, host '2' 5x the median: flagged after
    exactly `patience` windows, once per episode, with gauges + flight
    event naming it — and unflagged cleanly after recovery."""
    telemetry.flight().clear()
    pod = {"0": 0.010, "1": 0.012, "2": 0.050}
    mon = StragglerMonitor(window=2, factor=2.0, patience=2,
                           gather=lambda mean: dict(pod))
    flags = []
    for step in range(1, 9):                     # 4 windows
        flags += mon.observe(step, 0.01)
    assert flags == ["2"]                        # once, not per window
    assert mon.flagged == {"2": 1}
    assert mon.windows == 4
    assert mon.last_skew == pytest.approx(0.050 / 0.012)
    snap = telemetry.snapshot()["metrics"]
    assert snap["train_step_skew"]["value"] == pytest.approx(
        0.050 / 0.012)
    assert snap["train_step_window_median_s"]["value"] == \
        pytest.approx(0.012)
    assert snap["train_step_window_max_s"]["value"] == \
        pytest.approx(0.050)
    assert snap["train_stragglers_total"]["value"] == 1
    evs = [e for e in telemetry.flight().events()
           if e["name"] == "train.straggler"]
    assert len(evs) == 1 and evs[0]["host"] == "2"
    assert evs[0]["ratio"] == pytest.approx(0.050 / 0.012, rel=1e-3)
    # recovery: the episode closes, a relapse flags AGAIN
    pod["2"] = 0.011
    for step in range(9, 13):
        mon.observe(step, 0.01)
    assert mon._consec["2"] == 0
    pod["2"] = 0.060
    flags = []
    for step in range(13, 19):
        flags += mon.observe(step, 0.01)
    assert flags == ["2"] and mon.flagged == {"2": 2}


def test_straggler_absence_breaks_the_consecutive_chain():
    """A host missing from a window's gather (expired publish, dead
    peer) resets its consecutive count AND closes its episode: two
    non-adjacent slow windows must not satisfy patience=2, and a host
    that vanished mid-episode must record a FRESH onset on relapse."""
    views = [
        {"0": 0.01, "1": 0.05},      # w1: host 1 slow (consec 1)
        {"0": 0.01},                 # w2: host 1 ABSENT -> chain broken
        {"0": 0.01, "1": 0.05},      # w3: slow again (consec 1, NOT 2)
        {"0": 0.01, "1": 0.05},      # w4: consec 2 -> flag
        {"0": 0.01},                 # w5: absent mid-episode -> closed
        {"0": 0.01, "1": 0.05},      # w6: consec 1
        {"0": 0.01, "1": 0.05},      # w7: consec 2 -> SECOND onset
    ]
    mon = StragglerMonitor(window=1, factor=1.5, patience=2,
                           gather=lambda mean: dict(views.pop(0)))
    flags = []
    for step in range(1, 8):
        flags += mon.observe(step, 0.01)
    assert flags == ["1", "1"]
    assert mon.flagged == {"1": 2}


def test_straggler_below_patience_never_flags():
    calls = []

    def gather(mean):
        calls.append(mean)
        # slow only every other window: never `patience` consecutive
        slow = 0.05 if len(calls) % 2 else 0.01
        return {"0": 0.01, "1": slow}

    mon = StragglerMonitor(window=3, factor=2.0, patience=2,
                           gather=gather)
    for step in range(1, 19):                    # 6 windows
        assert mon.observe(step, 0.01) == []
    assert len(calls) == 6                       # one gather PER WINDOW
    assert mon.flagged == {}


def test_straggler_file_exchange_names_the_right_host(tmp_path,
                                                      monkeypatch):
    """The emulated pod's medium: two exchanges over one shared
    directory; the slow host's published mean makes BOTH sides' gather
    agree on who is slow."""
    ex0 = _FileTimeExchange(str(tmp_path), "0")
    ex1 = _FileTimeExchange(str(tmp_path), "1")
    assert ex0(0.010) == {"0": 0.010}            # peer not published yet
    view1 = ex1(0.055)
    assert view1 == {"0": 0.010, "1": 0.055}
    assert ex0(0.012) == {"0": 0.012, "1": 0.055}
    # a monitor driven from host 0's exchange flags host 1
    # factor 1.5: at TWO hosts the median averages the slow host in,
    # so a 2.0 factor could never fire (slow > slow + fast is absurd)
    mon = StragglerMonitor(window=1, factor=1.5, patience=2,
                           gather=ex0)
    mon.observe(1, 0.012)
    flags = mon.observe(2, 0.012)
    assert flags == ["1"]
    # a torn peer file is skipped, not fatal
    with open(os.path.join(str(tmp_path), "steptime-host9.json"),
              "w") as f:
        f.write("{torn")
    assert "9" not in ex0(0.012)
    # a STALE peer publish (dead host / previous run's leftovers in a
    # reused directory) expires instead of skewing every future median
    with open(os.path.join(str(tmp_path), "steptime-host8.json"),
              "w") as f:
        json.dump({"host": "8", "mean_s": 9.9,
                   "t": time.time() - 10_000}, f)
    view = ex0(0.012)
    assert "8" not in view and "1" in view


def test_straggler_loop_wiring_and_telemetry_off(tmp_path, monkeypatch):
    """ResilientLoop drives the monitor per step; MXNET_TELEMETRY=0
    keeps the seam a no-op (the gather never runs)."""
    calls = []
    loop = _loop(tmp_path / "a", straggler_window=2)
    assert loop._straggler is not None
    loop._straggler._gather = lambda mean: calls.append(mean) or \
        {"0": mean}
    for i in range(4):
        loop.step(*_batch(seed=i))
    assert len(calls) == 2
    # off by default (MXNET_STRAGGLER_WINDOW unset)
    monkeypatch.delenv("MXNET_STRAGGLER_WINDOW", raising=False)
    assert _loop(tmp_path / "b")._straggler is None
    # telemetry off: observe() is never reached
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    calls2 = []
    loop2 = _loop(tmp_path / "c", straggler_window=1)
    loop2._straggler._gather = lambda mean: calls2.append(mean) or \
        {"0": mean}
    loop2.step(*_batch())
    assert calls2 == []


# ---------------------------------------------------------------------------
# anomaly detection: the EWMA math, pinned by hand
# ---------------------------------------------------------------------------


def test_ewma_hand_computed_sequence():
    """alpha=0.5 over [2, 4, 4, 10] — every mean/var/z computed by
    hand:
      x=2:  seeds mean=2, var=0 (no z: nothing to score against)
      x=4:  z=(4-2)/sqrt(0+1e-12)        -> huge; m=3,    v=1
      x=4:  z=(4-3)/sqrt(1)      = 1.0   ;        m=3.5,  v=0.75
      x=10: z=(10-3.5)/sqrt(.75) = 7.5056;        m=6.75, v=10.9375
    """
    d = EwmaDetector(alpha=0.5, zscore=6.0, warmup=0)
    z0, f0 = d.observe(2.0)
    assert z0 is None and not f0
    assert d.mean == 2.0 and d.var == 0.0

    z1, f1 = d.observe(4.0)
    assert z1 == pytest.approx(2.0 / 1e-6, rel=1e-3)
    assert f1                                  # warmed up, |z| > 6
    assert d.mean == pytest.approx(3.0)
    assert d.var == pytest.approx(1.0)

    z2, f2 = d.observe(4.0)
    assert z2 == pytest.approx(1.0, rel=1e-6)
    assert not f2
    assert d.mean == pytest.approx(3.5)
    assert d.var == pytest.approx(0.75)

    z3, f3 = d.observe(10.0)
    assert z3 == pytest.approx(6.5 / np.sqrt(0.75), rel=1e-9)
    assert f3
    assert d.mean == pytest.approx(6.75)
    assert d.var == pytest.approx(10.9375)


def test_ewma_warmup_and_nonfinite():
    d = EwmaDetector(alpha=0.5, zscore=3.0, warmup=10)
    d.observe(1.0)
    z, flagged = d.observe(100.0)      # |z| enormous but n <= warmup
    assert abs(z) > 3.0 and not flagged
    n = d.n
    z, flagged = d.observe(float("nan"))   # the guard's territory
    assert z is None and not flagged and d.n == n


def test_anomaly_detector_records_metrics_and_flight():
    telemetry.flight().clear()
    det = AnomalyDetector(alpha=0.5, zscore=3.0, warmup=2)
    for step, v in enumerate([1.0, 1.1, 0.9, 1.0], start=1):
        assert det.observe(step, loss=v, grad_norm=v / 2) == []
    flagged = det.observe(5, loss=50.0, grad_norm=0.5)
    assert flagged == ["loss"]
    assert det.anomalies == 1
    snap = telemetry.snapshot()["metrics"]
    assert snap["train_anomalies_total"]["value"] == 1
    assert "train_loss_zscore" in snap and "train_grad_norm_zscore" \
        in snap
    evs = [e for e in telemetry.flight().events()
           if e["name"] == "train.anomaly"]
    assert len(evs) == 1
    assert evs[0]["signal"] == "loss" and evs[0]["step"] == 5
    assert abs(evs[0]["z"]) > 3.0


def test_anomaly_spike_trips_detector_not_guard(tmp_path):
    """The chaos `spike_step` fault: a LARGE FINITE grad poison — the
    bad-step guard must stay green (finite!) while the grad-norm
    z-score flags. The exact fault pair the multi-host drill injects."""
    telemetry.flight().clear()
    loop = _loop(tmp_path, anomaly=True)
    loop._anomaly.warmup = 3
    chaos.configure(spike_step=6)
    for i in range(8):
        loop.step(*_batch(seed=i))
    assert loop.bad_steps == 0                   # guard never tripped
    assert loop._anomaly.anomalies >= 1
    evs = [e for e in telemetry.flight().events()
           if e["name"] == "train.anomaly"]
    assert any(e["signal"] == "grad_norm" and e["step"] == 6
               for e in evs), evs
    assert "spike_step" in chaos.fired()


def test_anomaly_telemetry_off_noop(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    loop = _loop(tmp_path, anomaly=True)
    for i in range(4):
        loop.step(*_batch(seed=i))
    assert loop._anomaly.anomalies == 0
    assert loop._anomaly.last == {}              # observe never ran


# ---------------------------------------------------------------------------
# chaos slow_host
# ---------------------------------------------------------------------------


def test_chaos_slow_host_matches_host_and_repeats(monkeypatch):
    telemetry.flight().clear()
    monkeypatch.setenv("MXNET_HOST_ID", "3")
    chaos.configure(slow_host=("3", 0.01, 2))
    assert not chaos.maybe_slow_host(1)          # before from_step
    t0 = time.perf_counter()
    assert chaos.maybe_slow_host(2)
    assert chaos.maybe_slow_host(3)              # UNLATCHED: every step
    assert time.perf_counter() - t0 >= 0.02
    evs = [e for e in telemetry.flight().events()
           if e["name"] == "chaos.slow_host"]
    assert len(evs) == 1 and evs[0]["host"] == "3"
    monkeypatch.setenv("MXNET_HOST_ID", "1")     # some other host
    chaos.reset()
    chaos.configure(slow_host="3:0.01")
    assert not chaos.maybe_slow_host(5)


# ---------------------------------------------------------------------------
# train console + train_top
# ---------------------------------------------------------------------------


def _get(url, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, r.read()


def test_train_console_endpoints_and_read_only(tmp_path):
    loop = _loop(tmp_path, straggler_window=2, anomaly=True,
                 metrics_port=0)
    try:
        loop._straggler._gather = lambda mean: {
            "0": mean, "1": mean, "2": 5 * mean + 0.05}
        for i in range(5):
            loop.step(*_batch(seed=i))
        loop.save(block=True)
        host, port = loop.console_addr
        base = "http://%s:%d" % (host, port)
        code, body = _get(base + "/healthz")
        h = json.loads(body)
        assert code == 200 and h["ok"] and h["step"] == 5
        code, body = _get(base + "/statusz")
        z = json.loads(body)
        assert z["step"] == 5
        assert z["step_seconds"]["count"] == 5
        assert z["step_p95_ms"] > 0
        assert z["straggler"]["skew"] > 1
        assert z["anomalies"]["count"] == 0
        assert z["checkpoint"]["last_step"] == 5
        assert z["checkpoint"]["age_s"] >= 0
        assert z["comms"] is not None            # train.step compiled
        # /metrics content negotiation, same as the serving doors
        code, body = _get(base + "/metrics")
        assert "train_step_seconds" in json.loads(body)["metrics"]
        code, body = _get(base + "/metrics",
                          headers={"Accept": "text/plain"})
        assert b"train_step_skew" in body
        # read-only: POST /v1/generate is a 400, never a crash
        req = urllib.request.Request(
            base + "/v1/generate", data=b'{"tokens": [1]}',
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 400
    finally:
        loop.close_console()


def test_train_console_false_suppresses_env_port(tmp_path, monkeypatch):
    """metrics_port=False is the opt-out for secondary loops: a fixed
    MXNET_TRAIN_METRICS_PORT must not be re-bound (EADDRINUSE) by a
    second loop in the same process (the bench's ZeRO-1 A/B leg)."""
    monkeypatch.setenv("MXNET_TRAIN_METRICS_PORT", "0")
    first = _loop(tmp_path / "a", metrics_port=None)
    try:
        assert first.console_addr is not None       # env honored
        second = _loop(tmp_path / "b", metrics_port=False)
        assert second.console_addr is None
        assert second._console is None
        # and with a FIXED port, the opt-out is what prevents the bind
        monkeypatch.setenv("MXNET_TRAIN_METRICS_PORT",
                           str(first.console_addr[1]))
        third = _loop(tmp_path / "c", metrics_port=False)
        assert third.console_addr is None
    finally:
        first.close_console()


def test_train_top_renders_pod_degraded_and_unreachable(tmp_path):
    tt = _tool("train_top")
    loop = _loop(tmp_path, straggler_window=1, anomaly=True,
                 metrics_port=0)
    try:
        loop._straggler._gather = lambda mean: {
            "0": mean, "1": mean, "2": 5 * mean + 0.05}
        for i in range(3):
            loop.step(*_batch(seed=i))
        url = "http://%s:%d" % loop.console_addr
        frame = tt.render_once([url, "http://127.0.0.1:1"])
        assert "train console" in frame and "2 host(s)" in frame
        assert " live " in frame
        assert "UNREACHABLE" in frame            # degraded pod renders
        assert "stragglers:" in frame and "FLAGGED" in frame
        assert "comms (train.step):" in frame
        assert "anomaly z-scores" in frame
    finally:
        loop.close_console()
    # fully-dead pod: still a frame, never a crash
    frame = tt.render_once(["http://127.0.0.1:1"])
    assert "UNREACHABLE" in frame
    # --hosts parsing builds one URL per entry (full URLs untouched)
    args = type("A", (), {"hosts": "a:1, b:2,http://c:3", "url": "x"})()
    assert tt._urls(args) == ["http://a:1", "http://b:2", "http://c:3"]


# ---------------------------------------------------------------------------
# postmortem: ALERT callouts, skew table, per-host Perfetto rows
# ---------------------------------------------------------------------------


def _dump(path, host, pid, events, step_mean=None, step_count=10,
          extra_metrics=None):
    metrics = dict(extra_metrics or {})
    if step_mean is not None:
        metrics["train_step_seconds"] = {
            "kind": "histogram", "count": step_count,
            "sum": step_mean * step_count, "mean": step_mean,
            "p50": step_mean, "p95": step_mean, "p99": step_mean,
            "buckets": {}}
    doc = {"reason": "sigterm", "host": host, "pid": pid,
           "dumped_at": 10.0, "ring_capacity": 512, "events": events,
           "metrics": {"labels": {"host": host}, "metrics": metrics}}
    with open(path, "w") as f:
        json.dump(doc, f)
    doc["_path"] = str(path)
    return doc


def test_postmortem_alert_callouts_and_skew_table(tmp_path):
    pm = _tool("postmortem")
    _dump(tmp_path / "flight-host0-pid7-1.sigterm.json", "0", 7,
          [{"t": 1.0, "kind": "span", "name": "train.device_step",
            "trace": None, "dur_us": 900.0},
           {"t": 2.0, "kind": "event", "name": "train.straggler",
            "host": "1", "ratio": 4.2, "window": 3}],
          step_mean=0.010)
    _dump(tmp_path / "flight-host1-pid7-1.sigterm.json", "1", 7,
          [{"t": 1.5, "kind": "span", "name": "train.device_step",
            "trace": None, "dur_us": 42000.0},
           {"t": 2.5, "kind": "event", "name": "train.anomaly",
            "signal": "grad_norm", "value": 1e6, "z": 99.0, "step": 9}],
          step_mean=0.042)
    text = pm.render(pm.load_dumps([str(tmp_path)]))
    assert "ALERT " in text
    assert "train.straggler" in text and "train.anomaly" in text
    assert "detector alerts (2)" in text
    assert "per-host step-time skew" in text
    # host 1 is 0.042/median(0.026) = 1.62x and carries the flag mark
    lines = [l for l in text.splitlines() if "host1" in l and
             "STRAGGLER" in l]
    assert lines, text
    # ordinary dumps without detectors render WITHOUT the new sections
    plain = pm.render([_dump(tmp_path / "x.json", "9", 1,
                             [{"t": 1.0, "kind": "span",
                               "name": "train.device_step",
                               "trace": None, "dur_us": 1.0}])])
    assert "detector alerts" not in plain
    assert "per-host step-time skew" not in plain


def test_postmortem_perfetto_per_host_rows(tmp_path):
    """The row-collision regression: two hosts sharing an OS pid (both
    pid 7 — containers) must land on DISTINCT Perfetto process rows,
    named by host."""
    pm = _tool("postmortem")
    d0 = _dump(tmp_path / "a.json", "0", 7,
               [{"t": 1.0, "kind": "span", "name": "train.step",
                 "trace": "t1", "dur_us": 1000.0}])
    d1 = _dump(tmp_path / "b.json", "1", 7,
               [{"t": 1.0, "kind": "span", "name": "train.step",
                 "trace": "t1", "dur_us": 9000.0}])
    doc = pm.export_perfetto([d0, d1], str(tmp_path / "pod.json"))
    with open(tmp_path / "pod.json") as f:
        assert json.load(f) == doc
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 2
    assert spans[0]["pid"] != spans[1]["pid"]    # THE fix
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("name") == "process_name"}
    assert names == {"host 0 pid 7", "host 1 pid 7"}
    # same trace id on two hosts: distinct rows (pid differs)
    assert spans[0]["tid"] != spans[1]["tid"] or \
        spans[0]["pid"] != spans[1]["pid"]


def test_export_perfetto_folds_host_into_pid(monkeypatch):
    from mxnet_tpu.telemetry.tracing import host_pid
    monkeypatch.setenv("MXNET_HOST_ID", "5")
    telemetry.tracing.clear()
    with telemetry.span("obs.region", trace="tr"):
        pass
    doc = telemetry.export_perfetto()
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    want = host_pid("5", os.getpid())
    assert spans and all(e["pid"] == want for e in spans)
    assert all(e["args"]["host"] == "5" for e in spans)
    meta = {e["args"]["name"] for e in doc["traceEvents"]
            if e.get("name") == "process_name"}
    assert "host 5 pid %d" % os.getpid() in meta
    # non-numeric labels fold deterministically, distinct per host
    assert host_pid("tpu-a", 7) != host_pid("tpu-b", 7)
    assert host_pid("tpu-a", 7) == host_pid("tpu-a", 7)
