"""Env-knob surface tests (parity model: docs/faq/env_var.md contract —
documented variables must actually change behavior)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_bigarray_bound_read_at_call_time(monkeypatch):
    from mxnet_tpu import kvstore as kvs
    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "1234")
    assert kvs._bigarray_bound() == 1234
    monkeypatch.delenv("MXNET_KVSTORE_BIGARRAY_BOUND")
    assert kvs._bigarray_bound() == 1000000


def test_backward_do_mirror_default(monkeypatch):
    from mxnet_tpu.parallel.trainer import TrainStep
    from mxnet_tpu import gluon
    net = gluon.nn.Dense(2, in_units=3)
    net.initialize()
    loss = gluon.loss.L2Loss()
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    assert TrainStep(net, loss)._remat == "full"
    monkeypatch.delenv("MXNET_BACKWARD_DO_MIRROR")
    assert TrainStep(net, loss)._remat == "none"
    # explicit argument wins over the env default
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    assert TrainStep(net, loss, remat=False)._remat == "none"
    # MXNET_REMAT_POLICY selects the policy-based mode
    monkeypatch.setenv("MXNET_REMAT_POLICY", "io")
    assert TrainStep(net, loss)._remat == "io"
    monkeypatch.delenv("MXNET_REMAT_POLICY")
    # the remat step still trains correctly
    step = TrainStep(net, loss, "sgd", {"learning_rate": 0.1})
    assert step._remat == "full"
    l0 = float(step(mx.nd.ones((4, 3)), mx.nd.zeros((4, 2))))
    for _ in range(10):
        l1 = float(step(mx.nd.ones((4, 3)), mx.nd.zeros((4, 2))))
    assert l1 < l0


def test_profiler_autostart_subprocess():
    code = (
        "import os; os.environ['JAX_PLATFORMS']='cpu'; "
        "os.environ['MXNET_PROFILER_AUTOSTART']='1'; "
        "os.environ['MXNET_PROFILER_MODE']='imperative'; "
        "import mxnet_tpu as mx; "
        "from mxnet_tpu import profiler; "
        "assert profiler.is_running(); "
        "assert profiler._state['config']['mode'] == 'imperative'; "
        "a = mx.nd.ones((4, 4)); (a + a).wait_to_read(); "
        "assert profiler._state['events']; print('AUTOSTART_OK')"
    )
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=180,
                         env={**os.environ, "PYTHONPATH": REPO})
    assert "AUTOSTART_OK" in out.stdout, (out.stdout, out.stderr)


def test_gluon_repo_local_dir(monkeypatch, tmp_path):
    from mxnet_tpu.gluon.model_zoo import model_store
    (tmp_path / "toy.params").write_bytes(b"x")
    monkeypatch.setenv("MXNET_GLUON_REPO", str(tmp_path))
    assert model_store.get_model_file("toy") == str(tmp_path / "toy.params")
    monkeypatch.delenv("MXNET_GLUON_REPO")
    with pytest.raises(IOError):
        model_store.get_model_file("toy")


def test_cpu_worker_nthreads(monkeypatch):
    from mxnet_tpu import native
    if not native.AVAILABLE:
        pytest.skip("native library unavailable")
    monkeypatch.setenv("MXNET_CPU_WORKER_NTHREADS", "2")
    eng = native.NativeEngine()
    # engine functions with the env-sized pool
    token = {"done": False}
    v = eng.new_var()
    eng.push(lambda: token.__setitem__("done", True), read_vars=(),
             write_vars=(v,))
    eng.wait_all()
    assert token["done"]


def test_sharded_update_env_default(monkeypatch, tmp_path):
    """MXNET_SHARDED_UPDATE=1 flips TrainStep's ZeRO-1 default (and
    implies sharded optimizer-state placement); explicit arg wins."""
    from mxnet_tpu.parallel.trainer import TrainStep
    assert not TrainStep(None, None)._sharded_update
    monkeypatch.setenv("MXNET_SHARDED_UPDATE", "1")
    step = TrainStep(None, None)
    assert step._sharded_update and step._shard_opt
    assert not TrainStep(None, None, sharded_update=False)._sharded_update
    monkeypatch.delenv("MXNET_SHARDED_UPDATE")
    assert not TrainStep(None, None)._sharded_update


def test_elastic_dp_policy_env_default(monkeypatch, tmp_path):
    """MXNET_ELASTIC_DP_POLICY feeds ResilientLoop's elastic_dp default;
    unknown values fail loudly."""
    from mxnet_tpu.parallel.resilient import ResilientLoop
    from mxnet_tpu.parallel.trainer import TrainStep
    from mxnet_tpu.utils.recovery import CheckpointManager

    def loop(**kw):
        return ResilientLoop(TrainStep(None, None),
                             CheckpointManager(str(tmp_path)),
                             watch_preemption=False, verbose=False, **kw)

    assert loop().elastic_dp == "raise"
    monkeypatch.setenv("MXNET_ELASTIC_DP_POLICY", "rescale")
    assert loop().elastic_dp == "rescale"
    assert loop(elastic_dp="raise").elastic_dp == "raise"
    monkeypatch.setenv("MXNET_ELASTIC_DP_POLICY", "explode")
    with pytest.raises(ValueError):
        loop()


def test_telemetry_env_knobs(monkeypatch, tmp_path):
    """MXNET_TELEMETRY gates recording; MXNET_FLIGHT_RECORDER_RING sizes
    the black box; MXNET_FLIGHT_RECORDER_DIR routes its dumps (unset =
    record in-process, write nothing)."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import FlightRecorder

    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_RING", "5")
    fr = FlightRecorder()
    assert fr.capacity == 5
    for i in range(9):
        fr.record("event", "e%d" % i)
    assert len(fr.events()) == 5
    monkeypatch.delenv("MXNET_FLIGHT_RECORDER_DIR", raising=False)
    assert fr.dump("nowhere") is None       # no dir -> no file, no error
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER_DIR", str(tmp_path))
    path = fr.dump("somewhere")
    assert path and os.path.exists(path)

    monkeypatch.setenv("MXNET_TELEMETRY", "0")
    fr2 = FlightRecorder(capacity=4)
    fr2.record("event", "dropped")
    assert fr2.events() == []
    reg = telemetry.MetricsRegistry()
    reg.counter("off_total").inc(7)
    assert reg.counter("off_total").value == 0
    monkeypatch.delenv("MXNET_TELEMETRY")
    reg.counter("off_total").inc(7)
    assert reg.counter("off_total").value == 7


def test_slo_and_request_log_env_knobs(monkeypatch, tmp_path):
    """MXNET_SLO_* declare objectives (parsed at ServingMetrics
    construction; burn/attainment math pinned in test_slo.py);
    MXNET_REQUEST_LOG[_SAMPLE] route the lifecycle ledger. Malformed
    values fail loudly naming the knob."""
    from mxnet_tpu import telemetry

    monkeypatch.delenv("MXNET_SLO_TTFT_MS", raising=False)
    monkeypatch.delenv("MXNET_SLO_ITL_MS", raising=False)
    monkeypatch.delenv("MXNET_SLO_AVAILABILITY", raising=False)
    assert telemetry.parse_slo_env() == []
    monkeypatch.setenv("MXNET_SLO_TTFT_MS", "250,acme=100:0.99")
    monkeypatch.setenv("MXNET_SLO_AVAILABILITY", "0.999")
    objs = telemetry.parse_slo_env()
    assert {(o.kind, o.tenant) for o in objs} == {
        ("ttft", None), ("ttft", "acme"), ("availability", None)}
    monkeypatch.setenv("MXNET_SLO_AVAILABILITY", "99.9")  # not a fraction
    with pytest.raises(ValueError):
        telemetry.parse_slo_env()

    log = telemetry.request_log()
    monkeypatch.delenv("MXNET_REQUEST_LOG", raising=False)
    assert not log.enabled
    monkeypatch.setenv("MXNET_REQUEST_LOG", str(tmp_path / "r.jsonl"))
    assert log.enabled
    monkeypatch.setenv("MXNET_REQUEST_LOG_SAMPLE", "0.25")
    assert log.sample_rate() == 0.25
    monkeypatch.setenv("MXNET_REQUEST_LOG_SAMPLE", "lots")
    with pytest.raises(ValueError, match="MXNET_REQUEST_LOG_SAMPLE"):
        log.sample_rate()


def test_serving_tp_and_replicas_env_defaults(monkeypatch):
    """MXNET_SERVING_TP / MXNET_SERVING_REPLICAS are the construction
    defaults for Engine(tp=) and serve(replicas=); explicit arguments
    win (behavior pinned end-to-end in test_serving_tp.py and
    test_serving_router.py)."""
    from mxnet_tpu.serving import serving_tp, serving_replicas
    monkeypatch.setenv("MXNET_SERVING_TP", "2")
    monkeypatch.setenv("MXNET_SERVING_REPLICAS", "3")
    assert serving_tp() == 2
    assert serving_replicas() == 3
    monkeypatch.delenv("MXNET_SERVING_TP")
    monkeypatch.delenv("MXNET_SERVING_REPLICAS")
    assert serving_tp() == 1
    assert serving_replicas() == 1


def test_compile_and_hbm_budget_env_knobs(monkeypatch):
    """MXNET_COMPILE_BUDGET / MXNET_HBM_BUDGET_GB parse `<value>[:policy]`
    with per-knob policy defaults (warn for the compile budget, raise for
    the HBM pre-flight); a bad policy fails loudly. Enforcement is pinned
    end-to-end in test_introspect.py."""
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.telemetry import introspect

    monkeypatch.delenv("MXNET_COMPILE_BUDGET", raising=False)
    assert introspect.compile_budget() == (None, None)
    monkeypatch.setenv("MXNET_COMPILE_BUDGET", "4")
    assert introspect.compile_budget() == (4, "warn")
    monkeypatch.setenv("MXNET_COMPILE_BUDGET", "4:raise")
    assert introspect.compile_budget() == (4, "raise")
    monkeypatch.setenv("MXNET_COMPILE_BUDGET", "4:explode")
    with pytest.raises(MXNetError):
        introspect.compile_budget()
    # a malformed number names the env var too, instead of surfacing as
    # a bare ValueError from inside the next compile
    monkeypatch.setenv("MXNET_COMPILE_BUDGET", "4GB")
    with pytest.raises(MXNetError, match="MXNET_COMPILE_BUDGET"):
        introspect.compile_budget()

    monkeypatch.delenv("MXNET_HBM_BUDGET_GB", raising=False)
    assert introspect.hbm_budget_bytes() == (None, None)
    monkeypatch.setenv("MXNET_HBM_BUDGET_GB", "1.5")
    assert introspect.hbm_budget_bytes() == (1.5 * 1024.0 ** 3, "raise")
    monkeypatch.setenv("MXNET_HBM_BUDGET_GB", "2:warn")
    assert introspect.hbm_budget_bytes() == (2.0 * 1024.0 ** 3, "warn")


def test_train_observability_env_knobs(monkeypatch):
    """ISSUE 14 knobs: straggler window/factor/patience, anomaly
    alpha/zscore/warmup/detect, the train-console port, and the two new
    chaos faults — defaults, overrides, and loud failures naming the
    knob (enforcement is pinned end-to-end in
    test_train_observability.py)."""
    from mxnet_tpu.parallel import resilient
    from mxnet_tpu.telemetry import anomaly
    from mxnet_tpu.utils import chaos

    for var in ("MXNET_STRAGGLER_WINDOW", "MXNET_STRAGGLER_FACTOR",
                "MXNET_STRAGGLER_PATIENCE", "MXNET_ANOMALY_DETECT",
                "MXNET_ANOMALY_ALPHA", "MXNET_ANOMALY_ZSCORE",
                "MXNET_ANOMALY_WARMUP"):
        monkeypatch.delenv(var, raising=False)
    assert resilient.straggler_window_env() == 0       # off by default
    assert resilient.straggler_factor() == 2.0
    assert resilient.straggler_patience() == 2
    monkeypatch.setenv("MXNET_STRAGGLER_WINDOW", "16")
    monkeypatch.setenv("MXNET_STRAGGLER_FACTOR", "1.5")
    monkeypatch.setenv("MXNET_STRAGGLER_PATIENCE", "3")
    assert resilient.straggler_window_env() == 16
    assert resilient.straggler_factor() == 1.5
    assert resilient.straggler_patience() == 3
    monkeypatch.setenv("MXNET_STRAGGLER_WINDOW", "soon")
    with pytest.raises(ValueError, match="MXNET_STRAGGLER_WINDOW"):
        resilient.straggler_window_env()
    monkeypatch.setenv("MXNET_STRAGGLER_FACTOR", "0.5")  # <= 1: absurd
    with pytest.raises(ValueError, match="MXNET_STRAGGLER_FACTOR"):
        resilient.straggler_factor()

    assert not anomaly.detect_enabled()                # off by default
    monkeypatch.setenv("MXNET_ANOMALY_DETECT", "1")
    assert anomaly.detect_enabled()
    assert anomaly.anomaly_alpha() == 0.05
    assert anomaly.anomaly_zscore() == 6.0
    assert anomaly.anomaly_warmup() == 20
    monkeypatch.setenv("MXNET_ANOMALY_ALPHA", "0.2")
    monkeypatch.setenv("MXNET_ANOMALY_ZSCORE", "4")
    monkeypatch.setenv("MXNET_ANOMALY_WARMUP", "5")
    assert anomaly.anomaly_alpha() == 0.2
    assert anomaly.anomaly_zscore() == 4.0
    assert anomaly.anomaly_warmup() == 5
    monkeypatch.setenv("MXNET_ANOMALY_ALPHA", "2.0")   # not a weight
    with pytest.raises(ValueError, match="MXNET_ANOMALY_ALPHA"):
        anomaly.anomaly_alpha()

    monkeypatch.setenv("MXNET_STRAGGLER_WINDOW", "0")
    monkeypatch.setenv("MXNET_STRAGGLER_FACTOR", "2.0")
    monkeypatch.setenv("MXNET_ANOMALY_DETECT", "0")
    monkeypatch.setenv("MXNET_ANOMALY_ALPHA", "0.05")
    # console port: unset = no console; a non-integer fails naming the
    # knob at loop construction (before any training happened)
    import tempfile
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import ResilientLoop, TrainStep
    from mxnet_tpu.utils.recovery import CheckpointManager
    import mxnet_tpu as mx
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1})
    monkeypatch.delenv("MXNET_TRAIN_METRICS_PORT", raising=False)
    loop = ResilientLoop(step, CheckpointManager(tempfile.mkdtemp()),
                         watch_preemption=False, verbose=False)
    assert loop.console_addr is None and loop._console is None
    monkeypatch.setenv("MXNET_TRAIN_METRICS_PORT", "http")
    with pytest.raises(ValueError, match="MXNET_TRAIN_METRICS_PORT"):
        ResilientLoop(step, CheckpointManager(tempfile.mkdtemp()),
                      watch_preemption=False, verbose=False)

    # chaos: the two new faults parse (slow_host keyed by HOST string,
    # spike_step by step) and malformed values fail loudly
    chaos.reset()
    monkeypatch.setenv("MXNET_CHAOS_SLOW_HOST", "2:0.25:3")
    monkeypatch.setenv("MXNET_CHAOS_SPIKE_STEP", "7")
    active = chaos.active()
    assert active["slow_host"] == ("2", 0.25, 3)
    assert active["spike_step"] == 7
    chaos.reset()
    monkeypatch.setenv("MXNET_CHAOS_SLOW_HOST", "2")   # missing secs
    with pytest.raises(ValueError, match="MXNET_CHAOS_SLOW_HOST"):
        chaos.active()
    chaos.reset()


def test_remediation_env_knobs(monkeypatch):
    """ISSUE 15 knob surface: supervisor cadences/budgets parse with
    documented defaults, malformed values fail naming the knob, and the
    sdc_at chaos fault parses its <host>:<step> shape."""
    from mxnet_tpu.parallel import supervisor
    from mxnet_tpu.utils import chaos
    for var in ("MXNET_TRAIN_REMEDIATION", "MXNET_SDC_PROBE_EVERY",
                "MXNET_SDC_PROBE_TIMEOUT", "MXNET_TRAIN_RESTART_MAX",
                "MXNET_TRAIN_RESTART_BACKOFF", "MXNET_CORDON_MIN_HOSTS"):
        monkeypatch.delenv(var, raising=False)
    assert not supervisor.remediation_enabled()        # off by default
    assert supervisor.sdc_probe_every() == 0
    assert supervisor.sdc_probe_timeout() == 60.0
    assert supervisor.restart_max() == 3
    assert supervisor.restart_backoff() == 0.5
    assert supervisor.cordon_min_hosts() == 1
    monkeypatch.setenv("MXNET_TRAIN_REMEDIATION", "1")
    monkeypatch.setenv("MXNET_SDC_PROBE_EVERY", "64")
    monkeypatch.setenv("MXNET_TRAIN_RESTART_MAX", "5")
    monkeypatch.setenv("MXNET_TRAIN_RESTART_BACKOFF", "1.5")
    monkeypatch.setenv("MXNET_CORDON_MIN_HOSTS", "2")
    assert supervisor.remediation_enabled()
    assert supervisor.sdc_probe_every() == 64
    assert supervisor.restart_max() == 5
    assert supervisor.restart_backoff() == 1.5
    assert supervisor.cordon_min_hosts() == 2
    monkeypatch.setenv("MXNET_SDC_PROBE_EVERY", "often")
    with pytest.raises(ValueError, match="MXNET_SDC_PROBE_EVERY"):
        supervisor.sdc_probe_every()
    monkeypatch.setenv("MXNET_TRAIN_RESTART_MAX", "-1")
    with pytest.raises(ValueError, match="MXNET_TRAIN_RESTART_MAX"):
        supervisor.restart_max()
    monkeypatch.setenv("MXNET_CORDON_MIN_HOSTS", "0")  # a 0-host pod
    with pytest.raises(ValueError, match="MXNET_CORDON_MIN_HOSTS"):
        supervisor.cordon_min_hosts()
    # the sdc_at chaos fault: <host>:<step>, host stays a string
    chaos.reset()
    monkeypatch.setenv("MXNET_CHAOS_SDC_AT", "3:17")
    assert chaos.active()["sdc_at"] == ("3", 17)
    chaos.reset()
    monkeypatch.setenv("MXNET_CHAOS_SDC_AT", "3")      # missing step
    with pytest.raises(ValueError, match="MXNET_CHAOS_SDC_AT"):
        chaos.active()
    chaos.reset()


def test_anomaly_alpha_zero_fails_loudly_naming_the_knob(monkeypatch):
    """alpha=0 would freeze the EWMA; it must be rejected AT THE KNOB
    (named), not mid-training by the lazily-built detector."""
    from mxnet_tpu.telemetry import anomaly
    monkeypatch.setenv("MXNET_ANOMALY_ALPHA", "0")
    with pytest.raises(ValueError, match="MXNET_ANOMALY_ALPHA"):
        anomaly.anomaly_alpha()
    monkeypatch.setenv("MXNET_ANOMALY_ALPHA", "-0.1")
    with pytest.raises(ValueError, match="MXNET_ANOMALY_ALPHA"):
        anomaly.anomaly_alpha()


def test_train_metrics_host_env(monkeypatch, tmp_path):
    """MXNET_TRAIN_METRICS_HOST selects the console's bind interface
    (loopback by default; cross-host pod polling needs an explicit
    0.0.0.0)."""
    import tempfile
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import ResilientLoop, TrainStep
    from mxnet_tpu.utils.recovery import CheckpointManager
    import mxnet_tpu as mx
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1})
    monkeypatch.delenv("MXNET_TRAIN_METRICS_HOST", raising=False)
    loop = ResilientLoop(step, CheckpointManager(tempfile.mkdtemp()),
                         watch_preemption=False, verbose=False,
                         metrics_port=0)
    assert loop.console_addr[0] == "127.0.0.1"
    loop.close_console()
    monkeypatch.setenv("MXNET_TRAIN_METRICS_HOST", "0.0.0.0")
    loop = ResilientLoop(step, CheckpointManager(tempfile.mkdtemp()),
                         watch_preemption=False, verbose=False,
                         metrics_port=0)
    assert loop.console_addr[0] == "0.0.0.0"
    loop.close_console()


def test_serving_rollout_dir_env_attaches_controller(monkeypatch,
                                                     tmp_path):
    """MXNET_SERVING_ROLLOUT_DIR turns live rollouts on through
    serve() — even a single-replica fleet becomes a routed fleet with
    a watching controller — and the ladder/window/prompt knobs feed
    its config. Malformed ladders fail loudly naming the knob."""
    import jax
    from mxnet_tpu import serving
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params)
    cfg = TransformerConfig(vocab=48, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=64)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    monkeypatch.setenv("MXNET_SERVING_ROLLOUT_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_ROLLOUT_STAGES", "1/8,1/2")
    monkeypatch.setenv("MXNET_ROLLOUT_WINDOW_S", "0.5")
    monkeypatch.setenv("MXNET_ROLLOUT_PARITY_PROMPTS", "2")
    srv = serving.serve((params, cfg), max_batch=2, block_size=8)
    try:
        assert srv.rollout is not None
        assert srv.rollout.directory == str(tmp_path)
        assert srv.rollout.stages == (0.125, 0.5)
        assert srv.rollout.window_s == 0.5
        assert srv.rollout.parity_prompts == 2
        assert srv.statusz()["fleet"]["rollout"]["state"] == "idle"
    finally:
        srv.close()
    monkeypatch.setenv("MXNET_ROLLOUT_STAGES", "1/2,1/4")
    with pytest.raises(MXNetError, match="MXNET_ROLLOUT_STAGES"):
        serving.serve((params, cfg), max_batch=2, block_size=8)
    monkeypatch.delenv("MXNET_ROLLOUT_STAGES")
    monkeypatch.delenv("MXNET_SERVING_ROLLOUT_DIR")
