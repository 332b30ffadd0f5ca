"""SLO-driven elastic autoscaling tests (ISSUE 16,
serving/autoscale.py + the router's scale_up/scale_down).

Load-bearing claims: (1) a multi-window TTFT burn breach scales up —
and ONLY a multi-window breach with real traffic, one hot window or an
empty one is a blip; (2) sustained idleness plus cooled burn scales
down, and a drained retire loses zero in-flight requests; (3) the
min/max bounds are never violated, and the min floor is restored even
inside the cooldown; (4) hysteresis (down_burn < up_burn) plus the
action cooldown keep the scaler flap-free under oscillating load;
(5) `serve(autoscale=...)`/MXNET_SERVING_AUTOSCALE build the
replicated door with a live autoscaler attached.
"""
import threading
import time

import pytest

import jax

from mxnet_tpu import serving, telemetry
from mxnet_tpu.serving import Autoscaler, AutoscaleConfig, autoscale_enabled
from mxnet_tpu.telemetry import introspect
from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params)


@pytest.fixture(autouse=True)
def _fresh_watchdog():
    introspect.reset()
    telemetry.default_registry().reset()
    telemetry.tracing.clear()
    telemetry.flight().clear()
    yield
    introspect.reset()
    telemetry.default_registry().reset()
    telemetry.tracing.clear()
    telemetry.flight().clear()


@pytest.fixture
def _no_jax_persistent_cache():
    """jax's own persistent compilation cache poisons AOT serialization
    (an executable jax deserialized from ITS cache serializes to a
    payload `deserialize_and_load` rejects — see test_aot.py), so the
    warm-gauge test must compile genuinely fresh."""
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc)
        _cc.reset_cache()
    except Exception:
        pass
    yield
    jax.config.update("jax_compilation_cache_dir", old)
    try:
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc)
        _cc.reset_cache()
    except Exception:
        pass


def tiny_cfg(**kw):
    base = dict(vocab=48, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                max_len=64)
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = tiny_cfg()
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


def arith_prompt(start, stride, n, vocab=48):
    return [(start + stride * t) % vocab for t in range(n)]


class FakeRouter:
    """Just enough router for the decision-logic tests: a replica list
    and scale ops that honor nothing (bounds are the scaler's job)."""

    def __init__(self, n=1):
        self._closed = False
        self.replicas = ["rep%d" % i for i in range(n)]

    def scale_up(self):
        self.replicas.append("rep%d" % len(self.replicas))
        return self.replicas[-1]

    def scale_down(self):
        if len(self.replicas) <= 1:
            return None
        return self.replicas.pop()


def burns(rate, total=10, windows=(60, 300)):
    return {w: {"rate": rate, "good": max(0, total - 1),
                "total": total, "span_s": float(w)} for w in windows}


def scaler(router, **kw):
    base = dict(min_replicas=1, max_replicas=4, up_burn=1.0,
                down_burn=0.1, cooldown_s=30.0, idle_retire_s=60.0)
    base.update(kw)
    return Autoscaler(router, config=AutoscaleConfig(**base))


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------


def test_config_validation():
    with pytest.raises(ValueError):
        AutoscaleConfig(min_replicas=0)
    with pytest.raises(ValueError):
        AutoscaleConfig(min_replicas=3, max_replicas=2)
    # equal thresholds would flap: hysteresis is mandatory
    with pytest.raises(ValueError):
        AutoscaleConfig(up_burn=1.0, down_burn=1.0)
    with pytest.raises(ValueError):
        AutoscaleConfig(up_burn=0.5, down_burn=0.6)


def test_config_from_env(monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_MIN_REPLICAS", "2")
    monkeypatch.setenv("MXNET_SERVING_MAX_REPLICAS", "6")
    monkeypatch.setenv("MXNET_SERVING_SCALE_UP_BURN", "2.5")
    monkeypatch.setenv("MXNET_SERVING_SCALE_DOWN_BURN", "0.25")
    monkeypatch.setenv("MXNET_SERVING_SCALE_COOLDOWN_S", "7")
    monkeypatch.setenv("MXNET_SERVING_SCALE_IDLE_S", "11")
    monkeypatch.setenv("MXNET_SERVING_SCALE_INTERVAL_S", "0.5")
    cfg = AutoscaleConfig.from_env()
    assert (cfg.min_replicas, cfg.max_replicas) == (2, 6)
    assert (cfg.up_burn, cfg.down_burn) == (2.5, 0.25)
    assert (cfg.cooldown_s, cfg.idle_retire_s, cfg.interval_s) \
        == (7.0, 11.0, 0.5)


def test_autoscale_enabled_env(monkeypatch):
    for off in ("", "0", "false", "off"):
        monkeypatch.setenv("MXNET_SERVING_AUTOSCALE", off)
        assert not autoscale_enabled()
    monkeypatch.setenv("MXNET_SERVING_AUTOSCALE", "1")
    assert autoscale_enabled()


# ---------------------------------------------------------------------------
# the decision, on a fake clock
# ---------------------------------------------------------------------------


def test_burn_breach_scales_up_and_cooldown_holds():
    r = FakeRouter(1)
    sc = scaler(r, cooldown_s=30.0)
    sc.burn_rates = lambda: burns(5.0)
    sc.fleet_load_tokens = lambda: 100
    assert sc.step(now=0.0) == "up"
    assert len(r.replicas) == 2 and sc.scale_ups == 1
    assert sc.last_breach_to_action_s is not None
    assert sc.last_breach_to_action_s >= 0.0
    # still burning: the cooldown separates any two actions
    assert sc.step(now=1.0) is None
    assert sc.step(now=29.9) is None
    assert sc.step(now=31.0) == "up"
    assert len(r.replicas) == 3


def test_max_replicas_is_a_hard_ceiling():
    r = FakeRouter(4)
    sc = scaler(r, max_replicas=4, cooldown_s=0.0)
    sc.burn_rates = lambda: burns(99.0)
    sc.fleet_load_tokens = lambda: 1000
    for t in range(10):
        assert sc.step(now=float(t)) is None
    assert len(r.replicas) == 4 and sc.scale_ups == 0


def test_single_window_or_empty_breach_is_a_blip():
    r = FakeRouter(1)
    sc = scaler(r, cooldown_s=0.0)
    sc.fleet_load_tokens = lambda: 10
    # only the shortest window hot -> not a breach
    sc.burn_rates = lambda: {60: {"rate": 5.0, "total": 8},
                             300: {"rate": 0.2, "total": 8}}
    assert sc.step(now=0.0) is None
    # both windows "hot" but zero traffic -> not a breach
    sc.burn_rates = lambda: burns(5.0, total=0)
    assert sc.step(now=1.0) is None
    assert len(r.replicas) == 1


def test_idle_fleet_retires_after_idle_window():
    r = FakeRouter(3)
    sc = scaler(r, idle_retire_s=60.0, cooldown_s=5.0)
    sc.burn_rates = lambda: {}          # no SLO armed reads as cold
    sc.fleet_load_tokens = lambda: 0
    assert sc.step(now=0.0) is None     # idle clock starts
    assert sc.step(now=59.0) is None    # not idle long enough
    assert sc.step(now=61.0) == "down"
    assert len(r.replicas) == 2 and sc.scale_downs == 1
    # the idle clock restarts per retire — no machine-gun drain
    assert sc.step(now=62.0) is None             # 0s of NEW idle
    assert sc.step(now=121.9) is None            # 59.9s — not yet
    assert sc.step(now=122.0) == "down"
    assert len(r.replicas) == 1
    # min floor: never below min_replicas no matter how idle
    for t in range(300, 310):
        assert sc.step(now=float(t)) is None
    assert len(r.replicas) == 1


def test_warm_burn_blocks_idle_retire():
    """Idle queue but burn not cooled below down_burn: hysteresis says
    hold — the traffic that burned the budget may be coming back."""
    r = FakeRouter(2)
    sc = scaler(r, idle_retire_s=10.0, cooldown_s=0.0, down_burn=0.1)
    sc.burn_rates = lambda: burns(0.5)   # between down_burn and up_burn
    sc.fleet_load_tokens = lambda: 0
    for t in range(0, 100, 5):
        assert sc.step(now=float(t)) is None
    assert len(r.replicas) == 2


def test_min_floor_restored_inside_cooldown():
    r = FakeRouter(1)
    sc = scaler(r, min_replicas=2, max_replicas=4, cooldown_s=1000.0)
    sc.burn_rates = lambda: {}
    sc.fleet_load_tokens = lambda: 0
    sc._last_action_t = 0.0              # deep inside the cooldown
    assert sc.step(now=1.0) == "up"      # the floor is a promise
    assert len(r.replicas) == 2


def test_oscillating_burn_never_flaps():
    """Load oscillating across the hysteresis band (but never meeting
    BOTH action conditions) holds the fleet size through hundreds of
    ticks."""
    r = FakeRouter(2)
    sc = scaler(r, up_burn=1.0, down_burn=0.1, idle_retire_s=30.0,
                cooldown_s=5.0)
    actions = []
    for t in range(0, 600):
        # rate swings 0.2..0.9 — above the retire floor, below the
        # breach ceiling; traffic flickers on and off
        rate = 0.55 + 0.35 * (1 if t % 2 else -1)
        sc.burn_rates = lambda rate=rate: burns(rate)
        sc.fleet_load_tokens = lambda t=t: (t % 7 != 0) and 10 or 0
        a = sc.step(now=float(t))
        if a:
            actions.append((t, a))
    assert not actions, "hysteresis flapped: %r" % actions
    assert len(r.replicas) == 2


def test_closed_router_never_scales():
    r = FakeRouter(1)
    r._closed = True
    sc = scaler(r)
    sc.burn_rates = lambda: burns(9.0)
    sc.fleet_load_tokens = lambda: 50
    assert sc.step(now=0.0) is None
    assert len(r.replicas) == 1


def test_daemon_thread_start_stop():
    r = FakeRouter(1)
    sc = scaler(r, cooldown_s=0.0)
    sc.cfg.interval_s = 0.01
    hits = []
    sc.burn_rates = lambda: (hits.append(1), {})[1]
    sc.fleet_load_tokens = lambda: 1
    sc.start()
    sc.start()                           # idempotent
    deadline = time.monotonic() + 5.0
    while not hits and time.monotonic() < deadline:
        time.sleep(0.01)
    sc.stop()
    assert hits, "the autoscale thread never ticked"
    assert sc._thread is None


# ---------------------------------------------------------------------------
# the real router: warm capacity in, drained capacity out
# ---------------------------------------------------------------------------


def test_router_scale_up_down_zero_lost_requests(tiny_lm):
    """scale_up adds a serving replica (counters move, requests land on
    it); scale_down drains + re-homes the tail mid-flight and every
    in-flight request still completes — zero lost."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), replicas=2, max_batch=2,
                        block_size=8)
    try:
        assert srv.replica_count() == 2
        assert srv.scale_up() is not None
        assert srv.replica_count() == 3
        assert srv._c_scale_up.value == 1
        results = {}

        def client(i):
            results[i] = srv.generate(arith_prompt(i, 1, 5 + i % 3),
                                      max_new_tokens=4, timeout=120)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
            time.sleep(0.01)
        # retire the tail while the burst is in flight: drain + re-home
        assert srv.scale_down() is not None
        for t in threads:
            t.join()
        assert srv.replica_count() == 2
        assert srv._c_scale_down.value == 1
        for i in range(6):
            assert len(results[i]) == 4, "request %d lost in retire" % i
        snap = srv.snapshot()["aggregate"]
        assert snap["requests"].get("failed", 0) == 0
    finally:
        srv.close()


def test_scale_down_refuses_last_replica(tiny_lm):
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), replicas=2, max_batch=1,
                        block_size=8)
    try:
        assert srv.scale_down() is not None
        assert srv.replica_count() == 1
        assert srv.scale_down() is None          # never to zero
        assert srv.replica_count() == 1
    finally:
        srv.close()


def test_autoscaler_drill_on_real_router(tiny_lm):
    """The bench's drill, in-suite: scripted burn breach -> a real
    replica spawned within the cooldown; scripted idle+cold -> it is
    drained and retired; the fleet serves before, between, and after."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), replicas=1, autoscale=False,
                        max_batch=2, block_size=8)
    # replicas=1 without autoscale is a plain LMServer; the drill needs
    # the replicated door
    srv.close()
    srv = serving.serve((params, cfg), replicas=2, max_batch=2,
                        block_size=8)
    sc = Autoscaler(srv, AutoscaleConfig(
        min_replicas=1, max_replicas=3, cooldown_s=0.05,
        idle_retire_s=0.2))
    try:
        assert len(srv.generate(arith_prompt(1, 1, 6),
                                max_new_tokens=3, timeout=120)) == 3
        sc.burn_rates = lambda: burns(10.0, total=8)
        sc.fleet_load_tokens = lambda: 1
        t0 = time.monotonic()
        assert sc.step() == "up"
        assert time.monotonic() - t0 < 5.0
        assert srv.replica_count() == 3
        assert sc.last_breach_to_action_s is not None
        assert len(srv.generate(arith_prompt(2, 1, 7),
                                max_new_tokens=3, timeout=120)) == 3
        # cool + idle: retire back down
        sc.burn_rates = lambda: {}
        sc.fleet_load_tokens = lambda: 0
        time.sleep(0.06)                  # out of the cooldown
        now = time.monotonic()
        assert sc.step(now=now) is None   # idle clock starts
        assert sc.step(now=now + 0.3) == "down"
        assert srv.replica_count() == 2
        assert len(srv.generate(arith_prompt(3, 1, 5),
                                max_new_tokens=2, timeout=120)) == 2
    finally:
        sc.stop()
        srv.close()


def test_serve_autoscale_builds_replicated_door(tiny_lm, monkeypatch):
    params, cfg = tiny_lm
    # explicit kwarg wins even at replicas=1: the fleet needs somewhere
    # to grow
    srv = serving.serve((params, cfg), replicas=1, autoscale=True,
                        max_batch=1, block_size=8)
    try:
        assert isinstance(srv, serving.ReplicatedLMServer)
        assert srv.autoscaler is not None
        assert srv.autoscaler._thread is not None
    finally:
        srv.close()
    assert srv.autoscaler._thread is None        # close() stopped it
    # env default: off -> plain single-replica server
    srv = serving.serve((params, cfg), max_batch=1, block_size=8)
    try:
        assert not isinstance(srv, serving.ReplicatedLMServer)
    finally:
        srv.close()
    # MXNET_SERVING_AUTOSCALE=1 arms it without code changes
    monkeypatch.setenv("MXNET_SERVING_AUTOSCALE", "1")
    monkeypatch.setenv("MXNET_SERVING_MAX_REPLICAS", "2")
    srv = serving.serve((params, cfg), max_batch=1, block_size=8)
    try:
        assert isinstance(srv, serving.ReplicatedLMServer)
        assert srv.autoscaler is not None
        assert srv.autoscaler.cfg.max_replicas == 2
    finally:
        srv.close()


def test_warm_replica_gauge_tracks_aot_loads(tiny_lm, tmp_path,
                                             _no_jax_persistent_cache):
    """serving_warm_replicas counts replicas whose engine warm-loaded
    from the AOT cache — 0 on a cold fleet, rising once a respawn or
    scale-up loads from disk."""
    from mxnet_tpu import aot
    params, cfg = tiny_lm
    try:
        # populate the cache with one cold engine outside the router,
        # placed like replica 0 (entries are keyed by the device the
        # executable was compiled for)
        eng = serving.Engine(serving.TransformerLM(params, cfg),
                             max_batch=1, block_size=8,
                             devices=jax.devices()[:1],
                             aot_cache=tmp_path)
        s = eng.start(arith_prompt(1, 1, 6), max_new=2)
        while not s.done:
            eng.decode_step([s])
        eng.release(s)
        eng.close()
        srv = serving.serve((params, cfg), replicas=2, max_batch=1,
                            block_size=8, aot_cache=tmp_path)
        try:
            assert len(srv.generate(arith_prompt(1, 1, 6),
                                    max_new_tokens=2, timeout=120)) == 2
            # the gauge refreshes on the health sweep (traffic routing
            # or /healthz) — the warm load itself happened lazily at
            # the generate's prefill, after the submit-time sweep
            srv.health()
            assert srv._g_warm.value >= 1
        finally:
            srv.close()
    finally:
        aot.configure()
