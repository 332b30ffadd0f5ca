"""Remediation supervisor tests (ISSUE 15: parallel/supervisor.py,
the EXIT_RECONFIGURE drain, deadline-aware retry, the checkpoint
auditor, SDC parity probes, the cordon roster, and the chaos-coverage
static check).

The load-bearing claims:
(1) `utils.retry(deadline_s=)` caps TOTAL backoff sleep, and the
    PreemptionWatcher's `remaining_grace()` threads through
    `CheckpointManager._io_retry` so a SIGTERM drain can't sleep past
    the grace window;
(2) the cordon roster is atomic, idempotent, honored by
    `effective_hosts`, and a cordoned host refuses to start;
(3) a straggler episode or SDC quorum suspect cordons the host and the
    next step boundary drains with EXIT_RECONFIGURE (84), checkpoint
    published;
(4) the SDC probe is deterministic and donation-free; a flipped digest
    names exactly the divergent host under a strict-majority quorum
    and names nobody on an unattributable split;
(5) the background auditor demotes a published-then-corrupted step
    before restore_latest ever sees it, and never demotes a merely
    incomplete (mid-publish) step;
(6) elastic restore across a GROWN world honors the roster and still
    refuses genuinely missing shards;
(7) every fault name utils/chaos.py parses is exercised somewhere in
    tests/ or the drill tools (the PR 2 cost-estimate-scan pattern).
"""
import ast
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, telemetry
from mxnet_tpu.parallel.resilient import (ResilientLoop, Reconfigured,
                                          EXIT_PREEMPTED,
                                          EXIT_RECONFIGURE)
from mxnet_tpu.parallel.supervisor import (TrainSupervisor, CordonRoster,
                                           CordonedHostError, SDCProbe,
                                           CheckpointAuditor,
                                           effective_hosts,
                                           _FileDigestExchange)
from mxnet_tpu.parallel.trainer import TrainStep
from mxnet_tpu.utils import chaos, retry
from mxnet_tpu.utils.recovery import CheckpointManager

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _chaos_clean():
    chaos.reset()
    yield
    chaos.reset()


# ---------------------------------------------------------------------------
# shared fixtures
# ---------------------------------------------------------------------------


def make_net(seed=0):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, in_units=6, activation="relu"))
    net.add(gluon.nn.Dense(3, in_units=16))
    net.initialize(mx.init.Xavier())
    return net


def batch(i):
    rng = np.random.RandomState(2000 + i)
    return (rng.randn(8, 6).astype(np.float32),
            rng.randint(0, 3, (8,)).astype(np.float32))


def make_loop(ckpt_dir, **kw):
    net = make_net()
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                     {"learning_rate": 0.01}, guard=True)
    mgr = CheckpointManager(str(ckpt_dir), keep=3, async_save=False)
    loop = ResilientLoop(step, mgr, save_every=kw.pop("save_every", 4),
                         policy="skip", watch_preemption=False,
                         verbose=False, metrics_port=False, **kw)
    return net, step, mgr, loop


# ---------------------------------------------------------------------------
# (1) deadline-aware retry
# ---------------------------------------------------------------------------


def test_retry_deadline_caps_total_sleep(monkeypatch):
    """Fake clock: a deadline_s cap must clamp the backoff sleeps to the
    remaining budget and give up (re-raise) once it is spent — never
    sleep past the deadline no matter how many attempts remain."""
    clock = {"t": 100.0}
    sleeps = []

    def fake_monotonic():
        return clock["t"]

    def fake_sleep(s):
        sleeps.append(s)
        clock["t"] += s

    monkeypatch.setattr(time, "monotonic", fake_monotonic)
    monkeypatch.setattr(time, "sleep", fake_sleep)
    calls = []

    def always_fails():
        calls.append(1)
        raise OSError("transient")

    with pytest.raises(OSError):
        retry(always_fails, attempts=10, backoff=10.0, jitter=0.0,
              deadline_s=12.0)
    # sleep 1: 10s (within budget); sleep 2 would be 20s -> clamped to
    # the 2s remainder; then the budget is spent and attempt 3's failure
    # re-raises — 7 attempts never happen
    assert sleeps == [10.0, 2.0], sleeps
    assert sum(sleeps) <= 12.0
    assert len(calls) == 3


def test_retry_deadline_already_spent_reraises_immediately(monkeypatch):
    sleeps = []
    monkeypatch.setattr(time, "sleep", lambda s: sleeps.append(s))
    with pytest.raises(OSError):
        retry(lambda: (_ for _ in ()).throw(OSError("x")),
              attempts=5, backoff=1.0, jitter=0.0, deadline_s=0.0)
    assert sleeps == []


def test_retry_no_deadline_unchanged(monkeypatch):
    sleeps = []
    monkeypatch.setattr(time, "sleep", lambda s: sleeps.append(s))
    out = {"n": 0}

    def flaky():
        out["n"] += 1
        if out["n"] < 3:
            raise OSError("x")
        return "ok"

    assert retry(flaky, attempts=5, backoff=0.5, jitter=0.0) == "ok"
    assert sleeps == [0.5, 1.0]


def test_io_retry_threads_watcher_grace_deadline(tmp_path, monkeypatch):
    """The regression the satellite names: with the watcher triggered
    and (almost) no grace left, publish-IO retry must not sleep —
    the drain's final checkpoint can't be handed to the force-exit
    timer by a backoff nap."""
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    # ResilientLoop wires the watcher's remaining_grace through the
    # manager; emulate the wiring against a fake grace readout
    remaining = {"s": 0.0}
    mgr.deadline_fn = lambda: remaining["s"]
    sleeps = []
    monkeypatch.setattr(time, "sleep", lambda s: sleeps.append(s))
    attempts = []

    def always_fails():
        attempts.append(1)
        raise OSError("ENOSPC")

    with pytest.raises(OSError):
        mgr._io_retry(always_fails)
    assert sleeps == []              # zero grace -> zero backoff sleep
    assert len(attempts) == 1        # and no bonus attempts
    # with grace available the retries run normally
    remaining["s"] = None            # watcher not triggered -> no cap
    del attempts[:]
    with pytest.raises(OSError):
        mgr._io_retry(always_fails)
    assert len(attempts) == mgr.io_retries


def test_loop_wires_grace_deadline_into_manager(tmp_path):
    """Constructing a ResilientLoop with the watcher installs the
    remaining_grace readout on the manager (the production wiring the
    fake above emulates)."""
    net = make_net()
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                     {"learning_rate": 0.01}, guard=True)
    mgr = CheckpointManager(str(tmp_path), keep=2)
    loop = ResilientLoop(step, mgr, save_every=0, policy="skip",
                         watch_preemption=True, verbose=False,
                         metrics_port=False)
    try:
        assert mgr.deadline_fn == loop.watcher.remaining_grace
        assert mgr.deadline_fn() is None     # untriggered: no cap
    finally:
        loop.watcher.uninstall()


# ---------------------------------------------------------------------------
# (2) cordon roster
# ---------------------------------------------------------------------------


def test_cordon_roster_roundtrip(tmp_path):
    r = CordonRoster(str(tmp_path / "cordon"))
    assert r.hosts() == {} and len(r) == 0
    assert r.cordon("3", reason="straggler", step=41) is True
    assert r.cordon("3", reason="sdc") is False      # first writer wins
    assert r.is_cordoned("3") and not r.is_cordoned("0")
    entry = r.hosts()["3"]
    assert entry["reason"] == "straggler" and entry["step"] == 41
    assert effective_hosts(["0", "1", "2", "3"], r) == ["0", "1", "2"]
    assert r.uncordon("3") is True
    assert not r.is_cordoned("3")
    assert r.uncordon("3") is False


def test_cordon_roster_concurrent_writers_one_entry(tmp_path):
    """Two pod members cordoning the same host race on the roster
    directory: exactly one entry results, no torn file."""
    a = CordonRoster(str(tmp_path / "cordon"))
    b = CordonRoster(str(tmp_path / "cordon"))
    wins = [a.cordon("1", reason="straggler"),
            b.cordon("1", reason="sdc")]
    assert wins.count(True) == 1
    assert sorted(a.hosts()) == ["1"]
    assert a.hosts()["1"]["reason"] == "straggler"


def test_supervisor_refuses_cordoned_host(tmp_path):
    """Roster honored at startup: a worker whose host is cordoned must
    fail loudly instead of rejoining the pod."""
    _, _, mgr, loop = make_loop(tmp_path)
    roster = CordonRoster.beside(mgr.directory)
    roster.cordon("me", reason="sdc")
    with pytest.raises(CordonedHostError, match="cordon"):
        TrainSupervisor(loop, host="me", audit=False)
    # a different host attaches fine
    sup = TrainSupervisor(loop, host="other", audit=False)
    assert loop.supervisor is sup
    sup.close()


# ---------------------------------------------------------------------------
# (3) cordon -> reconfigure drain
# ---------------------------------------------------------------------------


def test_straggler_episode_cordons_and_drains_with_84(tmp_path):
    _, step, mgr, loop = make_loop(tmp_path)
    sup = TrainSupervisor(loop, host="0", expect_hosts=3, audit=False)
    loop.step(*batch(0))
    sup.on_step(loop.t, stragglers=["2"])
    assert sup.roster.is_cordoned("2")
    assert sup.reconfigure_requested
    assert sup.reconfigure_reason == "straggler:2"
    with pytest.raises(Reconfigured) as ei:
        loop.step(*batch(1))
    assert ei.value.code == EXIT_RECONFIGURE == 84
    assert EXIT_RECONFIGURE != EXIT_PREEMPTED
    # the drain published a checkpoint at the boundary step
    got_step, tree = mgr.restore_latest()
    assert got_step == ei.value.step == loop.t
    # and the action ledger + statusz carry the whole story
    acts = [a["action"] for a in sup.actions]
    assert "cordon" in acts and "reconfigure" in acts
    z = loop.statusz()["remediation"]
    assert sorted(z["cordoned"]) == ["2"]
    assert z["reconfigure"]["requested"] is True


def test_already_cordoned_host_never_redrains(tmp_path):
    """The livelock guard: a stale detector signal about an
    already-cordoned host (e.g. its last straggler publishes surviving
    into the relaunched incarnation) must not re-arm reconfigure."""
    _, _, mgr, loop = make_loop(tmp_path)
    roster = CordonRoster.beside(mgr.directory)
    roster.cordon("1", reason="straggler")
    sup = TrainSupervisor(loop, host="0", expect_hosts=2, audit=False)
    assert sup.consider_cordon("1", "straggler", 5) is False
    assert not sup.reconfigure_requested
    loop.step(*batch(0))             # trains on, no Reconfigured raise
    sup.close()


def test_peer_cordoning_me_first_still_drains_me(tmp_path):
    """The leg-C race: a peer wins the roster write for MY host; my own
    supervisor must still drain me out (a cordoned host training on is
    wasted, SDC-suspect work whose black box never dumps)."""
    _, _, mgr, loop = make_loop(tmp_path)
    roster = CordonRoster.beside(mgr.directory)
    sup = TrainSupervisor(loop, host="1", expect_hosts=3, audit=False)
    roster.cordon("1", reason="sdc")     # the peer's write, post-attach
    assert sup.consider_cordon("1", "sdc", 8) is True
    assert sup.reconfigure_requested
    assert sup.reconfigure_reason == "sdc:1"
    sup.close()


def test_cordon_floor_refuses_last_hosts(tmp_path):
    """Bounded action: the roster never shrinks the pod below
    MXNET_CORDON_MIN_HOSTS — better a slow pod than no pod."""
    _, _, mgr, loop = make_loop(tmp_path)
    sup = TrainSupervisor(loop, host="0", expect_hosts=2, audit=False,
                          min_hosts=1)
    assert sup.consider_cordon("1", "straggler", 3) is True
    assert sup.reconfigure_requested
    sup2_loop = make_loop(tmp_path / "b")[3]
    sup2 = TrainSupervisor(sup2_loop, host="0", expect_hosts=1,
                           audit=False, min_hosts=1)
    assert sup2.consider_cordon("0", "sdc", 3) is False
    assert not sup2.roster.is_cordoned("0")
    assert not sup2.reconfigure_requested
    assert any(a["action"] == "cordon_refused" for a in sup2.actions)
    sup.close()
    sup2.close()


def test_cordon_floor_ignores_previous_incarnation_entries(tmp_path):
    """After an elastic shrink the relauncher already excluded the
    cordoned host from expect_hosts — the floor must not subtract the
    stale roster entry AGAIN and refuse a legal cordon forever."""
    _, _, mgr, loop = make_loop(tmp_path)
    roster = CordonRoster.beside(mgr.directory)
    roster.cordon("1", reason="straggler")       # previous incarnation
    sup = TrainSupervisor(loop, host="0", expect_hosts=2, audit=False,
                          min_hosts=1)           # world is {0, 2}
    assert sup.consider_cordon("2", "straggler", 9) is True
    assert sup.roster.is_cordoned("2")
    assert sup.reconfigure_requested
    sup.close()


def test_fresh_peer_cordon_of_another_host_drains_me_too(tmp_path):
    """Same-incarnation race on a shared suspect: a peer wins the
    roster write; MY supervisor observing the FRESH entry must still
    arm my drain — a pod can only shrink together (on a real pod the
    drain barrier would otherwise hang on me)."""
    _, _, mgr, loop = make_loop(tmp_path)
    sup = TrainSupervisor(loop, host="0", expect_hosts=3, audit=False)
    CordonRoster.beside(mgr.directory).cordon("2", reason="straggler")
    assert sup.consider_cordon("2", "straggler", 6) is True
    assert sup.reconfigure_requested
    sup.close()


def test_env_auto_attach(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TRAIN_REMEDIATION", "1")
    _, _, _, loop = make_loop(tmp_path)
    assert isinstance(loop.supervisor, TrainSupervisor)
    loop.supervisor.close()
    monkeypatch.setenv("MXNET_TRAIN_REMEDIATION", "0")
    _, _, _, loop2 = make_loop(tmp_path / "off")
    assert loop2.supervisor is None


def test_publish_failure_budget_cordons_self(tmp_path):
    _, _, mgr, loop = make_loop(tmp_path)
    sup = TrainSupervisor(loop, host="h7", expect_hosts=4, audit=False,
                          publish_failure_max=3)
    assert mgr.on_error == sup._on_publish_error
    sup._on_publish_error(OSError("disk"))
    sup._on_publish_error(OSError("disk"))
    assert not sup.roster.is_cordoned("h7")
    sup.on_publish_ok()              # a clean publish resets the streak
    assert sup.publish_failures == 0
    for _ in range(3):
        sup._on_publish_error(OSError("disk"))
    assert sup.roster.is_cordoned("h7")
    assert sup.roster.hosts()["h7"]["reason"] == "ckpt_publish"
    assert sup.reconfigure_requested
    sup.close()
    assert mgr.on_error is None      # close unwires the hook


# ---------------------------------------------------------------------------
# (4) SDC parity probes
# ---------------------------------------------------------------------------


def test_trainstep_probe_deterministic_and_mutation_free(tmp_path):
    net = make_net()
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                     {"learning_rate": 0.01}, guard=True)
    import jax
    x, y = batch(0)
    step(x, y)
    t0 = step.t
    before = [np.array(v) for v in jax.tree.leaves(step.state_dict())]
    a = step.probe(x, y)
    b = step.probe(x, y)
    assert a == b                    # bit-identical floats
    assert np.isfinite(a[0]) and np.isfinite(a[1])
    assert step.t == t0              # no step-counter advance
    after = jax.tree.leaves(step.state_dict())
    assert len(before) == len(after)
    for i, (bb, aa) in enumerate(zip(before, after)):
        np.testing.assert_array_equal(bb, np.asarray(aa),
                                      err_msg="leaf %d" % i)
    # the step still runs after probes (no donated buffer was consumed)
    step(x, y)
    # and a different seed changes the dropout-free loss only when the
    # model is stochastic; either way the call stays deterministic
    assert step.probe(x, y, seed=1) == step.probe(x, y, seed=1)


def test_sdc_probe_quorum_names_divergent_host():
    """Strict-majority quorum: the odd digest out is the suspect; a
    1-1 split names nobody."""
    probes = {}
    vals = {"0": 1.0, "1": 1.0, "2": 1.5}     # host 2 silently corrupt

    def exchange_for(host):
        def exchange(step, digest):
            probes[host] = digest
            return {h: SDCProbe.digest({"loss": v})
                    for h, v in vals.items()}
        return exchange

    suspects = {}
    for h in vals:
        p = SDCProbe(lambda h=h: {"loss": vals[h]}, every=4, host=h,
                     exchange=exchange_for(h))
        suspects[h] = p.run(8)
        assert p.probes == 1
    assert suspects == {"0": ["2"], "1": ["2"], "2": ["2"]}
    # unattributable 1-1 split: no suspect, never a guess
    p = SDCProbe(lambda: {"loss": 1.0}, every=4, host="0",
                 exchange=lambda s, d: {"0": "aaa", "1": "bbb"})
    assert p.run(4) == []
    # all-agree: no suspect
    p = SDCProbe(lambda: {"loss": 1.0}, every=4, host="0",
                 exchange=lambda s, d: {"0": d, "1": d, "2": d})
    assert p.run(4) == []


def test_sdc_chaos_digest_flip_names_armed_host(tmp_path, monkeypatch):
    """The drill's fault end-to-end in one process: MXNET_CHAOS_SDC_AT
    perturbs exactly the armed host's probe values, so the quorum names
    it. Also pins the flight event."""
    monkeypatch.setenv("MXNET_HOST_ID", "1")
    chaos.reset()
    chaos.configure(sdc_at=("1", 8))
    seen = {}

    def exchange(step, digest):
        seen["mine"] = digest
        clean = SDCProbe.digest({"loss": 2.0})
        return {"0": clean, "1": digest, "2": clean}

    p = SDCProbe(lambda: {"loss": 2.0}, every=4, host="1",
                 exchange=exchange)
    assert p.run(4) == []            # before the armed step: clean
    assert p.run(8) == ["1"]         # flipped digest -> named
    assert p.suspects == {"1": 1}
    assert seen["mine"] != SDCProbe.digest({"loss": 2.0})
    assert p.run(12) == []           # one-shot latch
    events = [e for e in telemetry.flight().events()
              if e.get("name") == "chaos.sdc_at"]
    assert events and events[-1]["host"] == "1"


def test_sdc_file_digest_exchange_quorum(tmp_path):
    """The emulated pod's exchange: atomic publishes + poll until the
    expected quorum assembles; stale steps never alias."""
    d = str(tmp_path / "sdc")
    a = _FileDigestExchange(d, "0", expect=2, timeout_s=5.0)
    b = _FileDigestExchange(d, "1", expect=2, timeout_s=5.0)
    import threading
    out = {}

    def run(name, ex, digest):
        out[name] = ex(4, digest)

    ta = threading.Thread(target=run, args=("a", a, "d0"))
    tb = threading.Thread(target=run, args=("b", b, "d1"))
    ta.start(); tb.start(); ta.join(); tb.join()
    assert out["a"] == {"0": "d0", "1": "d1"}
    assert out["b"] == {"0": "d0", "1": "d1"}
    # a later probe step sees only its own files (host 0 never
    # publishes step 8: the lone host times out with its own digest)
    c = _FileDigestExchange(d, "1", expect=2, timeout_s=0.2)
    assert c(8, "d8") == {"1": "d8"}


def test_probe_cadence_via_loop_and_supervisor(tmp_path):
    """`MXNET_SDC_PROBE_EVERY` cadence through the real step boundary:
    the supervisor captures the first batch, probes on cadence, and a
    quorum suspect is cordoned + drained."""
    _, step, mgr, loop = make_loop(tmp_path, save_every=2)
    # a canned exchange that makes host "9" diverge at step 4
    def exchange(step_no, digest):
        other = digest if step_no != 4 else "flipped"
        return {"me": digest, "8": digest, "9": other}

    sup = TrainSupervisor(loop, host="me", expect_hosts=3, audit=False,
                          probe_every=2, exchange=exchange)
    loop.step(*batch(0))             # captures the probe batch
    assert sup._probe_batch is not None
    loop.step(*batch(1))             # step 2: probe, all agree
    assert sup.probe is not None and sup.probe.probes == 1
    loop.step(*batch(2))             # step 3: no probe
    assert sup.probe.probes == 1
    with pytest.raises(Reconfigured):
        loop.step(*batch(3))         # step 4: probe -> suspect -> drain
    assert sup.probe.probes == 2
    assert sup.roster.is_cordoned("9")
    assert sup.roster.hosts()["9"]["reason"] == "sdc"
    # SDC quarantine: the suspect window's state was never published —
    # no step-4 cadence or drain save — and the relaunch restores the
    # last quorum-certified step (the clean probe at 2)
    assert sup.suppress_saves
    assert sup.probe.last_clean_step == 2
    assert mgr.all_steps() == [2]
    step_got, _ = mgr.restore_latest()
    assert step_got == 2
    acts = [a["action"] for a in sup.actions]
    assert "sdc_quarantine" in acts


# ---------------------------------------------------------------------------
# (5) background checkpoint auditor
# ---------------------------------------------------------------------------


def test_auditor_demotes_corrupt_step_before_restore(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=5, async_save=False)
    tree = {"w": np.arange(64, dtype=np.float32)}
    mgr.save(1, tree)
    mgr.save(2, tree)
    aud = CheckpointAuditor(mgr, interval_s=999)
    assert aud.audit_once() == []
    assert aud.audits >= 2
    # bit-rot the NEWEST published npz (same size: only sha catches it)
    p = tmp_path / "ckpt-2.npz"
    blob = bytearray(p.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    p.write_bytes(bytes(blob))
    assert aud.audit_once() == [2]
    # demoted: invisible to all_steps, files kept as evidence
    assert mgr.all_steps() == [1]
    assert any(n.endswith(".corrupt") for n in os.listdir(tmp_path))
    step, _ = mgr.restore_latest()   # never sees the rotted step
    assert step == 1


def test_auditor_never_demotes_incomplete_step(tmp_path):
    """A mid-publish sharded step (peer's shard or sidecar not yet
    there) is incomplete, not corrupt: the auditor leaves it alone."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel.mesh import build_mesh
    mesh = build_mesh({"dp": 2}, jax.devices()[:2])
    w = jax.device_put(np.arange(16, dtype=np.float32).reshape(8, 2),
                       NamedSharding(mesh, P("dp")))
    tree = {"w": w}
    # only host 0 of 2 published (host 1 still writing)
    CheckpointManager(str(tmp_path), keep=5, sharded=True,
                      process_index=0, process_count=2).save(
                          4, tree, block=True)
    mgr = CheckpointManager(str(tmp_path), keep=5, process_count=1)
    aud = CheckpointAuditor(mgr, interval_s=999)
    assert aud.audit_once() == []
    assert mgr.all_steps() == [4]    # still there, still incomplete
    # now corrupt host 0's EXISTING shard: that IS corruption
    shard = tmp_path / "ckpt-4.shard0of2.npz"
    blob = bytearray(shard.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    shard.write_bytes(bytes(blob))
    assert aud.audit_once() == [4]
    assert mgr.all_steps() == []


def test_auditor_thread_runs_in_supervisor(tmp_path):
    _, _, mgr, loop = make_loop(tmp_path, save_every=2)
    sup = TrainSupervisor(loop, host="0", audit=True,
                          audit_interval_s=0.05)
    try:
        loop.step(*batch(0))
        loop.step(*batch(1))         # cadence save at step 2
        deadline = time.time() + 5.0
        while sup.auditor.audits == 0 and time.time() < deadline:
            time.sleep(0.05)
        assert sup.auditor.audits > 0
        assert sup.auditor.demoted == []
        assert loop.statusz()["remediation"]["audit"]["audits"] > 0
    finally:
        sup.close()
    assert sup.auditor._thread is None


# ---------------------------------------------------------------------------
# (6) elastic restore across a grown world, crossing a cordon
# ---------------------------------------------------------------------------


def test_elastic_restore_grown_world_honors_cordon(tmp_path):
    """A 4-host checkpoint with one cordoned host restores at 6 hosts
    (the cordoned host's SHARDS are still good — cordoning is about the
    future world, not the past bytes), the roster excludes the host
    from the new world, and a genuinely missing shard still refuses."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel.mesh import build_mesh
    mesh = build_mesh({"dp": 4}, jax.devices()[:4])
    w = jax.device_put(np.arange(32, dtype=np.float32).reshape(8, 4),
                       NamedSharding(mesh, P()))
    m = jax.device_put(np.arange(64, dtype=np.float32).reshape(16, 4),
                       NamedSharding(mesh, P("dp")))
    tree = {"w": w, "opt": (m, np.int64(7)), "t": np.int64(5)}
    for i in range(4):
        CheckpointManager(str(tmp_path), keep=5, sharded=True,
                          process_index=i, process_count=4).save(
                              5, tree, block=True)
    roster = CordonRoster.beside(str(tmp_path))
    roster.cordon("3", reason="sdc", step=5)
    # the grown world: 6 candidate hosts minus the cordoned one
    world = effective_hosts([str(i) for i in range(6)], roster)
    assert world == ["0", "1", "2", "4", "5"]
    # every member of the grown world restores the same global arrays
    for idx, label in enumerate(world):
        mgr = CheckpointManager(str(tmp_path), keep=5,
                                process_index=idx,
                                process_count=len(world))
        step, got = mgr.restore_latest()
        assert step == 5
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.asarray(w))
        np.testing.assert_array_equal(np.asarray(got["opt"][0]),
                                      np.asarray(m))
    # coverage-count refusal still fires on a genuinely missing shard
    os.remove(tmp_path / "ckpt-5.shard2of4.npz")
    with pytest.warns(UserWarning, match="incomplete|missing"):
        assert CheckpointManager(str(tmp_path), keep=5,
                                 process_count=6).restore_latest() \
            is None


# ---------------------------------------------------------------------------
# (7) chaos-coverage static check (tier-1)
# ---------------------------------------------------------------------------


def _chaos_fault_names():
    """Every fault name utils/chaos.py registers (the _*FAULTS tuples
    the env table and configure() are built from)."""
    src = pathlib.Path(REPO, "mxnet_tpu", "utils", "chaos.py")
    tree = ast.parse(src.read_text(), filename=str(src))
    names = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        targets = [t.id for t in node.targets
                   if isinstance(t, ast.Name)]
        if not any(t.endswith("FAULTS") and t.startswith("_")
                   for t in targets):
            continue
        assert isinstance(node.value, ast.Tuple), \
            "%s must stay a literal tuple for this scan" % targets
        for el in node.value.elts:
            assert isinstance(el, ast.Constant) and \
                isinstance(el.value, str)
            names.append(el.value)
    return names


def _chaos_exercise_population():
    """String literals + configure(...) keyword names across tests/
    and the drill tools — everything that can arm a fault."""
    files = sorted(pathlib.Path(REPO, "tests").glob("*.py")) \
        + [pathlib.Path(REPO, "tools", "chaos_train.py"),
           pathlib.Path(REPO, "tools", "chaos_serve.py")]
    population = set()
    for py in files:
        try:
            tree = ast.parse(py.read_text(), filename=str(py))
        except (OSError, SyntaxError):          # pragma: no cover
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) \
                    and isinstance(node.value, str):
                population.add(node.value)
            elif isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg:
                        population.add(kw.arg)
    return population


def test_every_chaos_fault_is_exercised():
    """ISSUE 15 satellite, the PR 2 cost-estimate-scan pattern: every
    fault utils/chaos.py can parse must be armed by at least one test
    or drill tool — via its MXNET_CHAOS_* env var or a configure()
    keyword — so a new fault cannot land untestable/untested."""
    names = _chaos_fault_names()
    assert len(names) >= 13, ("chaos fault scan broke (found %d: %s)"
                              % (len(names), names))
    population = _chaos_exercise_population()
    missing = [n for n in names
               if n not in population
               and ("MXNET_CHAOS_" + n.upper()) not in population]
    assert not missing, (
        "chaos faults with no test/drill coverage (arm them in a test "
        "or a tools/chaos_*.py drill): %s" % ", ".join(missing))


# ---------------------------------------------------------------------------
# relauncher ladder (tools/train_supervise.py, in-process via run= seam)
# ---------------------------------------------------------------------------


def _load_tool(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_supervise_ladder_budget_backoff_circuit(monkeypatch):
    ts = _load_tool("train_supervise")
    rcs = iter([1, 1, 1, 1])         # crash loop
    sleeps = []
    logs = []
    rc = ts.supervise([], restart_max=2, backoff=0.5, roster="",
                      run=lambda: next(rcs), sleep=sleeps.append,
                      log=logs.append)
    assert rc == 1                   # circuit open: child's code out
    assert sleeps == [0.5, 1.0]      # exponential backoff, 2 relaunches
    text = "\n".join(logs)
    assert "CIRCUIT OPEN" in text and "postmortem" in text


def test_supervise_ladder_drained_exits_are_free(tmp_path):
    ts = _load_tool("train_supervise")
    roster = CordonRoster(str(tmp_path / "cordon"))
    roster.cordon("5", reason="straggler")
    rcs = iter([ts.EXIT_PREEMPTED, ts.EXIT_RECONFIGURE, 0])
    sleeps = []
    logs = []
    rc = ts.supervise([], restart_max=0, backoff=0.5,
                      roster=str(tmp_path / "cordon"),
                      run=lambda: next(rcs), sleep=sleeps.append,
                      log=logs.append)
    assert rc == 0                   # zero budget, yet both drains free
    assert sleeps == []              # and no backoff for them
    assert any("'5'" in l for l in logs)   # roster printed on 84


def test_supervise_long_incarnation_refunds_budget_any_exit(monkeypatch):
    """The refund fires for ANY long incarnation, not only one that
    ends in a crash: a job healthy for hours that then preempts must
    not inherit a stale strike count into its next startup hiccup."""
    ts = _load_tool("train_supervise")
    # monotonic is read twice per incarnation (start, end); feed
    # durations: crash after 1s, preempt after 400s, crash 1s, done
    ticks = iter([0, 1, 10, 410, 420, 421, 430, 431])
    monkeypatch.setattr(time, "monotonic", lambda: next(ticks))
    rcs = iter([1, ts.EXIT_PREEMPTED, 1, 0])
    logs = []
    rc = ts.supervise([], restart_max=1, backoff=0.01, roster="",
                      reset_after=300.0, run=lambda: next(rcs),
                      sleep=lambda s: None, log=logs.append)
    assert rc == 0                   # without the refund: circuit, rc 1
    assert any("refunded" in l for l in logs)


def test_supervise_reads_roster_format(tmp_path):
    ts = _load_tool("train_supervise")
    roster = CordonRoster(str(tmp_path / "c"))
    roster.cordon("3", reason="sdc", step=7)
    got = ts.read_roster(str(tmp_path / "c"))
    assert got["3"]["reason"] == "sdc" and got["3"]["step"] == 7
    assert ts.read_roster(str(tmp_path / "missing")) == {}


# ---------------------------------------------------------------------------
# console rendering
# ---------------------------------------------------------------------------


def test_train_top_renders_remediation_block():
    tt = _load_tool("train_top")
    statusz = {
        "host": "0", "step": 41, "step_seconds": {"p50": 0.01},
        "remediation": {
            "cordoned": {"3": {"reason": "sdc", "step": 40}},
            "reconfigure": {"requested": True, "reason": "sdc:3"},
            "sdc": {"every": 8, "probes": 5, "suspects": {"3": 1},
                    "last": None},
            "audit": {"interval_s": 5.0, "audits": 12,
                      "demoted": [16]},
        },
    }
    frame = tt.render([("http://h0:9100", {"ok": True}, statusz)])
    assert "CORDONED 3(sdc)" in frame
    assert "RECONFIGURE pending" in frame
    assert "SUSPECT 3" in frame
    assert "DEMOTED steps [16]" in frame
    # and an empty remediation block renders nothing alarming
    frame2 = tt.render([("http://h0:9100", {"ok": True},
                         {"host": "0", "step": 1})])
    assert "CORDONED" not in frame2


def test_postmortem_alerts_include_remediation_events(tmp_path):
    pm = _load_tool("postmortem")
    dump = {"reason": "reconfigure", "host": "0", "pid": 1,
            "events": [
                {"t": 1.0, "kind": "event", "name": "train.sdc",
                 "host": "2", "quorum": True, "step": 8},
                {"t": 1.1, "kind": "event", "name": "train.cordon",
                 "host": "2", "reason": "sdc", "step": 8},
                {"t": 1.2, "kind": "event", "name": "train.reconfigure",
                 "reason": "sdc:2", "step": 8},
                {"t": 1.3, "kind": "fault", "name": "chaos.sdc_at",
                 "host": "2", "step": 8},
            ]}
    path = tmp_path / "flight-host0-pid1-0.reconfigure.json"
    path.write_text(json.dumps(dump))
    text = pm.render(pm.load_dumps([str(path)]))
    assert text.count("ALERT") >= 3
    assert "train.sdc" in text and "train.cordon" in text
    assert "FAULT" in text and "chaos.sdc_at" in text


# ---------------------------------------------------------------------------
# the supervised drill end-to-end (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_supervised_remediation_drill(tmp_path):
    """The ISSUE 15 acceptance drill: slow host cordoned + elastic N−1
    finish, SIGKILL auto-relaunch bit-identical within the budget, SDC
    digest flip names exactly the poisoned host, crash loop opens the
    circuit with a rendered postmortem — all flight-recorded."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("MXNET_CHAOS_")}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_train.py"),
         "--multihost", "--supervised", "--net", "mlp",
         "--steps", "12", "--save-every", "4",
         "--work-dir", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=1500)
    assert out.returncode == 0, (out.stdout[-4000:], out.stderr[-2000:])
    assert "leg A OK" in out.stdout
    assert "leg B OK" in out.stdout
    assert "leg C OK" in out.stdout
    assert "leg D OK" in out.stdout
    assert "CIRCUIT OPEN" in out.stdout
