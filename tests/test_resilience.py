"""Fault-tolerant training runtime tests (parallel/resilient.py,
utils/chaos.py, recovery manifest hardening, resumable data cursor).

The load-bearing claims:
(1) step-exact resume — train-N ≡ train-k / kill / restore / train-(N−k)
    bit-for-bit on params, INCLUDING RNG-dependent layers (Dropout) and
    the data-iterator cursor;
(2) the bad-step guard protects params/optimizer state in-graph, and the
    skip/rollback/raise policies behave as documented;
(3) a preemption notice produces a published checkpoint and the distinct
    relaunch exit code;
(4) checkpoint integrity — manifest checksums detect corruption and
    restore falls back to the previous intact checkpoint;
(5) pod scale (ISSUE 6) — per-host SHARDED checkpoints reassemble
    bit-exactly (including across a DIFFERENT mesh shape / process
    count: elastic resume), an incomplete or corrupt shard set is
    refused as a whole, the ZeRO-1 sharded weight update is bit-equal
    to the unsharded oracle, and every PR 3 fault guarantee survives a
    simulated multi-device dp×tp mesh with sharded optimizer state.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon.data import DataLoader
from mxnet_tpu.gluon.data.sampler import RandomSampler
from mxnet_tpu.lr_scheduler import FactorScheduler, MultiFactorScheduler
from mxnet_tpu.parallel.resilient import (ResilientLoop, BadStepError,
                                          Preempted, EXIT_PREEMPTED)
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


def make_dense_net(seed=0):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, in_units=6, activation="relu"))
    net.add(gluon.nn.Dropout(0.3))
    net.add(gluon.nn.Dense(3, in_units=16))
    net.initialize(mx.init.Xavier())
    return net


def dense_batch(i):
    rng = np.random.RandomState(1000 + i)
    return (rng.randn(8, 6).astype(np.float32),
            rng.randint(0, 3, (8,)).astype(np.float32))


def params_of(net):
    return np.concatenate([p.data().asnumpy().ravel()
                           for p in net.collect_params().values()])


def dense_loop(ckpt_dir, policy="skip", save_every=4, **kw):
    net = make_dense_net()
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                     {"learning_rate": 0.01}, guard=True)
    mgr = CheckpointManager(str(ckpt_dir), keep=3)
    loop = ResilientLoop(step, mgr, save_every=save_every, policy=policy,
                         watch_preemption=False, verbose=False, **kw)
    return net, step, mgr, loop


# ---------------------------------------------------------------------------
# resumable data cursor
# ---------------------------------------------------------------------------


def test_seeded_random_sampler_deterministic_per_epoch():
    a = RandomSampler(10, seed=7)
    e0, e1 = list(a), list(a)
    assert sorted(e0) == list(range(10)) and e0 != e1  # reshuffles
    b = RandomSampler(10, seed=7)
    assert list(b) == e0 and list(b) == e1  # pure function of (seed, epoch)
    b.set_epoch(0)
    assert list(b) == e0  # rewind


def test_sampler_resume_contract():
    s = RandomSampler(8, seed=3)
    epoch0 = list(s)
    state = s.state_dict()
    assert state == {"epoch": 1, "seed": 3, "length": 8}
    epoch1 = list(s)
    t = RandomSampler(8, seed=3)
    t.load_state_dict(state)
    assert list(t) == epoch1 and epoch1 != epoch0
    with pytest.raises(ValueError):
        RandomSampler(8, seed=4).load_state_dict(state)  # seed mismatch
    with pytest.raises(ValueError):
        RandomSampler(8).load_state_dict(state)  # unseeded not resumable


def test_seedless_sampler_fails_at_first_save():
    data = [(np.zeros(2, np.float32), np.float32(i)) for i in range(8)]
    ld = DataLoader(data, batch_size=2, shuffle=True)  # no seed
    with pytest.raises(ValueError, match="not resumable"):
        ld.state_dict()  # loudly, at save time — not hours later


def test_lr_schedule_state_survives_rollback_wrapper(tmp_path):
    """After ResilientLoop wraps the schedule with its rollback LR scale,
    checkpoints must still capture the underlying scheduler's state."""
    chaos.configure(nan_step=5)
    net, step, mgr, loop = dense_loop(tmp_path, policy="rollback",
                                      save_every=2, lr_shrink=0.5)
    loop.rollback_after = 1
    step.set_lr_schedule(FactorScheduler(step=3, factor=0.5, base_lr=0.02))
    n = 0
    while loop.t < 8 and n < 30:
        n += 1
        loop.step(*dense_batch(loop.t))
    assert loop.rollbacks == 1
    state = step.state_dict()
    assert "lr_sched" in state  # the wrapper did not hide the scheduler
    sd = json.loads(bytes(bytearray(
        np.asarray(state["lr_sched"]).astype(np.uint8))).decode())
    assert "base_lr" in sd and "count" in sd


def test_sampler_length_mismatch_raises():
    s = RandomSampler(50, seed=7)
    list(s)
    state = s.state_dict()
    grown = RandomSampler(60, seed=7)
    with pytest.raises(ValueError, match="length mismatch"):
        grown.load_state_dict(state)


def test_custom_batch_sampler_not_resumable_fails_at_save():
    class Custom:  # no state_dict: iterable of index lists only
        def __iter__(self):
            return iter([[0, 1], [2, 3]])

        def __len__(self):
            return 2

    data = [(np.zeros(2, np.float32), np.float32(i)) for i in range(4)]
    ld = DataLoader(data, batch_sampler=Custom())
    assert len(list(ld)) == 2          # iteration itself works
    with pytest.raises(ValueError, match="not resumable"):
        ld.state_dict()                # resumability fails LOUDLY


def _loader_ids(batches):
    return [int(b[1].asnumpy()[0]) for b in batches]


def _make_loader(n=24, batch_size=4, seed=11, num_workers=0):
    # dataset of (features, id): the id column tracks exactly which
    # samples a resumed loader yields
    data = [(np.full(3, i, np.float32), np.float32(i)) for i in range(n)]
    return DataLoader(data, batch_size=batch_size, shuffle=True, seed=seed,
                      num_workers=num_workers)


@pytest.mark.parametrize("num_workers", [0, 2])
def test_dataloader_cursor_resume_mid_epoch(num_workers):
    clean = _make_loader(num_workers=num_workers)
    want = [b for b in clean] + [b for b in clean]       # 2 epochs
    want_ids = [int(x) for b in want for x in b[1].asnumpy()]

    first = _make_loader(num_workers=num_workers)
    got = []
    it = iter(first)
    for _ in range(4):                                    # die mid-epoch 0
        got.append(next(it))
    state = first.state_dict()
    assert state["epoch"] == 0 and state["batch"] == 4

    resumed = _make_loader(num_workers=num_workers)       # fresh process
    resumed.load_state_dict(json.loads(json.dumps(state)))  # serializable
    got += list(resumed)                                  # rest of epoch 0
    got += list(resumed)                                  # epoch 1
    got_ids = [int(x) for b in got for x in b[1].asnumpy()]
    assert got_ids == want_ids


def test_dataloader_cursor_counts_yields_not_prefetch():
    ld = _make_loader(num_workers=2)
    it = iter(ld)
    next(it), next(it)
    # workers prefetch ahead, but the cursor counts delivered batches
    assert ld.state_dict()["batch"] == 2


def test_dataloader_cursor_with_device_prefetch():
    # the device-prefetch window pulls ahead of the consumer; the cursor
    # must still count only delivered batches or a resume drops data
    data = [(np.full(3, i, np.float32), np.float32(i)) for i in range(24)]
    ld = DataLoader(data, batch_size=4, shuffle=True, seed=11,
                    device_prefetch=2)
    it = iter(ld)
    next(it), next(it), next(it)
    state = ld.state_dict()
    assert state["batch"] == 3
    resumed = DataLoader(data, batch_size=4, shuffle=True, seed=11,
                         device_prefetch=2)
    resumed.load_state_dict(state)
    rest = [int(b[1].asnumpy()[0]) for b in resumed]
    clean = DataLoader(data, batch_size=4, shuffle=True, seed=11)
    want = [int(b[1].asnumpy()[0]) for b in clean][3:]
    assert rest == want


def test_dataloader_rollover_mid_pass_resume():
    """last_batch='rollover' carries a partial batch into the next pass;
    a mid-pass resume must replay with the SAME starting carry or every
    batch boundary shifts."""
    def build():
        data = [(np.full(2, i, np.float32), np.float32(i))
                for i in range(10)]
        from mxnet_tpu.gluon.data.sampler import BatchSampler
        sampler = RandomSampler(10, seed=4)
        return DataLoader(data, batch_sampler=BatchSampler(
            sampler, 4, last_batch="rollover"))

    clean = build()
    want = [[int(v) for v in b[1].asnumpy()] for b in clean]  # epoch 0
    want += [[int(v) for v in b[1].asnumpy()] for b in clean]  # epoch 1
    assert any(len(b) == 4 and len(set(b)) == 4 for b in want)

    first = build()
    got = [[int(v) for v in b[1].asnumpy()] for b in first]    # epoch 0
    it = iter(first)
    got.append([int(v) for v in next(it)[1].asnumpy()])        # 1 batch of
    state = first.state_dict()                                 # epoch 1

    resumed = build()
    resumed.load_state_dict(json.loads(json.dumps(state)))
    got += [[int(v) for v in b[1].asnumpy()] for b in resumed]
    assert got == want


def test_lr_scheduler_state_roundtrip():
    s = FactorScheduler(step=5, factor=0.5, base_lr=1.0)
    for t in range(1, 18):
        s(t)
    state = s.state_dict()
    fresh = FactorScheduler(step=5, factor=0.5, base_lr=1.0)
    fresh.load_state_dict(json.loads(json.dumps(state)))
    assert [fresh(t) for t in range(18, 40)] == [s(t) for t in range(18, 40)]

    m = MultiFactorScheduler(step=[4, 9], factor=0.1, base_lr=1.0)
    for t in range(1, 12):
        m(t)
    m2 = MultiFactorScheduler(step=[4, 9], factor=0.1, base_lr=1.0)
    m2.load_state_dict(m.state_dict())
    assert m2(15) == m(15)


# ---------------------------------------------------------------------------
# retry helper + downloads
# ---------------------------------------------------------------------------


def test_retry_succeeds_after_transients():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert retry(flaky, attempts=5, backoff=0.0, jitter=0.0) == "ok"
    assert len(calls) == 3


def test_retry_exhausts_and_raises():
    def always():
        raise OSError("down")

    with pytest.raises(OSError):
        retry(always, attempts=3, backoff=0.0, jitter=0.0)


def test_retry_nonretryable_propagates_immediately():
    calls = []

    def boom():
        calls.append(1)
        raise KeyError("not transient")

    with pytest.raises(KeyError):
        retry(boom, attempts=5, backoff=0.0, retry_on=OSError)
    assert len(calls) == 1


def test_download_file_url_and_sha1(tmp_path):
    import hashlib
    from mxnet_tpu.gluon.utils import download
    src = tmp_path / "weights.params"
    src.write_bytes(b"pretend-params")
    sha = hashlib.sha1(b"pretend-params").hexdigest()
    out = download("file://" + str(src), path=str(tmp_path / "out.params"),
                   sha1_hash=sha)
    assert open(out, "rb").read() == b"pretend-params"
    with pytest.raises(IOError):
        download("file://" + str(tmp_path / "missing.params"),
                 path=str(tmp_path / "nope.params"), retries=2)


def test_model_store_fetches_from_repo_url(tmp_path, monkeypatch):
    from mxnet_tpu.gluon.model_zoo import model_store
    repo = tmp_path / "repo"
    repo.mkdir()
    (repo / "tinymodel.params").write_bytes(b"zoo-bytes")
    monkeypatch.setenv("MXNET_GLUON_REPO", "file://" + str(repo))
    root = tmp_path / "cache"
    path = model_store.get_model_file("tinymodel", root=str(root))
    assert open(path, "rb").read() == b"zoo-bytes"
    assert str(root) in path


# ---------------------------------------------------------------------------
# checkpoint integrity: manifest + fallback
# ---------------------------------------------------------------------------


def test_manifest_published_and_valid(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr.save(5, {"w": np.arange(4, dtype=np.float32)})
    manifest = json.load(open(tmp_path / "ckpt-5.manifest.json"))
    assert manifest["step"] == 5 and manifest["file"] == "ckpt-5.npz"
    assert manifest["size"] == os.path.getsize(tmp_path / "ckpt-5.npz")
    assert manifest["arrays"] == ["w"]
    step, tree = mgr.restore_latest()
    assert step == 5
    np.testing.assert_array_equal(tree["w"], np.arange(4, dtype=np.float32))


def test_corrupt_manifest_falls_back(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=5, async_save=False)
    mgr.save(10, {"x": np.ones(3)})
    mgr.save(20, {"x": np.full(3, 2.0)})
    # ckpt-20's npz is fine, but its manifest is garbage: treat the pair
    # as suspect and fall back
    (tmp_path / "ckpt-20.manifest.json").write_text("{not json")
    with pytest.warns(UserWarning):
        step, tree = mgr.restore_latest()
    assert step == 10
    np.testing.assert_array_equal(tree["x"], np.ones(3))


def test_checksum_mismatch_falls_back(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=5, async_save=False)
    mgr.save(1, {"x": np.ones(3)})
    mgr.save(2, {"x": np.full(3, 2.0)})
    # same-size bit flip: only the sha256 can catch it
    path = tmp_path / "ckpt-2.npz"
    blob = bytearray(path.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    path.write_bytes(bytes(blob))
    with pytest.warns(UserWarning):
        step, _ = mgr.restore_latest()
    assert step == 1


def test_missing_manifest_tolerated(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr.save(3, {"x": np.ones(2)})
    os.remove(tmp_path / "ckpt-3.manifest.json")  # pre-manifest checkpoint
    step, tree = mgr.restore_latest()
    assert step == 3


def test_chaos_kill_during_save_leaves_latest_intact(tmp_path):
    """In-process variant: the kill hook fires between the temp write and
    the publish — simulate by checking the corrupt-tmp path; the
    subprocess drill below proves the real os._exit case."""
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr.save(4, {"x": np.ones(2)})
    # a torn temp file from a killed save must not shadow the published one
    (tmp_path / "ckpt-8.npz.tmp-999").write_bytes(b"torn")
    step, _ = mgr.restore_latest()
    assert step == 4
    assert mgr.all_steps() == [4]


# ---------------------------------------------------------------------------
# bad-step guard + policies
# ---------------------------------------------------------------------------


def test_guard_transparent_when_finite(tmp_path):
    netA = make_dense_net()  # reseeds the global RNG stream
    sA = TrainStep(netA, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                   {"learning_rate": 0.01}, guard=True)
    for i in range(5):
        sA(*dense_batch(i))
    netB = make_dense_net()  # reseeds again: identical key stream
    sB = TrainStep(netB, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                   {"learning_rate": 0.01})
    for i in range(5):
        sB(*dense_batch(i))
    sA.sync_params()
    sB.sync_params()
    np.testing.assert_array_equal(params_of(netA), params_of(netB))
    assert bool(np.asarray(sA.last_step_ok))
    assert np.isfinite(float(np.asarray(sA.last_grad_norm)))


def test_bad_step_skip_keeps_state(tmp_path):
    chaos.configure(nan_step=3)
    net, step, mgr, loop = dense_loop(tmp_path, policy="skip",
                                      save_every=100)
    loop.step(*dense_batch(0))
    loop.step(*dense_batch(1))
    before = step.state_dict()            # state entering poisoned step 3
    loop.step(*dense_batch(2))            # the NaN step: update dropped
    assert loop.bad_steps == 1 and loop.consecutive_bad == 1
    after_bad = step.state_dict()
    # skip = drop the whole update: params AND optimizer state unchanged
    import jax
    for name in ("grad_vals", "nograd_vals", "opt_state"):
        for x, y in zip(jax.tree.leaves(before[name]),
                        jax.tree.leaves(after_bad[name])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    loop.step(*dense_batch(3))            # training continues
    assert loop.consecutive_bad == 0      # reset by the good step
    after_good = step.state_dict()
    assert all(np.isfinite(np.asarray(v)).all()
               for v in after_good["grad_vals"])
    assert not np.array_equal(np.asarray(before["grad_vals"][0]),
                              np.asarray(after_good["grad_vals"][0]))


def test_bad_step_rollback_bit_exact(tmp_path):
    """One-shot NaN + rollback rejoins the clean trajectory exactly: the
    guard drops the poisoned update, the loop restores the last
    checkpoint (params+RNG+step), and the replay is clean."""
    netC, stepC, _, loopC = dense_loop(tmp_path / "clean", policy="skip",
                                       save_every=4)
    while loopC.t < 12:
        loopC.step(*dense_batch(loopC.t))
    stepC.sync_params()
    want = params_of(netC)

    chaos.configure(nan_step=7)
    netR, stepR, _, loopR = dense_loop(tmp_path / "roll", policy="rollback",
                                       save_every=4)
    loopR.rollback_after = 1
    while loopR.t < 12:
        loopR.step(*dense_batch(loopR.t))
    stepR.sync_params()
    assert loopR.rollbacks == 1 and loopR.bad_steps == 1
    np.testing.assert_array_equal(want, params_of(netR))


def test_rollback_shrinks_lr(tmp_path):
    chaos.configure(nan_step=6)
    net, step, mgr, loop = dense_loop(tmp_path, policy="rollback",
                                      save_every=2, lr_shrink=0.5)
    loop.rollback_after = 1
    n = 0
    while loop.t < 10 and n < 30:
        n += 1
        loop.step(*dense_batch(loop.t))
    assert loop.rollbacks == 1
    assert loop._lr_scale == 0.5
    # the wrapper feeds the shrunk lr into the step
    assert step._lr_schedule(loop.t) == pytest.approx(0.01 * 0.5)
    # and the scale survives a relaunch via the checkpoint
    mgr.wait(_barrier=False)
    net2, step2, _, loop2 = dense_loop(tmp_path, policy="rollback",
                                       save_every=2, lr_shrink=0.5)
    assert loop2.restore() > 0
    assert loop2._lr_scale == 0.5


def test_bad_step_raise_policy(tmp_path):
    chaos.configure(nan_step=2)
    net, step, mgr, loop = dense_loop(tmp_path, policy="raise",
                                      save_every=100)
    loop.step(*dense_batch(0))
    with pytest.raises(BadStepError):
        loop.step(*dense_batch(1))


def test_policy_env_default(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_BAD_STEP_POLICY", "skip")
    net, step, mgr, loop = dense_loop(tmp_path, policy=None)
    assert loop.policy == "skip"
    with pytest.raises(ValueError):
        dense_loop(tmp_path, policy="explode")


def test_guarded_precompiled_step_required_for_policy(tmp_path):
    net = make_dense_net()
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1})
    step(*dense_batch(0))  # compiles WITHOUT the guard
    mgr = CheckpointManager(str(tmp_path), keep=2)
    with pytest.raises(mx.MXNetError):
        ResilientLoop(step, mgr, policy="skip", watch_preemption=False)


# ---------------------------------------------------------------------------
# preemption watcher
# ---------------------------------------------------------------------------


def test_preemption_checkpoint_and_exit_code(tmp_path):
    net = make_dense_net()
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                     {"learning_rate": 0.01}, guard=True)
    mgr = CheckpointManager(str(tmp_path), keep=2)
    loop = ResilientLoop(step, mgr, save_every=100, policy="skip",
                         watch_preemption=True, grace_secs=0, verbose=False)
    try:
        for i in range(3):
            loop.step(*dense_batch(i))
        loop.watcher.trigger()  # simulated SIGTERM between steps
        with pytest.raises(Preempted) as exc:
            loop.step(*dense_batch(3))
        assert exc.value.code == EXIT_PREEMPTED == 83
        # the notice is honored at the POST-step boundary: the batch in
        # hand trains first (data-cursor consistency), then the drain
        # checkpoint publishes at step 4
        assert mgr.latest_step() == 4
    finally:
        loop.watcher.uninstall()


def test_resilient_loop_batches_resume_with_loader(tmp_path):
    """DataLoader-driven resume: preempt mid-epoch, rebuild EVERYTHING
    from the checkpoint, and the combined consumed-batch stream + final
    params match an uninterrupted 2-epoch run bit-for-bit."""
    def build(ckpt):
        net = make_dense_net()
        step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                         {"learning_rate": 0.01}, guard=True)
        data = [(np.random.RandomState(i).randn(6).astype(np.float32),
                 np.float32(i % 3)) for i in range(24)]
        loader = DataLoader(data, batch_size=4, shuffle=True, seed=13)
        mgr = CheckpointManager(str(ckpt), keep=3)
        loop = ResilientLoop(step, mgr, loader=loader, save_every=2,
                             policy="skip", epochs=2,
                             watch_preemption=False, verbose=False)
        return net, step, loop

    netC, stepC, loopC = build(tmp_path / "clean")
    clean_ids = []
    for x, y in loopC.batches():
        clean_ids.append(np.asarray(x.asnumpy()).sum())
        loopC.step(x, y)
    loopC.finish()
    stepC.sync_params()
    want = params_of(netC)
    assert loopC.t == 12  # 6 batches x 2 epochs

    netA, stepA, loopA = build(tmp_path / "faulted")
    got_ids = []
    n = 0
    for x, y in loopA.batches():
        got_ids.append(np.asarray(x.asnumpy()).sum())
        loopA.step(x, y)
        n += 1
        if n == 8:  # die mid-epoch 1 (checkpoint cadence 2 ⇒ ckpt at 8)
            loopA._manager.wait(_barrier=False)
            break

    netB, stepB, loopB = build(tmp_path / "faulted")  # relaunch
    assert loopB.restore() == 8
    for x, y in loopB.batches():
        got_ids.append(np.asarray(x.asnumpy()).sum())
        loopB.step(x, y)
    loopB.finish()
    stepB.sync_params()
    assert got_ids == clean_ids
    np.testing.assert_array_equal(want, params_of(netB))


# ---------------------------------------------------------------------------
# bit-exact resume: LeNet + word-LM (acceptance criteria fixtures)
# ---------------------------------------------------------------------------


def _bit_exact_resume(make_step, make_batch, total, kill_at, save_every,
                      tmp_path):
    def train(ckpt, stop=None, resume=False, seed=0):
        mx.random.seed(seed)
        np.random.seed(seed)
        net, step = make_step()
        mgr = CheckpointManager(str(ckpt), keep=3)
        loop = ResilientLoop(step, mgr, save_every=save_every,
                             policy="skip", watch_preemption=False,
                             verbose=False)
        start = loop.restore() if resume else 0
        while loop.t < (stop or total):
            loop.step(*make_batch(loop.t))
        mgr.wait(_barrier=False)
        step.sync_params()
        return start, params_of(net), net

    _, want, _ = train(tmp_path / "clean")
    train(tmp_path / "int", stop=kill_at)                 # "crash"
    start, got, _ = train(tmp_path / "int", resume=True, seed=555)
    assert start == (kill_at // save_every) * save_every
    np.testing.assert_array_equal(want, got)


def test_bit_exact_resume_lenet(tmp_path):
    """Acceptance: LeNet (Dropout active), f32, fixed seed — params after
    k steps + crash + auto-resume + (N−k) steps == uninterrupted N."""
    from mxnet_tpu.models.lenet import LeNet

    def make_step():
        net = LeNet(num_classes=10, dropout=0.3)
        net.initialize(mx.init.Xavier())
        net(mx.nd.array(np.zeros((4, 1, 28, 28), np.float32)))
        return net, TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              "adam", {"learning_rate": 0.01}, guard=True)

    def make_batch(i):
        rng = np.random.RandomState(77 + i)
        return (rng.randn(4, 1, 28, 28).astype(np.float32),
                rng.randint(0, 10, (4,)).astype(np.float32))

    _bit_exact_resume(make_step, make_batch, total=6, kill_at=4,
                      save_every=2, tmp_path=tmp_path)


def test_bit_exact_resume_word_lm(tmp_path):
    """Acceptance: the word LM (LSTM + Dropout 0.4 on embeddings and
    outputs) resumes step-exactly, proving the RNG key chain restores
    the per-step dropout masks."""
    from mxnet_tpu.models.word_lm import RNNModel

    T, N, V = 6, 4, 30

    def make_step():
        net = RNNModel(mode="lstm", vocab_size=V, num_embed=8,
                       num_hidden=8, num_layers=1, dropout=0.4)
        net.initialize(mx.init.Xavier())
        net(mx.nd.array(np.zeros((T, N), np.int32)))
        return net, TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              "adam", {"learning_rate": 0.01}, guard=True)

    def make_batch(i):
        rng = np.random.RandomState(55 + i)
        x = rng.randint(0, V, (T, N)).astype(np.int32)
        y = rng.randint(0, V, (T * N,)).astype(np.float32)
        return x, y

    _bit_exact_resume(make_step, make_batch, total=6, kill_at=3,
                      save_every=2, tmp_path=tmp_path)


# ---------------------------------------------------------------------------
# subprocess drills (slow tier): real signals, real hard kills
# ---------------------------------------------------------------------------


def _run_chaos_worker(ckpt_dir, chaos_env=None, steps=16, save_every=4):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("MXNET_CHAOS_")}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    env.update(chaos_env or {})
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_train.py"),
         "--worker", "--net", "mlp", "--steps", str(steps),
         "--save-every", str(save_every), "--policy", "rollback",
         "--ckpt-dir", str(ckpt_dir)],
        env=env, capture_output=True, text=True, timeout=300)


def _final(proc):
    lines = [l for l in proc.stdout.splitlines() if l.startswith("FINAL")]
    return lines[-1] if lines else None


@pytest.mark.slow
def test_sigterm_preemption_subprocess(tmp_path):
    """A real SIGTERM mid-epoch: checkpoint at the boundary, exit 83,
    relaunch continues step-exactly to the clean run's final state."""
    clean = _run_chaos_worker(tmp_path / "clean")
    assert clean.returncode == 0, clean.stderr[-1500:]
    p1 = _run_chaos_worker(tmp_path / "pre",
                           {"MXNET_CHAOS_SIGTERM_AT": "6"})
    assert p1.returncode == EXIT_PREEMPTED, (p1.returncode,
                                             p1.stderr[-1500:])
    p2 = _run_chaos_worker(tmp_path / "pre")
    assert p2.returncode == 0, p2.stderr[-1500:]
    assert "resumed from step 6" in p2.stdout
    assert _final(p2) == _final(clean)


# ---------------------------------------------------------------------------
# pod scale: per-host sharded checkpoints (recovery layer)
# ---------------------------------------------------------------------------


def _dp_mesh(n):
    import jax
    from mxnet_tpu.parallel.mesh import build_mesh
    return build_mesh({"dp": n}, jax.devices()[:n])


def _mesh_tree(n=4):
    """Replicated param + dp-sharded optimizer moment + host scalars —
    the shape of a ZeRO-1 TrainStep's state."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _dp_mesh(n)
    w = jax.device_put(np.arange(32, dtype=np.float32).reshape(8, 4),
                       NamedSharding(mesh, P()))
    m = jax.device_put(np.arange(64, dtype=np.float32).reshape(16, 4),
                       NamedSharding(mesh, P("dp")))
    return {"w": w, "opt": (m, np.int64(7)), "t": np.int64(5)}


def _emulated_save(d, step, tree, hosts=2, block=True):
    """Every emulated host of a pod writes its own shard file."""
    for i in range(hosts):
        CheckpointManager(str(d), keep=5, sharded=True, process_index=i,
                          process_count=hosts).save(step, tree, block=block)


def test_sharded_ckpt_roundtrip_and_manifest(tmp_path):
    tree = _mesh_tree()
    _emulated_save(tmp_path / "pod", 5, tree)
    names = sorted(os.listdir(tmp_path / "pod"))
    assert names == ["ckpt-5.manifest.json",
                     "ckpt-5.shard0of2.manifest.json",
                     "ckpt-5.shard0of2.npz",
                     "ckpt-5.shard1of2.manifest.json",
                     "ckpt-5.shard1of2.npz"]
    g = json.load(open(tmp_path / "pod" / "ckpt-5.manifest.json"))
    assert g["format"] == "sharded" and g["process_count"] == 2
    assert g["mesh"]["axes"] == {"dp": 4}
    assert g["arrays"]["opt/__t__0"]["spec"] == "PartitionSpec('dp',)"
    assert g["arrays"]["opt/__t__0"]["shards"] == 4
    assert g["files"] == ["ckpt-5.shard0of2.npz", "ckpt-5.shard1of2.npz"]
    for i in range(2):
        m = json.load(open(tmp_path / "pod" /
                           ("ckpt-5.shard%dof2.manifest.json" % i)))
        assert m["sha256"] and m["size"] == os.path.getsize(
            tmp_path / "pod" / ("ckpt-5.shard%dof2.npz" % i))
    # a reader with ANY process shape reassembles the global arrays
    step, got = CheckpointManager(str(tmp_path / "pod"),
                                  process_count=1).restore_latest()
    assert step == 5
    np.testing.assert_array_equal(np.asarray(got["w"]),
                                  np.asarray(tree["w"]))
    np.testing.assert_array_equal(np.asarray(got["opt"][0]),
                                  np.asarray(tree["opt"][0]))
    assert int(got["opt"][1]) == 7 and int(got["t"]) == 5
    # bytes-per-host: each shard holds a strict subset of the state
    single = CheckpointManager(str(tmp_path / "single"), keep=5,
                               sharded=False)
    single.save(5, tree, block=True)
    full = os.path.getsize(tmp_path / "single" / "ckpt-5.npz")
    for i in range(2):
        part = os.path.getsize(tmp_path / "pod" /
                               ("ckpt-5.shard%dof2.npz" % i))
        assert 0 < part < full


def test_sharded_ckpt_incomplete_step_refused(tmp_path):
    """A host that died mid-save leaves the step without its shard file:
    the WHOLE step must be refused (the satellite fix — previously each
    host could independently pick a different 'latest intact' step)."""
    tree = _mesh_tree()
    _emulated_save(tmp_path, 4, tree)
    # only host 0 reaches step 8 (host 1 was SIGKILLed): global manifest
    # published, host 1's shard missing
    CheckpointManager(str(tmp_path), keep=5, sharded=True, process_index=0,
                      process_count=2).save(8, tree, block=True)
    with pytest.warns(UserWarning, match="incomplete"):
        step, _ = CheckpointManager(str(tmp_path),
                                    process_count=1).restore_latest()
    assert step == 4


def test_sharded_ckpt_corrupt_shard_falls_back(tmp_path):
    tree = _mesh_tree()
    _emulated_save(tmp_path, 1, tree)
    _emulated_save(tmp_path, 2, tree)
    # same-size bit flip inside ONE host's shard: only the sha256 in its
    # sidecar manifest can catch it, and it must fail the whole step
    path = tmp_path / "ckpt-2.shard1of2.npz"
    blob = bytearray(path.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    path.write_bytes(bytes(blob))
    with pytest.warns(UserWarning):
        step, _ = CheckpointManager(str(tmp_path),
                                    process_count=1).restore_latest()
    assert step == 1


def test_sharded_auto_mode_stays_single_writer_in_process(tmp_path):
    """Mode auto-detection: fully-addressable trees (single-process
    runs, host-side numpy state) keep the verbatim single-writer path —
    no shard files, one npz."""
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr.save(3, _mesh_tree())  # sharded over devices but one process
    names = sorted(os.listdir(tmp_path))
    assert names == ["ckpt-3.manifest.json", "ckpt-3.npz"]
    step, got = mgr.restore_latest()
    assert step == 3
    np.testing.assert_array_equal(np.asarray(got["opt"][0]),
                                  np.arange(64, dtype=np.float32)
                                  .reshape(16, 4))


def test_publish_retry_survives_transient_io(tmp_path, monkeypatch):
    """Satellite: a transient NFS/GCS-fuse hiccup on the publish path is
    retried with bounded backoff instead of killing the save."""
    calls = {"n": 0}
    real = os.replace

    def flaky(a, b):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("transient fs hiccup")
        return real(a, b)

    monkeypatch.setattr(os, "replace", flaky)
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    mgr.save(3, {"x": np.ones(4, np.float32)})  # must not raise
    assert calls["n"] >= 2
    step, got = mgr.restore_latest()
    assert step == 3
    np.testing.assert_array_equal(got["x"], np.ones(4, np.float32))


def test_publish_retry_exhaustion_surfaces_on_wait(tmp_path, monkeypatch):
    """A save that exhausts its retries must surface on the next
    save()/wait() — never silently drop a step."""
    mgr = CheckpointManager(str(tmp_path), keep=3)

    def down(a, b):
        raise OSError("filesystem down")

    monkeypatch.setattr(os, "replace", down)
    mgr.save(3, {"x": np.ones(4, np.float32)})
    with pytest.raises(OSError):
        mgr.wait(_barrier=False)


# ---------------------------------------------------------------------------
# pod scale: ZeRO-1 sharded weight update (parity oracle) + elastic resume
# ---------------------------------------------------------------------------


def _mlp_step(dp, sharded, seed=3, lr=0.05):
    import jax
    mx.random.seed(seed)
    np.random.seed(seed)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(8, in_units=16, activation="relu"))
        net.add(gluon.nn.Dense(4, in_units=8))
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, 16)))
    return net, TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          "adam", {"learning_rate": lr},
                          mesh=_dp_mesh(dp), data_axis="dp",
                          sharded_update=sharded, guard=True)


def test_sharded_update_bit_equal_to_unsharded_oracle():
    """Acceptance: the ZeRO-1 path (reduce-scatter grads, 1/N-shard
    optimizer update, all-gather params) produces BIT-EQUAL params to
    the unsharded step after K steps on a simulated multi-device CPU
    mesh — the constraints re-place values, never change them."""
    from jax.sharding import PartitionSpec as P
    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (32, 16)).astype(np.float32)
    Y = rng.randint(0, 4, (32,)).astype(np.int32)
    netA, ref = _mlp_step(8, sharded=False)
    netB, zer = _mlp_step(8, sharded=True)
    for _ in range(6):
        ref(X, Y)
        zer(X, Y)
    ref.sync_params()
    zer.sync_params()
    pa = sorted((k.split("_", 1)[-1], v.data().asnumpy())
                for k, v in netA.collect_params().items())
    pb = sorted((k.split("_", 1)[-1], v.data().asnumpy())
                for k, v in netB.collect_params().items())
    for (ka, va), (kb, vb) in zip(pa, pb):
        np.testing.assert_array_equal(va, vb, err_msg=ka)
    # and the adam moments really live at 1/N per dp slice
    specs = [s.sharding.spec for st in zer._opt_state for s in st
             if hasattr(s, "sharding") and s.ndim > 0]
    assert any(spec == P("dp") or spec == P(None, "dp") for spec in specs)


def test_elastic_restore_reshards_bit_exact(tmp_path):
    """Elastic resume at the state level: a sharded checkpoint written
    under dp=4 restores onto a dp=2 mesh with every logical array
    bit-identical (reassemble global -> re-place under the live
    shardings)."""
    import jax
    netA, stepA = _mlp_step(4, sharded=True)
    mgrA = CheckpointManager(str(tmp_path), keep=3, sharded=True)
    loopA = ResilientLoop(stepA, mgrA, save_every=4, policy="skip",
                          watch_preemption=False, verbose=False)
    while loopA.t < 4:
        loopA.step(*dense_batch_16(loopA.t))
    mgrA.wait(_barrier=False)
    want = stepA.state_dict()

    netB, stepB = _mlp_step(2, sharded=True, seed=999)  # different init
    mgrB = CheckpointManager(str(tmp_path), keep=3, sharded=True)
    loopB = ResilientLoop(stepB, mgrB, save_every=4, policy="skip",
                          watch_preemption=False, verbose=False)
    assert loopB.restore() == 4
    got = stepB.state_dict()
    assert int(got["t"]) == int(want["t"]) == 4
    for name in ("grad_vals", "nograd_vals", "opt_state"):
        for a, b in zip(jax.tree.leaves(want[name]),
                        jax.tree.leaves(got[name])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(want["rng_key"], got["rng_key"])


def dense_batch_16(i):
    rng = np.random.RandomState(2000 + i)
    return (rng.randn(8, 16).astype(np.float32),
            rng.randint(0, 4, (8,)).astype(np.float32))


def test_elastic_dp_resize_policy_with_loader(tmp_path):
    """A dp resize with a DataLoader cursor attached is only
    loss-curve-preserving if the driver keeps the GLOBAL batch size
    constant — the default policy refuses, 'rescale' accepts the
    documented contract with a warning, same-dp resumes stay silent."""
    def build(dp, elastic=None):
        net, step = _mlp_step(dp, sharded=True)
        data = [(np.random.RandomState(i).randn(16).astype(np.float32),
                 np.float32(i % 4)) for i in range(16)]
        loader = DataLoader(data, batch_size=8, shuffle=True, seed=5)
        mgr = CheckpointManager(str(tmp_path), keep=3, sharded=True)
        kw = {"elastic_dp": elastic} if elastic else {}
        return ResilientLoop(step, mgr, loader=loader, save_every=2,
                             policy="skip", watch_preemption=False,
                             verbose=False, **kw)

    a = build(4)
    for x, y in a.batches():
        a.step(x, y)
        if a.t == 2:
            break
    a._manager.wait(_barrier=False)
    with pytest.raises(mx.MXNetError, match="dp=4.*dp=2"):
        build(2).restore()
    with pytest.warns(UserWarning, match="elastic resume"):
        assert build(2, elastic="rescale").restore() == 2
    assert build(4).restore() == 2  # same shape: no policy involved


# ---------------------------------------------------------------------------
# pod scale: the PR 3 fault guarantees under a dp x tp mesh with
# sharded optimizer state (simulated 4-device mesh)
# ---------------------------------------------------------------------------


def mesh_loop(ckpt_dir, policy="skip", save_every=4, dp=2, tp=2,
              watch_preemption=False, **kw):
    """Dense net (Dropout active) on a dp×tp mesh: one weight
    tensor-parallel, ZeRO-1 sharded update for the rest, guard compiled,
    per-host-sharded checkpoint manager (single emulated host)."""
    from jax.sharding import PartitionSpec as P
    mx.random.seed(0)
    np.random.seed(0)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(16, in_units=6, activation="relu"))
        net.add(gluon.nn.Dropout(0.3))
        net.add(gluon.nn.Dense(3, in_units=16))
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, 6)))
    import jax
    from mxnet_tpu.parallel.mesh import build_mesh
    mesh = build_mesh({"dp": dp, "tp": tp}, jax.devices()[:dp * tp])
    sh = {name: P("tp", None) for name, p in net.collect_params().items()
          if p.shape == (16, 6)}
    assert sh, "tensor-parallel target param not found"
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                     {"learning_rate": 0.01}, mesh=mesh, data_axis="dp",
                     param_shardings=sh, sharded_update=True, guard=True)
    mgr = CheckpointManager(str(ckpt_dir), keep=5, sharded=True)
    loop = ResilientLoop(step, mgr, save_every=save_every, policy=policy,
                         watch_preemption=watch_preemption, verbose=False,
                         **kw)
    return net, step, mgr, loop


def _run_mesh(ckpt_dir, total, policy="skip", **kw):
    net, step, mgr, loop = mesh_loop(ckpt_dir, policy=policy, **kw)
    while loop.t < total:
        loop.step(*dense_batch(loop.t))
    mgr.wait(_barrier=False)
    step.sync_params()
    return net, step, mgr, loop


def test_mesh_bit_exact_resume_sharded_ckpt(tmp_path):
    """Step-exact resume survives sharding: crash at 6, relaunch onto
    the same mesh, final params bit-equal the undisturbed run — and the
    checkpoints on disk really are the sharded format."""
    netC, *_ = _run_mesh(tmp_path / "clean", 10)
    want = params_of(netC)
    _run_mesh(tmp_path / "int", 6)
    assert any(_n.startswith("ckpt-4.shard") for _n in
               os.listdir(tmp_path / "int"))
    netR, stepR, mgrR, loopR = mesh_loop(tmp_path / "int")
    assert loopR.restore() == 4
    while loopR.t < 10:
        loopR.step(*dense_batch(loopR.t))
    stepR.sync_params()
    np.testing.assert_array_equal(want, params_of(netR))


def test_mesh_corrupt_ckpt_falls_back_and_rejoins(tmp_path):
    """chaos corrupt-ckpt under the mesh: the truncated shard fails its
    sidecar sha256, restore falls back a full cadence, and the replayed
    trajectory still rejoins the clean run bit-for-bit."""
    netC, *_ = _run_mesh(tmp_path / "clean", 12)
    want = params_of(netC)
    chaos.configure(corrupt_ckpt=8)
    _run_mesh(tmp_path / "f", 8)          # dies right after the bad save
    chaos.reset()
    netR, stepR, mgrR, loopR = mesh_loop(tmp_path / "f")
    with pytest.warns(UserWarning):
        assert loopR.restore() == 4       # 8 is corrupt -> previous step
    while loopR.t < 12:
        loopR.step(*dense_batch(loopR.t))
    stepR.sync_params()
    np.testing.assert_array_equal(want, params_of(netR))


def test_mesh_nan_rollback_restores_sharded_state(tmp_path):
    """Bad-step rollback under the mesh: the in-graph guard drops the
    poisoned update (params AND the dp-sharded optimizer shards), the
    rollback restores the sharded checkpoint bit-exactly, and the
    trajectory rejoins the clean run."""
    from jax.sharding import PartitionSpec as P
    netC, stepC, *_ = _run_mesh(tmp_path / "clean", 12)
    want = params_of(netC)
    chaos.configure(nan_step=7)
    netR, stepR, mgrR, loopR = mesh_loop(tmp_path / "roll",
                                         policy="rollback")
    loopR.rollback_after = 1
    while loopR.t < 12:
        loopR.step(*dense_batch(loopR.t))
    stepR.sync_params()
    assert loopR.rollbacks == 1 and loopR.bad_steps == 1
    np.testing.assert_array_equal(want, params_of(netR))
    specs = [s.sharding.spec for st in stepR._opt_state for s in st
             if hasattr(s, "sharding") and s.ndim > 0]
    assert any("dp" in str(spec) for spec in specs)


def test_mesh_preemption_drains_sharded_ckpt(tmp_path):
    """SIGTERM-at-step under the mesh: the drain publishes a SHARDED
    checkpoint at the boundary, exits with the relaunch code, and the
    relaunch continues bit-exactly."""
    netC, *_ = _run_mesh(tmp_path / "clean", 8, save_every=100)
    want = params_of(netC)
    net, step, mgr, loop = mesh_loop(tmp_path / "pre", save_every=100,
                                     watch_preemption=True, grace_secs=0)
    try:
        for i in range(3):
            loop.step(*dense_batch(loop.t))
        loop.watcher.trigger()
        with pytest.raises(Preempted) as exc:
            loop.step(*dense_batch(loop.t))
        assert exc.value.code == EXIT_PREEMPTED
        assert any(n.startswith("ckpt-4.shard") for n in
                   os.listdir(tmp_path / "pre"))
    finally:
        loop.watcher.uninstall()
    netR, stepR, mgrR, loopR = mesh_loop(tmp_path / "pre", save_every=100)
    assert loopR.restore() == 4
    while loopR.t < 8:
        loopR.step(*dense_batch(loopR.t))
    stepR.sync_params()
    np.testing.assert_array_equal(want, params_of(netR))


def test_mesh_torn_shard_tmp_never_shadows(tmp_path):
    """kill-during-save under sharding (fast-tier variant): a torn temp
    shard from a killed writer must not shadow the published step; the
    subprocess SIGKILL case is the slow-tier multihost drill."""
    _run_mesh(tmp_path, 4)
    (tmp_path / "ckpt-8.shard0of1.npz.tmp-999").write_bytes(b"torn")
    mgr = CheckpointManager(str(tmp_path), process_count=1)
    step, _ = mgr.restore_latest()
    assert step == 4
    assert mgr.all_steps() == [4]


@pytest.mark.slow
def test_multihost_chaos_drill(tmp_path):
    """The pod drill end-to-end: 2 emulated hosts x 4 virtual devices,
    SIGKILL one host mid-run (no drain), preempt the survivor, relaunch
    same-shape (bit-identical finish) then elastic onto 1 host x 2
    devices (loss-curve-identical finish)."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("MXNET_CHAOS_")}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "chaos_train.py"),
         "--multihost", "--net", "mlp", "--steps", "12",
         "--save-every", "4", "--work-dir", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-2000:])
    assert "same-shape relaunch: bit-identical" in out.stdout
    assert "loss-curve-identical" in out.stdout


@pytest.mark.slow
def test_kill_during_save_subprocess(tmp_path):
    """A hard kill in the middle of the checkpoint write: the torn temp
    file must not shadow the last published checkpoint, and the relaunch
    still reaches the clean final state."""
    clean = _run_chaos_worker(tmp_path / "clean")
    assert clean.returncode == 0, clean.stderr[-1500:]
    p1 = _run_chaos_worker(tmp_path / "kill",
                           {"MXNET_CHAOS_KILL_SAVE": "8"})
    assert p1.returncode == 43, (p1.returncode, p1.stderr[-1500:])
    mgr = CheckpointManager(str(tmp_path / "kill"), keep=3)
    step, _ = mgr.restore_latest()  # intact despite the mid-save kill
    assert step == 4
    p2 = _run_chaos_worker(tmp_path / "kill")
    assert p2.returncode == 0, p2.stderr[-1500:]
    assert "resumed from step 4" in p2.stdout
    assert _final(p2) == _final(clean)
