"""Failure-recovery subsystem tests (SURVEY §5.3 — the reference has manual
checkpoint-restart only; this suite proves async atomic checkpointing and
crash auto-resume producing bit-identical results to an uninterrupted run).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.utils.recovery import CheckpointManager

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def test_save_restore_round_trip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=False)
    tree = {"t": np.int64(7),
            "params": (np.arange(6).astype(np.float32),
                       np.ones((2, 3), np.float32)),
            "nested": {"a": [np.zeros(2), np.full(3, 5.0)]}}
    mgr.save(7, tree)
    step, out = mgr.restore_latest()
    assert step == 7
    assert isinstance(out["params"], tuple) and len(out["params"]) == 2
    np.testing.assert_array_equal(out["params"][0], tree["params"][0])
    assert isinstance(out["nested"]["a"], list)
    np.testing.assert_array_equal(out["nested"]["a"][1], np.full(3, 5.0))
    assert int(out["t"]) == 7
    # empty containers survive the round trip (a momentum-less optimizer
    # state is an empty tuple)
    mgr.save(8, {"empty_t": (), "empty_l": [], "empty_d": {},
                 "x": np.ones(1)})
    _, out2 = mgr.restore_latest()
    assert out2["empty_t"] == () and out2["empty_l"] == [] \
        and out2["empty_d"] == {}


def test_retention_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    for s in (1, 2, 3, 4):
        mgr.save(s, {"x": np.full(4, float(s))})
    assert mgr.all_steps() == [3, 4]
    assert mgr.latest_step() == 4
    _, out = mgr.restore_latest()
    assert out["x"][0] == 4.0


def test_async_save_publishes(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save(1, {"x": np.ones(128)})
    mgr.wait()
    assert mgr.all_steps() == [1]
    # no torn temp files remain
    assert not [f for f in os.listdir(tmp_path) if ".tmp" in f]


def test_torn_checkpoint_falls_back(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=5, async_save=False)
    mgr.save(10, {"x": np.ones(3)})
    (tmp_path / "ckpt-20.npz").write_bytes(b"this is not an npz")
    step, out = mgr.restore_latest()
    assert step == 10
    np.testing.assert_array_equal(out["x"], np.ones(3))


def test_crash_resume_matches_uninterrupted(tmp_path):
    """Kill training mid-run (hard exit), relaunch, auto-resume: the final
    parameters match an uninterrupted run exactly."""
    def run(ckpt_dir, crash_at=None):
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
        if crash_at is not None:
            env["MXTPU_CRASH_AT"] = str(crash_at)
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tests",
                                          "elastic_worker.py"), ckpt_dir],
            env=env, capture_output=True, text=True, timeout=300)

    clean = run(str(tmp_path / "clean"))
    assert clean.returncode == 0, clean.stderr[-1500:]
    crashed = run(str(tmp_path / "elastic"), crash_at=17)
    assert crashed.returncode == 17  # simulated preemption
    resumed = run(str(tmp_path / "elastic"))
    assert resumed.returncode == 0, resumed.stderr[-1500:]
    assert "resumed from step" in resumed.stdout
    final_clean = [l for l in clean.stdout.splitlines()
                   if l.startswith("FINAL")][0]
    final_resumed = [l for l in resumed.stdout.splitlines()
                     if l.startswith("FINAL")][0]
    assert final_clean == final_resumed, (final_clean, final_resumed)
