"""benchmarks/scaling_report.py — the allreduce-scaling evidence
generator (BASELINE.md north-star #2): the dp train step's collective
traffic must be one batched gradient all-reduce, O(model size),
independent of device count."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.mark.slow
def test_scaling_report_collectives_invariant(tmp_path):
    md = str(tmp_path / "SCALING.md")
    env = dict(os.environ, SCALING_SIZES="8,16", SCALING_OUT=md)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "scaling_report.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    rows = [r for r in lines if "n_devices" in r]
    assert len(rows) == 2
    # the expert-parallel section also ran and found collectives
    moe = [r["moe"] for r in lines if "moe" in r]
    assert moe and moe[0]["collectives"], moe
    for r in rows:
        assert "all-reduce" in r["collectives"] or \
            "reduce-scatter" in r["collectives"]
        # one batched reduction, not per-parameter collectives
        assert r["total"]["count"] <= 2, r
        # volume O(model size): within 5% of the parameter bytes
        assert abs(r["total"]["bytes"] - r["model_bytes"]) < \
            0.05 * r["model_bytes"], r
    # invariant in N (the ring-allreduce property)
    assert rows[0]["total"]["bytes"] == rows[1]["total"]["bytes"]
    assert os.path.exists(md)
