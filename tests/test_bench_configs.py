"""bench.py contract: every config emits a JSON line in smoke mode and
the driver-parsed FINAL line is the resnet headline. The driver runs
bench.py unattended on real hardware each round — a silently broken
config would only surface there, so pin the contract in CI."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _bench_mod():
    sys.path.insert(0, REPO)
    import bench
    return bench


def test_check_line_rejects_sentinel_comparisons():
    """Fast-tier self-test of the emit-time guard: no emitted line may
    carry a numeric comparison field that wasn't computed from a
    measurement (r5 verdict weak #5). _run_configs routes every line
    through check_line, so these rules hold for real runs too."""
    bench = _bench_mod()
    # the retired sentinel: vs_baseline 0.0 on a smoke line
    with pytest.raises(ValueError):
        bench.check_line({"metric": "smoke", "value": 1.0,
                          "vs_baseline": 0.0})
    # a ratio without a measured value
    with pytest.raises(ValueError):
        bench.check_line({"metric": "m", "value": None,
                          "vs_baseline": 2.5})
    with pytest.raises(ValueError):
        bench.check_line({"metric": "m", "value": None, "mfu": 0.3,
                          "vs_baseline": None, "baseline_note": "x"})
    # null-without-explanation ambiguity
    with pytest.raises(ValueError):
        bench.check_line({"metric": "m", "value": 1.0,
                          "vs_baseline": None})
    # the r5 committed inconsistency: overlap_efficiency > 1
    with pytest.raises(ValueError):
        bench.check_line({"metric": "e2e", "value": 500.0,
                          "overlap_efficiency": 1.101})
    # shapes every real line now takes
    bench.check_line({"metric": "smoke_resnet18_train_img_per_sec",
                      "value": 120.0, "vs_baseline": None,
                      "baseline_note": "smoke config", "mfu": 0.01,
                      "flops_per_step": 1e9,
                      "flops_source": "analytic_estimate"})
    bench.check_line({"metric": "resnet50_train_img_per_sec",
                      "value": 2453.8, "vs_baseline": 22.5, "mfu": 0.277,
                      "hbm_roofline_pct": 0.95, "flops_per_step": 5.7e12,
                      "flops_source": "xla_cost_model"})
    bench.check_line({"metric": "e2e_train_io_img_per_sec", "value": 500.0,
                      "overlap_efficiency": 0.97})


def test_check_line_wired_into_run_configs():
    """The guard must run on the emit path, not just exist."""
    import inspect
    bench = _bench_mod()
    src = inspect.getsource(bench._run_configs)
    assert "check_line(" in src


def test_bytes_report_mode_parsing():
    sys.path.insert(0, REPO)
    from benchmarks.bytes_report import parse_mode
    assert parse_mode("none") == ("none", False)
    assert parse_mode("io") == ("io", False)
    assert parse_mode("fused") == ("none", True)
    assert parse_mode("io+fused") == ("io", True)
    assert parse_mode(" full+fused ") == ("full", True)
    with pytest.raises(ValueError):
        parse_mode("io+full")


@pytest.mark.slow
def test_bench_smoke_emits_every_config():
    env = dict(os.environ, BENCH_SMOKE="1", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                         capture_output=True, text=True, timeout=1200,
                         env=env, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    metrics = [l["metric"] for l in lines]
    # no config degraded into an error line
    errors = [m for m in metrics if m.endswith("_error")]
    assert not errors, (errors, lines)
    for want in ("infer", "int8_infer", "lstm", "transformer", "ssd",
                 "sparse", "serving", "io_pipeline"):
        assert any(want in m for m in metrics), (want, metrics)
    # the driver parses the LAST stdout JSON line as the result
    assert metrics[-1] == "smoke_resnet18_train_img_per_sec"
    assert all(l.get("value") is not None for l in lines), lines
