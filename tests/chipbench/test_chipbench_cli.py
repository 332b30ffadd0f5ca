"""The command itself: without a TPU it exits non-zero and prints no result
line; in a directory that holds only the benchmark it cannot run."""
import json
import os
import subprocess
import sys

from chipbench.harness import manifest


def run_py(args, cwd, env=None):
    env = dict(os.environ if env is None else env, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chipbench", "run.py")] + args,
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def result_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "correct" in obj:
            out.append(obj)
    return out


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    book = manifest.load()
    cell = book["workloads"][0]["name"]
    p = run_py(["--workload", cell, "--seed", str(2**31 + 3), "--seconds", "1",
                "--trace", "0"], manifest.ROOT)
    assert p.returncode != 0
    assert result_lines(p.stdout) == []
    assert "no TPU" in p.stderr


def test_an_unknown_cell_is_an_error_not_a_result():
    p = run_py(["--workload", "no_such_cell", "--seed", "1", "--seconds", "1",
                "--trace", "0"], manifest.ROOT)
    assert p.returncode != 0 and result_lines(p.stdout) == []


def test_the_benchmarks_files_alone_cannot_run(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under `paths`
    has no program to measure: non-zero, no result."""
    import shutil
    book = manifest.load()
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    for path in book["paths"]:
        shutil.copytree(os.path.join(manifest.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = run_py(["--workload", book["workloads"][0]["name"], "--seed", "1",
                "--seconds", "1", "--trace", "0"], str(tmp_path), env)
    assert p.returncode != 0 and result_lines(p.stdout) == []
