"""Real multi-process distributed kvstore test.

Parity: the reference's nightly pattern — tests/nightly/dist_sync_kvstore.py
driven by tools/launch.py with N local workers
(`launch.py -n 3 --launcher local python dist_sync_kvstore.py`,
tests/nightly/test_all.sh). Here the launcher spawns real OS processes that
assemble a jax.distributed world and exercise dense / big-key chunked /
row_sparse / compressed / server-side-optimizer flows.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.mark.parametrize("nworker", [2, 3])
def test_dist_sync_kvstore_multiprocess(nworker):
    env = dict(os.environ)
    env.update({
        # small bound so the (1200, 7) key exercises chunked transport
        "MXNET_KVSTORE_BIGARRAY_BOUND": "4096",
        "PYTHONPATH": REPO,
        # 4 virtual devices per worker: the combined nightly-scale check
        # pushes per-device gradient lists through the local reduce
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    })
    # the launcher pins workers to pure-CPU jax (a chip belongs to one
    # process, so local workers cannot share the host's chips)
    cmd = [sys.executable, os.path.join(REPO, "tools", "launch.py"),
           "-n", str(nworker), "--launcher", "local", "--platform", "cpu",
           sys.executable, os.path.join(REPO, "tests",
                                        "dist_sync_kvstore.py")]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=420)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    for rank in range(nworker):
        assert "DIST_KVSTORE_OK rank=%d nworker=%d" % (rank, nworker) \
            in out.stdout, out.stdout[-2000:]


def test_dist_data_parallel_training():
    """Reference nightly dist_lenet pattern: 2-worker DP training converges
    with bit-identical parameters on every rank."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    cmd = [sys.executable, os.path.join(REPO, "tools", "launch.py"),
           "-n", "2", "--launcher", "local", "--platform", "cpu",
           sys.executable, os.path.join(REPO, "tests", "dist_lenet.py")]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=420)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "DIST_LENET_OK rank=0" in out.stdout
    assert "DIST_LENET_OK rank=1" in out.stdout


def test_launcher_cli_errors(capsys):
    from tools.launch import main
    with pytest.raises(SystemExit):
        main(["-n", "2"])  # no command
    with pytest.raises(SystemExit):
        # yarn is a documented disposition, not a silent no-op
        main(["-n", "2", "--launcher", "yarn", "python", "x.py"])
    # the disposition must explain itself, not just exit: the message
    # names the supported launchers and the DMLC_* escape hatch
    err = capsys.readouterr().err
    assert "yarn launcher is not supported on TPU deployments" in err
    assert "DMLC_" in err and "docs/PARITY.md" in err


_RANK_PROBE = ("import os;print('RANK %s of %s' % ("
               "os.environ['DMLC_WORKER_ID'], os.environ['DMLC_NUM_WORKER']),"
               "flush=True)")


def test_launcher_mpi_derives_ranks(tmp_path, capfd):
    """The mpi launcher's bootstrap must map the scheduler's rank env var
    onto DMLC_WORKER_ID. The stub mpirun runs each rank sequentially the
    way OpenMPI would, exporting OMPI_COMM_WORLD_RANK."""
    stub = tmp_path / "mpirun"
    stub.write_text(
        "#!/bin/bash\n"
        "# parse -n N, honor -x K=V exports, run command once per rank\n"
        "n=1; declare -a kv\n"
        "while [[ $# -gt 0 ]]; do\n"
        "  case $1 in\n"
        "    -n) n=$2; shift 2;;\n"
        "    --hostfile) shift 2;;\n"
        "    -x) kv+=(\"$2\"); shift 2;;\n"
        "    *) break;;\n"
        "  esac\n"
        "done\n"
        "for ((r=0; r<n; r++)); do\n"
        "  env \"${kv[@]}\" OMPI_COMM_WORLD_RANK=$r \"$@\" || exit $?\n"
        "done\n")
    stub.chmod(0o755)
    import tools.launch as launch
    old_path = os.environ["PATH"]
    os.environ["PATH"] = str(tmp_path) + os.pathsep + old_path
    try:
        rc = launch.main(["-n", "3", "--launcher", "mpi", "--platform",
                          "cpu", sys.executable, "-c", _RANK_PROBE])
    finally:
        os.environ["PATH"] = old_path
    out = capfd.readouterr().out
    assert rc == 0
    for r in range(3):
        assert "RANK %d of 3" % r in out, out


def test_launcher_sge_array_job(tmp_path, capfd):
    """The sge launcher submits a 1-N array job whose tasks derive
    DMLC_WORKER_ID from SGE_TASK_ID; the stub qsub executes every task."""
    stub = tmp_path / "qsub"
    stub.write_text(
        "#!/bin/bash\n"
        "while [[ $1 == -* ]]; do shift; [[ $1 == y ]] && shift; done\n"
        "script=$1\n"
        "range=$(grep -oP '(?<=#\\$ -t )1-\\d+' \"$script\")\n"
        "n=${range#1-}\n"
        "for ((t=1; t<=n; t++)); do\n"
        "  SGE_TASK_ID=$t bash \"$script\" || exit $?\n"
        "done\n")
    stub.chmod(0o755)
    import tools.launch as launch
    old_path = os.environ["PATH"]
    os.environ["PATH"] = str(tmp_path) + os.pathsep + old_path
    try:
        rc = launch.main(["-n", "2", "--launcher", "sge", "--platform",
                          "cpu", sys.executable, "-c", _RANK_PROBE])
    finally:
        os.environ["PATH"] = old_path
    out = capfd.readouterr().out
    assert rc == 0
    assert "RANK 0 of 2" in out and "RANK 1 of 2" in out, out
