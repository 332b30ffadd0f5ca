#!/usr/bin/env python
"""Cluster launcher for distributed training.

Parity: reference `tools/launch.py` (ssh/mpi/sge/yarn/local launchers that
spawn N workers + S servers and set `DMLC_*` roles consumed by ps-lite).

TPU-native redesign: there is no parameter-server tier — workers are
symmetric jax.distributed processes whose collectives carry the traffic, so
`-s/--num-servers` is accepted for CLI compatibility but ignored. Worker 0
hosts the coordination service; every worker gets
DMLC_PS_ROOT_URI/DMLC_PS_ROOT_PORT (coordinator address), DMLC_NUM_WORKER,
DMLC_WORKER_ID and DMLC_ROLE=worker, which mxnet_tpu.kvstore's
dist_sync/dist_async stores read to self-assemble the job
(kvstore._init_distributed).

A chip belongs to one process at a time, so several workers on one host
cannot share its chips: the local launcher with more than one worker is a
CPU drill and needs `--platform cpu`. On chips, run one worker per host
(ssh/mpi/sge).

Usage:
  tools/launch.py -n 4 --platform cpu python train.py ...  # local drill
  tools/launch.py -n 4 --launcher ssh -H hosts python train.py ...
"""
from __future__ import annotations

import argparse
import os
import shlex
import signal
import socket
import subprocess
import sys
import time


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_fail_fast(procs):
    """Wait on a worker fleet; the FIRST nonzero exit kills the rest — one
    dead worker deadlocks the survivors in collectives (parity:
    dmlc-tracker killing the job on any worker failure). The original
    failure code is preserved (not the -SIGTERM of the peers it killed)."""
    rc = 0
    signalled = False
    try:
        live = list(procs)
        while live:
            time.sleep(0.2)
            for p in list(live):
                ret = p.poll()
                if ret is None:
                    continue
                live.remove(p)
                if ret != 0 and rc == 0:
                    rc = ret
                if rc != 0 and not signalled:
                    signalled = True
                    for q in live:
                        q.send_signal(signal.SIGTERM)
    except KeyboardInterrupt:
        rc = 1
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return rc


def _worker_env(args, rank, coordinator):
    env = dict(os.environ)
    env.update({
        "DMLC_ROLE": "worker",
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_WORKER_ID": str(rank),
        "DMLC_PS_ROOT_URI": coordinator[0],
        "DMLC_PS_ROOT_PORT": str(coordinator[1]),
        "DMLC_NUM_SERVER": str(args.num_servers),
    })
    if args.platform:
        env["JAX_PLATFORMS"] = args.platform
    return env


# bootstrap run inside every MPI rank: the scheduler assigns ranks, so
# DMLC_WORKER_ID is derived from the MPI rank env var, then the user
# command replaces the shim (parity: dmlc-tracker mpi.py's rank pass-through)
_MPI_BOOTSTRAP = (
    "import os,sys;"
    "r=os.environ.get('OMPI_COMM_WORLD_RANK') or "
    "os.environ.get('PMI_RANK') or os.environ.get('PMIX_RANK') or "
    "os.environ.get('MV2_COMM_WORLD_RANK') or '0';"
    "os.environ['DMLC_WORKER_ID']=r;"
    "os.execvp(sys.argv[1],sys.argv[1:])"
)


def _launch_mpi(args, cmd):
    """Fan out via mpirun; per-rank id comes from the MPI rank env var
    (parity: reference tools/launch.py mpi path). Env travels as an
    `env K=V ...` command prefix — portable across OpenMPI and MPICH,
    whose env-forwarding flags (-x vs -env) disagree. The hostfile flag
    is OpenMPI's `--hostfile`; MPICH users should rely on their process
    manager's host configuration instead."""
    hosts = None
    if args.hostfile:
        with open(args.hostfile) as f:
            hosts = [h.strip() for h in f
                     if h.strip() and not h.startswith("#")]
    coord_host = hosts[0] if hosts else "127.0.0.1"
    # fixed default port (like the ssh path): rank 0 binds it on hosts[0],
    # so probing for a free port HERE would check the wrong machine
    coordinator = (coord_host, args.port or 9091)
    env = _worker_env(args, 0, coordinator)
    env.pop("DMLC_WORKER_ID")        # per-rank, set by the bootstrap
    mpi_cmd = ["mpirun", "-n", str(args.num_workers)]
    if args.hostfile:
        mpi_cmd += ["--hostfile", args.hostfile]
    mpi_cmd += ["env"]
    for k in sorted(env):
        if k.startswith(("DMLC_", "JAX_", "MXNET_", "PALLAS_")):
            mpi_cmd += ["%s=%s" % (k, env[k])]
    mpi_cmd += [sys.executable, "-c", _MPI_BOOTSTRAP] + cmd
    try:
        return subprocess.call(mpi_cmd, env=env)
    except FileNotFoundError:
        print("launch.py: mpirun not found on PATH", file=sys.stderr)
        return 127


def _launch_sge(args, cmd):
    """Submit an SGE array job, one task per worker; DMLC_WORKER_ID comes
    from SGE_TASK_ID. Worker 0 lands on an arbitrary execution node, so it
    PUBLISHES its hostname through a file in the (shared, `-cwd`) working
    directory and the fleet rendezvouses on that — the submit host never
    appears in the coordinator address (parity: reference tools/launch.py
    sge path via the dmlc tracker's shared-FS assumption)."""
    import tempfile
    port = args.port or 9091
    coordinator = ("__COORD__", port)  # placeholder, resolved per task
    env = _worker_env(args, 0, coordinator)
    exports = "\n".join(
        "export %s=%s" % (k, shlex.quote(str(env[k])))
        for k in sorted(env)
        if k.startswith(("DMLC_", "JAX_", "MXNET_", "PALLAS_"))
        and k not in ("DMLC_WORKER_ID", "DMLC_PS_ROOT_URI"))
    coordfile = os.path.join(
        os.getcwd(), ".mxtpu_sge_coord_%d_%d" % (os.getpid(), port))
    script = ("#!/bin/bash\n"
              "#$ -S /bin/bash\n"
              "#$ -cwd\n"
              "#$ -t 1-%d\n"
              "%s\n"
              "export DMLC_WORKER_ID=$((SGE_TASK_ID-1))\n"
              "if [[ $SGE_TASK_ID -eq 1 ]]; then\n"
              "  hostname > %s.tmp && mv %s.tmp %s\n"
              "fi\n"
              "for _ in $(seq 1 300); do\n"
              "  [[ -s %s ]] && break\n"
              "  sleep 1\n"
              "done\n"
              "if [[ ! -s %s ]]; then\n"
              "  echo 'launch.py sge: coordinator file never appeared (is "
              "the working dir on a shared filesystem?)' >&2; exit 1\n"
              "fi\n"
              "export DMLC_PS_ROOT_URI=$(cat %s)\n"
              "exec %s\n" % (args.num_workers, exports,
                             coordfile, coordfile, coordfile, coordfile,
                             coordfile, coordfile,
                             " ".join(shlex.quote(str(c)) for c in cmd)))
    with tempfile.NamedTemporaryFile("w", suffix=".sge.sh",
                                     delete=False) as f:
        f.write(script)
        path = f.name
    try:
        return subprocess.call(["qsub", "-sync", "y", path])
    except FileNotFoundError:
        print("launch.py: qsub not found on PATH", file=sys.stderr)
        return 127
    finally:
        os.unlink(path)
        if os.path.exists(coordfile):
            os.unlink(coordfile)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Launch a distributed mxnet_tpu job (parity: "
                    "reference tools/launch.py)")
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="accepted for reference CLI compatibility; "
                         "collective workers need no servers")
    ap.add_argument("--launcher",
                    choices=["local", "ssh", "mpi", "sge", "yarn"],
                    default="local")
    ap.add_argument("-H", "--hostfile", default=None,
                    help="newline-separated hosts (ssh launcher)")
    ap.add_argument("-p", "--port", type=int, default=0,
                    help="coordinator port (0 = pick a free one)")
    ap.add_argument("--platform", default=None,
                    help="force JAX_PLATFORMS for workers (e.g. cpu)")
    ap.add_argument("--sync-dst-dir", default=None,
                    help="rsync the working dir to this path on each ssh "
                         "host before launching")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if not args.command:
        ap.error("no command given")
    cmd = args.command[1:] if args.command[0] == "--" else args.command

    if args.launcher == "local":
        if args.num_workers > 1 and args.platform != "cpu":
            ap.error("the local launcher would start %d processes on this "
                     "host and a chip belongs to one process at a time: "
                     "they cannot share the host's chips. Pass --platform "
                     "cpu for a CPU drill, or launch one worker per host "
                     "(--launcher ssh/mpi/sge)" % args.num_workers)
        coordinator = ("127.0.0.1", args.port or _free_port())
        procs = []
        for rank in range(args.num_workers):
            procs.append(subprocess.Popen(
                cmd, env=_worker_env(args, rank, coordinator)))
        return _wait_fail_fast(procs)

    if args.launcher == "mpi":
        return _launch_mpi(args, cmd)
    if args.launcher == "sge":
        return _launch_sge(args, cmd)
    if args.launcher == "yarn":
        # Disposition (docs/PARITY.md): the reference's yarn launcher drives
        # a Hadoop tracker jar; TPU fleets are scheduled by GKE/XPK or
        # `gcloud alpha compute tpus`, not YARN. Use ssh/local/mpi here, or
        # one job-manager pod per worker with the DMLC_* env this launcher
        # sets (see _worker_env) when running under a cluster scheduler.
        ap.error("the yarn launcher is not supported on TPU deployments; "
                 "use --launcher ssh/local/mpi, or have your scheduler set "
                 "the DMLC_* variables directly (docs/PARITY.md)")

    # ssh launcher: round-robin ranks over the hostfile; worker 0's host is
    # the coordinator (parity: dmlc-tracker ssh.py)
    if not args.hostfile:
        ap.error("ssh launcher requires -H/--hostfile")
    with open(args.hostfile) as f:
        hosts = [h.strip() for h in f if h.strip() and not h.startswith("#")]
    if not hosts:
        ap.error("hostfile is empty")
    coordinator = (hosts[0], args.port or 9091)
    cwd = os.getcwd()
    if args.sync_dst_dir:
        # each unique host syncs exactly once, before any worker launches —
        # a per-rank sync would rewrite files under a running worker
        for host in dict.fromkeys(hosts[:args.num_workers] or hosts):
            subprocess.check_call(["rsync", "-a", "--delete", cwd + "/",
                                   "%s:%s" % (host, args.sync_dst_dir)])
    procs = []
    for rank in range(args.num_workers):
        host = hosts[rank % len(hosts)]
        env = _worker_env(args, rank, coordinator)
        envs = " ".join("%s=%s" % (k, shlex.quote(str(v)))
                        for k, v in env.items()
                        if k.startswith(("DMLC_", "JAX_", "MXNET_",
                                         "PALLAS_")))
        rdir = args.sync_dst_dir or cwd
        remote = "cd %s && env %s %s" % (
            shlex.quote(rdir), envs,
            " ".join(shlex.quote(str(c)) for c in cmd))
        procs.append(subprocess.Popen(["ssh", "-o",
                                       "StrictHostKeyChecking=no", host,
                                       remote]))
    return _wait_fail_fast(procs)


if __name__ == "__main__":
    sys.exit(main())
