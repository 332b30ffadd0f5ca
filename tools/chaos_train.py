#!/usr/bin/env python
"""Chaos drill: train LeNet through three injected faults and prove the
final state is bit-identical to an undisturbed run.

Orchestrator mode (default) runs four subprocess workers:

  1. a CLEAN run — the reference trajectory;
  2. the FAULTED sequence on a second checkpoint directory:
     a. SIGTERM delivered mid-epoch (``MXNET_CHAOS_SIGTERM_AT``): the
        preemption watcher checkpoints at the step boundary and exits
        with the relaunch code 83;
     b. relaunch, then a hard kill in the middle of a checkpoint write
        (``MXNET_CHAOS_KILL_SAVE``, exit 43): the torn temp file must
        not shadow the last published checkpoint;
     c. relaunch with a NaN injected into one step's gradients
        (``MXNET_CHAOS_NAN_STEP``) under the ``rollback`` policy: the
        bad-step guard drops the update in-graph, the loop restores the
        last checkpoint and replays — the fault is one-shot, so the
        replay is clean and the trajectory rejoins the reference.

Because every checkpoint captures the RNG key chain, LR-schedule state
and the step counter, and every batch is a pure function of its step
index, the faulted run's FINAL line (step, eval loss, param hash) must
EQUAL the clean run's — which this tool asserts.

Worker mode (``--worker``) is the training loop itself: build the net,
`ResilientLoop(TrainStep, CheckpointManager)`, `restore()`, train. All
fault behavior comes from the environment — the worker has no
fault-specific code, which is the point.

Multi-host mode (``--multihost``) is the POD-SCALE drill: N emulated
hosts (subprocesses, each a single-process jax CPU runtime with
`XLA_FLAGS=--xla_force_host_platform_device_count=D` virtual devices —
the jax.distributed-free local fallback) train the same dp mesh with the
ZeRO-1 sharded update and PER-HOST SHARDED checkpoints into one shared
directory. The drill then

  a. SIGKILLs one host mid-run (``MXNET_CHAOS_SIGKILL_AT``): no drain,
     no checkpoint — its shard files simply stop; the survivors are
     preempted (pod teardown) and their later per-host saves leave
     INCOMPLETE steps that restore must refuse;
  b. relaunches the SAME world shape: every host restores the newest
     step whose shards are complete on all hosts, and the finished run
     is bit-identical to an undisturbed reference;
  c. relaunches a SMALLER world (fewer hosts AND a smaller dp mesh) from
     the same checkpoint: elastic resume reassembles the global arrays
     from the old world's shard files, reshards onto the new mesh, and
     — with the global batch size held constant — finishes
     loss-curve-identical (equal up to collective reduction order).

Supervised mode (``--multihost --supervised``) is the ISSUE 15
REMEDIATION campaign: the pod runs with the training supervisor armed
(`MXNET_TRAIN_REMEDIATION=1`, parallel/supervisor.py) under a
relauncher implementing the restart ladder (budget, exponential
backoff, circuit breaker — the pod-scale sibling of
tools/train_supervise.py). Four legs:

  A. a chaos-armed SLOW host is flagged by the straggler detector,
     CORDONED onto the shared roster, the pod drains with
     EXIT_RECONFIGURE (84), and the relauncher rebuilds it at N−1
     hosts (cordoned host excluded) via the elastic sharded restore —
     the finish must equal the undisturbed reference;
  B. a SIGKILLed host is auto-relaunched within the restart budget and
     the pod finishes bit-identical;
  C. an injected SDC digest flip (``MXNET_CHAOS_SDC_AT``) makes the
     cross-host parity-probe quorum name EXACTLY the poisoned host,
     which is cordoned and excluded at N−1;
  D. a crash-looping worker (kill-during-save kept armed across
     relaunches) exhausts the budget: the circuit OPENS, the campaign
     leg fails loudly with a rendered postmortem.

Every detector flag, cordon, reconfigure, and injected fault must
appear on the merged flight-recorder timeline (tools/postmortem.py).

Usage:
    python tools/chaos_train.py                  # LeNet drill
    python tools/chaos_train.py --net mlp        # fast CI config
    python tools/chaos_train.py --multihost      # pod-scale drill
    python tools/chaos_train.py --multihost --supervised  # remediation
"""
import argparse
import os
import re
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_net(kind):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    if kind == "lenet":
        from mxnet_tpu.models.lenet import LeNet
        net = LeNet(num_classes=10, dropout=0.25)
    else:
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(32, in_units=64, activation="relu"))
        net.add(gluon.nn.Dropout(0.25))
        net.add(gluon.nn.Dense(10, in_units=32))
    net.initialize(mx.init.Xavier())
    return net


def batch_for(kind, step, batch_size=8):
    rng = np.random.RandomState(10_000 + step)
    if kind == "lenet":
        x = rng.randn(batch_size, 1, 28, 28).astype(np.float32)
    else:
        x = rng.randn(batch_size, 64).astype(np.float32)
    y = rng.randint(0, 10, (batch_size,)).astype(np.float32)
    return x, y


def worker(args):
    if args.devices:
        # must land BEFORE the first jax import (backend reads it once)
        flags = os.environ.get("XLA_FLAGS", "")
        want = "--xla_force_host_platform_device_count=%d" % args.devices
        if "xla_force_host_platform_device_count" in flags:
            flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                           want, flags)
        else:
            flags = (flags + " " + want).strip()
        os.environ["XLA_FLAGS"] = flags
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import ResilientLoop, TrainStep
    from mxnet_tpu.utils.recovery import CheckpointManager

    mx.random.seed(0)
    np.random.seed(0)
    net = build_net(args.net)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x0, y0 = batch_for(args.net, 0)
    net(mx.nd.array(x0))  # materialize deferred shapes before TrainStep
    mesh = None
    if args.devices:
        import jax
        from mxnet_tpu.parallel.mesh import build_mesh
        mesh = build_mesh({"dp": args.devices},
                          jax.devices()[:args.devices])
    step_fn = TrainStep(net, loss_fn, "adam", {"learning_rate": 0.01},
                        guard=True, mesh=mesh,
                        sharded_update=bool(mesh))
    # hosts > 0 = one emulated host of a pod: per-host sharded
    # checkpoints into the SHARED directory (each host writes only the
    # shards it owns; host 0 publishes the global manifest). Cadence
    # saves publish SYNCHRONOUSLY in pod mode so the drill's SIGKILL
    # step deterministically decides which steps are complete — the
    # async kill-during-save race has its own dedicated drills
    # (MXNET_CHAOS_KILL_SAVE, test_kill_during_save_subprocess).
    mgr = CheckpointManager(args.ckpt_dir, keep=3,
                            async_save=not args.hosts,
                            sharded=True if args.hosts else None,
                            process_index=args.host_index
                            if args.hosts else None,
                            process_count=args.hosts or None)
    loop = ResilientLoop(step_fn, mgr, save_every=args.save_every,
                         policy=args.policy, rollback_after=1,
                         lr_shrink=1.0)
    loop.restore()
    # drive batches off the CURRENT step counter: after a rollback the
    # trainer rewinds and the replayed steps must re-see their batches
    while loop.t < args.steps:
        loop.step(*batch_for(args.net, loop.t))
    loop.finish()
    step_fn.sync_params()
    # deterministic eval: dropout off outside training, fixed batch
    xe, ye = batch_for(args.net, 999)
    out = net(mx.nd.array(xe))
    eval_loss = float(np.mean(loss_fn(out, mx.nd.array(ye)).asnumpy()))
    flat = np.concatenate([p.data().asnumpy().ravel()
                           for p in net.collect_params().values()])
    print("FINAL step=%d loss=%.6f hash=%.8f"
          % (args.steps, eval_loss, float(np.sum(flat * flat))), flush=True)
    return 0


def _worker_cmd(args, ckpt_dir, host_index=None, hosts=None):
    cmd = [sys.executable, os.path.abspath(__file__), "--worker",
           "--net", args.net, "--steps", str(args.steps),
           "--save-every", str(args.save_every),
           "--policy", args.policy, "--ckpt-dir", ckpt_dir]
    if hosts:
        cmd += ["--hosts", str(hosts), "--host-index", str(host_index),
                "--devices", str(args.devices)]
    return cmd


def _worker_env(chaos=None):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("MXNET_CHAOS_")}
    # a CPU drill: several workers on one host could not share its chips
    env["JAX_PLATFORMS"] = "cpu"
    # the worker re-pins the virtual-device count itself from --devices;
    # drop any inherited value so a pytest parent's conftest flag can't
    # leak a different mesh size into the drill
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(chaos or {})
    return env


def run_worker(args, ckpt_dir, chaos=None, tag=""):
    proc = subprocess.run(_worker_cmd(args, ckpt_dir), env=_worker_env(chaos),
                          capture_output=True, text=True, timeout=600)
    print("-- %s: exit %d" % (tag or "worker", proc.returncode))
    for line in proc.stdout.splitlines():
        if line.startswith(("FINAL", "[resilient]")):
            print("   " + line)
    if proc.returncode not in (0, 43, 83):
        print(proc.stdout[-1500:])
        print(proc.stderr[-1500:])
    return proc


def final_line(proc):
    lines = [l for l in proc.stdout.splitlines() if l.startswith("FINAL")]
    return lines[-1] if lines else None


class _Host:
    """One emulated pod host: a Popen + its captured stdout."""

    def __init__(self, args, ckpt_dir, host_index, hosts, chaos=None):
        self.index = host_index
        self.out = tempfile.NamedTemporaryFile(
            mode="w+", prefix="chaos_host%d_" % host_index, suffix=".log",
            delete=False)
        self.proc = subprocess.Popen(
            _worker_cmd(args, ckpt_dir, host_index, hosts),
            env=_worker_env(chaos), stdout=self.out,
            stderr=subprocess.STDOUT, text=True)

    def wait(self, timeout=600):
        rc = self.proc.wait(timeout=timeout)
        self.out.flush()
        self.out.seek(0)
        self.stdout = self.out.read()
        self.out.close()
        try:
            os.unlink(self.out.name)
        except OSError:
            pass
        return rc

    def sigterm(self):
        self.proc.send_signal(signal.SIGTERM)

    def report(self, tag):
        print("-- %s: exit %s" % (tag, self.proc.returncode))
        for line in self.stdout.splitlines():
            if line.startswith(("FINAL", "[resilient]")):
                print("   host%d %s" % (self.index, line))


def _parse_final(line):
    m = re.search(r"step=(\d+) loss=([-\d.eE]+) hash=([-\d.eE]+)", line or "")
    assert m, "no FINAL line: %r" % (line,)
    return int(m.group(1)), float(m.group(2)), float(m.group(3))


def _final_of(host):
    lines = [l for l in host.stdout.splitlines() if l.startswith("FINAL")]
    return lines[-1] if lines else None


def _load_tool(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _check_flight_dumps(flight_dir, survivors, straggler_host=None):
    """Post-mortem gate of the pod drill: every SIGTERM'd survivor must
    have dumped its flight recorder, each dump must parse and hold the
    spans from right before the injected fault, the straggler and
    anomaly DETECTOR events (ISSUE 14) must have landed in the
    survivors' black boxes naming the right host, and
    tools/postmortem.py must render the set into a usable timeline
    (ALERT callouts + per-host skew table included)."""
    import json as _json
    files = sorted(os.path.join(flight_dir, n)
                   for n in os.listdir(flight_dir)
                   if n.startswith("flight-") and
                   n.endswith(".sigterm.json"))
    hosts_seen = set()
    straggler_events = []
    anomaly_events = []
    for f in files:
        with open(f) as fh:
            doc = _json.load(fh)           # parseable
        hosts_seen.add(doc["host"])
        span_names = [e["name"] for e in doc["events"]
                      if e.get("kind") == "span"]
        assert "train.device_step" in span_names, (
            "flight dump %s holds no train spans from before the fault"
            % f)
        faults = [e["name"] for e in doc["events"]
                  if e.get("kind") == "fault"]
        assert "chaos.sigterm_at" in faults, (
            "flight dump %s is missing the injected fault event" % f)
        straggler_events += [e for e in doc["events"]
                             if e.get("name") == "train.straggler"]
        anomaly_events += [e for e in doc["events"]
                           if e.get("name") == "train.anomaly"]
    assert len(hosts_seen) == survivors, (
        "expected flight dumps from %d survivor hosts, got %s"
        % (survivors, sorted(hosts_seen)))
    if straggler_host is not None:
        flagged = {str(e.get("host")) for e in straggler_events}
        assert flagged == {str(straggler_host)}, (
            "straggler detection flagged %s, expected exactly host %s"
            % (sorted(flagged) or "nobody", straggler_host))
        assert anomaly_events, ("the injected finite grad spike left "
                                "no train.anomaly event in any "
                                "survivor's black box")
        assert any(e.get("signal") == "grad_norm"
                   for e in anomaly_events), anomaly_events
    pm = _load_tool("postmortem")
    dumps = pm.load_dumps([flight_dir])
    text = pm.render(dumps)
    assert "FAULT" in text and "train.device_step" in text
    if straggler_host is not None:
        assert "ALERT" in text, "detector events not called out"
        assert "STRAGGLER" in text, "skew table did not mark the host"
        # the merged Perfetto export keeps per-host rows distinct
        # (MXNET_HOST_ID folded into the pid — the ISSUE 14 fix)
        doc = pm.export_perfetto(dumps)
        span_pids = {e["pid"] for e in doc["traceEvents"]
                     if e.get("ph") == "X"}
        assert len(span_pids) >= survivors, (
            "perfetto export merged hosts onto %d process row(s)"
            % len(span_pids))
    head = text.splitlines()
    print("-- flight recorder: %d dump(s) from %d survivor host(s); "
          "%d straggler + %d anomaly event(s); post-mortem timeline "
          "renders (%d lines)"
          % (len(files), len(hosts_seen), len(straggler_events),
             len(anomaly_events), len(head)))
    for line in head[:6]:
        print("   " + line)


def _await_console(host, timeout=180.0):
    """Poll one emulated host's captured stdout for the train-console
    line; returns the base URL. The console starts at ResilientLoop
    construction (before the first compile), so it is up for the whole
    multi-second compile window the drill renders its frame in."""
    deadline = time.time() + timeout
    pat = re.compile(r"train console on (http://[0-9.:]+)")
    while time.time() < deadline:
        try:
            with open(host.out.name) as f:
                m = pat.search(f.read())
            if m:
                return m.group(1)
        except OSError:
            pass
        time.sleep(0.25)
    raise AssertionError("host %d never printed its train-console "
                         "address" % host.index)


def _check_train_top(url, host):
    """`train_top --once` must render a live frame against the drill
    pod while the straggling host is still mid-run — and the frame
    must NAME the flagged straggler (the acceptance gate: flagged in
    the flight recorder, the postmortem timeline, AND a rendered
    frame)."""
    tt = _load_tool("train_top")
    deadline = time.time() + 240.0
    frame = best = ""
    while time.time() < deadline:
        frame = tt.render_once([url], timeout=5.0)
        if " live " in frame or " drain " in frame:
            best = frame
            if "FLAGGED" in frame:
                break
        if host.proc.poll() is not None:
            break
        time.sleep(0.25)
    assert "train console" in best, best or frame
    assert " live " in best or " drain " in best, (
        "train_top never rendered a live row against the drill pod:\n"
        + (best or frame))
    assert "FLAGGED" in best, (
        "train_top never rendered the flagged straggler:\n" + best)
    print("-- train_top --once frame against the live pod:")
    for line in best.splitlines():
        print("   " + line)


class _PodSupervisor:
    """Pod-scale relauncher with the ISSUE 15 restart ladder — the
    multihost counterpart of tools/train_supervise.py. Launches one
    `_Host` per world member, then:

      rc 0    host finished — final line collected;
      rc 83   preempted (drained): relaunch that host, FREE;
      rc 84   reconfigure (drained): wait for the whole incarnation to
              exit, re-read the cordon roster, relaunch the SHRUNK
              world (elastic restore picks up the pod's newest
              all-complete step);
      other   crash: consume one restart life, back off exponentially,
              relaunch that host with chaos scrubbed (unless
              `keep_chaos` — the crash-loop leg). Budget exhausted ⇒
              circuit OPEN: surviving hosts killed, postmortem rendered
              from the flight dir, run() returns False.
    """

    def __init__(self, args, ckpt_dir, labels, env_for, restart_max=None,
                 backoff=0.2, keep_chaos=False, flight_dir=None):
        self.args = args
        self.ckpt_dir = ckpt_dir
        self.labels = [str(l) for l in labels]
        self.env_for = env_for          # label -> extra env dict
        if restart_max is None:
            from mxnet_tpu.parallel import supervisor as _sup
            restart_max = _sup.restart_max()
        self.restart_max = int(restart_max)
        self.backoff = float(backoff)
        self.keep_chaos = keep_chaos
        self.flight_dir = flight_dir
        self.roster_dir = os.path.join(ckpt_dir, "cordon")
        self.finals = {}                # label -> FINAL line
        self.crashes = 0
        self.relaunches = 0
        self.incarnations = 0
        self.circuit_open = False
        self.worlds = []                # world per incarnation
        self.postmortem_text = ""
        self._launched = {}             # label -> launch count

    def _roster(self):
        return _load_tool("train_supervise").read_roster(self.roster_dir)

    def _launch(self, label, idx, n):
        env = dict(self.env_for(label) or {})
        if self._launched.get(label, 0) > 0 and not self.keep_chaos:
            # a relaunch scrubs the injected faults (a real relauncher
            # scrubs MXNET_CHAOS_*; the crash-loop leg keeps them to
            # model a fault that is really still there)
            env = {k: v for k, v in env.items()
                   if not k.startswith("MXNET_CHAOS_")}
        env["MXNET_HOST_ID"] = label
        self._launched[label] = self._launched.get(label, 0) + 1
        return _Host(self.args, self.ckpt_dir, idx, n, chaos=env)

    def run(self, deadline_s=900):
        from mxnet_tpu.parallel.resilient import (EXIT_PREEMPTED,
                                                  EXIT_RECONFIGURE)
        world = [l for l in self.labels if l not in self._roster()]
        deadline = time.time() + deadline_s
        while True:
            self.worlds.append(list(world))
            self.incarnations += 1
            n = len(world)
            print("[pod-supervise] incarnation %d: world %s"
                  % (self.incarnations - 1, world), flush=True)
            crew = {lab: self._launch(lab, i, n)
                    for i, lab in enumerate(world)}
            pending = dict(crew)
            reconfigure = False
            while pending:
                assert time.time() < deadline, \
                    "pod incarnation timed out (world %s)" % world
                for lab in list(pending):
                    h = pending[lab]
                    rc = h.proc.poll()
                    if rc is None:
                        continue
                    h.wait()
                    del pending[lab]
                    if rc == 0:
                        h.report("host %s finished" % lab)
                        self.finals[lab] = _final_of(h)
                        continue
                    if rc == EXIT_RECONFIGURE:
                        h.report("host %s reconfigure (84)" % lab)
                        reconfigure = True
                        continue
                    if rc == EXIT_PREEMPTED:
                        h.report("host %s preempted (83) — relaunch "
                                 "(free)" % lab)
                        self.relaunches += 1
                        pending[lab] = crew[lab] = self._launch(
                            lab, world.index(lab), n)
                        continue
                    # a crash: one life, exponential backoff, relaunch
                    self.crashes += 1
                    lives = self.restart_max - self.crashes
                    h.report("host %s CRASH rc=%s (%d of %d lives left)"
                             % (lab, rc, max(lives, 0),
                                self.restart_max))
                    if lives < 0:
                        self.circuit_open = True
                        print("[pod-supervise] CIRCUIT OPEN: restart "
                              "budget (MXNET_TRAIN_RESTART_MAX=%d) "
                              "exhausted — degrading loudly"
                              % self.restart_max, flush=True)
                        for o in pending.values():
                            o.proc.kill()
                            o.wait()
                        self._postmortem()
                        return False
                    delay = min(self.backoff * (2 ** (self.crashes - 1)),
                                30.0)
                    print("[pod-supervise] backing off %.2fs before "
                          "relaunching host %s" % (delay, lab),
                          flush=True)
                    time.sleep(delay)
                    self.relaunches += 1
                    pending[lab] = crew[lab] = self._launch(
                        lab, world.index(lab), n)
                if pending:
                    time.sleep(0.1)
            if not reconfigure:
                return True
            new_world = [l for l in self.labels
                         if l not in self._roster()]
            print("[pod-supervise] reconfigure: world %s -> %s "
                  "(cordoned: %s)" % (world, new_world,
                                      sorted(self._roster()) or "none"),
                  flush=True)
            assert new_world, "reconfigure cordoned the whole pod"
            self.relaunches += 1
            world = new_world

    def _postmortem(self):
        if not self.flight_dir or not os.path.isdir(self.flight_dir):
            return
        try:
            pm = _load_tool("postmortem")
            text = pm.render(pm.load_dumps([self.flight_dir]))
        except Exception as e:
            print("[pod-supervise] (postmortem render failed: %s)" % e)
            return
        self.postmortem_text = text
        print(text, flush=True)


def multihost(args):
    """The pod-scale drill (see the module docstring, Multi-host mode)."""
    import shutil
    base = args.work_dir or tempfile.mkdtemp(prefix="chaos_pod_")
    clean_dir = os.path.join(base, "clean")
    fault_dir = os.path.join(base, "faulted")
    elastic_dir = os.path.join(base, "elastic")
    hosts, devices = args.hosts or 2, args.devices
    k_kill = (args.steps // 2) + 1          # off the save cadence
    if k_kill % args.save_every == 0:
        k_kill += 1
    print("== multi-host chaos drill: %s, %d steps, save every %d, "
          "%d hosts x %d virtual devices (dp mesh, ZeRO-1 sharded "
          "update, per-host sharded checkpoints); SIGKILL host %d at "
          "step %d" % (args.net, args.steps, args.save_every, hosts,
                       devices, hosts - 1, k_kill))

    # 1. undisturbed reference: one host over the SAME dp mesh (emulated
    # hosts are trajectory replicas — IO partitioning is their only
    # difference, so one clean host pins the whole pod's trajectory)
    ref = _Host(args, clean_dir, 0, 1)
    rc = ref.wait()
    ref.report("clean reference")
    assert rc == 0, "clean run failed:\n" + ref.stdout[-2000:]
    want = _final_of(ref)
    assert want is not None

    # 2. the pod, one host dying hard mid-run. The emulated hosts do not
    # step in lockstep (no real cross-host collectives in the local
    # fallback), so the pod-teardown preemption is chaos-armed in each
    # survivor (a real SIGTERM, delivered at a deterministic step AFTER
    # the victim died) instead of racing an orchestrator-sent signal
    # against the survivors' progress. The survivors' drain checkpoints
    # land at a step the dead host never sharded -> incomplete, and the
    # relaunch must refuse it. Every pod host gets a flight-recorder
    # directory: the SIGKILL'd victim can't dump (that's the point of a
    # black box on the OTHERS), the SIGTERM'd survivors must.
    #
    # ISSUE 14 observability gates ride the same pod leg: host 0 (a
    # SURVIVOR) is the chaos-armed straggler (0.25s per-step sleep) and
    # carries the train console; the straggler detector must flag
    # exactly it (shared-dir step-time exchange, factor 1.5 because at
    # 2 emulated hosts the median averages the slow host in), a FINITE
    # grad spike after the last complete checkpoint must trip the
    # anomaly detector (the relaunch rewinds past the corruption, so
    # bit-identity still holds), and train_top must render a frame
    # against the live degraded pod. None of these knobs reach the
    # relaunch legs — _worker_env only carries them on this leg.
    flight_dir = os.path.join(base, "flight")
    k_drain = k_kill + 2
    k_spike = k_kill + 1               # after the last COMPLETE save
    observability = {
        "MXNET_STRAGGLER_DIR": os.path.join(base, "straggler"),
        "MXNET_STRAGGLER_WINDOW": "2",
        "MXNET_STRAGGLER_FACTOR": "1.5",
        "MXNET_STRAGGLER_PATIENCE": "2",
        "MXNET_ANOMALY_DETECT": "1",
        "MXNET_ANOMALY_WARMUP": "5",
    }
    crew = [_Host(args, fault_dir, i, hosts,
                  chaos=dict(
                      {"MXNET_CHAOS_SIGKILL_AT": str(k_kill)}
                      if i == hosts - 1 else
                      {"MXNET_CHAOS_SIGTERM_AT": str(k_drain)},
                      MXNET_FLIGHT_RECORDER_DIR=flight_dir,
                      MXNET_HOST_ID=str(i),
                      **dict(observability,
                             **({"MXNET_CHAOS_SLOW_HOST": "0:0.25",
                                 "MXNET_CHAOS_SPIKE_STEP": str(k_spike),
                                 "MXNET_TRAIN_METRICS_PORT": "0"}
                                if i == 0 else {}))))
            for i in range(hosts)]
    # the console is up from ResilientLoop construction (before the
    # first compile), and host 0's injected slowness stretches its run:
    # render the live frame while the pod is degraded
    console_url = _await_console(crew[0])
    _check_train_top(console_url, crew[0])
    victim = crew[-1]
    rc = victim.wait()
    victim.report("fault: SIGKILL host %d @%d" % (hosts - 1, k_kill))
    assert rc == -signal.SIGKILL, "expected SIGKILL death, got %r" % rc
    from mxnet_tpu.parallel.resilient import EXIT_PREEMPTED
    for h in crew[:-1]:
        rc = h.wait()
        h.report("survivor host %d preempted @%d" % (h.index, k_drain))
        assert rc == EXIT_PREEMPTED, \
            "survivor did not drain cleanly (%r):\n%s" % (rc,
                                                          h.stdout[-2000:])
    _check_flight_dumps(flight_dir, survivors=hosts - 1,
                        straggler_host=0)

    shutil.copytree(fault_dir, elastic_dir)   # snapshot for leg 4

    # 3. relaunch, SAME world shape: all hosts agree on the newest step
    # whose shards are complete everywhere, resume step-exactly, and the
    # finished pod is bit-identical to the undisturbed reference
    crew = [_Host(args, fault_dir, i, hosts) for i in range(hosts)]
    finals = []
    for h in crew:
        rc = h.wait()
        h.report("relaunch host %d" % h.index)
        assert rc == 0, "relaunch failed:\n" + h.stdout[-2000:]
        assert "resumed from step" in h.stdout, "host %d cold-started" \
            % h.index
        finals.append(_final_of(h))
    print("== clean:    %s" % want)
    for i, got in enumerate(finals):
        print("== host %d:  %s" % (i, got))
        assert got == want, "host %d diverged from the clean run" % i
    print("== same-shape relaunch: bit-identical on all %d hosts" % hosts)

    # 4. ELASTIC relaunch: fewer hosts AND a smaller mesh (dp halves,
    # global batch constant -> per-chip batch doubles). The single
    # survivor reassembles the old world's shard files into global
    # arrays, reshards, and finishes loss-curve-identical (equal up to
    # collective reduction order).
    el_args = argparse.Namespace(**vars(args))
    el_args.devices = max(1, devices // 2)
    el = _Host(el_args, elastic_dir, 0, 1)
    rc = el.wait()
    el.report("elastic relaunch (1 host x %d devices)" % el_args.devices)
    assert rc == 0, "elastic relaunch failed:\n" + el.stdout[-2000:]
    assert "resumed from step" in el.stdout, "elastic relaunch cold-started"
    s_w, l_w, h_w = _parse_final(want)
    s_e, l_e, h_e = _parse_final(_final_of(el))
    print("== elastic:  %s" % _final_of(el))
    assert s_e == s_w
    assert abs(l_e - l_w) <= 5e-4, (l_w, l_e)
    assert abs(h_e - h_w) <= 1e-3 * max(1.0, abs(h_w)), (h_w, h_e)
    print("== OK: dead host survived; same-shape resume bit-identical; "
          "elastic resume (dp %d -> %d) loss-curve-identical"
          % (devices, el_args.devices))
    return 0


def _flight_events(flight_dir):
    """(name -> [event, ...]) across every dump in `flight_dir`."""
    import json as _json
    out = {}
    for name in sorted(os.listdir(flight_dir)):
        if not (name.startswith("flight-") and name.endswith(".json")):
            continue
        with open(os.path.join(flight_dir, name)) as f:
            doc = _json.load(f)
        for ev in doc.get("events", []):
            out.setdefault(ev.get("name"), []).append(ev)
    return out


def supervised(args):
    """The ISSUE 15 remediation campaign (module docstring, Supervised
    mode): four legs through the detect -> decide -> act loop."""
    base = args.work_dir or tempfile.mkdtemp(prefix="chaos_sup_")
    hosts, devices = args.hosts or 3, args.devices
    roster_of = _load_tool("train_supervise").read_roster
    print("== supervised remediation campaign: %s, %d steps, save every "
          "%d, %d hosts x %d virtual devices"
          % (args.net, args.steps, args.save_every, hosts, devices))

    # undisturbed reference (emulated hosts are trajectory replicas:
    # one clean host pins the whole pod's trajectory)
    ref = _Host(args, os.path.join(base, "clean"), 0, 1)
    rc = ref.wait()
    ref.report("clean reference")
    assert rc == 0, "clean run failed:\n" + ref.stdout[-2000:]
    want = _final_of(ref)
    assert want is not None

    # -- leg A: slow host -> straggler flag -> cordon -> elastic N-1 --------
    dir_a = os.path.join(base, "leg_a")
    flight_a = os.path.join(base, "flight_a")
    env_a = {
        "MXNET_TRAIN_REMEDIATION": "1",
        "MXNET_STRAGGLER_DIR": os.path.join(base, "straggler_a"),
        "MXNET_STRAGGLER_WINDOW": "2",
        # the injected straggler sits ~50x the pod median, so a wide
        # factor keeps ms-scale CPU jitter between the HEALTHY hosts
        # (whose early windows may not include the slow host's first
        # publish yet) from ever reaching the cordon path
        "MXNET_STRAGGLER_FACTOR": "3.0",
        "MXNET_STRAGGLER_PATIENCE": "2",
        "MXNET_FLIGHT_RECORDER_DIR": flight_a,
    }
    print("== leg A: slow host 1 (0.25s/step) must be cordoned and the "
          "pod finish at %d hosts" % (hosts - 1))
    pod_a = _PodSupervisor(
        args, dir_a, [str(i) for i in range(hosts)],
        env_for=lambda lab: dict(
            env_a, **({"MXNET_CHAOS_SLOW_HOST": "1:0.25"}
                      if lab == "1" else {})),
        flight_dir=flight_a)
    assert pod_a.run(), "leg A pod did not finish"
    assert not pod_a.circuit_open
    rosterA = roster_of(os.path.join(dir_a, "cordon"))
    assert sorted(rosterA) == ["1"], (
        "expected exactly host 1 cordoned, roster: %s" % sorted(rosterA))
    assert rosterA["1"]["reason"] == "straggler", rosterA["1"]
    assert pod_a.worlds[-1] == [str(i) for i in range(hosts)
                                if i != 1], pod_a.worlds
    for lab in pod_a.worlds[-1]:
        got = pod_a.finals.get(lab)
        assert got == want, ("host %s diverged after the cordoned "
                             "restart: %r != %r" % (lab, got, want))
    ev = _flight_events(flight_a)
    for name in ("chaos.slow_host", "train.straggler", "train.cordon",
                 "train.reconfigure", "train.reconfigure_exit"):
        assert ev.get(name), "leg A flight timeline is missing %s" % name
    assert {str(e.get("host")) for e in ev["train.cordon"]} == {"1"}
    pm = _load_tool("postmortem")
    text = pm.render(pm.load_dumps([flight_a]))
    assert "train.cordon" in text and "ALERT" in text, text[:800]
    print("== leg A OK: cordoned host 1, finished loss-curve-identical "
          "at %d hosts (%d incarnation(s), %d relaunch(es))"
          % (hosts - 1, pod_a.incarnations, pod_a.relaunches))

    # -- leg B: SIGKILL -> auto-relaunch within the restart budget ----------
    dir_b = os.path.join(base, "leg_b")
    k_kill = (args.steps // 2) + 1
    if k_kill % args.save_every == 0:
        k_kill += 1
    print("== leg B: SIGKILL host 1 @%d must auto-relaunch within the "
          "budget and finish bit-identical" % k_kill)
    pod_b = _PodSupervisor(
        args, dir_b, ["0", "1"],
        env_for=lambda lab: (
            {"MXNET_CHAOS_SIGKILL_AT": str(k_kill)}
            if lab == "1" else {}),
        restart_max=3)
    assert pod_b.run(), "leg B pod did not finish"
    assert pod_b.crashes == 1, ("expected exactly one consumed life, "
                                "got %d" % pod_b.crashes)
    assert not pod_b.circuit_open
    for lab in ("0", "1"):
        assert pod_b.finals.get(lab) == want, (
            "host %s diverged after auto-relaunch: %r != %r"
            % (lab, pod_b.finals.get(lab), want))
    print("== leg B OK: dead host auto-relaunched (1 of 3 lives), "
          "bit-identical finish")

    # -- leg C: injected SDC digest flip -> right host named + cordoned -----
    dir_c = os.path.join(base, "leg_c")
    flight_c = os.path.join(base, "flight_c")
    k_probe = args.save_every            # on-cadence: drain step complete
    k_sdc = 2 * k_probe
    env_c = {
        "MXNET_TRAIN_REMEDIATION": "1",
        "MXNET_SDC_PROBE_EVERY": str(k_probe),
        "MXNET_SDC_PROBE_DIR": os.path.join(base, "sdc_c"),
        "MXNET_SDC_PROBE_TIMEOUT": "180",
        "MXNET_FLIGHT_RECORDER_DIR": flight_c,
    }
    print("== leg C: SDC digest flip on host 1 @ probe step %d must "
          "name and cordon exactly host 1" % k_sdc)
    pod_c = _PodSupervisor(
        args, dir_c, [str(i) for i in range(hosts)],
        env_for=lambda lab: dict(
            env_c, **({"MXNET_CHAOS_SDC_AT": "1:%d" % k_sdc}
                      if lab == "1" else {})),
        flight_dir=flight_c)
    assert pod_c.run(), "leg C pod did not finish"
    rosterC = roster_of(os.path.join(dir_c, "cordon"))
    assert sorted(rosterC) == ["1"], (
        "SDC quorum named %s, expected exactly host 1" % sorted(rosterC))
    assert rosterC["1"]["reason"] == "sdc", rosterC["1"]
    assert pod_c.worlds[-1] == [str(i) for i in range(hosts)
                                if i != 1], pod_c.worlds
    for lab in pod_c.worlds[-1]:
        assert pod_c.finals.get(lab) is not None, \
            "host %s left no FINAL line" % lab
    ev = _flight_events(flight_c)
    assert ev.get("chaos.sdc_at"), "injected SDC fault not on timeline"
    sdc_named = {str(e.get("host")) for e in ev.get("train.sdc", [])
                 if e.get("quorum")}
    assert sdc_named == {"1"}, (
        "train.sdc events named %s, expected exactly host 1"
        % sorted(sdc_named))
    text = pm.render(pm.load_dumps([flight_c]))
    assert "train.sdc" in text and "ALERT" in text
    print("== leg C OK: quorum named host 1, cordoned, finished at %d "
          "hosts" % (hosts - 1))

    # -- leg D: crash loop -> circuit opens, postmortem rendered ------------
    dir_d = os.path.join(base, "leg_d")
    flight_d = os.path.join(base, "flight_d")
    k_crash = 2 * args.save_every        # kill mid-save, every relaunch
    print("== leg D: kill-during-save @%d kept armed across relaunches "
          "must open the circuit (budget 2)" % k_crash)
    pod_d = _PodSupervisor(
        args, dir_d, ["0"],
        env_for=lambda lab: {
            "MXNET_CHAOS_KILL_SAVE": str(k_crash),
            "MXNET_FLIGHT_RECORDER_DIR": flight_d,
        },
        restart_max=2, keep_chaos=True, backoff=0.05,
        flight_dir=flight_d)
    ok = pod_d.run()
    assert ok is False and pod_d.circuit_open, (
        "crash loop did not open the circuit (ok=%r)" % ok)
    assert pod_d.crashes == 3            # budget 2 => 3 strikes
    assert "chaos.kill_save" in pod_d.postmortem_text, (
        "circuit-open postmortem did not render the injected fault:\n"
        + pod_d.postmortem_text[:800])
    print("== leg D OK: circuit opened after %d crashes, postmortem "
          "rendered (%d lines)"
          % (pod_d.crashes, len(pod_d.postmortem_text.splitlines())))

    print("== OK: supervised remediation campaign — straggler cordoned "
          "+ elastic N-1 finish, SIGKILL auto-relaunch bit-identical, "
          "SDC suspect named exactly, crash-loop circuit opened loudly")
    return 0


def orchestrate(args):
    from mxnet_tpu.parallel.resilient import EXIT_PREEMPTED
    base = args.work_dir or tempfile.mkdtemp(prefix="chaos_train_")
    clean_dir = os.path.join(base, "clean")
    fault_dir = os.path.join(base, "faulted")
    k_sigterm = args.steps // 4            # mid-epoch, off cadence
    k_killsave = (args.steps // 2 // args.save_every) * args.save_every
    k_nan = k_killsave + 2

    print("== chaos drill: %s, %d steps, save every %d (faults: SIGTERM@%d,"
          " kill-during-save@%d, NaN@%d)"
          % (args.net, args.steps, args.save_every, k_sigterm, k_killsave,
             k_nan))
    clean = run_worker(args, clean_dir, tag="clean reference")
    assert clean.returncode == 0, "clean run failed"

    p1 = run_worker(args, fault_dir,
                    {"MXNET_CHAOS_SIGTERM_AT": str(k_sigterm)},
                    tag="fault 1: SIGTERM@%d" % k_sigterm)
    assert p1.returncode == EXIT_PREEMPTED, (
        "expected preemption exit %d, got %d" % (EXIT_PREEMPTED,
                                                 p1.returncode))
    p2 = run_worker(args, fault_dir,
                    {"MXNET_CHAOS_KILL_SAVE": str(k_killsave)},
                    tag="fault 2: kill-during-save@%d" % k_killsave)
    assert p2.returncode == 43, (
        "expected chaos hard-kill exit 43, got %d" % p2.returncode)
    p3 = run_worker(args, fault_dir,
                    {"MXNET_CHAOS_NAN_STEP": str(k_nan)},
                    tag="fault 3: NaN grads@%d (rollback) + finish" % k_nan)
    assert p3.returncode == 0, "faulted run did not complete"

    want, got = final_line(clean), final_line(p3)
    print("== clean:   %s" % want)
    print("== faulted: %s" % got)
    assert want is not None and want == got, (
        "faulted trajectory diverged from the clean run")
    print("== OK: three faults survived, final state bit-identical")
    return 0


def main():
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog="A CPU drill: every worker is started with "
               "JAX_PLATFORMS=cpu and never uses a chip.")
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--multihost", action="store_true",
                    help="pod-scale drill: emulated hosts, sharded "
                         "checkpoints, SIGKILL one host, elastic resume")
    ap.add_argument("--supervised", action="store_true",
                    help="with --multihost: the ISSUE 15 remediation "
                         "campaign (cordon/elastic-restart, SIGKILL "
                         "auto-relaunch, SDC quorum, crash-loop "
                         "circuit)")
    ap.add_argument("--net", choices=("lenet", "mlp"), default="lenet")
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--save-every", type=int, default=4)
    ap.add_argument("--policy", default="rollback")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--work-dir", default="")
    ap.add_argument("--hosts", type=int, default=0,
                    help="emulated pod size (worker: my process_count)")
    ap.add_argument("--host-index", type=int, default=0)
    ap.add_argument("--devices", type=int, default=0,
                    help="virtual devices per host (dp mesh width); 0 = "
                         "no mesh")
    args = ap.parse_args()
    if args.worker:
        assert args.ckpt_dir, "--worker needs --ckpt-dir"
        return worker(args)
    if args.multihost:
        if args.supervised:
            if not args.devices:
                args.devices = 2
            if not args.hosts:
                args.hosts = 3
            return supervised(args)
        if not args.devices:
            args.devices = 4
        if not args.hosts:
            args.hosts = 2
        return multihost(args)
    return orchestrate(args)


if __name__ == "__main__":
    sys.exit(main())
