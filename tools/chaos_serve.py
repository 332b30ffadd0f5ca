#!/usr/bin/env python
"""Serving chaos drill (ISSUE 11): drive a 3-replica fleet through a
fault storm and prove the survival layer holds.

PR 3/6 proved the chaos discipline on the training side (injected
faults, bit-identical recovery); this drill ports it to serving. One
process runs a `ReplicatedLMServer` over a tiny transformer while
deterministic clients stream requests through the front door, and the
chaos harness (utils/chaos.py) injects, in sequence:

  1. **loop wedge** (replica 1): the serving thread stalls long enough
     to be judged wedged — drained, queued + in-flight work re-homed —
     then resumes and is RESTORED to rotation;
  2. **replica-thread kill** (replica 0): the loop dies mid-decode; the
     death hook fails over its in-flight sequences (prompt + generated
     tokens replay as prefills elsewhere) and the supervisor RESPAWNS a
     fresh replica that serves again within the drill;
  3. **decode-step poison** (replica 2): one decode step raises; the
     batch is locally resumed, the loop survives;
  4. **pool exhaustion** (replica 2): the free list vanishes for a few
     iterations; admission queues instead of failing;
  5. **crash loop** (replica 1): every (re)spawned instance dies; after
     its respawn budget the circuit OPENS and the fleet keeps serving
     on the survivors.

Asserted at the end:
  * availability: >= 99% of storm requests complete (failed-over or
    served; the drill's faults are all recoverable, so in practice
    100%);
  * every completed request is greedy-token-IDENTICAL to an undisturbed
    oracle rollout — failover replays may not perturb a single token;
  * zero leaked blocks: `Engine.audit_quiescent()` passes on every
    surviving replica AND every retired (crashed) engine;
  * every injected fault appears in the merged flight-recorder
    postmortem timeline (tools/postmortem.py), AND (ISSUE 13) so do the
    pinned failover victims' per-request lifecycle events
    (request.failover / request.finish, trace-linked), so a postmortem
    answers "what happened to THAT request" — not just "what broke";
  * the request-lifecycle JSONL ledger (MXNET_REQUEST_LOG) carries the
    victims' full lifecycles under ONE trace id across the hop;
  * tools/fleet_top.py renders a live frame against the degraded fleet
    (statusz + healthz + metrics over HTTP) without errors.

Usage:
    python tools/chaos_serve.py                  # CI config
    python tools/chaos_serve.py --requests 96 --clients 6
"""
import argparse
import importlib.util
import os
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# a CPU drill: the faults and the recovery are host-side control flow
os.environ["JAX_PLATFORMS"] = "cpu"

SERVE_FAULTS = ("chaos.serve_wedge", "chaos.serve_kill",
                "chaos.serve_poison", "chaos.serve_exhaust",
                "chaos.serve_crash_loop", "chaos.serve_rollout_corrupt",
                "chaos.serve_spec_poison")


def build_model():
    import jax
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params)
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=64)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


def workload(n, vocab=64):
    """Deterministic (prompt, max_new) pairs — the greedy rollouts are
    then pure functions of these, which is what makes token-parity
    through a fault storm checkable at all."""
    out = []
    for i in range(n):
        plen = 4 + (i * 3) % 7
        prompt = [(2 + i + 5 * t) % vocab for t in range(plen)]
        out.append((prompt, 3 + i % 4))
    return out


def oracle_rollouts(model, work):
    """Undisturbed single-server rollouts: the parity reference."""
    from mxnet_tpu import serving
    srv = serving.serve(model, max_batch=4, block_size=8)
    try:
        return [srv.generate(list(p), max_new_tokens=m, timeout=300)
                for p, m in work]
    finally:
        srv.close()


def wait_for(pred, timeout, what):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    raise AssertionError("timed out waiting for " + what)


def busy_with_tokens(rep, min_generated=1):
    """A racy-but-safe peek: does the replica hold a running sequence
    that has already generated tokens? (Arms the kill so the death is
    guaranteed to strand in-flight work — the failover path's quarry.)"""
    for seq in list(rep.scheduler.running):
        if seq.request is not None and \
                len(seq.tokens) - seq.prompt_len >= min_generated:
            return True
    return False


def main():
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog="A CPU drill: it pins JAX_PLATFORMS=cpu and never uses a "
               "chip.")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--flight-dir", default="")
    args = ap.parse_args()

    flight_dir = args.flight_dir or tempfile.mkdtemp(prefix="chaos_serve_")
    os.environ["MXNET_FLIGHT_RECORDER_DIR"] = flight_dir
    # the per-request lifecycle ledger (ISSUE 13) rides the drill:
    # every request's queued -> ... -> finish streams as JSONL, and the
    # pinned victims' lifecycles must survive the failover hop under
    # ONE trace id
    request_log = os.path.join(flight_dir, "requests.jsonl")
    os.environ["MXNET_REQUEST_LOG"] = request_log

    from mxnet_tpu import serving, telemetry
    from mxnet_tpu.utils import chaos

    model = build_model()
    work = workload(args.requests)
    # two pinned long-running requests: submitted DIRECTLY to the fault
    # phases' victim replicas so the kill lands mid-decode (in-flight
    # failover) and the poison lands on a live batch, no matter how
    # fast the background storm drains
    pin_kill = ([7, 11, 13, 17, 19], 32)
    pin_poison = ([23, 29, 31, 37], 32)
    print("== serving chaos drill: %d requests / %d clients, 3 replicas"
          % (args.requests, args.clients))
    t0 = time.time()
    want = oracle_rollouts(model, work + [pin_kill, pin_poison])
    want, want_kill, want_poison = want[:-2], want[-2], want[-1]
    print("-- oracle: %d undisturbed greedy rollouts (%.1fs)"
          % (len(want) + 2, time.time() - t0))

    # construct with a LENIENT beat threshold: first-traffic XLA
    # compiles stall each loop for ~a second, and judging those wedged
    # would drain the whole fleet at once. Warm every replica through
    # its compile lattice (decode batch buckets 1/2/4, both prefill
    # buckets) the way a production rollout warms a replica before it
    # takes traffic, THEN tighten the threshold so the storm's injected
    # wedge is detected fast.
    srv = serving.serve(model, replicas=3, max_batch=4, block_size=8,
                        max_queue=len(work) + 8, max_beat_age=5.0,
                        respawn_max=2, respawn_backoff=0.05)
    t0 = time.time()
    for rep in srv.replicas:
        # plens 5/9/17 cover prefill buckets 8/16/32 — 32 because a
        # failover replay's prompt is original + generated-so-far and
        # must not pay a fresh compile on the rescue path
        warm = [rep.submit([3 + t for t in range(plen)],
                           max_new_tokens=4)
                for plen in (5, 9, 17, 6)]
        for w in warm:
            w.result(timeout=300)
    # 2.5s: ~3x the worst honest stall observed on a contended CPU box
    # (concurrent engines + clients), still far under the injected 6s
    # wedge — a false drain self-heals via restore, but a false drain
    # during a REAL fault window is exactly when orphans happen
    srv.max_beat_age = 2.5
    print("-- fleet warmed: %d replicas through their compile lattice "
          "(%.1fs)" % (len(srv.replicas), time.time() - t0))
    # the live console's quarry: statusz/healthz/metrics over HTTP
    http_host, http_port = srv.serve_http(port=0, block=False)
    console_url = "http://%s:%d" % (http_host, http_port)
    stop_sweep = threading.Event()

    def sweeper():                     # drives drain/restore/respawn
        while not stop_sweep.is_set():
            try:
                srv.health()
            except Exception:
                pass
            time.sleep(0.05)

    threading.Thread(target=sweeper, daemon=True).start()

    results = {}

    def client(cid):
        for i in range(cid, len(work), args.clients):
            prompt, max_new = work[i]
            for attempt in range(8):   # absorb transient backpressure
                try:
                    req = srv.submit(list(prompt),
                                     max_new_tokens=max_new)
                    results[i] = req.result(timeout=300)
                    break
                except (serving.QueueFull, serving.NoHealthyReplicas):
                    time.sleep(0.1 * (attempt + 1))
                except Exception as e:
                    results[i] = e
                    break
            time.sleep(0.01)

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(args.clients)]
    t0 = time.time()
    for t in threads:
        t.start()

    # -- the storm: faults armed against live traffic -----------------------
    # 1. wedge replica 1 (stale beat -> drain + failover -> restore)
    wait_for(lambda: srv.replicas[1].scheduler.running, 60,
             "replica 1 under load")
    chaos.configure(serve_wedge=(1, 1, 6.0))
    wait_for(lambda: "serve_wedge" in chaos.fired(), 60, "wedge firing")
    print("-- fault 1: replica 1 wedged (6s stall)")
    wait_for(lambda: srv._drained[1], 30, "wedged replica drained")
    wait_for(lambda: not srv._drained[1], 60, "wedged replica restored")
    print("   drained, work re-homed, then RESTORED")
    telemetry.flight().dump("phase_wedge")

    # 2. kill replica 0 mid-decode (in-flight failover + respawn): a
    # pinned 32-token request guarantees the thread dies with work in
    # flight whatever the storm is doing
    victim0 = srv.replicas[0]
    req_kill = victim0.submit(list(pin_kill[0]),
                              max_new_tokens=pin_kill[1])
    wait_for(lambda: busy_with_tokens(victim0), 60,
             "replica 0 decoding the pinned request")
    chaos.configure(serve_kill=(0, 1))
    wait_for(lambda: "serve_kill" in chaos.fired(), 60, "kill firing")
    print("-- fault 2: replica 0's serving thread killed mid-decode")
    got = req_kill.result(timeout=300)
    assert got == want_kill, (
        "in-flight failover diverged: %r != %r" % (got, want_kill))
    wait_for(lambda: srv.replicas[0] is not victim0, 60,
             "replica 0 respawned")
    print("   in-flight work failed over token-identically; replica 0 "
          "RESPAWNED")
    telemetry.flight().dump("phase_kill")

    # 3. poison one decode step on replica 2 (local resume), again
    # against a pinned in-flight request
    req_poison = srv.replicas[2].submit(list(pin_poison[0]),
                                        max_new_tokens=pin_poison[1])
    wait_for(lambda: busy_with_tokens(srv.replicas[2]), 60,
             "replica 2 decoding the pinned request")
    chaos.configure(serve_poison=(2, 1))
    wait_for(lambda: "serve_poison" in chaos.fired(), 60,
             "poison firing")
    print("-- fault 3: replica 2 decode step poisoned (batch resumed)")
    got = req_poison.result(timeout=300)
    assert got == want_poison, (
        "local resume diverged: %r != %r" % (got, want_poison))

    # 4. transient pool exhaustion on replica 2
    chaos.configure(serve_exhaust=(2, 1, 10))
    wait_for(lambda: "serve_exhaust" in chaos.fired(), 60,
             "exhaustion firing")
    print("-- fault 4: replica 2 pool exhausted for 10 iterations")
    telemetry.flight().dump("phase_poison_exhaust")

    for t in threads:
        t.join(timeout=600)
    storm_s = time.time() - t0

    # -- verdict: availability + token parity -------------------------------
    done = {i: r for i, r in results.items() if isinstance(r, list)}
    availability = len(done) / float(len(work))
    print("== storm done in %.1fs: %d/%d requests completed (%.1f%%)"
          % (storm_s, len(done), len(work), 100 * availability))
    for i, err in sorted(results.items()):
        if not isinstance(err, list):
            print("   FAILED request %d: %r" % (i, err))
    assert availability >= 0.99, (
        "availability %.3f < 0.99" % availability)
    mismatched = [i for i, got in done.items() if got != want[i]]
    assert not mismatched, (
        "failover perturbed greedy tokens for requests %r" % mismatched)
    print("== every completed request greedy-token-identical to the "
          "undisturbed oracle")
    snap = srv.snapshot()["aggregate"]
    print("== ledger: failovers=%d respawns=%d orphaned=%d"
          % (snap["failovers"], snap["respawns"], snap["orphaned"]))
    assert snap["failovers"] >= 1, "the kill stranded no in-flight work?"
    assert snap["respawns"] >= 1
    # the respawned replica really serves again within the drill (its
    # fresh engine may still be paying a compile when the storm ends)
    wait_for(lambda: srv.health()["replicas_healthy"] == 3, 60,
             "respawned replica back in rotation")

    # -- crash loop: the circuit opens, the fleet survives ------------------
    chaos.configure(serve_crash_loop=(1, 1))
    wait_for(lambda: srv.health()["replicas_circuit_open"] == 1, 120,
             "crash-loop circuit opening")
    chaos.configure(serve_crash_loop=None)
    h = srv.health()
    assert h["ok"] and h["replicas"][1]["circuit_open"]
    print("-- fault 5: replica 1 crash-looped; circuit OPEN after %d "
          "respawns; fleet degraded-not-dead" % srv.respawn_max)
    extra = workload(6, vocab=64)
    for j, (p, m) in enumerate(extra):
        got = srv.generate(list(p), max_new_tokens=m, timeout=300)
        assert got == want[j], "survivor diverged post-circuit-open"
    print("   survivors keep serving, token-identical")

    # -- live console: fleet_top renders the DEGRADED fleet -----------------
    spec = importlib.util.spec_from_file_location(
        "fleet_top", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "fleet_top.py"))
    ft = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ft)
    frame = ft.render_once(console_url)
    assert "fleet:" in frame and "CIRCUIT" in frame, frame
    assert "tokens: submitted" in frame, frame
    for i in range(3):
        assert ("\n  %d " % i) in frame or (" %d " % i) in frame, (
            "replica %d missing from the console frame:\n%s" % (i, frame))
    print("-- fleet_top console frame (degraded fleet, circuit open):")
    for ln in frame.splitlines()[:8]:
        print("   | " + ln)

    # -- fault 6: live rollout with a corrupted candidate (ISSUE 18) --------
    # a new checkpoint publishes, then bitrot flips a byte in its
    # payload AFTER the manifest landed; the rollout watcher must catch
    # it at the verification/parity gate — BEFORE any user request
    # reaches the weights — quarantine it on the shared rejection
    # roster, and leave the fleet serving the incumbent with zero
    # requests lost
    import numpy as np
    from mxnet_tpu.utils.recovery import CheckpointManager
    ckpt_dir = os.path.join(flight_dir, "rollout_ckpts")
    mgr = CheckpointManager(ckpt_dir, async_save=False)
    params, _cfg = model
    mgr.save(1, {k: np.asarray(v) + 0.05 for k, v in params.items()})
    chaos.configure(serve_rollout_corrupt=(1, 0))
    ro = srv.attach_rollout(ckpt_dir, stages=(0.5,), window_s=0.0)
    ro_results = {}

    def rollout_client(j, p, m):
        try:
            ro_results[j] = srv.generate(list(p), max_new_tokens=m,
                                         timeout=300)
        except Exception as e:
            ro_results[j] = e

    ro_threads = [threading.Thread(target=rollout_client,
                                   args=(j, p, m))
                  for j, (p, m) in enumerate(extra)]
    for t in ro_threads:
        t.start()
    verdict = ro.step()
    for t in ro_threads:
        t.join(timeout=300)
    assert verdict == "rejected", (
        "corrupted candidate was not rejected: %r" % verdict)
    assert "serve_rollout_corrupt" in chaos.fired()
    assert ro.roster.steps() == {1}, ro.roster.steps()
    assert ro.state == "idle" and ro.candidate is None
    assert all(v is None for v in srv._version), (
        "a corrupted candidate reached a replica: %r" % srv._version)
    assert ro.last_rejection and ro.last_rejection["probe"] == "digest"
    lost = [j for j, r in ro_results.items() if not isinstance(r, list)]
    assert not lost, "rollout leg lost requests %r: %r" % (
        lost, [ro_results[j] for j in lost])
    mism = [j for j, r in ro_results.items() if r != want[j]]
    assert not mism, (
        "rollout leg perturbed greedy tokens for %r" % mism)
    print("-- fault 6: corrupted rollout candidate quarantined at the "
          "gate (probe=digest), %d live requests untouched, fleet "
          "stays on the incumbent" % len(ro_results))
    telemetry.flight().dump("phase_rollout")

    # -- fault 7: speculative-decoding draft poison (ISSUE 19) --------------
    # a dedicated spec-enabled replica (1-layer self-draft, k=3): NaN
    # draft logits on one decode iteration must DEGRADE that pass to
    # the verbatim non-speculative path — the request completes
    # greedy-token-identical to the undisturbed oracle, no request
    # fails, no resume is spent, and the fallback is COUNTED
    from mxnet_tpu.serving.spec import self_draft
    spec_srv = serving.LMServer(model, max_batch=4, block_size=8,
                                paged=True,
                                draft=self_draft(params, _cfg, 1),
                                spec_k=3, replica_id=7)
    assert spec_srv.engine.spec, (
        "spec replica fell back: %r" % spec_srv.engine.spec_fallback)
    chaos.configure(serve_spec_poison=(7, 1))
    try:
        got = spec_srv.generate(list(pin_poison[0]),
                                max_new_tokens=pin_poison[1],
                                timeout=300)
        assert got == want_poison, (
            "spec poison degrade diverged: %r != %r"
            % (got, want_poison))
        assert "serve_spec_poison" in chaos.fired(), (
            "spec poison never fired")
        assert spec_srv.engine.spec_fallbacks >= 1, (
            "poisoned pass was not counted as a spec fallback")
        assert spec_srv.engine.spec_accepted_tokens >= 1, (
            "spec replica never speculated after the degrade")
        wait_for(lambda: not spec_srv.engine.cache.pool.in_use, 30,
                 "spec replica pool quiescent")
        spec_srv.engine.audit_quiescent()
    finally:
        spec_srv.close()
    print("-- fault 7: spec replica's draft poisoned (NaN logits); pass "
          "degraded to non-spec, token-identical, fallback counted "
          "(fallbacks=%d, accepted=%d after recovery)"
          % (spec_srv.engine.spec_fallbacks,
             spec_srv.engine.spec_accepted_tokens))
    telemetry.flight().dump("phase_spec_poison")

    # -- leak audit: every pool quiescent, incl. the crashed engines --------
    stop_sweep.set()
    engines = ([rep.engine for i, rep in enumerate(srv.replicas)
                if not srv._circuit_open[i]]
               + list(srv._retired_engines))
    deadline = time.time() + 60
    while any(e.cache.pool.in_use for e in engines) \
            and time.time() < deadline:
        time.sleep(0.05)
    for eng in engines:
        eng.audit_quiescent()
    print("== assert_quiescent clean on %d engines (%d retired corpses "
          "included): zero leaked blocks" % (len(engines),
                                             len(srv._retired_engines)))
    srv.close()

    # -- postmortem: every injected fault on the merged timeline ------------
    telemetry.flight().dump("chaos_drill_end")
    spec = importlib.util.spec_from_file_location(
        "postmortem", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "postmortem.py"))
    pm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pm)
    text = pm.render(pm.load_dumps([flight_dir]))
    missing = [f for f in SERVE_FAULTS if f not in text]
    assert not missing, (
        "postmortem timeline is missing injected faults: %r" % missing)
    assert "FAULT" in text
    # ISSUE 13: the pinned failover victims' LIFECYCLES are on the same
    # timeline as the faults that moved them — the hop event names the
    # original request, and the replay's finish closes it out under the
    # SAME trace id (the timeline answers "what happened to THAT
    # request", not just "what broke")
    assert "request.failover" in text, text[-2000:]
    assert "request.finish" in text, text[-2000:]
    for victim in (req_kill, req_poison):
        assert ("request=%d" % victim.id) in text, (
            "pinned victim %d's failover event missing from the "
            "postmortem timeline" % victim.id)
        assert victim.trace in text, (
            "pinned victim %d's trace id missing from the postmortem "
            "timeline" % victim.id)
    print("== postmortem: all %d injected fault kinds + the pinned "
          "victims' request lifecycles on the merged timeline (%s)"
          % (len(SERVE_FAULTS), flight_dir))
    # the JSONL request ledger carries both victims' lifecycles under
    # ONE trace id across the hop: queued on the victim replica,
    # finish on the rescue path
    import json as _json
    with open(request_log) as fh:
        recs = [_json.loads(ln) for ln in fh if ln.strip()]
    for victim in (req_kill, req_poison):
        events = [r["event"] for r in recs
                  if r.get("trace") == victim.trace]
        for needed in ("queued", "failover", "finish"):
            assert needed in events, (
                "request log lost victim %d's %r event (has %r)"
                % (victim.id, needed, events))
    print("== request log: %d lifecycle events, victims' lifecycles "
          "trace-connected across the hop (%s)"
          % (len(recs), request_log))
    print("== OK: availability %.1f%%, failover token-identical, pools "
          "quiescent, faults accounted for" % (100 * availability))
    return 0


if __name__ == "__main__":
    sys.exit(main())
