"""Self-healing serving fleet tests (ISSUE 11): in-flight request
failover, replica supervision/respawn with crash-loop circuit breaking,
deadline enforcement + brownout shedding, and the block-pool leak audit.

Load-bearing claims:
* an in-flight request re-homed off a wedged/dead replica completes
  TOKEN-IDENTICALLY to an undisturbed run (greedy decode is a pure
  function of the token history; the replay re-prefills prompt +
  generated-so-far), exactly once — the drain/restore race cannot
  double-serve it;
* a dead replica is respawned (fresh engine + pool) and serves again; a
  crash-looping one opens its circuit after MXNET_REPLICA_RESPAWN_MAX
  lives and the fleet keeps serving on the survivors;
* deadlines shed at admission (computed Retry-After) and at scheduling
  (dropped before prefill, HTTP 504); brownout sheds the lowest
  priority class first and clamps max_new_tokens, never logits;
* `BlockPool.assert_quiescent` names leaked blocks; the dead replica's
  blocks return to its pool.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import serving
from mxnet_tpu.serving.kv_cache import BlockPool
from mxnet_tpu.serving.scheduler import (Scheduler, Request, QueueFull,
                                         BrownoutShed, DeadlineExceeded,
                                         DeadlineUnmeetable, make_resume)
from mxnet_tpu.utils import chaos
from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params)


def tiny_cfg(**kw):
    base = dict(vocab=48, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                max_len=64)
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = tiny_cfg()
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


@pytest.fixture(autouse=True)
def _chaos_reset():
    yield
    chaos.reset()


def arith_prompt(start, stride, n, vocab=48):
    return [(start + stride * t) % vocab for t in range(n)]


def oracle_tokens(tiny_lm, prompt, max_new):
    """The undisturbed greedy rollout every failover leg must match."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8)
    try:
        return srv.generate(list(prompt), max_new_tokens=max_new,
                            timeout=120)
    finally:
        srv.close()


def count_finishes(req):
    """Wrap req._finish to count invocations (the exactly-once pin)."""
    calls = {"n": 0}
    real = req._finish

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    req._finish = counting
    return calls


def park_after_decodes(rep, n_calls):
    """Patch a replica's engine so its serving thread parks INSIDE the
    decode seam after `n_calls` decode passes (the tokens of every step
    but the one in flight already appended) — the wedged-mid-generation
    shape. Returns (parked, hold)."""
    real = rep.engine.decode_pass
    parked, hold = threading.Event(), threading.Event()
    state = {"n": 0}

    def parking(*args, **kw):
        out = real(*args, **kw)
        state["n"] += 1
        if state["n"] == n_calls:
            parked.set()
            hold.wait()
        return out

    rep.engine.decode_pass = parking
    return parked, hold


# ---------------------------------------------------------------------------
# unit layer: leak audit + resume construction
# ---------------------------------------------------------------------------


def test_block_pool_assert_quiescent_lists_leaks():
    pool = BlockPool(8)
    pool.assert_quiescent()                       # empty pool is clean
    ids = pool.try_alloc(3)
    with pytest.raises(mx.MXNetError, match="leaked block"):
        pool.assert_quiescent()
    try:
        pool.assert_quiescent()
    except mx.MXNetError as e:                    # the ids are NAMED
        for b in ids:
            assert str(b) in str(e)
    # cache-resident blocks at refcount exactly 1 are quiescent ...
    pool.free(ids[1:])
    pool.assert_quiescent(cache_resident=[ids[0]])
    # ... but an extra pin on a resident is a phantom reader
    pool.add_ref([ids[0]])
    with pytest.raises(mx.MXNetError, match="leaked block"):
        pool.assert_quiescent(cache_resident=[ids[0]])
    pool.free([ids[0]])
    pool.free([ids[0]])
    pool.assert_quiescent()


def test_make_resume_carries_generation_and_budget():
    orig = Request([1, 2, 3], max_new_tokens=8, eos_id=7,
                   deadline_ms=5000.0)
    # two tokens already generated: the replay prompt carries them and
    # the remaining budget shrinks accordingly
    resume, carried = make_resume(orig, [1, 2, 3, 4, 5], max_len=64)
    assert carried == 2
    assert resume.prompt == [1, 2, 3, 4, 5]
    assert resume.max_new_tokens == 6
    assert resume.eos_id == 7
    assert resume.failovers == 1
    # the deadline stays ABSOLUTE: the hop must not extend it
    assert resume.t_deadline == orig.t_deadline
    # generation already complete -> nothing to place
    done, carried = make_resume(orig, [1, 2, 3] + [9] * 8, max_len=64)
    assert done is None and carried == 8
    # eos already emitted -> nothing to place
    done, _ = make_resume(orig, [1, 2, 3, 9, 7], max_len=64)
    assert done is None


# ---------------------------------------------------------------------------
# in-flight failover: wedge mid-generation, token-identical continuation
# ---------------------------------------------------------------------------


def test_inflight_failover_token_identical(tiny_lm):
    params, cfg = tiny_lm
    prompt, max_new = arith_prompt(3, 2, 6), 6
    want = oracle_tokens(tiny_lm, prompt, max_new)
    srv = serving.serve((params, cfg), replicas=2, max_batch=2,
                        block_size=8)
    hold = None
    try:
        victim = srv.replicas[0]
        parked, hold = park_after_decodes(victim, 2)
        req = victim.submit(prompt, max_new_tokens=max_new)
        calls = count_finishes(req)
        assert parked.wait(timeout=60)
        # 2 tokens exist (prefill's first + the one step collected: the
        # second pass launched a step that is still in flight, and its
        # token goes with the wedge; the replay chooses it again); the
        # loop is parked mid-iteration and stops beating
        victim._last_beat -= 999.0
        h = srv.health()                 # sweep: drain + failover
        assert srv._drained[0] is True and h["ok"] is True
        got = req.result(timeout=120)
        assert got == want, "failover diverged from the oracle rollout"
        assert calls["n"] == 1
        # the failover is visible on the TARGET replica's ledger
        assert srv.replicas[1].metrics.failovers == 1
        assert srv.replicas[1].metrics.failover_resumed_tokens == 2
        assert srv.snapshot()["aggregate"]["failovers"] == 1
        # unpark: the wedged loop resumes, must NOT double-finish, and
        # must release the detached sequence's blocks
        hold.set()
        deadline = time.time() + 60
        while victim.engine.cache.pool.in_use and time.time() < deadline:
            time.sleep(0.02)
        assert victim.engine.cache.pool.in_use == 0
        assert calls["n"] == 1
        assert got == req.result(timeout=1)
    finally:
        if hold is not None:
            hold.set()
        srv.close()


def test_drain_restore_race_exactly_once(tiny_lm):
    """Satellite (ISSUE 11): a replica that is drained, re-homed, and
    RESTORED while the failover replay is still mid-prefill on the
    target must not serve the request a second time — admission is
    exactly-once, pinned by the finish-call count and the fact that the
    source loop only ever releases the detached sequence."""
    params, cfg = tiny_lm
    prompt, max_new = arith_prompt(5, 3, 7), 5
    want = oracle_tokens(tiny_lm, prompt, max_new)
    srv = serving.serve((params, cfg), replicas=2, max_batch=2,
                        block_size=8)
    hold = gate = None
    try:
        victim, target = srv.replicas
        parked, hold = park_after_decodes(victim, 2)
        # slow the TARGET's prefill so the replay is observably mid-
        # flight while the victim is restored
        real_start = target.engine.start
        gate = threading.Event()
        in_prefill = threading.Event()

        def gated_start(*a, **kw):
            in_prefill.set()
            gate.wait()
            return real_start(*a, **kw)

        target.engine.start = gated_start
        req = victim.submit(prompt, max_new_tokens=max_new)
        calls = count_finishes(req)
        assert parked.wait(timeout=60)
        victim._last_beat -= 999.0
        srv.health()                      # drain + start the failover
        assert srv._drained[0] is True
        assert in_prefill.wait(timeout=60), "replay never reached prefill"
        # mid-replay: the victim recovers and is RESTORED
        hold.set()
        deadline = time.time() + 60
        while srv._drained[0] and time.time() < deadline:
            time.sleep(0.02)
            srv.health()
        assert srv._drained[0] is False, "victim never restored"
        # the restored victim must not have re-run the request: its
        # loop released the detached sequence instead
        d2 = time.time() + 60
        while victim.engine.cache.pool.in_use and time.time() < d2:
            time.sleep(0.02)
        assert victim.engine.cache.pool.in_use == 0
        assert not req._event.is_set(), "finished while replay was gated"
        gate.set()                        # let the replay run
        assert req.result(timeout=120) == want
        assert calls["n"] == 1
        assert srv.snapshot()["router"]["metrics"][
            "serving_router_orphaned_total"]["value"] == 0
    finally:
        if hold is not None:
            hold.set()
        if gate is not None:
            gate.set()
        srv.close()


def test_orphaned_inflight_counted_and_failed_promptly(tiny_lm):
    """Satellite (ISSUE 11): when NO healthy replica can absorb a
    failover replay, the in-flight request fails PROMPTLY with a
    distinct error and lands on serving_router_orphaned_total — the
    pre-ISSUE-11 silent wait-for-timeout was an invisible outage."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), replicas=2, max_batch=2,
                        block_size=8)
    hold = None
    try:
        victim, other = srv.replicas

        def full_adopt(req):
            raise QueueFull("saturated")

        other.adopt = full_adopt
        parked, hold = park_after_decodes(victim, 2)
        req = victim.submit(arith_prompt(2, 1, 5), max_new_tokens=6)
        assert parked.wait(timeout=60)
        victim._last_beat -= 999.0
        t0 = time.perf_counter()
        srv.health()
        with pytest.raises(mx.MXNetError, match="orphaned"):
            req.result(timeout=5)
        assert time.perf_counter() - t0 < 5.0, "orphan not failed promptly"
        assert srv.snapshot()["router"]["metrics"][
            "serving_router_orphaned_total"]["value"] == 1
    finally:
        if hold is not None:
            hold.set()
        srv.close()


# ---------------------------------------------------------------------------
# supervision: dead replicas respawn; crash loops open the circuit
# ---------------------------------------------------------------------------


def test_dead_replica_failover_then_respawn_serves_again(tiny_lm):
    params, cfg = tiny_lm
    prompt, max_new = arith_prompt(4, 1, 6), 6
    want = oracle_tokens(tiny_lm, prompt, max_new)
    srv = serving.serve((params, cfg), replicas=2, max_batch=2,
                        block_size=8, respawn_backoff=0.01)
    hold = None
    try:
        victim = srv.replicas[0]
        parked, hold = park_after_decodes(victim, 2)
        req = victim.submit(prompt, max_new_tokens=max_new)
        assert parked.wait(timeout=60)

        # kill the loop OUTSIDE the engine-fault isolation: evict raises
        def bomb(engine):
            raise RuntimeError("injected loop death")

        victim.scheduler.evict = bomb
        hold.set()                        # loop resumes straight into it
        victim._thread.join(timeout=60)
        assert victim._died is True
        # the death hook already failed the request OVER (on the dying
        # thread, no sweep needed) and released the dead engine's blocks
        assert req.result(timeout=120) == want
        assert victim.engine.cache.pool.in_use == 0
        # next sweep respawns: fresh engine + pool, back in rotation
        deadline = time.time() + 60
        while srv.replicas[0] is victim and time.time() < deadline:
            srv.health()
            time.sleep(0.02)
        assert srv.replicas[0] is not victim, "dead replica not respawned"
        srv._retired_engines[0].audit_quiescent()   # leak check on the corpse
        snap = srv.snapshot()
        assert snap["aggregate"]["respawns"] == 1
        assert snap["router"]["metrics"][
            "serving_respawn_total"]["value"] == 1
        # the respawned replica takes and serves traffic
        srv.replicas[1].load_tokens = lambda: 10 ** 9
        out = srv.generate(arith_prompt(7, 1, 5), max_new_tokens=3,
                           timeout=120)
        assert len(out) == 3
        assert srv.replicas[0].metrics.completed >= 1
        h = srv.health()
        assert h["ok"] is True and h["replicas_healthy"] == 2
    finally:
        if hold is not None:
            hold.set()
        srv.close()


# -- a fault with a decode step in flight (ISSUE 30) ---------------------------
#
# From its second decoding pass on the loop holds a step that is launched
# and not collected. Whatever ends the pass must drop or collect it so that
# a sequence's tokens are exactly those of collected steps: then a replay
# chooses the dropped step's tokens again and none is lost or doubled.

IN_FLIGHT = [(arith_prompt(3, 2, 6), 9), (arith_prompt(4, 1, 9), 6),
             (arith_prompt(5, 3, 7), 12)]


def kill_at_evict(srv):
    """The loop dies OUTSIDE the engine-fault isolation, in the eviction
    that follows its fourth collected step."""
    real, calls = srv.scheduler.evict, {"n": 0}

    def bomb(engine):
        calls["n"] += 1
        if calls["n"] == 4:
            raise RuntimeError("injected loop death")
        return real(engine)

    srv.scheduler.evict = bomb


FAULTS = {
    # the chaos seam raises where the next step would be launched
    "decode_poison": (1, lambda srv: chaos.configure(serve_poison=(0, 4)),
                      "fault"),
    # the read of the step in flight fails (what an error on the device
    # surfaces as): it and the step launched behind it are both dropped
    "collect_fails": (1, lambda srv: fail_read(srv.engine, 3), "fault"),
    # the read of a first token in flight fails (ISSUE 46): its prefill had
    # the pools, and so has the step launched behind it: everything replays
    "first_read_fails": (1, lambda srv: fail_read(srv.engine, 3,
                                                  "serving.prefill"),
                         "fault"),
    "killed_loop_rescued": (2, lambda srv: kill_at_evict(srv.replicas[0]),
                            "fault"),
    "killed_loop_alone": (1, kill_at_evict, "fault"),
    "close_drains": (1, lambda srv: None, None),
}


def fail_read(eng, at, of="serving.decode"):
    """The `at`-th read of a step's tokens (or, `of` = "serving.prefill",
    of a whole-prompt prefill's first token) raises."""
    real, calls = eng._read_back, {"n": 0}

    def read_back(span, result, stats):
        if span.name == of:
            calls["n"] += 1
            if calls["n"] == at:
                raise RuntimeError("injected fault in the read")
        return real(span, result, stats)

    eng._read_back = read_back


@pytest.mark.parametrize("case", sorted(FAULTS))
def test_a_fault_with_a_step_in_flight_loses_and_doubles_no_token(tiny_lm,
                                                                  case):
    replicas, arm, drain = FAULTS[case]
    want = [oracle_tokens(tiny_lm, p, n) for p, n in IN_FLIGHT]
    srv = serving.serve(tiny_lm, replicas=replicas, max_batch=4,
                        block_size=8, respawn_backoff=0.01) \
        if replicas > 1 else serving.serve(tiny_lm, max_batch=4, block_size=8)
    victim = srv.replicas[0] if replicas > 1 else srv
    engine = victim.engine
    try:
        arm(srv)
        reqs = [victim.submit(p, max_new_tokens=n) for p, n in IN_FLIGHT]
        calls = [count_finishes(r) for r in reqs]
        if case == "close_drains":
            deadline = time.time() + 60
            while victim.metrics.tokens_generated < 4:
                assert time.time() < deadline
                time.sleep(0.002)
            srv.close(drain=True)       # its audit: no block leaked
            assert victim._flight is None
        if case == "killed_loop_alone":
            for r in reqs:              # nobody to rescue them: failed, once
                with pytest.raises(mx.MXNetError, match="loop died"):
                    r.result(timeout=120)
        else:
            assert [r.result(timeout=120) for r in reqs] == want
        assert [c["n"] for c in calls] == [1, 1, 1]
        snap = victim.snapshot()
        assert snap["throughput"]["decode_steps_ahead"] >= 2
        if drain is not None:
            assert snap["throughput"]["decode_drains"][drain] == 1
            assert victim._flight is None
        deadline = time.time() + 60
        while engine.cache.pool.in_use and time.time() < deadline:
            time.sleep(0.01)
        assert engine.cache.pool.in_use == 0
        engine.audit_quiescent()
    finally:
        srv.close()


# -- a fault with a first token in flight (ISSUE 46) ---------------------------
#
# A whole-prompt prefill is launched and its first token read only behind
# the launch of the step that takes it on the device. What fails at that
# read had consumed the pools; what fails before it drops the token unread.

#: two that decode for long enough to be running still when the third comes
LATE = [(arith_prompt(3, 2, 6), 40), (arith_prompt(4, 1, 9), 44),
        (arith_prompt(5, 3, 7), 12)]


def running_and_late(srv, arm):
    """Two requests decoding, then `arm()`, then a third: its prefill is
    launched with a step in flight and rows that go on."""
    reqs = [srv.submit(p, max_new_tokens=n) for p, n in LATE[:2]]
    deadline = time.time() + 60
    while srv.metrics.tokens_generated < 4:
        assert time.time() < deadline
        time.sleep(0.002)
    arm()
    return reqs + [srv.submit(LATE[2][0], max_new_tokens=LATE[2][1])]


def test_a_fault_at_the_read_of_a_first_token_in_flight_replays_everything(
        tiny_lm):
    want = [oracle_tokens(tiny_lm, p, n) for p, n in LATE]
    srv = serving.serve(tiny_lm, max_batch=4, block_size=8)
    eng = srv.engine
    try:
        seen = {}
        real_replay = srv._replay_all

        def replay_all(err, req=None):
            seen.update(held=len(srv.scheduler.running), req=req,
                        firsts=len(eng._firsts), err=str(err))
            return real_replay(err, req)

        srv._replay_all = replay_all
        reqs = running_and_late(
            srv, lambda: fail_read(eng, 1, "serving.prefill"))
        calls = [count_finishes(r) for r in reqs]
        assert [r.result(timeout=120) for r in reqs] == want
        assert [c["n"] for c in calls] == [1, 1, 1]
        # the pools were made anew and all three sequences replayed, the
        # late one from its prompt alone: it was running, its token unread
        assert eng.pools_lost == 1 and not eng.cache.lost()
        assert (seen["held"], seen["req"], seen["firsts"]) == (3, None, 0)
        assert "PoolsLost" in seen["err"]
        snap = srv.snapshot()
        assert snap["requests"]["engine_failures"] == 1
        assert snap["requests"]["failovers"] == 3
        assert snap["requests"]["failed"] == 0
        assert snap["throughput"]["decode_drains"]["fault"] == 1
        # the failed read made no first token; the replays' three did
        # (one in flight at a time: of two admitted at once the last)
        th = snap["throughput"]
        assert th["prefills_ahead"] + th["prefill_syncs"].get(
            "more_admitted", 0) == 5
        deadline = time.time() + 60
        while eng.cache.pool.in_use and time.time() < deadline:
            time.sleep(0.01)
        eng.audit_quiescent()
    finally:
        srv.close()


def test_a_dropped_step_in_flight_drops_the_first_token_launched_into_it(
        tiny_lm):
    """The pass that admitted the late request fails before anything is
    read: `_drop_flight` forgets the step AND the first token in flight, so
    every sequence's tokens are exactly those of collected steps and
    prefills, and the replay chooses both dropped tokens again."""
    want = [oracle_tokens(tiny_lm, p, n) for p, n in LATE]
    srv = serving.serve(tiny_lm, max_batch=4, block_size=8)
    eng = srv.engine
    try:
        seen = {"replayed": {}}
        real_pass, real_replay = eng.decode_pass, srv._replay

        def decode_pass(seqs, after=None, **kw):
            if any(s.first is not None for s in seqs) and after is not None \
                    and "dropped" not in seen:
                seen["dropped"] = {id(s.request): list(s.tokens)
                                   for s in seqs}
                seen["in_flight"] = len(eng._firsts)
                raise RuntimeError("injected fault in the pass")
            return real_pass(seqs, after=after, **kw)

        def replay(req, tokens, err):
            seen["replayed"][id(req)] = list(tokens)
            seen["left"] = len(eng._firsts)
            return real_replay(req, tokens, err)

        def arm():
            eng.decode_pass, srv._replay = decode_pass, replay

        reqs = running_and_late(srv, arm)
        assert [r.result(timeout=120) for r in reqs] == want
        assert seen["in_flight"] == 1 and seen["left"] == 0
        assert eng.pools_lost == 0
        # what each was replayed from is what it had when the pass began:
        # the late one its prompt, the others every collected token
        assert seen["replayed"] == seen["dropped"]
        late = reqs[2]
        assert seen["replayed"][id(late)] == list(late.prompt)
        for r in reqs[:2]:
            assert len(seen["replayed"][id(r)]) > len(r.prompt)
        snap = srv.snapshot()["throughput"]
        assert snap["decode_drains"]["fault"] == 1
        deadline = time.time() + 60
        while eng.cache.pool.in_use and time.time() < deadline:
            time.sleep(0.01)
        eng.audit_quiescent()
    finally:
        srv.close()


def test_crash_loop_opens_circuit_fleet_survives(tiny_lm):
    """A replica whose every (re)spawned instance dies (chaos
    serve_crash_loop) exhausts its respawn budget, opens the circuit —
    reported distinctly in /healthz and the merged exposition — and the
    fleet keeps serving on the survivor."""
    params, cfg = tiny_lm
    chaos.configure(serve_crash_loop=(0, 3))
    srv = serving.serve((params, cfg), replicas=2, max_batch=2,
                        block_size=8, respawn_max=2,
                        respawn_backoff=0.01)
    try:
        deadline = time.time() + 120
        h = srv.health()
        while h["replicas_circuit_open"] != 1 and time.time() < deadline:
            time.sleep(0.05)
            h = srv.health()
        assert h["replicas_circuit_open"] == 1, "circuit never opened"
        assert h["replicas"][0]["circuit_open"] is True
        assert h["ok"] is True and h["degraded"] is True
        # it burned exactly its respawn budget
        snap = srv.snapshot()
        assert snap["router"]["metrics"][
            "serving_respawn_total"]["value"] == 2
        assert snap["router"]["metrics"][
            "serving_crash_loop_open"]["value"] == 1
        assert "serving_crash_loop_open" in srv.prometheus_text()
        # the survivor serves; the open circuit stays drained
        for i in range(3):
            assert len(srv.generate(arith_prompt(i, 1, 5),
                                    max_new_tokens=2, timeout=120)) == 2
        assert srv._drained[0] is True and srv._circuit_open[0] is True
    finally:
        srv.close()


def test_respawn_max_env_knob(tiny_lm, monkeypatch):
    monkeypatch.setenv("MXNET_REPLICA_RESPAWN_MAX", "5")
    assert serving.serving_respawn_max() == 5
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), replicas=2, max_batch=1,
                        block_size=8)
    try:
        assert srv.respawn_max == 5
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# deadlines: admission shed (computed Retry-After) + queue expiry (504)
# ---------------------------------------------------------------------------


def test_deadline_unmeetable_shed_at_admission(tiny_lm):
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8)
    try:
        # warm: establish an observed service rate (>= 8 decode steps)
        srv.generate(arith_prompt(1, 1, 4), max_new_tokens=10,
                     timeout=120)
        assert srv.metrics.observed_token_rate() is not None
        with pytest.raises(DeadlineUnmeetable) as ei:
            srv.submit(arith_prompt(2, 1, 4), max_new_tokens=8,
                       deadline_ms=0.001)
        assert ei.value.retry_after_s >= 1.0
        assert srv.metrics.deadline_shed == 1
        # a generous deadline admits and completes normally
        assert len(srv.submit(arith_prompt(2, 1, 4), max_new_tokens=3,
                              deadline_ms=60_000).result(120)) == 3
    finally:
        srv.close()


def test_deadline_shed_cold_server_never(tiny_lm):
    """No observed rate -> no shed: a cold server must not guess."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8)
    try:
        out = srv.submit(arith_prompt(1, 1, 4), max_new_tokens=2,
                         deadline_ms=120_000).result(timeout=120)
        assert len(out) == 2
    finally:
        srv.close()


def test_deadline_expired_in_queue_dropped_before_prefill(tiny_lm):
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8)
    hold = threading.Event()
    try:
        victim = srv
        parked = threading.Event()
        orig_admit = victim.scheduler.admit

        def stuck_admit(engine, now=None):
            parked.set()
            hold.wait()
            return orig_admit(engine, now)

        victim.scheduler.admit = stuck_admit
        victim._work.set()
        assert parked.wait(timeout=30)
        prefills_before = srv.metrics.prefill_chunks
        req = srv.submit(arith_prompt(1, 1, 5), max_new_tokens=4,
                         deadline_ms=30.0)
        time.sleep(0.1)                   # deadline passes in queue
        victim.scheduler.admit = orig_admit
        hold.set()
        with pytest.raises(DeadlineExceeded, match="deadline"):
            req.result(timeout=60)
        assert srv.metrics.deadline_shed == 1
        assert srv.metrics.prefill_chunks == prefills_before, \
            "prefill tokens were spent on an expired request"
    finally:
        hold.set()
        srv.close()


def test_deadline_http_contract(tiny_lm):
    """HTTP mapping: expired-in-queue -> 504; unmeetable-at-admission ->
    503 with the COMPUTED Retry-After."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8)
    hold = threading.Event()
    try:
        host, port = srv.serve_http(port=0, block=False)
        url = "http://%s:%d/v1/generate" % (host, port)

        def post(payload):
            return urllib.request.urlopen(urllib.request.Request(
                url, data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"}),
                timeout=60)

        # 504: park admission so the deadline passes in queue
        parked = threading.Event()
        orig_admit = srv.scheduler.admit

        def stuck_admit(engine, now=None):
            parked.set()
            hold.wait()
            return orig_admit(engine, now)

        srv.scheduler.admit = stuck_admit
        srv._work.set()
        assert parked.wait(timeout=30)
        results = {}

        def client():
            try:
                post({"tokens": [1, 2, 3], "max_new_tokens": 2,
                      "deadline_ms": 30.0})
                results["code"] = 200
            except urllib.error.HTTPError as e:
                results["code"] = e.code

        t = threading.Thread(target=client)
        t.start()
        time.sleep(0.2)
        srv.scheduler.admit = orig_admit
        hold.set()
        t.join(timeout=60)
        assert results["code"] == 504
        # 503 + Retry-After: warm the rate, then an impossible deadline
        post({"tokens": [1, 2, 3], "max_new_tokens": 10})
        with pytest.raises(urllib.error.HTTPError) as ei:
            post({"tokens": [1, 2, 3], "max_new_tokens": 8,
                  "deadline_ms": 0.001})
        assert ei.value.code == 503
        assert int(ei.value.headers["Retry-After"]) >= 1
    finally:
        hold.set()
        srv.close()


def test_deadline_expiry_not_shadowed_by_full_batch():
    """An expired-deadline corpse must be dropped even while the batch
    is saturated: it would otherwise hold a queue slot (inflating
    backpressure) and delay its 504 until a slot frees."""
    eng = _StubEngine()
    sched = Scheduler(max_batch=1)
    sched.running = [object()]            # batch full: nothing admits
    req = Request([1, 2, 3], max_new_tokens=4, deadline_ms=1.0)
    sched.submit(req)
    time.sleep(0.01)                      # deadline passes in queue
    admitted, expired = sched.admit(eng)
    assert admitted == [] and expired == [req]
    assert isinstance(req.error, DeadlineExceeded)
    assert sched.pending() == 0           # the corpse freed its slot
    assert sched.deadline_drops == 1


def test_brownout_never_sheds_or_clamps_failover_resumes():
    """A failover resume IS admitted work mid-generation: brownout must
    neither shed it (it would fail a response the client was already
    receiving) nor clamp it (silent truncation breaks token parity)."""
    eng = _StubEngine()
    sched = Scheduler(max_batch=4, max_queue=8, brownout=True,
                      brownout_after_s=0.0, brownout_max_new=2)
    lows = [Request([1, 2], max_new_tokens=16, priority=0)
            for _ in range(3)]
    highs = [Request([3, 4], max_new_tokens=16, priority=5)
             for _ in range(3)]
    resume = Request([1, 2, 9, 9], max_new_tokens=12, priority=0)
    resume.failovers = 1                  # marks it as a replay
    for r in lows + highs + [resume]:
        sched.submit(r)
    t0 = time.perf_counter()
    sched.running = [object()] * 4
    sched.admit(eng, now=t0)
    _, expired = sched.admit(eng, now=t0 + 0.01)
    assert sched.brownout_active is True
    assert resume not in expired          # lows shed, the resume spared
    assert all(r in expired for r in lows)
    sched.running = []
    admitted, _ = sched.admit(eng, now=t0 + 0.02)
    assert resume in admitted
    assert resume.max_new_tokens == 12    # never clamped
    clamped = [r for r in admitted if r is not resume]
    assert all(r.max_new_tokens == 2 for r in clamped)


def test_default_deadline_env_knob(tiny_lm, monkeypatch):
    params, cfg = tiny_lm
    monkeypatch.setenv("MXNET_SERVING_DEADLINE_MS", "45000")
    srv = serving.serve((params, cfg), max_batch=1, block_size=8)
    try:
        assert srv.default_deadline_ms == 45000.0
        req = srv.submit(arith_prompt(1, 1, 4), max_new_tokens=2)
        assert req.deadline_ms == 45000.0
        req.result(timeout=120)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# brownout: shed the lowest class first, clamp max_new, never touch logits
# ---------------------------------------------------------------------------


class _StubEngine:
    max_len = 64
    paged = False
    cache = None

    def can_admit(self, prompt_len, max_new):
        return True

    def prefill_tokens_per_step(self, prompt_len):
        return prompt_len


def test_brownout_sheds_lowest_class_then_clamps():
    eng = _StubEngine()
    sched = Scheduler(max_batch=2, max_queue=8, brownout=True,
                      brownout_after_s=0.0, brownout_max_new=2)
    lows = [Request([1, 2], max_new_tokens=16, priority=0)
            for _ in range(4)]
    highs = [Request([3, 4], max_new_tokens=16, priority=5)
             for _ in range(4)]
    for r in lows + highs:
        sched.submit(r)
    t0 = time.perf_counter()
    sched.running = [object(), object()]   # batch full: nothing admits
    sched.admit(eng, now=t0)               # saturation observed, not on
    assert sched.brownout_active is False
    admitted, expired = sched.admit(eng, now=t0 + 0.01)
    assert sched.brownout_active is True
    # the LOWEST class queued was shed, nothing admitted (batch full)
    shed = [r for r in expired if isinstance(r.error, BrownoutShed)]
    assert {r.priority for r in shed} == {0}
    assert len(shed) == 4 and sched.brownout_sheds == 4
    for r in shed:
        with pytest.raises(BrownoutShed):
            r.result(timeout=1)
    assert admitted == []
    # batch frees: the surviving high class admits, CLAMPED not denied
    sched.running = []
    admitted, _ = sched.admit(eng, now=t0 + 0.02)
    assert sched.brownout_active is True
    assert [r.priority for r in admitted] == [5, 5]
    assert all(r.max_new_tokens == 2 for r in admitted)
    # queue drained below the low watermark -> brownout disengages
    admitted, _ = sched.admit(eng, now=t0 + 0.03)
    assert sched.brownout_active is False
    assert all(r.max_new_tokens == 16 for r in admitted)


def test_brownout_single_class_clamps_without_shedding():
    """With ONE priority class queued, shedding 'the lowest class' would
    be a full outage — brownout must only clamp."""
    eng = _StubEngine()
    sched = Scheduler(max_batch=2, max_queue=8, brownout=True,
                      brownout_after_s=0.0, brownout_max_new=3)
    reqs = [Request([1, 2], max_new_tokens=16) for _ in range(6)]
    for r in reqs:
        sched.submit(r)
    t0 = time.perf_counter()
    sched.running = [object(), object()]
    sched.admit(eng, now=t0)
    _, expired = sched.admit(eng, now=t0 + 0.01)
    assert sched.brownout_active is True
    assert not any(isinstance(r.error, BrownoutShed) for r in expired)
    sched.running = []
    admitted, _ = sched.admit(eng, now=t0 + 0.02)
    assert admitted and all(r.max_new_tokens == 3 for r in admitted)


def test_brownout_env_knob(tiny_lm, monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_BROWNOUT", "1")
    sched = Scheduler(max_batch=2)
    assert sched.brownout is True
    monkeypatch.delenv("MXNET_SERVING_BROWNOUT")
    assert Scheduler(max_batch=2).brownout is False
