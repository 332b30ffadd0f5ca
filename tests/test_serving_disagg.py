"""Disaggregated prefill/decode serving tests (ISSUE 17): replica
roles, planned KV migration over the replay transport, and graceful
degradation back to co-scheduled serving.

Load-bearing claims:
* a role-less fleet is byte-for-byte unchanged — no role labels, no
  role gauges, no migration keys in its /statusz fleet block;
* a prompt prefilled on a prefill replica and decoded on a decode
  replica is greedy-token-identical to the single-replica oracle —
  including tp!=tp hops, COW-divergent prefixes, and a migration
  racing the target's drain — finished exactly once, with ONE
  connected trace row across the hop;
* migration spends no failover budget, keeps the client's anchors
  (deadline, tenant, priority, submit time), and is SLO-classified
  exactly once: `submitted == goodput + slow + shed + expired +
  failed` survives every hop;
* the target's prefix-cache hits are priced into a per-hop
  bytes-saved ledger (`serving_migration_bytes_saved_total`);
* role loss degrades to co-scheduled serving (flags switch placement,
  never logits), and the autoscaler maps TTFT burn to prefill
  replicas, ITL burn to decode replicas.
"""
import threading
import time

import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.serving.autoscale import Autoscaler, AutoscaleConfig
from mxnet_tpu.serving.router import serving_roles
from mxnet_tpu.serving.scheduler import Request, QueueFull, make_resume
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


def arith_prompt(start, stride, n, vocab=48):
    return [(start + stride * t) % vocab for t in range(n)]


def oracle_tokens(tiny_lm, prompt, max_new, **kw):
    """The undisturbed single-replica greedy rollout every migrated
    request must match."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8, **kw)
    try:
        return srv.generate(list(prompt), max_new_tokens=max_new,
                            timeout=120)
    finally:
        srv.close()


def disagg_fleet(tiny_lm, roles="prefill:1,decode:1", **kw):
    params, cfg = tiny_lm
    kw.setdefault("max_batch", 4)
    kw.setdefault("block_size", 8)
    return serving.serve((params, cfg), roles=roles, **kw)


def count_finishes(req):
    """Wrap req._finish to count invocations (the exactly-once pin)."""
    calls = {"n": 0}
    real = req._finish

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    req._finish = counting
    return calls


def _token_identity(tok):
    assert tok["submitted"] == (tok["goodput"] + tok["slow"]
                                + tok["shed"] + tok["expired"]
                                + tok["failed"]), tok


# ---------------------------------------------------------------------------
# unit layer: role spec parsing + migrate-flavored resume construction
# ---------------------------------------------------------------------------


def test_serving_roles_parser(monkeypatch):
    assert serving_roles("prefill:1,decode:2") == \
        {"prefill": 1, "decode": 2}
    assert serving_roles(" decode:3 , prefill:1 ") == \
        {"decode": 3, "prefill": 1}
    # a role at 0 is dropped; the layout keeps the named ones
    assert serving_roles("prefill:0,decode:2") == {"decode": 2}
    assert serving_roles({"prefill": 2}) == {"prefill": 2}
    # unset / empty -> role-less fleet
    monkeypatch.delenv("MXNET_SERVING_ROLES", raising=False)
    assert serving_roles() is None
    assert serving_roles("") is None
    # env read only when no explicit spec
    monkeypatch.setenv("MXNET_SERVING_ROLES", "prefill:1,decode:1")
    assert serving_roles() == {"prefill": 1, "decode": 1}
    with pytest.raises(mx.MXNetError, match="unknown serving role"):
        serving_roles("prefil:1")
    with pytest.raises(mx.MXNetError, match="bad count"):
        serving_roles("prefill:two")
    with pytest.raises(mx.MXNetError, match="zero replicas"):
        serving_roles("prefill:0,decode:0")
    with pytest.raises(mx.MXNetError, match="role:count"):
        serving_roles("prefill")


def test_make_resume_migrate_spends_no_failover_budget():
    orig = Request([1, 2, 3], max_new_tokens=8, eos_id=7,
                   deadline_ms=5000.0, tenant="acme", priority=2)
    resume, carried = make_resume(orig, [1, 2, 3, 4, 5], max_len=64,
                                  migrate=True)
    assert carried == 2
    assert resume.prompt == [1, 2, 3, 4, 5]
    assert resume.max_new_tokens == 6
    # the planned hop is not a fault: no failover budget spent, but the
    # request is flagged as admitted-work-in-motion (brownout-exempt)
    assert resume.failovers == 0
    assert resume.migrated is True
    assert orig.migrated is False
    # client identity survives the hop intact
    assert resume.tenant == "acme" and resume.priority == 2
    assert resume.t_deadline == orig.t_deadline
    assert resume.trace == orig.trace
    # a migrated request that later FAILS OVER burns budget normally
    # and stays marked migrated
    resume2, _ = make_resume(resume, [1, 2, 3, 4, 5, 6], max_len=64)
    assert resume2.failovers == 1
    assert resume2.migrated is True


# ---------------------------------------------------------------------------
# roles-off: byte-for-byte today's fleet
# ---------------------------------------------------------------------------


def test_roles_off_fleet_unchanged(tiny_lm, monkeypatch):
    monkeypatch.delenv("MXNET_SERVING_ROLES", raising=False)
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), replicas=2, max_batch=2,
                        block_size=8)
    try:
        assert srv._roles is None
        assert srv._role == [None, None]
        out = srv.generate(arith_prompt(3, 2, 6), max_new_tokens=4,
                           timeout=120)
        assert len(out) == 4
        # no role labels anywhere, no migration/role fleet keys
        for h in srv.health()["replicas"]:
            assert "role" not in h
        stz = srv.statusz()
        assert "roles" not in stz["fleet"]
        assert "migrations" not in stz["fleet"]
        for body in stz["replicas"]:
            assert "role" not in body
        assert "serving_role_" not in srv.prometheus_text()
        # no hand-off hook installed: nothing migrates
        for rep in srv.replicas:
            assert rep.role is None
            assert rep.on_prefill_done is None
            assert rep.metrics.migrations == 0
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# the migration hop: token identity, exactly-once, one trace row
# ---------------------------------------------------------------------------


def test_migration_token_identity_and_single_trace(tiny_lm, tmp_path):
    prompt, max_new = arith_prompt(3, 2, 12), 8
    want = oracle_tokens(tiny_lm, prompt, max_new)
    fleet = disagg_fleet(tiny_lm)
    try:
        req = fleet.submit(prompt, max_new_tokens=max_new)
        calls = count_finishes(req)
        got = req.result(timeout=120)
        assert got == want, "migrated rollout diverged from the oracle"
        assert calls["n"] == 1
        # the hop is visible: submitted on the prefill replica,
        # completed + the migration on the decode replica
        pre, dec = fleet.replicas
        assert pre.role == "prefill" and dec.role == "decode"
        assert pre.metrics.submitted == 1 and dec.metrics.submitted == 0
        assert dec.metrics.completed == 1
        assert dec.metrics.migrations == 1
        assert dec.metrics.migration_tokens >= 1
        # no failover budget was spent on the planned hop
        assert pre.metrics.failovers == 0
        assert dec.metrics.failovers == 0
        # ONE connected trace row across the hop: prefill-side spans,
        # the hop annotation, and decode-side spans share the trace id
        names = [s["name"] for s in telemetry.spans(trace=req.trace)]
        assert "serving.migration_hop" in names
        assert "serving.prefill" in names
        assert "serving.token" in names
        doc = telemetry.export_perfetto(str(tmp_path / "migr.json"))
        evs = [e for e in doc["traceEvents"]
               if e["ph"] == "X" and e["args"].get("trace") == req.trace]
        assert len({e["tid"] for e in evs}) == 1
    finally:
        fleet.close()


def test_migration_ledger_classified_exactly_once(tiny_lm):
    fleet = disagg_fleet(tiny_lm)
    try:
        for i in range(3):
            fleet.generate(arith_prompt(1 + i, 2, 8),
                           max_new_tokens=4, timeout=120)
        stz = fleet.statusz()
        _token_identity(stz["fleet"]["tokens"])
        agg = fleet.snapshot()["aggregate"]["requests"]
        # each client counted submitted exactly once (on the prefill
        # replica) and terminal exactly once (on the decode replica)
        assert agg["submitted"] == 3
        assert agg["completed"] == 3
        assert agg["migrations"] == 3
        assert stz["fleet"]["migrations"] == 3
    finally:
        fleet.close()


def test_migration_bytes_saved_by_target_cache_hits(tiny_lm):
    prompt = arith_prompt(5, 1, 24)
    fleet = disagg_fleet(tiny_lm, paged=True, prefix_cache=True,
                         prefill_chunk=8)
    try:
        a = fleet.generate(list(prompt), max_new_tokens=6, timeout=120)
        # the second hop replays a prompt whose prefix the decode
        # replica's cache already holds: bytes-saved must be accounted
        b = fleet.generate(list(prompt), max_new_tokens=6, timeout=120)
        assert a == b
        stz = fleet.statusz()["fleet"]
        assert stz["migrations"] == 2
        saved = stz["migration_bytes_saved"]
        dec = fleet.replicas[1]
        per_tok = dec.engine.kv_bytes_per_token()
        assert per_tok > 0
        # at least the shared full blocks of the 24-token prompt were
        # skipped, priced at the TARGET engine's KV layout
        assert saved >= 2 * dec.engine.cache.block_size * per_tok
        assert saved % per_tok == 0
        assert dec.metrics.migration_bytes_saved == saved
    finally:
        fleet.close()


def test_cow_divergent_prefix_migration(tiny_lm):
    base = arith_prompt(5, 1, 20)
    fork_a = base + [7, 9, 11, 13]
    fork_b = base + [8, 10, 12, 14]      # diverges mid-block
    want_a = oracle_tokens(tiny_lm, fork_a, 6, paged=True)
    want_b = oracle_tokens(tiny_lm, fork_b, 6, paged=True)
    fleet = disagg_fleet(tiny_lm, paged=True, prefix_cache=True,
                         prefill_chunk=8)
    try:
        got_a = fleet.generate(list(fork_a), max_new_tokens=6,
                               timeout=120)
        got_b = fleet.generate(list(fork_b), max_new_tokens=6,
                               timeout=120)
        assert got_a == want_a and got_b == want_b
        assert fleet.statusz()["fleet"]["migrations"] == 2
    finally:
        fleet.close()


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="tp!=tp hop needs >= 4 (emulated) devices")
def test_tp_mismatched_migration_hop(tiny_lm):
    prompt, max_new = arith_prompt(3, 2, 10), 6
    want = oracle_tokens(tiny_lm, prompt, max_new)
    fleet = disagg_fleet(tiny_lm, paged=True,
                         role_kwargs={"decode": {"tp": 2}})
    try:
        pre, dec = fleet.replicas
        assert pre.engine.tp == 1
        assert dec.engine.tp == 2, dec.engine.tp_fallback
        got = fleet.generate(list(prompt), max_new_tokens=max_new,
                             timeout=120)
        # the tp flag switches placement, never logits — even across
        # a tp=1 -> tp=2 migration hop
        assert got == want
        assert dec.metrics.migrations == 1
    finally:
        fleet.close()


def test_migration_racing_target_drain(tiny_lm):
    """The hop lands, then the decode replica wedges mid-decode: the
    request fails over BACK onto the survivor (the prefill replica) and
    still finishes token-identically, exactly once."""
    prompt, max_new = arith_prompt(3, 2, 6), 6
    want = oracle_tokens(tiny_lm, prompt, max_new)
    fleet = disagg_fleet(tiny_lm, max_batch=2)
    hold = None
    try:
        dec = fleet.replicas[1]
        real = dec.engine.decode_pass
        parked, hold = threading.Event(), threading.Event()
        state = {"n": 0}

        def parking(*args, **kw):
            out = real(*args, **kw)
            state["n"] += 1
            if state["n"] == 2:
                parked.set()
                hold.wait()
            return out

        dec.engine.decode_pass = parking
        req = fleet.submit(prompt, max_new_tokens=max_new)
        calls = count_finishes(req)
        assert parked.wait(timeout=60)
        dec._last_beat -= 999.0
        h = fleet.health()               # sweep: drain + failover
        assert fleet._drained[1] is True and h["ok"] is True
        got = req.result(timeout=120)
        assert got == want
        assert calls["n"] == 1
        # one planned hop + one fault hop, each accounted where it ran
        assert dec.metrics.migrations == 1
        assert fleet.replicas[0].metrics.failovers == 1
        hold.set()
        deadline = time.time() + 60
        while dec.engine.cache.pool.in_use and time.time() < deadline:
            time.sleep(0.02)
        assert dec.engine.cache.pool.in_use == 0
        assert calls["n"] == 1
    finally:
        if hold is not None:
            hold.set()
        fleet.close()


# ---------------------------------------------------------------------------
# graceful degradation: role loss -> co-scheduled serving
# ---------------------------------------------------------------------------


def test_role_loss_falls_back_to_co_scheduled(tiny_lm):
    prompt, max_new = arith_prompt(3, 2, 8), 5
    want = oracle_tokens(tiny_lm, prompt, max_new)
    fleet = disagg_fleet(tiny_lm)
    try:
        # retire the LAST decode replica (the tail): the fleet is now
        # prefill-only and must keep serving, decoding locally
        assert fleet.scale_down() is not None
        assert [r.role for r in fleet.replicas] == ["prefill"]
        got = fleet.generate(list(prompt), max_new_tokens=max_new,
                             timeout=120)
        assert got == want
        assert fleet.replicas[0].metrics.migrations == 0
        assert fleet.statusz()["fleet"]["migrations"] == 0
        roles = fleet.statusz()["fleet"]["roles"]
        assert "decode" not in roles
    finally:
        fleet.close()


def test_saturated_decode_target_reattaches_locally(tiny_lm):
    """A hand-off the decode replica refuses (QueueFull) re-attaches
    on the source and decodes co-scheduled — no lost request, no
    double finish."""
    prompt, max_new = arith_prompt(3, 2, 8), 5
    want = oracle_tokens(tiny_lm, prompt, max_new)
    fleet = disagg_fleet(tiny_lm)
    try:
        dec = fleet.replicas[1]

        def refuse(req):
            raise QueueFull("scripted saturation")

        dec.adopt = refuse
        req = fleet.submit(prompt, max_new_tokens=max_new)
        calls = count_finishes(req)
        got = req.result(timeout=120)
        assert got == want
        assert calls["n"] == 1
        # nothing migrated; the prefill replica finished its own work
        assert dec.metrics.migrations == 0
        assert fleet.replicas[0].metrics.completed == 1
        _token_identity(fleet.statusz()["fleet"]["tokens"])
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# observability + per-role autoscaling
# ---------------------------------------------------------------------------


def test_role_observability_surfaces(tiny_lm):
    fleet = disagg_fleet(tiny_lm, roles="prefill:1,decode:2")
    try:
        fleet.generate(arith_prompt(2, 3, 6), max_new_tokens=3,
                       timeout=120)
        stz = fleet.statusz()
        assert stz["fleet"]["roles"] == {
            "prefill": {"replicas": 1, "healthy": 1},
            "decode": {"replicas": 2, "healthy": 2}}
        roles_seen = [b.get("role") for b in stz["replicas"]]
        assert roles_seen == ["prefill", "decode", "decode"]
        for h in fleet.health()["replicas"]:
            assert h["role"] in ("prefill", "decode")
        import re
        text = fleet.prometheus_text()
        m = re.search(r'serving_role_prefill_replicas\{[^}]*'
                      r'replica="router"[^}]*\} (\d+)', text)
        assert m and int(m.group(1)) == 1, m
        m = re.search(r'serving_role_decode_replicas\{[^}]*'
                      r'replica="router"[^}]*\} (\d+)', text)
        assert m and int(m.group(1)) == 2, m
        assert "serving_migration_total" in text
        assert "serving_migration_bytes_saved_total" in text
        # the console renders a role column + the migration ledger
        from tools import fleet_top
        frame = fleet_top.render(fleet.health(), stz, fleet.snapshot())
        assert "role" in frame and "prefill" in frame
        assert "migrations" in frame
    finally:
        fleet.close()


class _FakeRoleRouter:
    def __init__(self, roles=None):
        self._closed = False
        self._roles = roles
        self.replicas = ["p", "d"]
        self.up_roles = []

    def scale_up(self, role=None):
        self.up_roles.append(role)
        self.replicas.append(role or "x")
        return self.replicas[-1]

    def scale_down(self):
        return None


def _burns(rate, total=10, windows=(60, 300)):
    return {w: {"rate": rate, "good": max(0, total - 1),
                "total": total, "span_s": float(w)} for w in windows}


def test_autoscaler_scales_the_burning_role():
    r = _FakeRoleRouter(roles={"prefill": 1, "decode": 1})
    sc = Autoscaler(r, config=AutoscaleConfig(
        min_replicas=1, max_replicas=8, cooldown_s=0.0))
    sc.fleet_load_tokens = lambda: 100
    # TTFT burning, ITL quiet -> prompt pressure -> prefill replica
    sc.burn_rates = lambda objective="ttft": (
        _burns(5.0) if objective == "ttft" else {})
    assert sc.step(now=0.0) == "up"
    assert r.up_roles == ["prefill"]
    # ITL burning -> decode pressure -> decode replica (decode wins
    # even when both burn)
    sc.burn_rates = lambda objective="ttft": _burns(5.0)
    assert sc.step(now=1.0) == "up"
    assert r.up_roles == ["prefill", "decode"]
    # a scripted NO-ARG burn stub (the PR 16 drill shape) still works:
    # the itl probe degrades gracefully and ttft burn picks prefill
    sc.burn_rates = lambda: _burns(5.0)
    assert sc.step(now=2.0) == "up"
    assert r.up_roles == ["prefill", "decode", "prefill"]
    # role-less router: role stays None end to end
    r2 = _FakeRoleRouter(roles=None)
    sc2 = Autoscaler(r2, config=AutoscaleConfig(
        min_replicas=1, max_replicas=8, cooldown_s=0.0))
    sc2.fleet_load_tokens = lambda: 100
    sc2.burn_rates = lambda: _burns(5.0)
    assert sc2.step(now=0.0) == "up"
    assert r2.up_roles == [None]


def test_respawned_replica_keeps_its_role(tiny_lm):
    fleet = disagg_fleet(tiny_lm, respawn_backoff=0.02)
    try:
        dec = fleet.replicas[1]
        # kill the decode replica's loop the way a crash does
        dec._died = True
        deadline = time.time() + 60
        while fleet.replicas[1] is dec and time.time() < deadline:
            fleet.health()
            time.sleep(0.05)
        fresh = fleet.replicas[1]
        assert fresh is not dec
        assert fresh.role == "decode"
        assert fresh.on_prefill_done is None      # hook is prefill-only
        # and it still receives migrations
        out = fleet.generate(arith_prompt(4, 3, 8), max_new_tokens=4,
                             timeout=120)
        assert len(out) == 4
        assert fresh.metrics.migrations == 1
    finally:
        fleet.close()
