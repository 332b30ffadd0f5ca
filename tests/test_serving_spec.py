"""Speculative decoding tests (ISSUE 19): a draft model proposes k
tokens, the target scores all k+1 positions in ONE ragged paged pass,
and greedy verification accepts a prefix — so the spec flag switches
SPEED, never logits.

Load-bearing claims: (1) spec-on greedy output is token-identical AND
per-token-logit-identical to the non-speculative paged oracle at every
step — including across a failover replay hop, through a prefix-cache
hit, and on the tp=2 emulated mesh; (2) the rejection-sampling math is
exactly the target distribution (pinned against hand-computed
probabilities and a fixed-seed Monte Carlo run); (3) acceptance
bookkeeping is conservative (emitted <= batch*(k+1), accepted <=
proposed, token history == prefill + 1 + sum of emitted); (4) the spec
path adds exactly two jit sites ("serving.spec_score",
"serving.draft"), stays within a bounded signature lattice, and
warm-loads from the persistent AOT cache; (5) ineligible configs fall
back to the verbatim per-token decode with a recorded reason, flags
are frozen after construction; (6) the scheduler prices a speculating
sequence at k+1 tokens on BOTH the admission and the prefill-chunk
side, so speculation cannot starve chunked prefill under one token
budget; (7) a poisoned draft (NaN logits — the serve_spec_poison chaos
seam) degrades one pass to the non-speculative body, token-identical,
counted on `spec_fallbacks`.
"""
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import serving
from mxnet_tpu.serving.spec import (DraftLM, self_draft, greedy_verify,
                                    rejection_sample)
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


def make_engine(params, cfg, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("keep_logits", True)
    return serving.Engine(serving.TransformerLM(params, cfg), **kw)


def spec_engine(params, cfg, draft_layers=1, spec_k=3, **kw):
    kw.setdefault("paged", True)
    return make_engine(params, cfg, spec_k=spec_k,
                       draft=self_draft(params, cfg, draft_layers), **kw)


def drive(eng, prompts, max_new=16):
    """Roll every prompt to completion; returns (token_lists,
    per-sequence per-emitted-token f32 logit rows)."""
    seqs = [eng.start(list(p), max_new=max_new) for p in prompts]
    live = [s for s in seqs if not s.done]
    while live:
        eng.decode_step(live)
        live = [s for s in live if not s.done]
    toks = [list(s.tokens) for s in seqs]
    logs = [[np.asarray(r) for r in s.token_logits] for s in seqs]
    for s in seqs:
        eng.release(s)
    return toks, logs


# ---------------------------------------------------------------------------
# parity: spec-on == spec-off, token- and logit-identical
# ---------------------------------------------------------------------------


def test_spec_greedy_parity_f32(tiny_lm):
    """Mixed-length batch through the spec engine vs the verbatim paged
    oracle: identical tokens and identical per-emitted-token logits
    (f32 1e-5) at EVERY position — and the engine really speculated
    (multiple tokens per pass), so the parity is not vacuous."""
    params, cfg = tiny_lm
    prompts = [arith_prompt(1, 1, 9), arith_prompt(5, 2, 4),
               arith_prompt(7, 3, 13)]
    e_ref = make_engine(params, cfg, paged=True)
    t_ref, l_ref = drive(e_ref, prompts)
    e_spec = spec_engine(params, cfg)
    assert e_spec.spec, e_spec.spec_fallback
    t_spec, l_spec = drive(e_spec, prompts)
    assert t_spec == t_ref
    for ref_rows, spec_rows in zip(l_ref, l_spec):
        assert len(ref_rows) == len(spec_rows)
        for a, b in zip(ref_rows, spec_rows):
            np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-5)
    assert e_spec.spec_passes >= 1
    assert e_spec.spec_proposed_tokens >= e_spec.spec_passes
    # speculation actually bought multi-token passes somewhere
    total_gen = sum(len(t) for t in t_spec) - sum(len(p) for p in prompts)
    assert total_gen > e_spec.spec_passes + len(prompts)
    e_ref.close()
    e_spec.close()


def test_spec_greedy_parity_bf16(tiny_lm):
    """bf16 params/pools: same tokens, logits at dtype tolerance (both
    paths accumulate attention statistics in f32; the k+1-wide scoring
    pass is the only reduction-shape difference)."""
    params, cfg = tiny_lm
    bf16 = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    prompts = [arith_prompt(2, 1, 9), arith_prompt(3, 2, 5)]
    e_ref = make_engine(bf16, cfg, paged=True)
    t_ref, l_ref = drive(e_ref, prompts, max_new=10)
    e_spec = spec_engine(bf16, cfg)
    assert e_spec.spec, e_spec.spec_fallback
    t_spec, l_spec = drive(e_spec, prompts, max_new=10)
    # bf16 rounding differs between the 1-wide and the k+1-wide scoring
    # shapes, so a near-tie argmax can legitimately flip; compare
    # logits row-by-row while the token histories are still identical
    # (a flipped token changes the conditioning for every later row)
    # and require the streams to agree for at least a few tokens.
    for p, t_r, t_s, lr, ls_ in zip(prompts, t_ref, t_spec,
                                    l_ref, l_spec):
        agree = 0
        while agree < min(len(t_r), len(t_s)) \
                and t_r[agree] == t_s[agree]:
            agree += 1
        assert agree >= len(p) + 3, (t_r, t_s)
        for j in range(min(agree - len(p) + 1, len(lr), len(ls_))):
            np.testing.assert_allclose(ls_[j], lr[j],
                                       rtol=2e-2, atol=2e-2)
    e_ref.close()
    e_spec.close()


def test_spec_env_var_enablement(tiny_lm, monkeypatch):
    """MXNET_SPEC_DECODE / MXNET_SPEC_K / MXNET_SPEC_DRAFT_LAYERS reach
    a default-constructed engine (docs/ENV_VARS.md); explicit arguments
    win; everything is read at construction only."""
    params, cfg = tiny_lm
    monkeypatch.setenv("MXNET_SPEC_DECODE", "1")
    monkeypatch.setenv("MXNET_SPEC_DRAFT_LAYERS", "1")
    monkeypatch.setenv("MXNET_SPEC_K", "2")
    eng = make_engine(params, cfg, paged=True)
    assert eng.spec_requested and eng.spec and eng.spec_k == 2
    assert eng.draft.cfg.n_layers == 1
    # the self-draft shares the target's embeddings/head by reference
    assert eng.draft.params["embed"] is eng.model.params["embed"]
    eng.close()
    # explicit spec=False wins over the env request
    off = make_engine(params, cfg, paged=True, spec=False)
    assert not off.spec_requested and not off.spec
    off.close()
    monkeypatch.delenv("MXNET_SPEC_DECODE")
    monkeypatch.delenv("MXNET_SPEC_DRAFT_LAYERS")
    dflt = make_engine(params, cfg, paged=True)
    assert not dflt.spec and dflt.spec_fallback is None
    dflt.close()


# ---------------------------------------------------------------------------
# verification math: greedy acceptance and exact rejection sampling
# ---------------------------------------------------------------------------


def test_greedy_verify_rules():
    # agree, agree, bonus: full sweep emits k+1
    assert greedy_verify([5, 6, 7], [5, 6], 2) == ([5, 6, 7], 2)
    # first disagreement's argmax is still emitted (conditions only on
    # accepted history)
    assert greedy_verify([5, 6, 7], [5, 9], 2) == ([5, 6], 1)
    assert greedy_verify([5, 6, 7], [9, 6], 2) == ([5], 0)
    # zero proposals (sequence one token from its budget): the pass is
    # a plain target step
    assert greedy_verify([5], [], 0) == ([5], 0)


def test_rejection_sample_pinned_hand_computed():
    """Every branch pinned against hand-computed probabilities: accept
    via the min(1, p/q) ratio, residual inverse-CDF on rejection,
    q(d)=0 auto-accept, and the p==q zero-residual edge."""
    # full sweep: d0 accepted (ratio 2 > u0), d1 accepted (ratio 1 >
    # u1), bonus sampled from p2 by inverse CDF (cdf .1/.3/.6/1.0,
    # u=.55 -> token 2)
    p = np.array([[0.1, 0.2, 0.5, 0.2],
                  [0.2, 0.4, 0.2, 0.2],
                  [0.1, 0.2, 0.3, 0.4]])
    q = np.array([[0.25, 0.25, 0.25, 0.25],
                  [0.2, 0.4, 0.2, 0.2]])
    emitted, acc = rejection_sample(p, q, [2, 1], [0.9, 0.999], 0.55)
    assert (emitted, acc) == ([2, 1, 2], 2)
    # rejection: p0(0)/q0(0) = .1/.4 = .25 <= u0=.5; residual
    # max(p-q,0) = [0,0,0,.5] -> all mass on token 3
    p = np.array([[0.1, 0.1, 0.2, 0.6], [0.25, 0.25, 0.25, 0.25]])
    q = np.array([[0.4, 0.3, 0.2, 0.1]])
    emitted, acc = rejection_sample(p, q, [0], [0.5], 0.7)
    assert (emitted, acc) == ([3], 0)
    # q(d) = 0: the ratio is unbounded, accept unconditionally
    q0 = np.array([[0.5, 0.0, 0.3, 0.2]])
    emitted, acc = rejection_sample(p, q0, [1], [0.999], 0.1)
    assert emitted[0] == 1 and acc >= 1
    # p == q exactly: acceptance probability is 1; a u >= 1 draw still
    # emits d (the residual is empty)
    peq = np.array([[0.25, 0.25, 0.25, 0.25], [0.25, 0.25, 0.25, 0.25]])
    qeq = np.array([[0.25, 0.25, 0.25, 0.25]])
    emitted, acc = rejection_sample(peq, qeq, [2], [1.0], 0.5)
    assert (emitted, acc) == ([2], 1)


def test_rejection_sample_distribution_is_target():
    """Fixed-seed Monte Carlo: marginalized over d ~ q and the accept /
    residual draws, the first emitted token is distributed EXACTLY as
    the target row p — the Leviathan et al. identity
    min(p,q) + (1 - sum min(p,q)) * norm(max(p-q,0)) = p."""
    p = np.array([0.5, 0.3, 0.2])
    q = np.array([0.2, 0.5, 0.3])
    rng = np.random.default_rng(0)
    n = 8000
    counts = np.zeros(3)
    qcdf = np.cumsum(q)
    for _ in range(n):
        d = int(np.searchsorted(qcdf, rng.random()))
        emitted, _ = rejection_sample(
            np.stack([p, p]), q[None], [d], [rng.random()], rng.random())
        counts[emitted[0]] += 1
    np.testing.assert_allclose(counts / n, p, atol=0.02)


# ---------------------------------------------------------------------------
# acceptance bookkeeping: conservative counters, history == emissions
# ---------------------------------------------------------------------------


def test_spec_accounting_and_token_history(tiny_lm):
    """Per pass: 1 <= emitted <= batch*(k+1), accepted <= proposed <=
    batch*k; across the rollout the token history is exactly prefill's
    1 token + the sum of emitted — no token is double-counted and none
    vanishes. The final-step logits prove the KV the later passes read
    is accepted history (rejected-draft rows never leak: a contaminated
    pool would shift every downstream logit)."""
    params, cfg = tiny_lm
    k = 3
    eng = spec_engine(params, cfg, spec_k=k)
    assert eng.spec, eng.spec_fallback
    s = eng.start(arith_prompt(4, 1, 7), max_new=14)
    emitted_total, passes = 0, 0
    while not s.done:
        eng.decode_step([s])
        ls = eng.last_spec
        assert ls is not None and not ls["fallback"]
        assert 1 <= ls["emitted"] <= ls["batch"] * (k + 1)
        assert ls["accepted"] <= ls["proposed"] <= ls["batch"] * k
        emitted_total += ls["emitted"]
        passes += 1
    assert len(s.tokens) == 7 + 1 + emitted_total
    assert eng.spec_passes == passes
    assert eng.decode_tokens_per_step() == k + 1
    eng.release(s)
    eng.audit_quiescent()
    eng.close()


def test_spec_respects_max_total_budget(tiny_lm):
    """Proposals shrink near the generation budget: a sequence never
    emits past max_new even when a full sweep would earn more, and the
    KV writes never touch positions past the block reservation."""
    params, cfg = tiny_lm
    eng = spec_engine(params, cfg, spec_k=3)
    ref = make_engine(params, cfg, paged=True)
    for max_new in (1, 2, 5):
        t_spec, _ = drive(eng, [arith_prompt(6, 1, 5)], max_new=max_new)
        t_ref, _ = drive(ref, [arith_prompt(6, 1, 5)], max_new=max_new)
        assert t_spec == t_ref
        assert len(t_spec[0]) == 5 + max_new
    eng.audit_quiescent()
    eng.close()
    ref.close()


# ---------------------------------------------------------------------------
# parity through the serving stack: failover hop, prefix cache, tp=2
# ---------------------------------------------------------------------------


def test_spec_failover_hop_parity(tiny_lm):
    """A failover replay (serving.make_resume) is token-identical
    through spec engines on BOTH sides of the hop: generate partway on
    engine A, replay prompt+generated on a fresh engine B, and the
    concatenation equals the undisturbed oracle. The draft is CACHE-
    FREE, so nothing draft-side migrates — B rebuilds it from config."""
    params, cfg = tiny_lm
    prompt, max_new = arith_prompt(3, 2, 8), 12
    ref = make_engine(params, cfg, paged=True)
    want, _ = drive(ref, [prompt], max_new=max_new)
    ref.close()

    e_a = spec_engine(params, cfg)
    assert e_a.spec, e_a.spec_fallback
    s = e_a.start(list(prompt), max_new=max_new)
    for _ in range(2):                       # partway: a few spec passes
        if not s.done:
            e_a.decode_step([s])
    mid = list(s.tokens)
    e_a.release(s)
    e_a.close()
    assert len(prompt) < len(mid) < len(want[0])

    orig = serving.Request(list(prompt), max_new_tokens=max_new)
    resume, carried = serving.make_resume(orig, mid, max_len=cfg.max_len)
    assert carried == len(mid) - len(prompt)
    assert resume.failovers == 1
    e_b = spec_engine(params, cfg)
    got, _ = drive(e_b, [resume.prompt],
                   max_new=resume.max_new_tokens)
    assert got[0] == want[0], "spec failover replay diverged"
    e_b.close()


def test_spec_prefix_cache_hit_parity(tiny_lm):
    """Spec + prefix cache: a shared-prefix replay hits resident blocks
    (hits counted) and still matches the cache-off non-spec oracle —
    the cache indexes tokens[:-1], which under speculation is accepted
    history by construction, so a hit can never resurrect a rejected
    draft token's KV."""
    params, cfg = tiny_lm
    shared = arith_prompt(2, 1, 16)
    prompts = [shared + [7, 9], shared + [11, 3]]
    ref = make_engine(params, cfg, paged=True)
    want, _ = drive(ref, [prompts[0]], max_new=8)
    want2, _ = drive(ref, [prompts[1]], max_new=8)
    ref.close()
    eng = spec_engine(params, cfg, prefix_cache=True)
    assert eng.spec and eng.prefix_cache is not None
    got, _ = drive(eng, [prompts[0]], max_new=8)
    got2, _ = drive(eng, [prompts[1]], max_new=8)
    assert got[0] == want[0] and got2[0] == want2[0]
    assert eng.prefix_cache.hits >= 1
    eng.close()


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="tp test needs >= 2 (emulated) devices")
def test_spec_tp2_parity(tiny_lm):
    """Spec through the tp=2 sharded scoring pass: the draft runs
    replicated, the target's k+1-wide pass runs sharded over heads, and
    tokens + logits match the single-device non-spec oracle (f32 1e-5).
    tp changes placement, spec changes speed — neither changes
    logits."""
    params, cfg = tiny_lm
    prompts = [arith_prompt(1, 1, 9), arith_prompt(5, 2, 4)]
    ref = make_engine(params, cfg, paged=True)
    want, wlog = drive(ref, prompts, max_new=8)
    ref.close()
    eng = spec_engine(params, cfg, tp=2)
    assert eng.tp == 2, eng.tp_fallback
    assert eng.spec, eng.spec_fallback
    got, glog = drive(eng, prompts, max_new=8)
    assert got == want
    for ref_rows, spec_rows in zip(wlog, glog):
        for a, b in zip(ref_rows, spec_rows):
            np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-5)
    eng.close()


# ---------------------------------------------------------------------------
# compile discipline: two new sites, bounded lattice, AOT warm-loads
# ---------------------------------------------------------------------------


def test_spec_recompile_bound(tiny_lm):
    """The spec path adds exactly TWO jit families — the k+1 scoring
    pass ("spec" signatures, (batch, width)-bucketed like plain decode)
    and the cache-free draft ("draft" signatures, (batch, len)-
    bucketed). Mixed-length staggered clients stay within a small
    closed lattice; nothing else appears."""
    params, cfg = tiny_lm
    srv = serving.LMServer((params, cfg), max_batch=4, block_size=8,
                           paged=True, draft=self_draft(params, cfg, 1),
                           spec_k=3)
    try:
        assert srv.engine.spec, srv.engine.spec_fallback
        results = {}

        def client(i, delay, plen):
            time.sleep(delay)
            req = srv.submit(arith_prompt(i, 1, plen),
                             max_new_tokens=10)
            results[i] = req.result(timeout=120)

        threads = [threading.Thread(target=client, args=(i, 0.05 * i, p))
                   for i, p in enumerate((5, 9, 17))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(len(results[i]) == 10 for i in range(3))
        eng = srv.engine
        decode_kinds = {sig[0] for kind, sig in eng._sigs
                        if kind == "decode" and isinstance(sig, tuple)}
        assert decode_kinds <= {"spec", "draft"}, sorted(eng._sigs)
        spec_sigs = [sig for kind, sig in eng._sigs
                     if kind == "decode" and sig[0] == "spec"]
        draft_sigs = [sig for kind, sig in eng._sigs
                      if kind == "decode" and sig[0] == "draft"]
        assert 1 <= len(spec_sigs) <= 4, sorted(eng._sigs)
        assert 1 <= len(draft_sigs) <= 6, sorted(eng._sigs)
        assert eng.prefill_compilations <= 2, sorted(eng._sigs)
    finally:
        srv.close()


@pytest.fixture
def _no_jax_persistent_cache():
    """Same seam as tests/test_aot.py: conftest arms jax's own
    persistent compilation cache, whose loaded executables serialize to
    payloads `deserialize_and_load` rejects on CPU — the AOT cache
    quarantines them and recompiles (graceful, but it defeats a
    zero-compile assertion). Run the warm-restart leg like production
    entry points do: without jax's cache. Restore the process-wide AOT
    configuration afterwards so `Engine(aot_cache=...)` cannot leak
    warm loads into later tests."""
    from mxnet_tpu import aot
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc)
        _cc.reset_cache()
    except Exception:
        pass
    yield
    aot.configure()
    jax.config.update("jax_compilation_cache_dir", old)
    try:
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc)
        _cc.reset_cache()
    except Exception:
        pass


def test_spec_aot_warm_restart(tiny_lm, tmp_path,
                               _no_jax_persistent_cache):
    """A restarted spec engine over the same AOT cache warm-loads its
    executables — scoring pass and draft included — paying ZERO fresh
    decode compiles, with bit-identical tokens (the elastic/respawn
    paths construct engines exactly like this)."""
    params, cfg = tiny_lm
    prompt = arith_prompt(3, 1, 9)
    cold = spec_engine(params, cfg, aot_cache=tmp_path)
    assert cold.spec, cold.spec_fallback
    cold_t, _ = drive(cold, [prompt], max_new=10)
    assert cold.decode_compilations > 0
    cold.close()
    warm = spec_engine(params, cfg, aot_cache=tmp_path)
    warm_t, _ = drive(warm, [prompt], max_new=10)
    assert warm_t == cold_t
    assert warm.decode_compilations == 0, (
        "warm spec engine recompiled: %r" % sorted(warm._sigs))
    assert warm.warm_loads > 0
    warm.close()


# ---------------------------------------------------------------------------
# fallback semantics and frozen flags
# ---------------------------------------------------------------------------


def test_spec_fallback_reasons(tiny_lm):
    params, cfg = tiny_lm
    # requested but no draft: reason recorded, engine serves non-spec
    eng = make_engine(params, cfg, paged=True, spec=True)
    assert not eng.spec and "no draft" in eng.spec_fallback
    got, _ = drive(eng, [arith_prompt(2, 1, 6)], max_new=4)
    assert len(got[0]) == 10          # fallback engine still serves
    eng.close()
    # paged off: the scoring pass needs the block tables
    eng = make_engine(params, cfg, paged=False, spec=True,
                      draft=self_draft(params, cfg, 1))
    assert not eng.spec and "paged" in eng.spec_fallback
    eng.close()
    # draft vocab mismatch: acceptance compares token ids
    other = tiny_cfg(vocab=32)
    other_params = init_transformer_params(jax.random.PRNGKey(1), other)
    eng = make_engine(params, cfg, paged=True,
                      draft=(other_params, other))
    assert not eng.spec and "vocab" in eng.spec_fallback
    eng.close()
    # draft that cannot reach the target's positions
    short = tiny_cfg(max_len=32)
    short_params = init_transformer_params(jax.random.PRNGKey(2), short)
    eng = make_engine(params, cfg, paged=True,
                      draft=(short_params, short))
    assert not eng.spec and "max_len" in eng.spec_fallback
    eng.close()
    # degenerate k is a config error, not a fallback
    with pytest.raises(mx.MXNetError, match="spec_k"):
        make_engine(params, cfg, paged=True, spec_k=0,
                    draft=self_draft(params, cfg, 1), spec=True)
    # so is an unusable draft argument
    with pytest.raises(mx.MXNetError, match="draft"):
        make_engine(params, cfg, paged=True, draft="nope")
    with pytest.raises(mx.MXNetError, match="n_layers"):
        self_draft(params, cfg, 99)


def test_spec_flags_frozen_after_construction(tiny_lm):
    params, cfg = tiny_lm
    eng = spec_engine(params, cfg)
    for flag, val in (("spec", False), ("spec_requested", True),
                      ("spec_k", 7), ("draft", None)):
        with pytest.raises(mx.MXNetError, match="fixed at construction"):
            setattr(eng, flag, val)
    eng.chaos_spec_poison = True          # the chaos seam stays mutable
    eng.close()


# ---------------------------------------------------------------------------
# scheduler pricing and fairness under one token budget
# ---------------------------------------------------------------------------


def test_scheduler_prices_speculating_sequence_at_k_plus_1():
    """Admission and per-tenant accounting both charge
    decode_tokens_per_step() per running sequence; engines without the
    hook (older stubs) price at 1."""

    class SpecEngine:
        def can_admit(self, plen, max_new):
            return True

        def prefill_tokens_per_step(self, plen):
            return 8

        def decode_tokens_per_step(self):
            return 4                       # k=3 speculating engine

    class LegacyEngine:
        """An engine stub WITHOUT the pricing hook: costs 1/seq."""

        def can_admit(self, plen, max_new):
            return True

        def prefill_tokens_per_step(self, plen):
            return 8

    sched = serving.Scheduler(max_batch=8, token_budget=16)
    for _ in range(3):
        sched.submit(serving.Request([1, 2, 3]))
    sched.running = [object(), object()]   # 2 spec sequences = 8 tokens
    admitted, _ = sched.admit(SpecEngine())
    # 8 committed + 8 chunk = 16 fits; the next chunk would not
    assert len(admitted) == 1
    # same queue under a non-spec engine: 2 committed + 8 = 10, + 8 > 16
    sched2 = serving.Scheduler(max_batch=8, token_budget=16)
    for _ in range(3):
        sched2.submit(serving.Request([1, 2, 3]))
    sched2.running = [object(), object()]
    admitted2, _ = sched2.admit(LegacyEngine())
    assert len(admitted2) == 1
    assert sched2.spent_tokens(LegacyEngine()) < \
        sched.spent_tokens(SpecEngine())


def test_spec_does_not_starve_prefill_chunks(tiny_lm):
    """Fairness under MXNET_SERVING_TOKEN_BUDGET semantics: with a
    speculating decode stream priced at k+1=4 and budget 12, a long
    prompt's chunks still land (8 tokens each), interleaved with decode
    passes — the same price on the admission side and the chunk side
    is what keeps either from starving the other."""
    params, cfg = tiny_lm
    srv = serving.LMServer((params, cfg), max_batch=2, block_size=8,
                           paged=True, prefill_chunk=8, token_budget=12,
                           draft=self_draft(params, cfg, 1), spec_k=3)
    try:
        assert srv.engine.spec, srv.engine.spec_fallback
        events = []
        real_chunk = srv.engine.prefill_step
        real_decode = srv.engine.decode_pass

        def chunk_spy(seq):
            events.append(("chunk", seq.request.id
                           if seq.request else None))
            return real_chunk(seq)

        def decode_spy(*args, **kw):
            events.append(("decode", None))
            return real_decode(*args, **kw)

        srv.engine.prefill_step = chunk_spy
        srv.engine.decode_pass = decode_spy
        short = srv.submit(arith_prompt(1, 1, 4), max_new_tokens=40)
        deadline = time.perf_counter() + 60
        while srv.snapshot()["throughput"]["tokens_generated"] < 2:
            assert time.perf_counter() < deadline
            time.sleep(0.01)
        long_req = srv.submit(arith_prompt(2, 1, 40), max_new_tokens=2)
        out = long_req.result(timeout=120)
        assert len(out) == 2
        chunk_idx = [i for i, (kind, rid) in enumerate(events)
                     if kind == "chunk" and rid == long_req.id]
        assert len(chunk_idx) == 5, events      # 40 tokens / chunk 8
        decodes_between = sum(
            1 for i in range(chunk_idx[0], chunk_idx[-1])
            if events[i][0] == "decode")
        assert decodes_between >= 1, events
        assert len(short.result(timeout=120)) == 40
        assert srv.engine.spec_passes >= 1      # it really speculated
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# metrics and chaos degrade
# ---------------------------------------------------------------------------


def test_spec_metrics_accounting(tiny_lm):
    """The serving loop feeds per-pass accounting into the metrics
    registry: acceptance rate in (0, 1], accepted-per-pass histogram
    mean >= 1, observed_token_rate counts EMITTED tokens (one spec step
    = several tokens), and the snapshot carries the spec section."""
    params, cfg = tiny_lm
    srv = serving.LMServer((params, cfg), max_batch=2, block_size=8,
                           paged=True, draft=self_draft(params, cfg, 1),
                           spec_k=3, keep_logits=False)
    try:
        assert srv.engine.spec
        out = srv.generate(arith_prompt(4, 1, 8), max_new_tokens=20,
                           timeout=120)
        assert len(out) == 20
        snap = srv.snapshot()
        assert snap["engine"]["spec_decode"] is True
        spec = snap["spec"]
        assert spec["k"] == 3
        assert spec["passes"] >= 1
        assert spec["proposed_tokens"] >= spec["accepted_tokens"] >= 0
        assert 0.0 < spec["acceptance_rate"] <= 1.0
        assert spec["accepted_per_pass"] >= 1.0
        assert spec["fallbacks"] == 0
        # tokens_generated counts every emitted token (19 decode-path
        # tokens here; prefill emits the 20th), not decode STEPS — the
        # old per-step counting would report spec["passes"] instead
        assert snap["throughput"]["tokens_generated"] >= 19
        assert snap["throughput"]["tokens_generated"] > spec["passes"]
    finally:
        srv.close()


def test_chaos_spec_poison_degrades_token_identical(tiny_lm):
    """serve_spec_poison NaN-fills ONE iteration's draft logits: that
    pass degrades to the verbatim non-speculative body (fallback
    counted, fault latched on the chaos ledger) and the request
    completes token-identical to the undisturbed oracle — garbage can
    slow a pass, never corrupt an emission."""
    from mxnet_tpu.utils import chaos
    params, cfg = tiny_lm
    prompt, max_new = arith_prompt(5, 1, 7), 16
    ref = make_engine(params, cfg, paged=True)
    want, _ = drive(ref, [prompt], max_new=max_new)
    ref.close()
    chaos.reset()
    chaos.configure(serve_spec_poison=(3, 1))
    srv = serving.LMServer((params, cfg), max_batch=2, block_size=8,
                           paged=True, draft=self_draft(params, cfg, 1),
                           spec_k=3, replica_id=3)
    try:
        assert srv.engine.spec
        got = srv.generate(list(prompt), max_new_tokens=max_new,
                           timeout=120)
        assert list(prompt) + got == want[0], (
            "poisoned pass perturbed tokens")
        assert "serve_spec_poison" in chaos.fired()
        assert srv.engine.spec_fallbacks >= 1
        assert srv.engine.spec_passes >= 1     # recovered and speculated
        snap = srv.snapshot()
        assert snap["spec"]["fallbacks"] >= 1
    finally:
        srv.close()
        chaos.reset()


def test_chaos_spec_poison_is_a_registered_fault():
    """The drill's static chaos-coverage check: the fault name is in
    the harness registry and tools/chaos_serve.py exercises it."""
    import os
    from mxnet_tpu.utils import chaos
    assert "serve_spec_poison" in chaos._SERVE_FAULTS
    drill = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "chaos_serve.py")
    with open(drill) as fh:
        src = fh.read()
    assert "chaos.serve_spec_poison" in src
    assert "serve_spec_poison=(" in src
