"""Tensor-parallel serving tests (ISSUE 8): the tp-sharded paged engine
on an emulated multi-device mesh (conftest.py forces
--xla_force_host_platform_device_count=8).

Load-bearing claims: (1) tp-sharded paged decode produces the SAME
logits as the single-device paged kernel AND the dense gather oracle at
every step — the tp flag switches placement, never logits; (2) the KV
pool really shards H/k heads per chip; (3) the tp path compiles within
the SAME signature bounds as single-chip paged serving; (4) unshardable
configs fall back to tp=1 with a recorded reason instead of changing
semantics; (5) placement flags are frozen after Engine construction.
"""
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import serving
from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params,
                                          transformer_apply)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="tp tests need >= 4 (emulated) devices")


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


def rollout_logits(eng, steps=5):
    """Start two mixed-length sequences and record per-step logits."""
    s1 = eng.start(arith_prompt(1, 1, 9), max_new=steps + 1)
    s2 = eng.start(arith_prompt(5, 2, 4), max_new=steps + 1)
    logs = [[np.asarray(s1.last_logits), np.asarray(s2.last_logits)]]
    for _ in range(steps):
        eng.decode_step([s1, s2])
        logs.append([np.asarray(s1.last_logits), np.asarray(s2.last_logits)])
    toks = (list(s1.tokens), list(s2.tokens))
    for s in (s1, s2):
        eng.release(s)
    return logs, toks


# ---------------------------------------------------------------------------
# parity: tp-sharded decode == single-device paged == gather oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_decode_parity_three_way(tiny_lm, tp):
    """Every prefill/decode step's logits from the tp-sharded engine
    must equal BOTH single-device oracles (f32 1e-5): the paged kernel
    and the PR 1 dense gather. The tp mesh changes placement only."""
    params, cfg = tiny_lm
    e_gather = make_engine(params, cfg, paged=False)
    e_paged = make_engine(params, cfg, paged=True)
    e_tp = make_engine(params, cfg, paged=True, tp=tp)
    assert e_tp.tp == tp, e_tp.tp_fallback
    assert e_tp.paged
    log_g, tok_g = rollout_logits(e_gather)
    log_p, tok_p = rollout_logits(e_paged)
    log_t, tok_t = rollout_logits(e_tp)
    for ref in (log_p, log_g):
        for a, b in zip(ref, log_t):
            for x, y in zip(a, b):
                np.testing.assert_allclose(y, x, rtol=1e-4, atol=1e-5)
    assert tok_t == tok_p == tok_g
    # the dense full-sequence forward agrees too (transitively pinned,
    # but cheap to check directly at the final step)
    for i, toks in enumerate(tok_t):
        dense = np.asarray(transformer_apply(
            params, jnp.asarray([toks[:-1]], jnp.int32), cfg),
            np.float32)[0, -1]
        np.testing.assert_allclose(log_t[-1][i], dense,
                                   rtol=1e-4, atol=1e-5)


def test_tp_decode_parity_bf16(tiny_lm):
    """bf16 pools/params: tp vs single-device paged at dtype tolerance
    (both accumulate softmax statistics in f32; the psum split-sum is
    the only reduction-order difference)."""
    params, cfg = tiny_lm
    bf16 = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    e_paged = make_engine(bf16, cfg, paged=True)
    e_tp = make_engine(bf16, cfg, paged=True, tp=2)
    assert e_tp.tp == 2, e_tp.tp_fallback
    log_p, tok_p = rollout_logits(e_paged, steps=3)
    log_t, tok_t = rollout_logits(e_tp, steps=3)
    for a, b in zip(log_p, log_t):
        for x, y in zip(a, b):
            np.testing.assert_allclose(y, x, rtol=2e-2, atol=2e-2)


def test_tp_pool_sharded_over_heads(tiny_lm):
    """The KV block pool is laid out with H/k heads per chip (axis 2 of
    (L, nb, H, bs, Dh)); block tables stay host-side replicated ints."""
    params, cfg = tiny_lm
    eng = make_engine(params, cfg, paged=True, tp=2)
    assert eng.tp == 2, eng.tp_fallback
    spec = eng.cache.k.sharding.spec
    assert tuple(spec) == (None, None, "tp", None, None)
    shard = eng.cache.k.addressable_shards[0].data
    assert shard.shape[2] == cfg.n_heads // 2
    assert eng.cache.v.sharding == eng.cache.k.sharding
    # H/k heads per chip => per-chip pool bytes are 1/k of the total
    total = np.prod(eng.cache.k.shape)
    assert np.prod(shard.shape) * 2 == total


# ---------------------------------------------------------------------------
# compile-count bound: tp must not widen the signature lattice
# ---------------------------------------------------------------------------


def test_tp_recompile_bound_mixed_lengths(tiny_lm):
    """The tp path reuses the paged path's (batch, width) signature
    lattice: three staggered mixed-length clients stay within the SAME
    bounds as single-chip paged serving (<= 2 prefill, <= 6 decode)."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=4, block_size=8,
                        paged=True, tp=2)
    try:
        assert srv.engine.tp == 2, srv.engine.tp_fallback
        results = {}

        def client(i, delay, plen):
            time.sleep(delay)
            results[i] = srv.generate(arith_prompt(i, 1, plen),
                                      max_new_tokens=10, timeout=120)

        threads = [threading.Thread(target=client, args=(i, 0.05 * i, p))
                   for i, p in enumerate((5, 9, 17))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(len(results[i]) == 10 for i in range(3))
        eng = srv.engine
        assert eng.prefill_compilations <= 2, (
            "tp chunked prefill compiled %d signatures: %r"
            % (eng.prefill_compilations, sorted(eng._sigs)))
        assert eng.decode_compilations <= 6, (
            "tp decode compiled %d signatures: %r"
            % (eng.decode_compilations, sorted(eng._sigs)))
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# fallback semantics: placement changes, logits never do
# ---------------------------------------------------------------------------


def test_tp_fallback_reasons(tiny_lm):
    params, cfg = tiny_lm
    # heads not divisible
    e = make_engine(params, cfg, paged=True, tp=3)
    assert e.tp == 1 and "n_heads" in e.tp_fallback
    # more chips than the host has (divisible degree, too few devices)
    wide = tiny_cfg(n_heads=16, d_model=64)
    wide_params = init_transformer_params(jax.random.PRNGKey(0), wide)
    e = make_engine(wide_params, wide, paged=True, tp=16)
    assert e.tp == 1 and "devices" in e.tp_fallback
    # explicit paged=False pins the single-device gather oracle
    e = make_engine(params, cfg, paged=False, tp=2)
    assert e.tp == 1 and not e.paged and "gather" in e.tp_fallback
    # MoE FFN is not tp-sharded
    moe = tiny_cfg(n_experts=2, d_ff=32)
    moe_params = init_transformer_params(jax.random.PRNGKey(0), moe)
    e = make_engine(moe_params, moe, paged=True, tp=2)
    assert e.tp == 1 and "MoE" in e.tp_fallback
    # cache-less model families serve single-device
    net = mx.models.RNNModel(mode="lstm", vocab_size=32, num_embed=16,
                             num_hidden=16, num_layers=1, dropout=0.0)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((4, 2)))
    adapter = serving.BlockLM(net, vocab=32, max_len=32, time_major=True)
    e = serving.Engine(adapter, max_batch=2, tp=2)
    assert e.tp == 1 and "cache hooks" in e.tp_fallback
    # degenerate degree is a config error, not a fallback
    with pytest.raises(mx.MXNetError):
        make_engine(params, cfg, tp=0)
    # the fallback engine still serves correctly (placement-only claim)
    e = make_engine(params, cfg, paged=True, tp=3)
    seq = e.start(arith_prompt(2, 1, 6), max_new=3)
    while not seq.done:
        e.decode_step([seq])
    e.release(seq)
    assert len(seq.generated) == 3


def test_tp_env_var_read_at_construction(tiny_lm, monkeypatch):
    """MXNET_SERVING_TP is the env default; the explicit argument wins;
    both are read at construction only (docs/ENV_VARS.md)."""
    params, cfg = tiny_lm
    monkeypatch.setenv("MXNET_SERVING_TP", "2")
    e = make_engine(params, cfg)
    assert e.tp_requested == 2 and e.tp == 2 and e.paged
    e = make_engine(params, cfg, tp=1)
    assert e.tp == 1 and e.tp_fallback is None
    monkeypatch.delenv("MXNET_SERVING_TP")
    e = make_engine(params, cfg)
    assert e.tp == 1


def test_engine_flags_frozen_after_construction(tiny_lm):
    """Placement flags are construction-only: a live engine raises on
    mutation of paged/tp/prefill_chunk (a replica must never straddle
    two placements); ordinary attributes stay assignable."""
    params, cfg = tiny_lm
    eng = make_engine(params, cfg, paged=True, tp=2)
    for flag, val in (("paged", False), ("paged_requested", False),
                      ("tp", 1), ("tp_requested", 4),
                      ("prefill_chunk", 32), ("mesh", None)):
        with pytest.raises(mx.MXNetError, match="fixed at construction"):
            setattr(eng, flag, val)
    eng.keep_logits = False          # non-placement attrs stay mutable
    assert eng.tp == 2 and eng.paged


# ---------------------------------------------------------------------------
# end-to-end: the serving loop over a tp engine
# ---------------------------------------------------------------------------


def test_tp_serve_end_to_end(tiny_lm):
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8, tp=2)
    try:
        assert srv.engine.tp == 2, srv.engine.tp_fallback
        out = srv.generate(arith_prompt(3, 1, 7), max_new_tokens=4,
                           timeout=120)
        assert len(out) == 4
        snap = srv.snapshot()
        assert snap["paths"]["paged_decode_steps"] >= 3
        assert snap["requests"]["completed"] == 1
        # greedy tokens equal the single-device server's
        ref = serving.serve((params, cfg), max_batch=2, block_size=8,
                            paged=True)
        try:
            assert ref.generate(arith_prompt(3, 1, 7), max_new_tokens=4,
                                timeout=120) == out
        finally:
            ref.close()
    finally:
        srv.close()
