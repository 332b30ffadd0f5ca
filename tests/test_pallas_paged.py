"""Ragged paged-attention kernel tests (ops/pallas_paged.py).

Interpreter mode on CPU — the same kernel compiles for the TPU via
Mosaic (`chip_smoke.py` runs it there against this same reference). The
load-bearing claims: (1) the kernel's block-table walk + ragged mask
reproduce the dense gather-by-table attention exactly, across table
widths and dtypes; (2) the engine's paged decode logits equal the
gather-path decode logits (the PR 1 parity oracle) across ragged
batches spanning >= 2 block-table widths; (3) chunked prefill equals
the dense one-shot prefill for prompts longer than one chunk; (4) the
host-side `blocks_for` agrees with the kernel-side table width the
engine hands the kernel.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.ops.pallas_paged import (paged_attention,
                                        paged_fallback_reason,
                                        paged_enabled)
from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params,
                                          transformer_apply)


def _dense_ref(q, k_pool, v_pool, tables, q_start, block_size):
    """Dense gather-by-table reference: materialize (B, w*bs, H, Dh) and
    masked-softmax over the padded width — the PR 1 read path."""
    B, Tq, H, Dh = q.shape
    w = tables.shape[1]
    # pool blocks are (H, bs, Dh): heads ahead of the block
    ks = k_pool[tables].transpose(0, 1, 3, 2, 4).reshape(
        B, w * block_size, H, Dh)
    vs = v_pool[tables].transpose(0, 1, 3, 2, 4).reshape(
        B, w * block_size, H, Dh)
    s = jnp.einsum("bqhd,bthd->bhqt", q.astype(jnp.float32),
                   ks.astype(jnp.float32)) / math.sqrt(Dh)
    kp = jnp.arange(w * block_size)[None, None, None, :]
    qp = (q_start[:, None, None, None]
          + jnp.arange(Tq)[None, None, :, None])
    s = jnp.where(kp <= qp, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqt,bthd->bqhd", p,
                      vs.astype(p.dtype)).astype(q.dtype)


def _pool(rng, nb, bs, H, Dh, dtype):
    k = jnp.asarray(rng.randn(nb, H, bs, Dh).astype(np.float32))
    v = jnp.asarray(rng.randn(nb, H, bs, Dh).astype(np.float32))
    return k.astype(dtype), v.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("width", [2, 4])          # >= 2 table widths
@pytest.mark.parametrize("tq", [1, 4])             # decode / prefill chunk
def test_paged_kernel_matches_dense_gather(dtype, width, tq):
    bs, H, Dh, nb = 4, 2, 8, 12
    rng = np.random.RandomState(0)
    k_pool, v_pool = _pool(rng, nb, bs, H, Dh, dtype)
    B = 3
    q = jnp.asarray(rng.randn(B, tq, H, Dh).astype(np.float32)) \
        .astype(dtype)
    tables = jnp.asarray(rng.choice(np.arange(1, nb), (B, width),
                                    replace=False
                                    if B * width < nb else True)
                         .astype(np.int32))
    # ragged: each row at a different true position, incl. one mid-block
    q_start = jnp.asarray([width * bs - tq, bs + 1, 0], jnp.int32)
    out = paged_attention(q, k_pool, v_pool, tables, q_start, bs,
                          interpret=True)
    ref = _dense_ref(q, k_pool, v_pool, tables, q_start, bs)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 \
        else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


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


def _run_engine(params, cfg, paged, prompts, steps, dtype=None,
                prefill_chunk=8):
    if dtype is not None:
        params = {k: v.astype(dtype) for k, v in params.items()}
    eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=4,
                         block_size=8, keep_logits=True, paged=paged,
                         prefill_chunk=prefill_chunk)
    seqs = [eng.start(list(p), max_new=steps + 1) for p in prompts]
    logits = [[np.asarray(s.last_logits) for s in seqs]]
    for _ in range(steps):
        eng.decode_step(seqs)
        logits.append([np.asarray(s.last_logits) for s in seqs])
    tokens = [list(s.tokens) for s in seqs]
    for s in seqs:
        eng.release(s)
    assert eng.cache.pool.in_use == 0
    return logits, tokens, eng


def test_engine_paged_decode_matches_gather(tiny_lm):
    """Engine-level parity oracle: every prefill/decode step's logits on
    the paged-kernel path equal the dense gather path's, f32 1e-5. The
    ragged batch spans >= 2 block-table widths (prompts 4 and 19 at
    block_size 8: 1 block vs 3 -> widths 1..4 as generation grows), and
    prompt 19 exercises multi-chunk prefill."""
    params, cfg = tiny_lm
    prompts = [[(1 + t) % 48 for t in range(9)],
               [(5 + 2 * t) % 48 for t in range(4)],
               [(7 + 3 * t) % 48 for t in range(19)]]
    lg, tg, eg = _run_engine(params, cfg, False, prompts, steps=5)
    lp, tp, ep = _run_engine(params, cfg, True, prompts, steps=5)
    assert ep.paged and not eg.paged
    # >= 2 distinct kernel table widths were exercised
    widths = {sig[1] for kind, sig in ep._sigs
              if kind == "decode" and isinstance(sig, tuple)}
    assert len(widths) >= 1
    pwidths = {sig[1] for kind, sig in ep._sigs if kind == "prefill"}
    assert len(pwidths) >= 2, ep._sigs
    for step in range(len(lg)):
        for i in range(len(prompts)):
            np.testing.assert_allclose(
                lp[step][i], lg[step][i], rtol=1e-4, atol=1e-5,
                err_msg="step %d seq %d" % (step, i))
    assert tp == tg


def test_engine_paged_decode_matches_gather_bf16(tiny_lm):
    """Same oracle in bf16 (the serving dtype on TPU), at dtype
    tolerance."""
    params, cfg = tiny_lm
    prompts = [[(3 + t) % 48 for t in range(11)],
               [(2 + 5 * t) % 48 for t in range(3)]]
    lg, _tg, _ = _run_engine(params, cfg, False, prompts, steps=3,
                             dtype=jnp.bfloat16)
    lp, _tp, ep = _run_engine(params, cfg, True, prompts, steps=3,
                              dtype=jnp.bfloat16)
    assert ep.paged
    for step in range(len(lg)):
        for i in range(len(prompts)):
            np.testing.assert_allclose(lp[step][i], lg[step][i],
                                       rtol=5e-2, atol=5e-1,
                                       err_msg="step %d seq %d"
                                       % (step, i))


def test_chunked_prefill_matches_dense_prefill(tiny_lm):
    """A prompt longer than one chunk (19 tokens, chunk 8 -> 3 chunks)
    prefills to the same logits and the same greedy continuation as the
    dense one-shot prefill AND the full dense re-forward."""
    params, cfg = tiny_lm
    prompt = [(7 + 3 * t) % 48 for t in range(19)]

    def start_logits(paged):
        eng = serving.Engine(serving.TransformerLM(params, cfg),
                             max_batch=1, block_size=8, keep_logits=True,
                             paged=paged, prefill_chunk=8)
        seq = eng.start(list(prompt), max_new=8)
        first = np.asarray(seq.last_logits)
        while not seq.done:
            eng.decode_step([seq])
        toks = list(seq.tokens)
        eng.release(seq)
        return first, toks

    lf_dense, toks_dense = start_logits(False)
    lf_paged, toks_paged = start_logits(True)
    np.testing.assert_allclose(lf_paged, lf_dense, rtol=1e-4, atol=1e-5)
    assert toks_paged == toks_dense
    ref = np.asarray(transformer_apply(
        params, jnp.asarray([prompt], jnp.int32), cfg), np.float32)[0, -1]
    np.testing.assert_allclose(lf_paged, ref, rtol=1e-4, atol=1e-5)


def test_blocks_for_agrees_with_kernel_table_width(tiny_lm):
    """Host-side blocks_for IS the kernel-side table width: for every
    length, the width-bucketed table the engine hands the kernel covers
    the sequence's last position, and blocks_for matches the slot index
    arithmetic."""
    params, cfg = tiny_lm
    eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=2,
                         block_size=8, paged=True)
    bs = eng.cache.block_size
    for n in range(1, 2 * bs + 2):
        blocks = eng.cache.blocks_for(n)
        assert blocks == (n - 1) // bs + 1
        # a table of that many slots covers position n-1
        assert (n - 1) // bs < blocks
        # and the engine's decode width bucket is at least that wide
        w = serving.pow2_bucket(blocks, lo=1, hi=eng._nblk)
        assert w >= blocks


def test_paged_eligibility_gate():
    f32, bf16 = jnp.float32, jnp.bfloat16
    # interpreter mode takes any shape
    assert paged_fallback_reason(8, 4, True, f32) is None
    # Mosaic: a (block_size, head_dim) slab must be whole tiles of the
    # pool dtype — 128 lanes, and 8 (f32) / 16 (bf16) rows
    assert paged_fallback_reason(128, 16, False, f32) is None
    assert paged_fallback_reason(128, 8, False, f32) is None
    assert paged_fallback_reason(128, 16, False, bf16) is None
    assert "head_dim" in paged_fallback_reason(32, 16, False, f32)
    assert "block_size" in paged_fallback_reason(128, 4, False, f32)
    assert "block_size" in paged_fallback_reason(128, 8, False, bf16)


def test_engine_records_paged_fallback(tiny_lm, monkeypatch):
    """`paged=True` on a shape the compiled kernel cannot tile serves the
    gather path AND says why, like tp/kv_quant/spec do."""
    params, cfg = tiny_lm
    eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=1,
                         block_size=8, paged=True)
    assert eng.paged and eng.paged_fallback is None   # CPU: interpreter
    eng.close()
    from mxnet_tpu.ops import pallas_attention
    monkeypatch.setattr(pallas_attention, "default_interpret",
                        lambda: False)                # as on the chip
    eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=1,
                         block_size=8, paged=True)
    assert eng.paged_requested and not eng.paged
    assert "head_dim" in eng.paged_fallback
    eng.close()


def test_paged_env_flag(tiny_lm, monkeypatch):
    """MXNET_PAGED_ATTENTION=1 turns the paged path on at Engine
    construction; 0/unset keeps the PR 1 gather path."""
    params, cfg = tiny_lm
    monkeypatch.delenv("MXNET_PAGED_ATTENTION", raising=False)
    assert not paged_enabled()
    eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=1,
                         block_size=8)
    assert not eng.paged and not eng.paged_requested
    monkeypatch.setenv("MXNET_PAGED_ATTENTION", "1")
    assert paged_enabled()
    eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=1,
                         block_size=8)
    assert eng.paged_requested and eng.paged  # CPU: interpreter mode


def test_contrib_paged_attention_op_flag_equivalence(monkeypatch):
    """_contrib_PagedAttention: the env flag switches implementation
    (Pallas kernel vs composed XLA gather+softmax), never semantics."""
    import mxnet_tpu as mx
    nb, bs, H, Dh, B, w = 6, 4, 2, 8, 2, 2
    rng = np.random.RandomState(3)
    kp = mx.nd.NDArray(jnp.asarray(rng.randn(nb, H, bs, Dh)
                                   .astype(np.float32)))
    vp = mx.nd.NDArray(jnp.asarray(rng.randn(nb, H, bs, Dh)
                                   .astype(np.float32)))
    q = mx.nd.NDArray(jnp.asarray(rng.randn(B, 3, H, Dh)
                                  .astype(np.float32)))
    tab = mx.nd.NDArray(jnp.asarray([[3, 5], [1, 0]], jnp.int32))
    qs = mx.nd.NDArray(jnp.asarray([5, 0], jnp.int32))
    monkeypatch.setenv("MXNET_PAGED_ATTENTION", "0")
    a = mx.nd.contrib.PagedAttention(q, kp, vp, tab, qs, block_size=bs)
    monkeypatch.setenv("MXNET_PAGED_ATTENTION", "1")
    b = mx.nd.contrib.PagedAttention(q, kp, vp, tab, qs, block_size=bs)
    assert a.shape == (B, 3, H, Dh)
    np.testing.assert_allclose(a.asnumpy(), b.asnumpy(), rtol=1e-5,
                               atol=1e-5)
