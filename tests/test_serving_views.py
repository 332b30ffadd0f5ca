"""One transformer layer over a cache view (ISSUE 29).

Load-bearing claim: every step function of the paged engine (`prefill`,
`decode`, `prefill_chunk`, `spec_score`), through every cache view that
applies to it, gives the logits of the training forward `transformer_apply`
on the same tokens: the layer is written once (`models.transformer.block`)
and a view only says where keys and values are kept and how they are read. A
new view is one more case of the one test here.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params,
                                          transformer_apply)
from mxnet_tpu.serving import engine, kv_cache

H, DH, BS, L = 4, 8, 8, 2
NBLK = 4                                    # table width: 32 positions
i32 = jnp.int32
#: three sequences; their lengths put every chunk and every speculative pass
#: below across a block boundary, one of each with padded positions after it
TOKENS = [[(3 + 5 * t) % 48 for t in range(18)],
          [(7 + 2 * t) % 48 for t in range(9)],
          [(11 + 3 * t) % 48 for t in range(26)]]


@pytest.fixture(scope="module")
def lm():
    cfg = TransformerConfig(vocab=48, d_model=H * DH, n_heads=H, n_layers=L,
                            d_ff=64, max_len=NBLK * BS)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    want = [np.asarray(transformer_apply(params, jnp.asarray([t], i32), cfg)[0])
            for t in TOKENS]
    return params, cfg, want


def empty_pools(view):
    shape = (L, len(TOKENS) * NBLK + 1, H, BS, DH)
    if view != "paged_int8":
        return (jnp.zeros(shape), jnp.zeros(shape))
    return (jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
            jnp.zeros(shape[:3]), jnp.zeros(shape[:3]))


TABLES = 1 + np.arange(len(TOKENS) * NBLK, dtype=np.int32).reshape(-1, NBLK)


def prefilled(params, cfg, view, lengths):
    """Pools holding the first `lengths[b]` tokens of every sequence. An f32
    pool is filled by the prompt view (`prefill`), an int8 one by the paged
    view itself, a chunk at a time: nothing else writes its scales."""
    pools = empty_pools(view)
    for b, n in enumerate(lengths):
        if n == 0:
            continue
        if view != "paged_int8":
            toks = np.zeros((NBLK * BS,), np.int32)
            toks[:n] = TOKENS[b][:n]
            *pools, _ = engine.prefill(params, pools, jnp.asarray(toks), i32(n),
                                       jnp.asarray(TABLES[b]), cfg)
        else:
            *pools, _ = chunked(params, cfg, pools, b, 0, n)
    return tuple(pools)


def chunked(params, cfg, pools, b, start, stop, C=8):
    """`prefill_chunk` over positions start..stop-1 of sequence b, C at a
    time; the last chunk's result."""
    for qs in range(start, stop, C):
        toks = np.zeros((C,), np.int32)
        n = min(C, stop - qs)
        toks[:n] = TOKENS[b][qs:qs + n]
        *pools, logits = engine.prefill_chunk(
            params, tuple(pools), jnp.asarray(toks), i32(qs), i32(stop),
            i32(n - 1), jnp.asarray(TABLES[b]), cfg, BS)
    return (*pools, logits)


def run_prefill(params, cfg, view):
    got = []
    for b, t in enumerate(TOKENS):
        toks = np.zeros((NBLK * BS,), np.int32)
        toks[:len(t)] = t
        got.append((b, len(t) - 1, engine.prefill(
            params, empty_pools(view), jnp.asarray(toks), i32(len(t)),
            jnp.asarray(TABLES[b]), cfg)[-1]))
    return got


def run_decode(params, cfg, view):
    """Each sequence's last token as one decode step over the others; a
    fourth, padded row carries the null table."""
    view_of = kv_cache.LiveGatherView if view == "live_gather" \
        else kv_cache.PagedView
    lengths = [len(t) - 1 for t in TOKENS]
    pools = prefilled(params, cfg, view, lengths)
    toks = jnp.asarray([t[-1] for t in TOKENS] + [0], i32)
    pos = jnp.asarray(lengths + [0], i32)
    tabs = jnp.asarray(np.concatenate([TABLES, np.zeros((1, NBLK), np.int32)]))
    *_, logits, nxt = engine.decode(params, pools, jnp.zeros_like(toks), toks,
                                    pos, tabs, cfg, BS, view_of)
    assert np.array_equal(np.asarray(nxt), np.asarray(logits).argmax(-1))
    return [(b, n, logits[b]) for b, n in enumerate(lengths)]


def run_prefill_chunk(params, cfg, view):
    """The back half of every sequence in chunks, over a front half the
    cache holds: a chunk starts inside a block, as after a prefix-cache hit
    on a partial block."""
    got = []
    for b, t in enumerate(TOKENS):
        half = len(t) // 2
        lengths = [half if j == b else 0 for j in range(len(TOKENS))]
        pools = prefilled(params, cfg, view, lengths)
        got.append((b, len(t) - 1,
                    chunked(params, cfg, pools, b, half, len(t))[-1]))
    return got


def run_spec_score(params, cfg, view, C=4):
    """The last 1..C tokens of every sequence scored in one pass: row j is
    the distribution after the history and the first j of them. Every row
    lies across two blocks and the short ones add the null block: one more
    distinct block than two a row."""
    counts = [4, 2, 3]
    starts = [len(t) - c for t, c in zip(TOKENS, counts)]
    pools = prefilled(params, cfg, view, starts)
    toks = np.zeros((len(TOKENS), C), np.int32)
    for b, (t, c) in enumerate(zip(TOKENS, counts)):
        toks[b, :c] = t[-c:]
    *_, logits = engine.spec_score(
        params, pools, jnp.asarray(toks), jnp.asarray(starts, i32),
        jnp.asarray(counts, i32), jnp.asarray(TABLES), cfg, BS)
    return [(b, starts[b] + j, logits[b, j])
            for b, c in enumerate(counts) for j in range(c)]


CASES = [("prefill", "prompt"),
         ("decode", "live_gather"), ("decode", "paged"),
         ("decode", "paged_int8"),
         ("prefill_chunk", "paged"), ("prefill_chunk", "paged_int8"),
         ("spec_score", "paged"), ("spec_score", "paged_int8")]
RUN = {"prefill": run_prefill, "decode": run_decode,
       "prefill_chunk": run_prefill_chunk, "spec_score": run_spec_score}


@pytest.mark.parametrize("operation, view", CASES)
def test_a_step_through_a_view_gives_the_training_forwards_logits(
        lm, operation, view):
    params, cfg, want = lm
    # an int8 pool holds keys and values to one part in 127 of a block's
    # largest: the logits follow to about that (tests/test_serving_quant.py
    # pins the served budget)
    tol = dict(rtol=1e-4, atol=1e-5) if view != "paged_int8" \
        else dict(rtol=0, atol=2e-2)
    for b, position, logits in RUN[operation](params, cfg, view):
        np.testing.assert_allclose(np.asarray(logits), want[b][position],
                                   err_msg="sequence %d, position %d"
                                   % (b, position), **tol)


def test_a_chunk_after_a_hit_on_a_partial_block_writes_every_block():
    """Through the engine: a prompt that shares 23 tokens (two blocks of 8
    and 7 of a third) with a cached one starts its chunk of 16 inside a
    block; with 13 tokens left the chunk's real positions lie in three
    blocks and its padded ones in the null block. All four are written: the
    first token's logits are the gather engine's (they were 8e-3 off while
    the candidate set held three)."""
    from mxnet_tpu import serving
    cfg = TransformerConfig(vocab=48, d_model=H * DH, n_heads=H, n_layers=L,
                            d_ff=64, max_len=64)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    shared = [(3 + 5 * t) % 48 for t in range(23)]
    prompt = shared + [(7 + 3 * t) % 48 for t in range(13)]
    first = {}
    for name, opts in (("gather", {}),
                       ("paged", dict(paged=True, prefix_cache=True))):
        eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=2,
                             block_size=BS, keep_logits=True, **opts)
        eng.release(eng.start(shared, max_new=1))
        seq = eng.start(prompt, max_new=1)
        first[name] = (seq.cache_hit_tokens, np.asarray(seq.token_logits[0]))
        eng.release(seq)
    assert first["paged"][0] == 23 and first["gather"][0] == 0
    np.testing.assert_allclose(first["paged"][1], first["gather"][1],
                               rtol=1e-4, atol=1e-5)
