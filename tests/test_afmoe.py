"""The window-and-full attention family over a cache of two kinds (ISSUE 31),
small widths (a window of 16 at blocks of 4: a ring of five blocks; two KV
heads under six query heads; 8 experts, 2 held), seeded float32 weights,
against the plain reference of `chipbench/reference/afmoe_lm.py`, logits not
tokens.

(a) the dense forward; (b) prefill then decode through the two pools for
sequences shorter than the window, ending exactly on it and wrapping the ring
more than twice, for a prompt longer than the window, and for short and long
rows in one batch; (c) the reference with the kinds swapped misses the
tolerance, so a window layer that attends past its window or a full layer cut
to it fails; (d) bfloat16 in float32's place fails it too; (e) the shares add
up: the ranks' routed parts plus the shared expert once equal the uncut
layer; (f) the expert layer is `models/latent_moe.py`'s own; (g) a window
sequence never holds more than its ring, admission takes all or nothing over
both kinds, every block of both kinds comes back after finish, fault and
close, and the one-kind families' tables are what they were; (h) the spans,
gauges and snapshot by kind; (i) the pools are aliased and no step holds a
copy of a pool's shape.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import afmoe, latent_moe
from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params)
from mxnet_tpu.serving import kv_cache

from chipbench.families import afmoe_lm as family
from chipbench.reference import afmoe_lm as reference

TYPES = ["sliding_attention", "sliding_attention", "full_attention",
         "sliding_attention", "sliding_attention"]
CONFIG = {
    "hidden_size": 48, "num_attention_heads": 6, "num_key_value_heads": 2,
    "head_dim": 8, "intermediate_size": 96, "moe_intermediate_size": 24,
    "num_shared_experts": 1, "num_experts": 2, "num_experts_published": 8,
    "expert_parallel": 4, "expert_rank": 1, "num_experts_per_tok": 4,
    "route_scale": 2.448, "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": TYPES, "sliding_window": 16, "vocab_size": 96,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "mup_enabled": True,
    "dtype": "float32"}
BS, MAX_LEN, WINDOW, RING = 4, 128, 16, 5
# float32 both sides at matmul precision "highest": what is left is the order
# of the sums (blocked against dense attention, the online softmax of the
# decode walk, grouped against dense experts). Logits are of order 0.5; the
# largest difference read over (a)-(b) is 4.5e-7. bfloat16 in float32's place
# reads 3.0e-2 (d), a layer's mask of the other kind 0.14 to 0.32 (c).
TOL = 2e-5


@pytest.fixture(scope="module")
def model():
    weights = family.make_weights(CONFIG, 11)
    cfg = family.program_config(CONFIG, MAX_LEN)
    return weights, family.program_params(weights), cfg


@pytest.fixture
def small_blocks(monkeypatch):
    """Prompts of a few dozen tokens are cut into several query blocks, so
    the band's edges and the skipped key blocks are exercised."""
    monkeypatch.setattr(kv_cache, "PROMPT_Q_BLOCK", 8)


def prompt(start, n):
    return [(start + 7 * t) % CONFIG["vocab_size"] for t in range(n)]


def ref_logits(weights, tokens, **kw):
    n = len(tokens)
    padded = list(tokens) + [0] * (-n % reference.Q_BLOCK)
    return np.asarray(reference.logits(weights, CONFIG, padded, **kw))[:n]


def engine(params, cfg, **kw):
    kw.setdefault("max_batch", 4)
    return serving.Engine(serving.AfmoeLM(params, cfg), max_len=MAX_LEN,
                          block_size=BS, **kw)


def served_against_reference(weights, seq):
    got = np.stack(seq.token_logits)
    want = ref_logits(weights, seq.tokens)[
        seq.prompt_len - 1:seq.prompt_len - 1 + len(got)]
    assert [int(t) for t in want.argmax(-1)] == seq.tokens[seq.prompt_len:]
    return np.abs(got - want).max()


# -- (a) ---------------------------------------------------------------------

def test_the_dense_forward_agrees_with_the_reference(model):
    weights, params, cfg = model
    tokens = prompt(5, 50)
    got, counts = afmoe.afmoe_apply(params, jnp.asarray(tokens), cfg)
    assert np.abs(np.asarray(got) - ref_logits(weights, tokens)).max() < TOL
    rows = []
    ref_logits(weights, tokens, counts=rows)
    assert np.array_equal(np.asarray(counts),
                          np.stack([np.asarray(r)[:50].sum(0) for r in rows]))


# -- (b) ---------------------------------------------------------------------

@pytest.mark.parametrize("n_prompt,n_new,case", [
    (5, 6, "shorter than the window"),
    (9, 8, "ends exactly on the window"),        # 16 tokens cached, one more
    (11, 50, "wraps the ring more than twice"),  # 61 tokens: 16 blocks on 5
    (37, 30, "a prompt longer than the window"),
    (16, 20, "a prompt of exactly the window"),
    (40, 3, "a prompt that wraps the ring itself"),
])
def test_prefill_then_decode_through_the_two_pools_agree_with_the_reference(
        model, small_blocks, n_prompt, n_new, case):
    weights, params, cfg = model
    eng = engine(params, cfg, keep_logits=True)
    seq = eng.start(prompt(3, n_prompt), n_new)
    while not seq.done:
        eng.decode_step([seq])
    assert len(seq.token_logits) == n_new
    assert served_against_reference(weights, seq) < TOL, case
    total = n_prompt + n_new
    assert [len(b) for b in seq.blocks] == [-(-total // BS),
                                            min(-(-total // BS), RING)]
    eng.release(seq)
    eng.close()


def test_short_and_long_rows_decode_in_one_batch(model, small_blocks):
    weights, params, cfg = model
    eng = engine(params, cfg, keep_logits=True)
    assert [a.shape for a in eng.cache.arrays()] == [
        (1, 129, 2, BS, 8), (1, 129, 2, BS, 8),          # one full layer
        (4, 21, 2, BS, 8), (4, 21, 2, BS, 8)]            # four window layers
    # 5, 16 and 37 tokens to start with: three sequences decode in a batch of
    # four, one row padded, 39 steps: the longest wraps its ring nine times
    seqs = [eng.start(prompt(3 + i, n), 40) for i, n in enumerate((5, 16, 37))]
    for _ in range(39):
        eng.decode_step(seqs)
    assert max(served_against_reference(weights, s) for s in seqs) < TOL
    assert [len(s.blocks[1]) for s in seqs] == [RING, RING, RING]
    assert eng.cache.pools[1].high_water == 3 * RING
    for s in seqs:
        eng.release(s)
    # each wrote ceil(tokens / 4) blocks' worth onto five columns
    assert eng.cache.recycled == sum(-(-(n + 39) // BS) - RING
                                     for n in (5, 16, 37))
    eng.close()


def test_serve_takes_the_family_through_the_same_door(model, small_blocks):
    weights, params, cfg = model
    telemetry.tracing.clear()
    srv = serving.serve((params, cfg), max_batch=4, max_len=MAX_LEN,
                        block_size=BS)
    try:
        assert isinstance(srv.engine.model, serving.AfmoeLM)
        assert srv.engine.sync_reason is None
        handles = [srv.submit(prompt(i, 9 + 12 * i), max_new_tokens=24)
                   for i in range(3)]
        for h in handles:
            assert h.wait(120) and h.error is None
            want = ref_logits(weights, h.tokens)
            n = len(h.tokens) - 24
            assert [int(t) for t in want[n - 1:-1].argmax(-1)] == h.tokens[n:]
        # (h) what the spans carry
        steps = [s for s in telemetry.spans() if s["name"] == "serving.decode"
                 and "batch" in s["attrs"] and "live_max" in s["attrs"]]
        assert steps and all(
            0 < s["attrs"]["live_window"] <= s["attrs"]["live_full"]
            and s["attrs"]["live_window"] <= WINDOW * s["attrs"]["batch"]
            and s["attrs"]["live_max"] <= s["attrs"]["live_full"]
            for s in steps)
        assert any(s["attrs"]["live_window"] < s["attrs"]["live_full"]
                   for s in steps)
        fills = [s["attrs"] for s in telemetry.spans()
                 if s["name"] == "serving.prefill"]
        assert sorted((a["length"], a["bucket"]) for a in fills) \
            == [(9, 16), (21, 32), (33, 64)]
        assert all("moe_pairs" in a for a in fills)
        snap = srv.metrics.snapshot(srv.engine, srv.scheduler)
        assert snap["cache"]["window"]["blocks_total"] == 4 * RING
        assert snap["cache"]["window"]["blocks_high_water"] == 3 * RING
        assert snap["cache"]["window"]["blocks_recycled"] > 0
        assert snap["cache"]["blocks_total"] == 4 * 32
        text = srv.metrics.prometheus_text(srv.engine, srv.scheduler)
        for name in ("serving_window_blocks_in_use",
                     "serving_window_blocks_high_water",
                     "serving_window_blocks_total",
                     "serving_window_blocks_recycled",
                     "serving_moe_expert_tokens_layer3_expert1"):
            assert name in text
    finally:
        srv.close()


# -- (c), (d) ----------------------------------------------------------------

def test_the_reference_with_the_kinds_swapped_misses_the_tolerance(model):
    """The comparison sees the window: against a reference whose window
    layers attend over everything and whose full layer is cut to the window
    (positions kept as they are), the same served logits are far outside
    the tolerance as soon as a sequence is longer than the window."""
    weights, params, cfg = model
    eng = engine(params, cfg, keep_logits=True)
    seq = eng.start(prompt(3, 11), 30)
    while not seq.done:
        eng.decode_step([seq])
    got = np.stack(seq.token_logits)
    rows = slice(seq.prompt_len - 1, seq.prompt_len - 1 + len(got))
    assert np.abs(got - ref_logits(weights, seq.tokens)[rows]).max() < TOL
    swap = {"sliding_attention": "full_attention",
            "full_attention": "sliding_attention"}
    for wrong in ([swap[t] for t in TYPES],              # both wrong
                  ["sliding_attention"] * 5,             # the full layer cut
                  TYPES[:3] + ["full_attention"] + TYPES[4:]):   # one too wide
        # the reference applies positions by the kind it is told: keep the
        # rotary turn where the model has it by swapping the mask alone
        other = wrong_mask_logits(weights, seq.tokens, wrong)[rows]
        inside = np.abs(got - other)[:WINDOW - seq.prompt_len].max()
        beyond = np.abs(got - other)[WINDOW - seq.prompt_len + 1:].max()
        assert inside < TOL and beyond > 100 * TOL, (wrong, inside, beyond)
    eng.release(seq)
    eng.close()


def wrong_mask_logits(weights, tokens, kinds):
    """The reference's forward with the layers' MASKS of `kinds` and the
    positions of the true kinds."""
    real = reference._attend

    def attend(q, k, v, window):
        i = attend.layer
        attend.layer += 1
        return real(q, k, v, window=CONFIG["sliding_window"]
                    if kinds[i] == "sliding_attention" else 0)

    attend.layer = 0
    reference._attend = attend
    try:
        return ref_logits(weights, tokens)
    finally:
        reference._attend = real


def test_bfloat16_in_float32s_place_fails_the_tolerance(model):
    weights, params, cfg = model
    low = {n: (a if a.dtype != jnp.float32 or n.endswith("router_bias")
               else a.astype(jnp.bfloat16)) for n, a in params.items()}
    tokens = prompt(5, 40)
    got, _ = afmoe.afmoe_apply(low, jnp.asarray(tokens), cfg)
    assert np.abs(np.asarray(got) - ref_logits(weights, tokens)).max() \
        > 100 * TOL


# -- (e), (f) ----------------------------------------------------------------

def test_the_ranks_parts_and_the_shared_expert_once_add_up_to_the_layer(model):
    weights, params, cfg = model
    lw = weights["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 48), jnp.float32)
    real = np.ones((24,), bool)
    # the uncut layer: every published expert held at once
    every = jax.random.split(jax.random.PRNGKey(4), 3)
    whole = dict(lw, **{n: 0.02 * jax.random.normal(k, (8,) + lw[n].shape[1:])
                        for n, k in zip(("we_gate", "we_up", "we_down"),
                                        every)})
    uncut = reference.moe(x, whole, dict(CONFIG, num_experts=8, expert_rank=0,
                                         expert_parallel=1), None)
    shared = reference._swiglu(x, lw["ws_gate"], lw["ws_up"], lw["ws_down"],
                               None)
    parts = jnp.zeros_like(x)
    for rank in range(4):
        held = (2 * rank, 2 * rank + 2)
        p = {"layer2_" + n: (whole[n][held[0]:held[1]] if n.startswith("we_")
                             else lw[n]) for n in lw}
        out, counts = latent_moe.moe_ffn(
            p, "layer2_", x, real,
            afmoe.AfmoeConfig(**dict(vars(cfg), experts_held=held)))
        parts = parts + (out - shared)           # the routed part alone
        assert int(counts.sum()) > 0
    assert np.abs(np.asarray(parts + shared - uncut)).max() < TOL
    # a token's four winners over eight experts: every pair counted once
    assert np.abs(np.asarray(parts)).max() > 1e-3


def test_the_expert_layer_is_the_latent_familys_own():
    assert afmoe.moe_ffn is latent_moe.moe_ffn
    assert afmoe.rms_norm is latent_moe.rms_norm
    assert afmoe.swiglu is latent_moe.swiglu
    assert afmoe.apply_rope is latent_moe.apply_rope
    for name in ("route", "grouped_experts", "moe_ffn"):
        assert "def %s(" % name not in open(afmoe.__file__).read()
    # one group: the router's top-k is over all experts
    assert (afmoe.AfmoeConfig.n_groups, afmoe.AfmoeConfig.top_groups) == (1, 1)


# -- (g) the allocator ---------------------------------------------------------

def test_a_window_sequence_never_holds_more_than_its_ring(model):
    weights, params, cfg = model
    eng = engine(params, cfg)
    cache = eng.cache
    assert cache.rings == (0, RING) and cache.blocks_of == (129, 21)
    assert eng.blocks_needed(5, 3) == (2, 2)
    assert eng.blocks_needed(9, 8) == (5, 5)          # 17 tokens: the ring
    assert eng.blocks_needed(40, 80) == (30, RING)
    assert eng.blocks_needed(100, 100) == (32, RING)  # capped at max_len
    seq = eng.start(prompt(1, 10), 110)
    assert [len(b) for b in seq.blocks] == [30, RING]
    assert seq.table_row.shape == (32 + RING,)
    assert list(seq.table_row[:30]) == seq.blocks[0]
    assert list(seq.table_row[32:]) == seq.blocks[1]
    while not seq.done:
        eng.decode_step([seq])
        assert cache.pools[1].in_use == RING
    assert len(seq.tokens) == 120
    eng.release(seq)
    assert cache.recycled == 30 - RING
    assert [p.in_use for p in cache.pools] == [0, 0]
    eng.close()


def test_admission_takes_both_kinds_or_neither(model):
    weights, params, cfg = model
    # room for one long sequence's full blocks, and for two rings
    eng = engine(params, cfg, max_batch=2, num_blocks=33)
    full, window = eng.cache.pools
    assert (full.num_blocks, window.num_blocks) == (33, 2 * RING + 1)
    a = eng.begin(prompt(1, 30), 60)                  # 23 full blocks, a ring
    assert a is not None and (full.in_use, window.in_use) == (23, RING)
    # the full kind is short: nothing is taken of the window kind either
    assert not eng.can_admit(30, 60)
    assert eng.begin(prompt(2, 30), 60) is None
    assert (full.in_use, window.in_use) == (23, RING)
    b = eng.begin(prompt(2, 20), 10)                  # 8 and a ring: fits
    assert b is not None and (full.in_use, window.in_use) == (31, 2 * RING)
    # now the window kind is short though the full kind is not
    assert eng.can_admit(4, 2) is False and full.available == 1
    assert eng.begin(prompt(3, 3), 1) is None
    assert (full.in_use, window.in_use) == (31, 2 * RING)
    eng.release(a, reusable=False)
    eng.release(b, reusable=False)
    assert (full.in_use, window.in_use) == (0, 0)
    # a request no pool could ever hold is an error, and takes nothing
    small = kv_cache.PagedKVCache.of(eng.cache.spec, block_size=BS,
                                     num_blocks=(9, 4))
    with pytest.raises(kv_cache.CacheOverflow):
        small.try_alloc((3, 5))
    assert [p.in_use for p in small.pools] == [0, 0]
    assert small.try_alloc((8, 3)) is not None
    assert small.try_alloc((1, 1)) is None
    assert [p.in_use for p in small.pools] == [8, 3]
    eng.close()


def test_every_block_of_both_kinds_is_back_after_finish_fault_and_close(
        model, small_blocks):
    weights, params, cfg = model
    srv = serving.serve((params, cfg), max_batch=2, max_len=MAX_LEN,
                        block_size=BS)
    eng = srv.engine
    try:
        done = srv.submit(prompt(1, 20), max_new_tokens=12)
        assert done.wait(120) and done.error is None
        assert [p.in_use for p in eng.cache.pools] == [0, 0]
        # a fault in a step: the loop replays or fails the request, and no
        # block of either kind stays behind
        real_decode = eng.model.decode
        calls = []

        def faulty(*args):
            calls.append(1)
            if len(calls) == 3:
                raise RuntimeError("injected step fault")
            return real_decode(*args)

        eng.model.decode = faulty
        hurt = [srv.submit(prompt(2 + i, 9), max_new_tokens=10)
                for i in range(2)]
        for h in hurt:
            assert h.wait(120)
        eng.model.decode = real_decode
    finally:
        srv.close()                 # `audit_quiescent` over both pools
    assert [p.in_use for p in eng.cache.pools] == [0, 0]
    # a leak in the window kind alone is found and named
    leaked = eng.cache.pools[1].try_alloc(2)
    with pytest.raises(MXNetError, match="not quiescent"):
        eng.cache.assert_quiescent()
    eng.cache.pools[1].free(leaked)
    eng.cache.assert_quiescent()


def test_the_one_kind_families_tables_and_counts_are_what_they_were():
    tcfg = TransformerConfig(vocab=48, d_model=32, n_heads=4, n_layers=2,
                             d_ff=64, max_len=64)
    eng = serving.Engine(serving.TransformerLM(
        init_transformer_params(jax.random.PRNGKey(0), tcfg), tcfg),
        max_batch=4, block_size=8)
    cache = eng.cache
    assert cache.spec.kinds == ("full",) and cache.rings == (0,)
    assert cache.spec.paged_unfit() is None
    assert [a.shape for a in cache.arrays()] == [(2, 33, 4, 8, 8)] * 2
    assert cache.pools == (cache.pool,) and cache.num_blocks == 33
    assert eng.blocks_needed(10, 9) == (3,)
    seq = eng.start([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 9)
    assert seq.blocks == (seq.block_ids,) and len(seq.block_ids) == 3
    assert seq.table_row.shape == (8,)
    assert list(seq.table_row) == seq.block_ids + [0] * 5
    assert "live_window" not in telemetry.spans()[-1]["attrs"]
    eng.release(seq)
    assert seq.block_ids == [] and cache.recycled == 0
    eng.close()
    # the latent layout: one array, one pool
    from chipbench.families import latent_moe_lm
    from test_latent_moe import CONFIG as LATENT
    weights = latent_moe_lm.make_weights(LATENT, 1)
    eng = serving.Engine(serving.LatentMoELM(
        latent_moe_lm.program_params(weights),
        latent_moe_lm.program_config(LATENT, 64)), max_batch=2, block_size=8)
    assert eng.cache.kv.shape == (3, 17, 8, 128) and len(eng.cache.pools) == 1
    assert eng.blocks_needed(10, 9) == (3,)
    assert "latent rows" in eng.cache.spec.paged_unfit()
    eng.close()


# -- (i) ---------------------------------------------------------------------

def pool_copies(hlo_text, shape, dtype="f32"):
    tag = "%s[%s]" % (dtype, ",".join(str(d) for d in shape))
    return [l for l in hlo_text.splitlines()
            if re.search(r"= \S+ copy\(", l) and tag in l.split(" copy(")[0]]


@pytest.mark.parametrize("attr", ["_prefill_jit", "_decode_jit"])
def test_step_program_aliases_and_consumes_its_four_pools(model, attr):
    _, params, cfg = model
    adapter = serving.AfmoeLM(params, cfg)
    adapter.bind(BS)
    shapes = [(1, 12, 2, BS, 8)] * 2 + [(4, 9, 2, BS, 8)] * 2
    pools = [jnp.zeros(s, jnp.float32) for s in shapes]
    i32 = jnp.int32
    rest = {"_prefill_jit": (jnp.zeros((16,), i32), i32(5),
                             jnp.arange(1, 9 + RING, dtype=i32) % 9),
            "_decode_jit": (jnp.zeros((4,), i32), jnp.zeros((2,), i32),
                            jnp.asarray([3, 9], i32),
                            jnp.asarray([[1, 2] + [0] * 6 + [1] + [0] * 4,
                                         [3, 4, 5] + [0] * 5 + [2, 3, 4, 0, 0]],
                                        i32))}[attr]
    jit = getattr(adapter, attr)
    compiled = jit.lower(params, *pools, *rest).compile()
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= sum(p.nbytes for p in pools)
    for shape in set(shapes):
        assert not pool_copies(compiled.as_text(), shape)
    out = jit(params, *pools, *rest)
    assert all(p.is_deleted() for p in pools)
    assert [o.shape for o in out[:4]] == shapes
    assert not any(a.is_deleted() for a in params.values())
