"""The gather decode step walks its block table only as far as the longest
live sequence (ISSUE 28).

Load-bearing claims: (a) the gather `decode` step's logits are those of a plain masked
softmax over the table's full width, written here, at ragged lengths on
both sides of a chunk boundary; (b) what `serve` emits is token for token
what the step's earlier form (one contraction over the full width, kept
here as the reference) gives; (c) a sequence that grows across chunk
boundaries compiles nothing: one program per batch bucket; (d) the compiled
step has one `while` a layer, no `conditional`, no copy of a pool, and
aliases both pools, on the CPU and for a described v5e; (e) the
`serving.decode` span's `live_max` is the longest sequence of its step.
"""
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving, telemetry
from mxnet_tpu.models.transformer import (TransformerConfig, _layer_norm,
                                          init_transformer_params)
from mxnet_tpu.serving import engine as engine_mod
from mxnet_tpu.serving import kv_cache

H, DH, BS, L = 4, 8, 16, 2
MAX_LEN = 512                               # 32 blocks: four chunks of 128
NBLK = MAX_LEN // BS
CHUNK = kv_cache._DECODE_CHUNK_TOKENS
i32 = jnp.int32


@pytest.fixture(scope="module")
def lm():
    cfg = TransformerConfig(vocab=48, d_model=H * DH, n_heads=H, n_layers=L,
                            d_ff=64, max_len=MAX_LEN)
    return init_transformer_params(jax.random.PRNGKey(0), cfg), cfg


def full_width_decode(params, k_pool, v_pool, tokens, positions, tables, cfg,
                      block_size):
    """The decode step as it was before the loop: every layer gathers the
    table's full width and runs one masked softmax over it."""
    B = tokens.shape[0]
    D, Hn = cfg.d_model, cfg.n_heads
    Dh = D // Hn
    x = params["embed"][tokens] + params["pos_embed"][positions]
    slots = kv_cache.flat_slots(tables, positions, block_size)
    nblk = tables.shape[1]
    T = nblk * block_size
    live = jnp.arange(T)[None, :] <= positions[:, None]
    for i in range(cfg.n_layers):
        pre = "layer%d_" % i
        h = _layer_norm(x, params[pre + "ln1_g"], params[pre + "ln1_b"])
        q, kk, vv = jnp.split(h @ params[pre + "wqkv"], 3, axis=-1)
        k_pool, v_pool = kv_cache.append_kv(
            k_pool, v_pool, i, slots, kk.reshape(B, Hn, Dh),
            vv.reshape(B, Hn, Dh))
        ks, vs = kv_cache.gather_kv(k_pool, v_pool, i, tables)
        s = jnp.einsum("bhd,bnhsd->bhns", q.reshape(B, Hn, Dh),
                       ks).astype(jnp.float32) / math.sqrt(Dh)
        s = jnp.where(live[:, None, :], s.reshape(B, Hn, T), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        att = jnp.einsum("bhns,bnhsd->bhd", p.reshape(B, Hn, nblk, block_size),
                         vs.astype(p.dtype))
        x = x + att.astype(x.dtype).reshape(B, D) @ params[pre + "wo"]
        h = _layer_norm(x, params[pre + "ln2_g"], params[pre + "ln2_b"])
        x = x + jax.nn.relu(h @ params[pre + "w1"]) @ params[pre + "w2"]
    h = _layer_norm(x, params["lnf_g"], params["lnf_b"])
    logits = (h @ params["head"]).astype(jnp.float32)
    return k_pool, v_pool, logits, jnp.argmax(logits, -1).astype(jnp.int32)


def gather_decode(cfg):
    """The gather engine's decode step function, pools as two arguments."""
    return lambda p, k, v, t, pos, tb: engine_mod.decode(
        p, (k, v), jnp.zeros_like(t), t, pos, tb, cfg, BS,
        kv_cache.LiveGatherView)


def filled_pools(cfg, n_rows, seed=1):
    """Pools whose every slot holds noise (what a reused block holds past a
    sequence's length must not reach the logits) and a table a row."""
    shape = (cfg.n_layers, n_rows * NBLK + 1, H, BS, DH)
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    tables = 1 + np.arange(n_rows * NBLK, dtype=np.int32).reshape(n_rows, NBLK)
    return (jax.random.normal(kk, shape), jax.random.normal(kv, shape), tables)


# -- (a) -----------------------------------------------------------------------

RAGGED = {
    "a_batch_of_one": ([300], 0),
    "padded_rows_on_the_null_table": ([37, 140], 2),
    "one_short_of_a_chunk_boundary": ([CHUNK - 1, 5], 0),
    "at_a_chunk_boundary": ([CHUNK, 2 * CHUNK, 9], 0),
    "one_past_a_chunk_boundary": ([CHUNK + 1, 3 * CHUNK + 1], 0),
    "one_at_max_len_beside_short_ones": ([MAX_LEN, 3, 70, 129], 0),
}


@pytest.mark.parametrize("case", sorted(RAGGED))
def test_logits_are_the_full_width_masked_softmaxs(lm, case):
    params, cfg = lm
    lengths, padded = RAGGED[case]
    k, v, tables = filled_pools(cfg, len(lengths))
    tables = np.concatenate([tables, np.zeros((padded, NBLK), np.int32)])
    # a sequence of n tokens decodes its last one at position n - 1
    pos = np.asarray([n - 1 for n in lengths] + [0] * padded, np.int32)
    toks = (np.arange(len(pos), dtype=np.int32) * 7 + 3) % cfg.vocab
    args = (params, k, v, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(tables))
    want_k, want_v, want, want_next = full_width_decode(*args, cfg, BS)
    got_k, got_v, got, got_next = jax.jit(gather_decode(cfg))(*args)
    live = len(lengths)
    np.testing.assert_allclose(np.asarray(got)[:live], np.asarray(want)[:live],
                               rtol=1e-4, atol=1e-5)
    assert np.isfinite(np.asarray(got)).all()           # padded rows too
    np.testing.assert_array_equal(np.asarray(got_next)[:live],
                                  np.asarray(want_next)[:live])
    # the pools are written as before; the loop only reads them. The null
    # block takes every padded row's write: whichever lands last is kept
    np.testing.assert_allclose(np.asarray(got_k)[:, 1:], np.asarray(want_k)[:, 1:],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_v)[:, 1:], np.asarray(want_v)[:, 1:],
                               rtol=1e-4, atol=1e-5)


# -- (b), (c), (e): through the server ------------------------------------------


def arith_prompt(start, stride, n, vocab=48):
    return [(start + stride * t) % vocab for t in range(n)]


def reference_tokens(params, cfg, prompt, max_new):
    """Greedy tokens of one sequence from the `prefill` step function and
    the full-width step above, under a plain `jax.jit`, on a pool of this
    test's own."""
    shape = (cfg.n_layers, NBLK + 1, H, BS, DH)
    k, v = jnp.zeros(shape), jnp.zeros(shape)
    row = jnp.arange(1, NBLK + 1, dtype=i32)
    prefill = jax.jit(lambda p, k, v, t, n, tb: engine_mod.prefill(
        p, (k, v), t, n, tb, cfg))
    decode = jax.jit(lambda p, k, v, t, pos, tb: full_width_decode(
        p, k, v, t, pos, tb, cfg, BS))
    toks = np.zeros((engine_mod.pow2_bucket(len(prompt), lo=8),), np.int32)
    toks[:len(prompt)] = prompt
    k, v, logits = prefill(params, k, v, jnp.asarray(toks), i32(len(prompt)),
                           row)
    out = list(prompt) + [int(np.argmax(np.asarray(logits)))]
    while len(out) < len(prompt) + max_new:
        k, v, _, nxt = decode(params, k, v, jnp.asarray(out[-1:], i32),
                              jnp.asarray([len(out) - 1], i32), row[None])
        out.append(int(nxt[0]))
    return out[len(prompt):]


def test_served_tokens_are_the_full_width_steps_tokens(lm):
    """Two requests side by side, one growing from 100 tokens across the
    boundaries at 128 and 256 while the other stays within the first chunk:
    token for token the reference's; nothing compiles after the first step
    of the batch; each step's span says how long its longest sequence was."""
    params, cfg = lm
    prompts = [arith_prompt(1, 1, 100), arith_prompt(5, 2, 9)]
    new = [2 * CHUNK + 40 - 100, 30]
    want = [reference_tokens(params, cfg, p, n) for p, n in zip(prompts, new)]
    telemetry.tracing.clear()
    srv = serving.serve((params, cfg), max_batch=2, block_size=BS,
                        max_len=MAX_LEN)
    try:
        eng = srv.engine
        assert not eng.paged                    # the default, gather path
        reqs = [srv.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
        assert [r.result(timeout=300) for r in reqs] == want       # (b)
        # (c): one program a batch bucket (2 while both ran, 1 after), and
        # the long request crossed two chunk boundaries inside them
        assert eng.decode_compilations <= 2, sorted(eng._sigs)
        steps = [s for s in telemetry.spans()
                 if s["name"] == "serving.decode" and "batch" in s["attrs"]]
        assert len(steps) >= new[0] - 1
        # (e): the long request is in every step and gains a token a step
        assert [s["attrs"]["live_max"] for s in steps] \
            == list(range(101, 101 + len(steps)))
        assert steps[-1]["attrs"]["live_max"] > 2 * CHUNK
        assert srv.metrics._g_live_max.value == steps[-1]["attrs"]["live_max"]
    finally:
        srv.close()


def test_growing_across_chunk_boundaries_compiles_nothing(lm):
    """The engine alone, one sequence: the step compiled at 100 tokens is the
    step that runs at 300; `live_max` is on a span with no server too."""
    params, cfg = lm
    eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=2,
                         block_size=BS, max_len=MAX_LEN)
    seq = eng.start(arith_prompt(3, 1, 100), max_new=2 * CHUNK + 50 - 100)
    eng.decode_step([seq])
    compiled = eng.decode_compilations
    assert compiled == 1
    telemetry.tracing.clear()
    lengths = []
    while not seq.done:
        lengths.append(len(seq.tokens))
        eng.decode_step([seq])
    assert lengths[0] < CHUNK and lengths[-1] > 2 * CHUNK
    assert eng.decode_compilations == compiled
    steps = [s for s in telemetry.spans()
             if s["name"] == "serving.decode" and "batch" in s["attrs"]]
    assert [s["attrs"]["live_max"] for s in steps] == lengths
    eng.release(seq)


# -- (d) -------------------------------------------------------------------------


def pool_copies(hlo_text, shape, dtype):
    tag = "%s[%s]" % (dtype, ",".join(str(d) for d in shape))
    return [l for l in hlo_text.splitlines()
            if re.search(r"= \S+ copy\(", l) and tag in l.split(" copy(")[0]]


def step_shapes(cfg, batch, pool, dtype, sharding=None):
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=sharding)
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, dtype), jax.eval_shape(
            lambda: init_transformer_params(jax.random.PRNGKey(0), cfg)))
    nblk = cfg.max_len // pool[3]
    return (params, sds(pool, dtype), sds(pool, dtype), sds((batch,), i32),
            sds((batch,), i32), sds((batch,), i32), sds((batch, nblk), i32))


def assert_one_loop_a_layer(compiled, cfg, k_pool):
    text = compiled.as_text()
    assert len(re.findall(r" while\(", text)) == cfg.n_layers
    assert " conditional(" not in text
    hlo_name = {"float32": "f32", "bfloat16": "bf16"}[k_pool.dtype.name]
    assert not pool_copies(text, k_pool.shape, hlo_name)
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * k_pool.size * k_pool.dtype.itemsize


def test_the_step_is_one_loop_a_layer_and_updates_its_pools_in_place(lm):
    params, cfg = lm
    model = serving.TransformerLM(params, cfg)
    model.bind(BS)
    pool = (L, 2 * NBLK + 1, H, BS, DH)
    shapes = step_shapes(cfg, 2, pool, jnp.float32)
    compiled = model.programs["decode"].lower(*shapes).compile()
    assert_one_loop_a_layer(compiled, cfg, shapes[1])


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def test_the_cells_step_compiles_for_the_chip_as_one_loop_a_layer(one_chip):
    """At the OPT cell's widths, table and pool (two of its eight layers),
    for the v5e's compiler: no branch, no copy of a pool, both pools
    aliased; no layer's slice of a pool written out before the gather (the
    chip's compiler does that for `pool[layer][table]`, once a pass of the
    loop: 0.13 GB each of K and V); and the scratch far under the 0.8 GB a
    layer that the full width's gathered and upcast V took."""
    cfg = TransformerConfig(vocab=50272, d_model=4096, n_heads=32, n_layers=2,
                            d_ff=16384, max_len=2048, dtype=jnp.bfloat16)
    pool = (2, 1025, 32, 16, 128)
    shapes = step_shapes(cfg, 16, pool, jnp.bfloat16, one_chip)
    model = serving.TransformerLM(shapes[0], cfg)
    model.bind(16)
    compiled = model.programs["decode"].lower(*shapes).compile()
    assert_one_loop_a_layer(compiled, cfg, shapes[1])
    assert "bf16[%s]" % ",".join(map(str, pool[1:])) not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


def test_the_two_kind_step_compiles_for_the_chip_without_a_copy_of_a_pool(
        one_chip):
    """At the `trinity_mixed_closed` cell's widths, tables and pools (a
    window layer and a full layer of its five; 8 experts of 256 held), for
    the v5e's compiler: a loop a layer, the four planes aliased, no copy of
    either kind's plane (their trailing axes, 16 x 128 of 8 heads, are whole
    tiles as they lie), and the scratch far under a plane."""
    from mxnet_tpu.models import afmoe
    from mxnet_tpu.serving import afmoe_lm
    cfg = afmoe.AfmoeConfig(
        vocab=25024, d_model=3072, n_heads=48, n_kv_heads=8, head_dim=128,
        n_layers=2, n_dense_layers=1, layer_kinds=("window", "full"),
        window=4096, d_ff=12288, d_expert=3072, n_experts=256,
        experts_held=(0, 8), max_len=9728, dtype=jnp.bfloat16)
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    mats, gains = afmoe.param_shapes(cfg)
    params = {n: sds(s, jnp.bfloat16) for n, s in {**mats, **gains}.items()}
    params["layer1_router_bias"] = sds((256,), jnp.float32)
    model = afmoe_lm.AfmoeLM(params, cfg)
    model.bind(16)
    full, ring = (1, 32 * 608 + 1, 8, 16, 128), (1, 32 * 257 + 1, 8, 16, 128)
    compiled = model._decode_jit.lower(
        params, sds(full, jnp.bfloat16), sds(full, jnp.bfloat16),
        sds(ring, jnp.bfloat16), sds(ring, jnp.bfloat16), sds((32,), i32),
        sds((32,), i32), sds((32,), i32), sds((32, 608 + 257), i32)).compile()
    text = compiled.as_text()
    assert " conditional(" not in text
    # one attention walk a layer, one tile loop in the expert layer
    assert len(re.findall(r" while\(", text)) == 3
    for shape in (full, ring):
        assert not pool_copies(text, shape, "bf16")
    planes = 2 * (np.prod(full) + np.prod(ring)) * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= planes
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1e9


def test_the_prompt_attention_kernel_compiles_for_the_chip_at_the_cells_widths(
        one_chip):
    """`ops/pallas_prompt_attention.py` at the `trinity_mixed_closed`
    cell's widths (48 query heads on 8 of 128, bf16) and its longest
    bucket, a window layer and the full layer, through Mosaic for the
    v5e: the blocks of `block_sizes` are whole tiles and the scores of
    3,072 rows by 1,024 keys fit the VMEM the kernel asks for. Handed q,
    k and v as the layer has them, the program is the kernel and the
    reshapes round it: no `while`, no operation of the scores' size."""
    from mxnet_tpu.ops import pallas_prompt_attention as pa
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    S, H, Hkv, Dh = 8192, 48, 8, 128
    assert pa.prompt_attention_unfit(S, Dh, H // Hkv, jnp.bfloat16,
                                     "tpu") is None
    # the benchmark's precision (the suite's "highest" would ask Mosaic
    # for a float32 product of bf16 operands)
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    try:
        for window in (4096, 0):
            compiled = jax.jit(
                lambda q, k, v, n: pa.prompt_attention(
                    q, k, v, n, window=window)).lower(
                sds((S, H, Dh), jnp.bfloat16), sds((S, Hkv, Dh), jnp.bfloat16),
                sds((S, Hkv, Dh), jnp.bfloat16), sds((), i32)).compile()
            text = compiled.as_text()
            assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                                  text)) == 1
            assert " while(" not in text and "f32[8,6," not in text
    finally:
        jax.config.update("jax_default_matmul_precision", was)


def test_the_state_kinds_steps_compile_for_the_chip_without_a_copy_of_a_plane(
        one_chip, monkeypatch):
    """At the `falconh1_chat_closed` cell's widths, tables and pools (two
    layers of its six), for the v5e's compiler, both kernels lowered by
    Mosaic as on the chip: the decode step holds one `decode_walk` and one
    `ssm_step` a layer, no loop, no copy of either kind's planes (as XLA's
    gather the state's 4 MB slices are split by writing the whole plane out
    as two halves, every layer: PERF.md, PR 37), all four planes aliased and
    the scratch far under a row's states; the prefill writes one slot and
    holds no operation of a plane's size either."""
    from mxnet_tpu.models import falcon_h1
    from mxnet_tpu.ops import pallas_decode_walk, pallas_ssm_step
    from mxnet_tpu.serving import falcon_h1_lm, kv_cache
    ssm_gate, walk_gate = (pallas_ssm_step.step_fallback_reason,
                           pallas_decode_walk.walk_fallback_reason)
    monkeypatch.setattr(pallas_ssm_step, "step_fallback_reason",
                        lambda plane, backend=None: ssm_gate(plane, "tpu"))
    monkeypatch.setattr(pallas_decode_walk, "walk_fallback_reason",
                        lambda *a, **k: walk_gate(*a[:3], backend="tpu"))
    monkeypatch.setattr(kv_cache, "default_interpret", lambda: False)
    # the benchmark's precision: the suite's "highest" asks the walk's kernel
    # for a float32 product of bf16 operands, which Mosaic has not
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    try:
        _state_steps_compile(one_chip)
    finally:
        jax.config.update("jax_default_matmul_precision", was)


def _state_steps_compile(one_chip):
    from mxnet_tpu.models import falcon_h1
    from mxnet_tpu.serving import falcon_h1_lm
    cfg = falcon_h1.FalconH1Config(
        vocab=261120, d_model=5120, n_heads=20, n_kv_heads=4, head_dim=128,
        n_layers=2, d_ff=21504, ssm_heads=32, ssm_head_dim=128, ssm_state=256,
        ssm_groups=2, conv_taps=4, chunk=128, max_len=1024,
        dtype=jnp.bfloat16, key_multiplier=0.011, lm_head_multiplier=0.0078125,
        ssm_multipliers=(0.35, 0.25, 0.18, 0.5, 0.35))
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    mats, gains, vectors = falcon_h1.param_shapes(cfg)
    params = {n: sds(s, jnp.bfloat16) for n, s in {**mats, **gains}.items()}
    params.update({n: sds(s, jnp.float32) for n, s in vectors.items()})
    model = falcon_h1_lm.FalconH1LM(params, cfg)
    model.bind(16)
    kv, state, conv = ((2, 64 * 64 + 1, 4, 16, 128), (2, 65, 2, 256, 16, 128),
                       (2, 65, 3 * 5120))
    assert model.cache_spec().state_shape == state[2:]
    pools = (sds(kv, jnp.bfloat16), sds(kv, jnp.bfloat16),
             sds(state, jnp.float32), sds(conv, jnp.bfloat16))
    planes = 2 * np.prod(kv) * 2 + np.prod(state) * 4 + np.prod(conv) * 2
    compiled = model._decode_jit.lower(
        params, *pools, sds((64,), i32), sds((64,), i32), sds((64,), i32),
        sds((64, 65), i32)).compile()
    text = compiled.as_text()
    assert " conditional(" not in text and " while(" not in text
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) == 4
    assert not pool_copies(text, kv, "bf16")
    assert not pool_copies(text, state, "f32")
    # no half of the state plane either, nor the rows' states gathered
    assert "f32[2,65,2,256,16,64]" not in text
    assert "f32[64,2,256,16,128]" not in text
    assert compiled.memory_analysis().alias_size_in_bytes >= planes
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05e9
    compiled = model._prefill_jit.lower(
        params, *pools, sds((512,), i32), sds((), i32),
        sds((65,), i32)).compile()
    text = compiled.as_text()
    assert not pool_copies(text, kv, "bf16")
    assert not pool_copies(text, state, "f32")
    assert compiled.memory_analysis().alias_size_in_bytes >= planes
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


def test_the_pattern_familys_steps_compile_for_the_chip_without_a_copy_of_a_plane(
        one_chip, monkeypatch):
    """At the `nemotron3_reason_closed` cell's widths, tables and pools
    (four of its thirteen layers, every letter: a state alone twice,
    experts and no cache, keys and values alone), for the v5e's compiler,
    both kernels lowered by Mosaic as on the chip: the decode step of 128
    rows holds an `ssm_step` a state layer over heads of 64 side by side
    and ONE `decode_walk` of 16 query heads a cached head, the expert
    layer's one tile loop, no copy of either kind's planes, all four planes
    aliased; the prefill writes one slot and one sequence's blocks and
    holds no operation of a plane's size either (left to choose, the
    compiler keeps the scan's state with N innermost and turns the WHOLE
    state plane round to match it, twice a prefill: PERF.md, PR 42)."""
    from mxnet_tpu.ops import pallas_decode_walk, pallas_ssm_step
    ssm_gate, walk_gate = (pallas_ssm_step.step_fallback_reason,
                           pallas_decode_walk.walk_fallback_reason)
    monkeypatch.setattr(pallas_ssm_step, "step_fallback_reason",
                        lambda plane, backend=None: ssm_gate(plane, "tpu"))
    monkeypatch.setattr(pallas_decode_walk, "walk_fallback_reason",
                        lambda *a, **k: walk_gate(*a[:3], backend="tpu"))
    monkeypatch.setattr(kv_cache, "default_interpret", lambda: False)
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    try:
        _pattern_steps_compile(one_chip)
    finally:
        jax.config.update("jax_default_matmul_precision", was)


def test_the_pattern_familys_steps_hold_one_grouped_product_a_layer_and_no_copy_of_the_experts(
        one_chip, monkeypatch):
    """The same steps with the experts' gate open as on the chip (ISSUE
    43): the decode step's one tile loop is gone and a fourth kernel stands
    in its place, and neither step copies a layer's stacked experts. The
    chip keeps `we_up` (64, 2688, 1856), whose last axis is not whole
    lanes, with the MODEL width innermost: handed to the kernel as it is
    named it would be copied whole, 660 MB a layer a step; it goes in by
    its transpose, which is the array as it lies (PERF.md §6, PR 43)."""
    from mxnet_tpu.models import latent_moe
    from mxnet_tpu.ops import (pallas_decode_walk, pallas_grouped_experts,
                               pallas_ssm_step)
    ssm_gate, walk_gate, moe_gate = (
        pallas_ssm_step.step_fallback_reason,
        pallas_decode_walk.walk_fallback_reason,
        pallas_grouped_experts.experts_unfit)
    monkeypatch.setattr(pallas_ssm_step, "step_fallback_reason",
                        lambda plane, backend=None: ssm_gate(plane, "tpu"))
    monkeypatch.setattr(pallas_decode_walk, "walk_fallback_reason",
                        lambda *a, **k: walk_gate(*a[:3], backend="tpu"))
    monkeypatch.setattr(pallas_grouped_experts, "experts_unfit",
                        lambda *a, **k: moe_gate(*a[:4], backend="tpu"))
    monkeypatch.setattr(kv_cache, "default_interpret", lambda: False)
    monkeypatch.setattr(latent_moe, "default_interpret", lambda: False)
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    try:
        _pattern_steps_compile(one_chip, experts_kernel=True)
    finally:
        jax.config.update("jax_default_matmul_precision", was)


def _pattern_steps_compile(one_chip, experts_kernel=False):
    from mxnet_tpu.models import nemotron_h
    from mxnet_tpu.serving import nemotron_h_lm
    cfg = nemotron_h.NemotronHConfig(
        vocab=65536, d_model=2688, pattern="MEM*", n_heads=32, n_kv_heads=2,
        head_dim=128, ssm_heads=64, ssm_head_dim=64, ssm_state=128,
        ssm_groups=8, conv_taps=4, chunk=128, d_expert=1856, d_shared=3712,
        n_experts=128, top_k=6, route_scale=2.5, experts_held=(0, 64),
        max_len=3072, dtype=jnp.bfloat16)
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    mats, gains, vectors = nemotron_h.param_shapes(cfg)
    params = {n: sds(s, jnp.bfloat16) for n, s in {**mats, **gains}.items()}
    params.update({n: sds(s, jnp.float32) for n, s in vectors.items()})
    model = nemotron_h_lm.NemotronHLM(params, cfg)
    model.bind(16)
    kv, state, conv = ((1, 128 * 192 + 1, 2, 16, 128), (2, 129, 8, 128, 512),
                       (2, 129, 3 * 6144))
    spec = model.cache_spec()
    assert spec.state_shape == state[2:] and spec.q_group == 16
    assert spec.layer_kinds == ("state", "none", "state", "full")
    pools = (sds(kv, jnp.bfloat16), sds(kv, jnp.bfloat16),
             sds(state, jnp.float32), sds(conv, jnp.bfloat16))
    planes = 2 * np.prod(kv) * 2 + np.prod(state) * 4 + np.prod(conv) * 2
    compiled = model._decode_jit.lower(
        params, *pools, sds((128,), i32), sds((128,), i32), sds((128,), i32),
        sds((128, 193), i32)).compile()
    text = compiled.as_text()
    assert " conditional(" not in text
    # the experts' tiles: one loop of passes, or one kernel
    assert len(re.findall(r" while\(", text)) == (not experts_kernel)
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", text)) \
        == 3 + experts_kernel
    experts = (64, 2688, 1856), (64, 1856, 2688)
    assert not pool_copies(text, kv, "bf16")
    assert not pool_copies(text, state, "f32")
    assert not any(pool_copies(text, shape, "bf16") for shape in experts)
    # nor the rows' states gathered
    assert "f32[128,8,128,512]" not in text
    assert compiled.memory_analysis().alias_size_in_bytes >= planes
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05e9
    compiled = model._prefill_jit.lower(
        params, *pools, sds((1024,), i32), sds((), i32),
        sds((193,), i32)).compile()
    text = compiled.as_text()
    assert not pool_copies(text, kv, "bf16")
    assert not pool_copies(text, state, "f32")
    assert not any(pool_copies(text, shape, "bf16") for shape in experts)
    assert compiled.memory_analysis().alias_size_in_bytes >= planes
    # with the kernel, the sorted rows there and back: 2 x 88 MB
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 0.2e9 + 0.05e9 * experts_kernel
