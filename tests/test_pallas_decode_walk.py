"""The gather path's decode walk as one kernel a layer (ISSUE 33):
ops/pallas_decode_walk.py under `kv_cache.LiveGatherView`.

Load-bearing claims: (a) the kernel, in interpreter mode, gives what
`_attend_live` gives and what a dense float32 softmax over the table's
whole width gives, for one query head a cached head and for a group of
six, over full columns and round a ring that has wrapped more than
twice, for rows of length 1, one block exactly, one token past a block
and the longest beside the shortest, for padded rows, for bf16 and
float32 planes, and one token either side of a window's edge; (b) a row
reads its own live blocks and no other; (c) both families are served
THROUGH the kernel, a step in flight, token for token as `_attend_live`
serves them, and the engine, the span and the counters say which walk a
step's program holds; (d) the gate's reasons; (e) a decode step's module,
lowered for the TPU, holds ONE `tpu_custom_call` a cache kind whatever the
number of layers: the layer's index is data, so every layer is a call
site of one lowered function; and the steps of every batch bucket splice
in ONE kernel, traced and lowered to Mosaic once a process: its operands
are at the engine's rows and the batch is its grid, as data; a
head-sharded step holds it over a chip's local heads (what is
traced or lowered per layer or per program is paid in every warm
`setup_s`: PERF.md §6, PRs 25, 32 and 33).

Tolerances. Float32 planes: 2e-5 on outputs of order 1: the kernel and
both references accumulate in float32 (the suite multiplies at "highest")
and differ in the order of the sums alone. bf16 planes: 2e-2: the kernel
rounds a chunk's probabilities to bf16 for the second product (as XLA's
default precision does on the chip) where the references keep them in
float32; half a bf16 ulp of a probability under 1 is 2e-3, against values
of order 1 summed over a row.
"""
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving, telemetry
from mxnet_tpu.models import afmoe
from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params)
from mxnet_tpu.ops import pallas_decode_walk as walk
from mxnet_tpu.serving import afmoe_lm, kv_cache

from chipbench.families import afmoe_lm as kinds_family

BS, DH, LAYERS = 16, 8, 3
i32 = jnp.int32
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


def pools(rng, B, Hkv, W, dtype, null_rows=0):
    """Planes of LAYERS layers, a table of distinct blocks a row (the last
    `null_rows` rows carry the all-null one), random queries."""
    blocks = B * W + 1
    k, v = (jnp.asarray(rng.normal(size=(LAYERS, blocks, Hkv, BS, DH)),
                        dtype) for _ in range(2))
    tables = (rng.permutation(blocks - 1)[:B * W] + 1).reshape(B, W)
    if null_rows:
        tables[-null_rows:] = 0
    return k, v, jnp.asarray(tables, i32)


def kernel(q, k, v, tables, pos, layer, window=0, blocks=2, rows=None):
    """The kernel in interpreter mode, in chunks of `blocks` blocks, so
    that short tables take several."""
    Hkv, W = k.shape[2], tables.shape[1]
    return walk.decode_walk(
        q, k, v, tables, pos, i32(layer), scale=1 / math.sqrt(DH),
        window=window, ring=W if window else 0, interpret=True, rows=rows,
        chunk_bytes=blocks * Hkv * BS * DH * k.dtype.itemsize)


W_FULL, WINDOW = 8, 64
RING = WINDOW // BS + 1                       # 5 blocks: 80 positions a lap
CASES = {
    # name: (G, window, positions of the real rows); two padded rows follow
    "g1_full": (1, 0, [0, BS - 1, BS, 2 * BS + 3, W_FULL * BS - 1, 0]),
    "g6_full": (6, 0, [W_FULL * BS - 1, 0, BS - 1, BS, 70, 100]),
    # the ring wraps at 80: 200 and 333 are past two and four laps
    "g1_ring": (1, WINDOW, [0, BS - 1, BS, 79, 80, 200, 333]),
    "g6_ring": (6, WINDOW, [333, 0, 15, 16, 79, 81, 255, 256]),
    # a window's edge: a row at `WINDOW - 1` sees position 0, one at
    # `WINDOW` does not, one at `WINDOW + 1` lost position 1 too
    "g6_edge": (6, WINDOW, [WINDOW - 2, WINDOW - 1, WINDOW, WINDOW + 1]),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_attend_live_and_the_dense_softmax(case, dtype):
    G, window, positions = CASES[case]
    Hkv, dtype = 2, jnp.dtype(dtype)
    rng = np.random.default_rng(len(case))
    B = len(positions) + 2
    W = RING if window else W_FULL
    k, v, tables = pools(rng, B, Hkv, W, dtype, null_rows=2)
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, DH)), dtype)
    pos = jnp.asarray(positions + [0, 0], i32)
    for layer in (0, LAYERS - 1):
        out = kernel(q, k, v, tables, pos, layer, window)
        live = kv_cache._attend_live(q, k, v, layer, tables, pos, window)
        dense = walk.reference(q, k, v, tables, pos, layer, window)
        assert out.shape == (B, Hkv * G, DH) and out.dtype == jnp.float32
        assert bool(jnp.isfinite(out).all())
        np.testing.assert_allclose(out, live, atol=TOL[dtype.name], rtol=0)
        np.testing.assert_allclose(out, dense, atol=TOL[dtype.name], rtol=0)
    # handed the engine's rows, the kernel visits the batch's alone: the
    # same numbers, bit for bit
    wide = kernel(q, k, v, tables, pos, layer, window, rows=B + 5)
    assert wide.shape == out.shape and bool((wide == out).all())


@pytest.mark.parametrize("side", ["outside", "inside"])
def test_the_key_at_the_windows_edge_is_seen_from_one_side_only(side):
    """One key given a score that swamps every other: at `t - WINDOW` (the
    oldest block still holds it) the row must not see it; one token nearer
    it must, and the output is that key's value."""
    Hkv = 2
    rng = np.random.default_rng(5)
    k, v, tables = pools(rng, 1, Hkv, RING, jnp.float32)
    t = 3 * RING * BS + 7                             # three laps round
    j = t - WINDOW + (side == "inside")
    blk = int(tables[0, (j // BS) % RING])
    q = jnp.asarray(rng.normal(size=(1, Hkv, DH)), jnp.float32)
    k = k.at[1, blk, :, j % BS].set(50.0 * q[0])
    pos = jnp.asarray([t], i32)
    out = kernel(q, k, v, tables, pos, 1, WINDOW)
    dense = walk.reference(q, k, v, tables, pos, 1, WINDOW)
    np.testing.assert_allclose(out, dense, atol=2e-5, rtol=0)
    near = np.abs(np.asarray(out[0]) - np.asarray(v[1, blk, :, j % BS])).max()
    # the swamping key's value, to within what the other keys still weigh
    assert (near < 0.2) == (side == "inside"), near


@pytest.mark.parametrize("window", [0, WINDOW])
def test_a_row_reads_its_own_live_blocks_and_no_other(window):
    """Every block a row's position does not reach (the columns behind its
    live ones, every other row's blocks' neighbours, the null block of the
    real rows) holds NaN: one read of one of them, even at weight 0, and
    the output is NaN. `_attend_live` reads them (a chunk of every row as
    far as the longest), which is what the kernel saves."""
    Hkv, G = 2, 3
    rng = np.random.default_rng(9)
    positions = [0, BS - 1, BS, 3 * BS + 5, 70]
    W = RING if window else W_FULL
    B = len(positions)
    k, v, tables = pools(rng, B, Hkv, W, jnp.float32)
    pos = jnp.asarray(positions, i32)
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, DH)), jnp.float32)
    dense = walk.reference(q, k, v, tables, pos, 2, window)
    live = np.zeros(k.shape[1], bool)
    for b, p in enumerate(positions):
        live[np.asarray(tables[b, :min(p // BS + 1, W)])] = True
    poison = jnp.where(jnp.asarray(live)[None, :, None, None, None], 0.0,
                       jnp.nan)
    out = kernel(q, k + poison, v + poison, tables, pos, 2, window)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(out, dense, atol=2e-5, rtol=0)
    assert not bool(jnp.isfinite(kv_cache._attend_live(
        q, k + poison, v + poison, 2, tables, pos, window)).all())


# -- (c) the engine ----------------------------------------------------------

KINDS = {
    "hidden_size": 48, "num_attention_heads": 6, "num_key_value_heads": 2,
    "head_dim": 8, "intermediate_size": 96, "moe_intermediate_size": 24,
    "num_shared_experts": 1, "num_experts": 2, "num_experts_published": 8,
    "expert_parallel": 4, "expert_rank": 2, "num_experts_per_tok": 4,
    "route_scale": 2.448, "num_hidden_layers": 3, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "full_attention",
                    "sliding_attention"],
    "sliding_window": 8, "vocab_size": 96, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "mup_enabled": True, "dtype": "float32"}


def family(name):
    if name == "dense":
        cfg = TransformerConfig(vocab=48, d_model=32, n_heads=4, n_layers=2,
                                d_ff=64, max_len=64)
        return init_transformer_params(jax.random.PRNGKey(0), cfg), cfg
    weights = kinds_family.make_weights(KINDS, 11)
    return (kinds_family.program_params(weights),
            kinds_family.program_config(KINDS, 64))


def prompt(start, n, vocab=48):
    return [(start + 5 * t) % vocab for t in range(n)]


#: a full batch and a queue behind it; a 20-token prompt and its 16 tokens
#: go twice round the two-block ring of the window layers
REQUESTS = [(prompt(1, 9), 5), (prompt(2, 20), 16), (prompt(3, 5), 9),
            (prompt(4, 12), 12), (prompt(5, 7), 3), (prompt(6, 10), 8)]


def serve_all(model):
    telemetry.tracing.clear()
    srv = serving.serve(model, max_batch=4, block_size=8)
    try:
        handles = [srv.submit(p, max_new_tokens=n) for p, n in REQUESTS]
        tokens = [list(h.result(timeout=300)) for h in handles]
        snap = srv.snapshot()
        walks = {s["attrs"].get("walk") for s in telemetry.spans()
                 if s["name"] == "serving.decode" and "batch" in s["attrs"]}
        return tokens, snap, walks, srv.engine
    finally:
        srv.close()


@pytest.mark.parametrize("name", ["dense", "kinds"])
def test_both_families_are_served_through_the_kernel_with_a_step_in_flight(
        name, monkeypatch):
    model = family(name)
    want, snap, walks, eng = serve_all(model)
    # the CPU's own answer is the XLA loop, and the engine says why
    assert "the backend is cpu" in eng.walk_fallback and walks == {"xla"}
    assert snap["throughput"]["decode_steps_walk_kernel"] == 0
    assert "the backend is cpu" in snap["engine"]["walk_fallback"]

    monkeypatch.setattr(walk, "walk_fallback_reason", lambda *a, **kw: None)
    got, snap, walks, eng = serve_all(model)
    assert eng.walk_fallback is None and walks == {"kernel"}
    # the kernel changes nothing of where the engine's arrays lie: a
    # program that returned them committed would meet a second signature
    # at its next step, and that is a second lowering of every program
    assert eng.device is None and not eng.cache.k.committed
    assert eng.decode_compilations <= 3                  # buckets 1, 2, 4
    assert "walk_fallback" not in snap["engine"]
    steps = snap["throughput"]
    assert steps["decode_steps_walk_kernel"] == steps["decode_steps"] > 0
    assert steps["decode_steps_ahead"] > 0.5 * steps["decode_steps"]
    # float32 on both sides, summed in another order: a token differs
    # only across a tie of 1e-6, which these weights do not have
    assert got == want


# -- (d) the gate ------------------------------------------------------------

def test_the_gates_reasons():
    bf16, f32 = jnp.bfloat16, jnp.float32
    reason = walk.walk_fallback_reason
    assert reason(128, 16, bf16, "tpu") is None
    assert reason(128, 8, f32, "tpu") is None
    assert reason(256, 32, jnp.int8, "tpu") is None
    assert "the backend is cpu" in reason(128, 16, bf16, "cpu")
    assert "the backend is cpu" in reason(128, 16, bf16)     # this host
    assert "head_dim 64" in reason(64, 16, bf16, "tpu")
    assert "block_size 8" in reason(128, 8, bf16, "tpu")
    assert "block_size 16" in reason(128, 16, jnp.int8, "tpu")
    # the engine and the view ask ONE function of the plane they share
    plane = jax.ShapeDtypeStruct((2, 9, 8, 16, 128), bf16)
    assert "the backend is cpu" in kv_cache.walk_unfit(plane)
    latent = kv_cache.CacheSpec(2, bf16, latent_dim=576)
    assert "latent rows" in kv_cache.walk_unfit(
        jax.ShapeDtypeStruct((2, 9, 16, 640), bf16), latent.layout)


def test_a_paged_engine_has_no_gather_walk_to_fall_back_from():
    params, cfg = family("dense")
    eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=2,
                         block_size=8, paged=True)
    try:
        assert eng.paged and eng.walk_fallback is None
    finally:
        eng.close()


def test_a_chunk_is_sized_by_bytes_in_whole_blocks():
    bf16 = jnp.bfloat16
    # the cells' shapes: 8 cached heads take four times the tokens of 32
    assert walk.chunk_blocks(32, 16, 128, bf16, 128) * 4 \
        == walk.chunk_blocks(8, 16, 128, bf16, 608)
    assert walk.chunk_blocks(8, 16, 128, bf16, 3) == 3       # the table's
    assert walk.chunk_blocks(64, 64, 256, jnp.float32, 128) == 1
    assert walk.chunk_blocks(2, 16, 8, bf16, 8, chunk_bytes=1024) == 2


# -- (e) lowered once a step program -------------------------------------------

def custom_calls(lowered):
    return len(re.findall(r"tpu_custom_call", lowered.as_text()))


@pytest.fixture
def compiled_kernel(monkeypatch):
    """The gate open and the interpreter off on this host: what a TPU
    process traces, lowered for the TPU and never run."""
    monkeypatch.setattr(walk, "walk_fallback_reason", lambda *a, **kw: None)
    monkeypatch.setattr(kv_cache, "default_interpret", lambda: False)


@pytest.mark.parametrize("n_layers", [2, 6])
def test_an_opt_shaped_step_holds_one_custom_call(n_layers, compiled_kernel):
    cfg = TransformerConfig(vocab=64, d_model=256, n_heads=2,
                            n_layers=n_layers, d_ff=64, max_len=256)
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(
        lambda: init_transformer_params(jax.random.PRNGKey(0), cfg))
    model = serving.TransformerLM(params, cfg)
    model.bind(16)
    pool = sds((n_layers, 33, 2, 16, 128), jnp.float32)
    walk._lowered_once.cache_clear()
    for B in (1, 2, 4):                    # the buckets of a batch of 4
        lowered = model.programs["decode"]._jitted.trace(
            params, pool, pool, sds((4,), i32), sds((B,), i32),
            sds((B,), i32), sds((B, 16), i32)).lower(
                lowering_platforms=("tpu",))
        assert custom_calls(lowered) == 1
        assert "stablehlo.while" not in lowered.as_text()   # no XLA walk
        # a step that called a `jax.export`ed kernel would return its
        # pools committed to their device, and an unplaced engine's next
        # step would be a second signature of every program (PERF.md §6,
        # PR 33): the spliced module leaves them as they came
        assert lowered._lowering.compile_args["committed"] is False
    # three programs, eighteen layers at the most: one kernel
    assert walk._lowered_once.cache_info().misses == 1


@pytest.mark.parametrize("n_layers", [2, 6])
def test_a_trinity_shaped_step_holds_one_custom_call_a_kind(
        n_layers, compiled_kernel):
    kinds = ("window", "window", "full")
    cfg = afmoe.AfmoeConfig(
        vocab=64, d_model=64, n_heads=4, n_kv_heads=2, head_dim=128,
        n_layers=n_layers, n_dense_layers=n_layers,
        layer_kinds=tuple(kinds[i % 3] for i in range(1, n_layers + 1)),
        window=64, d_ff=64, d_expert=32, n_experts=4, experts_held=(0, 4),
        max_len=256, dtype=jnp.float32)
    sds = jax.ShapeDtypeStruct
    mats, gains = afmoe.param_shapes(cfg)
    params = {n: sds(s, jnp.float32) for n, s in {**mats, **gains}.items()}
    model = afmoe_lm.AfmoeLM(params, cfg)
    model.bind(16)
    spec = model.cache_spec()
    ring = spec.ring("window", 16)
    planes = [sds((len(spec.layers_of(kind)), 65, 2, 16, 128), jnp.float32)
              for kind in spec.kinds for _ in range(2)]
    walk._lowered_once.cache_clear()
    for B in (2, 4):
        lowered = model._decode_jit._jitted.trace(
            params, *planes, sds((4,), i32), sds((B,), i32), sds((B,), i32),
            sds((B, 16 + ring), i32)).lower(lowering_platforms=("tpu",))
        assert custom_calls(lowered) == 2
    assert spec.kinds == ("full", "window")
    assert walk._lowered_once.cache_info().misses == 2      # one a kind


def test_a_head_sharded_step_holds_the_kernel_over_its_local_heads(
        compiled_kernel):
    """The gather step of a tensor-parallel engine runs under `shard_map`
    (serving/tp.py): each chip's program splices in the same kernel,
    lowered over that chip's share of the heads."""
    cfg = TransformerConfig(vocab=64, d_model=512, n_heads=4, n_layers=2,
                            d_ff=64, max_len=256)
    sds = jax.ShapeDtypeStruct
    model = serving.TransformerLM(
        init_transformer_params(jax.random.PRNGKey(0), cfg), cfg)
    model.bind(16, mesh=jax.sharding.Mesh(np.array(jax.devices()[:2]),
                                          ("tp",)))
    pool = sds((2, 33, 4, 16, 128), jnp.float32)
    walk._lowered_once.cache_clear()
    lowered = model.programs["decode"]._jitted.trace(
        model.step_params, pool, pool, sds((4,), i32), sds((2,), i32),
        sds((2,), i32), sds((2, 16), i32)).lower(lowering_platforms=("tpu",))
    assert custom_calls(lowered) == 1
    assert "stablehlo.while" not in lowered.as_text()
    # two of the four heads a chip: the kernel's queries are (the engine's
    # rows, local cached heads, the group, head_dim)
    assert "tensor<4x2x1x128xf32>" in lowered.as_text()
    assert walk._lowered_once.cache_info().misses == 1
