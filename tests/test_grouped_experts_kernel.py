"""The held experts' tiles as one grouped-product kernel a layer (ISSUE
43): ops/pallas_grouped_experts.py under `latent_moe.grouped_experts`.

Load-bearing claims: (a) the layer function through the kernel, in
interpreter mode, gives what it gives through the plain loop, for experts
of two matrices (a squared ReLU) and of three (a SwiGLU): exactly in
float32 where the kernel multiplies as the loop does, to the order of the
sums where it multiplies by the up matrix's transpose or adds blocks of
the expert width, and within bf16's rounding in bf16 (the kernel rounds
the hidden rows once, the loop twice: against a float32 reference it is
no further off); with an expert no pair chose, every pair on one expert
(several tiles of one block), pairs not held here, no pair held here at
all, rows that are not a multiple of the tile and an expert width that is
not a multiple of 128; the counts come back unchanged; (b) a tile no pair
lies in is never written and never read: poisoned buffers leave the sum
finite; (c) the tile's rows and the blocks follow the shapes at the three
cells' widths; (d) the gate gives each of its reasons; (e) an engine of
each of the three families says which of the two its programs hold
(`moe_fallback`, `moe` on both spans, the two counters) and serves the
same tokens either way; (f) the programs of one shape lower the kernel
once, whatever the number of expert layers.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving, telemetry
from mxnet_tpu.models import afmoe, latent_moe, nemotron_h
from mxnet_tpu.ops import pallas_grouped_experts as ge

i32 = jnp.int32


def layer(N, k, held, D, F, n_mats, dtype, seed=0, local=None):
    """Rows, choices, weights and one expert layer's stacked matrices."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    h = jax.random.normal(keys[0], (N, D), dtype)
    if local is None:
        # `held` and `held + 1` are experts of other chips
        local = jnp.minimum(jax.random.randint(keys[1], (N, k), 0, held + 2),
                            held)
    w = jax.random.uniform(keys[2], (N, k), jnp.float32)
    gate, up = ((0.1 * jax.random.normal(key, (held, D, F))).astype(dtype)
                for key in keys[3:5])
    down = (0.1 * jax.random.normal(keys[5], (held, F, D))).astype(dtype)
    return h, jnp.asarray(local, i32), w, gate if n_mats == 3 else None, \
        up, down


def both_ways(monkeypatch, operands, tile=None, block_bytes=None):
    """`grouped_experts` through the loop and through the kernel."""
    loop = jax.jit(lambda *a: latent_moe.grouped_experts(*a))(*operands)
    monkeypatch.setattr(ge, "experts_unfit", lambda *a, **kw: None)
    if tile:
        monkeypatch.setattr(ge, "tile_rows", lambda *a, **kw: tile)
    if block_bytes:
        monkeypatch.setattr(ge, "BLOCK_BYTES", block_bytes)
    kernel = jax.jit(lambda *a: latent_moe.grouped_experts(*a))(*operands)
    return loop, kernel


# -- (a) parity ------------------------------------------------------------

@pytest.mark.parametrize("n_mats", [2, 3])
def test_in_float32_the_kernel_is_the_loop_exactly(n_mats, monkeypatch):
    operands = layer(40, 3, 4, 128, 256, n_mats, jnp.float32)
    (want, counts), (got, counts_k) = both_ways(monkeypatch, operands,
                                                tile=8)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(counts_k), np.asarray(counts))
    assert int(counts.sum()) == int((operands[1] < 4).sum())


@pytest.mark.parametrize("n_mats", [2, 3])
def test_in_bf16_the_kernel_is_no_further_from_float32_than_the_loop(
        n_mats, monkeypatch):
    operands = layer(48, 4, 4, 256, 256, n_mats, jnp.bfloat16)
    (loop, _), (kernel, _) = both_ways(monkeypatch, operands)
    exact = latent_moe.grouped_experts(
        *(None if a is None else a.astype(jnp.float32) for a in operands[:1]),
        operands[1], operands[2],
        *(None if a is None else a.astype(jnp.float32)
          for a in operands[3:]))[0]
    scale = float(jnp.abs(exact).max())
    off_loop = float(jnp.abs(loop - exact).max()) / scale
    off_kernel = float(jnp.abs(kernel - exact).max()) / scale
    # bf16 keeps eight bits: a hidden row rounded once, an output once
    assert off_kernel < 2 ** -6
    assert off_kernel <= 1.25 * off_loop + 1e-3


#: name -> (rows, choices, held, model width, expert width, tile, how the
#: pairs choose)
CASES = {
    "an_expert_no_pair_chose": (24, 2, 4, 128, 128, 8, "skip_2"),
    "every_pair_on_one_expert": (40, 3, 4, 128, 128, 8, "all_on_1"),
    "pairs_not_held_here": (32, 4, 4, 128, 128, 8, "half_away"),
    "no_pair_held_here": (16, 2, 4, 128, 128, 8, "all_away"),
    "rows_not_a_multiple_of_the_tile": (13, 3, 4, 128, 128, 8, "random"),
    "a_width_that_is_not_whole_lanes": (24, 2, 4, 128, 96, 8, "random"),
    "blocks_of_the_expert_width_summed": (24, 2, 4, 128, 512, 8, "random"),
}


def choices(how, N, k, held, seed=3):
    rng = np.random.default_rng(seed)
    local = rng.integers(0, held, size=(N, k))
    if how == "skip_2":
        local[local == 2] = 3
    elif how == "all_on_1":
        local[:] = 1
    elif how == "half_away":
        local[rng.random((N, k)) < 0.5] = held
    elif how == "all_away":
        local[:] = held
    return local


@pytest.mark.parametrize("n_mats", [2, 3])
@pytest.mark.parametrize("case", CASES)
def test_the_kernel_is_the_loop_whatever_the_pairs_chose(case, n_mats,
                                                         monkeypatch):
    N, k, held, D, F, tile, how = CASES[case]
    operands = layer(N, k, held, D, F, n_mats, jnp.float32,
                     local=choices(how, N, k, held))
    # 128 columns a step where the width is 512: four blocks summed
    (want, counts), (got, counts_k) = both_ways(
        monkeypatch, operands, tile=tile,
        block_bytes=n_mats * D * 128 * 4 if F == 512 else None)
    assert np.array_equal(np.asarray(counts_k), np.asarray(counts))
    if how == "skip_2":
        assert int(counts[2]) == 0
    if how == "all_on_1":
        assert int(counts[1]) == N * k > 3 * tile
    if how == "all_away":
        assert int(counts.sum()) == 0 and not np.asarray(want).any()
    # the transpose's product and the blocks' sum differ from the loop's
    # in the order of float32 sums alone
    exact = F % 128 == 0 and F <= 128
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0 if exact else 2e-5,
                               atol=0 if exact else 2e-5)


# -- (b) what no pair lies in ------------------------------------------------

def test_a_tile_no_pair_lies_in_is_neither_written_nor_read(monkeypatch):
    """The kernel's result past the real tiles (the first is multiplied
    whatever the pairs chose) is whatever the buffer held: NaN here. The
    layer's sum is finite all the same, and with no pair at all it is
    zero."""
    def poisoned(x, tile_expert, n_tiles, *weights, tile, **kw):
        out = kernel(x, tile_expert, n_tiles, *weights, tile=tile, **kw)
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < jnp.maximum(n_tiles, 1) * tile, out,
                         jnp.nan)

    kernel = ge.grouped_experts
    monkeypatch.setattr(ge, "grouped_experts", poisoned)
    for how in ("half_away", "all_away"):
        operands = layer(16, 2, 4, 128, 128, 2, jnp.float32,
                         local=choices(how, 16, 2, 4))
        (want, _), (got, _) = both_ways(monkeypatch, operands, tile=8)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)


# -- (c) tiles and blocks from the shapes ----------------------------------------

@pytest.mark.parametrize("cell,pairs,held,tile", [
    ("nemotron_decode", 128 * 6, 64, 16), ("nemotron_one_row", 6, 64, 16),
    ("nemotron_prefill_1024", 1024 * 6, 64, 128),
    ("dsv3_decode", 32 * 8, 16, 16), ("dsv3_prefill_1024", 1024 * 8, 16, 128),
    ("trinity_decode", 32 * 4, 16, 16),
    ("trinity_prefill_8192", 8192 * 4, 16, 128)])
def test_a_tiles_rows_follow_the_rows_an_expert_can_get(cell, pairs, held,
                                                        tile):
    assert ge.tile_rows(pairs, held) == tile
    assert ge.tile_rows(pairs, held, jnp.float32) in (tile, 8)


@pytest.mark.parametrize("cell,D,F,n_mats", [
    ("nemotron", 2688, 1856, 2), ("dsv3", 7168, 2048, 3),
    ("trinity", 3072, 3072, 3)])
def test_the_blocks_are_whole_tiles_that_divide_the_width_and_fit(
        cell, D, F, n_mats):
    block = ge.block_width(D, F, n_mats, 2)
    assert F % block == 0
    assert block % (16 if F % 128 else 128) == 0
    # two of a step's blocks, the tile's rows there and back and the
    # float32 sum lie under what the kernel asks of VMEM
    held = 2 * n_mats * D * block * 2 + 128 * D * (2 * 2 * 2 + 4)
    assert held < ge.VMEM_LIMIT
    # the gate lets through the experts that are one block
    reason = ge.experts_unfit(D, F, n_mats, jnp.bfloat16, "tpu")
    assert (reason is None) == (block == F) == (cell == "nemotron")


# -- (d) the gate -----------------------------------------------------------------

@pytest.mark.parametrize("why,args", [
    ("the backend is cpu", (256, 128, 2, jnp.bfloat16, "cpu")),
    ("the experts are float32", (256, 128, 2, jnp.float32, "tpu")),
    ("the model width 200", (200, 128, 2, jnp.bfloat16, "tpu")),
    ("are 84 MiB, more than the 24 MiB block",
     (7168, 2048, 3, jnp.bfloat16, "tpu")),
    ("are 54 MiB, more than the 24 MiB block",
     (3072, 3072, 3, jnp.bfloat16, "tpu")),
])
def test_the_gate_gives_each_of_its_reasons(why, args):
    assert why in ge.experts_unfit(*args)


def test_rows_and_experts_of_two_dtypes_keep_the_loop():
    sds = jax.ShapeDtypeStruct
    reason = latent_moe.experts_unfit(
        sds((8, 128), jnp.float32), None, sds((4, 128, 128), jnp.bfloat16))
    assert "differ in dtype" in reason
    assert "the backend is cpu" in latent_moe.experts_unfit(
        sds((8, 128), jnp.bfloat16), None, sds((4, 128, 128), jnp.bfloat16))


# -- (e) the engine -----------------------------------------------------------------

def family(name):
    """A tiny model of each of the three families that share the layer."""
    key = jax.random.PRNGKey(7)
    if name == "latent":
        cfg = latent_moe.LatentMoEConfig(max_len=64)
        return latent_moe.init_latent_moe_params(key, cfg), cfg
    if name == "kinds":
        cfg = afmoe.AfmoeConfig(max_len=64)
        return afmoe.init_afmoe_params(key, cfg), cfg
    cfg = nemotron_h.NemotronHConfig(max_len=64)
    return nemotron_h.init_nemotron_h_params(key, cfg), cfg


def prompt(start, n, vocab=256):
    return [(start + 5 * t) % vocab for t in range(n)]


REQUESTS = [(prompt(1, 9), 5), (prompt(2, 20), 6), (prompt(3, 5), 4)]


def serve_all(model):
    telemetry.tracing.clear()
    srv = serving.serve(model, max_batch=4, block_size=8)
    try:
        handles = [srv.submit(p, max_new_tokens=n) for p, n in REQUESTS]
        tokens = [list(h.result(timeout=300)) for h in handles]
        moe = {name: [s["attrs"].get("moe") for s in telemetry.spans()
                      if s["name"] == name]
               for name in ("serving.prefill", "serving.decode")}
        return tokens, srv.snapshot(), moe, srv
    finally:
        srv.close()


@pytest.mark.parametrize("name", ["latent", "kinds", "pattern"])
def test_an_engine_of_each_family_says_what_walks_its_experts_tiles(
        name, monkeypatch):
    model = family(name)
    want, snap, moe, srv = serve_all(model)
    assert "the backend is cpu" in srv.engine.moe_fallback
    assert snap["engine"]["moe_fallback"] == srv.engine.moe_fallback
    assert moe["serving.prefill"] == ["xla"] * len(REQUESTS)
    assert moe["serving.decode"] and set(moe["serving.decode"]) == {"xla"}
    assert snap["throughput"]["decode_steps_moe_kernel"] == 0
    assert snap["throughput"]["prefills_moe_kernel"] == 0

    monkeypatch.setattr(ge, "experts_unfit", lambda *a, **kw: None)
    got, snap, moe, srv = serve_all(model)
    assert srv.engine.moe_fallback is None and srv.engine.moe == "kernel"
    assert "moe_fallback" not in snap["engine"]
    assert moe["serving.prefill"] == ["kernel"] * len(REQUESTS)
    assert set(moe["serving.decode"]) == {"kernel"}
    steps = snap["throughput"]["decode_steps"]
    assert snap["throughput"]["decode_steps_moe_kernel"] == steps > 0
    assert snap["throughput"]["prefills_moe_kernel"] == len(REQUESTS)
    text = srv.prometheus_text()
    assert re.search(r"^serving_decode_steps_moe_kernel_total\S* %d$" % steps,
                     text, re.M)
    assert re.search(r"^serving_prefills_moe_kernel_total\S* %d$"
                     % len(REQUESTS), text, re.M)
    # float32 on both sides: a token differs only across a tie of 1e-6
    assert got == want


def test_a_family_with_no_expert_layer_says_nothing_of_them():
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params)
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=1,
                            d_ff=64, max_len=32)
    srv = serving.serve((init_transformer_params(jax.random.PRNGKey(0), cfg),
                         cfg), max_batch=2, block_size=8)
    try:
        telemetry.tracing.clear()
        srv.submit(prompt(1, 5, 64), max_new_tokens=3).result(timeout=300)
        assert srv.engine.moe is None and srv.engine.moe_fallback is None
        assert not [s for s in telemetry.spans() if "moe" in s["attrs"]
                    and s["name"] in ("serving.prefill", "serving.decode")]
        assert srv.snapshot()["throughput"]["decode_steps_moe_kernel"] == 0
    finally:
        srv.close()


# -- (f) lowered once a shape ---------------------------------------------------------

def test_the_expert_layers_of_a_program_lower_the_kernel_once(monkeypatch):
    """Three expert layers of one decode program and of another bucket's:
    one `tpu_custom_call` in the program's module and a call site of it a
    layer, the kernel traced and lowered ONCE: the buffer's tiles are rounded up to the
    held experts, so the steps of every bucket share the shape."""
    from mxnet_tpu.serving import nemotron_h_lm
    gate = ge.experts_unfit
    monkeypatch.setattr(ge, "experts_unfit",
                        lambda *a, **kw: gate(*a[:4], backend="tpu"))
    monkeypatch.setattr(latent_moe, "default_interpret", lambda: False)
    cfg = nemotron_h.NemotronHConfig(
        vocab=64, d_model=128, pattern="EEE", d_expert=96, d_shared=128,
        n_experts=8, top_k=2, experts_held=(0, 4), max_len=64,
        dtype=jnp.bfloat16)
    sds = jax.ShapeDtypeStruct
    mats, gains, vectors = nemotron_h.param_shapes(cfg)
    params = {n: sds(s, jnp.bfloat16) for n, s in {**mats, **gains}.items()}
    params.update({n: sds(s, jnp.float32) for n, s in vectors.items()})
    model = nemotron_h_lm.NemotronHLM(params, cfg)
    assert model.moe_unfit() is None
    model.bind(16)
    ge._lowered_once.cache_clear()
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    try:
        for rows in (4, 32, 4):
            lowered = model._decode_jit._jitted.trace(
                params, sds((32,), i32), sds((rows,), i32), sds((rows,), i32),
                sds((rows, 1), i32)).lower(lowering_platforms=("tpu",))
            text = lowered.as_text()
            # one function, a call site a layer; no loop of passes
            assert len(re.findall(r"tpu_custom_call", text)) == 1
            assert len(re.findall(r"call @_experts", text)) == 3
            assert "stablehlo.while" not in text
    finally:
        jax.config.update("jax_default_matmul_precision", was)
    assert ge._lowered_once.cache_info().misses == 1
