"""The fifth served family (ISSUE 42): layers that are each ONE mixer by a
pattern's letter (a Mamba-2 mixer, a routed squared-ReLU expert layer, a
grouped-query attention with no positions), a cache whose kinds differ layer
by layer.

Load-bearing claims: (a) the dense forward and each kind of layer agree with
the plain float32 reference (chipbench/reference/nemotron_h_lm.py) on seeded
weights; (b) prefill then decode THROUGH the paged cache, ragged rows that
join, end and change place, give the reference's full-forward logits, and a
state kept in bf16 where float32 is stated fails the same tolerance; (c) the
two EP2 shares of an expert layer, the shared expert counted once, add up to
the uncut reference layer; (d) the recurrence kernel under the interpreter
agrees with `state_update` at heads of 64 (a group's heads side by side on
the lanes) AND at Falcon-H1's sizes (a head the lanes wide), one function;
(e) `CacheSpec` with state-only, K/V-only and cache-less layers lays planes,
pools and table columns over each kind's own layers; (f) `param_shapes` at
the published config is the model's 31,577,940,288 parameters; (g) every
option the family cannot take falls back with its reason.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving, telemetry
from mxnet_tpu.models import falcon_h1, latent_moe, nemotron_h
from mxnet_tpu.ops import pallas_ssm_step
from mxnet_tpu.serving import kv_cache

from chipbench.families import nemotron_h_lm as family
from chipbench.harness import manifest
from chipbench.reference import nemotron_h_lm as reference

#: every letter of the pattern, twice a state layer and twice an expert layer,
#: heads of 16 in groups of 8 (128 lanes side by side: the narrow-head layout)
TOY = {
    "hidden_size": 32, "hybrid_override_pattern": "MEM*E",
    "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "mamba_num_heads": 16,
    "mamba_head_dim": 16, "ssm_state_size": 16, "n_groups": 2,
    "conv_kernel": 4, "chunk_size": 8, "moe_intermediate_size": 16,
    "moe_shared_expert_intermediate_size": 32, "n_routed_experts": 4,
    "n_routed_experts_published": 8, "expert_parallel": 2, "expert_rank": 0,
    "num_experts_per_tok": 2, "n_group": 1, "topk_group": 1,
    "routed_scaling_factor": 2.5, "vocab_size": 96,
    "layer_norm_epsilon": 1e-5, "dtype": "float32", "state_dtype": "float32"}
BS, MAX_LEN = 8, 64
#: float32 through another order of sums (the chunked scan against the
#: recurrence a position at a time, the tile loop against every expert over
#: every token): what the served logits may differ from the reference's by,
#: as a share of the largest logit. A state kept in bf16 misses it by twenty
#: times (`test_a_state_kept_in_bf16...`)
REL = 2e-5


@pytest.fixture(scope="module")
def model():
    weights = family.make_weights(TOY, 7)
    # 32 wide, N(0, 0.02) matrices leave every product near nothing and the
    # state with them: eight times as wide a draw, and losing it shows
    wider = lambda lw: {n: a * 8 if a.ndim >= 2 and n != "conv_w" else a
                        for n, a in lw.items()}
    weights = dict(wider({n: a for n, a in weights.items() if n != "layers"}),
                   layers=[wider(lw) for lw in weights["layers"]])
    return (weights, family.program_params(weights),
            family.program_config(TOY, MAX_LEN))


def prompt(start, n):
    return [(start + 5 * t) % TOY["vocab_size"] for t in range(n)]


def ref_logits(weights, tokens, config=TOY, **kw):
    """The reference's logits for the first len(tokens) positions."""
    padded = np.zeros((reference.pad_len(len(tokens)),), np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(reference.logits(weights, config, padded, **kw))[
        :len(tokens)]


def engine(params, cfg, **kw):
    kw.setdefault("max_batch", 4)
    return serving.Engine(serving.NemotronHLM(params, cfg), max_len=MAX_LEN,
                          block_size=BS, keep_logits=True, **kw)


def gap(seq, weights, **kw):
    """Largest distance of a served sequence's logits (one row an emitted
    token) from the reference's, over the reference's largest logit."""
    want = ref_logits(weights, seq.tokens[:-1], **kw)[seq.prompt_len - 1:]
    got = np.stack(seq.token_logits)
    assert got.shape == want.shape
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_the_dense_forward_and_each_kind_of_layer_agree_with_the_reference(
        model):
    weights, params, cfg = model
    assert cfg.pattern == "MEM*E" and cfg.experts_held == (0, 4)
    toks = np.asarray(prompt(3, 128), np.int32)
    counts = []
    want = np.asarray(reference.logits(weights, TOY, toks, counts=counts))
    got, rows = nemotron_h.nemotron_h_apply(params, jnp.asarray(toks), cfg)
    assert np.abs(np.asarray(got) - want).max() <= REL * np.abs(want).max()
    # the pairs a held expert got, an expert layer: the reference counts the same
    assert np.array_equal(np.asarray(rows),
                          np.stack([np.asarray(c).sum(0) for c in counts]))
    assert int(np.asarray(rows).sum()) > 0
    # one layer of each letter alone, over rows that are not an embedding's
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 32))
    real = jnp.ones((128,), bool)
    for i, (letter, lw) in enumerate(zip(cfg.pattern, weights["layers"])):
        want = np.asarray(reference.layer(x, lw, letter, TOY, None))
        got, _ = nemotron_h.block(params, i, x, real, cfg,
                                  falcon_h1.DenseView())
        assert np.abs(np.asarray(got) - want).max() \
            <= 1e-5 * np.abs(want).max(), letter
    # attention applies no positions: the same keys in another order of the
    # EARLIER positions give the last position the same output
    lw, h = weights["layers"][3], x[:16]
    last = np.asarray(reference.attention(jnp.pad(h, ((0, 112), (0, 0))), lw,
                                          TOY, None))[15]
    turned = jnp.concatenate([h[:15][::-1], h[15:]])
    again = np.asarray(reference.attention(
        jnp.pad(turned, ((0, 112), (0, 0))), lw, TOY, None))[15]
    assert np.abs(last - again).max() <= 1e-5 * np.abs(last).max()


def test_prefill_then_decode_through_the_cache_over_a_ragged_batch(model):
    """Rows join, end and change place; prompts of 1, 2 and 3 tokens sit at
    the convolution's edge. Every emitted token's LOGITS against the
    reference's full forward."""
    weights, params, cfg = model
    eng = engine(params, cfg)
    spec = eng.cache.spec
    assert spec.kinds == ("full", "state")
    assert spec.layer_kinds == ("state", "none", "state", "full", "none")
    assert [a.shape for a in eng.cache.arrays()] == [
        (1, 4 * 8 + 1, 2, 8, 8)] * 2 + [(2, 5, 2, 16, 128), (2, 5, 3 * 320)]
    assert eng.paged_fallback is None and eng.sync_reason == "keep_logits"
    running = [eng.start(prompt(1, 1), 20), eng.start(prompt(2, 2), 6),
               eng.start(prompt(3, 3), 12), eng.start(prompt(4, 19), 9)]
    waiting = [(prompt(5, 11), 8), (prompt(6, 30), 10), (prompt(7, 2), 5)]
    finished = []
    for _ in range(40):
        if not running:
            break
        eng.decode_step(running)
        for s in [s for s in running if s.done]:
            running.remove(s)       # the rows after it move up a place
            finished.append(s)
            eng.release(s)
            if waiting:
                p, n = waiting.pop(0)
                running.insert(0, eng.start(p, n))      # and all move down
    assert len(finished) == 7 and not running
    for s in finished:
        assert gap(s, weights) < REL, (s.prompt_len, len(s.tokens))
    assert [p.in_use for p in eng.cache.pools] == [0, 0]
    # the expert tally: prefill and decode rows, padded rows routed nowhere
    rows = eng.model.expert_rows
    assert rows.shape == (2, 4) and rows.sum() > 0
    tokens = sum(len(s.tokens) - 1 for s in finished)
    assert rows.sum() <= 2 * 2 * tokens             # top-2, two expert layers
    eng.close()


def test_a_state_kept_in_bf16_where_float32_is_stated_fails_and_a_lost_one(
        model):
    weights, params, cfg = model
    import dataclasses
    low = engine(params, dataclasses.replace(cfg, state_dtype=jnp.bfloat16),
                 max_batch=1)
    assert low.cache.ssm_state.dtype == jnp.bfloat16
    seq = low.start(prompt(4, 40), 12)
    while not seq.done:
        low.decode_step([seq])
    print("bf16 state", gap(seq, weights))
    assert gap(seq, weights) > 10 * REL
    low.release(seq)
    low.close()
    eng = engine(params, cfg, max_batch=1)
    first = eng.start(prompt(1, 13), 10)
    slot = first.blocks[1]
    while not first.done:
        eng.decode_step([first])
    eng.release(first)
    # the slot given again carries nothing of the last sequence
    second = eng.start(prompt(9, 3), 10)
    assert second.blocks[1] == slot and int(second.table_row[-1]) == slot[0]
    while not second.done:
        eng.decode_step([second])
    assert gap(first, weights) < REL and gap(second, weights) < REL
    eng.release(second)
    # lose the state after the prefill, or leave the mixers out of the
    # reference: the comparison fails by orders
    third = eng.start(prompt(4, 40), 10)
    eng.cache.ssm_state = jnp.zeros_like(eng.cache.ssm_state)
    while not third.done:
        eng.decode_step([third])
    print("no state", gap(third, weights), "no mixer",
          gap(first, weights, zero_state=True))
    assert gap(third, weights) > 5 * REL
    assert gap(first, weights, zero_state=True) > 100 * REL
    eng.release(third)
    eng.close()


def test_the_two_shares_of_an_expert_layer_add_up_to_the_uncut_layer(model):
    """EP2: rank 0 holds experts 0-3, rank 1 experts 4-7, both route over all
    eight; the shared expert is on both chips and is counted ONCE."""
    weights, params, cfg = model
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    d, f = TOY["hidden_size"], TOY["moe_intermediate_size"]
    lw = dict(weights["layers"][1])
    lw["we_up"] = 0.2 * jax.random.normal(keys[0], (8, d, f))
    lw["we_down"] = 0.2 * jax.random.normal(keys[1], (8, f, d))
    x = jax.random.normal(keys[2], (37, d))
    whole = dict(TOY, n_routed_experts=8, expert_parallel=1)
    want = np.asarray(reference.moe(x, lw, whole, None))
    total, rows = 0.0, []
    for rank in (0, 1):
        part_cfg = family.program_config(dict(TOY, expert_rank=rank), MAX_LEN)
        lo, hi = part_cfg.experts_held
        part = {"layer1_" + n: (a[lo:hi] if n.startswith("we_") else a)
                for n, a in lw.items()}
        out, counts = latent_moe.moe_ffn(part, "layer1_", x,
                                         jnp.ones((37,), bool), part_cfg)
        shared = latent_moe.expert_ffn(x, None, lw["ws_up"], lw["ws_down"])
        total = total + np.asarray(out - shared)
        rows.append(np.asarray(counts))
        # and the program's share is the reference's share
        ref_part = dict(lw, we_up=lw["we_up"][lo:hi],
                        we_down=lw["we_down"][lo:hi])
        ref_share = np.asarray(reference.moe(
            x, ref_part, dict(TOY, expert_rank=rank), None))
        assert np.abs(np.asarray(out) - ref_share).max() \
            <= 1e-5 * np.abs(ref_share).max()
    total = total + np.asarray(latent_moe.expert_ffn(x, None, lw["ws_up"],
                                                     lw["ws_down"]))
    assert np.abs(total - want).max() <= 1e-5 * np.abs(want).max()
    assert sum(r.sum() for r in rows) == 37 * 2         # every pair, once
    # the experts' form is their weights': three matrices are still a SwiGLU
    gate = 0.2 * jax.random.normal(keys[0], (d, f))
    assert np.allclose(
        np.asarray(latent_moe.expert_ffn(x, gate, lw["we_up"][0],
                                         lw["we_down"][0])),
        np.asarray(latent_moe.swiglu(x, gate, lw["we_up"][0],
                                     lw["we_down"][0])))


@pytest.mark.parametrize("name,layout,hpg,P", [
    ("heads of 64 side by side", (8, 32, 512), 8, 64),
    ("falcon-h1's: a head the lanes wide", (2, 16, 8, 128), 8, 128)])
def test_the_kernel_updates_the_states_where_they_lie_in_both_layouts(
        name, layout, hpg, P):
    """The interpreter runs the kernel the chip compiles: against
    `state_update` on states gathered by hand, slots out of order and the
    null slot twice (padded rows); the rest of the plane is not touched."""
    G, N = layout[:2]
    k = jax.random.split(jax.random.PRNGKey(5), 6)
    plane = jax.random.normal(k[0], (3, 6) + layout)
    slots = jnp.asarray([4, 0, 2, 0], jnp.int32)
    decay = jnp.exp(-jax.random.uniform(k[1], (4, G, hpg, 1)))
    dtx = jax.random.normal(k[2], (4, G, hpg, P))
    Bm, Cm = (jax.random.normal(k[i], (4, G, N)) for i in (3, 4))
    want_h, want_y = falcon_h1.state_update(plane[1, slots], decay, dtx, Bm,
                                            Cm)
    # `state_update` itself, against the recurrence's step a head
    h0 = np.asarray(plane[1, 4]).reshape(G, N, hpg, P)
    by_hand = np.asarray(decay[0])[:, None] * h0 + np.asarray(Bm[0])[
        :, :, None, None] * np.asarray(dtx[0])[:, None]
    assert np.abs(np.asarray(want_h[0]).reshape(G, N, hpg, P)
                  - by_hand).max() < 1e-5
    assert np.abs(np.asarray(want_y[0]) - np.einsum(
        "gn,gnhp->ghp", np.asarray(Cm[0]), by_hand)).max() < 1e-4
    new, y = pallas_ssm_step.ssm_step(plane, jnp.int32(1), slots, decay, dtx,
                                      Bm, Cm, interpret=True)
    assert y.shape == (4, G, hpg, P)
    assert np.abs(np.asarray(y - want_y)).max() < 1e-4
    for row in (0, 2):
        assert np.abs(np.asarray(new[1, slots[row]] - want_h[row])).max() < 1e-5
    keep = np.asarray([1, 3, 5])
    assert np.array_equal(np.asarray(new[1, keep]), np.asarray(plane[1, keep]))
    assert np.array_equal(np.asarray(new[0]), np.asarray(plane[0]))
    assert np.array_equal(np.asarray(new[2]), np.asarray(plane[2]))
    # the gate takes the layout from the plane
    assert "backend is cpu" in pallas_ssm_step.step_fallback_reason(plane)
    assert pallas_ssm_step.step_fallback_reason(plane, "tpu") is None
    assert "whole (8, 128) tiles" in pallas_ssm_step.step_fallback_reason(
        plane[..., :64], "tpu")


def test_served_through_the_kernel_the_logits_are_the_same(model, monkeypatch):
    """The gate opened and the interpreter in the chip's place: the decode
    step updates the states of heads side by side by the kernel."""
    weights, params, cfg = model
    monkeypatch.setattr(pallas_ssm_step, "step_fallback_reason",
                        lambda *a, **k: None)
    eng = engine(params, cfg, max_batch=2)
    assert eng.state_step_fallback is None
    assert eng.cache.ssm_state.shape == (2, 3, 2, 16, 128)
    seqs = [eng.start(prompt(1, 2), 7), eng.start(prompt(2, 17), 7)]
    while not all(s.done for s in seqs):
        eng.decode_step([s for s in seqs if not s.done])
    assert all(gap(s, weights) < REL for s in seqs)
    for s in seqs:
        eng.release(s)
    eng.close()


def test_the_layout_is_the_head_widths_and_the_kernel_lowers_at_the_cells():
    real = manifest.read_json(manifest.cell(
        manifest.load(), "nemotron3_reason_closed").find(
        "configs", "nemotron-3-nano-30b-a3b.json"))
    cfg = family.program_config(real, 3072)
    assert falcon_h1.state_layout(cfg) == (8, 128, 512)
    assert falcon_h1.state_layout(falcon_h1.FalconH1Config()) == (2, 16, 2, 8)
    assert falcon_h1.state_layout(falcon_h1.FalconH1Config(
        ssm_heads=32, ssm_head_dim=128, ssm_state=256)) == (2, 256, 16, 128)
    assert pallas_ssm_step.groups_a_block(8, 128, 512) == 8     # a row: 2 MB
    assert pallas_ssm_step.groups_a_block(2, 256, 2048) == 1
    assert pallas_ssm_step.step_bytes(128, 8, 128, 8, 64) \
        == family.ssm_step_bytes(real, 128) \
        == 4 * 128 * 8 * (2 * 128 * 512 + 2 * 128 + 3 * 512)
    # the Python stage of the Mosaic lowering, with no chip: block shapes at
    # 128 rows of 8 x 128 x 512, six state layers
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    lowered = jax.jit(
        lambda *a: pallas_ssm_step._step_rows_lanes(*a, interpret=False)).trace(
        sds((6, 129, 8, 128, 512), f32), sds((1,), i32), sds((128,), i32),
        sds((128, 8, 8, 64), f32), sds((128, 8, 8, 64), f32),
        sds((128, 8, 128), f32), sds((128, 8, 128), f32)).lower(
        lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert "tpu_custom_call" in text and "ssm_step" in text


def test_the_cache_spec_with_state_only_kv_only_and_cacheless_layers():
    spec = kv_cache.CacheSpec(
        6, "float32", n_heads=2, head_dim=8, n_q_heads=8,
        layer_kinds=("state", "none", "full", "state", "none", "full+state"),
        state_shape=(2, 16, 128), conv_shape=(3, 64), state_dtype="float32")
    assert spec.kinds == ("full", "state")
    assert spec.layers_of("full") == (2, 5)
    assert spec.layers_of("state") == (0, 3, 5)
    assert spec.layers_of("none") == (1, 4)
    assert [spec.attn_kind(i) for i in range(6)] == [
        None, None, "full", None, None, "full"]
    # a token's values over the TWO layers that keep keys and values
    assert spec.values_per_token() == 2 * 2 * 2 * 8
    assert spec.state_bytes() == 3 * (2 * 16 * 128 * 4 + 3 * 64 * 4)
    assert spec.q_group == 4 and spec.ring("state", 8) == 1
    assert "recurrent state" in spec.paged_unfit()
    cache = kv_cache.PagedKVCache.of(spec, block_size=8, num_blocks=(9, 3))
    # planes over each kind's own layers
    assert [a.shape for a in cache.arrays()] == [
        (2, 9, 2, 8, 8)] * 2 + [(3, 3, 2, 16, 128), (3, 3, 192)]
    assert cache.rings == (0, 1) and cache.table_width(6) == 7
    assert cache.blocks_by_kind(40) == (5, 1)
    # columns: layer 5's keys are the full kind's second layer, its state the
    # state kind's third; a state-only layer is asked no columns of keys
    pools, tables = cache.arrays(), jnp.zeros((2, 7), jnp.int32)
    i, j, tab, window, ring = kv_cache._place(spec, 5, pools, tables)
    assert (i, j, tab.shape, window, ring) == (0, 1, (2, 6), 0, 0)
    assert kv_cache._place_state(spec, 5) == (2, 2)
    assert kv_cache._place_state(spec, 3) == (2, 1)
    # no kinds named: one kind over every layer, as it was
    plain = kv_cache.CacheSpec(3, "float32", n_heads=2, head_dim=8)
    assert plain.values_per_token() == 3 * 2 * 2 * 8
    assert [plain.attn_kind(i) for i in range(3)] == ["full"] * 3
    # a state alone says so in its refusal; beside keys and values as it did
    alone = kv_cache.CacheSpec(
        2, "float32", n_heads=2, head_dim=8, layer_kinds=("state", "full"),
        state_shape=(2, 16, 128), conv_shape=(3, 64), state_dtype="float32")
    assert "recurrent state alone" in alone.paged_unfit()


def test_param_shapes_at_the_published_config_and_at_the_cut():
    real = manifest.cell(manifest.load(), "nemotron3_reason_closed").config
    assert real["hybrid_override_pattern"] == "MEMEM*EMEMEM*"
    count = lambda cfg: sum(
        int(np.prod(s)) for shapes in nemotron_h.param_shapes(cfg)
        for s in shapes.values())
    whole = dict(real, **real["published"], n_routed_experts_published=128,
                 expert_parallel=1)
    cfg = family.program_config(whole, 3072)
    assert len(cfg.pattern) == 52 and (
        cfg.pattern.count("M"), cfg.pattern.count("E"),
        cfg.pattern.count("*")) == (23, 23, 6)
    assert count(cfg) == 31_577_940_288
    cut = family.program_config(real, 3072)
    assert cut.experts_held == (0, 64) and cut.n_moe_layers == 5
    assert count(cut) == family.param_count(real) == 3_926_018_560
    assert round(family.weight_bytes(real) / 1e9, 3) == 7.852


def test_each_option_the_family_cannot_take_falls_back_with_its_reason(model):
    _, params, cfg = model
    eng = serving.LMServer((params, cfg), max_batch=2, max_len=MAX_LEN,
                           paged=True, kv_quant=True,
                           prefix_cache=True).engine
    assert isinstance(eng.model, serving.NemotronHLM)
    assert not eng.paged and "recurrent state alone" in eng.paged_fallback
    assert "chunk to chunk" in eng.paged_fallback \
        and "roll it back" in eng.paged_fallback
    assert not eng.kv_quant and "needs the paged path" in eng.kv_quant_fallback
    assert eng.prefix_cache is None \
        and "chunked-prefill paged path" in eng.prefix_cache_fallback
    assert eng.prefill_chunk == 0 and eng.sync_reason is None
    assert "backend is cpu" in eng.state_step_fallback
    assert "backend is cpu" in eng.walk_fallback
    eng.close()
    eng = serving.LMServer((params, cfg), max_batch=2, max_len=MAX_LEN,
                           tp=2).engine
    assert eng.tp == 1 and "paged path off/ineligible" in eng.tp_fallback
    eng.close()
    eng = serving.LMServer((params, cfg), max_batch=2, max_len=MAX_LEN,
                           spec=True).engine
    assert not eng.spec and eng.spec_fallback
    eng.close()
    # on the chip the state step's gate opens at this family's sizes
    plane = jax.ShapeDtypeStruct((6, 129, 8, 128, 512), jnp.float32)
    assert pallas_ssm_step.step_fallback_reason(plane, "tpu") is None


def test_serve_takes_the_family_and_publishes_its_kinds_and_counts(model):
    weights, params, cfg = model
    telemetry.tracing.clear()
    srv = serving.serve((params, cfg), max_batch=4, max_len=MAX_LEN,
                        block_size=BS)
    try:
        assert isinstance(srv.engine.model, serving.NemotronHLM)
        handles = [srv.submit(prompt(n, 4 + 3 * n), max_new_tokens=9)
                   for n in range(1, 5)]
        out = [list(h.result(timeout=120)) for h in handles]
        assert all(len(o) == 9 for o in out)
        # greedy tokens are the reference's where its logits are not tied
        toks = prompt(2, 10) + out[1]
        logits = ref_logits(weights, toks[:-1])[9:]
        assert list(np.argmax(logits, -1)) == out[1]
        snap = srv.snapshot()
        assert snap["engine"]["cache_layers"] == {"full": [3],
                                                  "state": [0, 2]}
        assert snap["engine"]["state_dtype"] == "float32"
        assert "backend is cpu" in snap["engine"]["state_step_fallback"]
        assert snap["cache"]["state"]["blocks_high_water"] == 4
        assert re.search(r"serving_state_blocks_total\{[^}]*\} 4",
                         srv.prometheus_text())
        spans = telemetry.spans()
        steps = [s["attrs"] for s in spans
                 if s["name"] == "serving.decode" and "batch" in s["attrs"]]
        assert steps and all(a["state_rows"] == a["batch"] for a in steps)
        assert all(a["live_full"] >= a["live_max"] and a["walk"] == "xla"
                   for a in steps)
        assert any(a.get("moe_pairs", 0) > 0
                   and 0 < a["moe_experts_touched"] <= 8 for a in steps)
        prefills = [s["attrs"] for s in spans if s["name"] == "serving.prefill"]
        assert prefills and all(
            {"length", "bucket", "attn", "moe_pairs"} <= set(a)
            for a in prefills)
        assert snap["throughput"]["decode_steps_ahead"] > 0
    finally:
        srv.close()
