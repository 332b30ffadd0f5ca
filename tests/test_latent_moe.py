"""The latent-attention, dropless sparse-expert family (ISSUE 27), small
widths, seeded float32 weights, against the plain reference of
`chipbench/reference/latent_moe_lm.py`.

(a) prefill then decode through the latent pool agree with the reference's
full forward, logits; (b) the shares add up: the ranks' routed parts plus the
shared expert once equal the uncut layer; (c) absorbed decode equals expanded
attention on the same cache; (d) group-limited selection against a brute
force, ties included; (e) a real row's logits are bit-identical whatever the
padded rows hold; (f) the rows per expert a step returns are the reference's
routing; (g) each option the family cannot take falls back with its reason;
(h) the pool is aliased and no step holds a copy of the pool's shape.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import latent_moe as lm
from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params)
from mxnet_tpu.serving import kv_cache, latent_lm

from chipbench.families import latent_moe_lm as family
from chipbench.reference import latent_moe_lm as reference

CONFIG = {
    "hidden_size": 48, "num_attention_heads": 4, "q_lora_rank": 20,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "intermediate_size": 96, "moe_intermediate_size": 24,
    "n_shared_experts": 1, "n_routed_experts": 4,
    "n_routed_experts_published": 16, "expert_parallel": 4, "expert_rank": 2,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 2.5, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "vocab_size": 96, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "dtype": "float32"}
BS, MAX_LEN = 8, 64
# float32 both sides at matmul precision "highest": what is left is the order
# of the sums (absorbed against expanded attention, grouped against dense
# experts); logits are of order 0.1
TOL = 2e-5


@pytest.fixture(scope="module")
def model():
    weights = family.make_weights(CONFIG, 11)
    cfg = family.program_config(CONFIG, MAX_LEN)
    return weights, family.program_params(weights), cfg


def prompt(start, n):
    return [(start + 7 * t) % CONFIG["vocab_size"] for t in range(n)]


# -- (a) ---------------------------------------------------------------------

def test_prefill_then_decode_through_the_pool_agree_with_the_reference(model):
    weights, params, cfg = model
    eng = serving.Engine(serving.LatentMoELM(params, cfg), max_batch=4,
                         max_len=MAX_LEN, block_size=BS, num_blocks=40,
                         keep_logits=True)
    assert eng.cache.layout == "latent" and len(eng.cache.arrays()) == 1
    assert eng.cache.kv.shape == (3, 40, BS, 128)      # 24 wide, whole lanes
    assert eng.kv_bytes_per_token() == 3 * 128 * 4
    # 5, 13 and 21 tokens: inside a block, across one boundary, across two;
    # three sequences decode in a batch of four, one row padded
    seqs = [eng.start(prompt(3 + i, n), 14) for i, n in enumerate((5, 13, 21))]
    for _ in range(12):
        eng.decode_step(seqs)
    for s in seqs:
        ref = np.asarray(reference.logits(weights, CONFIG, s.tokens))
        got = np.stack(s.token_logits)
        want = ref[s.prompt_len - 1:s.prompt_len - 1 + len(got)]
        assert np.abs(got - want).max() < TOL
        assert [int(t) for t in want.argmax(-1)] == s.tokens[s.prompt_len:]
        eng.release(s)
    eng.close()


def test_serve_takes_the_family_through_the_same_door(model):
    weights, params, cfg = model
    telemetry.tracing.clear()
    srv = serving.serve((params, cfg), max_batch=4, num_blocks=40,
                        max_len=MAX_LEN, block_size=BS)
    try:
        assert isinstance(srv.engine.model, serving.LatentMoELM)
        handles = [srv.submit(prompt(i, 9 + 4 * i), max_new_tokens=6)
                   for i in range(3)]
        for h in handles:
            assert h.wait(120) and h.error is None
            ref = np.asarray(reference.logits(weights, CONFIG, h.tokens))
            n = len(h.tokens) - 6
            assert [int(t) for t in ref[n - 1:-1].argmax(-1)] == h.tokens[n:]
        steps = [s for s in telemetry.spans() if "moe_pairs" in s["attrs"]]
        assert {s["name"] for s in steps} == {"serving.prefill",
                                              "serving.decode"}
        rows = srv.engine.model.expert_rows
        assert sum(s["attrs"]["moe_pairs"] for s in steps) == rows.sum() > 0
        # a span carries one step's counts; the pass that collects the last
        # step beside the one before it carries both, added up
        assert all(0 <= s["attrs"]["moe_experts_touched"] <= rows.size
                   for s in steps[:-1])
        assert 0 <= steps[-1]["attrs"]["moe_experts_touched"] <= 2 * rows.size
        snap = srv.metrics.snapshot(srv.engine, srv.scheduler)
        text = srv.metrics.prometheus_text(srv.engine, srv.scheduler)
        assert "serving_moe_expert_tokens_layer1_expert3" in text
        assert snap is not None
    finally:
        srv.close()


# -- (b) ---------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    whole = dict(CONFIG, n_routed_experts=16, expert_parallel=1, expert_rank=0)
    weights = family.make_weights(whole, 5)
    lw = weights["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(2), (37, 48), jnp.float32)
    uncut = reference.moe(x, lw, whole, None)
    shared = lm.swiglu(x, lw["ws_gate"], lw["ws_up"], lw["ws_down"])
    total, rows = shared, 0
    for rank in range(4):
        cfg = family.program_config(dict(CONFIG, expert_rank=rank), MAX_LEN)
        lo, hi = cfg.experts_held
        assert (lo, hi) == (4 * rank, 4 * rank + 4)
        part = {"layer1_" + n: (a[lo:hi] if n.startswith("we_") else a)
                for n, a in lw.items()}
        out, counts = lm.moe_ffn(part, "layer1_", x, jnp.ones((37,), bool), cfg)
        total = total + (out - shared)          # the rank's routed part alone
        rows += int(counts.sum())
    assert rows == 37 * 4                       # every pair on exactly one rank
    assert float(jnp.abs(total - uncut).max()) < 1e-6


# -- (c) ---------------------------------------------------------------------

def test_absorbed_decode_equals_expanded_attention_on_the_same_cache(model):
    _, params, cfg = model
    S, H = 19, cfg.n_heads
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q_nope = jax.random.normal(ks[0], (S, H, cfg.nope_dim))
    q_rope = jax.random.normal(ks[1], (S, H, cfg.rope_dim))
    latent = jax.random.normal(ks[2], (S, cfg.latent_dim))
    wk_b, wv_b = params["layer0_wk_b"], params["layer0_wv_b"]
    expanded = lm.expanded_attention(q_nope, q_rope, latent, wk_b, wv_b, cfg)
    nblk = -(-S // BS)
    cached = jnp.pad(latent, ((0, nblk * BS - S), (0, 128 - cfg.latent_dim))
                     ).reshape(1, nblk, BS, 128)
    for pos in (0, 7, 8, 18):       # first, a block's last and first, last
        live = (jnp.arange(nblk * BS) <= pos)[None]
        absorbed = lm.absorbed_attention(q_nope[pos][None], q_rope[pos][None],
                                         cached, live, wk_b, wv_b, cfg)
        assert float(jnp.abs(absorbed[0] - expanded[pos]).max()) < 1e-5


# -- (d) ---------------------------------------------------------------------

def brute_force_route(s, bias, n_group, topk_group, top_k, scale,
                      select_with_bias=True, weigh_with_bias=False):
    """Numpy, a token at a time; ties go to the lower index."""
    first = lambda x, k: sorted(range(len(x)), key=lambda i: (-x[i], i))[:k]
    idx, w = [], []
    for row in s:
        sel = row + bias if select_with_bias else row
        per = len(row) // n_group
        group = [sum(sorted(sel[g * per:(g + 1) * per], reverse=True)[:2])
                 for g in range(n_group)]
        kept = set(first(group, topk_group))
        masked = [sel[e] if e // per in kept else -np.inf
                  for e in range(len(row))]
        win = first(masked, top_k)
        src = sel if weigh_with_bias else row
        idx.append(win)
        w.append([src[e] / (sum(src[e] for e in win) + 1e-20) * scale
                  for e in win])
    return np.asarray(idx), np.asarray(w)


def test_group_limited_selection_against_a_brute_force_ties_included(model):
    _, _, cfg = model
    rng = np.random.default_rng(3)
    N, E = 64, cfg.n_experts
    # scores from five values only, so that experts and groups tie; the
    # tokens are one-hot rows, which makes the router's rows the logits
    logits = rng.choice([-1.0, -0.5, 0.0, 0.5, 1.0], size=(N, E))
    bias = rng.choice([-0.25, 0.0, 0.25], size=(E,)).astype(np.float32)
    idx, w = lm.route(jnp.eye(N, dtype=jnp.float32),
                      jnp.asarray(logits, jnp.float32), jnp.asarray(bias), cfg)
    s = np.asarray(jax.nn.sigmoid(jnp.asarray(logits, jnp.float32)))
    args = (s, bias, cfg.n_groups, cfg.top_groups, cfg.top_k, cfg.route_scale)
    want_idx, want_w = brute_force_route(*args)
    assert np.array_equal(np.asarray(idx), want_idx)
    assert np.allclose(np.asarray(w), want_w, rtol=1e-6)
    assert np.allclose(np.asarray(w).sum(-1), cfg.route_scale, rtol=1e-6)
    # the reference's own routing is the same function of the same scores
    ref_idx, ref_w = reference.route(
        jnp.eye(N, dtype=jnp.float32), jnp.asarray(logits, jnp.float32),
        jnp.asarray(bias), n_group=cfg.n_groups, topk_group=cfg.top_groups,
        top_k=cfg.top_k, scale=cfg.route_scale)
    assert np.array_equal(np.asarray(ref_idx), want_idx)
    assert np.allclose(np.asarray(ref_w), want_w, rtol=1e-6)
    # and the comparison is one a wrong router fails: the bias dropped from
    # the selection, or taken into the weights
    no_bias, _ = brute_force_route(*args, select_with_bias=False)
    assert not np.array_equal(no_bias, want_idx)
    _, biased_w = brute_force_route(*args, weigh_with_bias=True)
    assert not np.allclose(biased_w, want_w, rtol=1e-3)


# -- (e) ---------------------------------------------------------------------

def test_a_real_rows_logits_do_not_depend_on_the_padded_rows(model):
    _, params, cfg = model
    pool = jnp.zeros((cfg.n_layers, 12, BS, 128), jnp.float32)
    prefill = jax.jit(lambda kv, t, n, tb: latent_lm.prefill(params, kv, t, n,
                                                           tb, cfg))
    decode = jax.jit(lambda kv, t, p, tb: latent_lm.decode(
        params, kv, jnp.zeros_like(t), t, p, tb, cfg))
    table = jnp.asarray([1, 2, 0, 0, 0, 0, 0, 0], jnp.int32)
    toks = np.zeros((16,), np.int32)
    toks[:11] = prompt(2, 11)
    outs = []
    for filler in (0, 57):                    # what the padded positions hold
        padded = toks.copy()
        padded[11:] = filler
        pool_a, logits, counts = prefill(pool, jnp.asarray(padded), 11, table)
        outs.append((np.asarray(logits), np.asarray(counts)))
    assert np.array_equal(outs[0][0], outs[1][0])
    assert np.array_equal(outs[0][1], outs[1][1])       # and they count nothing
    tables = jnp.zeros((4, 8), jnp.int32).at[0].set(table)
    steps = []
    for tok, pos in ((0, 0), (41, 5)):        # what the three padded rows hold
        t = jnp.asarray([int(outs[0][0].argmax()), tok, tok, tok], jnp.int32)
        p = jnp.asarray([11, pos, pos, pos], jnp.int32)
        _, logits, nxt, counts = decode(pool_a + 0, t, p, tables)
        steps.append((np.asarray(logits[0]), int(nxt[0]), np.asarray(counts)))
    assert np.array_equal(steps[0][0], steps[1][0]) and steps[0][1] == steps[1][1]
    assert np.array_equal(steps[0][2], steps[1][2])
    assert steps[0][2].sum() <= cfg.top_k * cfg.n_moe_layers    # one real row


# -- (f) ---------------------------------------------------------------------

def test_the_rows_per_expert_returned_are_the_references_routing(model):
    weights, params, cfg = model
    toks = jnp.asarray(prompt(9, 40), jnp.int32)
    per_token = []
    ref = reference.logits(weights, CONFIG, toks, counts=per_token)
    logits, counts = lm.latent_moe_apply(params, toks, cfg, length=33)
    # positions past `length` are padding: routed nowhere, so not compared
    assert float(jnp.abs(logits[:33] - ref[:33]).max()) < TOL
    want = np.stack([np.asarray(c)[:33].sum(0) for c in per_token])
    assert np.array_equal(np.asarray(counts), want) and want.sum() > 0


# -- (g) ---------------------------------------------------------------------

def test_options_the_family_cannot_take_fall_back_with_their_reason(model):
    _, params, cfg = model
    make = lambda **kw: serving.Engine(
        serving.LatentMoELM(params, cfg), max_batch=2, max_len=MAX_LEN,
        block_size=BS, num_blocks=20, **kw)
    eng = make(paged=True, kv_quant=True, prefix_cache=True)
    assert not eng.paged and "latent rows, not keys and values" in eng.paged_fallback
    assert not eng.kv_quant and "needs the paged path" in eng.kv_quant_fallback
    assert eng.prefix_cache is None \
        and "chunked-prefill paged path" in eng.prefix_cache_fallback
    eng.close()
    eng = make(tp=2)
    assert eng.tp == 1 and "paged path off/ineligible" in eng.tp_fallback
    assert "latent rows" in eng.paged_fallback      # tp asks for the paged path
    eng.close()
    dcfg = TransformerConfig(vocab=cfg.vocab, d_model=16, n_heads=2, n_layers=1,
                             d_ff=32, max_len=MAX_LEN)
    draft = (init_transformer_params(jax.random.PRNGKey(0), dcfg), dcfg)
    eng = make(draft=draft)
    assert not eng.spec and "paged attention off/ineligible" in eng.spec_fallback
    eng.close()
    eng = make(spec=True)
    assert not eng.spec and "no draft model" in eng.spec_fallback
    eng.close()
    eng = make(weight_quant="int8")
    assert eng.weight_quant is None \
        and "no weight hooks" in eng.weight_quant_fallback
    # every fallback serves the default gather path all the same
    seq = eng.start(prompt(1, 9), 4)
    eng.decode_step([seq])
    assert len(seq.tokens) == 11
    eng.release(seq)
    eng.close()


def test_the_old_family_still_refuses_capacity_routing_and_says_who_serves_it():
    cfg = TransformerConfig(vocab=32, d_model=16, n_heads=2, n_layers=1,
                            d_ff=32, n_experts=4, moe_top_k=2, max_len=32)
    with pytest.raises(MXNetError, match="dropless family"):
        serving.TransformerLM(init_transformer_params(jax.random.PRNGKey(0),
                                                      cfg), cfg)


# -- (h) ---------------------------------------------------------------------

def pool_copies(hlo_text, shape, dtype="f32"):
    tag = "%s[%s]" % (dtype, ",".join(str(d) for d in shape))
    return [l for l in hlo_text.splitlines()
            if re.search(r"= \S+ copy\(", l) and tag in l.split(" copy(")[0]]


@pytest.mark.parametrize("attr", ["_prefill_jit", "_decode_jit"])
def test_step_program_aliases_and_consumes_its_pool(model, attr):
    _, params, cfg = model
    adapter = serving.LatentMoELM(params, cfg)
    adapter.bind(BS)
    assert sorted(a for a in vars(adapter) if a.endswith("_jit")) \
        == ["_decode_jit", "_prefill_jit"]
    pool = jnp.zeros((cfg.n_layers, 12, BS, 128), jnp.float32)
    i32 = jnp.int32
    rest = {"_prefill_jit": (jnp.zeros((16,), i32), i32(5),
                             jnp.arange(1, 9, dtype=i32)),
            "_decode_jit": (jnp.zeros((4,), i32), jnp.zeros((2,), i32),
                            jnp.asarray([3, 9], i32),
                            jnp.asarray([[1, 2] + [0] * 6, [3, 4] + [0] * 6],
                                        i32))}[attr]
    jit = getattr(adapter, attr)
    compiled = jit.lower(params, pool, *rest).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= pool.nbytes
    assert not pool_copies(compiled.as_text(), pool.shape)
    out = jit(params, pool, *rest)
    assert pool.is_deleted() and out[0].shape == pool.shape
    assert not any(a.is_deleted() for a in params.values())


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


def test_the_cells_pool_compiles_for_the_chip_without_a_copy_of_itself(one_chip):
    """At the benchmark cell's pool size, for the v5e's compiler: the pool's
    default layout there keeps a block's rows together and neither the
    append nor the prompt write nor the gather moves the pool. With rows 576
    wide (not whole 128-lane tiles) the chip's default layout puts the block
    axis innermost and every step copies the pool twice: `CacheSpec.
    row_width` is what this test guards."""
    spec = kv_cache.CacheSpec(6, jnp.bfloat16, latent_dim=576)
    assert spec.row_width == 640
    shape = (6, 2817, 16, spec.row_width)
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)

    def step(pool, slots, new, tables, prompt_rows):
        for layer in range(shape[0]):
            pool = kv_cache.append_latent(pool, layer, slots, new)
            pool = kv_cache.write_latent_prompt(pool, layer, tables[1],
                                                prompt_rows)
            new = new + kv_cache.gather_latent(pool, layer, tables).sum(
                (1, 2))[:, :576].astype(new.dtype)
        return pool, new

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        sds(shape, jnp.bfloat16), sds((32,), jnp.int32),
        sds((32, 576), jnp.bfloat16), sds((32, 88), jnp.int32),
        sds((1024, 576), jnp.bfloat16)).compile()
    assert compiled.input_formats[0][0].layout.major_to_minor == (0, 1, 2, 3)
    assert not pool_copies(compiled.as_text(), shape, "bf16")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2 * 6 * 2817 * 16 * 640 * 2
