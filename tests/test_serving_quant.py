"""Quantized serving tests (ISSUE 20): int8 KV blocks dequantized
in-VMEM, int8 per-channel weights, and the int8 dp-grad collective —
every mode pinned against the f32 oracle.

Load-bearing claims:
* flags off is byte-for-byte the unquantized stack — the f32 pool, the
  plain matmuls, a metrics exposition with no quant names;
* the int8 paged kernel equals the f32 kernel run over the explicitly
  dequantized pool (the in-VMEM dequant is placement, not math), across
  dtypes and table widths;
* quantized engines emit the SAME greedy tokens as the f32 oracle on
  the tiny config, with pinned max-logit-error and perplexity-delta
  budgets — and every ineligible config records a fallback reason and
  serves f32;
* scale hygiene: COW copies move scales with data, reclaimed blocks
  re-quantize from zero (no stale-scale precision leak), shared prefix
  blocks keep their scales;
* `kv_bytes_per_token` prices the QUANTIZED layout (int8 payload +
  amortized f32 sidecars), so disagg bytes-saved stays truthful;
* the training leg: `MXNET_QUANTIZED_COLLECTIVES=int8` moves the dp
  grad all-reduce to s8 payload (comms ledger ~4x smaller than the f32
  ideal) with an error-feedback residual, inside a loss-curve
  tolerance.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import serving
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params)
from mxnet_tpu.ops.pallas_paged import (paged_attention, paged_call_cost,
                                        paged_fallback_reason)
from mxnet_tpu.serving.kv_cache import (PagedKVCache, write_kv_quant,
                                        copy_block,
                                        zero_block_scales)


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


def _prompt(n=20, vocab=48, seed=0):
    return list(np.random.RandomState(seed).randint(1, vocab, size=n))


def _rollout(tiny_lm, prompt, max_new=8, **kw):
    """Greedy rollout; returns (engine, tokens, per-token f32 logits)."""
    params, cfg = tiny_lm
    eng = serving.Engine(serving.TransformerLM(dict(params), cfg),
                         max_batch=2, block_size=16, keep_logits=True,
                         **kw)
    seq = eng.start(list(prompt), max_new)
    while not seq.done:
        eng.decode_step([seq])
    toks = list(seq.tokens)
    logits = [np.asarray(x, np.float32) for x in seq.token_logits]
    eng.release(seq)
    return eng, toks, logits


def _max_err(a, b):
    return max(float(np.max(np.abs(x - y))) for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# kernel: int8 pool + in-VMEM dequant == f32 kernel on the dequantized pool
# ---------------------------------------------------------------------------


def _quantize_pool(pool):
    """Per-block-per-head symmetric int8 of an (NB, H, bs, Dh) pool."""
    a = np.max(np.abs(np.asarray(pool, np.float32)), axis=(2, 3))
    s = np.maximum(a, 1e-12) / 127.0                       # (NB, H)
    q = np.clip(np.rint(np.asarray(pool, np.float32)
                        / s[:, :, None, None]), -127, 127).astype(np.int8)
    return jnp.asarray(q), jnp.asarray(s.astype(np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("width", [2, 4])
@pytest.mark.parametrize("tq", [1, 4])
def test_paged_kernel_int8_matches_dequantized_f32(dtype, width, tq):
    """The quant kernel must equal the f32 kernel fed the DEQUANTIZED
    pool: in-VMEM dequant moves bytes, never values."""
    bs, H, Dh, nb = 4, 2, 8, 12
    rng = np.random.RandomState(0)
    k_f = jnp.asarray(rng.randn(nb, H, bs, Dh).astype(np.float32))
    v_f = jnp.asarray(rng.randn(nb, H, bs, Dh).astype(np.float32))
    k_q, k_s = _quantize_pool(k_f)
    v_q, v_s = _quantize_pool(v_f)
    k_deq = k_q.astype(jnp.float32) * k_s[:, :, None, None]
    v_deq = v_q.astype(jnp.float32) * v_s[:, :, None, None]
    B = 3
    q = jnp.asarray(rng.randn(B, tq, H, Dh).astype(np.float32)) \
        .astype(dtype)
    tables = jnp.asarray(rng.choice(np.arange(1, nb), (B, width),
                                    replace=True).astype(np.int32))
    q_start = jnp.asarray([width * bs - tq, bs + 1, 0], jnp.int32)
    out_q = paged_attention(q, k_q, v_q, tables, q_start, bs,
                            interpret=True, k_scale=k_s, v_scale=v_s)
    out_f = paged_attention(q, k_deq.astype(dtype), v_deq.astype(dtype),
                            tables, q_start, bs, interpret=True)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 \
        else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(out_q, np.float32),
                               np.asarray(out_f, np.float32), **tol)


def test_paged_call_cost_declares_int8_bytes():
    """The cost model's int8 bytes: the dominant K/V term shrinks 4x,
    scale sidecars are accounted, and the A/B lands near the ~2x total
    read saving the bench proves."""
    B, Tq, H, Dh, w, bs, nb = 4, 1, 8, 64, 8, 32, 128
    fl_f, by_f = paged_call_cost(B, Tq, H, Dh, w, bs)
    fl_q, by_q = paged_call_cost(B, Tq, H, Dh, w, bs, kv_itemsize=1,
                                 scale_blocks=nb)
    assert fl_f == fl_q                        # same math either way
    nk = B * H * w * bs
    assert by_f - by_q == 2 * nk * Dh * 3 - 2 * nb * H * 4
    assert by_q < 0.5 * by_f, (by_q, by_f)


def test_paged_eligible_int8_tile_gate():
    """Real hardware wants block_size % 32 for the (32, 128) int8 tile;
    interpret mode takes any shape."""
    assert paged_fallback_reason(128, 32, False, jnp.int8) is None
    assert "block_size" in paged_fallback_reason(128, 16, False, jnp.int8)
    assert paged_fallback_reason(128, 16, False, jnp.float32) is None
    assert paged_fallback_reason(32, 8, True, jnp.int8) is None


# ---------------------------------------------------------------------------
# pool: layout, quantizing writes, scale hygiene
# ---------------------------------------------------------------------------


def test_quant_pool_layout_and_write_roundtrip():
    c = PagedKVCache(n_layers=2, num_blocks=6, block_size=4, n_heads=2,
                     head_dim=8, kv_dtype="int8")
    assert c.quantized and c.k.dtype == jnp.int8
    assert c.k_scale.shape == (2, 6, 2) and c.k_scale.dtype == jnp.float32
    rng = np.random.RandomState(1)
    kn = jnp.asarray(rng.randn(4, 2, 8).astype(np.float32))
    vn = jnp.asarray(rng.randn(4, 2, 8).astype(np.float32))
    slots = jnp.asarray([4, 5, 6, 7], jnp.int32)           # block 1 whole
    k, v, ks, vs = write_kv_quant(c.k, c.v, c.k_scale, c.v_scale, 0,
                                  slots, kn, vn)
    s = np.asarray(ks)[0, 1]                               # (H,)
    expect = np.max(np.abs(np.asarray(kn)), axis=(0, 2)) / 127.0
    np.testing.assert_allclose(s, expect, rtol=1e-6)
    # block 1 is (H, bs, Dh); the rows went in as (bs, H, Dh)
    deq = np.asarray(k)[0, 1].astype(np.float32) * s[:, None, None]
    np.testing.assert_allclose(deq.transpose(1, 0, 2), np.asarray(kn),
                               atol=float(np.max(s)) * 0.51)
    # monotonic: a smaller later row must not shrink the block's scale
    k2, v2, ks2, vs2 = write_kv_quant(k, v, ks, vs, 0,
                                      jnp.asarray([4], jnp.int32),
                                      kn[:1] * 0.01, vn[:1] * 0.01)
    assert np.all(np.asarray(ks2)[0, 1] >= s - 1e-9)


def test_cow_copies_scales_and_reclaim_rezeroes():
    """COW moves scales with data; `zero_block_scales` resets a
    reclaimed block so the monotonic max restarts from zero instead of
    inheriting the previous occupant's (possibly huge) scale."""
    c = PagedKVCache(n_layers=1, num_blocks=5, block_size=4, n_heads=2,
                     head_dim=8, kv_dtype="int8")
    big = jnp.asarray(100.0 * np.ones((4, 2, 8), np.float32))
    slots = jnp.asarray([4, 5, 6, 7], jnp.int32)
    k, v, ks, vs = write_kv_quant(c.k, c.v, c.k_scale, c.v_scale, 0,
                                  slots, big, big)
    k, v, ks, vs = copy_block(k, v, ks, vs, 1, 2)
    np.testing.assert_array_equal(np.asarray(k)[0, 2], np.asarray(k)[0, 1])
    np.testing.assert_array_equal(np.asarray(ks)[0, 2],
                                  np.asarray(ks)[0, 1])
    # divergence: rewriting the copy must leave the source untouched
    small = jnp.asarray(0.01 * np.ones((1, 2, 8), np.float32))
    ks, vs = zero_block_scales(ks, vs, jnp.asarray([2], jnp.int32))
    k2, v2, ks2, vs2 = write_kv_quant(k, v, ks, vs, 0,
                                      jnp.asarray([8], jnp.int32),
                                      small, small)
    np.testing.assert_array_equal(np.asarray(ks2)[0, 1],
                                  np.asarray(ks)[0, 1])
    # the reclaimed block quantizes at the SMALL scale, not the stale one
    assert float(np.asarray(ks2)[0, 2, 0]) == pytest.approx(0.01 / 127.0)
    # null-block writes are as harmless as the f32 path's
    k3, v3, ks3, vs3 = write_kv_quant(k2, v2, ks2, vs2, 0,
                                      jnp.asarray([0], jnp.int32),
                                      big[:1], big[:1])
    np.testing.assert_array_equal(np.asarray(k3)[0, 1:],
                                  np.asarray(k2)[0, 1:])


# ---------------------------------------------------------------------------
# engine: oracle parity, budgets, fallbacks, composition
# ---------------------------------------------------------------------------

#: pinned logit-error budgets vs the f32 oracle on the tiny config
#: (measured ~3e-4 kv-only, ~2.5e-3 with int8 weights; budget leaves
#: ~10x headroom without letting a real regression hide)
KV_LOGIT_BUDGET = 0.01
WEIGHT_LOGIT_BUDGET = 0.05


def test_flags_off_is_the_unquantized_stack(tiny_lm):
    eng, toks, _ = _rollout(tiny_lm, _prompt(), paged=True)
    try:
        assert not eng.kv_quant and eng.weight_quant is None
        assert not eng.cache.quantized and eng.cache.k_scale is None
        assert not any(isinstance(w, dict)
                       for w in eng.model.params.values())
        met = serving.metrics.ServingMetrics()
        assert "quant" not in met.prometheus_text(eng, None)
    finally:
        eng.close()


def test_kv_quant_tokens_match_oracle_within_budget(tiny_lm):
    e0, t0, l0 = _rollout(tiny_lm, _prompt(), paged=True)
    e1, t1, l1 = _rollout(tiny_lm, _prompt(), paged=True, kv_quant=True)
    try:
        assert e1.kv_quant and e1.kv_quant_fallback is None
        assert e1.cache.quantized and e1.cache.k.dtype == jnp.int8
        assert t1 == t0
        assert _max_err(l0, l1) < KV_LOGIT_BUDGET
    finally:
        e0.close()
        e1.close()


def test_weight_quant_within_budget_and_idempotent(tiny_lm):
    params, cfg = tiny_lm
    e0, t0, l0 = _rollout(tiny_lm, _prompt(), paged=True)
    e1, t1, l1 = _rollout(tiny_lm, _prompt(), paged=True,
                          weight_quant="int8")
    try:
        assert e1.weight_quant == "int8"
        assert t1 == t0
        assert _max_err(l0, l1) < WEIGHT_LOGIT_BUDGET
        m = e1.model
        assert isinstance(m.params["layer0_wqkv"], dict)
        assert m.params["layer0_wqkv"]["q"].dtype == jnp.int8
        assert m.params["embed"].dtype != jnp.int8     # embeds stay f32
        assert m.params_f32 is not None                # oracle kept
        before = m.params
        m.quantize_weights("int8")                     # idempotent
        assert m.params is before
    finally:
        e0.close()
        e1.close()
    with pytest.raises(MXNetError):
        serving.TransformerLM(dict(params), cfg).quantize_weights("int4")


def test_both_quant_ppl_delta_gate(tiny_lm):
    """Perplexity of the oracle's own emitted continuation, scored by
    each engine's logits: the quantized stack may move it only inside
    the pinned gate."""
    e0, t0, l0 = _rollout(tiny_lm, _prompt(), max_new=12, paged=True)
    e1, t1, l1 = _rollout(tiny_lm, _prompt(), max_new=12, paged=True,
                          kv_quant=True,
                          weight_quant="int8")
    try:
        assert t1 == t0

        def ppl(logits, toks):
            nll = 0.0
            for row, t in zip(logits, toks):
                z = row - np.max(row)
                nll -= float(z[t] - np.log(np.sum(np.exp(z))))
            return math.exp(nll / len(toks))

        p0, p1 = ppl(l0, t0), ppl(l1, t0)
        assert abs(p1 - p0) / p0 < 0.02, (p0, p1)
    finally:
        e0.close()
        e1.close()


def test_env_flags_enable_quant(tiny_lm, monkeypatch):
    monkeypatch.setenv("MXNET_QUANTIZED_KV", "1")
    monkeypatch.setenv("MXNET_QUANTIZED_WEIGHTS", "int8")
    params, cfg = tiny_lm
    eng = serving.Engine(serving.TransformerLM(dict(params), cfg),
                         max_batch=2, block_size=16, paged=True)
    try:
        assert eng.kv_quant_requested and eng.kv_quant
        assert eng.weight_quant == "int8"
    finally:
        eng.close()


def test_gather_path_falls_back_to_f32_pool(tiny_lm):
    """kv_quant against the gather oracle: reason recorded, f32 pool
    serves, tokens identical to the paged oracle."""
    e0, t0, _ = _rollout(tiny_lm, _prompt(), paged=True)
    e1, t1, _ = _rollout(tiny_lm, _prompt(), paged=False, kv_quant=True)
    try:
        assert not e1.kv_quant and e1.kv_quant_requested
        assert "paged" in e1.kv_quant_fallback
        assert not e1.cache.quantized
        assert t1 == t0
    finally:
        e0.close()
        e1.close()


def test_no_cache_family_records_both_fallbacks():
    net = mx.models.RNNModel(mode="lstm", vocab_size=32, num_embed=16,
                             num_hidden=16, num_layers=1)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((4, 1)))
    eng = serving.Engine(
        serving.BlockLM(net, vocab=32, max_len=32, time_major=True),
        max_batch=2, kv_quant=True, weight_quant="int8")
    try:
        assert not eng.kv_quant and eng.kv_quant_fallback is not None
        assert eng.weight_quant is None
        assert eng.weight_quant_fallback is not None
    finally:
        eng.close()


def test_kv_bytes_per_token_prices_quant_layout(tiny_lm):
    """int8 payload + ceil(2*L*H*4 / block_size) sidecar bytes — the
    number the migration bytes-saved ledger multiplies."""
    e0, _, _ = _rollout(tiny_lm, _prompt(), paged=True)
    e1, _, _ = _rollout(tiny_lm, _prompt(), paged=True, kv_quant=True)
    try:
        cs = e0.model.cache_spec()
        nl, nh, dh = cs.n_layers, cs.n_heads, cs.head_dim
        assert e0.kv_bytes_per_token() == 2 * nl * nh * dh * 4
        expect = 2 * nl * nh * dh + math.ceil(2 * nl * nh * 4 / 16.0)
        assert e1.kv_bytes_per_token() == expect
        assert e1.kv_bytes_per_token() * 3 < e0.kv_bytes_per_token()
    finally:
        e0.close()
        e1.close()


def test_prefix_cache_cow_keeps_shared_scales(tiny_lm):
    """A second request rides the shared prefix, COW-copies, and stays
    inside the logit budget; the shared block's scales are untouched."""
    params, cfg = tiny_lm
    prompt = _prompt()
    eng = serving.Engine(serving.TransformerLM(dict(params), cfg),
                         max_batch=2, block_size=16, keep_logits=True,
                         paged=True, kv_quant=True, prefix_cache=True)
    try:
        s1 = eng.start(list(prompt), 8)
        while not s1.done:
            eng.decode_step([s1])
        eng.release(s1)
        shared_scale = np.array(eng.cache.k_scale)
        p2 = prompt[:18] + [7, 9]
        s2 = eng.start(p2, 8)
        assert s2.cache_hit_tokens > 0
        assert eng.prefix_cache.cow_copies >= 1
        while not s2.done:
            eng.decode_step([s2])
        t2, l2 = list(s2.tokens), [np.asarray(x, np.float32)
                                   for x in s2.token_logits]
        # shared (still-cached) blocks kept their scales bit-for-bit
        resident = sorted(e.block_id
                          for e in eng.prefix_cache._by_hash.values())
        assert resident
        np.testing.assert_array_equal(
            np.array(eng.cache.k_scale)[:, resident],
            shared_scale[:, resident])
        eng.release(s2)
    finally:
        eng.close()
    e0, t0, l0 = _rollout(tiny_lm, p2, paged=True)
    e0.close()
    assert t2 == t0
    assert _max_err(l0, l2) < KV_LOGIT_BUDGET


def test_spec_decode_over_quant_pool_token_identical(tiny_lm):
    params, cfg = tiny_lm
    e0, t0, _ = _rollout(tiny_lm, _prompt(), paged=True)
    eng = serving.Engine(serving.TransformerLM(dict(params), cfg),
                         max_batch=2, block_size=16, paged=True, kv_quant=True,
                         draft=(params, cfg), spec=True, spec_k=3)
    try:
        assert eng.spec and eng.spec_fallback is None and eng.kv_quant
        seq = eng.start(_prompt(), 8)
        while not seq.done:
            eng.decode_step([seq])
        assert list(seq.tokens) == t0
        assert eng.spec_accepted_tokens > 0
        eng.release(seq)
    finally:
        eng.close()
    e0.close()


def test_tp_quant_parity_and_scale_sharding(tiny_lm):
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 (emulated) devices")
    from mxnet_tpu.serving.tp import TP_AXIS
    e0, t0, l0 = _rollout(tiny_lm, _prompt(), paged=True)
    e1, t1, l1 = _rollout(tiny_lm, _prompt(), tp=2, paged=True, kv_quant=True,
                          weight_quant="int8")
    try:
        assert e1.tp == 2 and e1.tp_fallback is None
        assert e1.kv_quant and e1.weight_quant == "int8"
        assert t1 == t0
        assert _max_err(l0, l1) < WEIGHT_LOGIT_BUDGET
        spec = e1.cache.k_scale.sharding.spec     # (L, NB, H) on heads
        assert tuple(spec) == (None, None, TP_AXIS)
    finally:
        e0.close()
        e1.close()


def test_serve_passthrough_and_metrics_gauges(tiny_lm):
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=16,
                        paged=True, kv_quant=True, weight_quant="int8")
    try:
        assert srv.engine.kv_quant and srv.engine.weight_quant == "int8"
        out = srv.generate(_prompt(), max_new_tokens=4, timeout=120)
        assert len(out) == 4
        txt = srv.metrics.prometheus_text(srv.engine, srv.scheduler)
        for tok in ("serving_kv_quant_enabled 1",
                    "serving_weight_quant_enabled 1",
                    "serving_kv_quant_bytes_per_token",
                    "serving_quant_max_logit_error"):
            assert tok in txt, tok
    finally:
        srv.close()


def test_aot_cache_key_covers_quant_flags():
    from mxnet_tpu.aot.cache import _FLAG_ENV
    assert "MXNET_QUANTIZED_KV" in _FLAG_ENV
    assert "MXNET_QUANTIZED_WEIGHTS" in _FLAG_ENV


# ---------------------------------------------------------------------------
# training leg: int8 dp-grad collective with error feedback
# ---------------------------------------------------------------------------


def _mlp():
    from mxnet_tpu.gluon import nn
    np.random.seed(0)
    net = nn.HybridSequential(prefix="q_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, 6)))
    return net


def test_quantized_collectives_loss_curve_and_ledger():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (emulated) devices")
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep
    from mxnet_tpu.parallel.mesh import build_mesh
    from mxnet_tpu.telemetry.introspect import comms_from_hlo

    lossfn = gloss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(1)
    xs = [rng.uniform(-1, 1, (16, 6)).astype(np.float32)
          for _ in range(20)]
    ys = [rng.randint(0, 4, (16,)).astype(np.float32) for _ in range(20)]

    mx.random.seed(0)
    sa = TrainStep(_mlp(), lossfn, "sgd", {"learning_rate": 0.1},
                   mesh=build_mesh({"dp": 8}))
    la = [float(sa(x, y)) for x, y in zip(xs, ys)]
    mx.random.seed(0)
    sb = TrainStep(_mlp(), lossfn, "sgd", {"learning_rate": 0.1},
                   mesh=build_mesh({"dp": 8}),
                   quantized_collectives="int8")
    lb = [float(sb(x, y)) for x, y in zip(xs, ys)]
    assert sb.collective_quant == "int8"
    assert sb.collective_quant_fallback is None
    # loss-curve tolerance: error feedback keeps int8 training on the
    # f32 trajectory on this toy problem
    assert max(abs(a - b) for a, b in zip(la, lb)) < 0.05, (la, lb)
    # comms ledger vs THEORY: grads move as s8 (1 byte/param/all-reduce)
    # plus tiny f32 scale/loss scalars — under half the f32 ideal
    hlo = sb._step_fn.lower(*sb._example_args).compile().as_text()
    kinds = comms_from_hlo(hlo)
    grad_params = sum(int(np.prod(p.shape))
                      for p in sb._net.collect_params().values()
                      if p.grad_req != "null")
    ar = kinds.get("all_reduce", {}).get("bytes", 0)
    assert ar >= grad_params, kinds          # every grad crossed, as s8
    assert ar < 0.5 * grad_params * 4, kinds  # ...not as f32
    assert "s8[" in hlo


def test_quantized_collectives_fallbacks(monkeypatch):
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep
    from mxnet_tpu.parallel.mesh import build_mesh
    lossfn = gloss.SoftmaxCrossEntropyLoss()
    s1 = TrainStep(_mlp(), lossfn, "sgd", {"learning_rate": 0.1},
                   quantized_collectives="int8")
    s1._build()
    assert s1.collective_quant is None
    assert "mesh" in s1.collective_quant_fallback
    if len(jax.devices()) >= 8:
        s2 = TrainStep(_mlp(), lossfn, "sgd", {"learning_rate": 0.1},
                       mesh=build_mesh({"dp": 8}), sharded_update=True,
                       quantized_collectives="int8")
        s2._build()
        assert s2.collective_quant is None
        assert "ZeRO" in s2.collective_quant_fallback
    # a typo must not silently measure a different config
    s3 = TrainStep(_mlp(), lossfn, "sgd", {"learning_rate": 0.1},
                   quantized_collectives="fp8")
    with pytest.raises(ValueError):
        s3._build()
    # env default, read at construction
    monkeypatch.setenv("MXNET_QUANTIZED_COLLECTIVES", "int8")
    s4 = TrainStep(_mlp(), lossfn, "sgd", {"learning_rate": 0.1})
    assert s4._qcoll_req == "int8"
