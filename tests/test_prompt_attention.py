"""A whole prompt's attention as one kernel a layer (ISSUE 41):
ops/pallas_prompt_attention.py under `kv_cache.PromptView`.

Load-bearing claims: (a) the kernel, in interpreter mode, gives what
`afmoe.banded_attention` gives over the rows below `length`, for 48 query
heads on 8 with a window of four key blocks, 20 on 4 and 32 on 32 without,
for buckets of one, two and many blocks, for `length` on a block's edge,
one past it, one short of the bucket and far short of it; the rows of
query blocks past `length` are zero; (b) what lies outside a query
block's band or past `length` is never read into a product: poisoned
with NaN it leaves the rows below `length` finite; (c) the clamped index
maps visit `ceil(length / bq)` query blocks and the band's key blocks of
each, and a step that visits nothing names the block already resident;
(d) a Trinity-shaped and a Falcon-H1-shaped model are served THROUGH the
kernel token for token as XLA serves them; (e) a prefill program's
module, lowered for the TPU, holds ONE `tpu_custom_call` a window
whatever the number of layers, and the programs of two buckets trace and
lower the kernel once a (bucket, window): what is traced or lowered per
layer or per program is paid in every warm `setup_s` (PERF.md §6).

Tolerance: 2e-5 on outputs of order 1: kernel and reference accumulate in
float32 (the suite multiplies at "highest") and differ in the order of
the sums alone.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving, telemetry
from mxnet_tpu.models import afmoe, falcon_h1
from mxnet_tpu.ops import pallas_prompt_attention as pa
from mxnet_tpu.serving import afmoe_lm, kv_cache

DH = 16
i32 = jnp.int32
#: name -> (query heads, cached heads, key blocks of the window or 0)
HEADS = {"48_on_8_window": (48, 8, 4), "20_on_4": (20, 4, 0),
         "32_on_32": (32, 32, 0)}
#: name -> (bucket, query block, key block)
BUCKETS = {"one_block": (32, 32, 32), "two_blocks": (64, 32, 32),
           "many_blocks": (256, 16, 32)}


def qkv(S, H, Hkv, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(S, h, DH)), jnp.float32)
                 for h in (H, Hkv, Hkv))


def lengths(S, bq):
    """On a block's edge, one past it, one short of the bucket, far short."""
    edge = max(bq, S // 2 // bq * bq)
    return sorted({edge, min(edge + 1, S), S - 1, 3})


# -- (a) parity ----------------------------------------------------------------

@pytest.mark.parametrize("bucket", BUCKETS)
@pytest.mark.parametrize("heads", HEADS)
def test_the_kernel_is_banded_attention_over_the_rows_below_length(
        heads, bucket):
    H, Hkv, window_blocks = HEADS[heads]
    S, bq, bk = BUCKETS[bucket]
    window = window_blocks * bk
    q, k, v = qkv(S, H, Hkv)
    want = np.asarray(afmoe.banded_attention(q, k, v, window))
    for length in lengths(S, bq):
        got = np.asarray(pa.prompt_attention(
            q, k, v, i32(length), window=window, blocks=(bq, bk),
            interpret=True))
        np.testing.assert_allclose(got[:length], want[:length], atol=2e-5,
                                   rtol=0, err_msg="length %d" % length)
        assert np.isfinite(got).all()
        # a query block that starts at or past `length` is left zero
        assert not got[-(-length // bq) * bq:].any()


def test_the_default_blocks_are_whole_tiles_of_every_bucket_the_gate_admits():
    for S in (pa.MIN_BUCKET, 1024, 8192):
        bq, bk = pa.block_sizes(S)
        assert S % bq == 0 and S % bk == 0 and S // bq >= 2
        assert bq % 16 == 0 and bk % 128 == 0


# -- (b) what is outside the band is not read -------------------------------------

@pytest.mark.parametrize("window_blocks", [0, 4])
def test_nan_outside_a_blocks_band_and_past_length_reaches_no_real_row(
        window_blocks):
    S, bq, bk, length = 256, 16, 32, 150
    window = window_blocks * bk
    q, k, v = qkv(S, 6, 2, seed=1)
    want = np.asarray(afmoe.banded_attention(q, k, v, window))
    at = np.arange(S)
    # everything past `length`: every query block at once
    poison = jnp.where(jnp.asarray(at < length)[:, None, None], 0.0, jnp.nan)
    got = np.asarray(pa.prompt_attention(
        q, k + poison, v + poison, i32(length), window=window,
        blocks=(bq, bk), interpret=True))
    assert np.isfinite(got[:length]).all()
    np.testing.assert_allclose(got[:length], want[:length], atol=2e-5, rtol=0)
    # and, a query block at a time, every key NO query of the block sees
    for qi in range(-(-length // bq)):
        lo, hi = qi * bq, min(qi * bq + bq, length) - 1
        seen = (at <= hi) & ((at > lo - window) if window else True)
        poison = jnp.where(jnp.asarray(seen)[:, None, None], 0.0, jnp.nan)
        got = np.asarray(pa.prompt_attention(
            q, k + poison, v + poison, i32(length), window=window,
            blocks=(bq, bk), interpret=True))
        np.testing.assert_allclose(got[lo:hi + 1], want[lo:hi + 1],
                                   atol=2e-5, rtol=0, err_msg="block %d" % qi)
    # the reference reads them all: the poison is real
    assert not np.isfinite(np.asarray(afmoe.banded_attention(
        q, k + poison, v + poison, window))[:length]).all()


# -- (c) the blocks visited ---------------------------------------------------------

@pytest.mark.parametrize("window", [0, 128])
@pytest.mark.parametrize("length", [1, 96, 97, 255, 256])
def test_the_index_maps_visit_the_real_query_blocks_and_their_bands(
        length, window):
    S, bq, bk = 256, 16, 32
    q_map, kv_map, o_map = pa.block_maps(bq, bk, window)
    steps = pa.band_blocks(S, bq, bk, window)
    n = np.asarray([length], np.int32)
    visited, q_blocks, last = set(), set(), None
    for qi in range(S // bq):
        first, end = pa._span(qi, length, bq, bk, window, np)
        for kj in range(steps):
            block = int(kv_map(0, qi, kj, n)[0])
            if qi * bq < length and first + kj <= end:
                visited.add((qi, block))
                q_blocks.add(int(q_map(0, qi, kj, n)[0]))
            else:
                # nothing to fetch: the block the step before left there
                assert block == last, (qi, kj)
                assert int(q_map(0, qi, kj, n)[0]) \
                    == min(qi, (length - 1) // bq)
            last = block
        assert int(o_map(0, qi, 0, n)[0]) == qi      # every row is written
    real = -(-length // bq)
    assert q_blocks == set(range(real))
    want = set()
    for qi in range(real):
        lo = max(0, qi * bq - window + 1) if window else 0
        hi = min(qi * bq + bq, length) - 1
        want |= {(qi, b) for b in range(lo // bk, hi // bk + 1)}
    assert visited == want
    if length == S:
        # the key-block axis is as long as the widest band, no longer
        assert steps == max(sum(1 for q, _ in want if q == qi)
                            for qi in range(real))


# -- (d) the engine ------------------------------------------------------------

def family(name):
    """A Trinity-shaped model (six query heads on two, window and full
    layers, experts) and a Falcon-H1-shaped one (four on two beside a
    recurrent state), at the models' own tiny defaults."""
    if name == "kinds":
        cfg = afmoe.AfmoeConfig(max_len=64)
        return afmoe.init_afmoe_params(jax.random.PRNGKey(11), cfg), cfg
    cfg = falcon_h1.FalconH1Config(max_len=64)
    return falcon_h1.init_falcon_h1_params(jax.random.PRNGKey(11), cfg), cfg


def prompt(start, n, vocab=256):
    return [(start + 5 * t) % vocab for t in range(n)]


#: prompts in the buckets of 8, 16 and 32, one of them a bucket exactly;
#: the 20 tokens go twice round the window layers' ring
REQUESTS = [(prompt(1, 9), 5), (prompt(2, 20), 6), (prompt(3, 5), 4),
            (prompt(4, 16), 6)]


def serve_all(model):
    telemetry.tracing.clear()
    srv = serving.serve(model, max_batch=4, block_size=8)
    try:
        handles = [srv.submit(p, max_new_tokens=n) for p, n in REQUESTS]
        tokens = [list(h.result(timeout=300)) for h in handles]
        attn = [s["attrs"].get("attn") for s in telemetry.spans()
                if s["name"] == "serving.prefill"]
        return tokens, srv.snapshot(), attn, srv
    finally:
        srv.close()


@pytest.mark.parametrize("name", ["kinds", "state"])
def test_both_families_are_served_through_the_kernel_as_xla_serves_them(
        name, monkeypatch):
    model = family(name)
    want, snap, attn, srv = serve_all(model)
    assert attn == ["xla"] * len(REQUESTS)
    assert snap["throughput"]["prefills_attn_kernel"] == 0

    monkeypatch.setattr(pa, "prompt_attention_unfit", lambda *a, **kw: None)
    got, snap, attn, srv = serve_all(model)
    assert srv.engine.prompt_attn_fallback is None
    assert "prompt_attn_fallback" not in snap["engine"]
    assert attn == ["kernel"] * len(REQUESTS)
    assert snap["throughput"]["prefills_attn_kernel"] == len(REQUESTS)
    assert re.search(r"^serving_prefills_attn_kernel_total\S* %d$"
                     % len(REQUESTS), srv.prometheus_text(), re.M)
    # float32 on both sides, summed in another order: a token differs
    # only across a tie of 1e-6, which these weights do not have
    assert got == want


# -- (e) lowered once a (bucket, window) ----------------------------------------

def test_two_buckets_of_five_layers_lower_the_kernel_once_a_bucket_and_window(
        monkeypatch):
    gate = pa.prompt_attention_unfit
    monkeypatch.setattr(
        pa, "prompt_attention_unfit",
        lambda S, head_dim, group, dtype, backend=None:
        gate(S, head_dim, group, dtype, "tpu"))
    monkeypatch.setattr(kv_cache, "default_interpret", lambda: False)
    low = pa.MIN_BUCKET
    cfg = afmoe.AfmoeConfig(
        vocab=64, d_model=64, n_heads=4, n_kv_heads=2, head_dim=128,
        n_layers=5, n_dense_layers=5, window=low // 2, d_ff=64, d_expert=32,
        n_experts=4, experts_held=(0, 4), max_len=4 * low,
        dtype=jnp.float32)
    assert cfg.layer_kinds.count("window") == 4
    sds = jax.ShapeDtypeStruct
    mats, gains = afmoe.param_shapes(cfg)
    params = {n: sds(s, jnp.float32) for n, s in {**mats, **gains}.items()}
    model = afmoe_lm.AfmoeLM(params, cfg)
    model.bind(16)
    spec = model.cache_spec()
    ring = spec.ring("window", 16)
    planes = [sds((len(spec.layers_of(kind)), 65, 2, 16, 128), jnp.float32)
              for kind in spec.kinds for _ in range(2)]
    pa._lowered_once.cache_clear()
    for bucket, kernels in ((low, 2), (2 * low, 2), (low // 2, 0), (low, 2)):
        lowered = model._prefill_jit._jitted.trace(
            params, *planes, sds((bucket,), i32), sds((), i32),
            sds((cfg.max_len // 16 + ring,), i32)).lower(
                lowering_platforms=("tpu",))
        # five layers, two windows: two kernels; under the gate, none
        assert len(re.findall(r"tpu_custom_call",
                              lowered.as_text())) == kernels
        assert lowered._lowering.compile_args["committed"] is False
    # four programs, fifteen layers through the kernel: lowered once a
    # (bucket, window), four times in all
    assert pa._lowered_once.cache_info().misses == 4
