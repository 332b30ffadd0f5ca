"""The fourth served family (ISSUE 37): attention heads and state-space
(Mamba-2) heads side by side in every layer, a recurrent state as a third
KIND of cache beside the keys and values.

Load-bearing claims, at a toy size on the CPU (two layers, 4 heads on 2, 4
state heads of 8, state 16, 2 groups, chunk 8), against the plain reference
(`chipbench/reference/falcon_h1_lm.py`: float32, the recurrence one step a
position): the block and the whole forward; the chunked scan against the
recurrence, at lengths that are no multiple of the chunk and in a padded
bucket (the state at `length`); prefill then decode through the cache, LOGITS
not tokens, over a ragged batch whose rows join, end and change place, with
prompts of 1, 2 and 3 tokens (the convolution's edge); a slot freed and given
again carries nothing over; a zeroed state FAILS the comparison; the kernel
that updates the states where they lie against the gather and scatter; every
kind taken or none, the pools made anew after a fault, the audit; each option
the family cannot take, with its reason; what the metrics publish.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import falcon_h1
from mxnet_tpu.ops import pallas_ssm_step
from mxnet_tpu.serving import kv_cache

from chipbench.families import falcon_h1_lm as family
from chipbench.reference import falcon_h1_lm as reference

TOY = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "intermediate_size": 64, "mamba_d_ssm": 32,
    "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "num_hidden_layers": 2, "vocab_size": 96, "rms_norm_eps": 1e-5,
    "rope_theta": 100000000000,
    "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "embedding_multiplier": 5.656854249492381,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "ssm_in_multiplier": 0.25, "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "dtype": "float32", "state_dtype": "float32"}
BS, MAX_LEN = 8, 64
#: float32 through another order of sums: what the served logits may differ
#: from the reference's by, as a share of the largest logit
REL = 2e-5


@pytest.fixture(scope="module")
def model():
    weights = family.make_weights(TOY, 7)
    # 32 wide, N(0, 0.02) matrices leave every product near nothing and the
    # state with them: eight times as wide a draw, and losing it shows
    wider = lambda lw: {n: a * 8 if a.ndim == 2 and n != "conv_w" else a
                        for n, a in lw.items()}
    weights = dict(wider({n: a for n, a in weights.items() if n != "layers"}),
                   layers=[wider(lw) for lw in weights["layers"]])
    return (weights, family.program_params(weights),
            family.program_config(TOY, MAX_LEN))


def prompt(start, n):
    return [(start + 5 * t) % TOY["vocab_size"] for t in range(n)]


def ref_logits(weights, tokens, **kw):
    """The reference's logits for the first len(tokens) positions."""
    padded = np.zeros((reference.pad_len(len(tokens)),), np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(reference.logits(weights, TOY, padded, **kw))[
        :len(tokens)]


def engine(params, cfg, **kw):
    kw.setdefault("max_batch", 4)
    return serving.Engine(serving.FalconH1LM(params, cfg), max_len=MAX_LEN,
                          block_size=BS, keep_logits=True, **kw)


def gap(seq, weights, **kw):
    """Largest distance of a served sequence's logits (one row an emitted
    token) from the reference's, over the reference's largest logit."""
    want = ref_logits(weights, seq.tokens[:-1], **kw)[seq.prompt_len - 1:]
    got = np.stack(seq.token_logits)
    assert got.shape == want.shape
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_the_dense_forward_and_each_layer_agree_with_the_reference(model):
    weights, params, cfg = model
    toks = np.asarray(prompt(3, 128), np.int32)
    want = np.asarray(reference.logits(weights, TOY, toks))
    got = np.asarray(falcon_h1.falcon_h1_apply(params, jnp.asarray(toks), cfg))
    assert np.abs(got - want).max() <= REL * np.abs(want).max()
    # one layer alone, over rows that are not an embedding's
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 32))
    cos, sin = reference.rope_tables(TOY, 128)
    for i, lw in enumerate(weights["layers"]):
        want = np.asarray(reference.layer(x, lw, TOY, cos, sin, None))
        got = np.asarray(falcon_h1.block(params, i, x, jnp.arange(128), cfg,
                                         falcon_h1.DenseView()))
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    # every multiplier is read: a published one set to 1 moves the logits
    got = np.asarray(falcon_h1.falcon_h1_apply(params, jnp.asarray(toks), cfg))
    for name in ("embedding_multiplier", "key_multiplier",
                 "attention_out_multiplier", "ssm_in_multiplier",
                 "ssm_out_multiplier"):
        other = family.program_config(dict(TOY, **{name: 1.0}), MAX_LEN)
        moved = np.asarray(falcon_h1.falcon_h1_apply(
            params, jnp.asarray(toks), other))
        assert np.abs(moved - got).max() > 0, name


def recurrence(x, dt, A, Bm, Cm):
    """One step a position, as the reference's."""
    S, H, _ = x.shape
    per_head = lambda t: jnp.repeat(t, H // t.shape[1], axis=1)
    h = jnp.zeros(x.shape[1:] + Bm.shape[-1:])
    ys = []
    for t in range(S):
        h = jnp.exp(dt[t] * A)[:, None, None] * h \
            + (dt[t][:, None] * x[t])[:, :, None] * per_head(Bm)[t][:, None, :]
        ys.append(jnp.einsum("hpn,hn->hp", h, per_head(Cm)[t]))
    return jnp.stack(ys), h


def scan_inputs(S, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (S, 4, 8)),
            jax.nn.softplus(jax.random.normal(k[1], (S, 4)) - 2.0),
            -jnp.exp(jax.random.uniform(k[2], (4,), minval=0.0, maxval=2.7)),
            jax.random.normal(k[3], (S, 2, 16)),
            jax.random.normal(k[4], (S, 2, 16)))


@pytest.mark.parametrize("S,chunk", [(5, 8), (8, 8), (13, 8), (21, 8),
                                     (21, 4), (16, 32)])
def test_the_chunked_scan_is_the_recurrence(S, chunk):
    x, dt, A, Bm, Cm = scan_inputs(S)
    y, h = falcon_h1.ssd_scan(x, dt, A, Bm, Cm, chunk)
    want_y, want_h = recurrence(x, dt, A, Bm, Cm)
    assert np.abs(np.asarray(y - want_y)).max() < 1e-5
    assert np.abs(np.asarray(h - want_h)).max() < 1e-5


@pytest.mark.parametrize("length", [1, 2, 3, 11, 16])
def test_a_padded_bucket_leaves_the_state_at_the_prompts_true_length(
        model, length):
    """`mix_prompt` over a bucket of 16 whose positions past `length` hold
    anything at all: the state is the recurrence's after `length` positions,
    in the cache's layout, and the convolution's last inputs are the last
    three REAL ones (zeros before position 0)."""
    _, params, cfg = model
    w = falcon_h1.mixer_weights(params, 0)
    xbc = jax.random.normal(jax.random.PRNGKey(2), (16, cfg.conv_channels))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(3), (16, 4)) - 2)
    y, state, tail = falcon_h1.mix_prompt(xbc, dt, w, cfg, length)
    exact_y, exact_state, _ = falcon_h1.mix_prompt(xbc[:length], dt[:length],
                                                   w, cfg)
    assert np.abs(np.asarray(y[:length] - exact_y)).max() < 1e-5
    assert np.abs(np.asarray(state - exact_state)).max() < 1e-5
    assert state.shape == falcon_h1.state_layout(cfg) == (2, 16, 2, 8)
    padded = np.concatenate([np.zeros((3, cfg.conv_channels)),
                             np.asarray(xbc)])
    assert np.array_equal(np.asarray(tail), padded[length:length + 3])
    # and against the recurrence itself, through the convolution
    conv = jax.nn.silu(sum(
        jnp.asarray(padded[k:k + 16]) * w["conv_w"][k] for k in range(4))
        + w["conv_b"])
    xs, Bm, Cm = falcon_h1._heads(conv, cfg)
    _, want = recurrence(xs[:length], dt[:length],
                         -jnp.exp(w["A_log"]), Bm[:length], Cm[:length])
    want = want.reshape(2, 2, 8, 16).transpose(0, 3, 1, 2)
    assert np.abs(np.asarray(state) - np.asarray(want)).max() < 1e-5


def test_prefill_then_decode_through_the_cache_over_a_ragged_batch(model):
    """Rows join, end and change place; prompts of 1, 2 and 3 tokens sit at
    the convolution's edge. Every emitted token's LOGITS against the
    reference's full forward."""
    weights, params, cfg = model
    eng = engine(params, cfg)
    assert eng.cache.spec.kinds == ("full", "state")
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
    eng.close()


def test_a_slot_given_again_carries_nothing_and_a_zeroed_state_fails(model):
    weights, params, cfg = model
    eng = engine(params, cfg, max_batch=1)
    assert eng.cache.pools[1].num_blocks == 2          # one slot and the null
    first = eng.start(prompt(1, 13), 10)
    slot = first.blocks[1]
    while not first.done:
        eng.decode_step([first])
    eng.release(first)
    # the next sequence is shorter than what the slot last held
    second = eng.start(prompt(9, 3), 10)
    assert second.blocks[1] == slot and int(second.table_row[-1]) == slot[0]
    while not second.done:
        eng.decode_step([second])
    assert gap(first, weights) < REL and gap(second, weights) < REL
    eng.release(second)
    # the state matters at this size: lose it after the prefill and the
    # comparison fails by orders; so does a reference that leaves it out
    third = eng.start(prompt(4, 40), 10)
    eng.cache.ssm_state = jnp.zeros_like(eng.cache.ssm_state)
    while not third.done:
        eng.decode_step([third])
    print("sound", gap(first, weights), gap(second, weights),
          "no state", gap(third, weights),
          "no mixer", gap(first, weights, zero_state=True))
    assert gap(third, weights) > 5 * REL
    assert gap(first, weights, zero_state=True) > 100 * REL
    eng.release(third)
    # and the convolution's last inputs alone
    fourth = eng.start(prompt(4, 40), 10)
    eng.cache.conv_state = jnp.zeros_like(eng.cache.conv_state)
    eng.decode_step([fourth])
    print("no last inputs", gap(fourth, weights))
    assert gap(fourth, weights) > 10 * REL
    eng.release(fourth)
    eng.close()


def test_the_kernel_updates_the_states_where_they_lie(model, monkeypatch):
    """The interpreter runs the kernel the chip compiles: against
    `state_update` on states gathered by hand, slots out of order and the
    null slot twice (padded rows); the rest of the plane is not touched."""
    k = jax.random.split(jax.random.PRNGKey(5), 6)
    plane = jax.random.normal(k[0], (3, 6, 2, 16, 8, 128))
    slots = jnp.asarray([4, 0, 2, 0], jnp.int32)
    decay = jnp.exp(-jax.random.uniform(k[1], (4, 2, 8, 1)))
    dtx = jax.random.normal(k[2], (4, 2, 8, 128))
    Bm, Cm = (jax.random.normal(k[i], (4, 2, 16)) for i in (3, 4))
    want_h, want_y = falcon_h1.state_update(plane[1, slots], decay, dtx, Bm,
                                            Cm)
    new, y = pallas_ssm_step.ssm_step(plane, jnp.int32(1), slots, decay, dtx,
                                      Bm, Cm, interpret=True)
    assert np.abs(np.asarray(y - want_y)).max() < 1e-4
    for row in (0, 2):
        assert np.abs(np.asarray(new[1, slots[row]] - want_h[row])).max() < 1e-5
    keep = np.asarray([1, 3, 5])
    assert np.array_equal(np.asarray(new[1, keep]), np.asarray(plane[1, keep]))
    assert np.array_equal(np.asarray(new[0]), np.asarray(plane[0]))
    assert np.array_equal(np.asarray(new[2]), np.asarray(plane[2]))
    # the gate: the CPU, a plane that is not float32, a slab off the tiles
    assert "backend is cpu" in pallas_ssm_step.step_fallback_reason(plane)
    assert pallas_ssm_step.step_fallback_reason(plane, "tpu") is None
    assert "bfloat16" in pallas_ssm_step.step_fallback_reason(
        plane.astype(jnp.bfloat16), "tpu")
    assert "whole (8, 128) tiles" in pallas_ssm_step.step_fallback_reason(
        plane[..., :4, :], "tpu")
    assert pallas_ssm_step.heads_a_block(256, 16, 128) == 16
    assert pallas_ssm_step.heads_a_block(256, 32, 128) == 16
    assert pallas_ssm_step.step_bytes(64, 2, 256, 16, 128) \
        == 4 * 64 * 2 * (2 * 256 * 2048 + 512 + 3 * 2048)
    # served THROUGH the kernel (the gate opened, the interpreter): the same
    # logits
    weights, params, cfg = model
    monkeypatch.setattr(pallas_ssm_step, "step_fallback_reason",
                        lambda *a, **k: None)
    eng = engine(params, cfg, max_batch=2)
    assert eng.state_step_fallback is None
    seqs = [eng.start(prompt(1, 2), 7), eng.start(prompt(2, 17), 7)]
    while not all(s.done for s in seqs):
        eng.decode_step([s for s in seqs if not s.done])
    assert all(gap(s, weights) < REL for s in seqs)
    for s in seqs:
        eng.release(s)
    eng.close()


def test_the_kernel_lowers_for_the_tpu_at_the_cells_widths():
    """The Python stage of the Mosaic lowering, with no chip: block shapes
    and the SMEM operands at 64 rows of 2 x 256 x 16 x 128."""
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    lowered = jax.jit(
        lambda *a: pallas_ssm_step._step_rows(*a, interpret=False)).trace(
        sds((2, 65, 2, 256, 16, 128), f32), sds((1,), i32), sds((64,), i32),
        sds((64, 2, 16, 128), f32), sds((64, 2, 16, 128), f32),
        sds((64, 2, 256), f32), sds((64, 2, 256), f32)).lower(
        lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert "tpu_custom_call" in text and "ssm_step" in text


def test_admission_takes_every_kind_or_none_and_the_audit_counts_slots(model):
    _, params, cfg = model
    eng = engine(params, cfg, max_batch=2, num_blocks=12)
    full, state = eng.cache.pools
    assert (full.num_blocks, state.num_blocks) == (12, 3)
    assert eng.cache.rings == (0, 1) and eng.cache.table_width(8) == 9
    assert eng.blocks_needed(10, 9) == (3, 1)
    assert eng.blocks_needed(60, 60) == (8, 1)          # capped at max_len
    a = eng.begin(prompt(1, 30), 30)
    assert (full.in_use, state.in_use) == (8, 1)
    assert list(a.table_row[:8]) == a.blocks[0] and a.table_row[8] == a.blocks[1][0]
    # K/V short: no slot is taken either
    assert eng.can_admit(40, 20) is False
    assert eng.begin(prompt(2, 40), 20) is None
    assert (full.in_use, state.in_use) == (8, 1)
    b = eng.begin(prompt(2, 9), 4)
    assert (full.in_use, state.in_use) == (10, 2)
    # now the slots are short though K/V blocks are not
    assert full.available == 1 and eng.can_admit(3, 2) is False
    assert eng.begin(prompt(3, 3), 2) is None
    assert (full.in_use, state.in_use) == (10, 2)
    assert eng.cache.held_at_high_water == (10, 2)
    eng.release(a, reusable=False)
    eng.release(b, reusable=False)
    assert (full.in_use, state.in_use) == (0, 0) and eng.cache.recycled == 0
    # a request no pool could ever hold raises and takes nothing
    with pytest.raises(kv_cache.CacheOverflow):
        eng.cache.try_alloc((3, 3))
    assert (full.in_use, state.in_use) == (0, 0)
    # a leaked slot alone is found and named
    leaked = state.try_alloc(1)
    with pytest.raises(MXNetError, match="not quiescent"):
        eng.audit_quiescent()
    state.free(leaked)
    eng.close()
    # the state costs no token: a flat count a sequence
    assert eng.kv_bytes_per_token() == 2 * 2 * 2 * 8 * 4
    assert eng.cache.spec.state_bytes() == 2 * (4 * 8 * 16 * 4 + 3 * 96 * 4)


def test_pools_lost_in_a_step_are_made_anew_with_the_state_kind(model):
    weights, params, cfg = model
    srv = serving.serve((params, cfg), max_batch=2, max_len=MAX_LEN,
                        block_size=BS)
    eng = srv.engine
    want = {}
    try:
        want = {n: list(srv.generate(prompt(n, 9), max_new_tokens=10,
                                     timeout=120)) for n in (2, 3)}
        real_decode, calls = eng.model.decode, []

        def faulty(*pools_and_args):
            calls.append(1)
            if len(calls) == 3:
                for pool in pools_and_args[:4]:
                    pool.delete()       # as a step that failed after launch
                raise RuntimeError("injected step fault")
            return real_decode(*pools_and_args)

        eng.model.decode = faulty
        hurt = {n: srv.submit(prompt(n, 9), max_new_tokens=10)
                for n in (2, 3)}
        got = {n: list(h.result(timeout=120)) for n, h in hurt.items()}
        eng.model.decode = real_decode
        # replayed through prefill: the states were rebuilt, token for token
        assert got == want and eng.pools_lost == 1
        assert [a.shape for a in eng.cache.arrays()] \
            == [(2, 17, 2, 8, 8)] * 2 + [(2, 3, 2, 16, 2, 8), (2, 3, 3 * 96)]
        assert not eng.cache.lost()
    finally:
        srv.close()                     # the audit, over both pools
    assert [p.in_use for p in eng.cache.pools] == [0, 0]
    eng.cache.drop()
    assert eng.cache.ssm_state is None and eng.cache.conv_state is None


def test_each_option_the_family_cannot_take_falls_back_with_its_reason(model):
    _, params, cfg = model
    eng = serving.LMServer((params, cfg), max_batch=2, max_len=MAX_LEN,
                           paged=True, kv_quant=True,
                           prefix_cache=True).engine
    assert not eng.paged and "recurrent state beside" in eng.paged_fallback
    assert "chunk to chunk" in eng.paged_fallback \
        and "roll it back" in eng.paged_fallback
    assert not eng.kv_quant and "needs the paged path" in eng.kv_quant_fallback
    assert eng.prefix_cache is None \
        and "chunked-prefill paged path" in eng.prefix_cache_fallback
    assert eng.prefill_chunk == 0 and eng.sync_reason is None
    assert "backend is cpu" in eng.state_step_fallback
    eng.close()
    eng = serving.LMServer((params, cfg), max_batch=2, max_len=MAX_LEN,
                           tp=2).engine
    assert eng.tp == 1 and "paged path off/ineligible" in eng.tp_fallback
    eng.close()
    eng = serving.LMServer((params, cfg), max_batch=2, max_len=MAX_LEN,
                           spec=True).engine
    assert not eng.spec and eng.spec_fallback
    eng.close()
    # the spec says which layers keep which kinds: here each keeps two
    spec = serving.FalconH1LM(params, cfg).cache_spec()
    assert spec.layer_kinds == ("full+state",) * 2
    assert spec.layers_of("full") == spec.layers_of("state") == (0, 1)
    assert spec.attn_kind(1) == "full" and spec.ring("state", 8) == 1
    # a state beside a window kind, in some layers only
    mixed = kv_cache.CacheSpec(
        3, "float32", n_heads=2, head_dim=8,
        layer_kinds=("window", "full+state", "window+state"), window=16,
        state_shape=(2, 16, 2, 8), conv_shape=(3, 64), state_dtype="float32")
    assert mixed.kinds == ("full", "window", "state")
    assert mixed.layers_of("state") == (1, 2) and mixed.attn_kind(2) == "window"
    cache = kv_cache.PagedKVCache.of(mixed, block_size=8, num_blocks=(9, 7, 3))
    assert [a.shape for a in cache.arrays()] == [
        (1, 9, 2, 8, 8)] * 2 + [(2, 7, 2, 8, 8)] * 2 + [
        (2, 3, 2, 16, 2, 8), (2, 3, 192)]
    assert cache.blocks_by_kind(40) == (5, 3, 1)
    assert cache.table_width(6) == 6 + 3 + 1
    assert "recurrent state" in mixed.paged_unfit()


def test_serve_takes_the_family_and_publishes_the_state_kind(model):
    weights, params, cfg = model
    telemetry.tracing.clear()
    srv = serving.serve((params, cfg), max_batch=4, max_len=MAX_LEN,
                        block_size=BS)
    try:
        assert isinstance(srv.engine.model, serving.FalconH1LM)
        handles = [srv.submit(prompt(n, 4 + 3 * n), max_new_tokens=9)
                   for n in range(1, 5)]
        out = [list(h.result(timeout=120)) for h in handles]
        assert all(len(o) == 9 for o in out)
        # greedy tokens are the reference's where its logits are not tied
        toks = prompt(2, 10) + out[1]
        logits = ref_logits(weights, toks[:-1])[9:]
        assert list(np.argmax(logits, -1)) == out[1]
        snap = srv.snapshot()
        assert snap["engine"]["state_dtype"] == "float32"
        assert "backend is cpu" in snap["engine"]["state_step_fallback"]
        assert "backend is cpu" in snap["engine"]["walk_fallback"]
        assert snap["cache"]["state"] == {
            "blocks_in_use": 0, "blocks_high_water": 4, "blocks_total": 4,
            "blocks_recycled": 0}
        text = srv.prometheus_text()
        for line in (r"serving_state_blocks_in_use\{[^}]*\} 0",
                     r"serving_state_blocks_high_water\{[^}]*\} 4",
                     r"serving_state_blocks_total\{[^}]*\} 4"):
            assert re.search(line, text), line
        steps = [s["attrs"] for s in telemetry.spans()
                 if s["name"] == "serving.decode" and "batch" in s["attrs"]]
        assert steps and all(a["state_rows"] == a["batch"] for a in steps)
        assert all(a["live_full"] >= a["live_max"] for a in steps)
        assert all("live_window" not in a and a["walk"] == "xla"
                   for a in steps)
        assert snap["throughput"]["decode_steps_ahead"] > 0
    finally:
        srv.close()
