"""Tensor-parallel serving: the paged engine sharded over a named mesh.

`MXNET_SERVING_TP=k` (or `Engine(tp=k)`) shards one engine replica's
transformer weights and KV block pool over a `{'tp': k}` mesh
(parallel/mesh.py — the SAME GSPMD axis the training dp×tp mesh uses),
so per-request decode latency stops being capped by one chip:

* Weights shard Megatron-style head-wise/column-row with NamedSharding
  (the SNIPPETS [1]–[3] pattern, `transformer_shardings`' tp specs):
  wqkv column-parallel over heads, wo row-parallel, FFN w1 column /
  w2 row. Embeddings, layer norms, and the LM head stay replicated —
  after every row-parallel psum the residual stream is replicated, so
  logits come out identical on every chip (no cross-chip argmax).
* The KV block pool shards over the HEAD axis — each chip owns H/k
  heads of EVERY block, so block tables stay replicated host-side
  integers and the free-list/scheduling logic is untouched.
* The ragged paged-attention kernel (ops/pallas_paged.py) runs inside
  `shard_map`: each chip walks the same block table against its own
  H/k-head pool shard. Online softmax is per-head, so no softmax
  statistic ever crosses a chip — the only collectives are the two
  psums per layer (attention output and FFN output projections), and
  the decode bytes each chip moves drop ~1/k.

Fallback semantics (docs/ENV_VARS.md): the flag switches PLACEMENT,
never logits. Configs the tp path can't shard (heads or d_ff not
divisible by k, MoE FFN, fewer than k devices, paged kernel ineligible,
model family without cache hooks) fall back to tp=1 with the reason
recorded on `Engine.tp_fallback`; the math is bit-comparable either way
(f32 parity pinned in tests/test_serving_tp.py against both the
single-device paged and the gather oracles).

Everything here is read at Engine CONSTRUCTION only — a replica can
never straddle two placements (Engine raises on post-start mutation).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError
from ..ops.quantization import maybe_quant_matmul as _mm
from ..parallel.mesh import build_mesh
from ..parallel.collectives import allreduce

#: the serving mesh axis name — deliberately the same axis name the
#: training dp×tp mesh uses for its tensor dimension.
TP_AXIS = "tp"


def serving_tp():
    """MXNET_SERVING_TP — read when an Engine is constructed
    (docs/ENV_VARS.md). 1/unset = single-chip."""
    env = os.environ.get("MXNET_SERVING_TP")
    return int(env) if env else 1


def tp_fallback_reason(cfg, paged, tp, devices=None):
    """Why a tp>1 request must fall back to tp=1 (None = shardable).
    Placement-only fallback: the served logits are identical either
    way."""
    if not paged:
        return ("paged path off/ineligible; the gather oracle is "
                "single-device")
    if cfg.n_experts:
        return "MoE FFN is not tp-sharded; serve dense-FFN configs"
    if cfg.n_heads % tp:
        return "n_heads %d not divisible by tp=%d" % (cfg.n_heads, tp)
    if cfg.d_ff % tp:
        return "d_ff %d not divisible by tp=%d" % (cfg.d_ff, tp)
    n = len(devices if devices is not None else jax.devices())
    if n < tp:
        return "tp=%d needs %d devices, have %d" % (tp, tp, n)
    return None


def build_tp_mesh(tp, devices=None):
    return build_mesh({TP_AXIS: tp}, devices)


def tp_cache_variant(mesh):
    """AOT-cache variant tag for one tp mesh: the tp degree plus the
    concrete device ids of the replica's window ("tp2@0,1"). Two
    replicas' tp steps trace EQUAL signatures (the sharding description
    is deliberately identity-free) but compile against different chips —
    this tag keeps their persistent cache entries apart."""
    try:
        ids = ",".join(str(d.id) for d in mesh.devices.flat)
    except Exception:                                    # pragma: no cover
        ids = "?"
    return "tp%d@%s" % (mesh.shape.get(TP_AXIS, 1), ids)


def kv_pool_spec():
    """The block pool (L, num_blocks, H, block_size, Dh) shards over the
    head axis: every chip owns H/k heads of every block, tables stay
    replicated."""
    return P(None, None, TP_AXIS, None, None)


def kv_scale_spec():
    """The int8 pool's f32 scale sidecars (L, num_blocks, H) shard on
    the same head axis as the pool (ISSUE 20): each chip holds exactly
    the scales of the heads it owns, so the quantized pool shards with
    zero cross-chip scale traffic."""
    return P(None, None, TP_AXIS)


def reorder_qkv_heads(wqkv, n_heads):
    """Rewrite a fused (D, 3D) QKV projection from qkv-major columns
    ([q all heads | k all heads | v all heads]) to HEAD-major
    ([head0: q,k,v | head1: q,k,v | ...]) so a contiguous column shard
    is exactly the q/k/v projections of H/k whole heads."""
    D = wqkv.shape[0]
    Dh = D // n_heads
    return wqkv.reshape(D, 3, n_heads, Dh).transpose(0, 2, 1, 3) \
        .reshape(D, 3 * D)


def tp_param_specs(cfg, weight_quant=False):
    """name -> PartitionSpec for the serving tp mesh (dense-FFN configs
    only; `tp_fallback_reason` gates MoE out). Matches the head-major
    wqkv layout of `reorder_qkv_heads`. With `weight_quant` the four
    matmul weights are `{"q", "s"}` dicts (quantize_tp_params): the
    int8 payload keeps the f32 spec; a column-parallel scale vector
    (per-output-channel) shards with its columns, while a row-parallel
    weight's scales are PER-CHIP (each chip quantized its own row
    shard) and ride a (tp, O) array sharded on its leading axis."""
    s = {"embed": P(), "pos_embed": P(), "head": P(),
         "lnf_g": P(), "lnf_b": P()}

    def col(spec):
        return {"q": spec, "s": P(TP_AXIS)} if weight_quant else spec

    def row(spec):
        return {"q": spec, "s": P(TP_AXIS, None)} if weight_quant \
            else spec

    for i in range(cfg.n_layers):
        pre = "layer%d_" % i
        s[pre + "ln1_g"] = P()
        s[pre + "ln1_b"] = P()
        s[pre + "wqkv"] = col(P(None, TP_AXIS))  # column parallel (heads)
        s[pre + "wo"] = row(P(TP_AXIS, None))    # row parallel
        s[pre + "ln2_g"] = P()
        s[pre + "ln2_b"] = P()
        s[pre + "w1"] = col(P(None, TP_AXIS))
        s[pre + "w2"] = row(P(TP_AXIS, None))
    return s


def place_tp_params(params, cfg, mesh):
    """Head-major-reorder the QKV projections and lay the whole params
    dict out on the mesh per `tp_param_specs`. Returns a NEW dict — the
    caller's original (replicated, qkv-major) params stay untouched as
    the single-device parity oracle."""
    out = dict(params)
    for i in range(cfg.n_layers):
        pre = "layer%d_" % i
        out[pre + "wqkv"] = reorder_qkv_heads(params[pre + "wqkv"],
                                              cfg.n_heads)
    specs = tp_param_specs(cfg)
    missing = set(out) - set(specs)
    if missing:
        raise MXNetError("tp serving: no PartitionSpec for params %r"
                         % sorted(missing))
    return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in out.items()}


def _quant_shard(w):
    """Per-output-channel symmetric int8 of one LOCAL weight shard —
    runs inside shard_map, so the amax never crosses a chip."""
    a = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)
    s = jnp.maximum(a, 1e-12) / 127.0
    q = jnp.clip(jnp.rint(w.astype(jnp.float32) / s), -127,
                 127).astype(jnp.int8)
    return q, s


def quantize_tp_params(tp_params, cfg, mesh):
    """Quantize the four matmul weights AFTER shard placement (ISSUE
    20): each chip quantizes its own shard, so scales are chip-local.
    Column-parallel weights get one scale per owned output channel
    (global (O,) sharded on tp). Row-parallel weights see only I/k rows
    per chip, so their per-output-channel amax is PER-CHIP — carried as
    a (tp, O) array sharded on its leading axis; each chip dequantizes
    its partial products with its own row before the psum, which is
    exact. Returns a new dict; norms/embeddings/head pass through."""
    out = dict(tp_params)
    def _row_quant(w):
        q, s = _quant_shard(w)
        return q, s[None]

    col_fn = jax.jit(jax.shard_map(
        _quant_shard, mesh=mesh, in_specs=(P(None, TP_AXIS),),
        out_specs=(P(None, TP_AXIS), P(TP_AXIS)), check_vma=False))
    row_fn = jax.jit(jax.shard_map(
        _row_quant, mesh=mesh, in_specs=(P(TP_AXIS, None),),
        out_specs=(P(TP_AXIS, None), P(TP_AXIS, None)),
        check_vma=False))
    for i in range(cfg.n_layers):
        pre = "layer%d_" % i
        for name, fn in (("wqkv", col_fn), ("w1", col_fn),
                         ("wo", row_fn), ("w2", row_fn)):
            q, s = fn(out[pre + name])
            out[pre + name] = {"q": q, "s": s}
    return out


# ---------------------------------------------------------------------------
# the sharded step bodies (run inside shard_map: every array is the
# per-chip LOCAL shard; heads dimension is H/k)
# ---------------------------------------------------------------------------


def _local_qkv(h, wqkv_local, Dh):
    """h (S, D) @ head-major wqkv shard -> per-head q/kk/vv (S, Hl, Dh)."""
    S = h.shape[0]
    qkv = _mm(h, wqkv_local).reshape(S, -1, 3, Dh)
    return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]


def _decode_body(params, k_pool, v_pool, tokens, positions, tables, cfg,
                 block_size, k_scale=None, v_scale=None):
    """Per-chip half of `engine._tf_decode_paged`: same contract, but
    q/k/v and the pool carry only this chip's heads and the output/FFN
    projections psum over the tp axis. The residual stream `x` is
    replicated-by-construction after every psum, so the logits (and the
    argmax) are identical on every chip. With `k_scale`/`v_scale`
    (ISSUE 20) the LOCAL head shard quantizes with its own sidecar
    slice — scales are per-head, so head-sharding them is exact."""
    from ..models.transformer import _layer_norm
    from ..ops.pallas_paged import paged_attention
    from .kv_cache import flat_slots, append_kv, write_kv_quant

    quant = k_scale is not None
    B = tokens.shape[0]
    D, H = cfg.d_model, cfg.n_heads
    Dh = D // H
    x = params["embed"][tokens] + params["pos_embed"][positions]
    slots = flat_slots(tables, positions, block_size)
    for i in range(cfg.n_layers):
        pre = "layer%d_" % i
        h = _layer_norm(x, params[pre + "ln1_g"], params[pre + "ln1_b"])
        q, kk, vv = _local_qkv(h, params[pre + "wqkv"], Dh)
        if quant:
            k_pool, v_pool, k_scale, v_scale = write_kv_quant(
                k_pool, v_pool, k_scale, v_scale, i, slots, kk, vv)
            att = paged_attention(q[:, None], k_pool[i], v_pool[i],
                                  tables, positions, block_size,
                                  k_scale=k_scale[i],
                                  v_scale=v_scale[i])[:, 0]
        else:
            k_pool, v_pool = append_kv(k_pool, v_pool, i, slots, kk, vv)
            att = paged_attention(q[:, None], k_pool[i], v_pool[i],
                                  tables, positions,
                                  block_size)[:, 0]          # (B,Hl,Dh)
        x = x + allreduce(_mm(att.reshape(B, -1), params[pre + "wo"]),
                          TP_AXIS)
        h = _layer_norm(x, params[pre + "ln2_g"], params[pre + "ln2_b"])
        x = x + allreduce(
            _mm(jax.nn.relu(_mm(h, params[pre + "w1"])),
                params[pre + "w2"]),
            TP_AXIS)
    h = _layer_norm(x, params["lnf_g"], params["lnf_b"])
    logits = (h @ params["head"]).astype(jnp.float32)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    if quant:
        return k_pool, v_pool, k_scale, v_scale, logits, nxt
    return k_pool, v_pool, logits, nxt


def _prefill_chunk_body(params, k_pool, v_pool, toks, qs, length,
                        last_idx, table_row, cfg, block_size,
                        k_scale=None, v_scale=None):
    """Per-chip half of `engine._tf_prefill_chunk` (one fixed-shape
    chunk of ONE sequence): identical null-block padding semantics, this
    chip's heads only, psum on the two output projections."""
    from ..models.transformer import _layer_norm
    from ..ops.pallas_paged import paged_attention
    from .kv_cache import write_kv, write_kv_quant

    quant = k_scale is not None
    C = toks.shape[0]
    D, H = cfg.d_model, cfg.n_heads
    Dh = D // H
    pos = qs + jnp.arange(C)
    x = params["embed"][toks] + params["pos_embed"][pos]
    slots = jnp.take(table_row, pos // block_size) * block_size \
        + pos % block_size
    slots = jnp.where(pos < length, slots, pos % block_size)   # null blk
    tables = table_row[None]
    qs_row = jnp.reshape(qs, (1,)).astype(jnp.int32)
    ncand = (C - 1) // block_size + 2
    for i in range(cfg.n_layers):
        pre = "layer%d_" % i
        h = _layer_norm(x, params[pre + "ln1_g"], params[pre + "ln1_b"])
        q, kk, vv = _local_qkv(h, params[pre + "wqkv"], Dh)
        if quant:
            k_pool, v_pool, k_scale, v_scale = write_kv_quant(
                k_pool, v_pool, k_scale, v_scale, i, slots, kk, vv,
                ncand=ncand)
            att = paged_attention(q[None], k_pool[i], v_pool[i],
                                  tables, qs_row, block_size,
                                  k_scale=k_scale[i],
                                  v_scale=v_scale[i])[0]
        else:
            k_pool, v_pool = write_kv(k_pool, v_pool, i, slots, kk, vv,
                                      ncand=ncand)
            att = paged_attention(q[None], k_pool[i], v_pool[i], tables,
                                  qs_row, block_size)[0]      # (C,Hl,Dh)
        x = x + allreduce(_mm(att.reshape(C, -1), params[pre + "wo"]),
                          TP_AXIS)
        h = _layer_norm(x, params[pre + "ln2_g"], params[pre + "ln2_b"])
        x = x + allreduce(
            _mm(jax.nn.relu(_mm(h, params[pre + "w1"])),
                params[pre + "w2"]),
            TP_AXIS)
    h_last = _layer_norm(x[last_idx], params["lnf_g"], params["lnf_b"])
    logits = (h_last @ params["head"]).astype(jnp.float32)
    if quant:
        return k_pool, v_pool, k_scale, v_scale, logits
    return k_pool, v_pool, logits


def _spec_score_body(params, k_pool, v_pool, toks, q_starts, counts,
                     tables, cfg, block_size, k_scale=None,
                     v_scale=None):
    """Per-chip half of `engine._tf_spec_score` (the speculative k+1
    scoring pass): same position/null-block semantics, this chip's
    heads only, psum on the two output projections. The residual stream
    stays replicated after every psum, so every chip computes identical
    (B, C, V) logits — greedy verification on the host sees the same
    argmaxes whether the target is sharded or not (placement, never
    logits)."""
    from ..models.transformer import _layer_norm
    from ..ops.pallas_paged import paged_attention
    from .kv_cache import write_kv, write_kv_quant

    quant = k_scale is not None
    B, C = toks.shape
    D, H = cfg.d_model, cfg.n_heads
    Dh = D // H
    w = tables.shape[1]
    pos = q_starts[:, None] + jnp.arange(C)[None, :]
    valid = jnp.arange(C)[None, :] < counts[:, None]
    pe = jnp.minimum(pos, cfg.max_len - 1)
    x = params["embed"][toks] + params["pos_embed"][pe]        # (B,C,D)
    blk = jnp.minimum(pos // block_size, w - 1)
    slots = jnp.take_along_axis(tables, blk, axis=1) * block_size \
        + pos % block_size
    slots = jnp.where(valid, slots, pos % block_size)          # null blk
    flat = slots.reshape(B * C)
    ncand = min(B * ((C - 1) // block_size + 2), B * C)
    for i in range(cfg.n_layers):
        pre = "layer%d_" % i
        h = _layer_norm(x, params[pre + "ln1_g"], params[pre + "ln1_b"])
        q, kk, vv = _local_qkv(h.reshape(B * C, D),
                               params[pre + "wqkv"], Dh)
        if quant:
            k_pool, v_pool, k_scale, v_scale = write_kv_quant(
                k_pool, v_pool, k_scale, v_scale, i, flat, kk, vv,
                ncand=ncand)
            att = paged_attention(q.reshape(B, C, -1, Dh), k_pool[i],
                                  v_pool[i], tables,
                                  q_starts.astype(jnp.int32),
                                  block_size, k_scale=k_scale[i],
                                  v_scale=v_scale[i])
        else:
            k_pool, v_pool = write_kv(k_pool, v_pool, i, flat, kk, vv,
                                      ncand=ncand)
            att = paged_attention(q.reshape(B, C, -1, Dh), k_pool[i],
                                  v_pool[i], tables,
                                  q_starts.astype(jnp.int32),
                                  block_size)                  # (B,C,Hl,Dh)
        x = x + allreduce(_mm(att.reshape(B, C, -1), params[pre + "wo"]),
                          TP_AXIS)
        h = _layer_norm(x, params[pre + "ln2_g"], params[pre + "ln2_b"])
        x = x + allreduce(
            _mm(jax.nn.relu(_mm(h, params[pre + "w1"])),
                params[pre + "w2"]),
            TP_AXIS)
    h = _layer_norm(x, params["lnf_g"], params["lnf_b"])
    logits = (h @ params["head"]).astype(jnp.float32)          # (B,C,V)
    if quant:
        return k_pool, v_pool, k_scale, v_scale, logits
    return k_pool, v_pool, logits


def _tp_step(fn, mesh, in_specs, out_specs):
    """jit(shard_map(fn)) that CONSUMES its pools, like the single-device
    steps (engine `_step_jit`): every argument laid out as a pool or a
    scale sidecar is donated, so each chip updates its shard in place."""
    pools = (kv_pool_spec(), kv_scale_spec())
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False),
        donate_argnums=tuple(i for i, spec in enumerate(in_specs)
                             if spec in pools))


def build_tp_decode(cfg, block_size, mesh, kv_quant=False,
                    weight_quant=False):
    """jit(shard_map(decode)) over the tp mesh. Signature matches the
    single-device `_decode_paged_jit`: (params, k, v, tokens, positions,
    tables) -> (k, v, logits, next); with `kv_quant` the scale sidecars
    ride along at the end of both tuples (matching the `_q` jits)."""
    specs = tp_param_specs(cfg, weight_quant)
    pool = kv_pool_spec()
    sc = kv_scale_spec()

    if kv_quant:
        def serving_decode_tp_q8(params, k, v, toks, pos, tabs, ks, vs):
            return _decode_body(params, k, v, toks, pos, tabs, cfg,
                                block_size, k_scale=ks, v_scale=vs)

        return _tp_step(
            serving_decode_tp_q8, mesh,
            in_specs=(specs, pool, pool, P(None), P(None),
                      P(None, None), sc, sc),
            out_specs=(pool, pool, sc, sc, P(None, None), P(None)))

    def serving_decode_tp(params, k, v, toks, pos, tabs):
        return _decode_body(params, k, v, toks, pos, tabs, cfg,
                            block_size)

    return _tp_step(
        serving_decode_tp, mesh,
        in_specs=(specs, pool, pool, P(None), P(None), P(None, None)),
        out_specs=(pool, pool, P(None, None), P(None)))


def build_tp_prefill_chunk(cfg, block_size, mesh, kv_quant=False,
                           weight_quant=False):
    """jit(shard_map(prefill_chunk)) over the tp mesh. Signature matches
    the single-device `_prefill_chunk_jit`: (params, k, v, toks, qs,
    length, last_idx, table_row) -> (k, v, logits)."""
    specs = tp_param_specs(cfg, weight_quant)
    pool = kv_pool_spec()
    sc = kv_scale_spec()

    if kv_quant:
        def serving_prefill_chunk_tp_q8(params, k, v, toks, qs, length,
                                        last_idx, table_row, ks, vs):
            return _prefill_chunk_body(params, k, v, toks, qs, length,
                                       last_idx, table_row, cfg,
                                       block_size, k_scale=ks,
                                       v_scale=vs)

        return _tp_step(
            serving_prefill_chunk_tp_q8, mesh,
            in_specs=(specs, pool, pool, P(None), P(), P(), P(),
                      P(None), sc, sc),
            out_specs=(pool, pool, sc, sc, P(None)))

    def serving_prefill_chunk_tp(params, k, v, toks, qs, length, last_idx,
                                 table_row):
        return _prefill_chunk_body(params, k, v, toks, qs, length,
                                   last_idx, table_row, cfg, block_size)

    return _tp_step(
        serving_prefill_chunk_tp, mesh,
        in_specs=(specs, pool, pool, P(None), P(), P(), P(), P(None)),
        out_specs=(pool, pool, P(None)))


def build_tp_spec_score(cfg, block_size, mesh, kv_quant=False,
                        weight_quant=False):
    """jit(shard_map(spec_score)) over the tp mesh. Signature matches
    the single-device `_spec_score_jit`: (params, k, v, tokens,
    q_starts, counts, tables) -> (k, v, logits (B, C, V))."""
    specs = tp_param_specs(cfg, weight_quant)
    pool = kv_pool_spec()
    sc = kv_scale_spec()

    if kv_quant:
        def serving_spec_score_tp_q8(params, k, v, toks, qs, counts, tabs,
                                     ks, vs):
            return _spec_score_body(params, k, v, toks, qs, counts,
                                    tabs, cfg, block_size, k_scale=ks,
                                    v_scale=vs)

        return _tp_step(
            serving_spec_score_tp_q8, mesh,
            in_specs=(specs, pool, pool, P(None, None), P(None),
                      P(None), P(None, None), sc, sc),
            out_specs=(pool, pool, sc, sc, P(None, None, None)))

    def serving_spec_score_tp(params, k, v, toks, qs, counts, tabs):
        return _spec_score_body(params, k, v, toks, qs, counts, tabs,
                                cfg, block_size)

    return _tp_step(
        serving_spec_score_tp, mesh,
        in_specs=(specs, pool, pool, P(None, None), P(None), P(None),
                  P(None, None)),
        out_specs=(pool, pool, P(None, None, None)))
