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
    return "tp%d@%s" % (mesh.shape.get(TP_AXIS, 1),
                        ",".join(str(d.id) for d in mesh.devices.flat))


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
# the sharded steps: engine.py's step functions run inside shard_map, where
# every array is the per-chip LOCAL shard and the heads dimension is H/k
# ---------------------------------------------------------------------------


class HeadShard:
    """One chip's share of the layer (models/transformer.py `block`, the
    tensor-parallel answer beside `OneChip`): q/k/v and the pool carry
    only this chip's heads, `wqkv`'s column shard is head-major
    (`reorder_qkv_heads`), and the two row-parallel products (`wo`,
    `w2`) are partial sums that a psum over the tp axis closes. The
    residual stream is replicated by construction after every psum, so
    the logits (and the argmax) are identical on every chip; per-head
    int8 scales shard with the heads, so a quantized pool stays exact."""

    @staticmethod
    def heads(qkv, head_dim):
        """(..., Hl * 3 * head_dim) head-major -> q, k, v (N, Hl, head_dim)."""
        qkv = qkv.reshape(-1, qkv.shape[-1] // (3 * head_dim), 3, head_dim)
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

    @staticmethod
    def close(y):
        return allreduce(y, TP_AXIS)


def shard_step(fn, mesh, param_specs, n_pools, n_args, n_results):
    """`fn(params, *pools, *args) -> (*pools, *results)` as one program
    over the tp mesh: the parameters laid out by `param_specs`, the
    `n_pools` leading arrays as pools (and, past the second, scale
    sidecars), everything else replicated. The caller jits it and
    donates the pools (engine `_program`), so each chip updates its
    shard in place."""
    pools = (kv_pool_spec(),) * 2 + (kv_scale_spec(),) * (n_pools - 2)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(param_specs, *pools, *(P(),) * n_args),
        out_specs=(*pools, *(P(),) * n_results), check_vma=False)
