"""Transformer language model, built mesh-first.

Capability upgrade over the reference (which predates transformers — its
sequence stack is fused RNNs + bucketing, SURVEY §5.7). This model is the
showcase for the framework's parallelism axes:

  dp  batch sharding (GSPMD inserts the gradient psum)
  tp  Megatron-style sharded attention heads + FFN (column→row parallel)
  sp  ring attention over the sequence axis (parallel/ring_attention.py)
  ep  expert-parallel mixture-of-experts FFN (gate-weighted dense dispatch;
      expert weights sharded over 'ep', GSPMD inserts the all_to_all-
      equivalent collectives)

The model is functional (params dict + pure apply) — the idiomatic form for
pjit over a Mesh; the Gluon API remains the imperative front door for the
reference's own model families.
"""
from __future__ import annotations

import dataclasses
import functools
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.quantization import maybe_quant_matmul as _mm


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 128
    n_experts: int = 0          # 0 => dense FFN; >0 => MoE
    moe_top_k: int = 0          # 0 => dense dispatch; >0 => top-k routing
    capacity_factor: float = 1.25  # per-expert buffer over the even share
    moe_group_size: int = 4096  # GShard token grouping; <=0 => one group
    max_len: int = 128
    dtype: object = jnp.float32


def init_transformer_params(rng, cfg):
    """Returns a flat dict name -> array."""
    keys = iter(jax.random.split(rng, 4 + 5 * cfg.n_layers))
    scale = 0.02
    p = {}

    def w(shape):
        return (scale * jax.random.normal(next(keys), shape)).astype(
            cfg.dtype)

    p["embed"] = w((cfg.vocab, cfg.d_model))
    p["pos_embed"] = w((cfg.max_len, cfg.d_model))
    for i in range(cfg.n_layers):
        pre = "layer%d_" % i
        p[pre + "ln1_g"] = jnp.ones((cfg.d_model,), cfg.dtype)
        p[pre + "ln1_b"] = jnp.zeros((cfg.d_model,), cfg.dtype)
        p[pre + "wqkv"] = w((cfg.d_model, 3 * cfg.d_model))
        p[pre + "wo"] = w((cfg.d_model, cfg.d_model))
        p[pre + "ln2_g"] = jnp.ones((cfg.d_model,), cfg.dtype)
        p[pre + "ln2_b"] = jnp.zeros((cfg.d_model,), cfg.dtype)
        if cfg.n_experts:
            p[pre + "wg"] = w((cfg.d_model, cfg.n_experts))
            p[pre + "w1"] = w((cfg.n_experts, cfg.d_model, cfg.d_ff))
            p[pre + "w2"] = w((cfg.n_experts, cfg.d_ff, cfg.d_model))
        else:
            p[pre + "w1"] = w((cfg.d_model, cfg.d_ff))
            p[pre + "w2"] = w((cfg.d_ff, cfg.d_model))
    p["lnf_g"] = jnp.ones((cfg.d_model,), cfg.dtype)
    p["lnf_b"] = jnp.zeros((cfg.d_model,), cfg.dtype)
    p["head"] = w((cfg.d_model, cfg.vocab))
    return p


def transformer_shardings(cfg):
    """name -> PartitionSpec over mesh axes ('tp', 'ep'); everything else
    replicated (batch/sequence sharding is on the activations)."""
    s = {"embed": P(), "pos_embed": P(), "head": P(None, "tp"),
         "lnf_g": P(), "lnf_b": P()}
    for i in range(cfg.n_layers):
        pre = "layer%d_" % i
        s[pre + "ln1_g"] = P()
        s[pre + "ln1_b"] = P()
        s[pre + "wqkv"] = P(None, "tp")   # column parallel
        s[pre + "wo"] = P("tp", None)     # row parallel
        s[pre + "ln2_g"] = P()
        s[pre + "ln2_b"] = P()
        if cfg.n_experts:
            s[pre + "wg"] = P()
            s[pre + "w1"] = P("ep", None, "tp")
            s[pre + "w2"] = P("ep", "tp", None)
        else:
            s[pre + "w1"] = P(None, "tp")
            s[pre + "w2"] = P("tp", None)
    return s


def _layer_norm(x, g, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _attention(x, wqkv, wo, cfg, mesh=None, sp_axis="sp", causal=True):
    B, S, D = x.shape
    H = cfg.n_heads
    qkv = x @ wqkv                      # (B, S, 3D)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):  # (B, S, D) -> (B, H, S, Dh)
        return t.reshape(B, S, H, D // H).transpose(0, 2, 1, 3)

    q, k, v = heads(q), heads(k), heads(v)
    if mesh is not None and sp_axis in mesh.shape and \
            mesh.shape[sp_axis] > 1:
        from ..parallel.ring_attention import ring_attention_sharded
        out = ring_attention_sharded(mesh, q, k, v, axis_name=sp_axis,
                                     causal=causal)
    elif mesh is None and \
            os.environ.get("MXNET_FLASH_ATTENTION", "0") == "1":
        # OPT-IN Pallas path: the 2026-07-31 v5e sweep (BENCH_FLASH_SWEEP
        # .jsonl) measured 0.96-1.06x vs XLA attention at seq 1024/2048/
        # 4096 — below the >=1.2x bar for a default-path kernel, so XLA
        # attention is the default and MXNET_FLASH_ATTENTION=1 enables the
        # kernel (VMEM-streamed online softmax; falls back to XLA when
        # shapes don't tile into the blocks). Single-device only: a
        # pallas_call has no GSPMD partitioning rule, so under a dp/tp
        # mesh it would force replication — the sharded paths go through
        # ring attention / the partitionable XLA reference instead
        from ..ops.pallas_attention import flash_attention
        out = flash_attention(q, k, v, causal=causal)
    else:
        from ..parallel.ring_attention import attention_reference
        out = attention_reference(q, k, v, causal=causal)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, D)
    return out @ wo


def _moe_ffn(x, wg, w1, w2):
    """Gate-weighted dense-dispatch MoE; expert dim sharded over 'ep' by
    GSPMD. Every expert sees every token, outputs weighted by the full
    softmax gate — the exact function _moe_ffn_topk approximates (and
    reproduces when k == n_experts with ample capacity)."""
    gates = jax.nn.softmax(x @ wg, axis=-1)           # (B, S, E)
    h = jnp.einsum("bsd,edf->besf", x, w1)
    h = jax.nn.relu(h)
    y = jnp.einsum("besf,efd->besd", h, w2)
    return jnp.einsum("bse,besd->bsd", gates, y)


class OneChip:
    """How `block` is laid over chips, when it is not: the fused `wqkv`
    product is three contiguous thirds (all heads' q, then k, then v)
    and the two products whose inputs a tensor-parallel mesh would split
    (`wo`, `w2`) are whole as they come. `serving/tp.py` holds the other
    answer, one chip's share of the heads."""

    @staticmethod
    def heads(qkv, head_dim):
        """(..., 3 * H * head_dim) -> q, k, v, each (N, H, head_dim)."""
        return tuple(t.reshape(-1, t.shape[-1] // head_dim, head_dim)
                     for t in jnp.split(qkv, 3, axis=-1))

    @staticmethod
    def close(y):
        return y


def block(params, i, x, cfg, view, shard=OneChip):
    """Layer i of the serving forward over rows x (..., D), N of them
    under any leading axes: pre-LayerNorm attention through `view` and a
    ReLU feed-forward (dense, or the dense-dispatch experts), each added
    to the residual. Every step program of the paged engine
    (serving/engine.py) is this layer; what a row is (a prompt's
    position, a sequence's newest token, a chunk's or a speculative
    pass's position) and where its keys and values are kept and read is
    the view's: `view.attend(layer, q, k, v)` takes the rows' heads
    (N, H, Dh) and returns their attention (N, H, Dh). Matrices may be
    int8 `{"q", "s"}` pairs (`_mm`). Every map but attention is a row's
    own, so padded rows cannot perturb real ones."""
    pre = "layer%d_" % i
    h = _layer_norm(x, params[pre + "ln1_g"], params[pre + "ln1_b"])
    q, k, v = shard.heads(_mm(h, params[pre + "wqkv"]),
                          cfg.d_model // cfg.n_heads)
    att = view.attend(i, q, k, v)
    x = x + shard.close(_mm(att.astype(x.dtype).reshape(*x.shape[:-1], -1),
                            params[pre + "wo"]))
    h = _layer_norm(x, params[pre + "ln2_g"], params[pre + "ln2_b"])
    if cfg.n_experts:
        rows = h.reshape(1, -1, h.shape[-1])
        return x + _moe_ffn(rows, params[pre + "wg"], params[pre + "w1"],
                            params[pre + "w2"]).reshape(h.shape)
    return x + shard.close(_mm(jax.nn.relu(_mm(h, params[pre + "w1"])),
                               params[pre + "w2"]))


def _route_group_topk(xg, wg, w1, w2, k, capacity):
    """Route ONE token group (Tg, D) through top-k capacity-bounded
    experts; returns (out (Tg, D), aux scalar). Static shapes, einsums
    over one-hot masks only — no dynamic-extent gather/scatter, so the
    expert dim shards over 'ep' and dispatch/combine lower to
    all-to-alls under GSPMD."""
    Tg, D = xg.shape
    E = w1.shape[0]
    gates = jax.nn.softmax(xg @ wg, axis=-1)              # (Tg, E)
    topv, topi = jax.lax.top_k(gates, k)                  # (Tg, k)
    # renormalize over the selected experts
    topv = topv / jnp.maximum(jnp.sum(topv, -1, keepdims=True), 1e-9)

    # routing bookkeeping in int32: under bf16 activations a float
    # cumsum of token counts goes inexact past 256 and capacity slots
    # would silently collide — only the masks cast to xg.dtype, at the
    # einsum boundary
    sel_i = jax.nn.one_hot(topi, E, dtype=jnp.int32)      # (Tg, k, E)
    # position of each (token, choice) within its expert's buffer:
    # cumulative count of prior selections of that expert, counting
    # choice slots in priority order (k=0 first, matching GShard)
    flat = sel_i.transpose(1, 0, 2).reshape(k * Tg, E)    # (k*Tg, E)
    pos_flat = jnp.cumsum(flat, axis=0) - flat            # prior count
    pos = pos_flat.reshape(k, Tg, E).transpose(1, 0, 2)   # (Tg, k, E)
    in_cap = ((pos < capacity) & (sel_i > 0)).astype(xg.dtype)  # kept
    pos_idx = jnp.sum(pos * sel_i, -1).astype(jnp.int32)  # (Tg, k)

    # dispatch mask (Tg, E, C) -> one-hot over capacity slots
    cap_hot = jax.nn.one_hot(pos_idx, capacity, dtype=xg.dtype)  # (Tg,k,C)
    dispatch = jnp.einsum("tke,tkc->tec", in_cap, cap_hot)       # (Tg,E,C)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, xg)          # (E,C,D)

    h = jax.nn.relu(jnp.einsum("ecd,edf->ecf", expert_in, w1))
    expert_out = jnp.einsum("ecf,efd->ecd", h, w2)               # (E,C,D)

    combine = jnp.einsum("tke,tk,tkc->tec", in_cap, topv, cap_hot)
    out = jnp.einsum("tec,ecd->td", combine, expert_out)

    # Switch/GShard load-balancing auxiliary: E * sum_e f_e * P_e, where
    # f_e = fraction of tokens whose TOP choice is expert e (hard count)
    # and P_e = mean softmax gate mass on e. Minimized at uniform
    # routing (value 1); without it top-k training collapses experts.
    f = jnp.mean(sel_i[:, 0, :].astype(jnp.float32), axis=0)     # (E,)
    p = jnp.mean(gates.astype(jnp.float32), axis=0)              # (E,)
    aux = E * jnp.sum(f * p)
    return out, aux


def _moe_groups(tokens, group_size):
    """Number of routing groups: smallest G dividing `tokens` with
    tokens/G <= group_size (G=1 when tokens already fit). The divisor
    hunt is bounded to 2x the ideal count — for prime-ish token counts
    it would otherwise degenerate to per-token groups (capacity == k,
    aux loss meaningless); such counts fall back to a single group."""
    if group_size <= 0 or tokens <= group_size:
        return 1
    ideal = (tokens + group_size - 1) // group_size
    for g in range(ideal, min(2 * ideal, tokens) + 1):
        if tokens % g == 0:
            return g
    return 1

def _moe_ffn_topk(x, wg, w1, w2, k, capacity_factor=1.25,
                  group_size=4096):
    """Top-k sparse-dispatch MoE (Switch/GShard style) with static
    shapes throughout — XLA/GSPMD friendly.

    GShard-style token grouping: the B*S tokens are split into G
    independent routing groups of Tg = B*S/G tokens (smallest G with
    Tg <= group_size), each with its own capacity
    C = ceil(capacity_factor * Tg * k / E). The dispatch/combine
    one-hot masks are (Tg, E, C) per group — O(T * E * C_group) total
    instead of the single-group O(T^2 * k * cf / E) blowup (at
    T = 8192, E = 8, k = 2 a single group's f32 dispatch tensor alone
    is ~2.7 GB; grouped at 4096 it is 2 x ~0.7 GB and scales linearly
    in T from there). Per token: softmax gate over E experts, keep the
    top k; overflow tokens past an expert's capacity drop to the
    residual path (the standard capacity trade). Combine weights are
    renormalized over the kept experts. The aux loss is the mean of the
    per-group Switch/GShard load-balancing terms.

    Reference seam: the reference's sparse embedding/expert flows ride
    row_sparse KVStore pulls (reference python/mxnet/kvstore.py
    row_sparse_pull); here routing is part of the one compiled step.
    """
    B, S, D = x.shape
    E = w1.shape[0]
    tokens = B * S
    G = _moe_groups(tokens, group_size)
    tg = tokens // G
    capacity = max(int(np.ceil(capacity_factor * tg * k / E)), k)

    xg = x.reshape(G, tg, D)
    out, aux = jax.vmap(
        lambda g: _route_group_topk(g, wg, w1, w2, k, capacity))(xg)
    return out.reshape(B, S, D), jnp.mean(aux)


def transformer_apply(params, tokens, cfg, mesh=None, causal=True,
                      return_aux=False):
    """tokens: (B, S) int32 -> logits (B, S, vocab).

    With return_aux=True also returns the summed MoE load-balancing
    auxiliary (0.0 for dense-dispatch / non-MoE configs)."""
    B, S = tokens.shape
    aux_total = jnp.float32(0.0)
    x = params["embed"][tokens] + params["pos_embed"][:S][None]
    for i in range(cfg.n_layers):
        pre = "layer%d_" % i
        h = _layer_norm(x, params[pre + "ln1_g"], params[pre + "ln1_b"])
        x = x + _attention(h, params[pre + "wqkv"], params[pre + "wo"],
                           cfg, mesh=mesh, causal=causal)
        h = _layer_norm(x, params[pre + "ln2_g"], params[pre + "ln2_b"])
        if cfg.n_experts and cfg.moe_top_k:
            moe_out, aux = _moe_ffn_topk(h, params[pre + "wg"],
                                         params[pre + "w1"],
                                         params[pre + "w2"],
                                         cfg.moe_top_k,
                                         cfg.capacity_factor,
                                         cfg.moe_group_size)
            x = x + moe_out
            aux_total = aux_total + aux
        elif cfg.n_experts:
            x = x + _moe_ffn(h, params[pre + "wg"], params[pre + "w1"],
                             params[pre + "w2"])
        else:
            x = x + jax.nn.relu(h @ params[pre + "w1"]) @ params[pre + "w2"]
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"])
    logits = x @ params["head"]
    if return_aux:
        return logits, aux_total
    return logits


def lm_loss(params, tokens, cfg, mesh=None, aux_coef=0.01):
    """Next-token cross entropy. Runs attention on the full (sp-shardable)
    sequence and shifts in loss space, so the sequence axis stays divisible
    by the 'sp' mesh axis. Top-k MoE configs add the load-balancing
    auxiliary (Switch-style, coefficient `aux_coef`)."""
    logits, aux = transformer_apply(params, tokens, cfg, mesh=mesh,
                                    return_aux=True)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp[:, :-1],
                             tokens[:, 1:][..., None], axis=-1)[..., 0]
    return -jnp.mean(ll) + aux_coef * aux


def make_train_step(mesh, cfg, lr=0.1, seed=0):
    """Build (step_fn, params) with params placed per transformer_shardings
    and the batch sharded over ('dp', 'sp'). step_fn is jitted with donated
    params; GSPMD inserts every collective (grad psum over dp, activation
    all_gathers for tp, expert collectives for ep; ring attention's
    ppermutes come from the explicit shard_map)."""
    params = init_transformer_params(jax.random.PRNGKey(seed), cfg)
    shardings = transformer_shardings(cfg)
    params = {k: jax.device_put(v, NamedSharding(mesh, shardings[k]))
              for k, v in params.items()}

    batch_spec = P("dp", "sp") if "sp" in mesh.shape else P("dp")

    @functools.partial(jax.jit, donate_argnums=0)
    def step(params, tokens):
        loss, grads = jax.value_and_grad(lm_loss)(params, tokens, cfg,
                                                  mesh=mesh)
        new_params = {k: v - lr * grads[k] for k, v in params.items()}
        return new_params, loss

    def run(params, tokens_np):
        tokens = jax.device_put(jnp.asarray(tokens_np, jnp.int32),
                                NamedSharding(mesh, batch_spec))
        return step(params, tokens)

    return run, params
