"""Window-and-full attention, grouped-query and gated, over the dropless
sparse-expert layer.

The third language-model family. Its expert layer is NOT its own: the
sigmoid router with a selection bias (one group, so the top-k of all
experts), the grouped held experts (one grouped-product kernel a layer on
the chip in bf16, a loop of passes elsewhere), the shared expert and the
share of an expert-parallel deployment (`experts_held`) are `route`,
`grouped_experts` and `moe_ffn` of `models/latent_moe.py`, as are
`rms_norm`, `swiglu` and `apply_rope`. What is here is the block around
it, as the published `afmoe` model (Arcee Trinity) computes it:

    x0 = embed[token] * sqrt(d_model)                     (`scale_embed`)
    a  = rms(x)
    q  = rms_head(a Wq)   H heads     k = rms_head(a Wk)   Hkv heads
    v  = a Wv   Hkv heads             (a norm over each head's width)
    a "window" layer turns q and k by their positions (rotate-half over
    the whole head); a "full" layer knows no position at all
    o  = attention(q, k, v): query head h reads head h // (H / Hkv); key
         j is seen by query t iff j <= t, and on a window layer iff also
         t - j < window
    o  = o * sigmoid(a Wg) ;  x = x + rms(o Wo)            (sandwich norm)
    m  = rms(x) ;  f = swiglu(m) or the expert layer(m) ;  x = x + rms(f)
    logits = rms(x) W_head

No bias anywhere. The layer is written ONCE (`block`) over a cache view
that says where attention writes its keys and values and how it reads
them: `view.attend(layer, q, k, v)`, heads (N, H, Dh) and (N, Hkv, Dh)
in, (N, H, Dh) out. Here is the view with no cache, `DenseView`; the
views over the paged pool of two kinds are the serving engine's
(`serving/kv_cache.py`), and the step functions over them are in
`serving/afmoe_lm.py`.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from .latent_moe import apply_rope, moe_ffn, rms_norm, swiglu


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab: int = 256
    d_model: int = 48
    n_heads: int = 6
    n_kv_heads: int = 2
    head_dim: int = 8
    n_layers: int = 5
    n_dense_layers: int = 1        # leading layers with a dense SwiGLU
    #: "window" or "full", a layer
    layer_kinds: tuple = ("window", "window", "full", "window", "window")
    window: int = 16
    d_ff: int = 96                 # the dense layers' SwiGLU width
    d_expert: int = 24             # every expert's SwiGLU width
    n_shared: int = 1
    n_experts: int = 8             # routed experts of the DEPLOYMENT
    top_k: int = 4
    route_scale: float = 2.448
    experts_held: tuple = (0, 8)   # [lo, hi) of them held on this chip
    rope_base: float = 10000.0
    norm_eps: float = 1e-5
    scale_embed: bool = True
    max_len: int = 128
    dtype: object = jnp.float32

    #: the router's groups (`latent_moe.route`): one, so every expert is
    #: a candidate
    n_groups = 1
    top_groups = 1

    @property
    def n_held(self):
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_moe_layers(self):
        return self.n_layers - self.n_dense_layers


def param_shapes(cfg):
    """{name: shape} of every matrix, {name: shape} of every gain."""
    D, H, K, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    mats = {"embed": (cfg.vocab, D), "head": (D, cfg.vocab)}
    gains = {"normf_g": (D,)}
    for i in range(cfg.n_layers):
        pre = "layer%d_" % i
        mats.update({pre + "wq": (D, H * Dh), pre + "wk": (D, K * Dh),
                     pre + "wv": (D, K * Dh), pre + "wg": (D, H * Dh),
                     pre + "wo": (H * Dh, D)})
        gains.update({pre + "q_norm_g": (Dh,), pre + "k_norm_g": (Dh,)})
        gains.update({pre + n: (D,) for n in (
            "norm_in_g", "norm_post_attn_g", "norm_pre_mlp_g",
            "norm_post_mlp_g")})
        if i < cfg.n_dense_layers:
            mats.update({pre + "w_gate": (D, cfg.d_ff),
                         pre + "w_up": (D, cfg.d_ff),
                         pre + "w_down": (cfg.d_ff, D)})
        else:
            ds, ne, de = cfg.n_shared * cfg.d_expert, cfg.n_held, cfg.d_expert
            mats.update({pre + "router": (D, cfg.n_experts),
                         pre + "ws_gate": (D, ds), pre + "ws_up": (D, ds),
                         pre + "ws_down": (ds, D),
                         pre + "we_gate": (ne, D, de),
                         pre + "we_up": (ne, D, de),
                         pre + "we_down": (ne, de, D)})
    return mats, gains


def init_afmoe_params(rng, cfg):
    """Flat dict name -> array: N(0, 0.02) matrices, gains N(1, 0.1), a
    selection bias N(0, 0.01) in float32 (not zero, so that dropping it
    shows)."""
    mats, gains = param_shapes(cfg)
    keys = iter(jax.random.split(rng, len(mats) + len(gains) + cfg.n_layers))
    p = {n: (0.02 * jax.random.normal(next(keys), s)).astype(cfg.dtype)
         for n, s in sorted(mats.items())}
    p.update((n, (1.0 + 0.1 * jax.random.normal(next(keys), s))
              .astype(cfg.dtype)) for n, s in sorted(gains.items()))
    for i in range(cfg.n_dense_layers, cfg.n_layers):
        p["layer%d_router_bias" % i] = 0.01 * jax.random.normal(
            next(keys), (cfg.n_experts,), jnp.float32)
    return p


def rope_cos_sin(positions, cfg):
    """(N,) int positions -> cos, sin (N, head_dim) float32: plain rotary
    frequencies base^(-2i / head_dim) over the whole head."""
    dim = cfg.head_dim
    freq = cfg.rope_base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang), jnp.sin(ang)


def banded_attention(q, k, v, window=0, q_block=None):
    """Causal attention of ONE sequence over its own keys and values. q
    (S, H, Dh); k, v (S, Hkv, Dh), H a multiple of Hkv: query head h
    reads head h // (H / Hkv), contracted as the heads lie (no K or V is
    repeated). Key j is seen by query t iff j <= t and, with a `window`,
    t - j < window. A block of `q_block` queries at a time (all at once
    by default) against the keys its band holds: those up to the block's
    end and, where a window is, from `window - 1` before its first query
    on; the key blocks outside are not touched, and the largest array is
    (heads, q_block, band) float32. Scores and softmax in float32.
    Returns (S, H, Dh) in q's dtype."""
    S, H, Dh = q.shape
    Hkv = k.shape[1]
    scale = 1.0 / math.sqrt(Dh)
    q = q.reshape(S, Hkv, H // Hkv, Dh)
    out = []
    for lo in range(0, S, q_block or S):
        hi = min(lo + (q_block or S), S)
        first = max(0, lo - window + 1) if window else 0
        s = jnp.einsum("qkgd,jkd->kgqj", q[lo:hi], k[first:hi]) \
            .astype(jnp.float32) * scale
        t, j = jnp.arange(lo, hi)[:, None], jnp.arange(first, hi)[None, :]
        seen = (j <= t) & (t - j < window) if window else j <= t
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("kgqj,jkd->qkgd", p, v[first:hi].astype(p.dtype))
                   .astype(q.dtype))
    out = jnp.concatenate(out, 0) if len(out) > 1 else out[0]
    return out.reshape(S, H, Dh)


class DenseView:
    """No cache: the rows are one sequence, positions in order."""

    def __init__(self, cfg):
        self.cfg = cfg

    def attend(self, layer, q, k, v):
        window = self.cfg.window \
            if self.cfg.layer_kinds[layer] == "window" else 0
        return banded_attention(q, k, v, window)


def block(params, i, x, positions, real, cfg, view):
    """Layer i over rows x (N, D) at `positions` (N,), attention through
    `view`. Returns the rows and, for an expert layer, the pairs per
    held expert (else None)."""
    pre = "layer%d_" % i
    N, H, K, Dh = x.shape[0], cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    eps = cfg.norm_eps
    a = rms_norm(x, params[pre + "norm_in_g"], eps)
    q = rms_norm((a @ params[pre + "wq"]).reshape(N, H, Dh),
                 params[pre + "q_norm_g"], eps)
    k = rms_norm((a @ params[pre + "wk"]).reshape(N, K, Dh),
                 params[pre + "k_norm_g"], eps)
    v = (a @ params[pre + "wv"]).reshape(N, K, Dh)
    if cfg.layer_kinds[i] == "window":
        cos, sin = rope_cos_sin(positions, cfg)
        q = apply_rope(q, cos[:, None], sin[:, None])
        k = apply_rope(k, cos[:, None], sin[:, None])
    o = view.attend(i, q, k, v).reshape(N, H * Dh).astype(x.dtype)
    o = o * jax.nn.sigmoid(a @ params[pre + "wg"])
    x = x + rms_norm(o @ params[pre + "wo"],
                     params[pre + "norm_post_attn_g"], eps)
    m = rms_norm(x, params[pre + "norm_pre_mlp_g"], eps)
    counts = None
    if i < cfg.n_dense_layers:
        f = swiglu(m, params[pre + "w_gate"], params[pre + "w_up"],
                   params[pre + "w_down"])
    else:
        f, counts = moe_ffn(params, pre, m, real, cfg)
    return x + rms_norm(f, params[pre + "norm_post_mlp_g"], eps), counts


def trunk(params, tokens, positions, real, cfg, view):
    """Embedding and every layer: rows (N, D) and the pairs per (expert
    layer, held expert)."""
    x = params["embed"][tokens]
    if cfg.scale_embed:
        x = (x * math.sqrt(cfg.d_model)).astype(x.dtype)
    counts = []
    for i in range(cfg.n_layers):
        x, c = block(params, i, x, positions, real, cfg, view)
        if c is not None:
            counts.append(c)
    counts = jnp.stack(counts) if counts \
        else jnp.zeros((0, cfg.n_held), jnp.int32)
    return x, counts


def logits_of(params, x, cfg):
    h = rms_norm(x, params["normf_g"], cfg.norm_eps)
    return (h @ params["head"]).astype(jnp.float32)


def afmoe_apply(params, tokens, cfg, length=None):
    """The dense forward of one sequence, no cache: tokens (S,) -> logits
    (S, vocab) float32 and the pairs per (expert layer, held expert)
    over the first `length` positions (all, by default)."""
    S = tokens.shape[0]
    positions = jnp.arange(S, dtype=jnp.int32)
    real = positions < (S if length is None else length)
    x, counts = trunk(params, tokens, positions, real, cfg, DenseView(cfg))
    return logits_of(params, x, cfg), counts
