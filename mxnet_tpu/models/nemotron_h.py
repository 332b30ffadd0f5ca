"""Layers that are each ONE mixer: a state-space mixer, a routed expert
layer or a grouped-query attention, chosen by a pattern's letter.

The fifth language-model family, as the published `nemotron_h` model
(NVIDIA Nemotron-H, arXiv:2504.03624; Nemotron 3 Nano) computes it. With
`h = rms(x; norm_g)`, layer i is `x <- x + mixer_i(h)` and `mixer_i` is
named by letter i of `pattern` (`hybrid_override_pattern`); a last
RMSNorm, then the untied head; the embedding is not scaled:

    M  the Mamba-2 mixer of models/falcon_h1.py, without its multipliers:
         z | xBC | dt = h W_in;  xBC <- silu(conv(xBC) + bias), causal,
         `conv_taps` taps;  x (Hs heads of P), B, C (G groups of N; head k
         reads group k // (Hs / G));  dt = softplus(dt + dt_bias)
         H_t = exp(-dt e^A_log) H_{t-1} + dt x_t (x) B_t;  y_t = H_t C_t + D x_t
         y = rms_grouped(y * silu(z); ssm_norm_g)  (gate before the norm)
         out = y W_out
    E  the dropless expert layer of models/latent_moe.py (`route`,
       `grouped_experts`, `moe_ffn`): a sigmoid router in float32 with a
       selection bias over ONE group, `top_k` winners, their scores
       normalised and scaled; every expert, and the shared one, is TWO
       matrices and a squared ReLU, `relu(h W_up)^2 W_down`; one chip of
       an expert-parallel deployment holds `experts_held` of them, and
       multiplies the pairs that chose them by ONE grouped-product kernel
       a layer on the chip in bf16 (ops/pallas_grouped_experts.py)
    *  attention: q (H heads of Dh), k, v (Hkv heads), no biases, causal
       softmax(q k^T / sqrt(Dh)), query head j on cached head j // (H /
       Hkv), o W_o. NO positions are applied: the published code turns
       neither q nor k (the state-space layers carry the order).

The layer is written ONCE (`block`) over a cache view, the K/V layout's
own (`serving/kv_cache.py` `PromptView`, `LiveGatherView`; here
`falcon_h1.DenseView`, no cache): an attention layer asks it `attend`, a
state-space layer `mix`, an expert layer nothing, so the cache's kinds
differ layer by layer (`CacheSpec.layer_kinds`: "state", "none", "full").
The step functions are in `serving/nemotron_h_lm.py`.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from . import falcon_h1
from .latent_moe import moe_ffn, rms_norm

F32 = jnp.float32

#: what a layer of each letter keeps in the cache (`CacheSpec.layer_kinds`)
CACHE_KIND = {"M": "state", "E": "none", "*": "full"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab: int = 256
    d_model: int = 32
    pattern: str = "ME*E"          # a letter a layer: M, E or *
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 8
    ssm_heads: int = 4
    ssm_head_dim: int = 8
    ssm_state: int = 16            # N: the state is (head_dim, N) a head
    ssm_groups: int = 2            # heads of one group share B and C
    conv_taps: int = 4
    chunk: int = 8                 # positions one pass of the scan takes
    d_expert: int = 32             # a routed expert's width
    d_shared: int = 64             # the shared expert's
    n_experts: int = 8             # routed experts of the DEPLOYMENT: the
                                   # router's width
    top_k: int = 2
    n_groups: int = 1              # the router's groups (`route`)
    top_groups: int = 1
    route_scale: float = 2.5
    experts_held: tuple = (0, 8)   # [lo, hi) of them held on this chip
    norm_eps: float = 1e-5
    max_len: int = 128
    dtype: object = jnp.float32
    #: what the recurrent state is kept in between tokens
    state_dtype: object = jnp.float32

    @property
    def n_layers(self):
        return len(self.pattern)

    def layers_of(self, letter):
        return tuple(i for i, c in enumerate(self.pattern) if c == letter)

    @property
    def n_held(self):
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_moe_layers(self):
        return len(self.layers_of("E"))

    @property
    def d_ssm(self):
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self):
        """x | B | C: what goes through the convolution."""
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @property
    def d_in_proj(self):
        """z | x | B | C | dt."""
        return self.d_ssm + self.conv_channels + self.ssm_heads


def param_shapes(cfg):
    """({name: shape} of every matrix, of every gain, of every float32
    vector): a layer has its norm's gain and its one mixer's leaves."""
    D, H, K, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    mats = {"embed": (cfg.vocab, D), "head": (D, cfg.vocab)}
    gains = {"normf_g": (D,)}
    vectors = {}
    for i, letter in enumerate(cfg.pattern):
        pre = "layer%d_" % i
        gains[pre + "norm_g"] = (D,)
        if letter == "M":
            mats.update({pre + "w_in": (D, cfg.d_in_proj),
                         pre + "conv_w": (cfg.conv_taps, cfg.conv_channels),
                         pre + "w_out": (cfg.d_ssm, D)})
            gains[pre + "ssm_norm_g"] = (cfg.d_ssm,)
            vectors.update({pre + "conv_b": (cfg.conv_channels,),
                            pre + "dt_bias": (cfg.ssm_heads,),
                            pre + "A_log": (cfg.ssm_heads,),
                            pre + "D": (cfg.ssm_heads,)})
        elif letter == "E":
            mats.update({pre + "router": (D, cfg.n_experts),
                         pre + "ws_up": (D, cfg.d_shared),
                         pre + "ws_down": (cfg.d_shared, D),
                         pre + "we_up": (cfg.n_held, D, cfg.d_expert),
                         pre + "we_down": (cfg.n_held, cfg.d_expert, D)})
            vectors[pre + "router_bias"] = (cfg.n_experts,)
        elif letter == "*":
            mats.update({pre + "wq": (D, H * Dh), pre + "wk": (D, K * Dh),
                         pre + "wv": (D, K * Dh), pre + "wo": (H * Dh, D)})
        else:
            raise ValueError("pattern %r: a layer is M, E or *, not %r"
                             % (cfg.pattern, letter))
    return mats, gains, vectors


def init_nemotron_h_params(rng, cfg):
    """Flat dict name -> array: N(0, 0.02) matrices and N(1, 0.1) gains in
    the weights' dtype, the router's selection bias N(0, 0.01) in float32
    (not zero, so that dropping it shows), the mixer's convolution, `A_log`, `dt_bias` and `D` as
    `falcon_h1.init_falcon_h1_params` draws them (and for its reasons)."""
    mats, gains, vectors = param_shapes(cfg)
    names = sorted(mats) + sorted(gains) + sorted(vectors)
    keys = dict(zip(names, jax.random.split(rng, len(names))))
    bound = cfg.conv_taps ** -0.5
    p = {}
    for n, s in mats.items():
        if n.endswith("conv_w"):
            p[n] = jax.random.uniform(keys[n], s, F32, -bound, bound) \
                .astype(cfg.dtype)
        else:
            p[n] = (0.02 * jax.random.normal(keys[n], s)).astype(cfg.dtype)
    for n, s in gains.items():
        p[n] = (1.0 + 0.1 * jax.random.normal(keys[n], s)).astype(cfg.dtype)
    for n, s in vectors.items():
        p[n] = 0.01 * jax.random.normal(keys[n], s, F32) \
            if n.endswith("router_bias") \
            else falcon_h1.init_mixer_vector(keys[n], n, s, bound)
    return p


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=F32)


def ssm_mixer(params, i, h, cfg, view):
    """The Mamba-2 mixer of layer i over normed rows h (N, D), through
    `view.mix`: its output before it joins the residual, float32."""
    pre = "layer%d_" % i
    z, xbc, dt = jnp.split(_dot(h, params[pre + "w_in"]),
                           [cfg.d_ssm, cfg.d_ssm + cfg.conv_channels], axis=-1)
    dt = jax.nn.softplus(dt + params[pre + "dt_bias"].astype(F32))
    y = view.mix(i, xbc.astype(h.dtype), dt,
                 falcon_h1.mixer_weights(params, i), cfg)
    y = falcon_h1.gated_group_norm(y, z, params[pre + "ssm_norm_g"], cfg)
    return _dot(y.astype(h.dtype), params[pre + "w_out"])


def attention(params, i, h, cfg, view):
    """Grouped-query attention of layer i over normed rows h, through
    `view.attend`; no positions are applied. Float32."""
    pre = "layer%d_" % i
    N, H, K, Dh = h.shape[0], cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ params[pre + "wq"]).reshape(N, H, Dh)
    k = (h @ params[pre + "wk"]).reshape(N, K, Dh)
    v = (h @ params[pre + "wv"]).reshape(N, K, Dh)
    o = view.attend(i, q, k, v).reshape(N, H * Dh).astype(h.dtype)
    return _dot(o, params[pre + "wo"])


def block(params, i, x, real, cfg, view):
    """Layer i over rows x (N, D): its norm, its ONE mixer by the
    pattern's letter, the residual (a float32 sum, rounded once). Returns
    the rows and, for an expert layer, the pairs per held expert (else
    None)."""
    pre, letter = "layer%d_" % i, cfg.pattern[i]
    h = rms_norm(x, params[pre + "norm_g"], cfg.norm_eps)
    counts = None
    if letter == "M":
        out = ssm_mixer(params, i, h, cfg, view)
    elif letter == "E":
        out, counts = moe_ffn(params, pre, h, real, cfg)
    else:
        out = attention(params, i, h, cfg, view)
    return (x.astype(F32) + out).astype(x.dtype), counts


def trunk(params, tokens, real, cfg, view):
    """Embedding and every layer: rows (N, D) and the pairs per (expert
    layer, held expert)."""
    x = params["embed"][tokens]
    counts = []
    for i in range(cfg.n_layers):
        x, c = block(params, i, x, real, cfg, view)
        if c is not None:
            counts.append(c)
    counts = jnp.stack(counts) if counts \
        else jnp.zeros((0, cfg.n_held), jnp.int32)
    return x, counts


def logits_of(params, x, cfg):
    return _dot(rms_norm(x, params["normf_g"], cfg.norm_eps), params["head"])


def nemotron_h_apply(params, tokens, cfg, length=None):
    """The dense forward of one sequence, no cache: tokens (S,) -> logits
    (S, vocab) float32 and the pairs per (expert layer, held expert) over
    the first `length` positions (all, by default)."""
    S = tokens.shape[0]
    real = jnp.arange(S) < (S if length is None else length)
    x, counts = trunk(params, tokens, real, cfg, falcon_h1.DenseView())
    return logits_of(params, x, cfg), counts
