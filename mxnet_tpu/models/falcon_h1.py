"""Attention heads and state-space heads side by side in every layer.

The fourth language-model family, as the published `falcon_h1` model
(TII Falcon-H1) computes it. One RMSNorm feeds TWO mixers in parallel,
their outputs are summed into the residual, then a SwiGLU feed-forward:

    e  = embed[token] * embedding_multiplier
    h  = rms(x)
    attention (grouped-query, rotary):
      a = h * attention_in_multiplier
      q = a Wq (H heads)   k = (a Wk) * key_multiplier (Hkv)   v = a Wv
      q, k turned by their positions (rotate-half over the whole head)
      o = causal softmax(q k^T / sqrt(Dh)) v, query head i on head i // (H/Hkv)
      out_a = (o Wo) * attention_out_multiplier
    state-space mixer (Mamba-2):
      u W_in = z | x | B | C | dt, each segment times its `ssm_multipliers`
               entry, u = h * ssm_in_multiplier
      x|B|C through a causal depthwise convolution of `conv_taps` taps
      with bias, then SiLU;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
      per head (P wide, its group's B and C of N):
        H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t      (H is P x N)
        y_t = H_t C_t + D x_t
      y = rms_grouped(y * silu(z));  out_s = (y W_out) * ssm_out_multiplier
    x  = x + out_a + out_s
    m  = rms(x)
    x  = x + ((silu((m Wg) * mlp_multipliers[0]) * (m Wu)) Wd) * mlp_multipliers[1]
    logits = (rms(x) W_head) * lm_head_multiplier

No bias on any projection. The layer is written ONCE (`block`) over a
cache view that says where attention writes its keys and values and how
it reads them (`view.attend(layer, q, k, v)`, as the other families'),
and where the mixer keeps what it carries from token to token
(`view.mix(layer, xbc, dt, w, cfg)`: the convolution's last inputs and
the state H). Here is the view with no cache, `DenseView`, and the
mixer's two forms, which the views over the paged pool
(`serving/kv_cache.py`) call too: `mix_prompt`, a whole sequence as a
CHUNKED scan in matrix form (per chunk: C B^T masked by the decay, times
x; the chunk's state from B^T x; the carried state through C), and
`mix_step`, one recurrence step a row over states the view keeps. Decay, dt and the state's update
are float32 whatever the weights' dtype, as the published kernels have
them. The step functions are in `serving/falcon_h1_lm.py`.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .afmoe import banded_attention
from .latent_moe import apply_rope, rms_norm

F32 = jnp.float32
#: lanes of a TPU tile: what decides how a state lies (`state_layout`)
LANES = 128


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    vocab: int = 256
    d_model: int = 32
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 8
    n_layers: int = 2
    d_ff: int = 64
    ssm_heads: int = 4
    ssm_head_dim: int = 8
    ssm_state: int = 16            # N: the state is (head_dim, N) a head
    ssm_groups: int = 2            # heads of one group share B and C
    conv_taps: int = 4
    chunk: int = 8                 # positions one pass of the scan takes
    rope_base: float = 1e11
    norm_eps: float = 1e-5
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    #: the segments z, x, B, C, dt of the state-space projection
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    #: the feed-forward's gate and its output
    mlp_multipliers: tuple = (1.0, 1.0)
    max_len: int = 128
    dtype: object = jnp.float32
    #: what the recurrent state is kept in between tokens
    state_dtype: object = jnp.float32

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
    vector of the mixer)."""
    D, H, K, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    mats = {"embed": (cfg.vocab, D), "head": (D, cfg.vocab)}
    gains = {"normf_g": (D,)}
    vectors = {}
    for i in range(cfg.n_layers):
        pre = "layer%d_" % i
        mats.update({pre + "wq": (D, H * Dh), pre + "wk": (D, K * Dh),
                     pre + "wv": (D, K * Dh), pre + "wo": (H * Dh, D),
                     pre + "w_in": (D, cfg.d_in_proj),
                     pre + "conv_w": (cfg.conv_taps, cfg.conv_channels),
                     pre + "w_out": (cfg.d_ssm, D),
                     pre + "w_gate": (D, cfg.d_ff), pre + "w_up": (D, cfg.d_ff),
                     pre + "w_down": (cfg.d_ff, D)})
        gains.update({pre + "norm_in_g": (D,), pre + "norm_mlp_g": (D,),
                      pre + "ssm_norm_g": (cfg.d_ssm,)})
        vectors.update({pre + "conv_b": (cfg.conv_channels,),
                        pre + "dt_bias": (cfg.ssm_heads,),
                        pre + "A_log": (cfg.ssm_heads,),
                        pre + "D": (cfg.ssm_heads,)})
    return mats, gains, vectors


def init_falcon_h1_params(rng, cfg):
    """Flat dict name -> array: N(0, 0.02) matrices and N(1, 0.1) gains in
    the weights' dtype; as the Mamba-2 code initialises them, the
    convolution's taps and (in float32) its bias uniform in +-1 /
    sqrt(conv_taps), `A_log` = log uniform(1, 16), `dt_bias` the inverse
    softplus of log-uniform(0.001, 0.1), `D` N(1, 0.1): a head then
    remembers between one and a thousand tokens, and the mixer's output
    is of the residual's size."""
    mats, gains, vectors = param_shapes(cfg)
    keys = iter(jax.random.split(rng, len(mats) + len(gains) + len(vectors)))
    bound = cfg.conv_taps ** -0.5
    p = {n: (jax.random.uniform(next(keys), s, F32, -bound, bound)
             if n.endswith("conv_w") else
             0.02 * jax.random.normal(next(keys), s)).astype(cfg.dtype)
         for n, s in sorted(mats.items())}
    p.update((n, (1.0 + 0.1 * jax.random.normal(next(keys), s))
              .astype(cfg.dtype)) for n, s in sorted(gains.items()))
    for n, s in sorted(vectors.items()):
        p[n] = init_mixer_vector(next(keys), n, s, bound)
    return p


def init_mixer_vector(key, name, shape, bound):
    """One float32 vector of a mixer by its name, as the Mamba-2 code
    draws it: `A_log`, `dt_bias`, `D`, else the convolution's bias."""
    if name.endswith("A_log"):
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if name.endswith("dt_bias"):
        dt = jnp.exp(jax.random.uniform(key, shape, F32, jnp.log(0.001),
                                        jnp.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name.endswith("D"):
        return 1.0 + 0.1 * jax.random.normal(key, shape, F32)
    return jax.random.uniform(key, shape, F32, -bound, bound)


def rope_cos_sin(positions, cfg):
    """(N,) int positions -> cos, sin (N, head_dim) float32: plain rotary
    frequencies base^(-2i / head_dim) over the whole head."""
    dim = cfg.head_dim
    freq = cfg.rope_base ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    ang = positions.astype(F32)[:, None] * freq[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang), jnp.sin(ang)


def in_proj_multipliers(cfg):
    """(d_in_proj,) float32: each column's segment's multiplier."""
    z, x, b, c, dt = cfg.ssm_multipliers
    gn = cfg.ssm_groups * cfg.ssm_state
    return jnp.concatenate([
        jnp.full((n,), m, F32) for n, m in (
            (cfg.d_ssm, z), (cfg.d_ssm, x), (gn, b), (gn, c),
            (cfg.ssm_heads, dt))])


def mixer_weights(params, i):
    """What the mixer's two forms take of layer i beside its rows."""
    pre = "layer%d_" % i
    return {n: params[pre + n] for n in ("conv_w", "conv_b", "A_log", "D")}


# ---------------------------------------------------------------------------
# the state-space mixer between its two projections
# ---------------------------------------------------------------------------


def _heads(conv_out, cfg):
    """The convolution's output (..., C) as x (..., Hs, P), B and C
    (..., G, N)."""
    lead = conv_out.shape[:-1]
    gn = cfg.ssm_groups * cfg.ssm_state
    x, b, c = jnp.split(conv_out, [cfg.d_ssm, cfg.d_ssm + gn], axis=-1)
    return (x.reshape(*lead, cfg.ssm_heads, cfg.ssm_head_dim),
            b.reshape(*lead, cfg.ssm_groups, cfg.ssm_state),
            c.reshape(*lead, cfg.ssm_groups, cfg.ssm_state))


def _conv_act(window_sum, bias, dtype):
    return jax.nn.silu(window_sum + bias.astype(F32)).astype(dtype)


def ssd_scan(x, dt, A, Bm, Cm, chunk):
    """The recurrence H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t, y_t =
    H_t C_t from H = 0, over ONE sequence, a chunk of positions at a time
    in matrix form. x (S, Hs, P); dt (S, Hs) float32; A (Hs,) float32 < 0;
    Bm, Cm (S, G, N), head h reading group h // (Hs / G). Inside a chunk
    position i takes from position j <= i through `C_i . B_j` times the
    decay between them; a chunk's own state is `B^T x` decayed to its
    end; the state carried into a chunk reaches position i through `C_i`.
    The products run in x's dtype with float32 sums, decays in float32.
    A position with dt = 0 leaves the state as it is and adds nothing.
    Returns y (S, Hs, P) in x's dtype and the state after position S - 1,
    (Hs, P, N) float32."""
    S, H, P = x.shape
    G, N = Bm.shape[1:]
    L = min(chunk, S)
    pad = -S % L
    if pad:
        x, dt, Bm, Cm = (jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                         for t in (x, dt, Bm, Cm))
    nc, hpg = (S + pad) // L, H // G
    # heads ahead of positions: (chunk, group, head of the group, L, ...)
    cum = jnp.cumsum((dt * A).reshape(nc, L, G, hpg), axis=1) \
        .transpose(0, 2, 3, 1)                                  # <= 0
    xd = (x.astype(F32) * dt[..., None]).reshape(nc, L, G, hpg, P) \
        .transpose(0, 2, 3, 1, 4)
    Bc, Cc = Bm.reshape(nc, L, G, N), Cm.reshape(nc, L, G, N)
    # inside a chunk: (C_i . B_j) exp(cum_i - cum_j), j <= i
    cb = jnp.einsum("cign,cjgn->cgij", Cc, Bc, preferred_element_type=F32)
    seen = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    decay = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    y = jnp.einsum("cghij,cghjp->cghip",
                   (cb[:, :, None] * decay).astype(x.dtype),
                   xd.astype(x.dtype), preferred_element_type=F32)
    # each chunk's own state at its end, then the carry from chunk to chunk
    to_end = jnp.exp(cum[..., -1:] - cum)                       # (nc, G, hpg, L)
    own = jnp.einsum("cghjp,cjgn->cghpn",
                     (xd * to_end[..., None]).astype(x.dtype), Bc,
                     preferred_element_type=F32)

    def carry(h, chunk_of):
        own_c, whole_c = chunk_of
        return whole_c[..., None, None] * h + own_c, h

    last, entering = jax.lax.scan(
        carry, jnp.zeros((G, hpg, P, N), F32), (own, jnp.exp(cum[..., -1])))
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "cign,cghpn->cghip", Cc, entering.astype(Cc.dtype),
        preferred_element_type=F32)
    return (y.transpose(0, 3, 1, 2, 4).reshape(S + pad, H, P)[:S]
            .astype(x.dtype), last.reshape(H, P, N))


def mix_prompt(xbc, dt, w, cfg, length=None):
    """The mixer between its projections over ONE sequence from an empty
    state: xbc (S, C) the convolution's inputs, dt (S, Hs) float32 after
    the softplus. Of a padded bucket only the first `length` positions
    are real: the rest leave the state as it is (their dt is 0). Returns
    y (S, d_ssm), the state after position length - 1 as the cache keeps
    it (`state_layout`), float32, and the convolution's last `conv_taps -
    1` real inputs (zeros before position 0)."""
    S, taps = xbc.shape[0], cfg.conv_taps
    if length is not None:
        dt = jnp.where(jnp.arange(S)[:, None] < length, dt, 0.0)
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    conv_w = w["conv_w"].astype(F32)
    conv = _conv_act(sum(padded[k:k + S].astype(F32) * conv_w[k]
                         for k in range(taps)), w["conv_b"], xbc.dtype)
    x, Bm, Cm = _heads(conv, cfg)
    y, state = ssd_scan(x, dt, -jnp.exp(w["A_log"].astype(F32)), Bm, Cm,
                        cfg.chunk)
    y = y + (w["D"].astype(F32)[:, None] * x.astype(F32)).astype(y.dtype)
    tail = jax.lax.dynamic_slice_in_dim(
        padded, S if length is None else length, taps - 1, axis=0)
    state = state.reshape(cfg.ssm_groups, -1, *state.shape[1:])
    return (y.reshape(S, cfg.d_ssm),
            state.transpose(0, 3, 1, 2).reshape(state_layout(cfg)), tail)


def state_update(state, decay, dtx, Bm, Cm):
    """The recurrence's step on states as the cache keeps them
    (`state_layout`): state (B, G, N, hpg, P), or (B, G, N, hpg * P)
    where a group's heads lie side by side; decay (B, G, hpg, 1) and dtx
    (B, G, hpg, P) a head; Bm, Cm (B, G, N) a group; all float32. Returns
    the new states as they came and y (B, G, hpg, P), `sum_n C[n] H[n]`."""
    B, G, N = Bm.shape
    row = lambda t: jnp.broadcast_to(t, dtx.shape).reshape(B, G, 1, -1)
    h = row(decay) * state.reshape(B, G, N, -1) + Bm[..., None] * row(dtx)
    return (h.reshape(state.shape),
            jnp.sum(Cm[..., None] * h, axis=2).reshape(dtx.shape))


def state_layout(cfg):
    """One sequence's state in one layer as the cache keeps it. Heads as
    wide as a tile's lanes or wider: (groups, N, heads of a group, P), so
    that for one n a group's (heads, P) slab is whole tiles and its
    `B[n]`, `C[n]` are scalars. Narrower heads that fill whole tiles side
    by side (8 heads of 64): (groups, N, heads of a group x P), the state
    index on a tile's sublanes, `B` and `C` columns and y a sum over
    sublanes. A choice of the cache (ops/pallas_ssm_step.py has a form for
    each), not of the mathematics."""
    hpg, P = cfg.ssm_heads // cfg.ssm_groups, cfg.ssm_head_dim
    if P < LANES and hpg * P % LANES == 0:
        return (cfg.ssm_groups, cfg.ssm_state, hpg * P)
    return (cfg.ssm_groups, cfg.ssm_state, hpg, P)


def mix_step(xbc, dt, tail, w, cfg, update):
    """One recurrence step a row: xbc (B, C), dt (B, Hs) float32, each
    row's convolution's last inputs `tail` (B, conv_taps - 1, C). The
    rows' states are the caller's: `update(decay, dtx, Bm, Cm)` applies
    `state_update` to them where they lie and returns its y. Returns y
    (B, d_ssm) and the new last inputs."""
    B = xbc.shape[0]
    G, hpg = cfg.ssm_groups, cfg.ssm_heads // cfg.ssm_groups
    window = jnp.concatenate([tail, xbc[:, None].astype(tail.dtype)], axis=1)
    conv = _conv_act(jnp.sum(window.astype(F32) * w["conv_w"].astype(F32),
                             axis=1), w["conv_b"], xbc.dtype)
    x, Bm, Cm = _heads(conv, cfg)
    xf = x.astype(F32)
    decay = jnp.exp(dt * -jnp.exp(w["A_log"].astype(F32)))
    y = update(decay.reshape(B, G, hpg, 1),
               (dt[..., None] * xf).reshape(B, G, hpg, -1),
               Bm.astype(F32), Cm.astype(F32))
    y = y.reshape(x.shape) + w["D"].astype(F32)[:, None] * xf
    return y.reshape(B, cfg.d_ssm).astype(xbc.dtype), window[:, 1:]


class DenseView:
    """No cache: the rows are one sequence, positions in order, the state
    empty before the first."""

    def attend(self, layer, q, k, v):
        return banded_attention(q, k, v)

    def mix(self, layer, xbc, dt, w, cfg):
        return mix_prompt(xbc, dt, w, cfg)[0]


def _scaled(x, multiplier, dtype):
    """A float32 product times its multiplier, rounded once."""
    return (x * multiplier).astype(dtype) if multiplier != 1.0 \
        else x.astype(dtype)


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=F32)


def gated_group_norm(y, z, gain, cfg):
    """`RMSNorm(y * silu(z))` over each of the `ssm_groups` groups' values
    with one gain of d_ssm (the gate before the norm): y (N, d_ssm) in
    any dtype, z float32. Float32."""
    y = (y.astype(F32) * jax.nn.silu(z)).reshape(y.shape[0], cfg.ssm_groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
    return y.reshape(y.shape[0], cfg.d_ssm) * gain.astype(F32)


def ssm_mixer(params, i, h, cfg, view):
    """The state-space mixer of layer i over normed rows h (N, D): its
    output before it joins the residual, float32."""
    pre = "layer%d_" % i
    proj = _dot(_scaled(h, cfg.ssm_in_multiplier, h.dtype),
                params[pre + "w_in"]) * in_proj_multipliers(cfg)
    z, xbc, dt = jnp.split(proj, [cfg.d_ssm, cfg.d_ssm + cfg.conv_channels],
                           axis=-1)
    dt = jax.nn.softplus(dt + params[pre + "dt_bias"].astype(F32))
    y = view.mix(i, xbc.astype(h.dtype), dt, mixer_weights(params, i), cfg)
    y = gated_group_norm(y, z, params[pre + "ssm_norm_g"], cfg)
    return _dot(y.astype(h.dtype), params[pre + "w_out"]) \
        * cfg.ssm_out_multiplier


def block(params, i, x, positions, cfg, view):
    """Layer i over rows x (N, D) at `positions` (N,): attention and the
    state-space mixer through `view`, side by side, then the
    feed-forward. The residual's sums are float32, rounded once."""
    pre = "layer%d_" % i
    N, H, K, Dh = x.shape[0], cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, params[pre + "norm_in_g"], cfg.norm_eps)
    a = _scaled(h, cfg.attention_in_multiplier, h.dtype)
    q = (a @ params[pre + "wq"]).reshape(N, H, Dh)
    k = _scaled(_dot(a, params[pre + "wk"]), cfg.key_multiplier,
                x.dtype).reshape(N, K, Dh)
    v = (a @ params[pre + "wv"]).reshape(N, K, Dh)
    cos, sin = rope_cos_sin(positions, cfg)
    q = apply_rope(q, cos[:, None], sin[:, None])
    k = apply_rope(k, cos[:, None], sin[:, None])
    o = view.attend(i, q, k, v).reshape(N, H * Dh).astype(x.dtype)
    out_a = _dot(o, params[pre + "wo"]) * cfg.attention_out_multiplier
    out_s = ssm_mixer(params, i, h, cfg, view)
    x = (x.astype(F32) + out_a + out_s).astype(x.dtype)
    m = rms_norm(x, params[pre + "norm_mlp_g"], cfg.norm_eps)
    gate = _scaled(_dot(m, params[pre + "w_gate"]), cfg.mlp_multipliers[0],
                   x.dtype)
    f = _dot(jax.nn.silu(gate) * (m @ params[pre + "w_up"]),
             params[pre + "w_down"]) * cfg.mlp_multipliers[1]
    return (x.astype(F32) + f).astype(x.dtype)


def trunk(params, tokens, positions, cfg, view):
    """Embedding and every layer: rows (N, D)."""
    x = _scaled(params["embed"][tokens].astype(F32),
                cfg.embedding_multiplier, params["embed"].dtype)
    for i in range(cfg.n_layers):
        x = block(params, i, x, positions, cfg, view)
    return x


def logits_of(params, x, cfg):
    h = rms_norm(x, params["normf_g"], cfg.norm_eps)
    return _dot(h, params["head"]) * cfg.lm_head_multiplier


def falcon_h1_apply(params, tokens, cfg):
    """The dense forward of one sequence, no cache: tokens (S,) -> logits
    (S, vocab) float32."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    return logits_of(params, trunk(params, tokens, positions, cfg,
                                   DenseView()), cfg)
