"""Latent-attention, dropless sparse-expert language model.

The second language-model family beside `models/transformer.py`: a
pre-norm residual block with RMSNorm (gain, no bias), no bias anywhere,
multi-head LATENT attention (low-rank query and key-value projections,
a rotary slice shared by all heads, YaRN-scaled) and a feed-forward
that is a dense SwiGLU in the leading layers and, after them, a sparse
expert layer: a sigmoid router with a selection bias, group-limited
top-k, a shared expert, and NO capacity (no token is ever dropped, so a
token's experts depend on that token alone). The equations are those of
the DeepSeek-V3 technical report (arXiv:2412.19437, sections 2.1.1 and
2.1.2); the widths are the config's.

One chip of an expert-parallel deployment holds a contiguous range of
the routed experts (`experts_held`). The router still scores ALL of
them at the published width, and the layer returns
`sum over (selected and held) w_e E_e(x) + E_shared(x)`: what the
absent experts would add is left out, and no code stands in for the
chips that hold them. Held experts are computed over the (token,
expert) pairs that chose them, sorted by expert and cut into tiles
whose rows follow the shapes (`grouped_experts`: on the chip in bf16 one
grouped-product kernel a layer, ops/pallas_grouped_experts.py, through
which the touched experts' matrices stream once, back to back; a loop of
one pass a tile elsewhere), not densely over every token.

The layer is written ONCE (`block`), over a cache view that says how
attention reads its keys; the layer knows a view by its `attend` and
nothing of pools. Here is the one with no cache, `DenseView`: the rows
are one sequence in order and every position attends by the EXPANDED
form (keys and values rebuilt from the latent, scores blocked over
queries; never a (heads, S, S) array). The views that write to and read
from the paged pool, and the step functions over them, are the serving
engine's (`serving/latent_lm.py`): prefill attends expanded after
writing the prompt's latents, decode attends in the ABSORBED form over
the cached latents (`absorbed_attention`: the key up-projection is
folded into the query and the value up-projection applied after the
weighted sum, so keys and values of cached tokens are never rebuilt).

What a cache holds per token per layer is `[c_kv after its norm
(kv_rank) ; k_rope after RoPE (rope_dim)]` and nothing else.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops import pallas_grouped_experts as _experts
from ..ops.pallas_attention import default_interpret

#: query rows scored at once by the expanded form: (heads, Q_BLOCK, keys)
#: float32 is the largest array attention makes
Q_BLOCK = 256
#: rows of one tile of the grouped experts' LOOP, the fallback (a decode
#: batch is one tile); the kernel's tile follows the rows an expert can get
#: (ops/pallas_grouped_experts.py `tile_rows`)
EXPERT_TILE = 128

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 3
    n_dense_layers: int = 1        # leading layers with a dense SwiGLU
    q_rank: int = 24               # low-rank query projection
    kv_rank: int = 16              # the latent c_kv
    nope_dim: int = 8              # per head, without position
    rope_dim: int = 8              # per head query / one shared key, rotary
    v_dim: int = 8
    d_ff: int = 128                # the dense layers' SwiGLU width
    d_expert: int = 32             # every expert's SwiGLU width
    n_shared: int = 1              # shared experts (one SwiGLU, n x wide)
    n_experts: int = 16            # routed experts of the DEPLOYMENT:
                                   # the router's width
    top_k: int = 4
    n_groups: int = 4
    top_groups: int = 2
    route_scale: float = 2.5
    experts_held: tuple = (0, 16)  # [lo, hi) of them held on this chip
    rope_base: float = 10000.0
    rope_factor: float = 40.0      # YaRN
    rope_orig_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6
    max_len: int = 128
    dtype: object = jnp.float32

    @property
    def n_held(self):
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_moe_layers(self):
        return self.n_layers - self.n_dense_layers

    @property
    def latent_dim(self):
        return self.kv_rank + self.rope_dim


def held_range(rank, world, n_experts):
    """Rank `rank` of `world` holds `[rank * n / world, (rank + 1) * n /
    world)` of the n routed experts."""
    per = n_experts // world
    return (rank * per, (rank + 1) * per)


def init_latent_moe_params(rng, cfg):
    """Flat dict name -> array: N(0, 0.02) matrices, gains of one, a
    selection bias N(0, 0.01) (not zero, so that dropping it shows)."""
    D, H = cfg.d_model, cfg.n_heads
    shapes = {"embed": (cfg.vocab, D), "head": (D, cfg.vocab)}
    for i in range(cfg.n_layers):
        pre = "layer%d_" % i
        shapes.update({
            pre + "wq_a": (D, cfg.q_rank),
            pre + "wq_b": (cfg.q_rank, H * (cfg.nope_dim + cfg.rope_dim)),
            pre + "wkv_a": (D, cfg.latent_dim),
            pre + "wk_b": (cfg.kv_rank, H * cfg.nope_dim),
            pre + "wv_b": (cfg.kv_rank, H * cfg.v_dim),
            pre + "wo": (H * cfg.v_dim, D)})
        if i < cfg.n_dense_layers:
            shapes.update({pre + "w_gate": (D, cfg.d_ff),
                           pre + "w_up": (D, cfg.d_ff),
                           pre + "w_down": (cfg.d_ff, D)})
        else:
            ds, ne, de = cfg.n_shared * cfg.d_expert, cfg.n_held, cfg.d_expert
            shapes.update({pre + "router": (D, cfg.n_experts),
                           pre + "ws_gate": (D, ds), pre + "ws_up": (D, ds),
                           pre + "ws_down": (ds, D),
                           pre + "we_gate": (ne, D, de),
                           pre + "we_up": (ne, D, de),
                           pre + "we_down": (ne, de, D)})
    keys = jax.random.split(rng, len(shapes) + cfg.n_layers)
    p = {n: (0.02 * jax.random.normal(k, s)).astype(cfg.dtype)
         for k, (n, s) in zip(keys, sorted(shapes.items()))}
    p["normf_g"] = jnp.ones((D,), cfg.dtype)
    for i, k in enumerate(keys[len(shapes):]):
        pre = "layer%d_" % i
        p[pre + "norm1_g"] = jnp.ones((D,), cfg.dtype)
        p[pre + "norm2_g"] = jnp.ones((D,), cfg.dtype)
        p[pre + "q_norm_g"] = jnp.ones((cfg.q_rank,), cfg.dtype)
        p[pre + "kv_norm_g"] = jnp.ones((cfg.kv_rank,), cfg.dtype)
        if i >= cfg.n_dense_layers:
            p[pre + "router_bias"] = 0.01 * jax.random.normal(
                k, (cfg.n_experts,), jnp.float32)
    return p


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------


def rms_norm(x, gain, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * gain.astype(jnp.float32)).astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def expert_ffn(x, w_gate, w_up, w_down):
    """One expert (routed or shared) in the form its weights give it:
    three matrices are a SwiGLU; two (`w_gate` None) a squared ReLU,
    `relu(x W_up)^2 W_down` (`mlp_hidden_act` "relu2")."""
    if w_gate is not None:
        return swiglu(x, w_gate, w_up, w_down)
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg):
    """The rotary frequencies, (rope_dim / 2,) float32: fast dimensions
    keep theirs, slow ones are interpolated by `rope_factor`, a linear
    ramp between the two corrections in between."""
    dim = cfg.rope_dim
    theta = cfg.rope_base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if cfg.rope_factor <= 1:
        return theta

    def correction(beta):
        return dim * math.log(cfg.rope_orig_len / (beta * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_base))

    low = max(math.floor(correction(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction(cfg.rope_beta_slow)), dim - 1)
    span = (high - low) or 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / span,
                    0.0, 1.0)
    return theta * (1.0 - ramp) + theta / cfg.rope_factor * ramp


def rope_cos_sin(positions, cfg):
    """(N,) int positions -> cos, sin (N, rope_dim) float32."""
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
        / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def apply_rope(x, cos, sin):
    """Rotate-half over the last axis; cos/sin broadcast against x."""
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-xf[..., half:], xf[..., :half]], -1)
    return (xf * cos + rot * sin).astype(x.dtype)


def score_scale(cfg):
    m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    return (cfg.nope_dim + cfg.rope_dim) ** -0.5 * m * m


# ---------------------------------------------------------------------------
# attention: two bodies of one layer
# ---------------------------------------------------------------------------


def expanded_attention(q_nope, q_rope, latent, wk_b, wv_b, cfg):
    """Causal attention of one sequence over its own latents, keys and
    values REBUILT from them. q_nope (S, H, nope), q_rope (S, H, rope),
    latent (S, kv_rank + rope) -> (S, H, v_dim). A block of Q_BLOCK
    queries at a time against the keys up to its end: the scores are
    (H, Q_BLOCK, keys) and the keys after the block are not touched."""
    S, H = q_nope.shape[:2]
    c_kv, k_rope = latent[:, :cfg.kv_rank], latent[:, cfg.kv_rank:]
    k_nope = (c_kv @ wk_b).reshape(S, H, cfg.nope_dim)
    v = (c_kv @ wv_b).reshape(S, H, cfg.v_dim)
    scale = score_scale(cfg)
    out = []
    for lo in range(0, S, Q_BLOCK):
        hi = min(lo + Q_BLOCK, S)
        s = jnp.einsum("qhd,khd->hqk", q_nope[lo:hi], k_nope[:hi]) \
            + jnp.einsum("qhr,kr->hqk", q_rope[lo:hi], k_rope[:hi])
        seen = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s.astype(jnp.float32)
                                     * scale, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v[:hi]))
    return jnp.concatenate(out, 0) if len(out) > 1 else out[0]


def absorbed_attention(q_nope, q_rope, cached, live, wk_b, wv_b, cfg):
    """One query a row over that row's cached latents as they lie in the
    pool's blocks. q_nope (B, H, nope), q_rope (B, H, rope), cached
    (B, nblk, block_size, W >= kv_rank + rope, zero past it), live (B,
    nblk * block_size) bool -> (B, H, v_dim). The key up-projection goes into the query
    (`q_lat = q_nope W_UK^T`), the score is `q_lat . c_kv + q_rope .
    k_rope`, the weighted sum is taken over the latents and the value
    up-projection applied to it: nothing of the cache's length is
    rebuilt."""
    B, H = q_nope.shape[:2]
    r = cfg.kv_rank
    nblk, bs, W = cached.shape[1:]
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope,
                       wk_b.reshape(r, H, cfg.nope_dim))
    q = jnp.concatenate([q_lat, q_rope], -1)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, W - q.shape[-1])))    # (B, H, W)
    s = jnp.einsum("bhw,bnsw->bhns", q, cached).astype(jnp.float32) \
        * score_scale(cfg)
    s = jnp.where(live[:, None, :], s.reshape(B, H, nblk * bs), -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).reshape(B, H, nblk, bs)
    # over the whole latent row: slicing the gathered blocks would copy them
    o_lat = jnp.einsum("bhns,bnsw->bhw", p.astype(cached.dtype), cached)
    return jnp.einsum("bhc,chd->bhd", o_lat[..., :r],
                      wv_b.reshape(r, H, cfg.v_dim))


class DenseView:
    """No cache: the rows are one sequence, positions in order."""

    def attend(self, layer, q_nope, q_rope, latent, wk_b, wv_b, cfg):
        return expanded_attention(q_nope, q_rope, latent, wk_b, wv_b, cfg)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------


def route(h, router, bias, cfg):
    """Per token, alone: (N, D) -> experts (N, top_k) int32 and weights
    (N, top_k) float32. Sigmoid scores `s`; the selection score is `s +
    bias`; a group's score is the sum of its two largest selection
    scores; the best `top_groups` groups stay; among their experts the
    `top_k` largest selection scores win; the weights are the winners'
    `s` (the bias selects and does not weigh), normalised over all
    `top_k` and scaled. Float32 throughout; ties go to the lower
    index."""
    N = h.shape[0]
    s = jax.nn.sigmoid(jnp.matmul(h.astype(jnp.float32),
                                  router.astype(jnp.float32),
                                  precision=_HIGHEST))
    sel = s + bias.astype(jnp.float32)
    G = cfg.n_groups
    by_group = sel.reshape(N, G, cfg.n_experts // G)
    group_score = jax.lax.top_k(by_group, 2)[0].sum(-1)            # (N, G)
    _, best = jax.lax.top_k(group_score, cfg.top_groups)
    kept = jnp.any(best[:, :, None] == jnp.arange(G)[None, None, :], axis=1)
    sel = jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(
        N, cfg.n_experts)
    _, idx = jax.lax.top_k(sel, cfg.top_k)
    won = jnp.take_along_axis(s, idx, axis=1)
    w = won / (won.sum(-1, keepdims=True) + 1e-20) * cfg.route_scale
    return idx.astype(jnp.int32), w


def experts_unfit(h, we_gate, we_up):
    """Why the held experts are XLA's loop over tiles and not the kernel
    (ops/pallas_grouped_experts.py), or None: asked of the rows and the
    experts' matrices as they are, by `grouped_experts` while it traces
    and by the serving model of the matrices it will hand that trace
    (`LatentMoELM.moe_unfit`), so the two cannot disagree."""
    mixed = {jnp.dtype(a.dtype).name for a in (h, we_gate, we_up)
             if a is not None}
    if len(mixed) > 1:
        return "rows and experts differ in dtype: %s" % " and ".join(
            sorted(mixed))
    return _experts.experts_unfit(we_up.shape[1], we_up.shape[2],
                                  2 if we_gate is None else 3, h.dtype)


def grouped_experts(h, local, w, we_gate, we_up, we_down):
    """The held experts over the pairs that chose them. h (N, D); local
    (N, k) the held expert of each (token, choice) pair, or `n_held`
    for a pair that is not computed here; w (N, k) float32; the experts'
    matrices stacked by held expert, `we_gate` None where an expert is
    two matrices and a squared ReLU (`expert_ffn`). Returns `sum_k w
    E_local(h)` (N, D) float32 and the pairs per held expert (n_held,)
    int32.

    Pairs are sorted by expert and each expert's run is cut into tiles
    of `tile` rows, laid in a buffer in which each expert starts on a
    tile boundary; as many tiles as the data has are each multiplied by
    their one expert's matrices, and an expert no pair chose is never
    read. Where the gate lets it (`experts_unfit`) the sorted rows are
    gathered ONCE and ONE kernel a layer walks the tiles
    (ops/pallas_grouped_experts.py: the next expert's matrices arrive
    while this tile multiplies; the tile's rows from the shapes,
    `tile_rows`); elsewhere a loop of one pass a tile gathers the tile's
    tokens, runs `expert_ffn` on them and lays the rows into the buffer.
    The weighted sum over a token's choices then gathers from the
    buffer."""
    N, k = local.shape
    n_held, D = we_up.shape[0], h.shape[1]
    kernel = experts_unfit(h, we_gate, we_up) is None
    tile = _experts.tile_rows(N * k, n_held, h.dtype) if kernel \
        else min(N, EXPERT_TILE)
    flat = local.reshape(N * k)
    hot = flat[:, None] == jnp.arange(n_held)[None, :]        # (N*k, n_held)
    counts = hot.sum(0).astype(jnp.int32)
    # a pair's rank among its expert's pairs, in (token, choice) order: the
    # order a stable sort by expert leaves them in
    rank = jnp.take_along_axis(
        jnp.cumsum(hot, 0, dtype=jnp.int32) - hot,
        jnp.minimum(flat, n_held - 1)[:, None], axis=1)[:, 0]
    token_of = jnp.argsort(flat, stable=True).astype(jnp.int32) // k
    tiles_of = -(-counts // tile)
    tile_end = jnp.cumsum(tiles_of)
    tile_start, pair_start = tile_end - tiles_of, jnp.cumsum(counts) - counts
    # every pair's tile and a part-filled one an expert; with the kernel,
    # rounded up so that the programs of a few rows each (every bucket of
    # a decode step) share one lowered shape
    max_tiles = -(-N * k // tile)
    if kernel:
        max_tiles = -(-max_tiles // n_held) * n_held
    max_tiles += n_held

    def expert_of(t):
        """The expert of tile(s) t; past the last tile, the last expert."""
        return jnp.minimum(jnp.sum(t[..., None] >= tile_end, axis=-1),
                           n_held - 1).astype(jnp.int32)

    def tile_tokens(t, e):
        """The tokens of tile(s) t of expert(s) e, a row of `tile` each."""
        within = (t - tile_start[e])[..., None] * tile + jnp.arange(tile)
        return token_of[jnp.minimum(pair_start[e][..., None] + within,
                                    N * k - 1)]

    def one_tile(t, buf):
        e = expert_of(t)
        y = expert_ffn(h[tile_tokens(t, e)],
                       None if we_gate is None else we_gate[e],
                       we_up[e], we_down[e])
        return jax.lax.dynamic_update_slice(buf, y.astype(buf.dtype),
                                            (t * tile, 0))

    if kernel:
        t = jnp.arange(max_tiles, dtype=jnp.int32)
        e = expert_of(t)
        buf = _experts.grouped_experts(
            h[tile_tokens(t, e).reshape(-1)], e, tile_end[-1], we_gate,
            we_up, we_down, tile=tile, interpret=default_interpret())
    else:
        buf = jax.lax.fori_loop(0, tile_end[-1], one_tile,
                                jnp.zeros((max_tiles * tile, D), h.dtype))
    here = flat < n_held
    row = jnp.where(here, tile_start[jnp.minimum(flat, n_held - 1)] * tile
                    + rank, 0).reshape(N, k)
    # a pair that is not computed here reads row 0 at weight 0: the first
    # tile is written whatever the pairs chose (the kernel leaves the tiles
    # past the last real one as it found them)
    out = jnp.einsum("nk,nkd->nd", jnp.where(here.reshape(N, k), w, 0.0),
                     buf[row].astype(jnp.float32))
    return out, counts


def moe_ffn(params, pre, h, real, cfg):
    """(N, D) -> the expert layer's output and the rows of `real` tokens
    sent to each held expert. Rows that are not real (batch and prompt
    padding) are routed nowhere: they cost no expert pass and count
    nothing. The experts' form is their weights': a layer with no
    `we_gate` / `ws_gate` has experts of two matrices (`expert_ffn`)."""
    idx, w = route(h, params[pre + "router"], params[pre + "router_bias"],
                   cfg)
    lo, hi = cfg.experts_held
    here = (idx >= lo) & (idx < hi) & real[:, None]
    routed, counts = grouped_experts(
        h, jnp.where(here, idx - lo, hi - lo), w, params.get(pre + "we_gate"),
        params[pre + "we_up"], params[pre + "we_down"])
    shared = expert_ffn(h, params.get(pre + "ws_gate"), params[pre + "ws_up"],
                        params[pre + "ws_down"])
    return routed.astype(h.dtype) + shared, counts


# ---------------------------------------------------------------------------
# the layer, once, and the dense forward over it
# ---------------------------------------------------------------------------


def block(params, i, x, positions, real, cfg, view):
    """Layer i over rows x (N, D) at `positions` (N,), attention through
    `view`. Returns the rows and, for an expert layer, the pairs per
    held expert (else None)."""
    pre = "layer%d_" % i
    N, H = x.shape[0], cfg.n_heads
    h = rms_norm(x, params[pre + "norm1_g"], cfg.norm_eps)
    c_q = rms_norm(h @ params[pre + "wq_a"], params[pre + "q_norm_g"],
                   cfg.norm_eps)
    q = (c_q @ params[pre + "wq_b"]).reshape(N, H, cfg.nope_dim + cfg.rope_dim)
    kv = h @ params[pre + "wkv_a"]
    c_kv = rms_norm(kv[:, :cfg.kv_rank], params[pre + "kv_norm_g"],
                    cfg.norm_eps)
    cos, sin = rope_cos_sin(positions, cfg)
    k_rope = apply_rope(kv[:, cfg.kv_rank:], cos, sin)
    q_rope = apply_rope(q[..., cfg.nope_dim:], cos[:, None], sin[:, None])
    att = view.attend(i, q[..., :cfg.nope_dim], q_rope,
                      jnp.concatenate([c_kv, k_rope], -1),
                      params[pre + "wk_b"], params[pre + "wv_b"], cfg)
    x = x + att.reshape(N, H * cfg.v_dim).astype(x.dtype) @ params[pre + "wo"]
    h = rms_norm(x, params[pre + "norm2_g"], cfg.norm_eps)
    if i < cfg.n_dense_layers:
        return x + swiglu(h, params[pre + "w_gate"], params[pre + "w_up"],
                          params[pre + "w_down"]), None
    y, counts = moe_ffn(params, pre, h, real, cfg)
    return x + y, counts


def _trunk(params, tokens, positions, real, cfg, view):
    x = params["embed"][tokens]
    counts = []
    for i in range(cfg.n_layers):
        x, c = block(params, i, x, positions, real, cfg, view)
        if c is not None:
            counts.append(c)
    counts = jnp.stack(counts) if counts \
        else jnp.zeros((0, cfg.n_held), jnp.int32)
    return x, counts


def _logits(params, x, cfg):
    h = rms_norm(x, params["normf_g"], cfg.norm_eps)
    return (h @ params["head"]).astype(jnp.float32)


def latent_moe_apply(params, tokens, cfg, length=None):
    """The dense forward of one sequence, no cache: tokens (S,) -> logits
    (S, vocab) float32 and the pairs per (expert layer, held expert)
    over the first `length` positions (all, by default)."""
    S = tokens.shape[0]
    positions = jnp.arange(S, dtype=jnp.int32)
    real = positions < (S if length is None else length)
    x, counts = _trunk(params, tokens, positions, real, cfg, DenseView())
    return _logits(params, x, cfg), counts
