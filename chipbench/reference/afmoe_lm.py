"""Plain reference for the `afmoe_lm` family: the decoder block of the
published `afmoe` model (Arcee Trinity; the model of that name in the
transformers library, and the configuration's `config.json`), in float32
`jax.numpy` with matmul precision "highest". No cache (every position attends
over the whole sequence under a dense mask), no batching, no kernels, nothing
imported from the program.

Rows x of width `hidden_size`; RMSNorm with a gain and no bias, eps
`rms_norm_eps`; no bias anywhere; the head is a matrix of its own:
    x0 = embed[token] * sqrt(hidden_size)                      (mup_enabled)
    a  = rms(x; norm_in_g)
    q  = rms(a wq -> H heads of head_dim; q_norm_g), per head
    k  = rms(a wk -> Hkv heads; k_norm_g), per head;  v = a wv -> Hkv heads
    layer_types[i] == "sliding_attention": q, k = RoPE(q, k), frequencies
        rope_theta^(-2i / head_dim) over the whole head, rotate-half;
    "full_attention": no positions at all
    s  = q k^T / sqrt(head_dim), query head h reads KV head h // (H / Hkv);
         key j is seen by query t iff j <= t and, on a sliding layer, also
         t - j < sliding_window (the query counts among the window's keys)
    o  = softmax(s) v;  o = o * sigmoid(a wg);  x = x + rms(o wo; norm_post_attn_g)
    m  = rms(x; norm_pre_mlp_g)
    f  = w_down(silu(m w_gate) * m w_up)          in the `num_dense_layers` first
    f  = E_shared(m) + sum over winners HELD HERE of w_e E_e(m)   in the others:
         p = sigmoid(m router) in float32; the `num_experts_per_tok` largest of
         p + router_bias win (ties to the lower index); w = p[won] /
         (sum p[won] + 1e-20) * route_scale
    x  = x + rms(f; norm_post_mlp_g);   logits = rms(x; normf_g) head

The share: experts [expert_rank * num_experts, (expert_rank + 1) *
num_experts) of the deployment's `num_experts_published` are present (the
stacked `we_*`, the lowest first). The router scores all of the published
count; what absent winners would add is left out, and that partial sum goes on
to the next layer.

Departures from the published model, shared with the system under test (the
configuration file lists them under `assumed`):
  1. random weights: the sandwich norms' gains are N(1, 0.1), not their
     depth-scaled initial value, which is a fact of the checkpoint and not of
     the forward;
  2. the selection bias (`expert_bias`) is N(0, 0.01), not the trained one;
  3. the vocabulary is a slice (rows 0..vocab_size-1).

`weights` is {"embed", "head", "normf_g", "layers": [{norm_in_g,
norm_post_attn_g, norm_pre_mlp_g, norm_post_mlp_g, q_norm_g, k_norm_g, wq, wk,
wv, wg, wo, and w_gate, w_up, w_down or router, router_bias, ws_gate, ws_up,
ws_down, we_gate, we_up, we_down}]} in any float dtype. Every matrix is upcast
to float32 inside a call of its own, one expert at a time, and attention scores
one block of queries at a time, so a 16,384-position forward fits beside bf16
weights that fill a third of the chip.

`weight_bits=8` is the control: every weight matmul computed in int8 (the
weight rounded per output channel, the activation per row), the router and
attention's own two products staying in float32.
"""
import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.transformer_lm import HIGHEST, _gaps, _mm, pad_len

Q_BLOCK = 128


def _rms(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain.astype(jnp.float32)


def rope_tables(config, n):
    """cos, sin (n, head_dim) float32 for positions 0..n-1."""
    dim = config["head_dim"]
    freq = float(config["rope_theta"]) ** (
        -2.0 * jnp.arange(dim // 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], -1) * sin


@functools.partial(jax.jit, static_argnames=("bits",))
def _proj(x, w, bits):
    return _mm(x, w, bits)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, g, eps):
    return _rms(x, g, eps)


@functools.partial(jax.jit, static_argnames=("bits",))
def _swiglu(x, w_gate, w_up, w_down, bits):
    return _mm(jax.nn.silu(_mm(x, w_gate, bits)) * _mm(x, w_up, bits),
               w_down, bits)


@functools.partial(jax.jit, static_argnames=("window",))
def _attend(q, k, v, window):
    """Causal attention under a dense mask, a block of queries at a time
    against ALL keys. q (S, H, Dh); k, v (S, Hkv, Dh); `window` 0 for a
    full layer."""
    S, H, Dh = q.shape
    Hkv = k.shape[1]
    keys = jnp.arange(S)[None, :]
    qb = q.reshape(S // Q_BLOCK, Q_BLOCK, Hkv, H // Hkv, Dh)

    def block(args):
        lo, qs = args
        t = (lo + jnp.arange(Q_BLOCK))[:, None]
        seen = keys <= t
        if window:
            seen = seen & (t - keys < window)
        s = jnp.einsum("qkgd,jkd->kgqj", qs, k, precision=HIGHEST) \
            / math.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqj,jkd->qkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(0, S, Q_BLOCK), qb))
    return out.reshape(S, H, Dh)


def _top(x, k):
    """Indices of the k largest along the last axis, ties to the lower
    index."""
    return jnp.argsort(-x, axis=-1, stable=True)[..., :k]


@functools.partial(jax.jit, static_argnames=("top_k", "scale"))
def route(x, router, bias, top_k, scale):
    """(N, D) float32 -> winners (N, top_k) int32 and their weights."""
    p = jax.nn.sigmoid(jnp.matmul(x, router.astype(jnp.float32),
                                  precision=HIGHEST))
    idx = _top(p + bias.astype(jnp.float32), top_k)
    won = jnp.take_along_axis(p, idx, axis=-1)
    return idx.astype(jnp.int32), \
        won / (won.sum(-1, keepdims=True) + 1e-20) * scale


def experts_held(config):
    """[lo, hi) of the deployment's routed experts present here."""
    per = config["num_experts"]
    return config["expert_rank"] * per, (config["expert_rank"] + 1) * per


def moe(x, lw, config, bits, counts=None, shared=True):
    """The expert layer over (N, D): every held expert over every token,
    weighted by what the router gave it (0 where it did not win). `counts`,
    if a list, receives the rows per held expert; `shared` False leaves the
    shared expert out (the test that adds the ranks' parts up counts it
    once)."""
    idx, w = route(x, lw["router"], lw["router_bias"],
                   top_k=config["num_experts_per_tok"],
                   scale=float(config["route_scale"]))
    lo, hi = experts_held(config)
    out = _swiglu(x, lw["ws_gate"], lw["ws_up"], lw["ws_down"], bits) \
        if shared else jnp.zeros_like(x)
    rows = []
    for e in range(lo, hi):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        rows.append(jnp.sum(idx == e, axis=-1))
        out = out + w_e[:, None] * _swiglu(
            x, lw["we_gate"][e - lo], lw["we_up"][e - lo],
            lw["we_down"][e - lo], bits)
    if counts is not None:
        counts.append(jnp.stack(rows, 1))          # (N, held)
    return out


def layer(x, lw, index, config, cos, sin, bits, counts=None, kinds=None):
    """`kinds` stands in for the configuration's `layer_types` (the test
    that swaps the kinds)."""
    S = x.shape[0]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    Dh, eps = config["head_dim"], float(config["rms_norm_eps"])
    sliding = (kinds or config["layer_types"])[index] == "sliding_attention"
    a = _norm(x, lw["norm_in_g"], eps)
    q = _norm(_proj(a, lw["wq"], bits).reshape(S, H, Dh), lw["q_norm_g"], eps)
    k = _norm(_proj(a, lw["wk"], bits).reshape(S, Hkv, Dh), lw["k_norm_g"],
              eps)
    v = _proj(a, lw["wv"], bits).reshape(S, Hkv, Dh)
    if sliding:
        q, k = _rope(q, cos[:, None], sin[:, None]), \
            _rope(k, cos[:, None], sin[:, None])
    o = _attend(q, k, v, window=config["sliding_window"] if sliding else 0)
    o = o.reshape(S, H * Dh) * jax.nn.sigmoid(_proj(a, lw["wg"], bits))
    x = x + _norm(_proj(o, lw["wo"], bits), lw["norm_post_attn_g"], eps)
    m = _norm(x, lw["norm_pre_mlp_g"], eps)
    if index < config["num_dense_layers"]:
        f = _swiglu(m, lw["w_gate"], lw["w_up"], lw["w_down"], bits)
    else:
        f = moe(m, lw, config, bits, counts)
    return x + _norm(f, lw["norm_post_mlp_g"], eps)


def logits(weights, config, tokens, weight_bits=None, counts=None, kinds=None):
    """(S,) int tokens, S a multiple of 128 -> (S, vocab) float32 logits, one
    full causal forward. Position i's row scores the token at position i+1."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = weights["embed"][tokens].astype(jnp.float32)
    if config.get("mup_enabled"):
        x = x * math.sqrt(config["hidden_size"])
    cos, sin = rope_tables(config, tokens.shape[0])
    for i, lw in enumerate(weights["layers"]):
        x = layer(x, lw, i, config, cos, sin, weight_bits, counts, kinds)
    return _proj(_norm(x, weights["normf_g"], float(config["rms_norm_eps"])),
                 weights["head"], weight_bits)


def served_gaps(weights, config, prompt, served, control_bits=None):
    """The number `correct` compares for one finished request: for every
    served token, the gap between the reference's best logit at that
    position and the reference's logit of the token that was served. With
    `control_bits`, the tokens judged are those the lower-precision forward
    puts first at the same positions (the control need not decode)."""
    n, m = len(prompt), len(served)
    S = pad_len(n + m)
    toks = jnp.zeros((S,), jnp.int32).at[:n + m].set(
        jnp.asarray(list(prompt) + list(served), jnp.int32))
    ref = logits(weights, config, toks)
    if control_bits is None:
        judged = jnp.zeros((S,), jnp.int32).at[n - 1:n - 1 + m].set(
            jnp.asarray(served, jnp.int32))
    else:
        low = logits(weights, config, toks, weight_bits=control_bits)
        judged = jnp.argmax(low, -1).astype(jnp.int32)
    return _gaps(ref, judged, n - 1, m)[n - 1:n - 1 + m]
