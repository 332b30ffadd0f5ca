"""Plain reference for the `latent_moe_lm` family: the decoder block of
DeepSeek-V3 (DeepSeek-AI, arXiv:2412.19437, sections 2.1.1 multi-head latent
attention and 2.1.2 DeepSeekMoE with auxiliary-loss-free balancing), in
float32 `jax.numpy` with matmul precision "highest". No cache (every position
attends by the expanded form: keys and values rebuilt from the latent), no
batching, no kernels, nothing imported from the program.

Block (x of width `hidden_size`, RMSNorm with a gain and no bias, eps
`rms_norm_eps`, no bias anywhere):
    h = x + MLA(norm1(x));  y = h + FFN(norm2(h));  logits = normf(y) @ head
MLA: c_q = rms(x wq_a); q = c_q wq_b -> heads x [q_nope ; q_rope];
    [c_kv ; k_r] = x wkv_a; c_kv = rms(c_kv); k_rope = RoPE(k_r) (one per
    token, shared by all heads); q_rope = RoPE(q_rope); k_nope = c_kv wk_b,
    v = c_kv wv_b per head; score = (q_nope.k_nope + q_rope.k_rope) *
    (nope + rope)^-0.5 * m^2, m = 0.1 mscale_all_dim ln(factor) + 1; causal
    softmax; out = concat_h(p v) wo.
RoPE is YaRN over `qk_rope_head_dim`: frequencies theta_i = base^(-2i/dim);
    low, high = floor, ceil of dim ln(orig / (beta 2 pi)) / (2 ln base) at
    beta_fast, beta_slow, clamped to [0, dim-1]; r_i = clip((i - low) / (high
    - low), 0, 1); the frequency used is theta_i (1 - r_i) + theta_i / factor
    r_i; cos and sin are scaled by yarn_mscale(factor, mscale) /
    yarn_mscale(factor, mscale_all_dim). Rotate-half.
FFN of the first `first_k_dense_replace` layers: w_down(silu(x w_gate) * x
    w_up). Of the others, in float32: s = sigmoid(x router); selection score s
    + bias; `n_group` groups, a group's score the sum of its two largest
    selection scores; the best `topk_group` groups stay; among their experts
    the `num_experts_per_tok` largest selection scores win (ties to the lower
    index); w_e = s_e / (sum of the winners' s + 1e-20) * routed_scaling_factor;
    FFN(x) = sum over winners HELD HERE of w_e E_e(x) + E_shared(x).

The share: `experts_held` = [lo, hi) of the deployment's routed experts are
present (their weights are the stacked `we_*`, expert lo first). The router
scores all `n_routed_experts_published`; what absent winners would add is
left out, and that partial sum goes on to the next layer.

Departures shared with the system under test (the configuration file lists
them under `assumed`): rotate-half pair order instead of the checkpoint's
interleaved one, and the key-value up-projection held as its two halves
`wk_b`, `wv_b` (both fixed column permutations of random weights); the
multi-token prediction module is not served.

`weights` is {"embed", "head", "normf_g", "layers": [{norm1_g, norm2_g,
q_norm_g, kv_norm_g, wq_a, wq_b, wkv_a, wk_b, wv_b, wo, and w_gate, w_up,
w_down or router, router_bias, ws_gate, ws_up, ws_down, we_gate, we_up,
we_down}]} in any float dtype. Every matrix is upcast to float32 inside a call
of its own, one expert at a time, so the reference fits beside bf16 weights
that fill most of the chip.

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


def _yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_tables(config, n):
    """cos, sin (n, qk_rope_head_dim) float32 for positions 0..n-1."""
    dim, base = config["qk_rope_head_dim"], float(config["rope_theta"])
    sc = config["rope_scaling"]
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    theta = base ** (-2.0 * i / dim)

    def correction(beta):
        return dim * math.log(sc["original_max_position_embeddings"]
                              / (beta * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(sc["beta_fast"])), 0)
    high = min(math.ceil(correction(sc["beta_slow"])), dim - 1)
    r = jnp.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    freq = theta * (1 - r) + theta / sc["factor"] * r
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freq[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    m = _yarn_mscale(sc["factor"], sc["mscale"]) \
        / _yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    return jnp.cos(ang) * m, jnp.sin(ang) * m


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


@functools.partial(jax.jit, static_argnames=("scale",))
def _attend(q_nope, q_rope, k_nope, k_rope, v, scale):
    """Causal attention, a block of queries at a time. q_nope (S, H, dn),
    q_rope (S, H, dr), k_nope (S, H, dn), k_rope (S, dr), v (S, H, dv)."""
    S = q_nope.shape[0]
    keys = jnp.arange(S)
    out = []
    for lo in range(0, S, Q_BLOCK):
        hi = min(lo + Q_BLOCK, S)
        s = (jnp.einsum("qhd,khd->hqk", q_nope[lo:hi], k_nope,
                        precision=HIGHEST)
             + jnp.einsum("qhr,kr->hqk", q_rope[lo:hi], k_rope,
                          precision=HIGHEST)) * scale
        seen = keys[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST))
    return jnp.concatenate(out, 0)


def _top(x, k):
    """Indices of the k largest along the last axis, ties to the lower
    index."""
    return jnp.argsort(-x, axis=-1, stable=True)[..., :k]


def router_scores(x, router):
    """(N, D) float32 -> sigmoid scores (N, experts) float32."""
    return jax.nn.sigmoid(jnp.matmul(x, router.astype(jnp.float32),
                                     precision=HIGHEST))


def selected(sel, n_group, topk_group, top_k):
    """Selection scores (N, experts) -> winners (N, top_k): the groups
    scored by their two largest, the best `topk_group` kept, the `top_k`
    largest among their experts."""
    N = sel.shape[0]
    groups = sel.reshape(N, n_group, -1)
    two = jnp.take_along_axis(groups, _top(groups, 2), axis=-1).sum(-1)
    kept = jnp.zeros((N, n_group), bool).at[
        jnp.arange(N)[:, None], _top(two, topk_group)].set(True)
    sel = jnp.where(jnp.repeat(kept, groups.shape[-1], axis=1), sel, -jnp.inf)
    return _top(sel, top_k)


@functools.partial(jax.jit, static_argnames=("n_group", "topk_group", "top_k",
                                             "scale"))
def route(x, router, bias, n_group, topk_group, top_k, scale):
    """(N, D) float32 -> winners (N, top_k) int32 and their weights."""
    s = router_scores(x, router)
    idx = selected(s + bias.astype(jnp.float32), n_group, topk_group, top_k)
    won = jnp.take_along_axis(s, idx, axis=-1)
    return idx.astype(jnp.int32), \
        won / (won.sum(-1, keepdims=True) + 1e-20) * scale


def experts_held(config):
    """[lo, hi) of the deployment's routed experts present here."""
    per = config["n_routed_experts"]
    return config["expert_rank"] * per, (config["expert_rank"] + 1) * per


def moe(x, lw, config, bits, counts=None):
    """The expert layer over (N, D): every held expert over every token,
    weighted by what the router gave it (0 where it did not win). `counts`,
    if a list, receives the rows per held expert."""
    idx, w = route(x, lw["router"], lw["router_bias"],
                   n_group=config["n_group"], topk_group=config["topk_group"],
                   top_k=config["num_experts_per_tok"],
                   scale=float(config["routed_scaling_factor"]))
    lo, hi = experts_held(config)
    out = _swiglu(x, lw["ws_gate"], lw["ws_up"], lw["ws_down"], bits)
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


def layer(x, lw, index, config, cos, sin, bits, counts=None):
    S = x.shape[0]
    H, eps = config["num_attention_heads"], float(config["rms_norm_eps"])
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, r = config["v_head_dim"], config["kv_lora_rank"]
    h = _norm(x, lw["norm1_g"], eps)
    c_q = _norm(_proj(h, lw["wq_a"], bits), lw["q_norm_g"], eps)
    q = _proj(c_q, lw["wq_b"], bits).reshape(S, H, dn + dr)
    kv = _proj(h, lw["wkv_a"], bits)
    c_kv = _norm(kv[:, :r], lw["kv_norm_g"], eps)
    k_rope = _rope(kv[:, r:], cos, sin)
    q_rope = _rope(q[..., dn:], cos[:, None], sin[:, None])
    k_nope = _proj(c_kv, lw["wk_b"], bits).reshape(S, H, dn)
    v = _proj(c_kv, lw["wv_b"], bits).reshape(S, H, dv)
    m = _yarn_mscale(config["rope_scaling"]["factor"],
                     config["rope_scaling"]["mscale_all_dim"])
    att = _attend(q[..., :dn], q_rope, k_nope, k_rope, v,
                  scale=(dn + dr) ** -0.5 * m * m)
    x = x + _proj(att.reshape(S, H * dv), lw["wo"], bits)
    h = _norm(x, lw["norm2_g"], eps)
    if index < config["first_k_dense_replace"]:
        return x + _swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], bits)
    return x + moe(h, lw, config, bits, counts)


def logits(weights, config, tokens, weight_bits=None, counts=None):
    """(S,) int tokens -> (S, vocab) float32 logits, one full causal
    forward. Position i's row scores the token at position i+1."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = weights["embed"][tokens].astype(jnp.float32)
    cos, sin = rope_tables(config, tokens.shape[0])
    for i, lw in enumerate(weights["layers"]):
        x = layer(x, lw, i, config, cos, sin, weight_bits, counts)
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
