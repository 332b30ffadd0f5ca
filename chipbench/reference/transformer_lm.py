"""Plain reference for the `transformer_lm` family: a decoder-only
pre-LayerNorm transformer as OPT (Zhang et al., arXiv:2205.01068) describes
it, in float32 `jax.numpy` with matmul precision "highest". No cache, no
batching, no kernels, nothing imported from the program.

Departures from the published OPT block, shared with the system under test
(listed in the configuration file under `assumed`):
  1. no bias terms on the projections and the feed-forward layers;
  2. the output head is a matrix of its own, not the transposed embedding;
  3. learned absolute positions start at 0 (OPT offsets them by 2).

Weight layout (a convention of the weights the benchmark makes, not a
choice of the program): `wqkv` is (d, 3d) with the columns [q | k | v], each
third head-major (`head h` owns columns h*dh:(h+1)*dh); `wo` is (d, d);
`w1` (d, ffn), `w2` (ffn, d); ReLU between them; LayerNorm eps 1e-5.

`weights` is {"embed", "pos", "lnf_g", "lnf_b", "head", "layers": [ {ln1_g,
ln1_b, wqkv, wo, ln2_g, ln2_b, w1, w2}, ... ]} in any float dtype; each
layer is upcast to float32 inside its own call, so the reference fits
beside bf16 weights of a model that fills most of the chip.

`weight_bits=8` is the control of "How correct is decided": the same
forward with every weight matmul computed in int8, the step below bfloat16
that a later PR would be tempted by: the weight rounded per output channel
and the activation per row (symmetric, scale = max|.|/127); attention's own
two products stay in float32.
"""
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


def _round(t, bits, axis):
    t = t.astype(jnp.float32)
    if bits is None:
        return t
    top = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(t), axis=axis, keepdims=True), 1e-30) / top
    return jnp.clip(jnp.round(t / scale), -top, top) * scale


def _mm(x, w, bits=None):
    """x @ w in float32; with `bits`, both operands rounded first."""
    return jnp.matmul(_round(x, bits, -1), _round(w, bits, 0), precision=HIGHEST)


def _layer_norm(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_heads", "weight_bits"))
def _layer(x, lw, n_heads, weight_bits):
    S, D = x.shape
    dh = D // n_heads
    h = _layer_norm(x, lw["ln1_g"], lw["ln1_b"])
    qkv = _mm(h, lw["wqkv"], weight_bits)
    q, k, v = (t.reshape(S, n_heads, dh).transpose(1, 0, 2)
               for t in jnp.split(qkv, 3, axis=-1))
    s = jnp.einsum("hqd,hkd->hqk", q, k, precision=HIGHEST) / jnp.sqrt(
        jnp.float32(dh))
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    att = jnp.einsum("hqk,hkd->hqd", p, v, precision=HIGHEST)
    x = x + _mm(att.transpose(1, 0, 2).reshape(S, D), lw["wo"], weight_bits)
    h = _layer_norm(x, lw["ln2_g"], lw["ln2_b"])
    return x + _mm(jax.nn.relu(_mm(h, lw["w1"], weight_bits)), lw["w2"],
                   weight_bits)


@jax.jit
def _embed(embed, pos, tokens):
    return embed[tokens].astype(jnp.float32) \
        + pos[:tokens.shape[0]].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("weight_bits",))
def _head(x, g, b, head, weight_bits):
    return _mm(_layer_norm(x, g, b), head, weight_bits)


def logits(weights, config, tokens, weight_bits=None):
    """(S,) int tokens -> (S, vocab) float32 logits, one full causal
    forward. Position i's row scores the token at position i+1."""
    x = _embed(weights["embed"], weights["pos"], jnp.asarray(tokens, jnp.int32))
    for lw in weights["layers"]:
        x = _layer(x, lw, n_heads=int(config["num_attention_heads"]),
                   weight_bits=weight_bits)
    return _head(x, weights["lnf_g"], weights["lnf_b"], weights["head"],
                 weight_bits=weight_bits)


@jax.jit
def _gaps(ref_logits, served, first, count):
    """Per served token, how far its reference logit lies below the
    reference's best at that position. `served` is padded to the row count;
    rows outside [first, first+count) give 0."""
    rows = jnp.arange(ref_logits.shape[0])
    live = (rows >= first) & (rows < first + count)
    tok = jnp.take_along_axis(ref_logits, served[:, None], axis=1)[:, 0]
    return jnp.where(live, ref_logits.max(-1) - tok, 0.0)


def pad_len(n, floor=128):
    """Sequence lengths are padded to a power of two so that a run compiles
    a handful of reference programs; causal attention keeps the padding
    from touching the real positions."""
    p = floor
    while p < n:
        p *= 2
    return p


def served_gaps(weights, config, prompt, served, control_bits=None):
    """The number `correct` compares for one finished request: for every
    served token, the gap between the reference's best logit at that
    position and the reference's logit of the token that was served
    (0 where the server agrees with the reference's argmax). Returns a
    float32 vector of len(served).

    With `control_bits`, the tokens judged are not `served` but those the
    lower-precision forward puts first at the same positions over the same
    prompt and served tokens (the control: it need not decode)."""
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
