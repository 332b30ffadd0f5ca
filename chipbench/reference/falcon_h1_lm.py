"""Plain reference for the `falcon_h1_lm` family: the decoder block of the
published `falcon_h1` model (TII Falcon-H1; the model of that name in the
transformers library, and the configuration's `config.json`), in float32
`jax.numpy` with matmul precision "highest". No cache, no batching, no
kernels, no chunks, nothing imported from the program.

Rows x of width `hidden_size`; RMSNorm with a gain and no bias, eps
`rms_norm_eps`; no bias on any projection; the head is a matrix of its own.
In EVERY layer one norm feeds two mixers side by side:
    e  = embed[token] * embedding_multiplier;   h = rms(x; norm_in_g)
    attention:
      a = h * attention_in_multiplier
      q = a wq -> H heads of head_dim;  k = (a wk) * key_multiplier -> Hkv heads;
      v = a wv -> Hkv heads;  q, k = RoPE(q, k), frequencies
      rope_theta^(-2i / head_dim) over the whole head, rotate-half
      o = causal softmax(q k^T / sqrt(head_dim)) v, query head i reading KV
          head i // (H / Hkv);  out_a = (o wo) * attention_out_multiplier
    state-space mixer (Mamba-2), Hs = mamba_n_heads heads of P = mamba_d_head,
    state N = mamba_d_state a head, G = mamba_n_groups groups:
      (h * ssm_in_multiplier) w_in = z (d_ssm) | x (d_ssm) | B (G N) | C (G N) |
          dt (Hs), each segment times its entry of ssm_multipliers
      x|B|C -> silu(causal depthwise convolution of mamba_d_conv taps + conv_b):
          out[t] = sum_k conv_w[k] in[t - (taps - 1) + k], zeros before position 0
      dt = softplus(dt + dt_bias);  A = -exp(A_log)            (a head each)
      head h with its group's B, C (heads 0..Hs/G-1 group 0, and so on):
          H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t    (H is P x N, H_{-1} = 0)
          y_t = H_t C_t + D x_t
      y = rms over each group's d_ssm / G values of (y * silu(z)), gain ssm_norm_g
          (mamba_norm_before_gate false);  out_s = (y w_out) * ssm_out_multiplier
    x  = x + out_a + out_s;   m = rms(x; norm_mlp_g)
    x  = x + ((silu((m w_gate) * mlp_multipliers[0]) * (m w_up)) w_down)
             * mlp_multipliers[1]
    logits = (rms(x; normf_g) head) * lm_head_multiplier
The recurrence is ONE `lax.scan` over positions, as written above.

Departures from the published model, shared with the system under test (the
configuration file lists them under `assumed`): random weights from the seed
(`A_log`, `dt_bias`, `D` as the Mamba-2 code initialises them); dt has no
upper limit (`time_step_limit` (0, inf)).

`weights` is {"embed", "head", "normf_g", "layers": [{norm_in_g, norm_mlp_g,
ssm_norm_g, wq, wk, wv, wo, w_in, conv_w (taps, channels), conv_b, dt_bias,
A_log, D, w_out, w_gate, w_up, w_down}]} in any float dtype. Every matrix is
upcast to float32 inside a call of its own, attention scores one block of
queries at a time and the head is taken a slice of its columns at a time
(`served_gaps` never holds a float32 copy of the head nor all the logits), so
a 1,024-position forward fits beside bf16 weights that fill most of the chip.

`weight_bits=8` is the control: every weight matmul computed in int8 (the
weight rounded per output channel, the activation per row); the convolution,
the recurrence and attention's own two products stay in float32.
"""
import functools

import jax
import jax.numpy as jnp

from chipbench.reference.afmoe_lm import (_attend, _norm, _proj, _rope,
                                          rope_tables)
from chipbench.reference.transformer_lm import _mm, pad_len


def segments(config):
    """Widths of z | x | B | C | dt in the state-space projection."""
    gn = config["mamba_n_groups"] * config["mamba_d_state"]
    return (config["mamba_d_ssm"], config["mamba_d_ssm"], gn, gn,
            config["mamba_n_heads"])


@functools.partial(jax.jit, static_argnames=("widths",))
def _conv_silu(xbc, conv_w, conv_b, widths):
    """silu(causal depthwise convolution + bias) of (S, channels), split
    into x | B | C."""
    S, taps = xbc.shape[0], conv_w.shape[0]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    out = sum(padded[k:k + S] * conv_w[k].astype(jnp.float32)
              for k in range(taps)) + conv_b.astype(jnp.float32)
    out = jax.nn.silu(out)
    return (out[:, :widths[0]], out[:, widths[0]:widths[0] + widths[1]],
            out[:, widths[0] + widths[1]:])


@jax.jit
def _recurrence(x, dt, A, Bh, Ch, D):
    """x (S, Hs, P); dt (S, Hs); A, D (Hs,); Bh, Ch (S, Hs, N) each head's
    group's. One step a position."""
    def step(h, at):
        x_t, dt_t, b_t, c_t = at
        h = jnp.exp(dt_t * A)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], -1) + D[:, None] * x_t
    h0 = jnp.zeros(x.shape[1:] + Bh.shape[-1:], jnp.float32)
    return jax.lax.scan(step, h0, (x, dt, Bh, Ch))[1]


@functools.partial(jax.jit, static_argnames=("groups", "eps"))
def _gated_group_norm(y, z, gain, groups, eps):
    y = (y * jax.nn.silu(z)).reshape(y.shape[0], groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return y.reshape(z.shape) * gain.astype(jnp.float32)


def mixer(h, lw, config, bits):
    """The state-space mixer over normed rows h (S, hidden_size): its
    output before the residual."""
    S = h.shape[0]
    Hs, P = config["mamba_n_heads"], config["mamba_d_head"]
    G, N = config["mamba_n_groups"], config["mamba_d_state"]
    proj = _proj(h * float(config["ssm_in_multiplier"]), lw["w_in"], bits)
    parts, lo = [], 0
    for width, mult in zip(segments(config), config["ssm_multipliers"]):
        parts.append(proj[:, lo:lo + width] * float(mult))
        lo += width
    z, x, b, c, dt = parts
    x, b, c = _conv_silu(jnp.concatenate([x, b, c], -1), lw["conv_w"],
                         lw["conv_b"], widths=(x.shape[1], b.shape[1]))
    dt = jax.nn.softplus(dt + lw["dt_bias"].astype(jnp.float32))
    per_head = lambda t: jnp.repeat(t.reshape(S, G, N), Hs // G, axis=1)
    y = _recurrence(x.reshape(S, Hs, P), dt,
                    -jnp.exp(lw["A_log"].astype(jnp.float32)),
                    per_head(b), per_head(c), lw["D"].astype(jnp.float32))
    y = _gated_group_norm(y.reshape(S, Hs * P), z, lw["ssm_norm_g"],
                          groups=G, eps=float(config["rms_norm_eps"]))
    return _proj(y, lw["w_out"], bits) * float(config["ssm_out_multiplier"])


def attention(h, lw, config, cos, sin, bits):
    S = h.shape[0]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    Dh = config["head_dim"]
    a = h * float(config["attention_in_multiplier"])
    q = _proj(a, lw["wq"], bits).reshape(S, H, Dh)
    k = (_proj(a, lw["wk"], bits) * float(config["key_multiplier"])) \
        .reshape(S, Hkv, Dh)
    v = _proj(a, lw["wv"], bits).reshape(S, Hkv, Dh)
    q, k = _rope(q, cos[:, None], sin[:, None]), \
        _rope(k, cos[:, None], sin[:, None])
    o = _attend(q, k, v, window=0).reshape(S, H * Dh)
    return _proj(o, lw["wo"], bits) * float(config["attention_out_multiplier"])


@functools.partial(jax.jit, static_argnames=("gate_mult", "bits"))
def _gated_ffn(m, w_gate, w_up, w_down, gate_mult, bits):
    return _mm(jax.nn.silu(_mm(m, w_gate, bits) * gate_mult)
               * _mm(m, w_up, bits), w_down, bits)


def layer(x, lw, config, cos, sin, bits, zero_state=False):
    """`zero_state` leaves the state-space mixer out (the test that shows
    the state matters)."""
    eps = float(config["rms_norm_eps"])
    h = _norm(x, lw["norm_in_g"], eps)
    x = x + attention(h, lw, config, cos, sin, bits)
    if not zero_state:
        x = x + mixer(h, lw, config, bits)
    m = _norm(x, lw["norm_mlp_g"], eps)
    gate_mult, out_mult = (float(v) for v in config["mlp_multipliers"])
    return x + _gated_ffn(m, lw["w_gate"], lw["w_up"], lw["w_down"],
                          gate_mult=gate_mult, bits=bits) * out_mult


def trunk(weights, config, tokens, weight_bits=None, zero_state=False):
    """(S,) int tokens, S a multiple of 128 -> the last norm's output (S,
    hidden_size) float32: what the head scores."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = weights["embed"][tokens].astype(jnp.float32) \
        * float(config["embedding_multiplier"])
    cos, sin = rope_tables(config, tokens.shape[0])
    for lw in weights["layers"]:
        x = layer(x, lw, config, cos, sin, weight_bits, zero_state)
    return _norm(x, weights["normf_g"], float(config["rms_norm_eps"]))


def logits(weights, config, tokens, weight_bits=None, zero_state=False):
    """(S,) int tokens -> (S, vocab) float32 logits, one full causal
    forward. Position i's row scores the token at position i+1."""
    return _proj(trunk(weights, config, tokens, weight_bits, zero_state),
                 weights["head"], weight_bits) \
        * float(config["lm_head_multiplier"])


# -- the head a slice of its columns at a time --------------------------------

@functools.partial(jax.jit, static_argnames=("width", "bits"))
def _head_slice(x, head, lo, width, bits):
    return _mm(x, jax.lax.dynamic_slice_in_dim(head, lo, width, axis=1), bits)


def _slices(vocab):
    """(how many, how wide): 16 slices where the vocabulary divides."""
    n = next(n for n in (16, 8, 4, 2, 1) if vocab % n == 0)
    return n, vocab // n


def head_best(x, head, mult, bits=None):
    """Per row of x, the best logit and its token, over all of the head."""
    n, width = _slices(head.shape[1])
    best = jnp.full((x.shape[0],), -jnp.inf, jnp.float32)
    arg = jnp.zeros((x.shape[0],), jnp.int32)
    for i in range(n):
        part = _head_slice(x, head, i * width, width, bits) * mult
        top = part.max(-1)
        arg = jnp.where(top > best, i * width + jnp.argmax(part, -1)
                        .astype(jnp.int32), arg)
        best = jnp.maximum(best, top)
    return best, arg


def head_at(x, head, mult, tokens):
    """Per row of x, the logit of that row's token."""
    n, width = _slices(head.shape[1])
    out = jnp.zeros((x.shape[0],), jnp.float32)
    for i in range(n):
        part = _head_slice(x, head, i * width, width, None) * mult
        here = (tokens >= i * width) & (tokens < (i + 1) * width)
        at = jnp.take_along_axis(
            part, jnp.clip(tokens - i * width, 0, width - 1)[:, None], 1)[:, 0]
        out = jnp.where(here, at, out)
    return out


def served_gaps(weights, config, prompt, served, control_bits=None,
                zero_state=False):
    """The number `correct` compares for one finished request: for every
    served token, the gap between the reference's best logit at that
    position and the reference's logit of the token that was served. With
    `control_bits`, the tokens judged are those the lower-precision forward
    puts first at the same positions (the control need not decode)."""
    n, m = len(prompt), len(served)
    S = pad_len(n + m)
    toks = jnp.zeros((S,), jnp.int32).at[:n + m].set(
        jnp.asarray(list(prompt) + list(served), jnp.int32))
    mult = float(config["lm_head_multiplier"])
    x = trunk(weights, config, toks, zero_state=zero_state)
    if control_bits is None:
        judged = jnp.zeros((S,), jnp.int32).at[n - 1:n - 1 + m].set(
            jnp.asarray(served, jnp.int32))
    else:
        low = trunk(weights, config, toks, weight_bits=control_bits)
        judged = head_best(low, weights["head"], mult, control_bits)[1]
    best, _ = head_best(x, weights["head"], mult)
    return (best - head_at(x, weights["head"], mult, judged))[n - 1:n - 1 + m]
