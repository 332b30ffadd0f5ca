"""Plain reference for the `nemotron_h_lm` family: the decoder of the
published `nemotron_h` model (NVIDIA Nemotron-H, arXiv:2504.03624; Nemotron 3
Nano; the model of that name in the transformers library, and the
configuration's `config.json`), in float32 `jax.numpy` with matmul precision
"highest". No cache, no batching, no kernels, no chunks, nothing imported
from the program.

Rows x of width `hidden_size`; RMSNorm with a gain and no bias, eps
`layer_norm_epsilon`; no bias on any projection; the embedding is not
scaled; the head is a matrix of its own. Layer i is ONE mixer, named by
letter i of `hybrid_override_pattern`:
    h = rms(x; norm_g);   x = x + mixer_i(h)
    M, the Mamba-2 mixer: Hs = mamba_num_heads heads of P = mamba_head_dim,
    state N = ssm_state_size a head, G = n_groups groups:
      h w_in = z (Hs P) | x (Hs P) | B (G N) | C (G N) | dt (Hs)
      x|B|C -> silu(causal depthwise convolution of conv_kernel taps + conv_b):
          out[t] = sum_k conv_w[k] in[t - (taps - 1) + k], zeros before position 0
      dt = softplus(dt + dt_bias);  A = -exp(A_log)            (a head each)
      head k with its group's B, C (heads 0..Hs/G-1 group 0, and so on):
          H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t    (H is P x N, H_{-1} = 0)
          y_t = H_t C_t + D x_t
      y = rms over each group's Hs P / G values of (y * silu(z)), gain ssm_norm_g
          (the gate before the norm);  out = y w_out
    E, the expert layer: s = sigmoid(h router) in float32; the
      num_experts_per_tok largest of s + router_bias over all experts (one
      group; ties to the lower index); weights the winners' s, normalised,
      times routed_scaling_factor;  expert_e(h) = relu(h we_up[e])^2 we_down[e],
      the shared expert the same over ws_up, ws_down (mlp_hidden_act relu2);
      out = sum over (winners held here) w_e expert_e(h) + shared(h)
    *, attention: q = h wq -> H heads of head_dim;  k, v -> Hkv heads;
      o = causal softmax(q k^T / sqrt(head_dim)) v, query head j reading KV
      head j // (H / Hkv);  out = o wo.  No positions are applied.
    logits = rms(x; normf_g) head
The recurrence is ONE `lax.scan` over positions, as written above.

One chip of an expert-parallel deployment holds `n_routed_experts` of the
router's `n_routed_experts_published` (rank `expert_rank`); the router scores
all of them and what the absent experts would add is left out, as in the
program.

Departures from the published model, shared with the system under test (the
configuration file lists them under `assumed`): random weights from the seed
(`A_log`, `dt_bias`, `D` and the convolution as the Mamba-2 code initialises
them); dt has no upper limit (`time_step_limit` (0, inf)); no rotary turn of
q or k (the published code applies none; `rope_theta` is unused).

`weights` is {"embed", "head", "normf_g", "layers": [{norm_g and, by letter,
M: w_in, conv_w (taps, channels), conv_b, dt_bias, A_log, D, ssm_norm_g,
w_out; E: router, router_bias, ws_up, ws_down, we_up (held, d, f), we_down;
*: wq, wk, wv, wo}]} in any float dtype. Every matrix is upcast to float32
inside a call of its own, a held expert at a time, attention scores one
block of queries at a time and the head is taken a slice of its columns at
a time (`served_gaps` never holds a float32 copy of the head nor all the
logits), so a 4,096-position forward fits beside bf16 weights that fill
half the chip.

`weight_bits=8` is the control: every weight matmul computed in int8 (the
weight rounded per output channel, the activation per row); the router, the
convolution, the recurrence and attention's own two products stay in
float32.
"""
import functools

import jax
import jax.numpy as jnp

from chipbench.reference.afmoe_lm import _attend, _norm, _proj, route
from chipbench.reference.falcon_h1_lm import (_conv_silu, _gated_group_norm,
                                              _recurrence, head_at, head_best)
from chipbench.reference.transformer_lm import _mm, pad_len


def segments(config):
    """Widths of z | x | B | C | dt in the state-space projection."""
    d_ssm = config["mamba_num_heads"] * config["mamba_head_dim"]
    gn = config["n_groups"] * config["ssm_state_size"]
    return d_ssm, d_ssm, gn, gn, config["mamba_num_heads"]


def mixer(h, lw, config, bits):
    """The Mamba-2 mixer over normed rows h (S, hidden_size)."""
    S = h.shape[0]
    Hs, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
    proj = _proj(h, lw["w_in"], bits)
    parts, lo = [], 0
    for width in segments(config):
        parts.append(proj[:, lo:lo + width])
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
                          groups=G, eps=float(config["layer_norm_epsilon"]))
    return _proj(y, lw["w_out"], bits)


def attention(h, lw, config, bits):
    S = h.shape[0]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    Dh = config["head_dim"]
    q = _proj(h, lw["wq"], bits).reshape(S, H, Dh)
    k = _proj(h, lw["wk"], bits).reshape(S, Hkv, Dh)
    v = _proj(h, lw["wv"], bits).reshape(S, Hkv, Dh)
    return _proj(_attend(q, k, v, window=0).reshape(S, H * Dh), lw["wo"], bits)


@functools.partial(jax.jit, static_argnames=("bits",))
def _relu2(x, w_up, w_down, bits):
    return _mm(jnp.square(jax.nn.relu(_mm(x, w_up, bits))), w_down, bits)


def experts_held(config):
    """[lo, hi) of the deployment's routed experts present here."""
    per = config["n_routed_experts"]
    return config["expert_rank"] * per, (config["expert_rank"] + 1) * per


def moe(x, lw, config, bits, counts=None, shared=True):
    """The expert layer over (N, D): every held expert over every token,
    weighted by what the router gave it (0 where it did not win). `counts`,
    if a list, receives the rows per held expert; `shared` False leaves the
    shared expert out (the test that adds the ranks' parts up counts it
    once)."""
    idx, w = route(x, lw["router"], lw["router_bias"],
                   top_k=config["num_experts_per_tok"],
                   scale=float(config["routed_scaling_factor"]))
    lo, hi = experts_held(config)
    out = _relu2(x, lw["ws_up"], lw["ws_down"], bits) if shared \
        else jnp.zeros_like(x)
    rows = []
    for e in range(lo, hi):
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        rows.append(jnp.sum(idx == e, axis=-1))
        out = out + w_e[:, None] * _relu2(x, lw["we_up"][e - lo],
                                          lw["we_down"][e - lo], bits)
    if counts is not None:
        counts.append(jnp.stack(rows, 1))          # (N, held)
    return out


def layer(x, lw, letter, config, bits, counts=None, zero_state=False):
    """`zero_state` leaves a state-space mixer out (the test that shows
    the state matters)."""
    h = _norm(x, lw["norm_g"], float(config["layer_norm_epsilon"]))
    if letter == "M":
        return x if zero_state else x + mixer(h, lw, config, bits)
    if letter == "E":
        return x + moe(h, lw, config, bits, counts)
    return x + attention(h, lw, config, bits)


def trunk(weights, config, tokens, weight_bits=None, counts=None,
          zero_state=False):
    """(S,) int tokens, S a multiple of 128 -> the last norm's output (S,
    hidden_size) float32: what the head scores."""
    x = weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for letter, lw in zip(config["hybrid_override_pattern"],
                          weights["layers"], strict=True):
        x = layer(x, lw, letter, config, weight_bits, counts, zero_state)
    return _norm(x, weights["normf_g"], float(config["layer_norm_epsilon"]))


def logits(weights, config, tokens, weight_bits=None, counts=None,
           zero_state=False):
    """(S,) int tokens -> (S, vocab) float32 logits, one full causal
    forward. Position i's row scores the token at position i+1."""
    return _proj(trunk(weights, config, tokens, weight_bits, counts,
                       zero_state), weights["head"], weight_bits)


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
    x = trunk(weights, config, toks)
    if control_bits is None:
        judged = jnp.zeros((S,), jnp.int32).at[n - 1:n - 1 + m].set(
            jnp.asarray(served, jnp.int32))
    else:
        low = trunk(weights, config, toks, weight_bits=control_bits)
        judged = head_best(low, weights["head"], 1.0, control_bits)[1]
    best, _ = head_best(x, weights["head"], 1.0)
    return (best - head_at(x, weights["head"], 1.0, judged))[n - 1:n - 1 + m]
