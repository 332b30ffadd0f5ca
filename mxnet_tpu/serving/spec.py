"""Draft-model speculative decoding for the paged serving engine.

Decode is one target pass per token per sequence — the last structural
latency lever in the serving stack. Speculation (Leviathan et al.,
arXiv:2211.17192) breaks that coupling: a small DRAFT model proposes k
tokens autoregressively (cheap — the draft is tiny), then the TARGET
scores all k+1 positions in ONE ragged paged pass reusing the chunked
multi-token machinery `prefill_chunk` (engine.py) already proved out against the
live block tables. A verification rule accepts a prefix of the draft so
the emitted distribution is EXACTLY the target's:

* greedy serving path (`greedy_verify`): emit target argmaxes while they
  agree with the draft, stop at the first disagreement (the target's
  argmax at the disagreement position is still a correct emission — it
  was computed from fully-accepted history), plus one "bonus" token when
  every draft token survives. Token-by-token identical to running the
  target alone, so the non-speculative path is the parity ORACLE.
* sampled path (`rejection_sample`): accept draft token d with
  probability min(1, p(d)/q(d)); on rejection sample from the residual
  norm(max(p - q, 0)). Output distribution is exactly p — pinned by
  hand-computed unit tests (the serving loop itself is greedy-only, so
  this lives here as the verified math for samplers built on top).

KV-safety is positional, not transactional: the scoring pass writes k+1
K/V rows at positions n-1..n-1+k, and after accepting m tokens the rows
past n+m hold rejected-draft state. They are UNREACHABLE garbage, never
contamination — the next speculative pass rewrites positions
n+m..n+m+k (a superset of the stale rows) before any attention touches
them, the non-speculative path masks keys past each query's true
position, and the prefix cache only ever indexes `tokens[:-1]`, whose
K/V is accepted history by construction.

The draft here is CACHE-FREE: one jitted full causal forward over the
pow2-bucketed token history per proposal step (site "serving.draft").
That trades draft-side FLOPs for zero draft state — nothing to migrate
on failover (`make_resume` replays ordinary tokens; the draft is rebuilt
from config on the target replica), nothing to shard under tp (draft
replicated, target sharded), and no second block pool to audit.
`MXNET_SPEC_DRAFT_LAYERS=n` builds the draft from the target's own first
n layers (shared embeddings/head), so speculation is reachable from env
vars alone — no second checkpoint required.
"""
import os
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from ..base import MXNetError
from .. import telemetry


def spec_decode_enabled():
    """`MXNET_SPEC_DECODE=1` requests speculative decoding (same
    opt-in shape as MXNET_PAGED_ATTENTION / MXNET_SERVING_TP)."""
    return os.environ.get("MXNET_SPEC_DECODE", "") == "1"


def spec_k(default=4):
    """`MXNET_SPEC_K`: draft tokens proposed per decode iteration.
    The target scores k+1 positions per pass."""
    v = os.environ.get("MXNET_SPEC_K", "")
    return int(v) if v else default


def spec_draft_layers():
    """`MXNET_SPEC_DRAFT_LAYERS=n`: build the draft from the target's
    own first n transformer layers (0/unset = no self-draft)."""
    v = os.environ.get("MXNET_SPEC_DRAFT_LAYERS", "")
    return int(v) if v else 0


def self_draft(params, cfg, n_layers):
    """Truncated self-draft: the first `n_layers` of the target's own
    stack, sharing its embeddings, final norm, and head. Returns a
    `(params, cfg)` pair for `DraftLM` — no second checkpoint, and the
    vocab/max_len eligibility checks hold by construction. Early
    transformer layers carry most next-token signal on small models, so
    this is the zero-infrastructure draft; a separately trained draft
    checkpoint plugs into the same `Engine(draft=...)` seam."""
    if not 1 <= n_layers <= cfg.n_layers:
        raise MXNetError(
            "self_draft: n_layers must be in [1, %d], got %d"
            % (cfg.n_layers, n_layers))
    keep = ("embed", "pos_embed", "lnf_g", "lnf_b", "head")
    prefixes = tuple("layer%d_" % i for i in range(n_layers))
    dparams = {k: v for k, v in params.items()
               if k in keep or k.startswith(prefixes)}
    return dparams, dataclasses.replace(cfg, n_layers=n_layers)


class DraftLM:
    """Cache-free draft model: params + TransformerConfig, one
    instrumented jit (site "serving.draft") running the full causal
    forward over a right-padded (B, S) batch and returning the f32
    logits at each row's true last position. Compile lattice is the
    pow2 length buckets x pow2 batch buckets the engine already uses —
    bounded, AOT-cacheable, and attributed on the compile watchdog."""

    def __init__(self, params, cfg):
        if cfg.n_experts and cfg.moe_top_k:
            raise MXNetError(
                "spec: top-k MoE routing is capacity-dependent across "
                "the token group — padded draft batches would change "
                "real tokens' routing; draft with dense-FFN or "
                "dense-dispatch configs (moe_top_k=0)")
        from ..models.transformer import transformer_apply
        self.params = params
        self.cfg = cfg
        self.vocab = cfg.vocab
        self.max_len = cfg.max_len

        def serving_draft(p, toks, lengths):
            out = transformer_apply(p, toks, cfg)          # (B, S, V)
            idx = (lengths - 1)[:, None, None]
            rows = jnp.take_along_axis(
                out, jnp.broadcast_to(idx, (toks.shape[0], 1,
                                            out.shape[-1])), axis=1)
            return rows[:, 0].astype(jnp.float32)

        self._logits_jit = telemetry.introspect.instrument(
            jax.jit(serving_draft), site="serving.draft", phase="decode",
            argnames=("params", "tokens", "lengths"),
            variant="draft_full")

    def logits_at(self, tokens, lengths):
        """Next-token f32 logits (B, V) at each row's `lengths`-1
        position; `tokens` is right-padded (B, S) int32."""
        return self._logits_jit(self.params, tokens, lengths)


def build_draft(draft, model):
    """Normalize the `Engine(draft=...)` argument into a DraftLM (or
    None). Accepts a DraftLM, a `(params, cfg)` tuple, or anything with
    `.params`/`.cfg` (e.g. a TransformerLM); with draft=None,
    `MXNET_SPEC_DRAFT_LAYERS` builds a truncated self-draft from the
    target's own params when the target exposes them."""
    if draft is None:
        n = spec_draft_layers()
        # a weight-quantized target keeps its f32 originals on
        # `params_f32` — the draft runs the plain dense forward
        # (transformer_apply), so it drafts from those; draft precision
        # only moves the acceptance rate, never the emitted tokens
        src = getattr(model, "params_f32", None)
        if src is None:
            src = getattr(model, "params", None)
        if n and src is not None \
                and getattr(model, "cfg", None) is not None:
            return DraftLM(*self_draft(src, model.cfg, n))
        return None
    if isinstance(draft, DraftLM):
        return draft
    if isinstance(draft, tuple) and len(draft) == 2:
        return DraftLM(draft[0], draft[1])
    if getattr(draft, "params", None) is not None \
            and getattr(draft, "cfg", None) is not None:
        return DraftLM(draft.params, draft.cfg)
    raise MXNetError(
        "Engine(draft=...): expected a DraftLM, a (params, cfg) tuple, "
        "or a model with .params/.cfg, got %r" % (type(draft).__name__,))


def spec_fallback_reason(model, draft, paged):
    """Why speculation must fall back to the verbatim per-token decode
    (None = eligible). Mirrors `tp_fallback_reason` /
    `prefix_cache_fallback`: the flag switches SPEED, never logits, so
    every ineligible config gets a reason string, not an exception."""
    if not getattr(model, "uses_cache", False):
        return ("model family has no paged-cache hooks; speculation "
                "scores k+1 positions against the block pool "
                "(TransformerLM only)")
    if draft is None:
        return ("no draft model: pass Engine(draft=(params, cfg)) or "
                "set MXNET_SPEC_DRAFT_LAYERS=n for a truncated "
                "self-draft")
    if not paged:
        return ("paged attention off/ineligible; the k+1 scoring pass "
                "reuses the chunked multi-token signature against the "
                "live block tables (MXNET_PAGED_ATTENTION=1)")
    if draft.vocab != model.vocab:
        return ("draft vocab %d != target vocab %d — acceptance "
                "compares token ids, so the vocabularies must be "
                "identical" % (draft.vocab, model.vocab))
    if draft.max_len < model.max_len:
        return ("draft max_len %d < target max_len %d — the draft must "
                "reach every position the target can decode"
                % (draft.max_len, model.max_len))
    return None


def greedy_verify(target_argmax, draft_tokens, n_draft):
    """Greedy acceptance for ONE sequence. `target_argmax[j]` is the
    target's argmax given the history plus the first j draft tokens
    (row j of the scoring pass), `draft_tokens[:n_draft]` the draft's
    proposals. Emit target argmaxes while they agree with the draft;
    the first disagreement's argmax is still emitted (it conditions
    only on accepted history), and a full sweep earns the bonus token
    from the last row. Returns (emitted_tokens, n_accepted) with
    1 <= len(emitted) == n_accepted + 1 <= n_draft + 1 — by induction,
    token-identical to running the target greedily one token at a
    time."""
    emitted = []
    for j in range(int(n_draft)):
        a = int(target_argmax[j])
        emitted.append(a)
        if a != int(draft_tokens[j]):
            return emitted, j
    emitted.append(int(target_argmax[int(n_draft)]))
    return emitted, int(n_draft)


def rejection_sample(target_probs, draft_probs, draft_tokens, uniforms,
                     resample_u):
    """Exact-distribution speculative sampling for ONE sequence,
    deterministic given the random draws (so tests pin it against
    hand-computed probabilities). `target_probs` is (k+1, V) rows of
    p_j, `draft_probs` (k, V) rows of q_j, `draft_tokens` (k,) the
    proposals, `uniforms` (k,) the per-position accept draws, and
    `resample_u` the single draw spent by whichever terminal sample
    ends the pass (residual on rejection, bonus p_k on a full sweep).

    Accept d_j when uniforms[j] < min(1, p_j(d)/q_j(d)); on rejection,
    sample from norm(max(p_j - q_j, 0)) by inverse CDF of resample_u.
    Marginalizing over d_j ~ q_j, each emitted token is distributed
    exactly as p_j — the Leviathan et al. identity
    min(p, q) + (1 - sum min(p, q)) * norm(max(p - q, 0)) = p.
    Returns (emitted_tokens, n_accepted)."""
    tp = np.asarray(target_probs, dtype=np.float64)
    qp = np.asarray(draft_probs, dtype=np.float64)
    k = len(draft_tokens)
    emitted = []
    for j in range(k):
        d = int(draft_tokens[j])
        p_d, q_d = tp[j, d], qp[j, d]
        if q_d <= 0.0 or uniforms[j] < min(1.0, p_d / q_d):
            emitted.append(d)
            continue
        resid = np.maximum(tp[j] - qp[j], 0.0)
        tot = resid.sum()
        if tot <= 0.0:
            # p_j == q_j exactly: acceptance probability was 1, so a
            # rejection here means uniforms[j] >= 1 — emit d regardless
            emitted.append(d)
            return emitted, j + 1
        cdf = np.cumsum(resid / tot)
        emitted.append(int(np.searchsorted(cdf, resample_u)))
        return emitted, j
    cdf = np.cumsum(tp[k] / tp[k].sum())
    emitted.append(int(np.searchsorted(cdf, resample_u)))
    return emitted, k
