"""Ring attention: sequence/context parallelism over the mesh.

Capability upgrade over the reference (SURVEY §5.7: absent there — it only
had bucketing + recompute). Long-context training shards the sequence axis
across devices; each device holds a Q block and passes K/V blocks around the
ring (ppermute over ICI) while accumulating attention with a numerically
stable online softmax (flash-attention style running max/denominator).

Communication overlaps compute: block k's K/V transfer is issued while
block k-1's scores are on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _block_attn(q, k, v, m_prev, l_prev, o_prev, scale, mask=None):
    """One online-softmax accumulation step.

    q: [B, H, Tq, D]; k/v: [B, H, Tk, D]; running (m, l, o).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (m == -inf)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1)
    o_new = alpha[..., None] * o_prev + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(p.dtype),
        preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def _ring_body(axis_name, causal, scale, q, k0, v0, q_index):
    """Scan over ring steps; each step attends to the current K/V block then
    rotates it to the neighbour."""
    n = lax.axis_size(axis_name)
    B, H, T, D = q.shape
    m0 = jnp.full((B, H, T), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((B, H, T), dtype=jnp.float32)
    o0 = jnp.zeros((B, H, T, D), dtype=jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, r):
        k, v, m, l, o = carry
        kv_index = (q_index - r) % n  # which shard this K/V block came from
        if causal:
            # block-level causality: attend fully if kv block strictly
            # earlier, diagonal gets a triangular mask, later blocks skipped
            tq = jnp.arange(T)[:, None] + q_index * T
            tk = jnp.arange(T)[None, :] + kv_index * T
            mask = (tk <= tq)[None, None]
        else:
            mask = None
        m2, l2, o2 = _block_attn(q, k, v, m, l, o, scale, mask)
        k2 = lax.ppermute(k, axis_name, perm)
        v2 = lax.ppermute(v, axis_name, perm)
        return (k2, v2, m2, l2, o2), None

    (kf, vf, m, l, o), _ = lax.scan(step, (k0, v0, m0, l0, o0),
                                    jnp.arange(n))
    out = o / jnp.maximum(l[..., None], 1e-20)
    return out.astype(q.dtype)


def ring_attention(q, k, v, axis_name="sp", causal=False, scale=None):
    """Per-shard ring attention; call inside shard_map over `axis_name`.

    q/k/v: [B, H, T_local, D] — the local sequence shard.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    q_index = lax.axis_index(axis_name)
    return _ring_body(axis_name, causal, scale, q, k, v, q_index)


def ring_attention_sharded(mesh, q, k, v, axis_name="sp", causal=False,
                           scale=None):
    """Convenience wrapper: shard the sequence axis over `axis_name` of
    `mesh` and run ring attention. q/k/v: [B, H, T, D] global arrays."""
    spec = P(None, None, axis_name, None)

    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=axis_name, causal=causal,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def attention_reference(q, k, v, causal=False, scale=None):
    """Dense reference implementation (for tests)."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        T = q.shape[2]
        mask = jnp.tril(jnp.ones((T, T), dtype=bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(p.dtype)).astype(q.dtype)
