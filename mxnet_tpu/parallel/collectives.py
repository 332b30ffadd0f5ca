"""Collective primitives over the mesh.

Parity: reference `src/kvstore/comm.h` (device reduce/broadcast) and the NCCL
calls in kvstore_nccl.h — here they are XLA collectives usable inside
shard_map/pjit: psum rides ICI, ppermute builds rings, reduce_scatter +
all_gather decompose the allreduce the way tuned NCCL rings do (but the
compiler schedules them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def allreduce(x, axis_name):
    """Sum-allreduce over a mesh axis (inside shard_map/pjit)."""
    return lax.psum(x, axis_name)


def allreduce_mean(x, axis_name):
    return lax.pmean(x, axis_name)


def reduce_scatter(x, axis_name, scatter_dim=0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dim,
                            tiled=True)


def all_gather(x, axis_name, gather_dim=0):
    return lax.all_gather(x, axis_name, axis=gather_dim, tiled=True)


def ring_permute(x, axis_name, shift=1):
    """Send each shard to the next device on the ring (ppermute)."""
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name, split_axis, concat_axis):
    """The Ulysses-style sequence<->head reshard primitive."""
    return lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=True)


def compressed_allreduce_2bit(x, axis_name, threshold=0.5, residual=None):
    """2-bit-compressed allreduce with error feedback — the reference's
    gradient_compression.h algorithm lifted into the collective layer for
    bandwidth-bound (DCN) axes. Returns (reduced, new_residual)."""
    g = x if residual is None else x + residual
    q = jnp.where(g >= threshold, threshold,
                  jnp.where(g <= -threshold, -threshold, 0.0)).astype(x.dtype)
    new_residual = g - q
    return lax.psum(q, axis_name), new_residual
