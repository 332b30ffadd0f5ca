"""Pipeline parallelism: GPipe-style microbatch pipelining over a 'pp' mesh
axis.

Capability upgrade over the reference (SURVEY §2.3: absent there — it only
had manual inter-layer placement via group2ctx, graph_executor.cc:314). The
TPU-native formulation: stage parameters are sharded over 'pp' (each rank
holds one stage), microbatches circulate around the ring with ppermute, and
the whole schedule is a lax.scan — so forward AND backward pipeline through
XLA's AD of the scan, no hand-written schedule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _gpipe_local(stage_fn, params_local, x_mb, axis_name):
    """Runs on one pp rank inside shard_map.

    params_local: this rank's stage params, leading stage axis of size 1.
    x_mb: (M, mb, ...) microbatches (replicated across pp).
    Returns (M, mb, ...) outputs of the final stage (replicated).
    """
    params = jax.tree_util.tree_map(lambda a: a[0], params_local)
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    M = x_mb.shape[0]
    T = M + n - 1  # pipeline ticks: fill + drain
    perm = [(i, (i + 1) % n) for i in range(n)]
    zero = jnp.zeros_like(x_mb[0])

    def tick(state, t):
        # rank 0 ingests microbatch t (while t < M), others take the
        # activation handed over from the left neighbour
        inp = jnp.where(t < M, x_mb[jnp.minimum(t, M - 1)], zero)
        cur = jnp.where(idx == 0, inp, state)
        out = stage_fn(params, cur)
        nxt = lax.ppermute(out, axis_name, perm)
        # the final stage emits valid output from tick n-1 onward
        emit = jnp.where((idx == n - 1) & (t >= n - 1), out,
                         jnp.zeros_like(out))
        return nxt, emit

    _, emits = lax.scan(tick, zero, jnp.arange(T))
    outs = lax.dynamic_slice_in_dim(emits, n - 1, M, axis=0)
    # broadcast final-stage outputs to every rank (zeros elsewhere -> psum)
    return lax.psum(outs, axis_name)


def gpipe_apply(stage_fn, stacked_params, x, n_microbatches, mesh,
                axis_name="pp", extra_specs=None):
    """Apply a pipeline of identical stages to x.

    stage_fn(params, x_mb) -> y_mb applies ONE stage (same shape in/out).
    stacked_params: pytree whose leaves have a leading stage axis of size
      mesh.shape[axis_name]; sharded over 'pp' inside.
    x: (B, ...) batch; split into n_microbatches along axis 0.
    Returns (B, ...) outputs of the last stage.
    """
    B = x.shape[0]
    assert B % n_microbatches == 0, "batch must divide into microbatches"
    x_mb = x.reshape((n_microbatches, B // n_microbatches) + x.shape[1:])

    param_specs = jax.tree_util.tree_map(
        lambda a: P(axis_name), stacked_params)
    fn = jax.shard_map(
        functools.partial(_gpipe_local, stage_fn, axis_name=axis_name),
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False)
    out_mb = fn(stacked_params, x_mb)
    return out_mb.reshape((B,) + out_mb.shape[2:])
