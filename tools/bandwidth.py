#!/usr/bin/env python
"""Measure transfer and collective bandwidth (parity: reference
tools/bandwidth — the multi-device kvstore allreduce benchmark, recast
for the TPU stack):

  1. host -> device staging bandwidth (device_put + readback),
  2. all-reduce bandwidth over a device mesh (jnp.psum via a jitted
     pmap/shard_map program — the KVStore('tpu') data path).

On one chip (the usual dev setup) the allreduce leg runs over a single
device and reports the degenerate number honestly; on a real multi-chip
mesh it measures ICI. Run with JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8 for a virtual-mesh
sanity check (numbers are host-memory speeds, not ICI).

Timing chains iterations through a data dependency and ends in a scalar
readback, which waits for the device.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def human(bps):
    for unit in ("B/s", "KB/s", "MB/s", "GB/s"):
        if bps < 1024:
            return "%.2f %s" % (bps, unit)
        bps /= 1024.0
    return "%.2f TB/s" % bps


def bench_host_device(jax, jnp, size_mb, iters):
    dev = jax.devices()[0]
    x = np.random.RandomState(0).rand(size_mb * 1024 * 128)  # f64: MB sized
    # warm
    jax.device_put(x, dev).block_until_ready()
    t0 = time.perf_counter()
    acc = 0.0
    for _ in range(iters):
        d = jax.device_put(x, dev)
        acc += float(d[0])  # readback forces completion through the chain
    dt = time.perf_counter() - t0
    return x.nbytes * iters / dt, acc


def bench_allreduce(jax, jnp, size_mb, iters):
    n = len(jax.devices())
    from jax.sharding import Mesh, PartitionSpec as P
    import functools
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    elems = size_mb * 1024 * 256  # f32 elements per MB
    x = jnp.asarray(np.random.RandomState(1).rand(n, elems)
                    .astype(np.float32))

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("dp", None),
                       out_specs=P("dp", None))
    def allreduce(v):
        return jax.lax.psum(v, "dp")

    out = allreduce(x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = allreduce(out * (1.0 / n))  # chained: no overlap illusion
    s = float(jnp.sum(out[:, :1]))
    dt = time.perf_counter() - t0
    # algorithm bytes: each replica contributes size and receives size
    payload = elems * 4
    return payload * iters / dt, n, s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=int, default=16,
                    help="payload per transfer/reduce")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    print("devices:", jax.devices())

    bw, _ = bench_host_device(jax, jnp, args.size_mb, args.iters)
    print("host->device staging : %s (%d MB x %d)"
          % (human(bw), args.size_mb, args.iters))

    bw, n, _ = bench_allreduce(jax, jnp, args.size_mb, args.iters)
    print("allreduce over %d dev : %s per-replica payload bandwidth"
          % (n, human(bw)))
    if n == 1:
        print("(single device: the reduce is a no-op — run on a mesh for "
              "a meaningful number)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
