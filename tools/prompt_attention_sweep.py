#!/usr/bin/env python3
"""The prompt-attention kernel against XLA's `banded_attention`, alone on
the chip, bucket by bucket and block size by block size:

    python3 tools/prompt_attention_sweep.py [--shapes trinity,falcon,opt] \\
        [--buckets 256,512,...] [--blocks 512x1024,256x1024,...] [--reps 20]

For every shape (query heads on cached heads, the window) and bucket it
times both at `length` = three quarters of the bucket (the mean prompt of
a power-of-two bucket) and at the whole bucket (`timed`), and prints one
JSON line a measurement: the milliseconds of each, and the largest
difference between the two over the rows below `length`. The numbers `Q_BLOCK`, `K_BLOCK` and
`MIN_BUCKET` of ops/pallas_prompt_attention.py came from it (PERF.md §6,
PR 41). Needs a TPU.
"""
import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: name -> (query heads, cached heads, head_dim, windows)
SHAPES = {"trinity": (48, 8, 128, (4096, 0)),
          "falcon": (20, 4, 128, (0,)),
          "opt": (32, 32, 128, (0,))}


def timed(fn, args, reps):
    """Milliseconds a call: the median of five rounds of `reps` calls
    sent one behind the other and waited for together, so that the
    host's turn (0.6 ms a call that is waited for alone, as long as a
    bucket of 1,024 takes) is hidden behind the device."""
    import jax
    jax.block_until_ready(fn(*args))
    rounds = []
    for _ in range(5):
        t = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        rounds.append((time.perf_counter() - t) / reps)
    return 1e3 * statistics.median(rounds)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="trinity,falcon,opt")
    ap.add_argument("--buckets", default="256,512,1024,2048,4096,8192")
    ap.add_argument("--blocks", default="")
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args(argv)

    import numpy as np
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models.afmoe import banded_attention
    from mxnet_tpu.ops import pallas_prompt_attention as pa
    from mxnet_tpu.serving.kv_cache import PROMPT_Q_BLOCK

    if jax.default_backend() != "tpu":
        raise SystemExit("prompt_attention_sweep: jax found no TPU; "
                         "nothing was measured")
    blocks = [tuple(int(n) for n in b.split("x"))
              for b in args.blocks.split(",") if b] or [None]
    for name in args.shapes.split(","):
        H, Hkv, Dh, windows = SHAPES[name]
        for S in (int(n) for n in args.buckets.split(",")):
            keys = jax.random.split(jax.random.PRNGKey(S), 3)
            q, k, v = (jax.random.normal(key, (S, h, Dh), jnp.bfloat16)
                       for key, h in zip(keys, (H, Hkv, Hkv)))
            for window in windows:
                if window >= S and window:
                    continue            # the same band as no window
                xla = jax.jit(lambda q, k, v, w=window: banded_attention(
                    q, k, v, w, PROMPT_Q_BLOCK))
                xla_ms = timed(xla, (q, k, v), args.reps)
                ref = np.asarray(xla(q, k, v), np.float32)
                for bl in blocks:
                    bl = bl or pa.block_sizes(S)
                    if bl[0] > S or bl[1] > S:
                        continue
                    kernel = jax.jit(
                        lambda q, k, v, n, w=window, bl=bl:
                        pa.prompt_attention(q, k, v, n, window=w, blocks=bl))
                    for length in (3 * S // 4, S):
                        n = jnp.int32(length)
                        try:
                            ms = timed(kernel, (q, k, v, n), args.reps)
                            out = np.asarray(kernel(q, k, v, n), np.float32)
                            gap = float(np.abs(out - ref)[:length].max())
                        except Exception as e:      # a refusal is a result
                            ms, gap = None, "%s: %s" % (
                                type(e).__name__, str(e)[:300])
                        print(json.dumps({
                            "shape": name, "bucket": S, "window": window,
                            "blocks": list(bl), "length": length,
                            "xla_ms": xla_ms, "kernel_ms": ms,
                            "max_gap": gap}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
