#!/usr/bin/env python3
"""The grouped-experts kernel against the loop of XLA passes it replaces,
alone on the chip, at the three expert cells' decode and prefill shapes:

    python3 tools/grouped_experts_sweep.py [--shapes nemotron,dsv3,trinity] \\
        [--rows 128,1024] [--tiles 16,32] [--block-mb 24,12] [--reps 20]

For every shape (widths, held experts of the router's, choices a token,
matrices an expert) and number of rows it routes random rows (`--skew`:
the spread of a per-expert bias on the scores; 0 is even), times the
whole layer function `latent_moe.grouped_experts` both ways (the sort,
the gathers and the weighted sum included) and the kernel's call alone,
and prints one JSON line a measurement: the milliseconds of each, the
GB/s of the touched experts' bytes, the largest difference between the
two ways' outputs, and what the gate says of the shape (`gate`: the
kernel is measured all the same). `tile_rows`, `BLOCK_BYTES` and `MAX_TILE` of
ops/pallas_grouped_experts.py came from it (PERF.md §6, PR 43). Needs a
TPU.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools.prompt_attention_sweep import timed          # noqa: E402

#: name -> (model width, expert width, held experts, the router's experts,
#: choices a token, matrices an expert, rows of (a decode step, prefills))
SHAPES = {"nemotron": (2688, 1856, 64, 128, 6, 2, (128, 256, 1024)),
          "dsv3": (7168, 2048, 16, 256, 8, 3, (32, 256, 1024)),
          "trinity": (3072, 3072, 16, 256, 4, 3, (32, 1024, 8192))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="nemotron,dsv3,trinity")
    ap.add_argument("--rows", default="")
    ap.add_argument("--tiles", default="")
    ap.add_argument("--block-mb", default="")
    ap.add_argument("--skew", type=float, default=0.5)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args(argv)

    import numpy as np
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import latent_moe
    from mxnet_tpu.ops import pallas_grouped_experts as ge

    if jax.default_backend() != "tpu":
        raise SystemExit("grouped_experts_sweep: jax found no TPU; "
                         "nothing was measured")
    bf16 = jnp.bfloat16
    gate, tile_rows, block_bytes = (ge.experts_unfit, ge.tile_rows,
                                    ge.BLOCK_BYTES)
    tiles = [int(n) for n in args.tiles.split(",") if n] or [None]
    blocks = [int(float(n) * 2 ** 20)
              for n in args.block_mb.split(",") if n] or [block_bytes]
    for name in args.shapes.split(","):
        D, F, held, experts, k, n_mats, rows = SHAPES[name]
        keys = jax.random.split(jax.random.PRNGKey(len(name)), 6)
        we_gate, we_up = ((0.02 * jax.random.normal(key, (held, D, F))
                           ).astype(bf16) for key in keys[:2])
        we_gate = we_gate if n_mats == 3 else None
        we_down = (0.02 * jax.random.normal(keys[2], (held, F, D))
                   ).astype(bf16)
        for N in [int(n) for n in args.rows.split(",") if n] or rows:
            h = jax.random.normal(keys[3], (N, D), bf16)
            scores = jax.random.normal(keys[4], (N, experts)) \
                + args.skew * jax.random.normal(keys[5], (experts,))
            idx = jax.lax.top_k(scores, k)[1]
            local = jnp.where(idx < held, idx, held).astype(jnp.int32)
            w = jnp.full((N, k), 1.0 / k, jnp.float32)
            operands = (h, local, w, we_gate, we_up, we_down)
            ge.experts_unfit = lambda *a, **kw: "the sweep's loop"
            fn = jax.jit(lambda *a: latent_moe.grouped_experts(*a))
            loop_ms = timed(fn, operands, args.reps)
            ref, counts = (np.asarray(a) for a in fn(*operands))
            touched = int(np.count_nonzero(counts))
            # what a call must read at least: each touched expert once
            gb = touched * n_mats * D * F * 2 / 1e9
            # the kernel is measured whatever the gate says of the shape
            ge.experts_unfit = lambda *a, **kw: None
            refused = gate(D, F, n_mats, bf16)
            for tile in tiles:
                for bb in blocks:
                    ge.tile_rows = (lambda *a, t=tile: t) if tile \
                        else tile_rows
                    ge.BLOCK_BYTES = bb
                    tile_ = ge.tile_rows(N * k, held)
                    block = ge.block_width(D, F, n_mats, 2, bb)
                    line = {"shape": name, "rows": N, "pairs_here":
                            int(counts.sum()), "touched": touched,
                            "busiest": int(counts.max()), "tile": tile_,
                            "block": block, "gate": refused,
                            "loop_ms": loop_ms,
                            "loop_gb_s": gb / loop_ms * 1e3}
                    try:
                        fn = jax.jit(
                            lambda *a: latent_moe.grouped_experts(*a))
                        ms = timed(fn, operands, args.reps)
                        out = np.asarray(fn(*operands)[0])
                        # the kernel's call alone, over the tiles the
                        # layer function hands it
                        n_tiles = int(np.sum(-(-counts // tile_)))
                        max_tiles = -(-N * k // tile_) + held
                        x = jnp.tile(h, (-(-max_tiles * tile_ // N), 1))[
                            :max_tiles * tile_]
                        e_of = jnp.minimum(jnp.searchsorted(
                            jnp.cumsum(-(-jnp.asarray(counts) // tile_)),
                            jnp.arange(max_tiles), side="right"), held - 1)
                        alone = jax.jit(lambda *a: ge.grouped_experts(
                            *a, tile=tile_, block=block))
                        alone_ms = timed(
                            alone, (x, e_of, jnp.int32(n_tiles), we_gate,
                                    we_up, we_down), args.reps)
                        line.update({
                            "kernel_ms": ms, "kernel_gb_s": gb / ms * 1e3,
                            "alone_ms": alone_ms, "tiles": n_tiles,
                            "alone_gb_s": gb / alone_ms * 1e3,
                            "max_gap": float(np.abs(out - ref).max()),
                            "max_ref": float(np.abs(ref).max())})
                    except Exception as e:          # a refusal is a result
                        line["error"] = "%s: %s" % (type(e).__name__,
                                                    str(e)[:400])
                    print(json.dumps(line), flush=True)
            ge.experts_unfit, ge.tile_rows, ge.BLOCK_BYTES = (
                gate, tile_rows, block_bytes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
