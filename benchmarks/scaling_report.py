"""Compiled-HLO scaling evidence for the multi-chip data-parallel path.

BASELINE.md's second north-star metric is KVStore/allreduce scaling
efficiency from 8 to 256 chips (the reference's published AlexNet /
Inception-v3 / ResNet-152 sweeps on 256 K80s,
example/image-classification/README.md:292-315, reach ~90% efficiency
with its parameter-server `dist_device_sync`). Real multi-chip hardware
is not available here, so this report produces the next-best checkable
artifact: it compiles the SAME fused dp train step this framework runs
on hardware against 8/64/256 virtual devices and extracts every
collective operation XLA emitted, with its shape and byte volume, from
the optimized HLO.

What "good" looks like (and what the assertions pin):
- gradient reduction compiles to all-reduce (or reduce-scatter +
  all-gather) over the dp axis — NOT per-parameter host round trips;
- the per-chip collective byte volume is O(model size) and INDEPENDENT
  of the number of chips (ring allreduce moves 2*(N-1)/N * bytes ->
  asymptotically 2x model bytes per chip regardless of N) — this is the
  property that makes ~90% scaling efficiency possible at 256 chips on
  a torus;
- the collective count does not grow with N (no N-proportional
  serialization in the program).

Run: python benchmarks/scaling_report.py  (CPU, no TPU needed)
Output: SCALING.md at the repo root + one JSON line per mesh size.
"""
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

_SIZES = [int(s) for s in
          os.environ.get("SCALING_SIZES", "8,64,256").split(",")]

from benchmarks._env import force_virtual_cpu_devices  # noqa: E402

force_virtual_cpu_devices(max(_SIZES))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# an instrument over virtual CPU devices by construction (it counts
# collectives in compiled HLO; it measures no device)
jax.config.update("jax_platforms", "cpu")

_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
                "collective-permute", "all-to-all")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                "s32": 4, "u32": 4, "s8": 1, "u8": 1, "pred": 1}


def _collective_stats(hlo_text):
    """Count collectives and sum their output bytes from optimized HLO."""
    stats = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        for kind in _COLLECTIVES:
            # match the op name at the assignment, not inside metadata
            if re.search(r"=\s*\(?\s*[a-z0-9]+\[[0-9,]*\]\S*\s+%s\(" % kind,
                         line) or \
                    re.search(r"=\s*\(.*\)\s+%s\(" % kind, line):
                # output shapes are everything left of the op name — a
                # tuple all-reduce (XLA batches every gradient into one)
                # lists one shape per gradient; operands to the right
                # would double-count
                out_part = line.split("%s(" % kind)[0]
                nbytes = 0
                for dt, dims in re.findall(r"([a-z0-9]+)\[([0-9,]*)\]",
                                           out_part):
                    if dt not in _DTYPE_BYTES:
                        continue
                    n = 1
                    for d in dims.split(","):
                        if d:
                            n *= int(d)
                    nbytes += n * _DTYPE_BYTES[dt]
                kstats = stats.setdefault(kind, {"count": 0, "bytes": 0})
                kstats["count"] += 1
                kstats["bytes"] += nbytes
    return stats


def report_for(n_devices, batch_per_chip=8):
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params,
                                              lm_loss, transformer_shardings)
    from mxnet_tpu.parallel.mesh import build_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    # size-1 tp axis: transformer_shardings names 'tp' in its specs; a
    # trivial axis keeps the program purely data-parallel
    mesh = build_mesh({"dp": n_devices, "tp": 1},
                      jax.devices()[:n_devices])
    cfg = TransformerConfig(vocab=512, d_model=128, n_heads=8, n_layers=2,
                            d_ff=256, max_len=32)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    shardings = transformer_shardings(cfg)
    params = {k: jax.device_put(v, NamedSharding(mesh, shardings[k]))
              for k, v in params.items()}
    model_bytes = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                      for v in params.values())

    lr = 0.1

    def step(params, tokens):
        loss, grads = jax.value_and_grad(lm_loss)(params, tokens, cfg,
                                                  mesh=mesh)
        return {k: v - lr * grads[k] for k, v in params.items()}, loss

    toks = jnp.zeros((batch_per_chip * n_devices, cfg.max_len), jnp.int32)
    toks = jax.device_put(toks, NamedSharding(mesh, P("dp")))
    hlo = (jax.jit(step, donate_argnums=0)
           .lower(params, toks).compile().as_text())
    stats = _collective_stats(hlo)
    total = {"count": sum(s["count"] for s in stats.values()),
             "bytes": sum(s["bytes"] for s in stats.values())}
    return {"n_devices": n_devices, "model_bytes": model_bytes,
            "collectives": stats, "total": total}


def report_moe(n_devices=8, ep=4):
    """Collectives of the top-2 MoE step on a dp x ep mesh: experts are
    ep-sharded; tokens are dp-sharded and replicated across ep, so
    dispatch/combine stay local einsums and the wire traffic is the
    gradient reduction — the layout that keeps MoE scaling on ICI."""
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params,
                                              lm_loss, transformer_shardings)
    from mxnet_tpu.parallel.mesh import build_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = build_mesh({"dp": n_devices // ep, "tp": 1, "ep": ep},
                      jax.devices()[:n_devices])
    cfg = TransformerConfig(vocab=256, d_model=64, n_heads=4, n_layers=1,
                            d_ff=128, n_experts=ep * 2, moe_top_k=2,
                            max_len=32)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    shardings = transformer_shardings(cfg)
    params = {k: jax.device_put(v, NamedSharding(mesh, shardings[k]))
              for k, v in params.items()}

    def step(params, tokens):
        loss, grads = jax.value_and_grad(lm_loss)(params, tokens, cfg,
                                                  mesh=mesh)
        return {k: v - 0.1 * grads[k] for k, v in params.items()}, loss

    toks = jnp.zeros((8, cfg.max_len), jnp.int32)
    toks = jax.device_put(toks, NamedSharding(mesh, P("dp")))
    hlo = (jax.jit(step, donate_argnums=0)
           .lower(params, toks).compile().as_text())
    return {"mesh": {"dp": n_devices // ep, "ep": ep},
            "collectives": _collective_stats(hlo)}


def main():
    rows = [report_for(n) for n in _SIZES]
    for r in rows:
        print(json.dumps(r))

    # the scaling property: per-chip collective bytes must not grow with N
    base = rows[0]["total"]["bytes"]
    for r in rows[1:]:
        if base and r["total"]["bytes"] > base * 1.5:
            raise AssertionError(
                "per-chip collective bytes grew with device count: "
                f"{base} at {rows[0]['n_devices']} -> "
                f"{r['total']['bytes']} at {r['n_devices']}")
    if not any(k in rows[-1]["collectives"]
               for k in ("all-reduce", "reduce-scatter")):
        raise AssertionError("no gradient reduction collective found "
                             "in the 256-device program")

    out = ["# Multi-chip scaling evidence (compiled HLO)", "",
           "The fused dp train step (transformer LM, per-chip batch 8) "
           "compiled against virtual meshes. Per-chip collective traffic "
           "must stay O(model size), independent of chip count — the "
           "property behind the reference's ~90% scaling efficiency at "
           "256 GPUs (example/image-classification/README.md:292-315) "
           "and this framework's path to the same on a TPU torus "
           "(collectives ride ICI, inserted by GSPMD, see "
           "docs/PARITY.md §2.3).", "",
           "| devices | collectives | per-chip collective bytes | "
           "model bytes | ratio |", "|---|---|---|---|---|"]
    for r in rows:
        kinds = ", ".join(f"{k}x{v['count']}"
                          for k, v in sorted(r["collectives"].items()))
        ratio = (r["total"]["bytes"] / r["model_bytes"]
                 if r["model_bytes"] else 0)
        out.append(f"| {r['n_devices']} | {kinds} | "
                   f"{r['total']['bytes']:,} | {r['model_bytes']:,} | "
                   f"{ratio:.2f}x |")
    moe = report_moe(min(8, _SIZES[0]))
    print(json.dumps({"moe": moe}))
    kinds = ", ".join(f"{k}x{v['count']} ({v['bytes']:,} B)"
                      for k, v in sorted(moe["collectives"].items()))
    out += ["",
            "**Expert parallel (top-2 MoE, dp x ep mesh "
            f"{moe['mesh']})**: {kinds or 'no collectives'}. Experts are "
            "ep-sharded while tokens replicate across ep within each dp "
            "shard, so dispatch/combine stay local einsums and the wire "
            "traffic is dominated by gradient/loss reductions (all bytes "
            "above are sub-model-size).",
            "",
            "Generated by `benchmarks/scaling_report.py` (CPU, virtual "
            "devices; re-run anywhere). The assertion suite fails the "
            "run if collective bytes grow with N or gradient reduction "
            "is missing from the 256-device program."]
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    path = os.environ.get("SCALING_OUT",
                          os.path.join(root, "SCALING.md"))
    with open(path, "w") as f:
        f.write("\n".join(out) + "\n")
    print("wrote " + path)


if __name__ == "__main__":
    main()
