"""Bytes-per-step report: A/B the remat policies on the headline ResNet-50
training step via XLA's own cost model.

The 2026-07-31 roofline analysis (BENCH_LAST_TPU.json) pinned the full
train step at 95% of the v5e HBM-bandwidth floor: 81.49 GB accessed / 5.689 TFLOP per
step at batch 256 bf16. Further headline gains therefore require MOVING
FEWER BYTES, not faster kernels. The candidate lever is the "io" remat
policy (parallel/trainer.py): keep the MXU outputs (conv/matmul, tagged
via checkpoint_name) + BN batch stats, recompute the cheap elementwise
chains (BN normalize / relu / residual adds) in backward instead of
writing them in forward and re-reading them.

This script compiles the step under each mode and prints XLA's flops /
bytes-accessed counts plus the implied bandwidth-floor step time. A mode
is `<remat>[+fused]`: the remat policy (none/full/io) crossed with the
Pallas fused BN/ReLU/residual epilogue (MXNET_FUSED_BN_EPILOGUE=1,
ops/pallas_fused.py) — the four decision modes of the bytes ledger are
none / io / fused / io+fused.

Run on TPU for the authoritative numbers (fusion decisions are
backend-specific; XLA:CPU CSEs remat differently). A CPU run
(BYTES_SMALL=1 recommended) still shows the program-level delta: saved-residual bytes
move out of the forward/backward boundary. Two disclosures on every CPU
line: the numbers are DIRECTIONAL (backend-specific fusion), and in
fused modes the kernels run under the Pallas interpreter, whose lowered
HLO differs from the Mosaic kernel the TPU executes (each pallas_call
declares a CostEstimate so the TPU cost model counts the custom call's
real traffic instead of zero).

Knobs: BENCH_BATCH (256), BENCH_DTYPE (bfloat16), BYTES_SMALL=1 (resnet18
@ 64px, for CPU), BYTES_MODES (comma list, default
none,full,io,fused,io+fused), BYTES_EXEC=1 (also time 5 real steps per
mode).

Output: one JSON line per mode + a summary table on stderr.
"""
import json
import os
import sys
import time

import numpy as np


def parse_mode(mode):
    """'io+fused' -> ('io', True); 'fused' -> ('none', True)."""
    parts = [p for p in mode.strip().split("+") if p]
    fused = "fused" in parts
    parts = [p for p in parts if p != "fused"]
    if len(parts) > 1:
        raise ValueError("bad mode %r" % (mode,))
    return (parts[0] if parts else "none"), fused


def build_step(remat, dtype, batch, image, small):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel.trainer import TrainStep
    import jax.numpy as jnp

    make = vision.resnet18_v1 if small else vision.resnet50_v1
    net = make()
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, 3, image, image)))
    step = TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4},
                     dtype=dtype, remat=remat)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.uniform(-1, 1, (batch, 3, image, image))
                    .astype(np.float32))
    y = jnp.asarray(rng.randint(0, 1000, (batch,)).astype(np.int32))
    return step, x, y


def analyze(step, x, y):
    """AOT-compile once; return (cost/memory info, compiled, args). The
    same executable is reused for timing — recompiling through the jit
    dispatch path would pay the batch-256 XLA compile twice per mode."""
    import jax
    import jax.numpy as jnp
    step._build()
    args = (step._grad_vals, step._nograd_vals, step._opt_state, x, y,
            jax.random.PRNGKey(0), jnp.float32(0.05), jnp.int32(1),
            jnp.float32(0.0))  # chaos grad-poison seam: 0.0 = disarmed
    compiled = step._step_fn.lower(*args).compile()
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    return {
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
    }, compiled, args


def main():
    import jax
    dev = jax.devices()[0]
    small = os.environ.get("BYTES_SMALL", "0") == "1"
    batch = int(os.environ.get("BENCH_BATCH", "32" if small else "256"))
    image = 64 if small else 224
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    modes = os.environ.get("BYTES_MODES",
                           "none,full,io,fused,io+fused").split(",")
    do_exec = os.environ.get("BYTES_EXEC", "0") == "1"
    try:
        from bench import _hbm_bw  # the maintained per-kind spec table
        hbm_bw = _hbm_bw(dev.device_kind)
    except ImportError:
        hbm_bw = None

    rows = []
    for mode in modes:
        mode = mode.strip()
        remat, fused = parse_mode(mode)
        # the fused flag is read at TRACE time (ops/nn.py), so it must be
        # set for both the build and the lowering, and restored after
        prior = os.environ.get("MXNET_FUSED_BN_EPILOGUE")
        os.environ["MXNET_FUSED_BN_EPILOGUE"] = "1" if fused else "0"
        try:
            step, x, y = build_step(remat, dtype, batch, image, small)
            t0 = time.perf_counter()
            info, compiled, args = analyze(step, x, y)
        finally:
            if prior is None:
                os.environ.pop("MXNET_FUSED_BN_EPILOGUE", None)
            else:
                os.environ["MXNET_FUSED_BN_EPILOGUE"] = prior
        info["compile_s"] = round(time.perf_counter() - t0, 1)
        info["mode"] = mode
        info["remat"] = remat
        info["fused_bn_epilogue"] = fused
        if fused and dev.platform != "tpu":
            info["note"] = ("fused kernels ran under the Pallas "
                            "interpreter — directional; TPU lowers them "
                            "as Mosaic custom calls with declared "
                            "CostEstimates")
        info["batch"] = batch
        info["device"] = dev.device_kind
        if do_exec:
            # drive the AOT executable directly, chaining the donated
            # (grad, nograd, opt_state) outputs back in — same timing
            # discipline as bench.py (data-dependent chain + readback)
            key, lr, t = args[5], args[6], args[7]
            loss, gv, ngv, st = compiled(*args)
            loss, gv, ngv, st = compiled(gv, ngv, st, x, y, key, lr, t)
            t0 = time.perf_counter()
            n = 5
            for _ in range(n):
                loss, gv, ngv, st = compiled(gv, ngv, st, x, y, key, lr, t)
            float(np.asarray(loss))
            dt = (time.perf_counter() - t0) / n
            info["step_ms"] = round(dt * 1e3, 2)
            info["img_per_sec"] = round(batch / dt, 1)
        if hbm_bw and info["bytes_accessed"]:
            info["roofline_floor_ms"] = round(
                info["bytes_accessed"] / hbm_bw * 1e3, 2)
        rows.append(info)
        print(json.dumps(info), flush=True)

    base = next((r for r in rows if r["mode"] == "none"), None)
    print("\nmode       GB/step  GFLOP/step  temp GB  floor ms%s" %
          ("  step ms  img/s" if do_exec else ""), file=sys.stderr)
    for r in rows:
        gb = (r["bytes_accessed"] or 0) / 1e9
        gf = (r["flops"] or 0) / 1e9
        tg = (r["temp_bytes"] or 0) / 1e9
        extra = ""
        if do_exec:
            extra = "  %7.1f  %6.1f" % (r.get("step_ms") or 0,
                                        r.get("img_per_sec") or 0)
        delta = ""
        if base and r is not base and base["bytes_accessed"]:
            delta = "  (bytes %+0.1f%%)" % (
                100.0 * (r["bytes_accessed"] - base["bytes_accessed"])
                / base["bytes_accessed"])
        print("%-9s %7.2f  %10.1f  %7.2f  %8s%s%s" %
              (r["mode"], gb, gf, tg, r.get("roofline_floor_ms", "-"),
               extra, delta), file=sys.stderr)


if __name__ == "__main__":
    main()
