"""RNN carry-traffic bytes A/B: lax.scan vs the persistent fused kernel.

The 2026-07-31 word-LM profile pins the LSTM train step to the
sequential scan's per-iteration cost. Structurally, every XLA
while-loop iteration of the scan path moves per step:

- the h/c carry round trip: ~4·N·H·itemsize (2 reads + 2 writes);
- a fresh HBM read of the recurrent weight wh: G·H·H·itemsize (TPUs have
  no cache — a loop-body operand is re-read every iteration);
- the px/ys sequence slices (irreducible streams — both paths pay them).

The persistent Pallas kernel (ops/pallas_rnn.py, MXNET_FUSED_RNN=1)
eliminates the first two by construction: the carry lives in VMEM
scratch for the whole sequence and wh is DMA'd once. This report pins
that claim in the cost model BEFORE any TPU time is spent — the
measurement-before-TPU discipline of BENCH_BYTES_CPU.txt /
BENCH_BYTES_SERVING_CPU.txt.

Method: compile grad(one fused LSTM layer) at several T and take the
bytes-per-step SLOPE dB/dT, which cancels everything T-independent:

- scan leg: XLA's own cost analysis of the lowered while loop. XLA
  multiplies known-trip-count loop bodies by T, so the slope carries the
  REAL per-iteration body traffic (carry + wh re-read + streams).
- fused leg: the kernels are opaque custom calls whose declared
  CostEstimates (pallas_rnn.fwd_declared_cost/bwd_declared_cost — the
  exact BlockSpec traffic Mosaic streams) are what the TPU cost model
  counts; the report prints the same numbers here. The CPU-compiled
  fused program is ALSO cost-analyzed for completeness, with the
  standing disclosure that interpreter-mode lowering inflates it
  (staging copies per pallas_call — same artifact as the fused modes in
  BENCH_BYTES_CPU.txt); the declared column is the TPU-authoritative
  one.

The acceptance claim: the fused slope minus the analytic stream bytes is
ZERO — h/c bytes per step independent of T — while the scan slope
carries the 4·N·H carry + G·H·H weight-re-read overhead per step.

Knobs: RNN_BYTES_T (default 8,35,140), BENCH_LSTM_BATCH (32),
RNN_BYTES_HIDDEN (256 — the Mosaic-tile-eligible sweep width),
BENCH_DTYPE (float32).

Output: one JSON line per (mode, T) + the slope ledger on stderr.
Committed artifact: BENCH_BYTES_RNN_CPU.txt (CPU run); it has not run
on the chip.
"""
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_layer_grad(fused, T, N, C, H, dtype):
    """grad of one LSTM layer-direction (the unit the kernel replaces):
    loss = sum(ys^2), grads on (xs, wi, wh, bi, bh, h0, c0)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import nn

    rng = np.random.RandomState(0)
    G = 4
    cd = jnp.dtype(dtype)
    args = (jnp.asarray(rng.randn(T, N, C) * 0.1, cd),      # xs
            jnp.asarray(rng.randn(N, H) * 0.1, cd),         # h0
            jnp.asarray(rng.randn(N, H) * 0.1, cd),         # c0
            jnp.asarray(rng.randn(G * H, C) * 0.1, cd),     # wi
            jnp.asarray(rng.randn(G * H, H) * 0.1, cd),     # wh
            jnp.asarray(rng.randn(G * H) * 0.1, cd),        # bi
            jnp.asarray(rng.randn(G * H) * 0.1, cd))        # bh

    def loss(xs, h0, c0, wi, wh, bi, bh):
        ys, hT, cT = nn._scan_layer("lstm", xs, h0, c0, wi, wh, bi, bh,
                                    fused=fused)
        return jnp.sum((ys * ys).astype(jnp.float32))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5, 6))), args


def cost_of(jitted, args):
    cost = jitted.lower(*args).compile().cost_analysis()
    return (float(cost.get("flops", 0) or 0),
            float(cost.get("bytes accessed", 0) or 0))


def main():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_rnn

    dev = jax.devices()[0]
    dtype = os.environ.get("BENCH_DTYPE", "float32")
    N = int(os.environ.get("BENCH_LSTM_BATCH", "32"))
    H = int(os.environ.get("RNN_BYTES_HIDDEN", "256"))
    C = H  # layer-1 shape of the stacked word-LM: input = previous hidden
    Ts = [int(t) for t in
          os.environ.get("RNN_BYTES_T", "8,35,140").split(",")]
    sz = jnp.dtype(dtype).itemsize
    G = 4

    rows = []
    for mode in ("scan", "fused"):
        for T in Ts:
            jitted, args = build_layer_grad(mode == "fused", T, N, C, H,
                                            dtype)
            flops, nbytes = cost_of(jitted, args)
            info = {"mode": mode, "T": T, "batch": N, "hidden": H,
                    "dtype": dtype, "device": dev.device_kind,
                    "flops": flops, "bytes_accessed": nbytes}
            if mode == "fused":
                ff, fb, _ = pallas_rnn.fwd_declared_cost("lstm", T, N, H,
                                                         dtype)
                bf, bb, _ = pallas_rnn.bwd_declared_cost("lstm", T, N, H,
                                                         dtype)
                info["declared_kernel_bytes"] = fb + bb
                info["declared_kernel_flops"] = ff + bf
                if dev.platform != "tpu":
                    info["note"] = (
                        "fused program compiled under the Pallas "
                        "INTERPRETER — bytes_accessed is lowering-"
                        "inflated (disclosed); declared_kernel_* is "
                        "what the TPU cost model counts for the "
                        "custom calls")
            rows.append(info)
            print(json.dumps(info), flush=True)

    if len(Ts) < 2:
        print("\n(single T point — the slope ledger needs at least two "
              "RNN_BYTES_T values)", file=sys.stderr)
        return

    # slope ledger: d(bytes)/dT between the two largest T values
    def slope(vals):
        (t1, b1), (t2, b2) = vals[-2], vals[-1]
        return (b2 - b1) / (t2 - t1)

    scan_s = slope([(r["T"], r["bytes_accessed"]) for r in rows
                    if r["mode"] == "scan"])
    fused_cpu_s = slope([(r["T"], r["bytes_accessed"]) for r in rows
                         if r["mode"] == "fused"])
    fused_decl_s = slope([(r["T"], r["declared_kernel_bytes"])
                          for r in rows if r["mode"] == "fused"])
    # irreducible per-step streams both paths pay for the recurrence:
    # px fwd read + px bwd read + dpx write (3·N·G·H), ys/cs fwd writes +
    # hprev/cprev/cs/dys bwd reads (6·N·H)
    streams = (3 * N * G * H + 6 * N * H) * sz
    carry = 4 * N * H * sz
    wh_reread = G * H * H * sz
    # the fused bwd reads the shifted hprev/cprev sequences, built by one
    # concat outside the kernel: 4·N·H/step of XLA-counted traffic the
    # scan path does not pay (its residuals are already per-step) —
    # charged to the fused column below so the win is not overstated
    shift_concat = 4 * N * H * sz
    err = sys.stderr
    print("\nconfig: lstm layer N=%d H=%d %s on %s"
          % (N, H, dtype, dev.device_kind), file=err)
    print("bytes-per-step slope dB/dT (T=%d..%d):" % (Ts[-2], Ts[-1]),
          file=err)
    print("  scan  (XLA while body x T)   : %10.0f B/step" % scan_s,
          file=err)
    print("  fused (declared CostEstimate): %10.0f B/step" % fused_decl_s,
          file=err)
    print("  fused (CPU interpret lowering, disclosed-inflated): "
          "%10.0f B/step" % fused_cpu_s, file=err)
    print("analytic ledger per step:", file=err)
    print("  irreducible px/ys/cs streams : %10.0f B" % streams, file=err)
    print("  h/c carry round trip (4NH)   : %10.0f B" % carry, file=err)
    print("  wh re-read per iteration     : %10.0f B (fwd; bwd re-reads "
          "again)" % wh_reread, file=err)
    print("carry+weight overhead (slope minus streams):", file=err)
    print("  scan : %10.0f B/step" % (scan_s - streams), file=err)
    print("  fused: %10.0f B/step kernel + %d B/step hprev/cprev shift "
          "concats\n         <- h/c carry + wh re-read ELIMINATED (VMEM-"
          "resident; kernel bytes/step independent of T)"
          % (fused_decl_s - streams, shift_concat), file=err)
    print("fused : scan per-step ratio (incl. concat charge): %.2fx "
          "fewer bytes"
          % (scan_s / (fused_decl_s + shift_concat)), file=err)


if __name__ == "__main__":
    main()
