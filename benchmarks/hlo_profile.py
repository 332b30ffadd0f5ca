#!/usr/bin/env python
"""Per-HLO breakdown of the fused ResNet-50 training step.

Answers "where does the step time go" (VERDICT r2 weak #2): compiles the
TrainStep, then
  1. classifies every convolution in the optimized HLO as forward /
     input-grad (lhs-dilated or padded-reversed form) / weight-grad
     (batch-as-contracting form), with shapes and flops;
  2. prints XLA's cost-analysis totals;
  3. on a real device (BENCH_PROFILE_TRACE=1), captures a profiler trace
     for N steps so per-op wall times can be pulled from the XPlane.

Usage: [BENCH_BATCH=256 BENCH_DTYPE=bfloat16] python benchmarks/hlo_profile.py
CPU smoke: BENCH_SMOKE=1 python benchmarks/hlo_profile.py
"""
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks._layout import bench_layout, img_shape  # noqa: E402


def build_step(smoke, dtype):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel.trainer import TrainStep

    image = 32 if smoke else 224
    layout = bench_layout()
    make = vision.resnet18_v1 if smoke else vision.resnet50_v1
    net = make(layout=layout)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros(img_shape(layout, 1, image)))
    step = TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4},
                     dtype=dtype)
    return step, image, layout


def build_lstm_step(smoke, dtype, batch):
    """BENCH_PROFILE_MODEL=lstm: the word-LM TrainStep (LSTM-200x2,
    bptt 35 — bench.py's lstm config) so the scan's per-HLO times can be
    read from the XPlane (VERDICT r4 weak #3: where do the tok/s go)."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep

    vocab, emb, hid, layers = (200, 32, 32, 1) if smoke else \
        (10000, 200, 200, 2)
    # BENCH_LSTM_HIDDEN: match the lstm_sweep config (256, Mosaic-tile
    # eligible) so a MXNET_FUSED_RNN=1 profile exercises the fused kernel
    hid = int(os.environ.get("BENCH_LSTM_HIDDEN", hid))
    bptt = 8 if smoke else 35
    net = mx.models.RNNModel(mode="lstm", vocab_size=vocab, num_embed=emb,
                             num_hidden=hid, num_layers=layers, dropout=0.0)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((bptt, batch)))
    step = TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1}, dtype=dtype)
    return step, vocab, bptt


def conv_table(hlo_text, batch):
    """Classify convolution ops in optimized HLO text.

    Forms after XLA optimization (all channels-last b01f_01io here):
    - forward: output batch dim == data batch, plain window;
    - input_grad: lhs_dilate (strided-conv grads) or rhs_reversal;
    - weight_grad: batch is the contracting dim, so the op's output is the
      weight tensor — its leading dim is a channel count, not the data
      batch (e.g. out=[512,3,3,512] window={size=4x4}).
    """
    rows = []
    for line in hlo_text.splitlines():
        if "convolution(" not in line and " convolution" not in line:
            continue
        if "dim_labels=" not in line:
            continue
        window = re.search(r"window={([^}]*)}", line)
        labels = re.search(r"dim_labels=(\S+?)(?:,|\s|$)", line)
        out_shape = re.search(r"=\s*\w+\[([\d,]*)\]", line)
        w = window.group(1) if window else ""
        lab = labels.group(1) if labels else ""
        dims = [int(d) for d in out_shape.group(1).split(",")] \
            if out_shape and out_shape.group(1) else []
        kind = "forward"
        if "lhs_dilate" in w or "rhs_reversal" in w:
            kind = "input_grad"
        elif dims and dims[0] != batch:
            kind = "weight_grad"
        rows.append({"kind": kind,
                     "out": out_shape.group(1) if out_shape else "?",
                     "window": w, "dim_labels": lab})
    return rows


def scan_attribution(rows, us):
    """Split self time into while-loop SELF (per-iteration scan overhead:
    loop bookkeeping, condition, carry shuffling — the ops whose name or
    category carries `while`), matmul work (dot/convolution, wherever it
    sits), and everything else. This is the (2)-vs-(3) tiebreaker of the
    2026-07-31 word-LM analysis: if the while bucket
    dominates the step, the scan is latency-bound and the persistent
    fused kernel (MXNET_FUSED_RNN, ops/pallas_rnn.py) is the lever; if
    the dot bucket dominates, the loop body itself is the cost and a
    bigger batch is. hlo_stats reports SELF time, so a while row never
    double-counts its body fusions — they have their own rows."""
    while_self = dot_self = other_self = 0.0
    for r in rows:
        cat = (r.get("category") or "").lower()
        name = (r.get("hlo_op_name") or "").lower()
        expr = (r.get("hlo_op_expression") or "").lower()
        t = us(r)
        if "while" in cat or name.startswith("while") \
                or " while(" in expr or expr.startswith("while"):
            while_self += t
        elif ("dot" in cat or "conv" in cat or "dot(" in expr
              or "convolution(" in expr):
            dot_self += t
        else:
            other_self += t
    total = (while_self + dot_self + other_self) or 1.0
    print("\n== scan-overhead vs matmul attribution (self time) ==")
    for label, t in (("while-loop self (scan overhead)", while_self),
                     ("dot/convolution (incl. loop-body matmuls)",
                      dot_self),
                     ("everything else", other_self)):
        print("  %-42s %10.0f us  %5.1f%%" % (label, t, 100 * t / total))
    if dot_self:
        print("  while-self : dot ratio = %.2f  (>1 => latency-bound "
              "loop; the fused-kernel lever applies)"
              % (while_self / dot_self))


def xplane_summary(logdir, top=20):
    """Per-op wall times from the captured XPlane via xprof's hlo_stats
    table: category totals (where does the step go) + the heaviest ops
    (what to attack first). Best-effort — any failure leaves the raw
    trace usable in tensorboard."""
    import glob
    try:
        from xprof.convert import raw_to_tool_data as rtd
        paths = sorted(glob.glob(logdir + "/**/*.xplane.pb",
                                 recursive=True))
        if not paths:
            print("no xplane.pb under %s" % logdir)
            return
        data, _ = rtd.xspace_to_tool_data([paths[-1]], "hlo_stats", {})
        tab = json.loads(data.decode() if isinstance(data, bytes)
                         else data)
        cols = [c["id"] for c in tab.get("cols", [])]
        rows = []
        for row in tab.get("rows", []):
            vals = [c.get("v") if isinstance(c, dict) else c
                    for c in row["c"]]
            rows.append(dict(zip(cols, vals)))
        if not rows:
            print("xplane has no hlo_stats rows (CPU traces don't carry "
                  "the device plane; on TPU this table populates)")
            return
        def us(r):
            v = r.get("total_self_time") or 0.0
            if isinstance(v, str):       # gviz cells may carry "1,234.5"
                v = v.replace(",", "")
            return float(v)

        by_cat = {}
        for r in rows:
            cat = r.get("category") or "?"
            by_cat[cat] = by_cat.get(cat, 0.0) + us(r)
        total = sum(by_cat.values()) or 1.0
        print("\n== self time by HLO category ==")
        for cat, t in sorted(by_cat.items(), key=lambda kv: -kv[1]):
            print("  %-28s %10.0f us  %5.1f%%" % (cat, t, 100 * t / total))
        scan_attribution(rows, us)
        rows.sort(key=us, reverse=True)
        print("\n== top %d ops by self time ==" % top)
        for r in rows[:top]:
            print("  %8.0f us  %-16s %s" % (
                us(r), (r.get("category") or "?")[:16],
                (r.get("hlo_op_expression") or r.get("hlo_op_name")
                 or "")[:95]))
    except Exception as e:
        print("xplane summary unavailable: %s: %s" % (type(e).__name__, e))
        return


def main():
    # BENCH_SMOKE=1 is the explicit CPU mode; nothing else picks the CPU
    smoke = os.environ.get("BENCH_SMOKE", "") == "1"
    if smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"
    dtype = os.environ.get("BENCH_DTYPE",
                           "float32" if smoke else "bfloat16")
    batch = int(os.environ.get("BENCH_BATCH", "8" if smoke else "256"))

    import jax
    import jax.numpy as jnp

    if smoke:
        jax.config.update("jax_platforms", "cpu")

    model = os.environ.get("BENCH_PROFILE_MODEL", "resnet")
    rng = np.random.RandomState(0)
    if model == "lstm":
        batch = int(os.environ.get("BENCH_LSTM_BATCH",
                                   "4" if smoke else "32"))
        step, vocab, bptt = build_lstm_step(smoke, dtype, batch)
        x = jnp.asarray(rng.randint(0, vocab, (bptt, batch))
                        .astype(np.float32))
        y = jnp.asarray(rng.randint(0, vocab, (bptt * batch,))
                        .astype(np.int32))
    else:
        step, image, layout = build_step(smoke, dtype)
        x = jnp.asarray(rng.uniform(-1, 1, img_shape(layout, batch, image))
                        .astype(np.float32))
        y = jnp.asarray(rng.randint(0, 1000, (batch,)).astype(np.int32))

    float(step(x, y))  # build + compile the fused step
    compiled = step._step_fn.lower(*step._example_args).compile()

    cost = compiled.cost_analysis()
    print(json.dumps({"cost_analysis": {
        k: cost[k] for k in ("flops", "bytes accessed", "transcendentals")
        if k in cost}}))

    hlo = compiled.as_text()
    rows = conv_table(hlo, batch)
    by_kind = {}
    for r in rows:
        by_kind.setdefault(r["kind"], []).append(r)
    print(json.dumps({"conv_counts": {k: len(v)
                                      for k, v in by_kind.items()}}))
    for kind, items in sorted(by_kind.items()):
        print("\n== %s convolutions (%d) ==" % (kind, len(items)))
        for r in items:
            print("  out=[%s] window={%s} labels=%s"
                  % (r["out"], r["window"][:70], r["dim_labels"]))

    if os.environ.get("BENCH_PROFILE_TRACE", "") == "1":
        # capture a real trace: tensorboard-readable, and the XPlane holds
        # per-op times on TPU
        logdir = os.environ.get("BENCH_TRACE_DIR", "/tmp/mxtpu_trace")
        float(step(x, y))
        with jax.profiler.trace(logdir):
            loss = None
            for _ in range(5):
                loss = step(x, y)
            float(loss)
        print("\ntrace written to %s" % logdir)
        xplane_summary(logdir)

    t0 = time.perf_counter()
    loss = None
    float(step(x, y))
    t0 = time.perf_counter()
    for _ in range(10):
        loss = step(x, y)
    float(loss)
    dt = (time.perf_counter() - t0) / 10
    if model == "lstm":
        print("\nstep time: %.2f ms (batch %d x bptt %d -> %.0f tok/s)"
              % (dt * 1e3, batch, bptt, batch * bptt / dt))
    else:
        print("\nstep time: %.2f ms (batch %d -> %.0f img/s)"
              % (dt * 1e3, batch, batch / dt))


if __name__ == "__main__":
    main()
