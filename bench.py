#!/usr/bin/env python
"""Benchmarks for every BASELINE.md exercise config, headline last.

Headline: ResNet-50 training throughput (img/s) on one chip vs the
reference's published 109 img/s (1x K80, example/image-classification/
README.md:147-157). Also measured, one JSON line each: ResNet-50
inference (benchmark_score.py role) in bf16 and through the int8
quantize_model graph rewrite, LSTM word LM (example/rnn/word_lm),
transformer LM with vs without the Pallas flash attention kernel, SSD
forward (example/ssd), sparse linear (example/sparse/
linear_classification), the native C++ RecordIO+JPEG input pipeline
(io_pipeline — host-side, accelerator-independent), and BENCH_RESILIENCE
(checkpoint capture/publish/restore latency + steps-lost-per-simulated-
preemption — the fault-tolerance runtime's overhead line).

Timing methodology: every loop chains iterations through a data
dependency (donated params feed the next step) and ends with a float()
readback of the last result, which waits for the device.

One process, on the backend JAX gives it: the configs run in the calling
process, and the run fails (non-zero exit, no metric line) unless that
backend is a TPU. The only other mode is the explicit BENCH_SMOKE=1: tiny
configs on the CPU, whose lines are named `smoke_*` and measure nothing
comparable. A config that raises prints a `<name>_error` line and makes
the whole run exit non-zero.

Env knobs: BENCH_BATCH (256), BENCH_STEPS (20), BENCH_DTYPE (bfloat16),
BENCH_CONFIGS (comma list or "all"; "headline" = resnet50 only),
BENCH_SMOKE=1 (tiny CPU config), BENCH_REMAT (none|full|io) and
BENCH_FUSED (1|0 — Pallas fused BN epilogue) for the bytes/step
experiment modes.

Every emitted line passes check_line(): numeric comparison fields
(vs_baseline, mfu, overlap_efficiency, ...) must be computed from a
measurement — sentinels are rejected at emit time, never recorded.

Every config line also carries the compile watchdog's accounting
(telemetry/introspect.py): `compile_s` — total wall time the config
spent compiling (trace + XLA, summed over the watchdog events the
config triggered) — and `exec_hbm_bytes` — the peak compiled-executable
device footprint among them via memory_analysis (null where the
backend doesn't expose it). `tools/bench_sentinel.py` judges a fresh
run's lines against the committed BASELINE.json + BENCH_r*.json
trajectory.
"""
import json
import math
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from benchmarks._layout import bench_layout, img_shape  # noqa: E402

# bf16 peak FLOP/s per chip by device kind (public spec sheets); used only
# to normalize MFU. A kind that is not here is an error, not a default.
_PEAK_BF16 = {
    "v2": 45e12, "v3": 105e12, "v4": 275e12,
    "v5 lite": 197e12, "v5e": 197e12, "v5p": 459e12,
    "v6 lite": 918e12, "v6e": 918e12,
}


def _peak_flops(device_kind, dtype):
    """Spec peak for the device, None on the CPU (the explicit smoke mode
    has no peak and reports mfu null). An accelerator missing from the
    table raises: a guessed denominator would make mfu silently wrong."""
    kind = (device_kind or "").lower()
    if kind == "cpu":
        return None
    for k, v in sorted(_PEAK_BF16.items(), key=lambda kv: -len(kv[0])):
        if k in kind:
            return v / 2 if dtype == "float32" else v
    raise ValueError("no peak FLOP/s for device_kind %r: add it to "
                     "_PEAK_BF16 with its source" % device_kind)


def _xla_cost(jitted, *args):
    """(flops, bytes accessed) of the compiled program, from XLA's own
    cost model."""
    try:
        cost = jitted.lower(*args).compile().cost_analysis()
        return (float(cost.get("flops", 0)) or None,
                float(cost.get("bytes accessed", 0)) or None)
    except Exception:
        return None, None


# HBM bandwidth per chip, bytes/s (public spec sheets) — the roofline
# denominator. ResNet-50 training's arithmetic intensity (~70 flops/byte
# by XLA's own counts) is far below every TPU's compute:bandwidth balance
# point (v5e: 197e12/819e9 = 240), so the train step is bandwidth-bound
# and `roofline_pct` (achieved bytes/s over spec) is the honest
# utilization number; `mfu` is reported alongside but cannot approach 1.0
# for this program on this hardware.
_HBM_BYTES_PER_S = {
    "v2": 700e9, "v3": 900e9, "v4": 1228e9,
    "v5 lite": 819e9, "v5e": 819e9, "v5p": 2765e9,
    "v6 lite": 1640e9, "v6e": 1640e9,
}


def _hbm_bw(device_kind):
    """Spec bandwidth, or None for unknown kinds — a guessed denominator
    would make hbm_roofline_pct silently wrong (mfu handles unknown peak
    the same way)."""
    kind = (device_kind or "").lower()
    for k, v in sorted(_HBM_BYTES_PER_S.items(), key=lambda kv: -len(kv[0])):
        if k in kind:
            return v
    return None


def check_line(r):
    """Sentinel-vs-measured guard, applied to every emitted line: a
    numeric comparison field must have been COMPUTED FROM A MEASUREMENT,
    never a placeholder (r5 verdict weak #5: the smoke line carried
    `vs_baseline: 0.0`). Raises ValueError so a bad line surfaces as a
    config error instead of being recorded as a result.

    Rules:
    - `vs_baseline` is either null (with a `baseline_note` saying why) or
      a float derived from a non-null `value`; exactly 0.0 is the retired
      sentinel (no real config runs at 0x baseline).
    - derived ratios (`mfu`, `hbm_roofline_pct`, `overlap_efficiency`,
      `flash_speedup_vs_xla_attention`) require a non-null `value`.
    - `overlap_efficiency` must be <= 1 (its construction guarantees it).
    - an estimated flop count must be disclosed via `flops_source`.
    """
    vb = r.get("vs_baseline")
    if vb == 0.0:
        raise ValueError("vs_baseline 0.0 is a sentinel, not a "
                         "measurement: %r" % (r,))
    if vb is None and "vs_baseline" in r and "baseline_note" not in r:
        raise ValueError("null vs_baseline without a baseline_note: "
                         "%r" % (r,))
    if vb is not None and r.get("value") is None:
        raise ValueError("vs_baseline without a measured value: %r" % (r,))
    for field in ("mfu", "hbm_roofline_pct", "overlap_efficiency",
                  "flash_speedup_vs_xla_attention"):
        if r.get(field) is not None and r.get("value") is None:
            raise ValueError("%s carries a number but value is null: %r"
                             % (field, r))
    ov = r.get("overlap_efficiency")
    if ov is not None and ov > 1.0:
        raise ValueError("overlap_efficiency %.3f > 1 — legs mismeasured"
                         % ov)
    if r.get("flops_per_step") is not None and "flops_source" not in r \
            and r.get("mfu") is not None:
        raise ValueError("mfu derived from an undisclosed flop count: "
                         "%r" % (r,))
    # SLO/goodput fields (ISSUE 13): attainment is a fraction of
    # MEASURED requests against a DISCLOSED threshold, and goodput can
    # never exceed the measured throughput it is a subset of.
    att = r.get("slo_ttft_attainment")
    if att is not None:
        if r.get("value") is None:
            raise ValueError("slo_ttft_attainment without a measured "
                             "value: %r" % (r,))
        if not isinstance(att, (int, float)) or isinstance(att, bool) \
                or not 0.0 <= att <= 1.0:
            raise ValueError("slo_ttft_attainment must be a fraction "
                             "in [0, 1]: %r" % (r,))
        if r.get("slo_ttft_ms") is None:
            raise ValueError("slo_ttft_attainment without the "
                             "slo_ttft_ms threshold it was judged "
                             "against: %r" % (r,))
    gp = r.get("goodput_tok_per_sec")
    if gp is not None:
        if r.get("value") is None or att is None:
            raise ValueError("goodput_tok_per_sec needs a measured "
                             "value and its attainment fraction: %r"
                             % (r,))
        if not isinstance(gp, (int, float)) or isinstance(gp, bool) \
                or gp < 0:
            raise ValueError("goodput_tok_per_sec must be a "
                             "non-negative rate: %r" % (r,))
        if gp > 1.001 * r["value"] + 1e-9:
            raise ValueError("goodput %.3f exceeds the measured "
                             "throughput %.3f it is a subset of: %r"
                             % (gp, r["value"], r))
    # compile-watchdog fields (ISSUE 9): compile_s is the summed wall time
    # of the watchdog-observed compilations this config triggered,
    # exec_hbm_bytes the peak compiled-executable footprint among them.
    # Both are measurements, so the same sentinel rules apply.
    cs = r.get("compile_s")
    if cs is not None and (not isinstance(cs, (int, float))
                           or isinstance(cs, bool) or cs < 0
                           or cs != cs or cs == float("inf")):
        raise ValueError("compile_s must be a finite non-negative "
                         "number of seconds: %r" % (r,))
    hbm = r.get("exec_hbm_bytes")
    if hbm is not None:
        if not isinstance(hbm, int) or isinstance(hbm, bool) or hbm <= 0:
            raise ValueError("exec_hbm_bytes must be a positive byte "
                             "count or null (backend without "
                             "memory_analysis): %r" % (r,))
        if not cs:
            raise ValueError("exec_hbm_bytes without compile time — the "
                             "footprint can only come from a compile "
                             "event: %r" % (r,))
    # training-observability fields (ISSUE 14): fractions are fractions,
    # and the collective ledger can never exceed the executable traffic
    # it is a subset of.
    for field in ("data_wait_fraction", "comms_fraction_of_step"):
        frac = r.get(field)
        if frac is not None and (
                not isinstance(frac, (int, float))
                or isinstance(frac, bool) or not 0.0 <= frac <= 1.0):
            raise ValueError("%s must be a fraction in [0, 1]: %r"
                             % (field, r))
    p95 = r.get("step_p95_ms")
    if p95 is not None and (not isinstance(p95, (int, float))
                            or isinstance(p95, bool) or p95 < 0
                            or p95 != p95 or p95 == float("inf")):
        raise ValueError("step_p95_ms must be a finite non-negative "
                         "number of ms: %r" % (r,))
    cb = r.get("comms_bytes_per_step")
    if cb is not None:
        if not isinstance(cb, int) or isinstance(cb, bool) or cb < 0:
            raise ValueError("comms_bytes_per_step must be a "
                             "non-negative byte count: %r" % (r,))
        ba = r.get("step_bytes_accessed")
        if ba is not None and cb > 1.001 * ba:
            raise ValueError("comms_bytes_per_step %d exceeds the "
                             "executable's total bytes accessed %d it "
                             "is a subset of: %r" % (cb, ba, r))
    # remediation fields (ISSUE 15): MTTR is a measured wall-time span
    # (fault-inject -> first post-recovery step) and the steps lost to
    # a remediation restart are a re-executed-work count — both real
    # measurements, never placeholders.
    mttr = r.get("mttr_s")
    if mttr is not None:
        if not isinstance(mttr, (int, float)) or isinstance(mttr, bool) \
                or mttr <= 0 or mttr != mttr or mttr == float("inf"):
            raise ValueError("mttr_s must be a finite positive number "
                             "of seconds: %r" % (r,))
        if r.get("value") is None:
            raise ValueError("mttr_s without a measured value: %r" % (r,))
    slr = r.get("steps_lost_per_remediation")
    if slr is not None:
        if not isinstance(slr, int) or isinstance(slr, bool) or slr < 0:
            raise ValueError("steps_lost_per_remediation must be a "
                             "non-negative step count: %r" % (r,))
        if mttr is None:
            raise ValueError("steps_lost_per_remediation without the "
                             "mttr_s measurement it rides: %r" % (r,))
    # AOT warm-start fields (ISSUE 16): the warm respawn TTFT only
    # means something NEXT TO the cold one it halves, and
    # breach-to-capacity is a measured wall span that must ride an
    # actually-recorded scale-up.
    wttft = r.get("respawn_to_first_token_warm_ms")
    if wttft is not None:
        if not isinstance(wttft, (int, float)) or isinstance(wttft, bool) \
                or wttft < 0 or wttft != wttft or wttft == float("inf"):
            raise ValueError("respawn_to_first_token_warm_ms must be a "
                             "finite non-negative number of ms: %r"
                             % (r,))
        if r.get("respawn_to_first_token_ms") is None:
            raise ValueError("warm respawn TTFT without the cold "
                             "respawn_to_first_token_ms it is the A/B "
                             "of: %r" % (r,))
    b2s = r.get("burn_to_scale_up_s")
    if b2s is not None:
        if not isinstance(b2s, (int, float)) or isinstance(b2s, bool) \
                or b2s < 0 or b2s != b2s or b2s == float("inf"):
            raise ValueError("burn_to_scale_up_s must be a finite "
                             "non-negative number of seconds: %r" % (r,))
        if not r.get("scale_ups"):
            raise ValueError("burn_to_scale_up_s without a recorded "
                             "scale-up action: %r" % (r,))
    # disaggregated-serving fields (ISSUE 17): KV bytes saved only
    # exist as a side effect of migration hops — a savings number with
    # zero hops is a ledger bug, not a result — and the flattening
    # ratio is derived from the measured p95 pair.
    mbs = r.get("migration_kv_bytes_saved")
    if mbs is not None:
        if not isinstance(mbs, int) or isinstance(mbs, bool) or mbs < 0:
            raise ValueError("migration_kv_bytes_saved must be a "
                             "non-negative byte count: %r" % (r,))
        if mbs > 0 and not r.get("migrations"):
            raise ValueError("migration_kv_bytes_saved %d without a "
                             "recorded migration hop: %r" % (mbs, r))
    fx = r.get("itl_p95_flattening_x")
    if fx is not None and (r.get("value") is None
                           or r.get("coscheduled_decode_itl_p95_ms")
                           is None):
        raise ValueError("itl_p95_flattening_x without the measured "
                         "p95 pair it is derived from: %r" % (r,))
    # live-rollout fields (ISSUE 18): a rollout bench line is only a
    # result if the shift lost NOTHING (a rollout that drops requests
    # is an outage, not a measurement), the corruption-detection
    # latency must ride an actually-recorded rejection, and the TTFT
    # shift delta needs the measured p95 pair it is derived from.
    lost = r.get("rollout_requests_lost")
    if lost is not None:
        if not isinstance(lost, int) or isinstance(lost, bool) \
                or lost != 0:
            raise ValueError("rollout_requests_lost must be exactly 0 "
                             "— a rollout that loses requests is an "
                             "outage, not a result: %r" % (r,))
        if r.get("value") is None:
            raise ValueError("rollout_requests_lost without a measured "
                             "rollout duration: %r" % (r,))
    dm = r.get("corrupt_detect_ms")
    if dm is not None:
        if not isinstance(dm, (int, float)) or isinstance(dm, bool) \
                or dm < 0 or dm != dm or dm == float("inf"):
            raise ValueError("corrupt_detect_ms must be a finite "
                             "non-negative number of ms: %r" % (r,))
        if not r.get("corrupt_steps_rejected"):
            raise ValueError("corrupt_detect_ms without a recorded "
                             "rejection — nothing was detected: %r"
                             % (r,))
    sd = r.get("ttft_p95_shift_delta_ms")
    if sd is not None and (r.get("ttft_p95_shift_ms") is None
                           or r.get("ttft_p95_steady_ms") is None):
        raise ValueError("ttft_p95_shift_delta_ms without the measured "
                         "p95 pair it is derived from: %r" % (r,))
    # speculative-decoding fields (ISSUE 19): the per-pass multiplier
    # only means something next to the k / draft config it was measured
    # under (a full-clone draft pins acceptance at its 1.0 upper bound
    # — that must be visible on the line), it can never exceed the k+1
    # ceiling (above it the ledger double-counted), and acceptance is a
    # fraction riding the same measurement. Spec goodput <= throughput
    # is already enforced by the generic goodput rule above.
    app = r.get("spec_accepted_per_pass")
    if app is not None:
        if not isinstance(app, (int, float)) or isinstance(app, bool) \
                or app <= 0 or app != app or app == float("inf"):
            raise ValueError("spec_accepted_per_pass must be a finite "
                             "positive token count: %r" % (r,))
        if r.get("spec_k") is None or r.get("spec_draft_layers") is None:
            raise ValueError("spec_accepted_per_pass without the "
                             "spec_k / spec_draft_layers config it was "
                             "measured under: %r" % (r,))
        if app > r["spec_k"] + 1 + 1e-9:
            raise ValueError("spec_accepted_per_pass %.3f exceeds the "
                             "k+1=%d ceiling — the acceptance ledger "
                             "double-counted: %r"
                             % (app, r["spec_k"] + 1, r))
    ar = r.get("spec_acceptance_rate")
    if ar is not None:
        if not isinstance(ar, (int, float)) or isinstance(ar, bool) \
                or not 0.0 < ar <= 1.0 + 1e-9:
            raise ValueError("spec_acceptance_rate must be a fraction "
                             "in (0, 1]: %r" % (r,))
        if app is None:
            raise ValueError("spec_acceptance_rate without the "
                             "accepted-per-pass measurement it rides: "
                             "%r" % (r,))
    # quantized-serving fields (ISSUE 20): the precision contract must
    # be ON the line — a logit error only means something next to the
    # budget it was judged against and the quant config it was measured
    # under, and an error above the budget is a refused line, not a
    # recorded one. The capacity claim rides the layout pair: int8
    # bytes/token must actually be smaller than the f32 bytes/token it
    # is the A/B of.
    qle = r.get("quant_max_logit_error")
    if qle is not None:
        if not isinstance(qle, (int, float)) or isinstance(qle, bool) \
                or qle < 0 or qle != qle or qle == float("inf"):
            raise ValueError("quant_max_logit_error must be a finite "
                             "non-negative number: %r" % (r,))
        qb = r.get("quant_logit_budget")
        if qb is None:
            raise ValueError("quant_max_logit_error without the "
                             "quant_logit_budget it was judged "
                             "against: %r" % (r,))
        if qle > qb:
            raise ValueError("quant_max_logit_error %.4g exceeds its "
                             "own budget %.4g — outside the pinned "
                             "precision contract, refused at emit: %r"
                             % (qle, qb, r))
        if r.get("kv_quant") is None and r.get("weight_quant") is None:
            raise ValueError("quant_max_logit_error without the "
                             "kv_quant/weight_quant config it was "
                             "measured under: %r" % (r,))
    pdf = r.get("ppl_delta_frac")
    if pdf is not None:
        if r.get("ppl_f32") is None or r.get("ppl_quant") is None:
            raise ValueError("ppl_delta_frac without the measured "
                             "ppl_f32/ppl_quant pair it is derived "
                             "from: %r" % (r,))
        if not isinstance(pdf, (int, float)) or isinstance(pdf, bool) \
                or pdf < 0 or pdf != pdf or pdf == float("inf"):
            raise ValueError("ppl_delta_frac must be a finite "
                             "non-negative fraction: %r" % (r,))
    b8 = r.get("kv_bytes_per_token_int8")
    if b8 is not None:
        b4 = r.get("kv_bytes_per_token_f32")
        if b4 is None:
            raise ValueError("kv_bytes_per_token_int8 without the f32 "
                             "bytes/token it is the A/B of: %r" % (r,))
        if b8 >= b4:
            raise ValueError("kv_bytes_per_token_int8 %d >= f32 %d — "
                             "the quantized layout saved nothing: %r"
                             % (b8, b4, r))
    return r


# ---------------------------------------------------------------------------
# configs: each returns a result dict (metric/value/unit + extras)
# ---------------------------------------------------------------------------


def bench_resnet50(smoke, dtype, device_kind):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel.trainer import TrainStep

    batch = int(os.environ.get("BENCH_BATCH", "8" if smoke else "256"))
    steps = int(os.environ.get("BENCH_STEPS", "3" if smoke else "20"))
    image = 32 if smoke else 224
    layout = bench_layout()  # layout A/B knob

    make = vision.resnet18_v1 if smoke else vision.resnet50_v1
    net = make(layout=layout)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros(img_shape(layout, 1, image)))

    # BENCH_REMAT: none | full | io — the bytes/step experiment knob
    # (benchmarks/bytes_report.py; "io" keeps MXU outputs + BN stats,
    # recomputes elementwise chains in backward). Unset -> remat=None so
    # the framework env vars (MXNET_BACKWARD_DO_MIRROR /
    # MXNET_REMAT_POLICY) keep their documented effect.
    remat_env = os.environ.get("BENCH_REMAT")
    # BENCH_FUSED: 1|0 — the Pallas fused BN/ReLU/residual epilogue A/B
    # knob (MXNET_FUSED_BN_EPILOGUE, ops/pallas_fused.py). Set BEFORE the
    # TrainStep build: the flag is read at trace time. Unset -> the
    # ambient env var keeps its documented effect.
    if os.environ.get("BENCH_FUSED") is not None:
        os.environ["MXNET_FUSED_BN_EPILOGUE"] = \
            "1" if os.environ["BENCH_FUSED"] == "1" else "0"
    fused = os.environ.get("MXNET_FUSED_BN_EPILOGUE", "0") == "1"
    step = TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4},
                     dtype=dtype, remat=remat_env)
    remat = step._remat  # resolved mode, reported on the line
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.uniform(-1, 1, img_shape(layout, batch, image))
                    .astype(np.float32))
    y = jnp.asarray(rng.randint(0, 1000, (batch,)).astype(np.int32))
    x.block_until_ready()

    float(step(x, y))  # compile + warmup
    float(step(x, y))
    t0 = time.perf_counter()
    loss = None
    for _ in range(steps):
        loss = step(x, y)  # donated params chain step i -> i+1
    float(loss)
    dt = time.perf_counter() - t0
    img_s = batch * steps / dt

    flops, nbytes = _xla_cost(step._step_fn, step._grad_vals,
                              step._nograd_vals, step._opt_state, x, y,
                              jax.random.PRNGKey(0), jnp.float32(0.05),
                              jnp.int32(1), jnp.float32(0.0))
    flops_source = "xla_cost_model"
    if flops is None:
        # disclosed estimate — an undisclosed fallback here would make the
        # derived mfu read as measured (sentinel-vs-measured audit)
        flops = (12.3e9 if not smoke else 0.11e9) * batch
        flops_source = "analytic_estimate"
    peak = _peak_flops(device_kind, dtype)
    mfu = (flops * steps / dt / peak) if peak else None
    bw = _hbm_bw(device_kind)
    roofline = (nbytes * steps / dt / bw) if (nbytes and bw) else None
    line = {
        "metric": ("smoke_resnet18_train_img_per_sec" if smoke
                   else "resnet50_train_img_per_sec"),
        "value": round(img_s, 2), "unit": "img/s",
        "vs_baseline": None if smoke else round(img_s / 109.0, 3),
        "batch": batch, "mfu": round(mfu, 4) if mfu is not None else None,
        "flops_per_step": flops, "flops_source": flops_source,
        "bytes_per_step": nbytes,
        "hbm_roofline_pct": (round(roofline, 4) if roofline is not None
                             else None),
        "layout": layout, "remat": remat, "fused_bn_epilogue": fused,
    }
    if smoke:
        # null, not 0.0: the smoke config (resnet18, tiny images, CPU
        # fallback) measures nothing comparable to the K80 baseline
        line["baseline_note"] = ("smoke config — not comparable to the "
                                 "109 img/s K80 ResNet-50 baseline")
    return line


def bench_resnet50_infer(smoke, dtype, device_kind):
    """Forward-only ResNet-50 throughput — the reference's
    benchmark_score.py role (inference img/s). Higher arithmetic
    intensity than training: this is where the MXU MFU ceiling shows
    (~0.48 measured vs ~0.28 for the bandwidth-bound train step)."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    batch = int(os.environ.get("BENCH_BATCH", "8" if smoke else "256"))
    steps = int(os.environ.get("BENCH_STEPS", "3" if smoke else "20"))
    image = 32 if smoke else 224
    layout = bench_layout()

    make = vision.resnet18_v1 if smoke else vision.resnet50_v1
    net = make(layout=layout)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros(img_shape(layout, 1, image)))  # materialize params

    from mxnet_tpu.parallel.functional import functionalize

    apply_fn, _names, values = functionalize(net, train_mode=False)
    cdtype = jnp.dtype(dtype)
    # cast once outside the jitted program: a per-step in-jit cast would
    # re-read every f32 parameter each timed iteration
    params = tuple(v.astype(cdtype)
                   if jnp.issubdtype(v.dtype, jnp.floating) else v
                   for v in values)

    jfwd = jax.jit(lambda vals, img: apply_fn(vals, img.astype(cdtype)))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.uniform(-1, 1, img_shape(layout, batch, image))
                    .astype(np.float32))
    out = jfwd(params, x)
    float(jnp.sum(out.astype(jnp.float32)))  # compile + warmup readback
    t0 = time.perf_counter()
    acc = None
    xi = x
    for _ in range(steps):
        out = jfwd(params, xi)
        # chain iterations through a data dependency, so the final
        # readback waits for every one of them
        s = jnp.sum(out.astype(jnp.float32))
        xi = x + (s * 1e-12).astype(x.dtype)
        acc = s
    float(acc)
    dt = time.perf_counter() - t0
    img_s = batch * steps / dt

    flops, nbytes = _xla_cost(jfwd, params, x)
    peak = _peak_flops(device_kind, dtype)
    mfu = (flops * steps / dt / peak) if (peak and flops) else None
    bw = _hbm_bw(device_kind)
    roofline = (nbytes * steps / dt / bw) if (nbytes and bw) else None
    return {"metric": ("smoke_resnet18_infer_img_per_sec" if smoke
                       else "resnet50_infer_img_per_sec"),
            "value": round(img_s, 2), "unit": "img/s", "batch": batch,
            "mfu": round(mfu, 4) if mfu is not None else None,
            "hbm_roofline_pct": (round(roofline, 4)
                                 if roofline is not None else None),
            "layout": layout}


def bench_resnet50_int8_infer(smoke, dtype, device_kind):
    """Quantized int8 inference through the contrib.quantization graph
    rewrite (reference: quantize_model + quantized benchmark flow) —
    gluon ResNet-50 exported to a Symbol, conv/FC nodes rewritten to
    int8, bound as a symbolic executor."""
    import tempfile

    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.contrib.quantization import quantize_model
    from mxnet_tpu.gluon.model_zoo import vision

    batch = int(os.environ.get("BENCH_BATCH", "8" if smoke else "256"))
    steps = int(os.environ.get("BENCH_STEPS", "3" if smoke else "20"))
    image = 32 if smoke else 224

    make = vision.resnet18_v1 if smoke else vision.resnet50_v1
    net = make()
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, 3, image, image)))
    with tempfile.TemporaryDirectory() as d:
        net.export(os.path.join(d, "r50"))
        sym, args, aux = mx.model.load_checkpoint(os.path.join(d, "r50"), 0)
    qsym, qargs, qaux = quantize_model(sym, args, aux)

    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (batch, 3, image, image)).astype(np.float32)
    exe = qsym.bind(mx.cpu(), args={**qargs, "data": nd.array(x)},
                    aux_states=qaux, grad_req="null")
    exe.forward()
    float(jnp.sum(exe.outputs[0]._data.astype(jnp.float32)))  # compile
    xj = jnp.asarray(x)
    t0 = time.perf_counter()
    s = None
    for _ in range(steps):
        exe.forward(data=nd.NDArray(xj))
        # chain: next input depends on this output
        s = jnp.sum(exe.outputs[0]._data.astype(jnp.float32))
        xj = xj + (s * 1e-12).astype(xj.dtype)
    float(s)
    dt = time.perf_counter() - t0
    return {"metric": ("smoke_resnet18_int8_infer_img_per_sec" if smoke
                       else "resnet50_int8_infer_img_per_sec"),
            "value": round(batch * steps / dt, 2), "unit": "img/s",
            "batch": batch, "quantized_dtype": "int8"}


def _run_word_lm(smoke, dtype, device_kind, batch, hid, emb):
    """Shared word-LM TrainStep harness behind the lstm_lm and lstm_sweep
    configs: build, warm, time, cost-model MFU. Returns (tok/s, mfu,
    bptt) — one timing loop so the two A/B instruments cannot drift."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep

    vocab, layers = (200, 1) if smoke else (10000, 2)
    bptt = 8 if smoke else 35
    steps = 3 if smoke else 20

    net = mx.models.RNNModel(mode="lstm", vocab_size=vocab, num_embed=emb,
                             num_hidden=hid, num_layers=layers, dropout=0.0)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((bptt, batch)))

    step = TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1}, dtype=dtype)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, vocab, (bptt, batch)).astype(np.float32))
    y = jnp.asarray(rng.randint(0, vocab, (bptt * batch,)).astype(np.int32))
    float(step(x, y))
    float(step(x, y))
    t0 = time.perf_counter()
    loss = None
    for _ in range(steps):
        loss = step(x, y)
    float(loss)
    dt = time.perf_counter() - t0
    tok_s = bptt * batch * steps / dt
    flops, _ = _xla_cost(step._step_fn, step._grad_vals, step._nograd_vals,
                         step._opt_state, x, y, jax.random.PRNGKey(0),
                         jnp.float32(0.1), jnp.int32(1), jnp.float32(0.0))
    peak = _peak_flops(device_kind, dtype)
    mfu = (flops * steps / dt / peak) if (peak and flops) else None
    return tok_s, mfu, bptt


def bench_lstm_lm(smoke, dtype, device_kind):
    """Word LM: 2-layer LSTM-200 over vocab 10k, bptt 35 (the reference
    example/rnn/word_lm defaults); fused TrainStep, tokens/s."""
    # BENCH_LSTM_BATCH: batch sweep knob (32 = reference-parity default;
    # larger batches amortize the scan's per-step latency — the word-LM
    # utilization question from the r4 verdict)
    batch = int(os.environ.get("BENCH_LSTM_BATCH", "4" if smoke else "32"))
    hid, emb = (32, 32) if smoke else (200, 200)
    tok_s, mfu, bptt = _run_word_lm(smoke, dtype, device_kind, batch, hid,
                                    emb)
    return {"metric": "lstm_word_lm_train_tok_per_sec",
            "value": round(tok_s, 1), "unit": "tok/s",
            "batch": batch, "bptt": bptt,
            "vs_baseline": None,
            "baseline_note": "no published throughput in the reference "
                             "tree (example/rnn/word_lm README reports "
                             "perplexity only)",
            "mfu": round(mfu, 4) if mfu is not None else None}


def bench_lstm_sweep(smoke, dtype, device_kind, batch=None, fused=False):
    """Word-LM LSTM batch sweep x fused-RNN A/B — the ADVICE round-5
    artifact adjudicating latency-bound vs bandwidth-bound
    (BENCH_LSTM_SWEEP.jsonl). Each line is one
    (batch, fused) point: `fused_rnn: on` routes the recurrence through
    the persistent Pallas scan kernel (MXNET_FUSED_RNN,
    ops/pallas_rnn.py — one launch per sequence, h/c resident in VMEM);
    `off` is today's lax.scan path. Hidden is widened 200->256 so the
    kernel is Mosaic-tile eligible on TPU (H % 128 == 0) — disclosed on
    the line; the canonical `lstm_lm` config keeps reference parity at
    200. BENCH_LSTM_SWEEP_FULL=1 runs the full batch {32,64,128,256}
    sweep; default emits the batch-32 A/B pair only."""
    emb, hid = (32, 32) if smoke else (256, 256)
    hid = int(os.environ.get("BENCH_LSTM_HIDDEN", hid))
    if batch is None:
        batch = int(os.environ.get("BENCH_LSTM_BATCH", "4" if smoke
                                   else "32"))

    # the flag is read at TRACE time (ops/nn.py _scan_layer), so it must
    # cover the TrainStep build; restored after (bytes_report discipline)
    prior = os.environ.get("MXNET_FUSED_RNN")
    os.environ["MXNET_FUSED_RNN"] = "1" if fused else "0"
    try:
        tok_s, mfu, bptt = _run_word_lm(smoke, dtype, device_kind, batch,
                                        hid, emb)
    finally:
        if prior is None:
            os.environ.pop("MXNET_FUSED_RNN", None)
        else:
            os.environ["MXNET_FUSED_RNN"] = prior
    return {"metric": ("smoke_lstm_sweep_train_tok_per_sec" if smoke
                       else "lstm_sweep_train_tok_per_sec"),
            "value": round(tok_s, 1), "unit": "tok/s",
            "batch": batch, "bptt": bptt, "hidden": hid,
            "fused_rnn": "on" if fused else "off",
            "vs_baseline": None,
            "baseline_note": "in-line fused-off leg is the comparison; "
                             "hidden widened 200->256 for Mosaic tile "
                             "eligibility (H%128) — the canonical "
                             "lstm_lm line keeps reference parity",
            "mfu": round(mfu, 4) if mfu is not None else None}


def bench_transformer_flash(smoke, dtype, device_kind, seq_len=None):
    """Transformer LM train step, Pallas flash attention vs XLA reference
    attention. BENCH_FLASH_SEQ=1024,2048,... sweeps sequence lengths.

    DECIDED 2026-07-31 (v5e sweep, BENCH_FLASH_SWEEP.jsonl): 0.987x /
    1.058x / 0.956x at seq 1024/2048/4096 — below the >=1.2x bar, so the
    kernel is OPT-IN (MXNET_FLASH_ATTENTION=1); XLA attention is the
    default path. This bench keeps measuring both so a future JAX/Pallas
    upgrade that flips the ratio is caught."""
    import functools
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params,
                                              lm_loss)

    cfg = TransformerConfig(vocab=256, d_model=64, n_heads=4, n_layers=2,
                            d_ff=128, max_len=128) if smoke else \
        TransformerConfig(vocab=8192, d_model=512, n_heads=8, n_layers=6,
                          d_ff=2048, max_len=seq_len or 1024)
    batch = 2 if smoke else max(1, 8 * 1024 // (seq_len or 1024))
    steps = 2 if smoke else 10
    lr = 0.1

    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, cfg.vocab, (batch, cfg.max_len)),
                       jnp.int32)

    def measure(flash):
        os.environ["MXNET_FLASH_ATTENTION"] = "1" if flash else "0"

        @functools.partial(jax.jit, donate_argnums=0)
        def step(params, tokens):
            loss, grads = jax.value_and_grad(lm_loss)(params, tokens, cfg,
                                                      mesh=None)
            return {k: v - lr * grads[k] for k, v in params.items()}, loss

        params = init_transformer_params(jax.random.PRNGKey(0), cfg)
        if dtype == "bfloat16":
            params = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
        params, l0 = step(params, toks)
        float(l0)
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            params, loss = step(params, toks)
        float(loss)
        return time.perf_counter() - t0

    from mxnet_tpu.ops.pallas_attention import default_interpret
    interp = default_interpret()
    prior = os.environ.get("MXNET_FLASH_ATTENTION")
    try:
        dt_flash = measure(True)
        # off-TPU the ratio is interpreter overhead, not the kernel — skip
        # the reference run entirely instead of burning minutes to discard it
        dt_ref = None if interp else measure(False)
    finally:
        if prior is None:
            os.environ.pop("MXNET_FLASH_ATTENTION", None)
        else:
            os.environ["MXNET_FLASH_ATTENTION"] = prior
    tok_s = batch * cfg.max_len * steps / dt_flash
    line = {"metric": "transformer_lm_flash_tok_per_sec",
            "value": round(tok_s, 1), "unit": "tok/s",
            "batch": batch, "seq_len": cfg.max_len,
            "vs_baseline": None,
            "baseline_note": "the reference tree (2018-era) has no "
                             "transformer benchmark; the in-line XLA-"
                             "attention A/B is the comparison"}
    if interp:
        # off-TPU the kernel runs under the Pallas INTERPRETER — a ratio
        # would measure interpreter overhead, not the kernel; labeled
        # instead of published as a speedup claim
        line["interpret_mode"] = True
    else:
        line["flash_speedup_vs_xla_attention"] = round(dt_ref / dt_flash, 3)
    return line


def bench_ssd_forward(smoke, dtype, device_kind):
    """SSD detection forward (example/ssd benchmark role), img/s."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.parallel.functional import functionalize

    batch = 2 if smoke else 32
    image = 64 if smoke else 256
    steps = 3 if smoke else 20

    net = mx.models.SSDLite(num_classes=20)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, 3, image, image)))
    apply_fn, _names, values = functionalize(net, train_mode=False)
    if dtype == "bfloat16":
        values = [v.astype(jnp.bfloat16) if v.dtype == jnp.float32 else v
                  for v in values]

    in_dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    fwd = jax.jit(lambda vals, img: apply_fn(vals, img.astype(in_dtype)))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.uniform(-1, 1, (batch, 3, image, image))
                    .astype(np.float32))
    out = fwd(values, x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        # chain: feed a scalar of the previous output back into the input
        first = out[0] if isinstance(out, (list, tuple)) else out
        x = x + 0 * first.reshape(-1)[0].astype(x.dtype)
        out = fwd(values, x)
    first = out[0] if isinstance(out, (list, tuple)) else out
    float(first.reshape(-1)[0].astype(jnp.float32))
    dt = time.perf_counter() - t0
    # Anchor: the reference's published SSD speed table — VGG16_reduced
    # 300x300 forward on TITAN X (Maxwell)/cuDNN 5.1 = 95 FPS at batch
    # 8/16 (example/ssd/README.md:43-49, "forward time only"). Backbone
    # differs (SSDLite here), so the ratio is a directional anchor, not a
    # same-model comparison — disclosed on the line.
    return {"metric": "ssd_forward_img_per_sec",
            "vs_baseline": (None if smoke
                            else round(batch * steps / dt / 95.0, 3)),
            "baseline_note": "95 FPS VGG16-reduced 300x300 TITAN X "
                             "forward (example/ssd/README.md:43-49); "
                             "backbone differs (SSDLite) - directional",
            "value": round(batch * steps / dt, 2), "unit": "img/s",
            "batch": batch, "image": image}


def bench_sparse_linear(smoke, dtype, device_kind):
    """Sparse logistic regression step (example/sparse/linear_
    classification): csr batch -> csr^T segment-sum gradient -> row_sparse
    lazy update. samples/s (eager path: per-step host loop)."""
    import mxnet_tpu as mx
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.ndarray.sparse import CSRNDArray
    from mxnet_tpu.models.sparse_linear import SparseLinear

    n, d, nnz_row = (64, 1000, 10) if smoke else (512, 2000000, 60)
    # same-config device A/B (r4 verdict weak: the TPU 2M-feature line and
    # the CPU 1k smoke line were incomparable): BENCH_SPARSE_FULL=1 forces
    # the full config even in a CPU smoke run; BENCH_SPARSE_D sweeps the
    # feature scale so the crossover point is measurable on both devices.
    if os.environ.get("BENCH_SPARSE_FULL", "") == "1":
        n, d, nnz_row = 512, 2000000, 60
        steps_full = True
    else:
        steps_full = not smoke
    d = int(os.environ.get("BENCH_SPARSE_D", d))
    steps = 15 if steps_full else 3
    rng = np.random.RandomState(0)
    cols = rng.randint(0, d, n * nnz_row).astype(np.int32)
    indptr = np.arange(0, n * nnz_row + 1, nnz_row).astype(np.int32)
    x = CSRNDArray(rng.rand(n * nnz_row).astype(np.float32), cols, indptr,
                   (n, d))
    y = NDArray((rng.rand(n) > 0.5).astype(np.float32))
    model = SparseLinear(num_features=d, num_classes=2, learning_rate=0.1)
    model.step(x, y)  # warm
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = model.step(x, y)  # weight updates chain the iterations
    dt = time.perf_counter() - t0
    return {"metric": "sparse_linear_train_samples_per_sec",
            "value": round(n * steps / dt, 1), "unit": "samples/s",
            "num_features": d, "nnz_per_row": nnz_row,
            "vs_baseline": None,
            "baseline_note": "no published throughput in the reference "
                             "tree (example/sparse/linear_classification "
                             "README is usage-only); paired CPU/TPU "
                             "same-config lines are the comparison",
            "final_loss": round(loss, 4)}


def _write_synthetic_rec(n, side):
    """Pack n JPEG records (8 distinct images reused, labels i%10) into a
    temp .rec; shared by the io-pipeline and e2e-train benches. Caller
    unlinks the returned path."""
    import io as pyio
    import tempfile
    from PIL import Image
    import mxnet_tpu as mx

    fd, rec = tempfile.mkstemp(suffix=".rec")
    os.close(fd)
    try:
        rng = np.random.RandomState(0)
        jpgs = []
        for _ in range(8):
            arr = rng.randint(0, 255, (side, side, 3)).astype(np.uint8)
            buf = pyio.BytesIO()
            Image.fromarray(arr).save(buf, format="JPEG", quality=90)
            jpgs.append(buf.getvalue())
        w = mx.recordio.MXRecordIO(rec, "w")
        for i in range(n):
            w.write(mx.recordio.pack(
                mx.recordio.IRHeader(0, float(i % 10), i, 0), jpgs[i % 8]))
        w.close()
    except BaseException:
        try:
            os.unlink(rec)
        except OSError:
            pass
        raise
    return rec


def bench_io_pipeline(smoke, dtype, device_kind):
    """Native C++ RecordIO + JPEG decode/augment pipeline throughput
    (the input half of the reference's ImageRecordIter benchmark; host-
    side, so the number is real regardless of accelerator state)."""
    from mxnet_tpu import native

    if not native.AVAILABLE:
        return {"metric": "io_pipeline_img_per_sec", "value": None,
                "unit": "img/s", "error": "native extension not built"}
    n, side = (64, 64) if smoke else (512, 224)
    rec = _write_synthetic_rec(n, side)
    it = None
    try:
        it = native.NativeImageIter(rec, batch_size=32,
                                    data_shape=(3, side, side),
                                    num_threads=0, rand_mirror=True)
        # warm epoch (thread spin-up), then timed epoch
        while it.next_batch() is not None:
            pass
        it.reset()
        total = 0
        t0 = time.perf_counter()
        while True:
            out = it.next_batch()
            if out is None:
                break
            total += out[2]
        dt = time.perf_counter() - t0
    finally:
        if it is not None:
            it.close()
        try:
            os.unlink(rec)
        except OSError:
            pass
    return {"metric": "io_pipeline_img_per_sec",
            "value": round(total / dt, 1), "unit": "img/s",
            "image": side, "images": total}


def bench_e2e_train_io(smoke, dtype, device_kind):
    """End-to-end: RecordIO -> native JPEG decode/augment -> host prefetch
    -> DevicePrefetchIter staging -> fused ResNet train step. Reports the
    steady-state img/s AND the overlap accounting the r4 verdict asked
    for: wall time vs the io-only and compute-only legs (perfect overlap
    => wall ~= max(leg); serialization => wall ~= sum). On this 1-core
    container the absolute number is input-bound by design; the artifact
    is the overlap ratio + the decode-pool worker scaling table.
    Reference recipe: iter_image_recordio_2.cc's double-buffered pipeline
    feeding benchmark.py."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import native
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.io import DevicePrefetchIter, ImageRecordIter
    from mxnet_tpu.parallel.trainer import TrainStep

    if not native.AVAILABLE:
        return {"metric": ("smoke_e2e_train_io_img_per_sec" if smoke
                           else "e2e_train_io_img_per_sec"),
                "value": None,
                "unit": "img/s", "error": "native extension not built"}
    n, side, batch = (128, 64, 32) if smoke else (1024, 224, 64)
    n = int(os.environ.get("BENCH_E2E_N", n))
    rec = _write_synthetic_rec(n, side)
    try:
        rng = np.random.RandomState(0)

        def host_iter(threads=0):
            return ImageRecordIter(path_imgrec=rec, batch_size=batch,
                                   data_shape=(3, side, side),
                                   preprocess_threads=threads,
                                   rand_mirror=True)

        make = vision.resnet18_v1 if smoke else vision.resnet50_v1
        net = make(classes=10)
        net.initialize(mx.init.Xavier())
        net(mx.nd.zeros((1, 3, side, side)))
        step = TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                         {"learning_rate": 0.05, "momentum": 0.9},
                         dtype=dtype)

        def run_epoch(it):
            """One e2e epoch; returns (images, wall_s). Loss readback at
            the end only — intermediate steps chain through donation."""
            it.reset()
            total, loss = 0, None
            t0 = time.perf_counter()
            for b in it:
                x = b.data[0]._data
                y = b.label[0]._data.astype(jnp.int32)
                loss = step(x, y)
                total += x.shape[0]
            float(loss)
            return total, time.perf_counter() - t0

        dev_it = DevicePrefetchIter(host_iter(), depth=2)
        # ONE throwaway epoch warms everything every leg reuses: the
        # jitted step (compile), the decode thread pool, and the device
        # staging buffers. Both legs are then measured from that same
        # state BEFORE the e2e wall, so a cold cache can only make `wall`
        # larger — overlap_efficiency <= 1 by construction instead of by
        # luck (r5 verdict weak #3: a committed line showed 1.101 because
        # the io-only leg ran colder than the e2e epoch it was compared
        # against).
        warm_total, _ = run_epoch(dev_it)

        # compute-only leg: same number of steps on one staged batch,
        # reusing the already-jitted step (no recompile in the timing)
        steps = (warm_total + batch - 1) // batch
        x0 = jnp.asarray(rng.uniform(-1, 1, (batch, 3, side, side))
                         .astype(np.float32))
        y0 = jnp.asarray(rng.randint(0, 10, (batch,)).astype(np.int32))
        float(step(x0, y0))
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = step(x0, y0)
        float(loss)
        t_comp = time.perf_counter() - t0

        # io-only leg (host pipeline + device staging, no compute), with
        # its own warm drain first — the same state the e2e epoch starts
        # from. Chain every staged batch into a scalar and read it back,
        # so the drain waits for every transfer.
        def drain():
            dev_it.reset()
            t0 = time.perf_counter()
            acc = jnp.float32(0)
            for b in dev_it:
                acc = acc + b.data[0]._data.reshape(-1)[0] \
                    .astype(jnp.float32)
            float(acc)
            return time.perf_counter() - t0

        drain()                               # warm
        t_io = drain()

        # e2e wall LAST, from the same warmed state as both legs
        total, wall = run_epoch(dev_it)
        e2e = total / wall

        # self-consistency, enforced in-bench: the e2e epoch does BOTH
        # workloads, so its wall can't beat the slower leg alone — if it
        # does, a leg was mismeasured and this line must not be emitted.
        # Explicit raise, not `assert`: python -O must not turn a
        # mismeasured run into a recorded number (same as check_line).
        if wall < max(t_comp, t_io) * 0.98:
            raise ValueError(
                "e2e wall %.3fs < max(compute %.3fs, io %.3fs) * 0.98 — "
                "overlap legs mismeasured" % (wall, t_comp, t_io))

        # 1.0 = the slower leg fully hides the faster one (min() clamps
        # the <=2% assertion slack so the field is <= 1 by construction)
        overlap = min(1.0, max(t_comp, t_io) / wall) if wall else None

        # decode-pool scaling on the host leg (queue behavior even when
        # nproc=1: more workers only help if decode blocks on IO)
        scaling = {}
        for k in (1, 2, 4):
            it = host_iter(threads=k)
            for _ in it:      # warm epoch (thread spin-up)
                pass
            it.reset()
            cnt = 0
            t0 = time.perf_counter()
            for b in it:
                cnt += b.data[0].shape[0]
            scaling["%d" % k] = round(cnt / (time.perf_counter() - t0), 1)

        return {"metric": ("smoke_e2e_train_io_img_per_sec" if smoke
                           else "e2e_train_io_img_per_sec"),
                "value": round(e2e, 1), "unit": "img/s",
                "batch": batch, "image": side, "images": total,
                "wall_s": round(wall, 3),
                "compute_only_s": round(t_comp, 3),
                "io_only_s": round(t_io, 3),
                "overlap_efficiency": (round(overlap, 3)
                                       if overlap else None),
                "decode_pool_img_per_sec": scaling}
    finally:
        try:
            os.unlink(rec)
        except OSError:
            pass


def bench_serving(smoke, dtype, device_kind, batch=None, tp=None,
                  replicas=None):
    """Offline continuous-batching decode throughput (tokens/s) through
    mxnet_tpu.serving's paged-KV engine — the serving trajectory line.
    BENCH_SERVING_BATCH overrides the batch; the full run sweeps
    {1, 8, 32} via _run_configs. Decode-only timing: prefill compiles
    and the cache fill are excluded (reported separately, now with
    per-request time-to-first-token p50/p95 and prefill tok/s), matching
    how a steady-state server spends its time. `paged_attention: on|off`
    (MXNET_PAGED_ATTENTION, the ragged Pallas kernel + chunked prefill
    of ops/pallas_paged.py) labels every line so A/B runs pair up.

    With `tp=`/`replicas=` (the ISSUE 8 grid) the leg measures the multi-chip front door instead: aggregate tok/s
    through `serve(replicas=..., tp=...)` under a mixed-length request
    wave, per-replica TTFT p50/p95, and the router's pick overhead in
    microseconds."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import serving
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params)

    if tp is not None or replicas is not None:
        return _bench_serving_frontdoor(smoke, dtype, tp or 1,
                                        replicas or 1, batch)
    if batch is None:
        batch = int(os.environ.get("BENCH_SERVING_BATCH", "2" if smoke
                                   else "8"))
    # r6: d_model 256->512, heads 8->4 (head_dim 32->128) so the Mosaic
    # paged kernel is tile-eligible on TPU; trajectory comparable r6 on
    cfg = TransformerConfig(vocab=128, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=64) if smoke else \
        TransformerConfig(vocab=8192, d_model=512, n_heads=4, n_layers=4,
                          d_ff=2048, max_len=1024)
    prompt_len = 8 if smoke else 64
    gen = 8 if smoke else 128
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    if dtype == "bfloat16":
        params = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    eng = serving.Engine(serving.TransformerLM(params, cfg),
                         max_batch=batch, block_size=16)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, cfg.vocab, prompt_len))
               for _ in range(batch)]
    # prefill-path compile warmup (same signature as the timed starts),
    # so TTFT percentiles measure the steady-state path
    warm = eng.start(list(prompts[0]), max_new=2)
    eng.release(warm)
    # telemetry histograms ride the emitted line (the `telemetry` field
    # added by _run_configs): full TTFT/step distributions, not just the
    # p50/p95 the headline carries
    from mxnet_tpu import telemetry as _telemetry
    h_ttft = _telemetry.histogram(
        "serving_bench_ttft_seconds",
        help="per-request time to first token (bench harness)")
    h_step = _telemetry.histogram(
        "serving_bench_decode_step_seconds",
        help="per decode step, synchronous host timing (bench harness)")
    ttft_s = []
    seqs = []
    t0 = time.perf_counter()
    for p in prompts:
        t1 = time.perf_counter()
        seqs.append(eng.start(list(p), max_new=gen + 1))
        ttft_s.append(time.perf_counter() - t1)
        h_ttft.observe(ttft_s[-1])
    t_prefill = time.perf_counter() - t0
    eng.decode_step(seqs)  # decode-path compile + warmup
    steps = 0
    t0 = time.perf_counter()
    for _ in range(gen - 1):
        t1 = time.perf_counter()
        eng.decode_step(seqs)
        h_step.observe(time.perf_counter() - t1)
        steps += 1
    # the loop runs synchronous host steps; the final per-step readback
    # already forces completion, no extra sync needed
    dt = time.perf_counter() - t0
    for s in seqs:
        eng.release(s)
    # SLO view of the same measurements (ISSUE 13): fraction of
    # requests whose TTFT met the disclosed threshold, and the tokens
    # those requests delivered per second (every sequence decodes the
    # same `steps` tokens here, so goodput is exactly attainment-scaled
    # throughput). BENCH_SLO_TTFT_MS overrides the threshold.
    slo_ttft_ms = float(os.environ.get("BENCH_SLO_TTFT_MS", "250"))
    n_meet = sum(1 for t in ttft_s if 1e3 * t <= slo_ttft_ms)
    attainment = n_meet / float(len(ttft_s))
    value = round(batch * steps / dt, 1)
    return {"metric": ("smoke_serving_decode_tok_per_sec" if smoke
                       else "serving_decode_tok_per_sec"),
            "value": value, "unit": "tok/s",
            "slo_ttft_ms": slo_ttft_ms,
            "slo_ttft_attainment": round(attainment, 4),
            "goodput_tok_per_sec": round(n_meet * steps / dt, 1),
            "batch": batch, "prompt_len": prompt_len,
            "seq_len": cfg.max_len,
            "decode_ms_per_step": round(1e3 * dt / steps, 3),
            "prefill_s": round(t_prefill, 3),
            "prefill_tok_per_sec": round(batch * prompt_len / t_prefill,
                                         1),
            "ttft_ms_p50": round(1e3 * float(np.percentile(ttft_s, 50)),
                                 3),
            "ttft_ms_p95": round(1e3 * float(np.percentile(ttft_s, 95)),
                                 3),
            "paged_attention": "on" if eng.paged else "off",
            "prefill_chunk": eng.prefill_chunk or None,
            "decode_compilations": eng.decode_compilations,
            "prefill_compilations": eng.prefill_compilations,
            "vs_baseline": None,
            "baseline_note": "no serving path exists in the reference "
                             "tree (c_predict_api is one-shot); this "
                             "line tracks the trajectory from PR 1 on "
                             "(config widened r6 for kernel tile "
                             "eligibility)"}


def _bench_serving_frontdoor(smoke, dtype, tp, replicas, batch=None):
    """One tp x replicas leg of the multi-chip serving grid (ISSUE 8):
    a mixed-length wave of `replicas * batch` requests through the real
    front door (`serve(replicas=, tp=)` — router, per-replica engines,
    continuous batching). Reports AGGREGATE tok/s over the timed wave
    (one untimed warmup wave absorbs every prefill/decode compile),
    per-replica TTFT p50/p95 from the replica registries, and router
    pick overhead in microseconds. tp falls back per the placement
    rules; the emitted `tp` is the EFFECTIVE degree, with the requested
    one and the reason disclosed on fallback."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import serving
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params)

    if batch is None:
        batch = int(os.environ.get("BENCH_SERVING_BATCH", "2" if smoke
                                   else "8"))
    cfg = TransformerConfig(vocab=128, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=64) if smoke else \
        TransformerConfig(vocab=8192, d_model=512, n_heads=4, n_layers=4,
                          d_ff=2048, max_len=1024)
    gen = 8 if smoke else 64
    base_len = 8 if smoke else 32
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    if dtype == "bfloat16":
        params = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    srv = serving.serve((params, cfg), replicas=replicas, tp=tp,
                        max_batch=batch, block_size=16, paged=True,
                        max_queue=4 * batch * replicas)
    try:
        reps = srv.replicas if replicas > 1 else [srv]
        eng0 = reps[0].engine
        rng = np.random.RandomState(0)
        # mixed lengths: the router's least-loaded score has real work
        # to balance, same spread every leg
        lens = [max(1, int(l)) for l in
                rng.randint(base_len // 2, 2 * base_len,
                            batch * replicas)]

        def wave(lengths):
            reqs = [srv.submit(list(rng.randint(1, cfg.vocab, L)),
                               max_new_tokens=gen) for L in lengths]
            for r in reqs:
                r.result(timeout=600)
            return reqs

        # warmup replays the SAME length multiset the timed wave uses,
        # so every pow2 prefill/decode bucket the timed wave can hit is
        # already compiled — no compile lands inside the timing
        wave(lens)
        t0 = time.perf_counter()
        timed = wave(lens)
        dt = time.perf_counter() - t0
        tokens = sum(len(r.tokens) - len(r.prompt) for r in timed)

        # steady-state TTFT per replica from the TIMED wave only (the
        # registries' lifetime histograms include warmup compiles)
        by_rep = [[] for _ in reps]
        for r in timed:
            by_rep[getattr(r, "replica", None) or 0].append(
                1e3 * (r.t_first_token - r.t_submit))

        # SLO view (ISSUE 13): per-request TTFT against the disclosed
        # threshold; goodput counts only the tokens of meeting requests
        slo_ttft_ms = float(os.environ.get("BENCH_SLO_TTFT_MS", "250"))
        meeting = [r for r in timed
                   if 1e3 * (r.t_first_token - r.t_submit)
                   <= slo_ttft_ms]
        goodput_tokens = sum(len(r.tokens) - len(r.prompt)
                             for r in meeting)

        def ttft_ms(i, q):
            return (round(float(np.percentile(by_rep[i], q)), 3)
                    if by_rep[i] else None)

        line = {"metric": ("smoke_serving_frontdoor_tok_per_sec" if smoke
                           else "serving_frontdoor_tok_per_sec"),
                "value": round(tokens / dt, 1), "unit": "tok/s",
                "tp": eng0.tp, "tp_requested": eng0.tp_requested,
                "replicas": replicas, "batch": batch,
                "requests_timed": len(timed), "gen_tokens": gen,
                "requests_per_replica": [len(b) for b in by_rep],
                "slo_ttft_ms": slo_ttft_ms,
                "slo_ttft_attainment": (round(
                    len(meeting) / float(len(timed)), 4)
                    if timed else None),
                "goodput_tok_per_sec": (round(goodput_tokens / dt, 1)
                                        if timed else None),
                "paged_attention": "on" if eng0.paged else "off",
                "ttft_ms_p50_per_replica": [ttft_ms(i, 50)
                                            for i in range(len(reps))],
                "ttft_ms_p95_per_replica": [ttft_ms(i, 95)
                                            for i in range(len(reps))],
                "prefill_compilations": [r.engine.prefill_compilations
                                         for r in reps],
                "decode_compilations": [r.engine.decode_compilations
                                        for r in reps],
                "vs_baseline": None,
                "baseline_note": "ISSUE 8 tp x replicas grid; pairs "
                                 "against its own tp=1/replicas=1 leg, "
                                 "not the reference (no serving path "
                                 "exists there)"}
        if eng0.tp_fallback:
            line["tp_fallback"] = eng0.tp_fallback
        if replicas > 1:
            pick = srv.registry.histogram("serving_router_pick_seconds")
            line["router_pick_us_mean"] = (
                round(1e6 * pick.mean, 2) if pick.count else None)
            p95 = pick.quantile(0.95)
            line["router_pick_us_p95"] = (
                round(1e6 * p95, 2) if p95 is not None else None)
            line["replicas_drained"] = sum(srv._drained)
        return line
    finally:
        srv.close()


def bench_serving_prefix(smoke, dtype, device_kind, prefix_cache=False):
    """Shared-system-prompt serving A/B (ISSUE 10): R requests share a
    long common prefix (the multi-tenant system-prompt / few-shot
    pattern) with unique per-request suffixes, streamed sequentially
    through the paged engine with the prefix cache off vs on. The
    cache-on leg should serve later requests' shared blocks from
    residency — whole prefill chunks skipped — so the line reports
    per-request TTFT p50/p95 (the headline value), prefill tok/s, the
    hit rate, and tokens whose prefill was skipped. Both legs run the
    SAME compiled kernels; the only difference is which blocks the
    tables point at (logit parity pinned in
    tests/test_serving_prefix.py). On CPU the paged kernels run in
    Pallas interpret mode — absolute times are inflated; judge the
    on/off DELTA, not the magnitudes (disclosed on the line)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import serving
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params)

    cfg = TransformerConfig(vocab=128, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=128) if smoke else \
        TransformerConfig(vocab=8192, d_model=512, n_heads=4, n_layers=4,
                          d_ff=2048, max_len=1024)
    block_size = 8 if smoke else 16
    shared_len = 48 if smoke else 256
    suffix_len = 8 if smoke else 32
    gen = 4 if smoke else 16
    requests = 6 if smoke else 8
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    if dtype == "bfloat16":
        params = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    eng = serving.Engine(serving.TransformerLM(params, cfg),
                         max_batch=requests, block_size=block_size,
                         paged=True, prefix_cache=prefix_cache)
    if not eng.paged:
        raise RuntimeError("prefix A/B needs the paged path; fallback: "
                           "%r" % (eng.prefix_cache_fallback,))
    rng = np.random.RandomState(0)
    shared = list(rng.randint(1, cfg.vocab, shared_len))
    prompts = [shared + list(rng.randint(1, cfg.vocab, suffix_len))
               for _ in range(requests)]
    # warmup: two same-shape requests with a shared prefix, so the
    # chunk/decode kernels AND the cache-on leg's COW copy are all
    # compiled before timing; drop the warmup's cache state afterwards
    wshared = list(rng.randint(1, cfg.vocab, shared_len))
    for wsuf in ([1, 2], [1, 3]):
        w = eng.start(wshared + wsuf + [0] * (suffix_len - 2),
                      max_new=2)
        eng.decode_step([w])
        eng.release(w)
    pc = eng.prefix_cache
    if pc is not None:
        pc.flush()
        pc.lookups = pc.hits = pc.misses = 0
        pc.hit_tokens_total = pc.cow_copies = pc.evictions = 0
    ttft_s, seqs = [], []
    t0 = time.perf_counter()
    for p in prompts:
        t1 = time.perf_counter()
        seqs.append(eng.start(list(p), max_new=gen + 1))
        ttft_s.append(time.perf_counter() - t1)
    t_prefill = time.perf_counter() - t0
    t0 = time.perf_counter()
    steps = 0
    for _ in range(gen - 1):
        eng.decode_step(seqs)
        steps += 1
    dt = time.perf_counter() - t0
    for s in seqs:
        eng.release(s)
    line = {"metric": ("smoke_serving_prefix_ttft_ms_p50" if smoke
                       else "serving_prefix_ttft_ms_p50"),
            "value": round(1e3 * float(np.percentile(ttft_s, 50)), 3),
            "unit": "ms",
            "prefix_cache": "on" if prefix_cache else "off",
            "requests": requests, "shared_prefix_len": shared_len,
            "suffix_len": suffix_len, "prompt_len": shared_len
            + suffix_len, "block_size": block_size,
            "ttft_ms_p95": round(1e3 * float(np.percentile(ttft_s, 95)),
                                 3),
            "prefill_s_total": round(t_prefill, 4),
            "prefill_tok_per_sec": round(
                requests * (shared_len + suffix_len) / t_prefill, 1),
            "decode_tok_per_sec": round(requests * steps / dt, 1),
            "paged_attention": "on",
            "vs_baseline": None,
            "baseline_note": "ISSUE 10 cache on/off A/B at a shared-"
                             "system-prompt workload; pairs against its "
                             "own prefix_cache=off leg (no serving path "
                             "exists in the reference tree)"}
    if pc is not None:
        line.update(prefix_hit_rate=round(pc.hit_rate, 4),
                    prefix_hit_tokens=pc.hit_tokens_total,
                    prefix_cow_copies=pc.cow_copies,
                    prefix_evictions=pc.evictions)
    if device_kind in ("cpu", "CPU") or "cpu" in str(device_kind).lower():
        line["interpreter_note"] = (
            "CPU leg: Pallas paged kernels run in interpret mode; "
            "absolute times are inflated ~100x — judge the cache "
            "on/off delta only")
    return line


def bench_resilience(smoke, dtype, device_kind):
    """BENCH_RESILIENCE: fault-tolerance runtime overhead — checkpoint
    state-capture (device->host copy, the only part that blocks the
    train loop), async publish and restore latency, and steps lost per
    simulated preemption (re-executed work after a kill at an
    off-cadence step). Tracks the watcher's cost across PRs; the model
    is an MLP sized so state volume, not compile time, dominates."""
    import shutil
    import tempfile
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel.trainer import TrainStep
    from mxnet_tpu.parallel.resilient import ResilientLoop
    from mxnet_tpu.utils.recovery import CheckpointManager

    hidden = 64 if smoke else 1024
    batch = 16 if smoke else 128
    save_every, kill_at = (2, 5) if smoke else (8, 19)
    mx.random.seed(0)
    np.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(hidden, in_units=hidden, activation="relu"))
    net.add(gluon.nn.Dense(hidden, in_units=hidden, activation="relu"))
    net.add(gluon.nn.Dense(10, in_units=hidden))
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                     {"learning_rate": 1e-3}, guard=True)

    def batch_for(i):
        r = np.random.RandomState(i)
        return (r.randn(batch, hidden).astype(np.float32),
                r.randint(0, 10, (batch,)).astype(np.float32))

    d = tempfile.mkdtemp(prefix="bench_resil_")
    try:
        mgr = CheckpointManager(d, keep=3)
        # the batches flow through a real DataLoader + loop.batches()
        # so the train_data_wait_seconds histogram is fed and the
        # emitted data_wait_fraction (ISSUE 14) is a measurement, not a
        # placeholder
        from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
        xs = np.stack([batch_for(i)[0] for i in range(kill_at)]) \
            .reshape(-1, hidden)
        ys = np.concatenate([batch_for(i)[1] for i in range(kill_at)])
        loader = DataLoader(ArrayDataset(xs, ys), batch_size=batch)
        # cadence saves OFF in the loop (save_every=0): the bench times
        # its own blocking saves below — a concurrent async save of the
        # same state would make every timed publish first drain it
        # warm the compile BEFORE the loop exists: TrainStep.__call__
        # records no train_step_seconds sample, so the first step's XLA
        # compile (seconds vs ~ms steady steps) never lands in the
        # histograms the step_p95_ms / data_wait_fraction fields read
        from mxnet_tpu import telemetry as _telemetry
        step(*batch_for(kill_at + 1))
        loop = ResilientLoop(step, mgr, loader=loader, save_every=0,
                             policy="skip", watch_preemption=False,
                             verbose=False, metrics_port=False)
        capture_s = []
        publish_s = []
        batches = loop.batches()
        while loop.t < kill_at:          # train to the simulated kill
            loop.step(*next(batches))
            if loop.t % save_every == 0:
                t0 = time.perf_counter()
                state = loop.state_dict()      # device->host capture
                capture_s.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                mgr.save(loop.t, state, block=True)  # full publish
                publish_s.append(time.perf_counter() - t0)
        mgr.wait(_barrier=False)
        # remediation MTTR (ISSUE 15): fault-inject -> first
        # post-recovery step, measured over the exact path a
        # supervisor-driven restart takes (restore_latest + state load
        # + one already-compiled step); steps_lost_per_remediation is
        # the re-executed work the restart cadence implies
        t_fault = time.perf_counter()
        restored = mgr.restore_latest()        # the relaunch path
        step0, tree = restored
        loop.load_state_dict(tree)
        restore_s = time.perf_counter() - t_fault
        loop.step(*batch_for(loop.t))      # first post-recovery step
        mttr_s = time.perf_counter() - t_fault
        steps_lost = kill_at - step0
        state_bytes = sum(np.asarray(v).nbytes
                          for v in jax.tree.leaves(tree))
        single_npz = os.path.getsize(
            os.path.join(d, "ckpt-%d.npz" % mgr.latest_step()))

        # ISSUE 14 step-tail / data-wait fields: read from the loop's
        # OWN statusz (the live console computes them identically — one
        # definition, bench and console can't diverge), snapshotted
        # HERE because the sharded ZeRO-1 leg below runs loaderless
        # steps (+ its own compile) that would dilute the fraction and
        # hand the p95 to compile time
        z = loop.statusz()
        data_wait_fraction = (round(z["data_wait_fraction"], 4)
                              if z["data_wait_fraction"] is not None
                              else None)
        step_p95_ms = z["step_p95_ms"]

        # -- sharded A/B (ISSUE 6): per-host sharded checkpoints of the
        # SAME state volume, N emulated hosts over a dp mesh with the
        # ZeRO-1 sharded update. Measures what the single-writer
        # protocol cannot scale: bytes-per-host (should land at
        # ~total/N vs total-on-process-0) and the publish/restore
        # latency of the sharded format.
        sharded = None
        n_hosts = min(4, len(jax.devices()))
        if n_hosts > 1:
            from mxnet_tpu.parallel.mesh import build_mesh
            mx.random.seed(0)
            np.random.seed(0)
            net2 = gluon.nn.HybridSequential()
            net2.add(gluon.nn.Dense(hidden, in_units=hidden,
                                    activation="relu"))
            net2.add(gluon.nn.Dense(hidden, in_units=hidden,
                                    activation="relu"))
            net2.add(gluon.nn.Dense(10, in_units=hidden))
            net2.initialize(mx.init.Xavier())
            mesh = build_mesh({"dp": n_hosts}, jax.devices()[:n_hosts])
            step2 = TrainStep(net2, gluon.loss.SoftmaxCrossEntropyLoss(),
                              "adam", {"learning_rate": 1e-3},
                              mesh=mesh, sharded_update=True, guard=True)
            loop2 = ResilientLoop(step2, CheckpointManager(
                os.path.join(d, "throwaway")), save_every=0,
                policy="skip", watch_preemption=False, verbose=False,
                metrics_port=False)
            for i in range(3):
                loop2.step(*batch_for(i))
            d2 = os.path.join(d, "sharded")
            pub2 = []
            state = loop2.state_dict(device=True)  # live arrays, no copy
            for host in range(n_hosts):     # one emulated host at a time
                m2 = CheckpointManager(d2, keep=2, sharded=True,
                                       process_index=host,
                                       process_count=n_hosts)
                # save() = this host's shard extraction (the
                # device->host copy, ~1/N of the state) + write + sha +
                # atomic publish — the full per-host critical path
                t0 = time.perf_counter()
                m2.save(loop2.t, state, block=True)
                pub2.append(time.perf_counter() - t0)
            per_host = [os.path.getsize(os.path.join(d2, f))
                        for f in sorted(os.listdir(d2))
                        if f.endswith(".npz")]
            t0 = time.perf_counter()
            step1, tree2 = CheckpointManager(
                d2, process_count=1).restore_latest()
            restore2_s = time.perf_counter() - t0
            loop2.load_state_dict(tree2)   # incl. reshard device_put
            sharded = {
                "hosts": n_hosts,
                "publish_ms_per_host": round(1e3 * float(np.mean(pub2)),
                                             3),
                "restore_ms": round(1e3 * restore2_s, 3),
                "bytes_per_host_max": int(max(per_host)),
                "bytes_total": int(sum(per_host)),
                # ~1.0 = the balance claim: max shard ≈ total/N
                "bytes_balance": round(
                    max(per_host) / (sum(per_host) / n_hosts), 3),
                "single_writer_bytes_on_host0": int(single_npz),
                "zero1_sharded_update": True,
            }

        # ISSUE 14 collective ledger: read AFTER the sharded leg so the
        # latest train.step executable is the ZeRO-1 one when devices
        # allowed it (else the single-device leg's honest 0)
        comms = _telemetry.site_comms("train.step")
        comms_bytes = comms_fraction = bytes_accessed = None
        if comms is not None:
            comms_bytes = int(comms["total_bytes"])
            if comms.get("bytes_accessed"):
                bytes_accessed = int(comms["bytes_accessed"])
            if comms.get("fraction") is not None:
                comms_fraction = round(comms["fraction"], 4)

        name = ("smoke_resilience_ckpt_publish_ms" if smoke
                else "resilience_ckpt_publish_ms")
        return {"metric": name,
                "value": round(1e3 * float(np.mean(publish_s)), 3),
                "unit": "ms",
                "capture_ms": round(1e3 * float(np.mean(capture_s)), 3),
                "restore_ms": round(1e3 * restore_s, 3),
                "state_bytes": int(state_bytes),
                "save_every": save_every,
                "steps_lost_per_preemption": steps_lost,
                "mttr_s": round(mttr_s, 4),
                "steps_lost_per_remediation": steps_lost,
                "bad_step_guard": True,
                "data_wait_fraction": data_wait_fraction,
                "step_p95_ms": step_p95_ms,
                "comms_bytes_per_step": comms_bytes,
                "comms_fraction_of_step": comms_fraction,
                "step_bytes_accessed": bytes_accessed,
                "sharded_ckpt": sharded,
                "vs_baseline": None,
                "baseline_note": "the reference has no in-tree recovery "
                                 "(SURVEY §5.3: manual restart from epoch "
                                 "checkpoints); this line tracks the "
                                 "fault-tolerance runtime's overhead "
                                 "from PR 3 on; sharded_ckpt is the "
                                 "ISSUE 6 per-host A/B vs the "
                                 "single-writer baseline at equal state "
                                 "size; comms_bytes_per_step is the "
                                 "latest train.step executable's "
                                 "collective ledger (the ZeRO-1 "
                                 "sharded leg when devices allow, else "
                                 "the single-device leg's 0); mttr_s is "
                                 "fault-inject -> first post-recovery "
                                 "step over the supervisor-driven "
                                 "restart path (ISSUE 15), with "
                                 "steps_lost_per_remediation the "
                                 "re-executed work that restart implies"}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_serving_chaos(smoke, dtype, device_kind):
    """Serving survival-layer bench (ISSUE 11): a small multi-replica
    fleet absorbs a replica-thread kill mid-storm. Reported: request
    availability through the fault (the headline — completed/total % of
    the FAULTED leg), the p95 ADDED latency of the failed-over pinned
    requests (their wall time minus the same requests' median wall time
    under an identical UNFAULTED storm leg on the same warm fleet —
    paired legs, so ordinary storm queueing cancels out and the delta
    isolates the failover path), and respawn-to-first-token (router
    swap of the
    rebuilt replica -> its first completed prefill), measured COLD
    (fresh XLA compiles) and WARM (ISSUE 16: the respawned replica
    loads its executables from a persistent AOT cache —
    `respawn_to_first_token_warm_ms`), plus the autoscale drill's
    breach-to-capacity span (`burn_to_scale_up_s`: scripted TTFT burn
    breach -> a warm replica added by the Autoscaler). Judged WARN-ONLY
    by the sentinel: fault-drill numbers are health signals, not perf
    measurements."""
    import threading as _threading
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import serving
    from mxnet_tpu.utils import chaos as _chaos
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params)

    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=64) if smoke else \
        TransformerConfig(vocab=1024, d_model=128, n_heads=4, n_layers=2,
                          d_ff=256, max_len=128)
    requests = 16 if smoke else 32
    max_new = 6 if smoke else 12
    pinned_n = 3                      # in-flight victims of the kill
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    if dtype == "bfloat16":
        params = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    rng = np.random.RandomState(0)
    work = [list(rng.randint(1, cfg.vocab, 5 + i % 6))
            for i in range(requests)]
    pinned = [list(rng.randint(1, cfg.vocab, 6))
              for _ in range(pinned_n)]
    srv = serving.serve((params, cfg), replicas=2, max_batch=4,
                        block_size=8, max_queue=requests + 8,
                        max_beat_age=5.0, respawn_backoff=0.02)
    try:
        # warm both replicas through their compile lattice first
        for rep in srv.replicas:
            for p in pinned:
                rep.submit(list(p), max_new_tokens=3 * max_new) \
                   .result(timeout=300)

        def run_storm(kill):
            """One full storm leg: pinned requests on replica 0 plus
            the client wave. The CLEAN leg (kill=False) measures the
            pinned requests' wall time under the SAME contention the
            fault leg sees — so `added latency` isolates the failover
            path, not ordinary storm queueing."""
            victim = srv.replicas[0]
            pin_reqs = [victim.submit(list(p),
                                      max_new_tokens=3 * max_new)
                        for p in pinned]
            t_pin = time.perf_counter()
            results = {}

            def client(i):
                try:
                    results[i] = srv.generate(work[i],
                                              max_new_tokens=max_new,
                                              timeout=300)
                except Exception as e:
                    results[i] = e

            threads = [_threading.Thread(target=client, args=(i,))
                       for i in range(requests)]
            for t in threads:
                t.start()
            if kill:
                # gate the kill on the pinned requests actually
                # DECODING (>=1 generated token), like the chaos drill:
                # killing while they are still queued would measure the
                # queued-re-home path under an in-flight label
                deadline = time.perf_counter() + 120
                while time.perf_counter() < deadline:
                    if sum(1 for s in list(victim.scheduler.running)
                           if len(s.tokens) > s.prompt_len) \
                            >= len(pin_reqs):
                        break
                    time.sleep(0.002)
                _chaos.configure(serve_kill=(0, 1))
            pin_s = []
            for r in pin_reqs:
                r.wait(timeout=300)
                pin_s.append(time.perf_counter() - t_pin)
            for t in threads:
                t.join(timeout=300)
            done = sum(1 for r in results.values()
                       if isinstance(r, list))
            done += sum(1 for r in pin_reqs if r.state == "done")
            return done, requests + len(pin_reqs), pin_s, victim

        # leg A: identical storm, no fault — the contention baseline
        _, _, clean_s, _ = run_storm(kill=False)
        clean_ref = float(np.median(clean_s))
        # leg B: same storm with the replica-thread kill
        done, total, failover_s, victim = run_storm(kill=True)
        availability = 100.0 * done / total
        # respawn-to-first-token: poll for the swap, then probe
        t_swap = None
        deadline = time.perf_counter() + 120
        while time.perf_counter() < deadline:
            srv.health()
            if srv.replicas[0] is not victim:
                t_swap = time.perf_counter()
                break
            time.sleep(0.005)
        respawn_ttft_ms = None
        if t_swap is not None:
            probe = srv.replicas[0].submit(list(pinned[0]),
                                           max_new_tokens=2)
            probe.result(timeout=300)
            respawn_ttft_ms = 1e3 * (probe.t_first_token - t_swap)
        added = [max(0.0, s - clean_ref) for s in failover_s]
        snap = srv.snapshot()["aggregate"]
        # leg C (ISSUE 16): the SAME kill against an AOT-cached fleet —
        # the respawned replica warm-loads its executables from disk
        # instead of re-compiling, which is exactly the gap between
        # respawn_to_first_token_ms and its _warm_ twin. Then the
        # autoscale mini-drill: script a hot TTFT burn into the
        # Autoscaler and measure breach -> warm replica ready.
        import shutil as _shutil
        import tempfile as _tempfile
        from mxnet_tpu import aot as _aot
        from mxnet_tpu.serving import Autoscaler, AutoscaleConfig
        _chaos.reset()
        # the cold fleet must be DOWN before re-arming serve_kill: the
        # chaos fault keys on replica id only, and a still-beating
        # replica 0 of the old fleet would consume the kill meant for
        # the warm fleet's victim
        srv.close()
        warm_ttft_ms = None
        burn_to_scale_up_s = None
        scale_ups = 0
        cache_dir = _tempfile.mkdtemp(prefix="mxtpu-aot-bench-")
        srv2 = serving.serve((params, cfg), replicas=2, max_batch=4,
                             block_size=8, max_queue=requests + 8,
                             max_beat_age=5.0, respawn_backoff=0.02,
                             aot_cache=cache_dir)
        try:
            # drive the compile lattice once: every executable both
            # replicas build is PUBLISHED to the cache as a side effect
            for rep in srv2.replicas:
                for p in pinned:
                    rep.submit(list(p), max_new_tokens=3 * max_new) \
                       .result(timeout=300)
            victim2 = srv2.replicas[0]
            pin2 = [victim2.submit(list(p), max_new_tokens=3 * max_new)
                    for p in pinned]
            deadline = time.perf_counter() + 120
            while time.perf_counter() < deadline:
                if sum(1 for s in list(victim2.scheduler.running)
                       if len(s.tokens) > s.prompt_len) >= len(pin2):
                    break
                time.sleep(0.002)
            _chaos.configure(serve_kill=(0, 1))
            for r in pin2:
                r.wait(timeout=300)
            t_swap2 = None
            deadline = time.perf_counter() + 120
            while time.perf_counter() < deadline:
                srv2.health()
                if srv2.replicas[0] is not victim2:
                    t_swap2 = time.perf_counter()
                    break
                time.sleep(0.005)
            if t_swap2 is not None:
                probe = srv2.replicas[0].submit(list(pinned[0]),
                                                max_new_tokens=2)
                probe.result(timeout=300)
                warm_ttft_ms = 1e3 * (probe.t_first_token - t_swap2)
            _chaos.reset()
            # autoscale mini-drill: a scripted burn breach (both short
            # windows hot) must produce a WARM third replica; the span
            # is breach-observed -> scale_up() returned a serving
            # replica, dominated by the warm-start load, not XLA
            sc = Autoscaler(srv2, AutoscaleConfig(
                min_replicas=1, max_replicas=3, cooldown_s=0.1,
                idle_retire_s=3600.0))
            hot_burn = {60: {"rate": 10.0, "good": 0, "total": 8,
                             "span_s": 60.0},
                        300: {"rate": 10.0, "good": 0, "total": 8,
                              "span_s": 300.0}}
            sc.burn_rates = lambda: hot_burn
            sc.fleet_load_tokens = lambda: 1
            t_breach = time.perf_counter()
            if sc.step() == "up":
                burn_to_scale_up_s = time.perf_counter() - t_breach
            scale_ups = sc.scale_ups
        finally:
            try:
                srv2.close()
            finally:
                _aot.configure()      # back to env control
                _shutil.rmtree(cache_dir, ignore_errors=True)
        return {
            "metric": ("smoke_serving_chaos_availability_pct" if smoke
                       else "serving_chaos_availability_pct"),
            "value": round(availability, 2), "unit": "%",
            "requests": total, "replicas": 2,
            "failover_added_latency_p95_ms": round(
                1e3 * float(np.percentile(added, 95)), 2),
            "respawn_to_first_token_ms": (round(respawn_ttft_ms, 1)
                                          if respawn_ttft_ms is not None
                                          else None),
            "respawn_to_first_token_warm_ms": (
                round(warm_ttft_ms, 1)
                if warm_ttft_ms is not None
                and respawn_ttft_ms is not None else None),
            "burn_to_scale_up_s": (round(burn_to_scale_up_s, 3)
                                   if burn_to_scale_up_s is not None
                                   and scale_ups else None),
            "scale_ups": scale_ups,
            "failovers": snap["failovers"],
            "respawns": snap["respawns"],
            "orphaned": snap["orphaned"],
            "vs_baseline": None,
            "baseline_note": "ISSUE 11 fault-storm leg: no serving "
                             "(or fault-injection) path exists in the "
                             "reference tree; sentinel judges "
                             "serving_chaos_* warn-only",
        }
    finally:
        _chaos.reset()
        srv.close()


def bench_serving_disagg(smoke, dtype, device_kind):
    """Disaggregated prefill/decode serving bench (ISSUE 17): a paired
    A/B on one tiny transformer — leg A a co-scheduled 2-replica
    fleet, leg B the SAME engine count split `prefill:1,decode:1`,
    both absorbing an identical storm: a steady wave of short-prompt
    decode clients (tenant `clients`, long generations) overlapped by
    a burst of long-prompt, short-generation requests (tenant `storm`,
    repeated prompts so migration hops hit resident prefix blocks on
    the decode target). Headline: the decode clients' p95 inter-token
    latency on the roles leg, which must sit BELOW the co-scheduled
    leg's under the same storm — the storm's prefill iterations land
    exclusively on the prefill specialist. Per-tenant ITL/TTFT
    histograms are merged across replicas by summing bucket counts
    (never averaging quantiles); the roles leg also reports migration
    count, carried tokens, and KV bytes saved by target cache hits
    (warm-up traffic subtracted). Judged WARN-ONLY by the sentinel:
    wall-clock A/B under thread contention."""
    import threading as _threading
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import serving
    from mxnet_tpu.telemetry import metrics as _tm
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params)

    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=64) if smoke else \
        TransformerConfig(vocab=1024, d_model=128, n_heads=4, n_layers=2,
                          d_ff=256, max_len=128)
    clients = 6 if smoke else 8
    client_new = 24 if smoke else 32
    storm_n = 6 if smoke else 10
    storm_len = 48 if smoke else 96
    storm_new = 2
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    if dtype == "bfloat16":
        params = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    rng = np.random.RandomState(17)
    client_prompts = [list(rng.randint(1, cfg.vocab, 5 + i % 4))
                     for i in range(clients)]
    # two DISTINCT long prompts, repeated across the wave: from each
    # prompt's second hop on, the decode target already holds the
    # prefix blocks by content hash — the migration carries hashes
    # instead of KV and the bytes-saved ledger moves
    storm_bases = [list(rng.randint(1, cfg.vocab, storm_len))
                   for _ in range(2)]
    storm_prompts = [list(storm_bases[i % 2]) for i in range(storm_n)]

    def merged_hist(fleet, tenant, which):
        """One fleet-wide histogram for `tenant`'s `which` ('itl' /
        'ttft'): bucket counts SUMMED across replicas — a migrated
        request's observations land on the target, so no single
        replica's histogram is the client's truth."""
        reg = _tm.MetricsRegistry()
        out = None
        for rep in list(fleet.replicas):
            h = (rep.metrics._tenants_view().get(tenant) or {}) \
                .get(which)
            if h is None:
                continue
            if out is None:
                out = reg.histogram("bench_merge_%s" % which,
                                    buckets=h.buckets)
            for i, c in enumerate(h._counts):
                out._counts[i] += c
            out.sum += h.sum
            out.count += h.count
        return out

    def run_leg(roles):
        """One full storm leg on a fresh fleet; returns the decode
        clients' merged latency quantiles plus (roles leg only) the
        migration ledger deltas."""
        srv = serving.serve((params, cfg),
                            replicas=None if roles else 2,
                            roles=roles, max_batch=clients + 2,
                            block_size=8, paged=True, prefix_cache=True,
                            prefill_chunk=8,
                            max_queue=clients + storm_n + 8)
        try:
            # warm every replica through its compile lattice with the
            # leg's own shapes (default tenant — the measured tenants'
            # histograms start clean); on the roles leg this also
            # leaves the storm prefixes resident on the decode target
            for rep in srv.replicas:
                rep.submit(list(storm_bases[0]),
                           max_new_tokens=storm_new).result(timeout=600)
                rep.submit(list(client_prompts[0]),
                           max_new_tokens=client_new) \
                   .result(timeout=600)
            base = (0, 0, 0)
            if roles:
                fz = srv.statusz()["fleet"]
                base = (fz.get("migrations", 0),
                        fz.get("migration_tokens", 0),
                        fz.get("migration_bytes_saved", 0))
            results = {}

            def client(i):
                try:
                    results[i] = srv.submit(
                        list(client_prompts[i]),
                        max_new_tokens=client_new,
                        tenant="clients").result(timeout=600)
                except Exception as e:          # ledger'd; leg reports
                    results[i] = e

            def storm(i):
                try:
                    srv.submit(list(storm_prompts[i]),
                               max_new_tokens=storm_new,
                               tenant="storm").result(timeout=600)
                except Exception:
                    pass

            cthreads = [_threading.Thread(target=client, args=(i,))
                        for i in range(clients)]
            for t in cthreads:
                t.start()
            # fire the storm only once every client holds a first
            # token: the clients are mid-decode (and, on the roles
            # leg, already migrated — the hop gap stays out of the
            # storm window) when the long prompts slam the fleet
            deadline = time.perf_counter() + 300
            while time.perf_counter() < deadline:
                h = merged_hist(srv, "clients", "ttft")
                if h is not None and h.count >= clients:
                    break
                time.sleep(0.002)
            sthreads = [_threading.Thread(target=storm, args=(i,))
                        for i in range(storm_n)]
            for t in sthreads:
                t.start()
            for t in cthreads + sthreads:
                t.join(timeout=600)
            ok = sum(1 for r in results.values() if isinstance(r, list))
            itl = merged_hist(srv, "clients", "itl")
            ttft = merged_hist(srv, "clients", "ttft")
            leg = {
                "ok": ok,
                "itl_p50_ms": round(1e3 * itl.quantile(0.5), 3),
                "itl_p95_ms": round(1e3 * itl.quantile(0.95), 3),
                "ttft_p95_ms": round(1e3 * ttft.quantile(0.95), 3),
            }
            if roles:
                fz = srv.statusz()["fleet"]
                leg["migrations"] = fz.get("migrations", 0) - base[0]
                leg["carried"] = (fz.get("migration_tokens", 0)
                                  - base[1])
                leg["saved"] = (fz.get("migration_bytes_saved", 0)
                                - base[2])
                leg["failovers"] = srv.snapshot()["aggregate"][
                    "failovers"]
            return leg
        finally:
            srv.close()

    co = run_leg(None)                        # leg A: co-scheduled
    ro = run_leg("prefill:1,decode:1")        # leg B: disaggregated
    line = {
        "metric": ("smoke_serving_disagg_decode_itl_p95_ms" if smoke
                   else "serving_disagg_decode_itl_p95_ms"),
        "value": ro["itl_p95_ms"], "unit": "ms",
        "coscheduled_decode_itl_p95_ms": co["itl_p95_ms"],
        "decode_itl_p50_ms": ro["itl_p50_ms"],
        "coscheduled_decode_itl_p50_ms": co["itl_p50_ms"],
        "itl_p95_flattening_x": (round(co["itl_p95_ms"]
                                       / ro["itl_p95_ms"], 2)
                                 if ro["itl_p95_ms"] else None),
        "ttft_p95_ms": ro["ttft_p95_ms"],
        "coscheduled_ttft_p95_ms": co["ttft_p95_ms"],
        "migrations": ro["migrations"],
        "migration_carried_tokens": ro["carried"],
        "migration_kv_bytes_saved": ro["saved"],
        "migration_failovers_spent": ro["failovers"],
        "clients_completed": "%d+%d/%d" % (co["ok"], ro["ok"],
                                           2 * clients),
        "clients": clients, "storm_requests": storm_n,
        "replicas": 2, "roles": "prefill:1,decode:1",
        "vs_baseline": None,
        "baseline_note": "ISSUE 17 A/B: the co-scheduled leg IS the "
                         "baseline (same engine count, identical "
                         "storm); no disaggregated-serving path "
                         "exists in the reference tree — sentinel "
                         "judges serving_disagg_* warn-only",
    }
    if "cpu" in str(device_kind).lower():
        line["interpreter_note"] = (
            "CPU leg: Pallas paged kernels run in interpret mode; "
            "absolute latencies are inflated and the prefill/decode "
            "cost asymmetry flattens — judge the roles-vs-coscheduled "
            "ORDERING, not the magnitudes")
    return line


def bench_serving_rollout(smoke, dtype, device_kind):
    """Zero-downtime live weight rollout bench (ISSUE 18): one
    2-replica fleet, three measured legs on a tiny transformer.
    Leg 1 (detection): a freshly published candidate checkpoint is
    bit-flipped after its manifest lands; the watcher must quarantine
    it at the verification gate — the headline is publish→rejected
    latency. Leg 2 (steady): a client wave with NO rollout in flight
    pins the fleet's baseline TTFT p95. Leg 3 (shift): an identical
    wave streams while a GOOD candidate canaries through the ladder
    and promotes fleet-wide — measured: full rollout duration
    (publish→promoted, the headline `value`), requests lost (MUST be
    0 — check_line rejects the line otherwise), and the TTFT p95
    delta vs the steady wave (the cost of shifting traffic through a
    drain-to-completion promotion). Judged WARN-ONLY by the sentinel:
    wall-clock under thread contention; the zero-loss gate is the
    committed verdict."""
    import tempfile as _tempfile
    import threading as _threading
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import serving
    from mxnet_tpu.telemetry import metrics as _tm
    from mxnet_tpu.utils.recovery import CheckpointManager
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params)

    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=64) if smoke else \
        TransformerConfig(vocab=1024, d_model=128, n_heads=4, n_layers=2,
                          d_ff=256, max_len=128)
    clients = 4 if smoke else 8
    per_client = 3 if smoke else 6
    max_new = 8 if smoke else 16
    window_s = 0.02 if smoke else 0.25
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    if dtype == "bfloat16":
        params = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    rng = np.random.RandomState(23)
    prompts = [list(rng.randint(1, cfg.vocab, 5 + i % 4))
               for i in range(clients)]
    new_params = {k: np.asarray(v) + np.float32(0.05)
                  for k, v in params.items()}
    ckpt_dir = _tempfile.mkdtemp(prefix="bench_rollout_")

    # promotion REPLACES replica objects (drain-to-completion swap),
    # so per-tenant histograms recorded on a retired incumbent vanish
    # from `fleet.replicas` — accumulate every metrics object ever
    # seen and merge over the full set
    seen_metrics = []

    def collect(fleet):
        for rep in list(fleet.replicas):
            m = getattr(rep, "metrics", None)
            if m is not None \
                    and not any(m is s for s in seen_metrics):
                seen_metrics.append(m)

    def merged_ttft(tenant):
        reg = _tm.MetricsRegistry()
        out = None
        for m in seen_metrics:
            h = (m._tenants_view().get(tenant) or {}).get("ttft")
            if h is None:
                continue
            if out is None:
                out = reg.histogram("bench_merge_ttft",
                                    buckets=h.buckets)
            for i, c in enumerate(h._counts):
                out._counts[i] += c
            out.sum += h.sum
            out.count += h.count
        if out is None or not out.count:
            raise RuntimeError("no %r-tenant TTFT recorded" % tenant)
        return out

    srv = serving.serve((params, cfg), replicas=2,
                        max_batch=clients + 2, block_size=8,
                        max_queue=clients * per_client + 8)
    try:
        ro = srv.attach_rollout(ckpt_dir, stages=(0.25, 0.5),
                                window_s=window_s)
        # warm both replicas through the wave's shapes
        for rep in srv.replicas:
            rep.submit(list(prompts[0]),
                       max_new_tokens=max_new).result(timeout=600)

        # -- leg 1: corrupted candidate -> publish->rejected latency --
        CheckpointManager(ckpt_dir, async_save=False).save(1, new_params)
        path = os.path.join(ckpt_dir, "ckpt-1.npz")
        with open(path, "r+b") as f:
            f.seek(os.path.getsize(path) // 2)
            b = f.read(1)
            f.seek(os.path.getsize(path) // 2)
            f.write(bytes([b[0] ^ 0xFF]))
        t0 = time.perf_counter()
        while ro.step() != "rejected":
            if time.perf_counter() - t0 > 300:
                raise RuntimeError("corrupt candidate never rejected")
        detect_ms = 1e3 * (time.perf_counter() - t0)

        def wave(tenant):
            results = {}

            def client(i):
                for k in range(per_client):
                    key = i * per_client + k
                    try:
                        results[key] = srv.submit(
                            list(prompts[i]), max_new_tokens=max_new,
                            tenant=tenant).result(timeout=600)
                    except Exception as e:
                        results[key] = e
                    time.sleep(0.005)

            threads = [_threading.Thread(target=client, args=(i,))
                       for i in range(clients)]
            for t in threads:
                t.start()
            return threads, results

        # -- leg 2: steady wave, no rollout in flight -----------------
        threads, steady = wave("steady")
        for t in threads:
            t.join(timeout=600)
        collect(srv)
        steady_p95 = 1e3 * merged_ttft("steady").quantile(0.95)

        # -- leg 3: identical wave WHILE a good candidate promotes ----
        threads, shift = wave("shift")
        CheckpointManager(ckpt_dir, async_save=False).save(2, new_params)
        t0 = time.perf_counter()
        transitions = []
        while time.perf_counter() - t0 < 600:
            collect(srv)            # snapshot before a swap retires one
            v = ro.step()
            if v:
                transitions.append(v)
            if v == "promoted":
                break
            time.sleep(0.002)
        duration_s = time.perf_counter() - t0
        for t in threads:
            t.join(timeout=600)
        if transitions[-1:] != ["promoted"]:
            raise RuntimeError("rollout never promoted: %r"
                               % transitions)
        collect(srv)
        shift_p95 = 1e3 * merged_ttft("shift").quantile(0.95)
        lost = sum(1 for r in list(steady.values()) + list(shift.values())
                   if not isinstance(r, list))
        line = {
            "metric": ("smoke_serving_rollout_duration_s" if smoke
                       else "serving_rollout_duration_s"),
            "value": round(duration_s, 3), "unit": "s",
            "rollout_requests_lost": lost,
            "corrupt_detect_ms": round(detect_ms, 1),
            "corrupt_steps_rejected": 1,
            "ttft_p95_steady_ms": round(steady_p95, 3),
            "ttft_p95_shift_ms": round(shift_p95, 3),
            "ttft_p95_shift_delta_ms": round(shift_p95 - steady_p95, 3),
            "promoted_version": srv.weights_version,
            "stages": "1/4,1/2", "window_s": window_s,
            "replicas": 2,
            "requests": len(steady) + len(shift),
            "transitions": ",".join(transitions),
            "vs_baseline": None,
            "baseline_note": "ISSUE 18: no live-rollout path exists in "
                             "the reference tree; the in-run steady "
                             "wave IS the TTFT baseline and the "
                             "committed verdict is zero requests lost "
                             "— sentinel judges serving_rollout_* "
                             "warn-only",
        }
        if "cpu" in str(device_kind).lower():
            line["interpreter_note"] = (
                "CPU leg: engine rebuilds pay interpreted compiles and "
                "thread contention inflates the shift delta — judge "
                "the zero-loss gate and detection ORDERING, not the "
                "magnitudes")
        return line
    finally:
        srv.close()


def bench_serving_spec(smoke, dtype, device_kind):
    """Speculative decoding A/B (ISSUE 19): the SAME client wave on two
    single-replica paged engines — spec OFF (the baseline leg; the
    non-speculative path is the verbatim oracle) vs a FULL-CLONE
    self-draft (`draft_layers == n_layers`) at k=3. The clone pins
    acceptance at its 1.0 upper bound BY CONSTRUCTION (disclosed in
    `draft_note`): the run measures the ceiling of the verification
    plumbing (k+1-wide scoring, burst emission, block accounting),
    not a trained draft's quality. Headline: spec-leg decode tok/s
    over the measured window with `vs_baseline` = spec/off; the line
    carries accepted-per-pass (the bench refuses to emit unless it
    exceeds 1.0), acceptance rate, windowed goodput for both legs
    under a disclosed TTFT SLO, and both legs' ITL quantiles. Judged
    WARN-ONLY by the sentinel: wall-clock A/B under thread
    contention, and CPU interpret mode inverts the draft economics."""
    import threading as _threading
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import serving
    from mxnet_tpu.serving.spec import self_draft
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params)

    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=96) if smoke else \
        TransformerConfig(vocab=1024, d_model=128, n_heads=4, n_layers=2,
                          d_ff=256, max_len=160)
    clients = 4 if smoke else 8
    client_new = 24 if smoke else 48
    spec_k = int(os.environ.get("BENCH_SPEC_K", "3"))
    draft_layers = cfg.n_layers  # FULL CLONE: acceptance == 1.0 ceiling
    slo_ms = float(os.environ.get("BENCH_SPEC_SLO_TTFT_MS",
                                  "5000" if smoke else "500"))
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    if dtype == "bfloat16":
        params = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    rng = np.random.RandomState(19)
    prompts = [list(rng.randint(1, cfg.vocab, 6 + i % 5))
               for i in range(clients)]

    def run_leg(draft):
        """One measured wave on a fresh engine; the warm-up request
        pays every compile (prefill lattice + the spec leg's draft /
        spec_score sites) OUTSIDE the measured window."""
        srv = serving.LMServer((params, cfg), max_batch=clients + 2,
                               block_size=8, paged=True,
                               draft=draft, spec_k=spec_k)
        try:
            if draft is not None and not srv.engine.spec:
                raise RuntimeError("spec leg fell back: %r"
                                   % srv.engine.spec_fallback)
            srv.generate(list(prompts[0]), max_new_tokens=client_new,
                         timeout=600)
            led0 = srv.metrics.tokens_ledger()["goodput"]
            results = {}

            def client(i):
                try:
                    results[i] = srv.submit(
                        list(prompts[i]), max_new_tokens=client_new,
                        tenant="clients").result(timeout=600)
                except Exception as e:      # ledger'd; leg reports ok<n
                    results[i] = e

            t0 = time.perf_counter()
            threads = [_threading.Thread(target=client, args=(i,))
                       for i in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            wall = time.perf_counter() - t0
            gen = sum(len(r) for r in results.values()
                      if isinstance(r, list))
            tv = srv.metrics._tenants_view().get("clients") or {}
            itl, ttft = tv.get("itl"), tv.get("ttft")
            slo = [o for o in srv.metrics.slo.payload()
                   if o.get("objective") == "ttft"
                   and o.get("tenant") is None]
            leg = {
                "ok": sum(1 for r in results.values()
                          if isinstance(r, list)),
                "tok_per_sec": (gen / wall) if wall > 0 else None,
                # windowed goodput: the SLO-met subset of the tokens
                # the window just delivered, over the same wall span
                "goodput_tok_per_sec": (round(
                    (srv.metrics.tokens_ledger()["goodput"] - led0)
                    / wall, 3) if wall > 0 else None),
                "attainment": (slo[0]["attainment"] if slo else None),
                "itl_p50_ms": (round(1e3 * itl.quantile(0.5), 3)
                               if itl is not None and itl.count
                               else None),
                "itl_p95_ms": (round(1e3 * itl.quantile(0.95), 3)
                               if itl is not None and itl.count
                               else None),
                "ttft_p95_ms": (round(1e3 * ttft.quantile(0.95), 3)
                                if ttft is not None and ttft.count
                                else None),
            }
            if draft is not None:
                snap = srv.snapshot()
                sp = snap["spec"]
                leg.update(passes=sp["passes"],
                           accepted_per_pass=sp["accepted_per_pass"],
                           acceptance_rate=sp["acceptance_rate"],
                           fallbacks=sp["fallbacks"],
                           decode_compilations=snap["engine"][
                               "decode_compilations"])
            return leg
        finally:
            srv.close()

    # the SLO threshold is read when the server's metrics are built —
    # arm it for both legs, restore the ambient value after
    prev_slo = os.environ.get("MXNET_SLO_TTFT_MS")
    os.environ["MXNET_SLO_TTFT_MS"] = "%g" % slo_ms
    try:
        base = run_leg(None)                              # leg A: off
        spec = run_leg(self_draft(params, cfg, draft_layers))  # leg B
    finally:
        if prev_slo is None:
            os.environ.pop("MXNET_SLO_TTFT_MS", None)
        else:
            os.environ["MXNET_SLO_TTFT_MS"] = prev_slo
    app = spec.get("accepted_per_pass")
    if app is None or app <= 1.0:
        # the one hard gate: a pass that doesn't beat one-token-per-
        # iteration means speculation never engaged — refuse the line
        raise RuntimeError("speculation did not pay per pass: "
                           "accepted_per_pass=%r (passes=%r)"
                           % (app, spec.get("passes")))
    line = {
        "metric": ("smoke_serving_spec_decode_tok_per_sec" if smoke
                   else "serving_spec_decode_tok_per_sec"),
        "value": round(spec["tok_per_sec"], 3), "unit": "tok/s",
        "vs_baseline": (round(spec["tok_per_sec"]
                              / base["tok_per_sec"], 3)
                        if base["tok_per_sec"] else None),
        "baseline_tok_per_sec": (round(base["tok_per_sec"], 3)
                                 if base["tok_per_sec"] else None),
        "spec_accepted_per_pass": round(app, 3),
        "spec_acceptance_rate": (round(spec["acceptance_rate"], 4)
                                 if spec["acceptance_rate"] is not None
                                 else None),
        "spec_passes": spec["passes"],
        "spec_fallback_passes": spec["fallbacks"],
        "spec_k": spec_k, "spec_draft_layers": draft_layers,
        "draft_note": "FULL-CLONE self-draft (draft_layers == "
                      "n_layers): acceptance is pinned at its 1.0 "
                      "upper bound by construction — the per-pass "
                      "multiplier measures the verification "
                      "plumbing's ceiling, not a trained draft",
        "itl_p50_ms": spec["itl_p50_ms"],
        "itl_p95_ms": spec["itl_p95_ms"],
        "baseline_itl_p50_ms": base["itl_p50_ms"],
        "baseline_itl_p95_ms": base["itl_p95_ms"],
        "ttft_p95_ms": spec["ttft_p95_ms"],
        "decode_compilations": spec["decode_compilations"],
        "clients": clients, "tokens_per_client": client_new,
        "clients_completed": "%d+%d/%d" % (base["ok"], spec["ok"],
                                           2 * clients),
    }
    if spec["attainment"] is not None and \
            spec["goodput_tok_per_sec"] is not None:
        line.update(goodput_tok_per_sec=spec["goodput_tok_per_sec"],
                    baseline_goodput_tok_per_sec=base[
                        "goodput_tok_per_sec"],
                    slo_ttft_attainment=spec["attainment"],
                    slo_ttft_ms=slo_ms)
    if "cpu" in str(device_kind).lower():
        line["interpreter_note"] = (
            "CPU leg: the cache-free draft pays a full interpreted "
            "causal forward per proposed token, so wall-clock "
            "vs_baseline inverts (< 1) — judge the acceptance ledger "
            "and the per-pass multiplier; the tok/s ratio means "
            "something on real TPUs where the draft is a fraction of "
            "target cost")
    return line


def bench_serving_quant(smoke, dtype, device_kind):
    """Quantized serving A/B (ISSUE 20): the SAME client wave on two
    single-replica paged engines — f32 (the oracle leg, kept verbatim)
    vs int8 KV pool + int8 per-channel weights. Headline: RESIDENT
    SEQUENCES PER CHIP at the f32 leg's measured pool HBM — pool bytes
    divided by (kv_bytes_per_token x max_len), the capacity multiplier
    the int8 layout buys (~3.9x: int8 payload + amortized f32 scale
    sidecars). The line carries both legs' measured decode tok/s and
    the PRECISION CONTRACT: a greedy parity probe replays one prompt on
    both engines with per-token logits kept, and the bench REFUSES to
    emit unless quant-leg tokens match the oracle exactly and max
    |logit - f32| sits inside the disclosed budget (the same budgets
    tests/test_serving_quant.py pins); perplexity of the oracle's own
    continuation under both engines rides along as ppl_f32 / ppl_quant
    / ppl_delta_frac. Judged WARN-ONLY by the sentinel: wall-clock A/B
    under thread contention, and CPU interpret mode stages int8 blocks
    through f32 copies so the quant leg's wall-clock saving does not
    materialize off-TPU — capacity and the precision ledger are the
    decision signals there."""
    import threading as _threading
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import serving
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params)

    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=96) if smoke else \
        TransformerConfig(vocab=1024, d_model=128, n_heads=4, n_layers=2,
                          d_ff=256, max_len=160)
    clients = 4 if smoke else 8
    client_new = 24 if smoke else 48
    block_size = 32                 # % 32 == 0: int8-eligible on real HW
    logit_budget = float(os.environ.get("BENCH_QUANT_LOGIT_BUDGET",
                                        "0.05"))
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    if dtype == "bfloat16":
        params = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
    rng = np.random.RandomState(20)
    prompts = [list(rng.randint(1, cfg.vocab, 6 + i % 5))
               for i in range(clients)]

    # --- precision probe: greedy rollout, per-token logits kept -------
    def probe(**kw):
        eng = serving.Engine(serving.TransformerLM(dict(params), cfg),
                             max_batch=2, block_size=block_size,
                             paged=True, keep_logits=True, **kw)
        try:
            if kw.get("kv_quant") and not eng.kv_quant:
                raise RuntimeError("kv quant leg fell back: %r"
                                   % eng.kv_quant_fallback)
            if kw.get("weight_quant") and not eng.weight_quant:
                raise RuntimeError("weight quant leg fell back: %r"
                                   % eng.weight_quant_fallback)
            seq = eng.start(list(prompts[0]), client_new)
            while not seq.done:
                eng.decode_step([seq])
            toks = list(seq.tokens)
            logits = [np.asarray(x, np.float32)
                      for x in seq.token_logits]
            eng.release(seq)
            return toks, logits
        finally:
            eng.close()

    t_f32, l_f32 = probe()
    t_q, l_q = probe(kv_quant=True, weight_quant="int8")
    if t_q != t_f32:
        # the one hard token gate: the precision contract is "same
        # greedy tokens on the pinned config" — refuse the line
        raise RuntimeError("quant leg diverged from the f32 oracle: "
                           "%r vs %r" % (t_q[:8], t_f32[:8]))
    logit_err = max(float(np.max(np.abs(a - b)))
                    for a, b in zip(l_f32, l_q))
    if logit_err > logit_budget:
        raise RuntimeError("quant logit error %.4g exceeds the pinned "
                           "budget %.4g" % (logit_err, logit_budget))

    def ppl(logits):
        nll = 0.0
        for row, t in zip(logits, t_f32):
            z = row - np.max(row)
            nll -= float(z[t] - np.log(np.sum(np.exp(z))))
        return math.exp(nll / len(t_f32))

    ppl_f32, ppl_q = ppl(l_f32), ppl(l_q)

    # --- throughput wave: same clients on both legs -------------------
    def run_leg(**kw):
        srv = serving.LMServer((params, cfg), max_batch=clients + 2,
                               block_size=block_size, paged=True, **kw)
        try:
            eng = srv.engine
            if kw.get("kv_quant") and not eng.kv_quant:
                raise RuntimeError("kv quant leg fell back: %r"
                                   % eng.kv_quant_fallback)
            srv.generate(list(prompts[0]), max_new_tokens=client_new,
                         timeout=600)                         # warm-up
            results = {}

            def client(i):
                try:
                    results[i] = srv.submit(
                        list(prompts[i]),
                        max_new_tokens=client_new).result(timeout=600)
                except Exception as e:
                    results[i] = e

            t0 = time.perf_counter()
            threads = [_threading.Thread(target=client, args=(i,))
                       for i in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            wall = time.perf_counter() - t0
            gen = sum(len(r) for r in results.values()
                      if isinstance(r, list))
            pool_bytes = (eng.cache.k.nbytes + eng.cache.v.nbytes)
            if eng.cache.k_scale is not None:
                pool_bytes += (eng.cache.k_scale.nbytes
                               + eng.cache.v_scale.nbytes)
            return {
                "ok": sum(1 for r in results.values()
                          if isinstance(r, list)),
                "tok_per_sec": (gen / wall) if wall > 0 else None,
                "bytes_per_token": eng.kv_bytes_per_token(),
                "pool_bytes": pool_bytes,
            }
        finally:
            srv.close()

    base = run_leg()
    quant = run_leg(kv_quant=True, weight_quant="int8")
    # resident sequences at the F32 LEG'S measured pool HBM: the
    # capacity each layout buys from the same bytes
    budget = base["pool_bytes"]
    res_f32 = budget // (base["bytes_per_token"] * cfg.max_len)
    res_q = budget // (quant["bytes_per_token"] * cfg.max_len)
    line = {
        "metric": ("smoke_serving_quant_resident_seqs_per_chip" if smoke
                   else "serving_quant_resident_seqs_per_chip"),
        "value": int(res_q), "unit": "sequences",
        "vs_baseline": (round(res_q / res_f32, 3) if res_f32 else None),
        "baseline_resident_seqs": int(res_f32),
        "pool_hbm_bytes": int(budget),
        "kv_bytes_per_token_f32": base["bytes_per_token"],
        "kv_bytes_per_token_int8": quant["bytes_per_token"],
        "kv_quant": "int8", "weight_quant": "int8",
        "block_size": block_size, "max_len": cfg.max_len,
        "decode_tok_per_sec": (round(quant["tok_per_sec"], 3)
                               if quant["tok_per_sec"] else None),
        "baseline_decode_tok_per_sec": (round(base["tok_per_sec"], 3)
                                        if base["tok_per_sec"]
                                        else None),
        "quant_max_logit_error": round(logit_err, 6),
        "quant_logit_budget": logit_budget,
        "ppl_f32": round(ppl_f32, 4), "ppl_quant": round(ppl_q, 4),
        "ppl_delta_frac": round(abs(ppl_q - ppl_f32) / ppl_f32, 5),
        "clients": clients, "tokens_per_client": client_new,
        "clients_completed": "%d+%d/%d" % (base["ok"], quant["ok"],
                                           2 * clients),
    }
    if "cpu" in str(device_kind).lower():
        line["interpreter_note"] = (
            "CPU leg: the Pallas interpreter stages int8 blocks "
            "through f32 copies, so the quant leg's HBM saving does "
            "not show up as wall-clock off-TPU — judge the capacity "
            "ratio, the precision ledger, and the declared kernel "
            "bytes (BENCH_BYTES_SERVING_CPU.txt quant leg); tok/s "
            "ratios mean something on real TPUs")
    return line


_CONFIGS = [
    ("resnet50_infer", bench_resnet50_infer),
    ("resnet50_int8_infer", bench_resnet50_int8_infer),
    ("lstm_lm", bench_lstm_lm),
    ("lstm_sweep", bench_lstm_sweep),
    ("transformer_flash", bench_transformer_flash),
    ("ssd_forward", bench_ssd_forward),
    ("sparse_linear", bench_sparse_linear),
    ("serving", bench_serving),
    ("serving_prefix", bench_serving_prefix),
    ("serving_chaos", bench_serving_chaos),
    ("serving_disagg", bench_serving_disagg),
    ("serving_rollout", bench_serving_rollout),
    ("serving_spec", bench_serving_spec),
    ("serving_quant", bench_serving_quant),
    ("resilience", bench_resilience),
    ("io_pipeline", bench_io_pipeline),
    ("e2e_train_io", bench_e2e_train_io),
    ("resnet50", bench_resnet50),   # headline LAST: the driver parses the
]                                   # final stdout JSON line


def _telemetry_config_snapshot():
    """Compact view of the process-global telemetry registry for ONE
    config: histograms as count/mean/p50/p95/p99 (the step-time/TTFT
    distributions the means on the line can't carry), counters/gauges
    as values. Resets the registry afterwards so configs don't bleed
    into each other's lines. Returns None when nothing was recorded."""
    from mxnet_tpu import telemetry
    snap = telemetry.snapshot()
    out = {}
    for name, m in snap["metrics"].items():
        if m["kind"] == "histogram":
            if m["count"]:
                out[name] = {k: m[k] for k in
                             ("count", "mean", "p50", "p95", "p99")}
        elif m["value"]:
            out[name] = m["value"]
    telemetry.default_registry().reset()
    return out or None


def _run_configs(smoke):
    dtype = os.environ.get("BENCH_DTYPE",
                           "float32" if smoke else "bfloat16")
    want = os.environ.get("BENCH_CONFIGS", "all")
    if want == "headline":
        names = ["resnet50"]
    elif want == "all":
        names = [n for n, _ in _CONFIGS]
    else:
        names = [n.strip() for n in want.split(",")]
        names.sort(key=lambda n: n == "resnet50")  # headline stays last

    import jax
    dev = jax.devices()[0]
    device_kind = dev.device_kind

    flash_seqs = [int(s) for s in
                  os.environ.get("BENCH_FLASH_SEQ", "").split(",") if s]

    results = []
    table = dict(_CONFIGS)
    for name in names:
        runs = [{}]
        if name == "transformer_flash" and flash_seqs and not smoke:
            runs = [{"seq_len": s} for s in flash_seqs]
        if name == "serving" and not smoke and \
                os.environ.get("BENCH_SERVING_BATCH") is None:
            # the serving trajectory is tracked at three batch points
            runs = [{"batch": b} for b in (1, 8, 32)]
            if os.environ.get("BENCH_SERVING_GRID") == "1":
                # ISSUE 8 multi-chip grid: tp x replicas front-door
                # legs (the tp=1/replicas=1 leg is the grid's own
                # baseline)
                runs += [{"tp": t, "replicas": r}
                         for r in (1, 2) for t in (1, 2)]
        if name == "serving_prefix":
            # ISSUE 10 A/B: both legs in one invocation, same process,
            # so the pair always lands together in the artifact
            runs = [{"prefix_cache": False}, {"prefix_cache": True}]
        if name == "lstm_sweep":
            # always a paired A/B; the full batch sweep (the round-7
            # latency-vs-bandwidth adjudicator) is opt-in — 8 TrainStep
            # compiles would dominate an all-configs session
            batches = ((32, 64, 128, 256)
                       if os.environ.get("BENCH_LSTM_SWEEP_FULL") == "1"
                       and not smoke else (None,))
            runs = [{**({} if b is None else {"batch": b}), "fused": f}
                    for b in batches for f in (False, True)]
        for kw in runs:
            # bracket the config with a watchdog mark: compile_s is the
            # wall time this config spent compiling (trace + XLA),
            # exec_hbm_bytes the peak compiled-executable footprint from
            # memory_analysis (null where the backend doesn't expose it)
            from mxnet_tpu.telemetry.introspect import watchdog
            wd_mark = watchdog().mark()
            try:
                r = table[name](smoke, dtype, device_kind, **kw)
                compile_s, peak_hbm = watchdog().since(wd_mark)
                r.setdefault("compile_s", round(compile_s, 6))
                r.setdefault("exec_hbm_bytes", peak_hbm)
                r = check_line(r)
            except Exception as e:
                # one broken config must not eat the rest; main() turns
                # any `_error` line into a non-zero exit
                traceback.print_exc()
                r = {"metric": name + "_error", "value": None, "unit": "",
                     "error": "%s: %s" % (type(e).__name__, e), **kw}
            r.update(device=device_kind, dtype=dtype)
            snap = _telemetry_config_snapshot()
            if snap:
                r["telemetry"] = snap
            results.append(r)
            print(json.dumps(r))
            sys.stdout.flush()
    return results


def main():
    smoke = os.environ.get("BENCH_SMOKE", "") == "1"
    import jax
    if smoke:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    if platform != "tpu" and not smoke:
        sys.stderr.write(
            "bench: jax found no TPU (platform %r); nothing was measured. "
            "BENCH_SMOKE=1 runs the tiny CPU configs.\n" % platform)
        sys.exit(1)
    from mxnet_tpu.base import enable_compile_cache
    enable_compile_cache()
    failed = [r for r in _run_configs(smoke)
              if r["metric"].endswith("_error")]
    if failed:
        sys.stderr.write("bench: %d config(s) failed: %s\n" % (
            len(failed), ", ".join(r["metric"] for r in failed)))
        sys.exit(3)


if __name__ == "__main__":
    main()
