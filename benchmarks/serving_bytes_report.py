"""Decode-step bytes report: A/B the serving engine's gather vs paged
attention read via XLA's own cost model.

The claim under test (ISSUE 4 acceptance): the bytes one decode step
moves on the PAGED path (ops/pallas_paged.py — block-table walk,
width-bucketed tables) are independent of the padded history length T,
while the GATHER path (PR 1 — dense (B, T, H, Dh) materialization per
layer) grows linearly with T.

Methodology: the padded history length enters the compiled decode step
through ONE variable — the block-table width. The gather engine's width
is structurally tied to capacity (`_nblk` = max_len/block_size); the
paged engine's is bucketed to the longest TRUE length in the batch
(serving/engine.py decode_step). So the instrument holds everything
else constant — one pool sized for T_max, fixed true lengths — and
compiles each path's decode at the table width its engine would hand
XLA for each T: gather at T/block_size, paged at the (T-independent)
true-length bucket. Pinning the pool operand isolates the attention
read from a scatter-copy artifact: XLA's cost model charges the
`write_kv` pool update (identical on both paths) proportionally to the
pool operand, which would add the same linear-in-T noise to both legs
and hide the signal being measured.

On TPU each pallas_call is an opaque custom call whose declared
CostEstimate feeds the cost model — without it the paged mode would
count zero bytes. On CPU the kernel lowers through the Pallas
INTERPRETER, whose staging copies inflate the paged path's absolute
bytes (disclosed on every CPU line, same caveat as bytes_report.py);
the decision signals on CPU are the flat-vs-linear byte/flop curves in
T, not the absolute paged bytes.

A second claim rode in with ISSUE 8: under tensor-parallel serving
(`MXNET_SERVING_TP=k`, serving/tp.py) the bytes ONE CHIP moves per
decode step scale ~1/k — the pool shards over heads, each chip's paged
kernel walks H/k heads of the same table. The instrument compiles the
tp-sharded decode over an emulated k-device mesh and reads XLA's cost
model for the PER-PARTITION module (SPMD: the compiled module IS one
chip's program), alongside the kernel's own declared per-chip bytes
(ops/pallas_paged.paged_call_cost at the local head count). Replicated
weights/activations keep the ratio above the pure-KV 1/k floor at this
tiny d_model; the KV term dominates as models grow.

Knobs: SERVING_BYTES_T (comma list, default 128,512,2048),
SERVING_BYTES_BATCH (4), SERVING_BYTES_EXEC=1 (also time 20 real decode
steps per leg), SERVING_BYTES_TP (comma list, default 1,2,4 — legs that
don't fit the device/head count are skipped with a note). Output: one
JSON line per (path, T) and per tp leg + a summary table on stderr.
The committed CPU run is BENCH_BYTES_SERVING_CPU.txt; it has not run on
the chip.
"""
import json
import os
import sys
import time

import numpy as np


def build_engine(paged, max_len, batch, cfg_kw, block_size=16, tp=None,
                 kv_quant=None):
    import jax
    from mxnet_tpu import serving
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params)
    cfg = TransformerConfig(max_len=max_len, **cfg_kw)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    model = serving.TransformerLM(params, cfg)
    eng = serving.Engine(model, max_batch=batch, block_size=block_size,
                         paged=paged, tp=tp, kv_quant=kv_quant)
    return eng, model


def decode_args(eng, true_lens, width):
    """The exact (tokens, positions, tables) the engine's decode_step
    would build for sequences at `true_lens`, at table width `width` —
    allocation only, no compute (Engine.begin)."""
    from mxnet_tpu.serving.engine import pow2_bucket
    seqs = [eng.begin(list(range(1, l + 1)), 4) for l in true_lens]
    bb = pow2_bucket(len(seqs), lo=1, hi=eng.max_batch)
    toks = np.zeros((bb,), np.int32)
    pos = np.zeros((bb,), np.int32)
    tabs = np.zeros((bb, width), np.int32)
    for i, s in enumerate(seqs):
        toks[i] = s.tokens[-1]
        pos[i] = len(s.tokens) - 1
        tabs[i] = s.table_row[:width]
    for s in seqs:
        eng.release(s)
    return toks, pos, tabs


def paged_width(eng, true_lens):
    """The width bucket the paged decode_step computes — covers the
    longest TRUE length, independent of max_len."""
    from mxnet_tpu.serving.engine import pow2_bucket
    return pow2_bucket(max(eng.cache.blocks_for(l) for l in true_lens),
                       lo=1, hi=eng._nblk)


def analyze(eng, model, padded_T, width, true_lens):
    import jax.numpy as jnp
    toks, pos, tabs = decode_args(eng, true_lens, width)
    # the decode program this engine's configuration bound
    fn, params = model.programs["decode"], model.step_params
    # every row's token from the host: the carry (the last step's tokens
    # on the device, at max_batch) is not referred to
    args = (params, eng.cache.k, eng.cache.v, eng._no_carry,
            jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(tabs))
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    cost = compiled.cost_analysis()
    info = {
        "path": "paged" if eng.paged else "gather",
        "tp": eng.tp,
        "padded_T": padded_T,
        "table_width": width,
        "true_lens": list(true_lens),
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
        "compile_s": round(time.perf_counter() - t0, 1),
    }
    if os.environ.get("SERVING_BYTES_EXEC", "0") == "1":
        k, v, logits, nxt = fn(*args)          # warmup (jit cache hot)
        np.asarray(nxt)
        t0 = time.perf_counter()
        n = 20
        for _ in range(n):
            k, v, logits, nxt = fn(params, k, v, *args[3:])
        np.asarray(nxt)
        info["decode_ms_per_step"] = round(
            1e3 * (time.perf_counter() - t0) / n, 3)
        # the step consumed the engine's pools: hand it the last results
        eng.cache.k, eng.cache.v = k, v
    return info


def main():
    # the tp legs need a multi-device host platform; the flag must land
    # before the first jax import and is a no-op for real TPU backends
    tp_legs = [int(x) for x in os.environ.get("SERVING_BYTES_TP",
                                              "1,2,4").split(",") if x]
    flags = os.environ.get("XLA_FLAGS", "")
    if max(tp_legs, default=1) > 1 and \
            "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d"
            % max(tp_legs)).strip()
    import jax
    dev = jax.devices()[0]
    batch = int(os.environ.get("SERVING_BYTES_BATCH", "4"))
    ts = [int(t) for t in os.environ.get("SERVING_BYTES_T",
                                         "128,512,2048").split(",")]
    # fixed true lengths — the raggedness the paged path exploits; all
    # well under the smallest padded T so every T shares them
    true_lens = [100, 40, 7, 1][:batch]
    cfg_kw = dict(vocab=512, d_model=128, n_heads=4, n_layers=2, d_ff=256)
    interp = dev.platform != "tpu"
    block_size = 16

    # ONE pool per path, sized for T_max (see module docstring: pins the
    # write_kv scatter artifact so the T sweep varies only the table
    # width — the variable that carries the padded history length)
    t_max = max(ts)
    eng_g, model_g = build_engine(False, t_max, batch, cfg_kw, block_size)
    eng_p, model_p = build_engine(True, t_max, batch, cfg_kw, block_size)
    w_paged = paged_width(eng_p, true_lens)

    rows = []
    for T in ts:
        for eng, model in ((eng_g, model_g), (eng_p, model_p)):
            width = w_paged if eng.paged else T // block_size
            info = analyze(eng, model, T, width, true_lens)
            info["batch"] = batch
            info["device"] = getattr(dev, "device_kind", dev.platform)
            if eng.paged and interp:
                info["note"] = ("paged kernel ran under the Pallas "
                                "interpreter — absolute bytes inflated "
                                "by staging copies; the flat-vs-linear "
                                "shape in T is the decision signal on "
                                "CPU, absolute bytes are TPU-only "
                                "(declared CostEstimates)")
            rows.append(info)
            print(json.dumps(info), flush=True)

    print("\npath    padded_T  width  MB/step  MFLOP/step", file=sys.stderr)
    base = {}
    for r in rows:
        mb = (r["bytes_accessed"] or 0) / 1e6
        mf = (r["flops"] or 0) / 1e6
        key = r["path"]
        delta = ""
        if key in base and base[key]:
            delta = "  (bytes %+.1f%% vs T=%d)" % (
                100.0 * ((r["bytes_accessed"] or 0) - base[key][1])
                / base[key][1], base[key][0])
        else:
            base[key] = (r["padded_T"], r["bytes_accessed"])
        print("%-7s %8d  %5d  %7.2f  %10.1f%s"
              % (r["path"], r["padded_T"], r["table_width"], mb, mf,
                 delta), file=sys.stderr)
    gather = [r["bytes_accessed"] for r in rows if r["path"] == "gather"]
    paged = [r["bytes_accessed"] for r in rows if r["path"] == "paged"]
    if len(gather) >= 2 and all(gather) and all(paged):
        print("\ngather bytes T-max/T-min: %.2fx   paged: %.2fx "
              "(flat == independent of padded history)"
              % (max(gather) / min(gather), max(paged) / min(paged)),
              file=sys.stderr)

    # --- tensor-parallel legs: PER-CHIP decode bytes vs tp=1 ------------
    from mxnet_tpu.ops.pallas_paged import paged_call_cost
    cfg_heads, cfg_dh = cfg_kw["n_heads"], \
        cfg_kw["d_model"] // cfg_kw["n_heads"]
    n_dev = len(jax.devices())
    tp_rows = []
    for k in tp_legs:
        if k > 1 and (cfg_heads % k or n_dev < k):
            print(json.dumps({"path": "paged", "tp": k,
                              "skipped": "needs %d devices and heads%%%d"
                                         "==0 (have %d devices, %d heads)"
                                         % (k, k, n_dev, cfg_heads)}),
                  flush=True)
            continue
        eng_t, model_t = build_engine(True, t_max, batch, cfg_kw,
                                      block_size, tp=k)
        if eng_t.tp != k:
            print(json.dumps({"path": "paged", "tp": k,
                              "skipped": eng_t.tp_fallback}), flush=True)
            continue
        info = analyze(eng_t, model_t, t_max, w_paged, true_lens)
        info["batch"] = batch
        info["device"] = getattr(dev, "device_kind", dev.platform)
        # the kernel's own declared per-chip traffic at H/k local heads
        # (exact 1/k modulo the replicated int32 tables)
        fl, by = paged_call_cost(batch, 1, cfg_heads // k, cfg_dh,
                                 w_paged, block_size)
        info["declared_kernel_bytes_per_chip_per_layer"] = by
        if interp:
            info["note"] = ("per-partition cost of the SPMD module "
                            "(one chip's program); Pallas interpreter "
                            "staging inflates absolute bytes on CPU — "
                            "the tp RATIO is the decision signal, and "
                            "replicated weights keep it above the "
                            "pure-KV 1/k floor at this tiny d_model")
        tp_rows.append(info)
        print(json.dumps(info), flush=True)
    if tp_rows and all(r["bytes_accessed"] for r in tp_rows):
        # baseline is the tp=1 leg when it ran; otherwise the smallest
        # tp that did (SERVING_BYTES_TP may exclude 1) — the header
        # names whichever it is, never a silently-wrong "tp1"
        base = min(tp_rows, key=lambda r: r["tp"])
        b1 = base["bytes_accessed"]
        print("\ntp   per-chip MB/step  ratio-vs-tp%d   declared-kernel-"
              "bytes/chip/layer" % base["tp"], file=sys.stderr)
        for r in tp_rows:
            print("%-4d %15.2f  %12.2f   %d"
                  % (r["tp"], r["bytes_accessed"] / 1e6,
                     r["bytes_accessed"] / b1,
                     r["declared_kernel_bytes_per_chip_per_layer"]),
                  file=sys.stderr)

    # --- quantized-KV leg (ISSUE 20): f32 vs int8 pool, same step ------
    # The decision signal is the kernel's DECLARED per-call bytes
    # (paged_call_cost at kv_itemsize=1 + scale sidecars — exact
    # arithmetic, no interpreter); the compiled cost-model line rides
    # along with the usual CPU staging-inflation disclosure. The pool-
    # layout ratio (Engine.kv_bytes_per_token) is the resident-
    # sequences-per-chip headline bench_serving_quant measures.
    if os.environ.get("SERVING_BYTES_QUANT", "1") == "1":
        import jax.numpy as jnp
        eng_q, model_q = build_engine(True, t_max, batch, cfg_kw,
                                      block_size, kv_quant=True)
        assert eng_q.kv_quant, eng_q.kv_quant_fallback
        toks, pos, tabs = decode_args(eng_q, true_lens, w_paged)
        args = (model_q.step_params, *eng_q.cache.arrays(),
                eng_q._no_carry, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(tabs))
        t0 = time.perf_counter()
        cost = model_q.programs["decode"].lower(*args).compile() \
            .cost_analysis()
        fl4, by4 = paged_call_cost(batch, 1, cfg_heads, cfg_dh,
                                   w_paged, block_size)
        fl8, by8 = paged_call_cost(batch, 1, cfg_heads, cfg_dh,
                                   w_paged, block_size, kv_itemsize=1,
                                   scale_blocks=eng_q.cache.num_blocks)
        eng_f, _ = build_engine(True, t_max, batch, cfg_kw, block_size)
        qrow = {
            "path": "paged", "kv_quant": "int8", "tp": 1,
            "padded_T": t_max, "table_width": w_paged,
            "true_lens": list(true_lens),
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
            "compile_s": round(time.perf_counter() - t0, 1),
            "declared_kernel_bytes_per_layer_f32": by4,
            "declared_kernel_bytes_per_layer_int8": by8,
            "kv_bytes_per_token_f32": eng_f.kv_bytes_per_token(),
            "kv_bytes_per_token_int8": eng_q.kv_bytes_per_token(),
            "device": getattr(dev, "device_kind", dev.platform),
        }
        if interp:
            qrow["note"] = ("Pallas interpreter staging inflates "
                            "absolute bytes on CPU (the int8 blocks "
                            "are staged through f32 copies) — the "
                            "DECLARED kernel bytes and the pool-layout "
                            "bytes/token are the decision signals; "
                            "absolute cost-model bytes are TPU-only")
        print(json.dumps(qrow), flush=True)
        print("\nquant leg (int8 KV pool, per decode call/layer):\n"
              "declared kernel bytes  f32 %d  int8 %d  ratio %.2fx\n"
              "pool bytes/token       f32 %d  int8 %d  ratio %.2fx "
              "(resident-sequences multiplier at fixed pool HBM)"
              % (by4, by8, by8 / by4,
                 qrow["kv_bytes_per_token_f32"],
                 qrow["kv_bytes_per_token_int8"],
                 qrow["kv_bytes_per_token_int8"]
                 / qrow["kv_bytes_per_token_f32"]),
              file=sys.stderr)


if __name__ == "__main__":
    main()
