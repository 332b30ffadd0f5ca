#!/usr/bin/env python3
"""Where one compiled program's device time goes, by operation:

    python3 tools/program_ops.py --workload <serving cell> --seed <n> \\
        --prompt-lengths 6000,3000 [--program jit_serving_prefill] \\
        [--match '8,6,256' ...]

It builds the cell's system exactly as `chipbench/run.py` does (the same
family, weights from the seed, the same server), sends one request a
prompt length once to compile what it needs, then sends them again under
the jax profiler, one at a time on an otherwise idle server, and prints
one JSON object: for every run of a program whose name starts with
`--program` in the trace, its device seconds, what the request's
`serving.prefill` span said (`bucket`, `length`, `attn`), and its
operations summed by name, the longest first, each with the head of its
HLO text (the result's shape is in it). `--match` sums the operations
whose text holds a string (an attention block's shape, a kernel's name).
Needs a TPU, like the benchmark (PERF.md §6, PR 41).
"""
import argparse
import collections
import glob
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TOP = 30


def program_runs(trace_dir, program):
    """[(name, seconds, [(operation text, seconds)])] of device 0's runs of
    the programs named `program`*, in order."""
    from jax.profiler import ProfileData
    from chipbench.trace import reduce as tr
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    planes = sorted((p for p in ProfileData.from_file(path).planes
                     if p.name.startswith("/device:")), key=lambda p: p.name)
    lines = {}
    for plane in planes:      # the first device that ran operations
        lines = {line.name: [(e.name, e.start_ns, e.duration_ns)
                             for e in line.events] for line in plane.lines}
        if lines.get(tr.OPS_LINE):
            break
    ops = sorted(lines.get(tr.OPS_LINE, ()), key=lambda e: e[1])
    if not any(name.startswith(program)
               for name, _, _ in lines.get(tr.MODULES_LINE, ())):
        print("program_ops: no run of %r in the trace; planes %s; programs %s"
              % (program, [(p.name, [ln.name for ln in p.lines])
                           for p in planes],
                 sorted({n for n, _, _ in lines.get(tr.MODULES_LINE, ())})),
              file=sys.stderr)
    runs = []
    for name, start, dur in sorted(lines.get(tr.MODULES_LINE, ()),
                                   key=lambda e: e[1]):
        if name.startswith(program):
            inside = [(n, d * 1e-9) for n, s, d in ops
                      if start <= s + d / 2 <= start + dur]
            runs.append((name, dur * 1e-9, inside))
    return runs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompt-lengths", required=True)
    ap.add_argument("--program", default="jit_serving_prefill")
    ap.add_argument("--match", action="append", default=[])
    args = ap.parse_args(argv)
    os.environ.setdefault("MXNET_TELEMETRY_SPAN_RING", "1000000")

    import numpy as np
    import jax
    from chipbench.generators import schedule
    from chipbench.harness import device, manifest
    from chipbench.trace import reduce as tr
    from mxnet_tpu import telemetry
    from mxnet_tpu.base import enable_compile_cache

    cell = manifest.cell(manifest.load(), args.workload, seed=args.seed,
                         seconds=1.0)
    device.require(cell.chips)
    enable_compile_cache()
    lengths = [int(n) for n in args.prompt_lengths.split(",")]
    rng = np.random.default_rng(args.seed)
    prompts = [schedule.prompt_tokens(n, cell.config["vocab_size"], rng)
               for n in lengths]
    system = cell.module("families", cell.config["family"]).build(cell)
    trace_dir = tempfile.mkdtemp(prefix="program_ops_")
    try:
        for traced in (False, True):
            if traced:
                mark_us = time.perf_counter() * 1e6
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            for prompt in prompts:
                if not system.submit(prompt, 2).wait(1800.0):
                    raise SystemExit("a request did not finish")
        jax.profiler.stop_trace()
        spans = [s["attrs"] for s in telemetry.spans()
                 if s["name"] == "serving.prefill" and s["ts"] >= mark_us]
    finally:
        system.close()
    out = []
    for i, (name, seconds, ops) in enumerate(
            program_runs(trace_dir, args.program)):
        by_op, text = collections.Counter(), {}
        for op, s in ops:
            by_op[tr.short_name(op)] += s
            text[tr.short_name(op)] = op[:200]
        attrs = spans[i] if i < len(spans) else {}
        out.append({
            "program": name, "device_s": seconds,
            "span": {k: attrs.get(k) for k in ("length", "bucket", "attn")},
            "operations": len(ops), "operations_s": sum(by_op.values()),
            "matched_s": {m: sum(s for op, s in ops if m in op)
                          for m in args.match},
            "top": [[text[k], v] for k, v in by_op.most_common(TOP)]})
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "device": jax.devices()[0].device_kind,
                      "runs": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
