#!/usr/bin/env python3
"""The benchmark's one command:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run. It finds the cell in BENCHMARK.json, its
configuration and traffic mix in their files, the family that builds the
system under test and the generator that drives it by the names those files
give, and the per-layer readers by the names of the metrics. It knows no
cell, configuration, mix or metric itself.

Set-up (imports, weights made on the device from the seed, every shape the
window will use compiled or read from the cache) ends where the window
starts and is reported as `setup_s`. The window lasts `--seconds`. After it
the timed path's own output is compared with the plain reference; every
number compared is printed beside its limit. The last line of the standard
output is the one JSON object the contract fixes. Without a TPU, or with
fewer chips than the cell asks, it exits non-zero and prints no result.
"""
import time

_PROCESS_START = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(kind, payload):
    """An earlier line: free-form, one JSON object, never the last."""
    print(json.dumps({kind: payload}), flush=True)


def window_spans(record):
    """The program's spans that started inside the window."""
    from mxnet_tpu import telemetry
    lo = record["t0"] * 1e6
    hi = lo + record["window_s"] * 1e6
    return [s for s in telemetry.spans() if lo <= s["ts"] <= hi]


def run_cell(cell, trace_on, devices):
    """Everything between finding the chip and printing: returns the result
    object. Tests drive this with a stand-in for the chip."""
    family = cell.module("families", cell.config["family"])
    generator = cell.module("generators", cell.traffic["generator"])
    plan = generator.plan(cell)
    system = family.build(cell)
    try:
        return _measure(cell, trace_on, devices, family, generator, plan,
                        system)
    finally:
        system.close()      # whatever happened, no serving thread is left


def _measure(cell, trace_on, devices, family, generator, plan, system):
    from mxnet_tpu.telemetry import introspect
    from chipbench.harness import context, device, tracing, util
    from chipbench.trace import reduce as tr
    generator.warm_up(system, plan)

    timers = tracing.Timers()
    trace = None
    if trace_on:
        trace = tracing.DeviceTrace(
            os.path.join(ROOT, ".chipbench_trace", cell.name),
            system.trace_slice_s)
        trace.arm(timers, cell.seconds)
    compile_mark = introspect.watchdog().mark()
    setup_s = time.perf_counter() - _PROCESS_START

    record = generator.run(system, plan, cell.seconds, timers)

    reduced = trace.stop() if trace else None
    compiled = [e for e in introspect.watchdog().events()
                if e["seq"] > compile_mark]
    memory_peak = device.memory_peak_bytes(devices)
    say("memory", device.memory_stats(devices))
    spans = window_spans(record)
    counters = system.counters()
    say("window", dict(generator.details(record), setup_s=setup_s))
    say("counters", counters)

    compared = system.check(record)
    compared.append(util.compared("compilations_in_window", len(compiled), 0))
    system.close()
    say("compared", compared)
    say("after_window_s", time.perf_counter() - _PROCESS_START - setup_s
        - record["window_s"])

    info = device.describe(devices)
    info["memory_peak_bytes"] = memory_peak
    result = {"correct": all(c["ok"] for c in compared),
              "attempted": record["attempted"], "failed": record["failed"]}
    if not trace_on:
        values = dict(generator.end_to_end(record), setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
    else:
        ctx = context.Context(cell=cell, record=record, counters=counters,
                              spans=spans, trace=reduced,
                              peaks=device.peaks(info["kind"]), family=family)
        result["metrics"] = {}
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        info["busy_s"], info["window_s"] = reduced["busy_s"], reduced["window_s"]
        # the family says what the host was doing; put it on the trace's clock
        host = [(name, tr.to_trace_s(reduced, b), tr.to_trace_s(reduced, e))
                for name, b, e in system.host_spans(record, spans)]
        result["breakdown"] = {
            "device_ops": tr.top(reduced["ops"]),
            "idle_gaps": tr.top(tr.label_gaps(reduced, host))}
        prog = ctx.step_program()
        say("trace", {"devices": reduced["devices"],
                      "programs_run": len(reduced["modules"]),
                      "settle_idle_s": reduced["settle_idle_s"],
                      "longest_gaps_s": sorted(
                          (d for _, d in reduced["gaps"]), reverse=True)[:3],
                      "busiest_program": prog and [prog[0], len(prog[1]),
                                                   context.median(prog[1])]})
    result["device"] = info
    return result


def main(argv=None):
    args = parse(argv)
    # the program's span ring is bounded; a window of decode steps, each
    # copied once per request, must fit (read at the program's import)
    os.environ.setdefault("MXNET_TELEMETRY_SPAN_RING", "1000000")
    from chipbench.harness import device, manifest
    cell = manifest.cell(manifest.load(), args.workload, seed=args.seed,
                         seconds=args.seconds)
    devices = device.require(cell.chips)
    from mxnet_tpu.base import enable_compile_cache
    say("run", {"workload": cell.name, "seed": args.seed,
                "seconds": args.seconds, "trace": args.trace,
                "compile_cache_dir": enable_compile_cache(),
                "device": device.describe(devices)})
    result = run_cell(cell, bool(args.trace), devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
