#!/usr/bin/env python3
"""Where a benchmark cell's `setup_s` goes, by phase:

    python3 tools/setup_split.py --workload <cell> --seed <n> [--seconds <s>]

It runs the cell's set-up exactly as `chipbench/run.py` does (the same
family, generator and warm-up, the same compile cache) with jax's own
monitoring listeners on, and prints one JSON object: seconds of imports,
of `family.build` (weights, server), of the warm-up, and inside the
warm-up the seconds jax spent tracing step programs to jaxprs, lowering
them to their modules (a warm process pays both: the persistent cache's
key is made from the module), compiling or reading the compile back
from the cache, by program and, for whatever took 20 ms, call by call in
order (a trace holds the traces nested in it, so the phases' sums count
those twice; `warm_up_s` and the watchdog's seconds a program do not).
A short window follows (`--seconds`, default 2) so that the server is
driven once before it is closed. Needs a TPU, like the benchmark
(PERF.md §6, PR 33).
"""
import time

_T0 = time.perf_counter()

import argparse
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: jax's duration events -> the phase they are counted under
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_or_read_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    os.environ.setdefault("MXNET_TELEMETRY_SPAN_RING", "1000000")

    import jax
    from jax import monitoring
    from chipbench.harness import device, manifest, tracing
    from mxnet_tpu.base import enable_compile_cache
    from mxnet_tpu.telemetry import introspect

    by_phase = collections.Counter()
    by_program = collections.defaultdict(collections.Counter)
    calls = collections.Counter()
    in_order = []       # (function, phase, seconds) of what took 20 ms

    def listen(event, seconds, **kw):
        phase = PHASES.get(event)
        if phase is None:
            return
        by_phase[phase] += seconds
        name = kw.get("fun_name")
        if name:
            by_program[name][phase] += seconds
            calls[name, phase] += 1
            if seconds > 0.02:
                in_order.append((name, phase, round(seconds, 3)))

    monitoring.register_event_duration_secs_listener(listen)

    cell = manifest.cell(manifest.load(), args.workload, seed=args.seed,
                         seconds=args.seconds)
    devices = device.require(cell.chips)
    cache_dir = enable_compile_cache()
    family = cell.module("families", cell.config["family"])
    generator = cell.module("generators", cell.traffic["generator"])
    t_imports = time.perf_counter()
    plan = generator.plan(cell)
    system = family.build(cell)
    t_built = time.perf_counter()
    in_build = dict(by_phase)
    try:
        generator.warm_up(system, plan)
        t_warm = time.perf_counter()
        in_setup = dict(by_phase)
        record = generator.run(system, plan, cell.seconds, tracing.Timers())
    finally:
        system.close()
    warm = {k: in_setup.get(k, 0.0) - in_build.get(k, 0.0)
            for k in PHASES.values()}
    out = {
        "workload": cell.name, "seed": args.seed,
        "device": jax.devices()[0].device_kind,
        "compile_cache_dir": cache_dir,
        "setup_s": t_warm - _T0,
        "imports_s": t_imports - _T0,
        "build_s": t_built - t_imports,
        "build_jax_s": in_build,
        "warm_up_s": t_warm - t_built,
        "warm_up_jax_s": warm,
        "by_program": {
            name: dict(c, n={p: calls[name, p] for p in c})
            for name, c in sorted(by_program.items())
            if name.startswith("serving") or sum(c.values()) > 0.05},
        "in_order": in_order,
        "watchdog": [
            {"site": e.get("site"), "seconds": e.get("seconds"),
             "phase": e.get("phase"), "reason": e.get("reason")}
            for e in introspect.watchdog().events()],
        "tokens_in_window": record["tokens_in_window"],
        "failed": record["failed"],
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
