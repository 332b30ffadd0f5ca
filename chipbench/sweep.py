#!/usr/bin/env python3
"""Finds the knee of an open-loop mix, once, before its rate is fixed in the
traffic file (the benchmark's own runs never search for a rate):

    python3 chipbench/sweep.py --config <config> --traffic <mix> \\
        --rates 1.5,1.6,1.7 --seconds 51 --seed <n>

One server, built and warmed as a run builds it, then one window per rate
(lowest first, the server left to drain between them). Per rate it prints the
requests due and finished, the backlog (due and not done) at the middle and at
the end of the window, the first-token, queue-wait and per-token times, the
tokens a second and the compilations inside the window (a row with any is no
reading). The knee is the highest rate, from the lowest up, before the
backlog first grows through the second half of its window; the last line
names it. A cell below the knee runs at about four fifths of it. A window of
30 s on one seed places the knee to about 0.2 requests a second and no closer
(PERF.md, the sweeps of PR 23). Needs the chip.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DRAIN_S = 120.0


def backlog(requests, t):
    """Requests due by `t` (seconds from the window's start) and not done."""
    return sum(1 for r in requests
               if r["due"] <= t and (r["t_done"] is None or r["t_done"] > t))


def row(rate, record, details, compilations):
    from chipbench.harness import util
    reqs = record["requests"]
    waits = [1e3 * (r["t_admit"] - r["t_submit"]) for r in reqs
             if r["t_admit"] is not None]
    return {"rate": rate, "due": len(reqs), "finished": details["finished"],
            "backlog_mid": backlog(reqs, record["window_s"] / 2),
            "backlog_end": backlog(reqs, record["window_s"]),
            "ttft_ms": details["ttft_ms"], "tpot_ms": details["tpot_ms"],
            "queue_wait_ms": util.percentiles(waits),
            "decode_tok_per_s": details["decode_tok_per_s"],
            "failed": record["failed"], "compilations": compilations}


def knee(rows):
    """The highest rate, from the lowest up, before the backlog first grows
    through the second half of its window; None where the lowest rate
    already grows."""
    best = None
    for r in sorted(rows, key=lambda r: r["rate"]):
        if r["backlog_end"] > r["backlog_mid"]:
            break
        best = r["rate"]
    return best


def sweep(cell, rates, out=print):
    """Rows of one server over the rates, lowest first."""
    from mxnet_tpu.telemetry import introspect
    from chipbench.harness import tracing
    family = cell.module("families", cell.config["family"])
    generator = cell.module("generators", cell.traffic["generator"])
    rates = sorted(rates)
    system = family.build(cell)
    rows = []
    try:
        # the highest rate sends the most requests: its plan holds every
        # length any rate will send
        cell.traffic["rate_per_s"] = rates[-1]
        generator.warm_up(system, generator.plan(cell))
        handles, submit = [], system.submit
        system.submit = lambda *a: handles.append(submit(*a)) or handles[-1]
        for rate in rates:
            cell.traffic["rate_per_s"] = rate
            mark = introspect.watchdog().mark()
            record = generator.run(system, generator.plan(cell), cell.seconds,
                                   tracing.Timers())
            compiled = [e for e in introspect.watchdog().events()
                        if e["seq"] > mark]
            rows.append(row(rate, record, generator.details(record),
                            len(compiled)))
            out(json.dumps(rows[-1]))
            deadline = time.perf_counter() + DRAIN_S
            for h in handles:       # what is in flight finishes first
                h.wait(max(0.0, deadline - time.perf_counter()))
            del handles[:]
    finally:
        system.close()
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("MXNET_TELEMETRY_SPAN_RING", "1000000")
    from chipbench.harness import device, manifest
    book = manifest.load()
    config = {c["name"]: c for c in book["configs"]}[args.config]
    cell = manifest.Cell(
        name="sweep", chips=1,
        config=manifest.read_json(os.path.join(ROOT, config["file"])),
        traffic={}, end_to_end=[], per_layer=[], seed=args.seed,
        seconds=args.seconds)
    cell.traffic = manifest.read_json(cell.find("traffic",
                                                args.traffic + ".json"))
    devices = device.require(cell.chips)
    from mxnet_tpu.base import enable_compile_cache
    enable_compile_cache()
    print(json.dumps({"sweep": {"config": args.config, "traffic": args.traffic,
                                "seed": args.seed, "seconds": args.seconds,
                                "device": device.describe(devices)}}),
          flush=True)
    rows = sweep(cell, [float(r) for r in args.rates.split(",")],
                 out=lambda line: print(line, flush=True))
    print(json.dumps({"knee_per_s": knee(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
