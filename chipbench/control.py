#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from (PERF.md gives them):

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For every seed, in one process: build the cell's system as a run does, warm
it, drive a short window at the cell's own load (training needs none), and
print each number `correct` compares, once for the timed path (`sound`) and
once with the plain reference in the program's place computed in the next
precision down (`control`; a crash or no number counts as failed). The last
line gives, per number, the sound runs' largest, the control's smallest and
their ratio. The benchmark's own runs never call this. Needs the chip.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def readings(cell):
    """({name: value} of the sound run, {name: value} of the control)."""
    from chipbench.harness import tracing
    family = cell.module("families", cell.config["family"])
    generator = cell.module("generators", cell.traffic["generator"])
    plan = generator.plan(cell)
    system = family.build(cell)
    try:
        generator.warm_up(system, plan)
        record = {}
        if cell.seconds > 0:
            record = generator.run(system, plan, cell.seconds, tracing.Timers())
        sound = system.check(record)
        try:
            control = system.control(record)
        except Exception as e:      # a control that crashes has failed
            control = [{"name": "control_raised", "value": repr(e),
                        "limit": None}]
    finally:
        system.close()
    pick = lambda rows: {r["name"]: r["value"] for r in rows
                         if r["limit"] is not None}
    return pick(sound), pick(control)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    os.environ.setdefault("MXNET_TELEMETRY_SPAN_RING", "1000000")
    from chipbench.harness import device, manifest
    book = manifest.load()
    chips = manifest.cell(book, args.workload).chips
    devices = device.require(chips)
    from mxnet_tpu.base import enable_compile_cache
    enable_compile_cache()
    sound_max, control_min = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = manifest.cell(book, args.workload, seed=seed,
                             seconds=args.seconds)
        sound, control = readings(cell)
        gc.collect()
        print(json.dumps({"seed": seed, "sound": sound, "control": control,
                          "seconds": time.perf_counter() - t0,
                          "device": device.describe(devices)}), flush=True)
        for k, v in sound.items():
            sound_max[k] = max(sound_max.get(k, v), v)
        for k, v in control.items():
            if isinstance(v, float):
                control_min[k] = min(control_min.get(k, v), v)
    print(json.dumps({"summary": {
        k: {"sound_max": sound_max[k], "control_min": control_min.get(k),
            "ratio": (control_min[k] / sound_max[k]
                      if control_min.get(k) and sound_max[k] else None)}
        for k in sound_max}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
