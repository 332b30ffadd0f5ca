"""Generator `train_steps`: optimizer steps back to back on batches that are
resident on the device, a few steps in flight so the device never waits for
the host to notice that one has finished."""
import collections
import time

import jax

from chipbench.harness import util

IN_FLIGHT = 4     # steps dispatched ahead of the one waited for


def plan(cell):
    return {"batch": cell.traffic["batch"]}


def warm_up(system, plan_):
    """Compiles the step and drives the first steps the check follows."""
    system.first_steps()
    system.block()


def run(system, plan_, seconds, timers):
    pending = collections.deque()
    dispatch_ms, losses = [], []
    t0 = time.perf_counter()
    steps0 = system.n_steps
    while True:
        ts = time.perf_counter()
        pending.append(system.step())
        te = time.perf_counter()
        dispatch_ms.append((ts - t0, 1e3 * (te - ts)))
        if len(pending) > IN_FLIGHT:
            losses.append(pending.popleft())
            jax.block_until_ready(losses[-1])
            now = time.perf_counter() - t0
            timers.fire(now)
            if now >= seconds:
                break
    system.block()
    t1 = time.perf_counter()
    losses.extend(pending)
    return {"t0": t0, "window_s": t1 - t0, "steps": system.n_steps - steps0,
            "global_batch": plan_["batch"],
            "dispatch": dispatch_ms,
            "losses": [float(v) for v in losses],
            "attempted": system.n_steps - steps0, "failed": 0}


def end_to_end(record):
    return {"train_samples_per_s":
            record["steps"] * record["global_batch"] / record["window_s"]}


def details(record):
    d = [ms for _, ms in record["dispatch"]]
    return {"steps": record["steps"], "window_s": record["window_s"],
            "step_ms_mean": 1e3 * record["window_s"] / record["steps"],
            "dispatch_ms_p50": util.percentile(d, 50),
            "loss_first": record["losses"][0], "loss_last": record["losses"][-1]}
