"""Generator `closed_loop`: a fixed number of clients, each sending its next
request when its last one has returned. One thread polls all clients."""
import time

import numpy as np

from chipbench.generators import schedule, serving
from chipbench.harness import util

POLL_S = 0.002


def plan(cell):
    t, seed = cell.traffic, cell.seed
    n = t["schedule_length"]
    p_len = schedule.lengths(t["prompt_tokens"], n, t["schedule_seed"], seed, "prompt")
    o_len = schedule.lengths(t["output_tokens"], n, t["schedule_seed"], seed, "output")
    rng = np.random.default_rng(seed)
    vocab = cell.config["vocab_size"]
    return {"clients": t["clients"], "requests": [
        {"prompt": schedule.prompt_tokens(p, vocab, rng), "max_new": o}
        for p, o in zip(p_len, o_len)]}


def warm_up(system, plan_):
    serving.warm_up(system, [r["prompt"] for r in plan_["requests"]])


def run(system, plan_, seconds, timers):
    reqs, cursor = plan_["requests"], 0
    live = [None] * plan_["clients"]      # (handle, request, sent time)
    done = []
    t0 = time.perf_counter()
    tokens0 = system.tokens_generated()
    while True:
        now = time.perf_counter() - t0
        timers.fire(now)
        if now >= seconds:
            break
        for c, slot in enumerate(live):
            if slot is not None and not slot[0].wait(0):
                continue
            if slot is not None:
                done.append(slot)
            r = reqs[cursor % len(reqs)]
            cursor += 1
            at = time.perf_counter() - t0
            live[c] = (system.submit(r["prompt"], r["max_new"]), r, at)
        time.sleep(POLL_S)
    tokens1, t_close = system.tokens_generated(), time.perf_counter() - t0
    requests = [serving.request_record(h, t0, at, at, r["prompt"])
                for h, r, at in done + [s for s in live if s is not None]]
    failed = sum(1 for r in requests if r["error"] is not None)
    return {"t0": t0, "window_s": t_close, "requests": requests,
            "tokens_in_window": tokens1 - tokens0,
            "attempted": len(requests), "failed": failed}


def end_to_end(record):
    return {"serve_tok_per_s": record["tokens_in_window"] / record["window_s"],
            "tpot_p90_ms": util.percentile(serving.tpot_ms(record["requests"]), 90)}


def details(record):
    tpot = serving.tpot_ms(record["requests"])
    return {"requests_sent": len(record["requests"]),
            "finished": sum(1 for r in record["requests"] if r["ok"]),
            "tpot_ms": util.percentiles(tpot),
            "tpot_samples": len(tpot),
            "decode_tokens": record["tokens_in_window"]}
