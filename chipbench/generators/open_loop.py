"""Generator `open_loop`: requests are due on a schedule fixed by the mix and
the seed, whether or not earlier ones have finished. One sender thread;
every request is timed from when it was due, not from when it was sent."""
import threading
import time

import numpy as np

from chipbench.generators import schedule, serving
from chipbench.harness import util

FIRST_TOKEN_GRACE_S = 10.0


def plan(cell):
    t, seed = cell.traffic, cell.seed
    due = schedule.arrivals(t["rate_per_s"], cell.seconds, t["schedule_seed"], seed)
    n = len(due)
    p_len = schedule.lengths(t["prompt_tokens"], n, t["schedule_seed"], seed, "prompt")
    o_len = schedule.lengths(t["output_tokens"], n, t["schedule_seed"], seed, "output")
    rng = np.random.default_rng(seed)
    vocab, cap = cell.config["vocab_size"], t["max_total_tokens"]
    return {"requests": [
        {"due": d, "prompt": schedule.prompt_tokens(p, vocab, rng),
         "max_new": max(1, min(o, cap - p))}
        for d, p, o in zip(due, p_len, o_len)]}


def warm_up(system, plan_):
    serving.warm_up(system, [r["prompt"] for r in plan_["requests"]])


def run(system, plan_, seconds, timers):
    reqs = plan_["requests"]
    sent = [None] * len(reqs)     # (handle, sent time, error)
    t0 = time.perf_counter() + 0.05

    def sender():
        for i, r in enumerate(reqs):
            serving.sleep_until(t0 + r["due"])
            now = time.perf_counter() - t0
            try:
                sent[i] = (system.submit(r["prompt"], r["max_new"]), now, None)
            except Exception as e:      # shed or refused: a failed request
                sent[i] = (None, now, e)

    th = threading.Thread(target=sender, name="chipbench-open-loop")
    th.start()
    tokens0 = system.tokens_generated()
    while True:
        now = time.perf_counter() - t0
        timers.fire(now)
        if now >= seconds:
            break
        time.sleep(min(0.02, seconds - now))
    tokens1, t_close = system.tokens_generated(), time.perf_counter() - t0
    th.join()
    # the window is closed: no more arrivals. Give what was due its first
    # token, then stop; what is still decoding is abandoned, not waited for.
    deadline = time.perf_counter() + FIRST_TOKEN_GRACE_S
    while time.perf_counter() < deadline and any(
            h is not None and h.t_client_first_token is None and not h.wait(0)
            for h, _, _ in sent):
        time.sleep(0.01)
    requests = [serving.request_record(h, t0, r["due"], at, r["prompt"], err)
                for r, (h, at, err) in zip(reqs, sent)]
    failed = sum(1 for r in requests if r["t_first"] is None)
    return {"t0": t0, "window_s": t_close, "requests": requests,
            "tokens_in_window": tokens1 - tokens0,
            "attempted": len(requests), "failed": failed}


def ttft_ms(requests):
    """First token minus DUE time, for the requests that got one."""
    return [1e3 * (r["t_first"] - r["due"]) for r in requests
            if r["t_first"] is not None]


def end_to_end(record):
    reqs = record["requests"]
    ttft = ttft_ms(reqs)
    return {"ttft_p90_ms": util.tail(ttft, len(reqs) - len(ttft), 90),
            "tpot_p90_ms": util.percentile(serving.tpot_ms(reqs), 90)}


def details(record):
    reqs = record["requests"]
    ttft = ttft_ms(reqs)
    tpot = serving.tpot_ms(reqs)
    late = [1e3 * (r["sent"] - r["due"]) for r in reqs]
    open_at_close = sum(1 for r in reqs if not r["ok"])
    return {"requests_due": len(reqs), "finished": len(reqs) - open_at_close,
            "ttft_ms": util.percentiles(ttft),
            "tpot_ms": util.percentiles(tpot),
            "tpot_samples": len(tpot),
            "gen_late_ms_max": max(late, default=None),
            "decode_tok_per_s": record["tokens_in_window"] / record["window_s"]}
