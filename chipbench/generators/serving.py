"""What the two serving generators share: warming every shape through the
server's own door, and turning the program's request objects into plain
records once the window has closed."""
import time


def warm_up(system, prompts):
    """Waves of 1, 2, ... `max_batch` requests at once, three tokens each,
    over prompts of every length the window will send: every prefill shape
    and every decode batch the window can form is compiled (or read from the
    cache) here, whatever way the program buckets them."""
    by_len = {}
    for p in prompts:
        by_len.setdefault(len(p), p)
    todo = sorted(by_len.values(), key=len)
    wave, i = 1, 0
    while wave <= system.max_batch or i < len(todo):
        n = min(wave, system.max_batch)
        handles = [system.submit(todo[(i + j) % len(todo)], 3) for j in range(n)]
        for h in handles:
            if not h.wait(1200.0) or h.error is not None:
                raise RuntimeError("warm-up request failed: %r" % (h.error,))
        i += n
        wave += 1


def request_record(handle, t0, due, sent, prompt, error=None):
    """A request as the readers see it: times in seconds from the window's
    start; `ok` only where it finished without error."""
    rec = {"due": due, "sent": sent, "n_prompt": len(prompt), "prompt": prompt,
           "ok": False, "served": [], "t_submit": None, "t_admit": None,
           "t_first": None, "t_done": None,
           "error": repr(error) if error else None}
    if handle is None:
        return rec
    rel = lambda t: None if t is None else t - t0
    finished = handle.wait(0)
    rec.update(t_submit=rel(handle.t_submit), t_admit=rel(handle.t_admit),
               t_first=rel(handle.t_client_first_token))
    if finished and handle.error is None:
        rec.update(ok=True, t_done=rel(handle.t_done),
                   served=[int(t) for t in handle.tokens[len(prompt):]])
    elif finished:
        rec["error"] = repr(handle.error)
    return rec


def tpot_ms(requests):
    """Per finished request with two tokens or more: (done - first token) over
    (tokens - 1), in ms."""
    return [1e3 * (r["t_done"] - r["t_first"]) / (len(r["served"]) - 1)
            for r in requests if r["ok"] and len(r["served"]) >= 2]


def sleep_until(t):
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))
