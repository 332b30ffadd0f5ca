"""The traffic generators: deterministic in the seed, the same work for every
seed, and an open loop that times from when a request was due."""
import threading
import time

from chipbench.generators import closed_loop, open_loop, schedule
from chipbench.harness import manifest, tracing

LM = {"vocab_size": 500}
OPEN = {"generator": "open_loop", "rate_per_s": 40.0, "schedule_seed": 3,
        "prompt_tokens": {"kind": "lognormal", "median": 16, "sigma": 0.9,
                          "min": 4, "max": 64},
        "output_tokens": {"kind": "lognormal", "median": 8, "sigma": 0.6,
                          "min": 2, "max": 32},
        "max_total_tokens": 80}
CLOSED = {"generator": "closed_loop", "clients": 3, "schedule_seed": 3,
          "schedule_length": 12,
          "prompt_tokens": {"kind": "uniform", "min": 4, "max": 16},
          "output_tokens": {"kind": "uniform", "min": 2, "max": 6}}


def cell(traffic, seed, seconds=1.0):
    return manifest.Cell(name="t", chips=1, config=LM, traffic=traffic,
                         end_to_end=[], per_layer=[], seed=seed, seconds=seconds)


class Handle:
    """A request as the program's front door returns it, finished by a fake
    server thread."""

    def __init__(self, prompt, max_new):
        self.prompt, self.max_new = prompt, max_new
        self.t_submit = time.perf_counter()
        self.t_admit = self.t_client_first_token = self.t_done = None
        self.error, self.tokens = None, None
        self._event = threading.Event()

    def wait(self, timeout=None):
        return self._event.wait(timeout)


class FakeServer:
    """Serves one request at a time, `service_s` each; `stall` = (at, for):
    the server stops once for a while, as a compile or a long prefill would."""
    max_batch = 2

    def __init__(self, service_s=0.002, stall=None):
        self.service_s, self.stall = service_s, stall
        self.queue, self.lock, self.generated = [], threading.Lock(), 0
        self.stop = False
        self.t_start = time.perf_counter()
        self.thread = threading.Thread(target=self.loop, daemon=True)
        self.thread.start()

    def submit(self, prompt, max_new):
        h = Handle(prompt, max_new)
        with self.lock:
            self.queue.append(h)
        return h

    def tokens_generated(self):
        return self.generated

    def loop(self):
        while not self.stop:
            if self.stall and time.perf_counter() - self.t_start >= self.stall[0]:
                time.sleep(self.stall[1])
                self.stall = None
            with self.lock:
                h = self.queue.pop(0) if self.queue else None
            if h is None:
                time.sleep(0.0005)
                continue
            h.t_admit = time.perf_counter()
            time.sleep(self.service_s)
            h.t_client_first_token = time.perf_counter()
            h.tokens = list(h.prompt) + [1] * h.max_new
            self.generated += h.max_new - 1
            h.t_done = time.perf_counter()
            h._event.set()

    def close(self):
        self.stop = True
        self.thread.join(timeout=5)


def test_plans_are_deterministic_in_the_seed_and_differ_between_seeds():
    a, b = open_loop.plan(cell(OPEN, 2**31 + 5)), open_loop.plan(cell(OPEN, 2**31 + 5))
    assert a == b
    c = open_loop.plan(cell(OPEN, 6))
    assert a != c
    assert closed_loop.plan(cell(CLOSED, 9)) == closed_loop.plan(cell(CLOSED, 9))


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    plans = [open_loop.plan(cell(OPEN, s))["requests"] for s in (1, 2, 12345)]
    sizes = [sorted((len(r["prompt"]), r["max_new"]) for r in p) for p in plans]
    assert sizes[0] == sizes[1] == sizes[2]
    assert len(plans[0]) == 40                       # rate x seconds
    gaps = [sorted(round(b["due"] - a["due"], 9) for a, b in zip(p, p[1:]))
            for p in plans]
    assert [len(g) for g in gaps] == [39] * 3
    assert all(0 < r["due"] < 1.0 for p in plans for r in p)
    assert [r["max_new"] for r in plans[0]] != [r["max_new"] for r in plans[1]]
    # every prompt is a prompt of its own
    assert len({tuple(r["prompt"]) for r in plans[0]}) == len(plans[0])
    for p in plans:
        assert all(len(r["prompt"]) + r["max_new"] <= 80 for r in p)


def test_quantiles_follow_the_stated_distribution():
    qs = schedule.quantiles({"kind": "lognormal", "median": 256, "sigma": 0.9,
                             "min": 16, "max": 1536}, 101)
    assert abs(qs[50] - 256) < 1e-6 and qs[0] >= 16 and qs[-1] <= 1536
    us = schedule.quantiles({"kind": "uniform", "min": 32, "max": 128}, 4)
    assert us == [44.0, 68.0, 92.0, 116.0]


def run_open(stall):
    c = cell(OPEN, 7, seconds=1.0)
    srv = FakeServer(stall=stall)
    try:
        rec = open_loop.run(srv, open_loop.plan(c), c.seconds, tracing.Timers())
    finally:
        srv.close()
    return rec


def test_open_loop_times_from_due_time_so_a_stall_shows_in_later_requests():
    smooth, stalled = run_open(None), run_open((0.3, 0.3))
    for rec in (smooth, stalled):
        assert rec["attempted"] == 40 and rec["failed"] == 0
        # the sender kept to its schedule whatever the server did
        assert max(r["sent"] - r["due"] for r in rec["requests"]) < 0.05
    p90 = lambda rec: open_loop.end_to_end(rec)["ttft_p90_ms"]
    assert p90(smooth) < 30.0
    assert p90(stalled) > 100.0
    # requests due during the stall waited for it: timed from due, not from
    # when the server got round to them
    waited = [r for r in stalled["requests"] if 0.32 <= r["due"] <= 0.45]
    assert waited and all(r["t_first"] - r["due"] > 0.04 for r in waited)


def test_closed_loop_sends_the_next_request_when_the_last_returns():
    c = cell(CLOSED, 4, seconds=0.5)
    srv = FakeServer(service_s=0.01)
    try:
        rec = closed_loop.run(srv, closed_loop.plan(c), c.seconds, tracing.Timers())
    finally:
        srv.close()
    done = [r for r in rec["requests"] if r["ok"]]
    assert rec["failed"] == 0 and len(done) >= 10
    # never more than `clients` outstanding: with one server thread at 10 ms
    # a request, three clients complete about 50 requests in half a second
    assert len(rec["requests"]) <= len(done) + 3
    out = closed_loop.end_to_end(rec)
    assert out["serve_tok_per_s"] == rec["tokens_in_window"] / rec["window_s"]


def test_timers_fire_once_in_order():
    fired, t = [], tracing.Timers()
    t.at(2.0, lambda: fired.append("b"))
    t.at(1.0, lambda: fired.append("a"))
    t.fire(0.5)
    assert fired == []
    t.fire(2.5)
    t.fire(3.0)
    assert fired == ["a", "b"]


def test_the_traced_slice_ends_where_the_window_does():
    """The profiler starts its settling time before the slice."""
    calls, timers = [], tracing.Timers()
    assert tracing.SETTLE_S == 0.5
    trace = tracing.DeviceTrace("unused", slice_s=4.0)
    trace.start = lambda: calls.append("start")
    trace.end = lambda: calls.append("end")
    trace.arm(timers, 51.0)
    timers.fire(46.4)
    assert calls == []
    timers.fire(46.5)
    assert calls == ["start"]
    timers.fire(51.0)
    assert calls == ["start", "end"]
