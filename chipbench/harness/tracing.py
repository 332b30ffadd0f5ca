"""Timers a generator fires from its own loop, and the profiler around the
last slice of the window."""
import os
import shutil
import time


class Timers:
    """Callbacks due at a time since the window's start; the generator calls
    `fire(now)` from its loop, so nothing here needs a thread."""

    def __init__(self):
        self._due = []

    def at(self, seconds, fn):
        self._due.append([seconds, fn])
        self._due.sort(key=lambda x: x[0])

    def fire(self, now):
        while self._due and self._due[0][0] <= now:
            self._due.pop(0)[1]()


SETTLE_S = 0.5


class DeviceTrace:
    """The jax profiler over the last `slice_s` seconds of the window. Python
    tracing is off: it would slow the host the trace is there to watch.

    Starting the profiler stalls the host for up to some tenths of a second
    (one traced training run read 0.2 s of idle device right after the start
    mark and 6.7 % idle for it, others 0.04 and 1.6 %, PERF.md Findings PR
    23), so it is started `SETTLE_S` before the slice and the reduction leaves
    that first stretch out: the slice measures the program, not the
    profiler's start."""

    def __init__(self, directory, slice_s):
        self.directory, self.slice_s = directory, slice_s
        self.started = self.ended = False

    def arm(self, timers, seconds):
        """The slice ends where the window does, while work is still in
        flight: a mark set later would count the drain as idle time."""
        timers.at(max(0.0, seconds - self.slice_s - SETTLE_S), self.start)
        timers.at(seconds, self.end)

    def start(self):
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.started = True
        self._mark("chipbench.window_start")

    def end(self):
        if self.started and not self.ended:
            self.ended = True
            self._mark("chipbench.window_end")

    def _mark(self, name):
        import jax
        with jax.profiler.TraceAnnotation(
                name, perf_counter_ns=time.perf_counter_ns()):
            time.sleep(0.0002)

    def stop(self):
        """Stop the profiler (once the window has closed); returns the
        reduced trace, or None where the slice never started."""
        import jax
        from chipbench.trace import reduce as tr
        if not self.started:
            return None
        self.end()
        jax.profiler.stop_trace()
        reduced = tr.reduce(tr.read_xplane(self.directory), SETTLE_S)
        shutil.rmtree(self.directory, ignore_errors=True)
        return reduced
