"""From a profiler trace to the numbers the per-layer metrics read.

`read_xplane` turns the `.xplane.pb` the jax profiler writes into plain
lists (so the arithmetic below can be checked on a small hand-made fixture,
`fixture.json`); `reduce` computes, over the traced slice of the window:

  busy_s       union of the intervals in which an operation ran on a device,
               averaged over the device planes
  window_s     length of the traced slice: from `settle_s` after the harness's
               first mark (the profiler's own start is left out) to its second
  settle_idle_s  device 0's idle seconds in that first stretch, which no
               metric reads: printed, to show what the profiler's start cost
  ops          seconds by operation name (device 0's "XLA Ops" line)
  modules      every run of a compiled program on device 0: (start_s, dur_s,
               name), start on the trace's clock
  gaps         the idle gaps of device 0: (start_s, dur_s)
  collective_s seconds device 0 spent in collective operations on its "XLA Ops"
               line: that line runs one operation at a time, so no compute ran
               meanwhile (the part of a collective that overlaps compute is on
               the "Async XLA Ops" line and is not counted)
  clock        (perf_counter_ns, trace_ns) of the first mark, which puts the
               program's host spans on the trace's clock

All of it is read from the lines a TPU's device plane carries ("XLA Ops",
"XLA Modules"); the harness's marks are `TraceAnnotation`s on a host plane.
"""
import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK_START = "chipbench.window_start"
MARK_END = "chipbench.window_end"
MIN_GAP_S = 20e-6
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def read_xplane(trace_dir):
    """[{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns,
    stats]]}]}] from the newest .xplane.pb under `trace_dir`."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for e in line.events:
                if not device and not e.name.startswith("chipbench."):
                    continue
                stats = dict(e.stats) if not device else {}
                events.append([e.name, float(e.start_ns), float(e.duration_ns),
                               stats])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _mark(planes, name):
    for plane in planes:
        for line in plane["lines"]:
            for ev in line["events"]:
                if ev[0] == name:
                    return ev
    raise ValueError("the trace holds no %r mark" % name)


def reduce(planes, settle_s=0.0):
    start, end = _mark(planes, MARK_START), _mark(planes, MARK_END)
    lo, hi = start[1] + settle_s * 1e9, end[1]
    if hi <= lo:
        raise ValueError("the traced slice is shorter than its settling time")
    devices = sorted((p for p in planes if p["name"].startswith("/device:")
                      and _line(p, OPS_LINE)), key=lambda p: p["name"])
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy = []
    for plane in devices:
        merged = clip(union([e[1], e[1] + e[2]] for e in _line(plane, OPS_LINE)),
                      lo, hi)
        busy.append(merged)
    first = devices[0]
    settling = clip(union([e[1], e[1] + e[2]] for e in _line(first, OPS_LINE)),
                    start[1], lo)
    ops = {}
    for name, s, d, _ in _line(first, OPS_LINE):
        part = min(s + d, hi) - max(s, lo)
        if part > 0:
            ops[short_name(name)] = ops.get(short_name(name), 0.0) + part * 1e-9
    modules = [(s * 1e-9, d * 1e-9, name)
               for name, s, d, _ in _line(first, MODULES_LINE)
               if lo <= s + d / 2 <= hi]
    gaps, at = [], lo
    for s, e in busy[0] + [[hi, hi]]:
        if (s - at) * 1e-9 >= MIN_GAP_S:
            gaps.append((at * 1e-9, (s - at) * 1e-9))
        at = max(at, e)
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(sum(e - s for s, e in b) for b in busy)
            * 1e-9 / len(busy),
            "devices": len(devices), "ops": ops, "modules": modules,
            "gaps": gaps,
            "settle_idle_s": (lo - start[1] - sum(e - s for s, e in settling))
            * 1e-9,
            "collective_s": sum(v for k, v in ops.items()
                                if k.startswith(COLLECTIVES)),
            "clock": (int(start[3]["perf_counter_ns"]), start[1])}


def short_name(event_name):
    """A TPU trace names an operation by its whole HLO instruction
    ("%fusion.7 = bf16[...] fusion(...)"); the name before the "=" is enough
    to find it in the program's HLO."""
    return event_name.split(" = ")[0].lstrip("%")[:80]


def to_trace_s(reduced, perf_counter_s):
    """A host time (time.perf_counter seconds) on the trace's clock."""
    perf_ns, trace_ns = reduced["clock"]
    return (perf_counter_s * 1e9 - perf_ns + trace_ns) * 1e-9


def label_gaps(reduced, host_spans):
    """Seconds of device-0 idle time by what the host was doing: each gap goes
    to the shortest host span (name, start_s, end_s on the trace's clock)
    that covers its midpoint, or to "none"."""
    by_label = {}
    for s, d in reduced["gaps"]:
        mid = s + d / 2
        covering = [(e - b, name) for name, b, e in host_spans if b <= mid <= e]
        label = min(covering)[1] if covering else "none"
        by_label[label] = by_label.get(label, 0.0) + d
    return by_label


def module_time_in(reduced, start_s, end_s):
    """Device seconds of the compiled programs whose midpoint lies in a host
    interval (trace clock): the device time of one engine step."""
    return sum(d for s, d, _ in reduced["modules"] if start_s <= s + d / 2 <= end_s)


def top(by_name, n=10):
    return [[k, v] for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])[:n]]
