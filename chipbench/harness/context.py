"""What a per-layer metric's reader is handed: the window's record, the
program's spans and counters, the reduced device trace (traced runs), the
cell with its configuration, the family's module (which holds the functions
that count the work's operations and bytes from shapes) and the chip's
peaks. A reader returns a number, or None where it finds nothing to read,
and the harness then leaves the metric out."""
import dataclasses
import statistics

from chipbench.trace import reduce as tr


@dataclasses.dataclass
class Context:
    cell: object
    record: dict
    counters: dict
    spans: list          # the program's spans inside the window
    trace: dict          # reduced device trace, or None
    peaks: dict
    family: object = None

    def named(self, name, batch_level=False):
        """The program's spans of one name in the window; `batch_level` keeps
        only those that carry a `batch` attribute (an engine step's own span,
        not the copies it makes per request)."""
        return [s for s in self.spans if s["name"] == name
                and (not batch_level or "batch" in s.get("attrs", {}))]

    def span_ms(self, name, batch_level=False):
        """Durations (ms) of those spans."""
        return [s["dur"] / 1e3 for s in self.named(name, batch_level)]

    def steps_in_trace(self, name, batch_level=False):
        """Those spans that lie in the traced slice, with the device seconds
        of the programs run under each: [(span, device_s)]."""
        if self.trace is None:
            return []
        out = []
        for s in self.named(name, batch_level):
            b = tr.to_trace_s(self.trace, s["ts"] / 1e6)
            e = b + s["dur"] / 1e6
            dev = tr.module_time_in(self.trace, b, e)
            if dev > 0:
                out.append((s, dev))
        return out

    def step_program(self):
        """(name, [device seconds]) of the compiled program that took most of
        the traced slice: the training step."""
        if self.trace is None:
            return None
        by_name = {}
        for _, d, name in self.trace["modules"]:
            by_name.setdefault(name, []).append(d)
        if not by_name:
            return None
        name = max(by_name, key=lambda n: sum(by_name[n]))
        return name, by_name[name]


def median(values):
    return statistics.median(values) if values else None
