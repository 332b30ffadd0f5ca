"""BENCHMARK.json and the files it names, found by name."""
import dataclasses
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def read_json(path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of `workloads`, with its files read and this run's
    arguments."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # metric entries this cell reports
    per_layer: list
    seed: int = 0
    seconds: float = 0.0
    bench_dir: str = BENCH_DIR

    def find(self, kind, filename):
        """The file `<kind>/<filename>` of the benchmark's directory: a later
        PR adds a family, a generator, a mix or a reader as a file there and
        edits none that is there."""
        path = os.path.join(self.bench_dir, kind, filename)
        if not os.path.exists(path):
            raise FileNotFoundError("no %s/%s under %s"
                                    % (kind, filename, self.bench_dir))
        return path

    def module(self, kind, name):
        from chipbench.harness import util
        return util.load_file_module(self.find(kind, name + ".py"),
                                     "chipbench_%s_%s" % (kind, name.replace(".", "_")))

    def reader(self, metric):
        """A per-layer metric's reader: `layer_metrics/<metric>.py`, or, for
        a quantity split by what it moves (`device_idle_share.train`,
        `device_idle_share.serve`), the one file named before the last dot."""
        try:
            return self.module("layer_metrics", metric)
        except FileNotFoundError:
            if "." not in metric:
                raise
            return self.module("layer_metrics", metric.rsplit(".", 1)[0])


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def reads_in(metric, cell_name, end_to_end):
    """Is this per-layer metric read in this cell? In the cells it lists, or,
    where it lists none, in every cell that reports the end-to-end metric it
    moves."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    moved = {m["name"]: m for m in end_to_end}[metric["moves"]]
    return applies(moved, cell_name)


def cell(manifest, name, root=ROOT, **run_args):
    """Resolve a cell: its configuration's file as the manifest names it, its
    traffic mix as `traffic/<traffic>.json` under the benchmark's directory,
    the first of the manifest's `paths`."""
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise KeyError("no workload %r in BENCHMARK.json (have: %s)"
                       % (name, ", ".join(sorted(by_name))))
    w = by_name[name]
    cfg = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    bench_dir = os.path.join(root, manifest["paths"][0])
    return Cell(
        name=name, chips=int(w["chips"]),
        config=read_json(os.path.join(root, cfg["file"])),
        traffic=read_json(os.path.join(bench_dir, "traffic",
                                       w["traffic"] + ".json")),
        end_to_end=[m for m in manifest["end_to_end"] if applies(m, name)],
        per_layer=[m for m in manifest["per_layer"]
                   if reads_in(m, name, manifest["end_to_end"])],
        bench_dir=bench_dir, **run_args)
