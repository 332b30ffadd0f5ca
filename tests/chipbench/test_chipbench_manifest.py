"""BENCHMARK.json against the contract's limits, and every name in it
resolved to its file."""
import os
import re

import pytest

from chipbench.harness import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden_size|intermediate|latent|state_size|_proj|_dim$|_rank$|head_dim|head_size|expand|experts_per_tok)")


@pytest.fixture(scope="module")
def book():
    return manifest.load()


def test_top_level_keys_and_limits(book):
    assert set(book) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert isinstance(book["run_seconds"], int) and 1 <= book["run_seconds"] <= 51
    assert 1 <= len(book["paths"]) <= 16
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert len(book["command"]) <= 32
    for word in book["command"]:
        assert not word.startswith("/") and ".." not in word
    # the whole check must fit the driver's time with all 24 cells
    runs = 2 + 14 * 24
    assert runs * (book["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_every_name_unit_and_line_is_within_the_contracts_alphabet(book):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in book[k]]
    for n in names + [w["traffic"] for w in book["workloads"]]:
        assert NAME.match(n), n
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [x["name"] for x in book[k]]
        assert len(ns) == len(set(ns))
    metrics = book["end_to_end"] + book["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for x in book["configs"] + book["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"] and "\t" not in x["why"]


def test_entries_have_exactly_the_contracts_keys(book):
    for c in book["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16 and 1 <= len(c["source"]) <= 200
        assert not any(WIDTHS.search(k) for k in c["reduced"]), c["reduced"]
    for w in book["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in book["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in book["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200


def test_cells_configs_and_metrics_hang_together(book):
    cells = {w["name"]: w for w in book["workloads"]}
    configs = {c["name"] for c in book["configs"]}
    assert {w["config"] for w in cells.values()} == configs
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)
    e2e = {m["name"]: m for m in book["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.1
    for m in book["end_to_end"] + book["per_layer"]:
        for w in m.get("workloads", []):
            assert w in cells, (m["name"], w)
    for name in cells:
        reported = [m for m in book["end_to_end"] if manifest.applies(m, name)]
        assert len(reported) >= 2, name          # setup_s and one more
        layer = [m for m in book["per_layer"] if manifest.applies(m, name)
                 and manifest.applies(e2e[m["moves"]], name)]
        assert layer, name
    for m in book["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        # every cell a per-layer metric is read in reports the metric it moves
        for name in cells:
            if "workloads" in m and name in m["workloads"]:
                assert manifest.applies(e2e[m["moves"]], name), (m["name"], name)


def test_every_file_the_manifest_names_resolves(book):
    files = [c["file"] for c in book["configs"]]
    assert len(files) == len(set(files))
    for c in book["configs"]:
        assert any(c["file"].startswith(p + "/") for p in book["paths"])
        cfg = manifest.read_json(os.path.join(manifest.ROOT, c["file"]))
        assert cfg["reduced"] == c["reduced"]
        assert len(cfg["source"]) <= 200
        for key in ("source", "reduced", "assumed", "deployment", "family", "check"):
            assert key in cfg, (c["name"], key)
    for w in book["workloads"]:
        cell = manifest.cell(book, w["name"])
        assert os.path.exists(cell.find("families", cell.config["family"] + ".py"))
        assert os.path.exists(cell.find("generators",
                                        cell.traffic["generator"] + ".py"))
        reference = os.path.join(manifest.BENCH_DIR, "reference",
                                 cell.config["family"] + ".py")
        assert os.path.exists(reference)
        assert "mxnet_tpu" not in open(reference).read().replace(
            "`mxnet_tpu", "").split('"""', 2)[2]
        for m in cell.per_layer:
            assert hasattr(cell.reader(m["name"]), "read")
    with pytest.raises(KeyError):
        manifest.cell(book, "no_such_cell")


def test_a_split_quantity_shares_the_reader_named_before_the_last_dot(book):
    cell = manifest.cell(book, book["workloads"][0]["name"])
    train, serve = (cell.reader("device_idle_share." + k)
                    for k in ("train", "serve"))
    assert train.__file__ == serve.__file__ == cell.find(
        "layer_metrics", "device_idle_share.py")
    # a reader of the metric's full name comes first
    assert cell.reader("mxu_share.train").__file__.endswith("mxu_share.train.py")
    for missing in ("no_such_metric", "no_such.metric"):
        with pytest.raises(FileNotFoundError):
            cell.reader(missing)


def test_every_cell_keeps_within_its_configurations_lengths(book):
    for w in book["workloads"]:
        cell = manifest.cell(book, w["name"])
        mix, cfg = cell.traffic, cell.config
        if "prompt_tokens" in mix:
            assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
                <= mix.get("max_total_tokens", cfg["max_position_embeddings"])
            assert mix.get("clients", 0) <= cfg["server"]["max_batch"]
