"""The three per-gap readers over `serving.token` spans (ISSUE 40):
`itl_p50_ms`, `itl_p99_ms`, `itl_prefill_stall_share`. Their arithmetic on
hand-made spans (a gap the window's edge cuts, a request's first token and a
program that records no such span are each left out); the manifest's three
entries, found BY NAME; a toy serving cell run whole reports all three."""
import json
import shutil

import jax
import pytest

from chipbench import run
from chipbench.harness import context, manifest, tokens

from test_chipbench_cells import MIXES, TINY_LM, stand_in_for_the_chip

NAMES = ["itl_p50_ms", "itl_p99_ms", "itl_prefill_stall_share"]
SERVING = ["opt6b7_batch_closed", "dsv3_batch_closed", "trinity_mixed_closed",
           "falconh1_chat_closed"]
#: the window: 100 ms from t0 = 2 s on the program's clock
RECORD = {"t0": 2.0, "window_s": 0.1}


def token(ts_ms, gap_ms, prefills=0, **attrs):
    """A served token read `ts_ms + gap_ms` into the window, its request's
    last one `ts_ms` into it."""
    return {"id": 0, "name": tokens.NAME, "ts": int(2e6 + 1e3 * ts_ms),
            "dur": int(1e3 * gap_ms), "parent": None, "trace": "r",
            "attrs": dict(attrs, position=7, prefills=prefills, ahead=1,
                          prefill_tokens=512 * prefills)}


def read(name, spans, cell="dsv3_batch_closed"):
    cell = manifest.cell(manifest.load(), cell)
    return cell.reader(name).read(context.Context(
        cell=cell, record=RECORD, counters={}, spans=spans, trace=None,
        peaks={}))


#: nine steps of 10 ms and one that waited for a 30 ms prefill
TEN = [token(5 * i, 10.0) for i in range(9)] + [token(50, 40.0, prefills=1)]
OTHERS = [
    {"id": 1, "name": "serving.decode", "ts": int(2e6), "dur": 9000,
     "parent": None, "trace": None, "attrs": {"batch": 4}},
    # a request's first token spans its prefill: no gap between two tokens
    token(1, 55.0, prefills=1, first=1, stamp_lag_us=40),
    # read after the window closed: the edge cuts the gap
    token(95, 10.0),
]


@pytest.mark.parametrize("spans, want", [
    (TEN, (10.0, 40.0 - 0.09 * 30.0, 100 * 30.0 / 130.0)),
    (TEN + OTHERS, (10.0, 40.0 - 0.09 * 30.0, 100 * 30.0 / 130.0)),
    # every gap held a prefill: none stands out from the median
    ([token(5 * i, 20.0, prefills=1) for i in range(4)], (20.0, 20.0, 0.0)),
    # two prefills in one gap count once, by what the gap exceeds the median
    ([token(0, 10.0), token(10, 10.0), token(20, 70.0, prefills=2)],
     (10.0, 70.0 - 0.02 * 60.0, 100 * 60.0 / 90.0)),
    # a program that records no `serving.token` (the parent's): nothing
    (OTHERS[:1], (None, None, None)),
    ([], (None, None, None)),
], ids=["one_stall_in_ten", "first_and_cut_left_out", "all_stalled",
        "two_prefills_one_gap", "the_parents_spans", "no_spans"])
def test_the_readers_arithmetic_on_hand_made_spans(spans, want):
    got = tuple(read(name, spans) for name in NAMES)
    assert got == tuple(pytest.approx(w) if w is not None else None
                        for w in want)


def test_a_gap_is_left_out_where_either_edge_of_the_window_cuts_it():
    """`run.window_spans` keeps what STARTED in the window, so a gap whose
    token before it came earlier never reaches a reader; one read after the
    window's end does, and the helper drops it."""
    inside, late = token(10, 10.0), token(95, 10.0)
    ctx = context.Context(cell=None, record=RECORD, counters={},
                          spans=[inside, late], trace=None, peaks={})
    assert tokens.gaps(ctx) == [(10.0, 0)]
    ends_with_it = token(90, 10.0)          # read as the window closes: kept
    ctx.spans = [ends_with_it]
    assert tokens.gaps(ctx) == [(10.0, 0)]


@pytest.mark.parametrize("name", NAMES)
def test_the_manifest_holds_the_entry_by_name(name):
    book = manifest.load()
    entry, = [m for m in book["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "%" if name.endswith("share") else "ms",
        "better": "lower", "source": "program_span", "layer": "serving loop",
        "moves": "tpot_p90_ms", "workloads": SERVING}
    for cell in book["workloads"]:
        read_there = name in [m["name"] for m in manifest.cell(
            book, cell["name"]).per_layer]
        assert read_there == (cell["name"] in SERVING)
    # a reader a metric, a file of its own
    assert manifest.cell(book, SERVING[0]).reader(name).read is not None


BOOK = {
    "paths": ["chipbench"],
    "configs": [{"name": "tiny_lm", "file": "chipbench/configs/tiny_lm.json"}],
    "workloads": [{"name": "tiny_closed", "config": "tiny_lm",
                   "traffic": "tiny_closed", "chips": 1}],
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "tpot_p90_ms", "unit": "ms"},
                   {"name": "serve_tok_per_s", "unit": "tokens/s"}],
    "per_layer": [{"name": n, "unit": "1", "moves": "tpot_p90_ms"}
                  for n in ["decode_step_ms_p50"] + NAMES]}


def test_a_toy_serving_cell_reports_all_three(tmp_path, monkeypatch):
    bench = tmp_path / "chipbench"
    shutil.copytree(manifest.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs" / "tiny_lm.json").write_text(json.dumps(TINY_LM))
    (bench / "traffic" / "tiny_closed.json").write_text(
        json.dumps(MIXES["tiny_closed"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(BOOK))
    cell = manifest.cell(manifest.load(str(tmp_path)), "tiny_closed",
                         root=str(tmp_path), seed=2**31 + 40, seconds=1.0)
    stand_in_for_the_chip(monkeypatch)
    traced = run.run_cell(cell, True, jax.devices()[:1])
    got = {n: traced["metrics"][n]["value"] for n in NAMES}
    assert traced["correct"] is True
    assert 0 < got["itl_p50_ms"] <= got["itl_p99_ms"]
    # four clients, each next request prefilled whole between two steps
    assert 0 < got["itl_prefill_stall_share"] < 100
    # a step's span is the interval less what follows it in a pass
    assert got["itl_p50_ms"] > 0.5 * traced["metrics"][
        "decode_step_ms_p50"]["value"]
