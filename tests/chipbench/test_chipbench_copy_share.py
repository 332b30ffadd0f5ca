"""`decode_copy_share` (PR 26): its arithmetic on hand-made reduced traces
and on the recorded fixture, and where the manifest reads it."""
import json
import os

import pytest

from chipbench.harness import context, manifest
from chipbench.trace import reduce as tr

MS = 1e-3


def _copy_share(trace):
    cell = manifest.cell(manifest.load(), "opt6b7_batch_closed")
    return cell.reader("decode_copy_share").read(context.Context(
        cell=cell, record={}, counters={}, spans=[], trace=trace, peaks={}))


@pytest.mark.parametrize("ops, programs_ms, share", [
    # copy.3 and a bare copy count; a fusion with "copy" in its name, a
    # copy-start / copy-done pair (asynchronous, beside compute) and every
    # other operation do not: 3.1 + 0.9 of 2 x 8 ms of programs
    ({"copy.3": 3.1 * MS, "copy": 0.9 * MS, "copy_add_fusion": 5 * MS,
      "copy-start.2": 1 * MS, "copy-done.2": 1 * MS, "fusion.7": 4 * MS},
     [8, 8], 25.0),
    ({"fusion.7": 4 * MS, "copy_add_fusion": 5 * MS}, [8], 0.0),
    ({"copy.11": 1 * MS}, [], None),            # no program in the slice
], ids=["copies", "none_left", "no_programs"])
def test_decode_copy_share_counts_plain_copies_over_program_time(
        ops, programs_ms, share):
    trace = {"ops": ops,
             "modules": [(i * 10 * MS, d * MS, "jit_serving_decode(1)")
                         for i, d in enumerate(programs_ms)]}
    got = _copy_share(trace)
    assert got == (pytest.approx(share) if share is not None else None)


def test_decode_copy_share_without_a_trace_and_on_the_fixture():
    assert _copy_share(None) is None            # an untraced run leaves it out
    path = os.path.join(manifest.BENCH_DIR, "trace", "fixture.json")
    with open(path) as f:
        reduced = tr.reduce(json.load(f)["planes"])
    # the fixture: copy.3 runs 1.0 ms, its two programs 4.5 ms
    assert _copy_share(reduced) == pytest.approx(100 * 1.0 / 4.5)


def test_the_manifest_reads_decode_copy_share_in_the_closed_cell_only():
    book = manifest.load()
    entry = [m for m in book["per_layer"] if m["name"] == "decode_copy_share"]
    assert len(entry) == 1 and book["per_layer"][-1] is entry[0]
    assert entry[0]["workloads"] == ["opt6b7_batch_closed"]
    assert entry[0]["moves"] == "tpot_p90_ms"
    assert entry[0]["layer"] == "engine step"
