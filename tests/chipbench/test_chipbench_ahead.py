"""`decode_ahead_share` (PR 30): its arithmetic on hand-made spans, and the
manifest entry as the issue states it."""
import pytest

from chipbench.harness import context, manifest


def dispatch(i, **attrs):
    return {"id": i, "name": "serving.decode.dispatch", "ts": 1000 * i,
            "dur": 300, "parent": None, "attrs": dict(attrs, batch=16)}


def _ahead_share(spans, cell="opt6b7_batch_closed"):
    cell = manifest.cell(manifest.load(), cell)
    return cell.reader("decode_ahead_share").read(context.Context(
        cell=cell, record={}, counters={}, spans=spans, trace=None, peaks={}))


@pytest.mark.parametrize("marks, share", [
    ([1, 1, 1, 1], 100.0),
    ([0, 0, 0], 0.0),
    # an admission's bubble, then nineteen steps from the device's tokens
    ([0] + [1] * 19, 95.0),
    ([], None),
], ids=["all_ahead", "none_ahead", "one_in_twenty", "no_spans"])
def test_decode_ahead_share_counts_the_dispatches_marked_ahead(marks, share):
    spans = [dispatch(i, ahead=a) for i, a in enumerate(marks)]
    # other spans, and another span's `ahead`, are not a dispatch
    spans.append({"id": 99, "name": "serving.decode", "ts": 0, "dur": 9,
                  "parent": None, "attrs": {"batch": 16, "ahead": 1}})
    assert _ahead_share(spans) == (pytest.approx(share)
                                   if share is not None else None)


def test_a_program_that_marks_no_dispatch_says_nothing():
    """The parent's spans: dispatches with no `ahead`. The reader returns
    nothing and the line leaves the metric out."""
    assert _ahead_share([dispatch(i) for i in range(5)]) is None
    assert _ahead_share([dispatch(i) for i in range(5)],
                        cell="dsv3_batch_closed") is None


def test_the_manifest_reads_decode_ahead_share_in_both_serving_cells():
    book = manifest.load()
    entry = [m for m in book["per_layer"] if m["name"] == "decode_ahead_share"]
    assert len(entry) == 1 and book["per_layer"][-1] is entry[0]
    assert entry[0] == {
        "name": "decode_ahead_share", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "serving loop",
        "moves": "tpot_p90_ms",
        "workloads": ["opt6b7_batch_closed", "dsv3_batch_closed"]}
    for name in ("opt6b7_batch_closed", "dsv3_batch_closed"):
        cell = manifest.cell(book, name)
        assert "decode_ahead_share" in [m["name"] for m in cell.per_layer]
    for name in ("resnet50_train", "resnet50_train_dp4"):
        cell = manifest.cell(book, name)
        assert "decode_ahead_share" not in [m["name"] for m in cell.per_layer]
