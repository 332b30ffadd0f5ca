"""The yardstick's arithmetic on hand-made samples: percentiles and failed
requests, the spread rule, the trace reduction on the recorded fixture, and
the byte and FLOP functions against the figures of ISSUE 23 §3 and §6."""
import json
import os

import pytest

from chipbench.families import resnet_gluon, transformer_lm as lm
from chipbench.harness import device, manifest, util
from chipbench.trace import reduce as tr

MS = 1e-3


def test_percentile_interpolates_like_numpy():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert util.percentile(xs, 50) == 30.0
    assert util.percentile(xs, 90) == pytest.approx(46.0)
    assert util.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        util.percentile([], 50)


def test_failed_requests_count_as_the_largest_value():
    ttft = [float(v) for v in range(1, 10)]            # 9 answered
    assert util.tail(ttft, 0, 90) == pytest.approx(8.2)
    # one request in ten never answered: the p90 moves to the largest seen
    assert util.tail(ttft, 1, 90) == 9.0
    with pytest.raises(ValueError):
        util.tail([], 3, 90)


def test_spread_is_the_quartile_distance_over_the_median():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    # statistics.quantiles(n=4): q1 = 100.75, q3 = 104.25, median 102.5
    assert util.spread(vals) == pytest.approx(3.5 / 102.5)


@pytest.fixture(scope="module")
def reduced():
    path = os.path.join(manifest.BENCH_DIR, "trace", "fixture.json")
    with open(path) as f:
        return tr.reduce(json.load(f)["planes"])


def test_trace_busy_union_and_idle_share(reduced):
    assert reduced["window_s"] == pytest.approx(10 * MS)
    assert reduced["devices"] == 2
    # device 0: [1,1.5] + [2,5.5] + [8,9] + [9.2,9.5] + [10.5,11] = 5.8 ms;
    # device 1: 2.5 ms
    assert reduced["busy_s"] == pytest.approx(4.15 * MS)
    assert [round(d / MS, 3) for _, d in reduced["gaps"]] == [0.5, 2.5, 0.2, 1.0]


def test_the_slice_leaves_out_the_profilers_own_start():
    """With 1.5 ms of settling the slice starts at 2.5 ms of the fixture:
    device 0 is busy [2.5,5.5] + [8,9] + [9.2,9.5] + [10.5,11] = 4.8 ms of
    8.5, device 1 as before; the first 1.5 ms held 0.5 + 0.5 ms of work."""
    path = os.path.join(manifest.BENCH_DIR, "trace", "fixture.json")
    with open(path) as f:
        planes = json.load(f)["planes"]
    whole, cut = tr.reduce(planes), tr.reduce(planes, settle_s=1.5 * MS)
    assert cut["window_s"] == pytest.approx(8.5 * MS)
    assert whole["settle_idle_s"] == 0.0
    assert cut["settle_idle_s"] == pytest.approx(0.5 * MS)
    assert [round(d / MS, 3) for _, d in cut["gaps"]] == [2.5, 0.2, 1.0]
    # host spans are still placed by the first mark itself
    assert tr.to_trace_s(cut, 5.4 * MS) == tr.to_trace_s(whole, 5.4 * MS)
    with pytest.raises(ValueError):
        tr.reduce(planes, settle_s=10 * MS)


def test_memory_peak_is_the_sum_of_the_runtimes_two_disjoint_peaks():
    class Chip:
        def __init__(self, **stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    chips = [Chip(peak_bytes_in_use=3, peak_bytes_reserved=7, bytes_in_use=1,
                  bytes_reserved=7, num_allocs=9),
             Chip(peak_bytes_in_use=8, peak_bytes_reserved=1)]
    assert device.memory_peak_bytes(chips) == 10        # the fullest chip
    assert device.memory_stats(chips)[0] == {
        "peak_bytes_in_use": 3, "peak_bytes_reserved": 7, "bytes_in_use": 1,
        "bytes_reserved": 7, "footprint_bytes": 8}
    assert device.memory_peak_bytes([Chip(peak_bytes_in_use=5)]) == 5


def test_trace_op_time_by_name_is_clipped_to_the_window(reduced):
    ops = {k: round(v / MS, 3) for k, v in reduced["ops"].items()}
    # a TPU trace names an operation by its whole HLO instruction
    assert ops == {"fusion.1": 1.5, "conv.2": 3.0, "copy.3": 1.0, "late.4": 0.5,
                   "all-reduce.7": 0.3}
    assert reduced["collective_s"] == pytest.approx(0.3 * MS)
    assert tr.top(reduced["ops"], 2)[0][0] == "conv.2"


def test_trace_programs_and_step_device_time(reduced):
    assert [n for _, _, n in reduced["modules"]] == ["jit_step(1)", "jit_step(1)"]
    assert tr.module_time_in(reduced, 1.5 * MS, 6 * MS) == pytest.approx(3.5 * MS)


def test_gap_attribution_by_the_shortest_covering_host_span(reduced):
    # the program's clock: perf_counter 5 ms is the trace's 1 ms
    assert tr.to_trace_s(reduced, 5.4 * MS) == pytest.approx(1.4 * MS)
    spans = [("serving.decode", 1.4 * MS, 2.1 * MS), ("generator", 5 * MS, 9 * MS),
             ("serving.prefill", 6 * MS, 7 * MS)]
    by = {k: round(v / MS, 3) for k, v in tr.label_gaps(reduced, spans).items()}
    assert by == {"serving.decode": 0.5, "serving.prefill": 2.5, "none": 1.2}


def test_a_trace_without_marks_or_devices_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([{"name": "/device:TPU:0", "lines": []}])


def _config(name):
    return manifest.read_json(os.path.join(manifest.BENCH_DIR, "configs", name))


def test_opt_bytes_match_the_issues_reckoning():
    cfg = _config("opt-6.7b.json")
    d, ffn, n = cfg["hidden_size"], cfg["ffn_dim"], cfg["num_hidden_layers"]
    assert 4 * d * d + 2 * d * ffn == 201_326_592            # 201.3 M per layer
    assert lm.kv_bytes_per_token(cfg) == n * 16384     # 16 KB a layer
    per_step = lm.weight_bytes_per_step(cfg)
    assert per_step == 2 * (n * 201_326_592 + d * cfg["vocab_size"])
    # the issue's figures are for 16 layers: 6.9 GB of matrices and head a
    # step, 256 KB of K and V a token
    at16 = dict(cfg, num_hidden_layers=16)
    assert 6.8e9 < lm.weight_bytes_per_step(at16) < 6.9e9
    assert lm.kv_bytes_per_token(at16) == 262144
    assert lm.decode_step_min_bytes(at16, 16 * 1024) \
        - lm.weight_bytes_per_step(at16) == 16 * 1024 * 262144


def test_resnet50_flops_match_the_published_count():
    cfg = _config("resnet50_v1.json")
    fwd = resnet_gluon.forward_flops_per_image(cfg)
    assert 7.6e9 < fwd < 8.3e9          # 3.8-4.1 G multiply-adds
    assert resnet_gluon.train_flops_per_step(cfg, 256) == 3 * 256 * fwd
    assert 22e9 < resnet_gluon.train_flops_per_step(cfg, 1) < 25e9


def test_peaks_are_keyed_by_device_kind_and_unknown_raises():
    p = device.peaks("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["int8_ops_per_s"], p["hbm_bytes_per_s"],
            p["hbm_bytes"]) == (197e12, 393e12, 819e9, 16e9)
    assert "Google Cloud documentation, TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")
