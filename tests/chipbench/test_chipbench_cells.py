"""Whole runs at tiny sizes on the CPU, through `run_cell` (everything after
the harness's look for a chip). Every cell here is added the way a later PR
adds one: new files beside the benchmark's own (in a copy of its directory)
and new entries in a manifest, no file that exists touched. Also: `correct` comes out false when the timed
path is broken underneath, and when the system runs in the next precision
down."""
import json
import os
import shutil

import jax
import pytest

from chipbench import run
from chipbench.harness import manifest, tracing
from chipbench.trace import reduce as tr

TINY_LM = {
    "family": "transformer_lm", "hidden_size": 64, "ffn_dim": 128,
    "num_attention_heads": 4, "num_hidden_layers": 2, "vocab_size": 512,
    "max_position_embeddings": 128, "dtype": "bfloat16",
    "server": {"max_batch": 4, "num_blocks": 65, "max_len": 128},
    # limits read at this size on the CPU (seeds 1-6, 16 requests of 24-48
    # tokens): the mean gap is 4e-6..1.9e-5 for the bf16 server and
    # 2.6e-5..8.7e-5 in int8; the widest gap does not separate them here
    "check": {"sample_requests": 16, "served_gap_max": 0.02,
              "served_gap_mean": 0.000022, "control_weight_bits": 8}}
TINY_RESNET = {
    "family": "resnet_gluon", "layers": [1, 1], "channels": [16, 32, 64],
    "classes": 10, "image": 32,
    "trainer": {"optimizer": "sgd", "dtype": "bfloat16",
                "optimizer_params": {"learning_rate": 0.05, "momentum": 0.9,
                                     "wd": 0.0001}},
    # limits read at this size on the CPU (seeds 1-4): the median leaf's gap
    # is 0.012-0.027 for the bf16 step and 0.032-0.054 with fp8's mantissa;
    # the worst leaf swings (0.3-0.7 either way) and is held only loosely
    "check": {"loss_gap": 0.05, "first_grad_norm_gap": 1.5,
              "first_grad_norm_gap_median_leaf": 0.03,
              "first_grad_norm_gap_all_leaves": 0.05,
              "param_change_norm_gap": 1.5,
              "param_change_norm_gap_median_leaf": 0.03,
              "param_change_norm_gap_all_leaves": 0.05,
              "control_mantissa_bits": 3}}
MIXES = {
    "tiny_steps": {"generator": "train_steps", "batch": 32,
                   "resident_batches": 4, "check_steps": 3},
    "tiny_steps_dp4": {"generator": "train_steps", "batch": 32,
                       "resident_batches": 4, "check_steps": 3,
                       "mesh": {"dp": 4}},
    "tiny_open": {"generator": "open_loop", "rate_per_s": 12.0,
                  "schedule_seed": 1, "max_total_tokens": 128,
                  "prompt_tokens": {"kind": "lognormal", "median": 16,
                                    "sigma": 0.9, "min": 4, "max": 64},
                  "output_tokens": {"kind": "lognormal", "median": 8,
                                    "sigma": 0.6, "min": 2, "max": 32}},
    "tiny_closed": {"generator": "closed_loop", "clients": 4,
                    "schedule_seed": 1, "schedule_length": 24,
                    "prompt_tokens": {"kind": "uniform", "min": 4, "max": 16},
                    "output_tokens": {"kind": "uniform", "min": 8, "max": 24}},
    "toy_mix": {"generator": "toy_ticks", "ticks": 5}}
TOY_FAMILY = '''
class Counter:
    trace_slice_s = 1.0
    def __init__(self, cell):
        self.ticks = 0
    def host_spans(self, record, spans):
        return [("toy.tick", record["t0"], record["t0"] + record["window_s"])]
    def counters(self):
        return {"ticks": self.ticks}
    def check(self, record):
        return [{"name": "ticks_lost", "value": 5 - self.ticks, "limit": 0,
                 "ok": self.ticks == 5}]
    def close(self):
        pass
def build(cell):
    return Counter(cell)
'''
TOY_GENERATOR = '''
import time
def plan(cell):
    return {"ticks": cell.traffic["ticks"]}
def warm_up(system, plan_):
    pass
def run(system, plan_, seconds, timers):
    t0 = time.perf_counter()
    for _ in range(plan_["ticks"]):
        system.ticks += 1
    timers.fire(seconds)
    return {"t0": t0, "window_s": max(time.perf_counter() - t0, 1e-6),
            "attempted": plan_["ticks"], "failed": 0}
def end_to_end(record):
    return {"ticks_per_s": record["attempted"] / record["window_s"]}
def details(record):
    return {}
'''
TOY_READER = '''
def read(ctx):
    return float(ctx.counters["ticks"])
'''
BOOK = {
    "paths": ["chipbench"],
    "configs": [{"name": "tiny_lm", "file": "chipbench/configs/tiny_lm.json"},
                {"name": "tiny_resnet", "file": "chipbench/configs/tiny_resnet.json"},
                {"name": "toy", "file": "chipbench/configs/toy.json"}],
    "workloads": [
        {"name": "tiny_train", "config": "tiny_resnet", "traffic": "tiny_steps", "chips": 1},
        {"name": "tiny_train_dp4", "config": "tiny_resnet", "traffic": "tiny_steps_dp4", "chips": 4},
        {"name": "tiny_open", "config": "tiny_lm", "traffic": "tiny_open", "chips": 1},
        {"name": "tiny_closed", "config": "tiny_lm", "traffic": "tiny_closed", "chips": 1},
        {"name": "toy_cell", "config": "toy", "traffic": "toy_mix", "chips": 1}],
    "end_to_end": [
        {"name": "setup_s", "unit": "s"},
        {"name": "train_samples_per_s", "unit": "samples/s",
         "workloads": ["tiny_train", "tiny_train_dp4"]},
        {"name": "ttft_p90_ms", "unit": "ms", "workloads": ["tiny_open"]},
        {"name": "tpot_p90_ms", "unit": "ms", "workloads": ["tiny_open", "tiny_closed"]},
        {"name": "serve_tok_per_s", "unit": "tokens/s", "workloads": ["tiny_closed"]},
        {"name": "ticks_per_s", "unit": "1/s", "workloads": ["toy_cell"]}],
    "per_layer": [
        {"name": "toy.ticks", "unit": "1", "moves": "ticks_per_s", "workloads": ["toy_cell"]},
        {"name": "train_dispatch_ms_p50", "unit": "ms", "moves": "train_samples_per_s"},
        {"name": "device_idle_share.train", "unit": "%", "moves": "train_samples_per_s"},
        {"name": "allreduce_exposed_share", "unit": "%", "moves": "train_samples_per_s"},
        {"name": "batch_occupancy", "unit": "%", "moves": "tpot_p90_ms"},
        {"name": "kv_blocks_peak", "unit": "%", "moves": "tpot_p90_ms"},
        {"name": "decode_step_ms_p50", "unit": "ms", "moves": "tpot_p90_ms"},
        {"name": "queue_wait_p90_ms", "unit": "ms", "moves": "ttft_p90_ms"},
        {"name": "gen_late_p90_ms", "unit": "ms", "moves": "ttft_p90_ms"},
        {"name": "prefill_ms_p50", "unit": "ms", "moves": "ttft_p90_ms"}]}


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """A root with a manifest of its own and a copy of the benchmark's
    directory, to which cells, configurations, mixes, a family, a generator
    and a reader are added as new files: none takes the place of a file the
    benchmark has."""
    root = tmp_path_factory.mktemp("added")
    bench = root / "chipbench"
    shutil.copytree(manifest.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    new = {"configs/tiny_lm.json": json.dumps(TINY_LM),
           "configs/tiny_resnet.json": json.dumps(TINY_RESNET),
           "configs/toy.json": json.dumps({"family": "toy_counter"}),
           "families/toy_counter.py": TOY_FAMILY,
           "generators/toy_ticks.py": TOY_GENERATOR,
           "layer_metrics/toy.ticks.py": TOY_READER}
    new.update({"traffic/%s.json" % name: json.dumps(mix)
                for name, mix in MIXES.items()})
    for rel, text in new.items():
        assert not (bench / rel).exists(), rel     # added, never replaced
        (bench / rel).write_text(text)
    (root / "BENCHMARK.json").write_text(json.dumps(BOOK))

    def cell(name, seed=2**31 + 11, seconds=1.0):
        return manifest.cell(manifest.load(str(root)), name, root=str(root),
                             seed=seed, seconds=seconds)
    return cell


def go(cell, trace=False):
    return run.run_cell(cell, trace, jax.devices()[:cell.chips])


CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def stand_in_for_the_chip(monkeypatch):
    """A traced run without a chip: the recorded fixture for the profiler's
    trace, and the v5e's peaks for whatever device this is."""
    from chipbench.harness import device
    v5e = device.peaks("TPU v5 lite")
    monkeypatch.setattr(tracing, "DeviceTrace", FixtureTrace)
    monkeypatch.setattr(device, "peaks", lambda kind: v5e)


def test_a_toy_cell_family_generator_and_reader_added_as_files_run(added, monkeypatch):
    res = go(added("toy_cell"))
    assert set(res) == CONTRACT_KEYS and res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "ticks_per_s"}
    stand_in_for_the_chip(monkeypatch)
    traced = go(added("toy_cell"), trace=True)
    assert traced["metrics"] == {"toy.ticks": {"value": 5.0, "unit": "1"}}
    assert set(traced) == CONTRACT_KEYS | {"breakdown"}
    assert traced["device"]["busy_s"] > 0 and traced["device"]["window_s"] > 0
    assert len(traced["breakdown"]["device_ops"]) <= 10
    # the toy family's own host span labels the fixture's idle gaps
    assert [k for k, _ in traced["breakdown"]["idle_gaps"]] == ["none"]


class FixtureTrace:
    """Stands in for the profiler on a machine without a device plane: the
    recorded fixture, reduced by the real reduction."""

    def __init__(self, directory, slice_s):
        assert slice_s > 0      # the family's own `trace_slice_s`

    def arm(self, timers, seconds):
        pass

    def stop(self):
        with open(os.path.join(manifest.BENCH_DIR, "trace", "fixture.json")) as f:
            return tr.reduce(json.load(f)["planes"])


def test_training_cell_runs_and_agrees_with_its_reference(added, monkeypatch):
    res = go(added("tiny_train"))
    assert set(res) == CONTRACT_KEYS
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "train_samples_per_s"}
    assert res["metrics"]["train_samples_per_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    stand_in_for_the_chip(monkeypatch)
    traced = go(added("tiny_train", seconds=0.5), trace=True)
    assert {"train_dispatch_ms_p50", "device_idle_share.train"} <= set(traced["metrics"])
    assert traced["metrics"]["device_idle_share.train"]["value"] == pytest.approx(58.5)
    # 0.3 ms in an all-reduce over 4.5 ms of programs, in the fixture
    assert traced["metrics"]["allreduce_exposed_share"]["value"] == pytest.approx(100 * 0.3 / 4.5)


def test_a_mesh_cell_feeds_batches_already_spread_over_its_chips(added):
    """Data-parallel over four (virtual) chips: the resident batches lie by
    their rows over the mesh before the window, the leaves on all four, and
    the step agrees with the reference, whose rows are spread alike."""
    cell = added("tiny_train_dp4", seconds=0.3)
    family = cell.module("families", "resnet_gluon")
    trainer = family.build(cell)
    assert all(len(x.devices()) == 4 and len(y.devices()) == 4
               and x.sharding.shard_shape(x.shape)[0] == 32 // 4
               for x, y in zip(trainer.xs, trainer.ys))
    res = go(cell)
    assert res["correct"] is True and res["device"]["count"] == 4
    assert res["metrics"]["train_samples_per_s"]["value"] > 0


@pytest.mark.parametrize("name,expect", [
    ("tiny_open", {"setup_s", "ttft_p90_ms", "tpot_p90_ms"}),
    ("tiny_closed", {"setup_s", "serve_tok_per_s", "tpot_p90_ms"})])
def test_serving_cells_run_and_agree_with_their_reference(added, monkeypatch,
                                                          name, expect):
    res = go(added(name))
    assert set(res) == CONTRACT_KEYS
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == expect
    assert all(m["value"] > 0 for m in res["metrics"].values())
    stand_in_for_the_chip(monkeypatch)
    traced = go(added(name), trace=True)
    want = {"batch_occupancy", "kv_blocks_peak", "decode_step_ms_p50"}
    if name == "tiny_open":
        want |= {"queue_wait_p90_ms", "gen_late_p90_ms", "prefill_ms_p50"}
    assert want <= set(traced["metrics"])
    assert 0 < traced["metrics"]["batch_occupancy"]["value"] <= 100


def test_a_step_that_returns_its_state_unchanged_is_not_correct(added, monkeypatch):
    from mxnet_tpu.parallel import TrainStep
    real = TrainStep.__call__

    def frozen(self, x, y):
        """Computes the loss and throws the update away."""
        if self._step_fn is None:
            return real(self, x, y)         # the first call builds and runs
        keep = jax.tree.map(lambda v: v + 0, (self._grad_vals,
                                              self._nograd_vals, self._opt_state))
        loss = real(self, x, y)
        self._grad_vals, self._nograd_vals, self._opt_state = keep
        return loss

    monkeypatch.setattr(TrainStep, "__call__", frozen)
    res = go(added("tiny_train", seconds=0.3))
    assert res["correct"] is False


def test_a_token_altered_where_it_is_produced_is_not_correct(added, monkeypatch):
    from mxnet_tpu.serving.engine import Engine
    real = Engine._append

    def altered(self, seq, token):
        return real(self, seq, (token + 1) % 512 if len(seq.tokens) % 5 == 0
                    else token)

    monkeypatch.setattr(Engine, "_append", altered)
    res = go(added("tiny_closed", seconds=0.5))
    assert res["correct"] is False


def test_the_lower_precision_fails_each_familys_comparison(added):
    """The control of each family at this size: the reference in the program's
    place, one precision down, against the limits the sound run passes; and
    the program's own int8 path in the server's case."""
    cell = added("tiny_train", seed=5)
    family = cell.module("families", "resnet_gluon")
    trainer = family.build(cell)
    trainer.first_steps()
    sound, control = trainer.check({}), trainer.control({})
    assert all(c["ok"] for c in sound)
    assert not all(c["ok"] for c in control)

    cell = added("tiny_closed", seed=5)
    family = cell.module("families", "transformer_lm")
    generator = cell.module("generators", "closed_loop")
    serving = cell.module("generators", "serving")
    plan = generator.plan(cell)["requests"]

    def served_by(**serve_options):
        """A fixed set of requests (not a fixed time, so the same tokens come
        out however busy this machine is), four at a time."""
        server = family.Server(cell, serve_options=serve_options)
        requests = []
        for i in range(0, 24, 4):
            batch = [(r, server.submit(r["prompt"], 40)) for r in plan[i:i + 4]]
            for r, h in batch:
                assert h.wait(120) and h.error is None
                requests.append(serving.request_record(h, 0.0, 0.0, 0.0,
                                                       r["prompt"]))
        return server, {"requests": requests}

    server, record = served_by()
    sound, control = server.check(record), server.control(record)
    assert all(c["ok"] for c in sound), sound
    assert not all(c["ok"] for c in control), control
    quantized, record = served_by(weight_quant="int8")
    assert quantized.counters()["weight_quant"] == "int8"
    low = quantized.check(record)
    assert not all(c["ok"] for c in low), low
    print("sound", sound[:2], "control", control[:2], "int8 server", low[:2])


def test_the_sweep_finds_the_knee_where_the_backlog_starts_to_grow(added):
    from chipbench import sweep
    reqs = [{"due": 0.1, "t_done": 0.3}, {"due": 0.4, "t_done": None},
            {"due": 0.6, "t_done": 0.9}, {"due": 0.95, "t_done": 1.4}]
    assert [sweep.backlog(reqs, t) for t in (0.05, 0.2, 0.5, 1.0)] == [0, 1, 1, 2]
    rows = [{"rate": r, "backlog_mid": m, "backlog_end": e}
            for r, m, e in ((2.0, 13, 25), (1.0, 5, 5), (1.5, 17, 11),
                            (2.5, 20, 20))]
    assert sweep.knee(rows) == 1.5          # 2.5 holds only after 2.0 grew
    assert sweep.knee(rows[:1]) is None
    # one tiny server over two rates, lowest first, drained between them
    lines = []
    got = sweep.sweep(added("tiny_open", seconds=0.5), [16.0, 8.0],
                      out=lines.append)
    assert [r["rate"] for r in got] == [8.0, 16.0] and len(lines) == 2
    assert [r["due"] for r in got] == [4, 8]
    assert all(r["failed"] == 0 and r["finished"] > 0 and
               r["backlog_end"] <= r["due"] and r["queue_wait_ms"] for r in got)
    assert got[1]["compilations"] == 0      # the first rate's window warmed it
