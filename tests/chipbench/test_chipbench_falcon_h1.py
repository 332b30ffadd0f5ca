"""The `falcon_h1_lm` family added as files (ISSUE 37): a toy cell of it runs
whole on the CPU stand-in for the chip, is `correct` against its plain
reference and reports the cache's and the step's metrics; the control (the
reference one precision down) fails the comparison; the configuration's bytes
by count from shapes (10.51 GB, the three pools); `decode_step_min_bytes`,
`prefill_flops` and each new reader's arithmetic on hand-made input; the
manifest's new entries, found BY NAME; the configuration file holds the
published widths."""
import json
import shutil
import types

import jax
import pytest

from chipbench import run
from chipbench.harness import context, manifest
from chipbench.trace import reduce as tr

from test_chipbench_cells import stand_in_for_the_chip

#: two layers, 4 heads on 2, 4 state heads, state 16, 2 groups, chunk 8. At 64
#: wide the published multipliers leave every product near nothing (they are
#: the 5,120-wide model's): the toy's own keep the rows, the mixers' outputs
#: and the logits of the order of 1, so that bf16 and int8 can be told apart
TINY = {
    "family": "falcon_h1_lm", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "mamba_d_ssm": 64, "mamba_n_heads": 4, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "num_hidden_layers": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-5, "rope_theta": 100000000000,
    "attention_in_multiplier": 1, "attention_out_multiplier": 6.0,
    "embedding_multiplier": 50.0, "key_multiplier": 1.0,
    "lm_head_multiplier": 6.0, "mlp_multipliers": [6.0, 6.0],
    "ssm_in_multiplier": 1.0, "ssm_out_multiplier": 6.0,
    "ssm_multipliers": [6.0, 6.0, 6.0, 6.0, 3.0],
    "dtype": "bfloat16", "state_dtype": "float32",
    "server": {"max_batch": 4, "max_len": 128},
    # limits read at this size on the CPU (seeds 1-6, 24 requests of 40
    # served tokens): the bf16 server's mean gap is at most 7.1e-5, the int8
    # control's at least 2.4e-4; the 99th percentile at most 1.4e-3 against
    # at least 1.07e-2; the widest gap (0.0159 against 0.0274) is held loosely
    "check": {"sample_requests": 16, "served_gap_max": 0.05,
              "served_gap_p99": 0.004, "served_gap_mean": 0.00013,
              "control_weight_bits": 8}}
MIX = {"generator": "closed_loop", "clients": 4, "schedule_seed": 1,
       "schedule_length": 24,
       "prompt_tokens": {"kind": "uniform", "min": 8, "max": 72},
       "output_tokens": {"kind": "uniform", "min": 8, "max": 40}}
NEW = ["decode_hbm_share.ssm", "state_slots_peak", "cache_state_share",
       "ssm_step_hbm_share"]
SHARED = ["decode_copy_share", "decode_ahead_share", "prefill_device_share",
          "prefill_mxu_share"]
CELL = "falconh1_chat_closed"
BOOK = {
    "paths": ["chipbench"],
    "configs": [{"name": "tiny", "file": "chipbench/configs/tiny_falcon.json"}],
    "workloads": [{"name": "tiny_falcon_closed", "config": "tiny",
                   "traffic": "tiny_falcon_closed", "chips": 1}],
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "tpot_p90_ms", "unit": "ms"},
                   {"name": "serve_tok_per_s", "unit": "tokens/s"}],
    "per_layer": [{"name": n, "unit": "1", "moves": "tpot_p90_ms"}
                  for n in ["batch_occupancy", "kv_blocks_peak",
                            "decode_step_ms_p50"] + SHARED + NEW]}


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """A root with a manifest of its own and a copy of the benchmark's
    directory, to which the toy cell's configuration and mix are added."""
    root = tmp_path_factory.mktemp("added_falcon")
    bench = root / "chipbench"
    shutil.copytree(manifest.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs" / "tiny_falcon.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny_falcon_closed.json").write_text(json.dumps(MIX))
    (root / "BENCHMARK.json").write_text(json.dumps(BOOK))

    def cell(seed=2**31 + 11, seconds=1.0):
        return manifest.cell(manifest.load(str(root)), "tiny_falcon_closed",
                             root=str(root), seed=seed, seconds=seconds)
    return cell


def real_config():
    return manifest.read_json(
        manifest.ROOT + "/chipbench/configs/falcon-h1-34b.json")


def test_a_toy_cell_of_the_family_is_correct_and_reports_its_metrics(
        added, monkeypatch):
    res = run.run_cell(added(), False, jax.devices()[:1])
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "serve_tok_per_s", "tpot_p90_ms"}
    stand_in_for_the_chip(monkeypatch)
    traced = run.run_cell(added(), True, jax.devices()[:1])
    got = traced["metrics"]
    # the fixture's device trace has no serving program: the readers of the
    # device's time find nothing of theirs and leave their metric out
    assert {"batch_occupancy", "kv_blocks_peak", "decode_step_ms_p50",
            "decode_copy_share", "decode_ahead_share", "state_slots_peak",
            "cache_state_share"} <= set(got)
    assert got["state_slots_peak"]["value"] == 100.0       # 4 clients, 4 slots
    # a slot is 2 x (4 x 16 x 16 x 4 + 3 x 128 x 2) B, a K/V block 16 x 2 x 2 x
    # 2 x 16 x 2 B: short sequences hold more in their state than in blocks
    assert 30 < got["cache_state_share"]["value"] < 90
    assert got["decode_ahead_share"]["value"] > 50


def test_the_lower_precision_fails_the_familys_comparison(added):
    cell = added(seed=5)
    family = cell.module("families", "falcon_h1_lm")
    generator = cell.module("generators", "closed_loop")
    serving = cell.module("generators", "serving")
    plan = generator.plan(cell)["requests"]
    server = family.Server(cell)
    requests = []
    for i in range(0, 24, 4):       # a fixed set of requests, not a fixed time
        batch = [(r, server.submit(r["prompt"], 40)) for r in plan[i:i + 4]]
        for r, h in batch:
            assert h.wait(120) and h.error is None
            requests.append(serving.request_record(h, 0.0, 0.0, 0.0, r["prompt"]))
    counters = server.counters()
    assert counters["pool_kinds"] == ["full", "state"] and not counters["paged"]
    assert counters["pool_dtype"] == "bfloat16"
    assert counters["state_dtype"] == "float32"
    assert counters["state_num_slots"] == 4 == counters["state_high_water_slots"]
    assert counters["kv_num_blocks"] == 4 * 8
    assert counters["state_bytes_per_sequence"] \
        == 2 * (4 * 16 * 16 * 4 + 3 * 128 * 2)
    assert counters["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 2
    record = {"requests": requests}
    sound, control = server.check(record), server.control(record)
    assert all(c["ok"] for c in sound), sound
    assert not all(c["ok"] for c in control), control
    print("sound", sound[:3], "control", control[:3])


def test_the_configurations_bytes_by_count_from_shapes(added):
    family = added().module("families", "falcon_h1_lm")
    real = real_config()
    weights = jax.eval_shape(lambda: family.make_weights(real, 1))
    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(weights))
    assert 10.50e9 < total < 10.52e9
    assert family.weight_bytes(real) == pytest.approx(total, rel=1e-5)
    # a layer, as the issue counts it: mixer + attention + feed-forward + norms
    mixer = 5120 * 9248 + 4096 * 5120 + 4 * 5120 + 5120 + 3 * 32 + 4096
    assert mixer == 68351072
    assert family.layer_params(real) == mixer + 31457280 + 330301440 + 10240
    kv, state, conv = family.pool_bytes(real)
    assert kv == 6 * (64 * 64 + 1) * 16 * 2048
    assert state == 6 * 65 * 4194304 and conv == 6 * 65 * 3 * 5120 * 2
    assert (round(kv / 1e9, 2), round(state / 1e9, 2), round(conv / 1e6)) \
        == (0.81, 1.64, 12)
    # at rest: 77 % of the chip's 16 GB... of 16.9e9 B, as the issue counts
    assert round((total + kv + state + conv) / 1e9, 2) == 12.96
    # what the engine makes is what was counted
    from mxnet_tpu import serving
    from mxnet_tpu.serving import kv_cache
    cfg = family.program_config(real, 1024)
    params = {"embed": jax.ShapeDtypeStruct((1, 1), "bfloat16")}
    spec = serving.FalconH1LM(params, cfg).cache_spec()
    assert spec.kinds == ("full", "state") and spec.ring("state", 16) == 1
    assert spec.state_shape == (2, 256, 16, 128)
    assert spec.state_bytes() * 65 == state + conv
    shapes = jax.eval_shape(lambda: kv_cache.PagedKVCache.of(
        spec, block_size=16, num_blocks=(64 * 64 + 1, 65)).arrays())
    assert sum(a.size * a.dtype.itemsize for a in shapes) == kv + state + conv


def test_decode_step_min_bytes_and_prefill_flops_by_hand(added):
    family = added().module("families", "falcon_h1_lm")
    cfg = dict(TINY)
    # wq, wo; wk, wv; w_in (z 64 | x 64 | B 32 | C 32 | dt 4), w_out; the three
    # of the feed-forward
    matrices = 2 * 64 * 64 + 2 * 64 * 32 + 64 * 196 + 64 * 64 + 3 * 64 * 128
    assert family._size(family.layer_shapes(cfg)) == matrices
    every_step = 2 * (2 * matrices + 64 * 512)
    assert family.matrix_bytes_per_step(cfg) == every_step
    state, conv = family.state_bytes_per_layer(cfg)
    assert (state, conv) == (4 * 16 * 16 * 4, 3 * 128 * 2)
    assert family.kv_bytes_per_token_layer(cfg) == 2 * 2 * 16 * 2
    # 3 rows holding 100 tokens: their states there and back, two layers
    assert family.decode_step_min_bytes(cfg, 3, 100) \
        == every_step + 2 * 3 * 2 * (state + conv) + 100 * 2 * 128
    # 16 rows, chunks of 8: the matrices; 1 + ... + 16 keys a query head pair
    # of products; the taps; per chunk C B^T (2 groups of 16), its product
    # with x (4 heads of 16), the chunk's state and the carried one
    scan = 2 * (2 * 8 * 8 * (2 * 16 + 4 * 16) + 4 * 8 * 4 * 16 * 16)
    assert family.prefill_flops(cfg, 16) == 2 * (
        2 * 16 * matrices + (16 * 17 // 2) * 4 * 4 * 16 + 2 * 16 * 4 * 128
        + scan)
    assert family.prefill_flops(cfg, 16, pairs=7) == family.prefill_flops(cfg, 16)
    # a bucket under the chunk is one chunk of its own length
    assert family.prefill_flops(cfg, 4) == 2 * (
        2 * 4 * matrices + 10 * 4 * 4 * 16 + 2 * 4 * 4 * 128
        + 2 * 4 * 4 * 96 + 4 * 4 * 4 * 16 * 16)
    assert family.ssm_step_bytes(cfg, 3) \
        == 4 * 3 * 2 * (2 * 16 * 32 + 2 * 16 + 3 * 32)
    assert family.cache_state_share(cfg, 10, 4, 16) == pytest.approx(
        100 * 4 * 2 * (state + conv)
        / (4 * 2 * (state + conv) + 10 * 16 * 2 * 128))
    # at the published widths: the issue's estimates, by the same functions
    real = real_config()
    assert family.kv_bytes_per_token_layer(real) == 2048
    assert family.state_bytes_per_layer(real) == (4194304, 30720)
    assert 5.15e9 < 6 * family._size(family.layer_shapes(real)) * 2 < 5.17e9
    least = family.decode_step_min_bytes(real, 64, 64 * 450)
    assert least == family.matrix_bytes_per_step(real) \
        + 2 * 64 * 6 * (4194304 + 30720) + 64 * 450 * 6 * 2048
    assert 11.3e9 < least < 11.5e9              # 13.9 ms at 819 GB/s
    assert 3.2e9 < 2 * 64 * 6 * 4194304 < 3.3e9
    assert 2.6e12 < family.prefill_flops(real, 512) < 2.75e12
    assert family.ssm_step_bytes(real, 64) == 4 * 64 * 2 * (
        2 * 256 * 2048 + 512 + 3 * 2048)
    # a sequence of 450 tokens: 82 % of what it holds is its state
    assert 81 < family.cache_state_share(real, 29, 1) < 84


def span(name, ts, dur, **attrs):
    return {"name": name, "ts": ts, "dur": dur, "attrs": attrs}


def test_the_new_readers_arithmetic_on_hand_made_input(added):
    cell = added()
    family = cell.module("families", "falcon_h1_lm")
    trace = {"modules": [(0.0100, 0.002, "jit_serving_decode(1)"),
                         (0.0200, 0.002, "jit_serving_decode(1)"),
                         (0.0300, 0.001, "jit_serving_prefill(2)")],
             "clock": (0, 0),
             "ops": {"ssm_step.1": 0.0005, "ssm_step": 0.0003, "fusion.3": 1.0}}
    spans = [span("serving.decode", 9000, 4000, batch=2, live_max=50,
                  live_full=80, state_rows=2),
             span("serving.decode", 9010, 4000, position=30),
             span("serving.decode", 19000, 4000, batch=3, live_max=51,
                  live_full=120, state_rows=3),
             span("serving.prefill", 29500, 2000, length=40, bucket=64)]
    counters = {"state_high_water_slots": 3, "state_num_slots": 4,
                "kv_blocks_at_high_water": [20, 3], "block_size": 16}
    peaks = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e10}
    ctx = context.Context(cell=cell, record={}, spans=spans, trace=trace,
                          family=family, counters=counters, peaks=peaks)
    real, tr.to_trace_s = tr.to_trace_s, lambda reduced, s: s
    try:
        shares = [100.0 * family.decode_step_min_bytes(TINY, rows, full)
                  / 1e9 / 0.002 for rows, full in ((2, 80), (3, 120))]
        assert cell.reader("decode_hbm_share.ssm").read(ctx) \
            == pytest.approx(sum(shares) / 2)
        assert cell.reader("prefill_mxu_share").read(ctx) == pytest.approx(
            100.0 * family.prefill_flops(TINY, 64) / 1e10 / 0.001)
        # two decode programs of two layers each, the median step's rows,
        # over the seconds of the operations named for the kernel
        assert cell.reader("ssm_step_hbm_share").read(ctx) == pytest.approx(
            100.0 * 2 * 2 * family.ssm_step_bytes(TINY, 2.5) / 1e9 / 0.0008)
    finally:
        tr.to_trace_s = real
    assert cell.reader("state_slots_peak").read(ctx) == pytest.approx(75.0)
    assert cell.reader("cache_state_share").read(ctx) \
        == pytest.approx(family.cache_state_share(TINY, 20, 3, 16))
    # a program without the family's spans and counters (the parent commit),
    # and a cell of another family: the readers find nothing and return None
    bare = context.Context(cell=cell, record={}, spans=[
        span("serving.decode", 9000, 4000, batch=2, live_max=50),
        span("serving.prefill", 29500, 2000, prompt_len=40)], trace=trace,
        family=types.SimpleNamespace(),
        counters={"kv_blocks_at_high_water": [20, 9]}, peaks=peaks)
    for name in NEW:
        assert cell.reader(name).read(bare) is None, name
    untraced = context.Context(cell=cell, record={}, spans=spans, trace=None,
                               family=family, counters={}, peaks={})
    for name in NEW:
        assert cell.reader(name).read(untraced) is None, name
    # the XLA fallback runs no kernel: nothing named for it in the trace
    no_kernel = context.Context(
        cell=cell, record={}, spans=spans, family=family, counters=counters,
        trace=dict(trace, ops={"fusion.3": 1.0}), peaks=peaks)
    assert cell.reader("ssm_step_hbm_share").read(no_kernel) is None


def test_the_manifest_gains_the_configuration_the_cell_and_its_readers():
    """By NAME: a later PR appends its own entries behind these."""
    book = manifest.load()
    metrics = {m["name"]: m for m in book["per_layer"]}
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL], name
        assert metrics[name]["moves"] == "tpot_p90_ms"
        assert metrics[name]["unit"] == "%"
    assert [metrics[n]["layer"] for n in NEW] == [
        "kernels", "cache manager", "cache manager", "kernels"]
    assert [metrics[n]["source"] for n in NEW] == [
        "device_trace", "program_counter", "program_counter", "device_trace"]
    for name in SHARED:
        assert CELL in metrics[name]["workloads"], name
    for m in book["end_to_end"]:
        if m["name"] in ("serve_tok_per_s", "tpot_p90_ms"):
            assert CELL in m["workloads"]
    work = {w["name"]: w for w in book["workloads"]}[CELL]
    assert work == {"name": CELL, "config": "falcon-h1-34b",
                    "traffic": "chat_closed_64", "chips": 1,
                    "why": work["why"]}
    assert len(work["why"]) <= 200
    real = real_config()
    entry = {c["name"]: c for c in book["configs"]}["falcon-h1-34b"]
    assert entry["reduced"] == real["reduced"] == list(real["published"]) \
        == ["num_hidden_layers"]
    assert entry["source"] == real["source"] and len(entry["why"]) <= 200
    assert entry["file"] == "chipbench/configs/falcon-h1-34b.json"
    where = {m["name"]: [w["name"] for w in book["workloads"]
                         if manifest.reads_in(m, w["name"], book["end_to_end"])]
             for m in book["per_layer"]}
    for name in NEW + SHARED + ["batch_occupancy", "kv_blocks_peak",
                                "decode_step_ms_p50", "device_idle_share.serve"]:
        assert CELL in where[name], name
    for name in ("decode_hbm_share", "decode_hbm_share.moe",
                 "decode_hbm_share.swa", "moe_rows_per_expert",
                 "kv_window_blocks_peak", "mxu_share.train"):
        assert CELL not in where[name], name
    cell = manifest.cell(book, CELL)
    assert cell.chips == 1 and cell.config["family"] == "falcon_h1_lm"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "serve_tok_per_s",
                                                   "tpot_p90_ms"]
    for name in NEW:
        assert cell.reader(name).read.__module__.endswith(
            name.replace(".", "_"))
    mix = cell.traffic
    assert (mix["generator"], mix["clients"], mix["max_total_tokens"],
            mix["schedule_length"], mix["schedule_seed"]) \
        == ("closed_loop", 64, 1024, 256, 20260930)
    assert mix["prompt_tokens"] == {"kind": "uniform", "min": 64, "max": 512}
    assert mix["output_tokens"] == {"kind": "uniform", "min": 128, "max": 512}
    assert cell.config["server"] == {"max_batch": 64, "max_len": 1024}
    # one four-chip cell of six, as before
    assert [w["chips"] for w in book["workloads"]].count(4) == 1


def test_the_configurations_file_holds_the_published_widths():
    real = real_config()
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        pytest.skip("no catalog here")
    row = next(r for r in rows if r["name"] == "Falcon-H1-34B-Instruct")
    assert real["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in real["reduced"]:
            assert real["published"][key] == value
        else:
            assert real[key] == value, key
    assert (real["hidden_size"], real["num_attention_heads"],
            real["num_key_value_heads"], real["head_dim"],
            real["intermediate_size"]) == (5120, 20, 4, 128, 21504)
    assert (real["mamba_d_ssm"], real["mamba_n_heads"], real["mamba_d_head"],
            real["mamba_d_state"], real["mamba_n_groups"], real["mamba_d_conv"],
            real["mamba_chunk_size"]) == (4096, 32, 128, 256, 2, 4, 128)
    assert (real["vocab_size"], real["rope_theta"],
            real["num_hidden_layers"]) == (261120, 1e11, 6)
    assert real["published"] == {"num_hidden_layers": 72}
    assert real["state_dtype"] == "float32" and real["dtype"] == "bfloat16"
    for key in ("deployment", "assumed"):
        assert real[key]
    # every limit is written beside the readings it came from
    check = real["check"]
    assert set(check["reasons"]) == {"served_gap_mean", "served_gap_p99",
                                     "served_gap_max"}
    assert all("control" in r and "sound" in r
               for r in check["reasons"].values())
    assert check["control_weight_bits"] == 8
    # the program's configuration reads every multiplier as published
    family = manifest.cell(manifest.load(), CELL).module("families",
                                                         "falcon_h1_lm")
    cfg = family.program_config(real, 1024)
    assert (cfg.embedding_multiplier, cfg.lm_head_multiplier,
            cfg.key_multiplier, cfg.attention_out_multiplier,
            cfg.ssm_in_multiplier, cfg.ssm_out_multiplier) == (
        5.656854249492381, 0.0078125, 0.011048543456039804, 0.0375, 0.25,
        0.08838834764831845)
    assert cfg.ssm_multipliers == tuple(real["ssm_multipliers"])
    assert cfg.mlp_multipliers == tuple(real["mlp_multipliers"])
    assert (cfg.chunk, cfg.conv_taps, cfg.d_in_proj) == (128, 4, 9248)
