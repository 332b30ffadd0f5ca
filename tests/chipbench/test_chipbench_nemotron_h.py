"""The `nemotron_h_lm` family added as files (ISSUE 42): a toy cell of it runs
whole on the CPU stand-in for the chip, is `correct` against its plain
reference and reports the cache's, the experts' and the step's metrics; the
control (the reference one precision down) fails the comparison; the cut's
7.852 GB and the pools by count from the family's functions, each counting by
the PATTERN's letters; each new reader's arithmetic on hand-made input (a
value, `None` without its spans, `None` untraced, never over 100); the
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

#: every letter of the pattern; 16 state heads of 16 in 2 groups (a group's 8
#: heads fill the 128 lanes side by side, as the published 8 of 64 fill 512),
#: 8 experts of which rank 0 of 2 holds 4, top-2
TINY = {
    "family": "nemotron_h_lm", "hidden_size": 64,
    "hybrid_override_pattern": "MEM*E", "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 16, "mamba_head_dim": 16, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 64,
    "n_routed_experts": 4, "n_routed_experts_published": 8,
    "expert_parallel": 2, "expert_rank": 0, "num_experts_per_tok": 2,
    "n_group": 1, "topk_group": 1, "routed_scaling_factor": 2.5,
    "vocab_size": 512, "layer_norm_epsilon": 1e-5,
    "dtype": "bfloat16", "state_dtype": "float32",
    "server": {"max_batch": 4, "max_len": 128},
    # limits read at this size on the CPU (seeds 1-6, 24 requests of 40
    # served tokens): the bf16 server's mean gap is at most 2.4e-5, the int8
    # control's at least 5.9e-5; the 99th percentile at most 9.4e-4 against
    # at least 1.9e-3; the widest gap at most 3.9e-3 against at least 8.2e-3
    # (the logits are small: 64 wide under N(0, 0.02) matrices)
    "check": {"sample_requests": 16, "served_gap_max": 0.006,
              "served_gap_p99": 0.0014, "served_gap_mean": 0.00004,
              "control_weight_bits": 8}}
MIX = {"generator": "closed_loop", "clients": 4, "schedule_seed": 1,
       "schedule_length": 24,
       "prompt_tokens": {"kind": "uniform", "min": 8, "max": 72},
       "output_tokens": {"kind": "uniform", "min": 8, "max": 40}}
NEW = ["decode_hbm_share.ssm_moe", "ssm_step_hbm_share.hybrid",
       "moe_rows_per_expert.hybrid", "state_slots_peak.hybrid",
       "cache_state_share.hybrid"]
SHARED = ["decode_copy_share", "decode_ahead_share", "prefill_device_share",
          "prefill_mxu_share", "moe_load_max_over_mean"]
CELL = "nemotron3_reason_closed"
BOOK = {
    "paths": ["chipbench"],
    "configs": [{"name": "tiny",
                 "file": "chipbench/configs/tiny_nemotron.json"}],
    "workloads": [{"name": "tiny_nemotron_closed", "config": "tiny",
                   "traffic": "tiny_nemotron_closed", "chips": 1}],
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
    root = tmp_path_factory.mktemp("added_nemotron")
    bench = root / "chipbench"
    shutil.copytree(manifest.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs" / "tiny_nemotron.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny_nemotron_closed.json").write_text(
        json.dumps(MIX))
    (root / "BENCHMARK.json").write_text(json.dumps(BOOK))

    def cell(seed=2**31 + 11, seconds=1.0):
        return manifest.cell(manifest.load(str(root)), "tiny_nemotron_closed",
                             root=str(root), seed=seed, seconds=seconds)
    return cell


def real_config():
    return manifest.read_json(
        manifest.ROOT + "/chipbench/configs/nemotron-3-nano-30b-a3b.json")


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
            "decode_copy_share", "decode_ahead_share",
            "moe_load_max_over_mean", "moe_rows_per_expert.hybrid",
            "state_slots_peak.hybrid", "cache_state_share.hybrid"} <= set(got)
    assert "decode_hbm_share.ssm_moe" not in got
    assert got["state_slots_peak.hybrid"]["value"] == 100.0   # 4 clients, 4 slots
    # two state layers of 2 x (16 x 16 x 16 x 4 + 3 x 320 x 2) B a slot against
    # ONE attention layer's blocks of 16 x 2 x 2 x 16 x 2 B, reserved to max_total
    assert 60 < got["cache_state_share.hybrid"]["value"] < 100
    # 4 rows x top-2 x half the experts held / (4 held x 2 expert layers)
    assert 0.2 < got["moe_rows_per_expert.hybrid"]["value"] <= 1.0
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    assert got["decode_ahead_share"]["value"] > 50


def test_the_lower_precision_fails_the_familys_comparison(added):
    cell = added(seed=5)
    family = cell.module("families", "nemotron_h_lm")
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
    assert counters["layers_by_kind"] == {"full": [3], "state": [0, 2]}
    assert counters["pool_dtype"] == "bfloat16"
    assert counters["state_dtype"] == "float32"
    assert counters["state_shape"] == [2, 16, 128]
    assert counters["state_num_slots"] == 4 == counters["state_high_water_slots"]
    assert counters["kv_num_blocks"] == 4 * 8
    assert counters["state_bytes_per_sequence"] \
        == 2 * (16 * 16 * 16 * 4 + 3 * 320 * 2)
    # one attention layer of five keeps keys and values
    assert counters["kv_bytes_per_token"] == 1 * 2 * 2 * 16 * 2
    assert len(counters["moe_expert_tokens"]) == 2 \
        and len(counters["moe_expert_tokens"][0]) == 4
    record = {"requests": requests}
    sound, control = server.check(record), server.control(record)
    assert all(c["ok"] for c in sound), sound
    assert not all(c["ok"] for c in control), control
    print("sound", sound[:3], "control", control[:3])


def test_the_cuts_bytes_and_pools_by_count_from_the_familys_functions(added):
    family = added().module("families", "nemotron_h_lm")
    real = real_config()
    weights = jax.eval_shape(lambda: family.make_weights(real, 1))
    leaves = jax.tree.leaves(weights)
    assert sum(x.size for x in leaves) == family.param_count(real) \
        == 3_926_018_560
    assert round(family.weight_bytes(real) / 1e9, 3) == 7.852
    # a layer of each letter, as the issue counts them
    assert family.layer_params(real, "M") == 38_744_896
    assert family.layer_params(real, "*") == 23_399_040
    assert family.expert_bytes(real) == 2 * 9_977_856
    assert family.layer_params(real, "E") == 20_302_592 + 64 * 9_977_856
    assert [family.layers_of(real, c) for c in "ME*"] == [6, 5, 2]
    # the pools, each over its own kind's layers
    kv, state, conv = family.pool_bytes(real)
    assert kv == 2 * (128 * 192 + 1) * 16 * 1024
    assert state == 6 * 129 * 2_097_152 and conv == 6 * 129 * 3 * 6144 * 2
    assert (round(kv / 1e9, 2), round((state + conv) / 1e9, 2)) == (0.81, 1.65)
    # at rest: 64 % of the chip's 16 GB, as the issue counts
    assert round((family.weight_bytes(real) + kv + state + conv) / 1e9, 1) \
        == 10.3
    # what the engine makes is what was counted
    from mxnet_tpu import serving
    from mxnet_tpu.serving import kv_cache
    cfg = family.program_config(real, 3072)
    params = {"embed": jax.ShapeDtypeStruct((1, 1), "bfloat16")}
    spec = serving.NemotronHLM(params, cfg).cache_spec()
    assert spec.kinds == ("full", "state")
    assert spec.layers_of("state") == (0, 2, 4, 7, 9, 11)
    assert spec.layers_of("full") == (5, 12)
    assert spec.layers_of("none") == (1, 3, 6, 8, 10)
    assert spec.state_shape == (8, 128, 512) and spec.q_group == 16
    assert spec.state_bytes() * 129 == state + conv
    assert spec.values_per_token() * 2 == 2 * family.kv_bytes_per_token_layer(
        real) == 2048
    shapes = jax.eval_shape(lambda: kv_cache.PagedKVCache.of(
        spec, block_size=16, num_blocks=(128 * 192 + 1, 129)).arrays())
    assert sum(a.size * a.dtype.itemsize for a in shapes) == kv + state + conv


def test_decode_step_min_bytes_and_prefill_flops_by_hand(added):
    family = added().module("families", "nemotron_h_lm")
    cfg = dict(TINY)
    # M: w_in (z 256 | x 256 | B 32 | C 32 | dt 16), w_out; E: router, the
    # shared expert's two, 4 held experts' two each; *: wq, wo, wk, wv
    mixer = 64 * 592 + 256 * 64
    shared = 64 * 8 + 2 * 64 * 64
    experts = 4 * 2 * 64 * 32
    attn = 2 * 64 * 64 + 2 * 64 * 32
    assert family._size(family.layer_shapes(cfg, "M")) == mixer
    assert family._size(family.layer_shapes(cfg, "E")) == shared + experts
    assert family._size(family.layer_shapes(cfg, "*")) == attn
    every_step = 2 * (2 * mixer + 2 * shared + attn + 64 * 512)
    assert family.matrix_bytes_per_step(cfg) == every_step
    state, conv = family.state_bytes_per_layer(cfg)
    assert (state, conv) == (256 * 16 * 4, 3 * 320 * 2)
    assert family.kv_bytes_per_token_layer(cfg) == 2 * 2 * 16 * 2
    assert family.expert_bytes(cfg) == 2 * 64 * 32 * 2
    # 3 rows holding 100 tokens that touched 5 (expert layer, held expert)
    # pairs: TWO state layers there and back, ONE attention layer
    assert family.decode_step_min_bytes(cfg, 3, 100, 5) \
        == every_step + 5 * 2 * 64 * 32 * 2 + 2 * 3 * 2 * (state + conv) \
        + 100 * 1 * 128
    # 16 rows, chunks of 8, 7 routed pairs: the dense matrices; the pairs; on
    # the one attention layer 1 + ... + 16 keys a query head pair of products;
    # on the two state layers the taps and per chunk C B^T (2 groups of 16),
    # its product with x (16 heads of 16), the chunk's state and the carried
    scan = 2 * (2 * 8 * 8 * (2 * 16 + 16 * 16) + 4 * 8 * 16 * 16 * 16)
    assert family.prefill_flops(cfg, 16, pairs=7) == 2 * 16 * (
        2 * mixer + 2 * shared + attn) + 7 * 2 * 2 * 64 * 32 \
        + (16 * 17 // 2) * 4 * 4 * 16 + 2 * (2 * 16 * 4 * 320 + scan)
    # no pairs given: by expectation, rows x top-2 x half the experts held
    assert family.prefill_flops(cfg, 16) == family.prefill_flops(
        cfg, 16, pairs=16 * 2 * 4 / 8)
    assert family.ssm_step_bytes(cfg, 3) \
        == 4 * 3 * 2 * (2 * 16 * 128 + 2 * 16 + 3 * 128)
    assert family.cache_state_share(cfg, 10, 4, 16) == pytest.approx(
        100 * 4 * 2 * (state + conv)
        / (4 * 2 * (state + conv) + 10 * 16 * 1 * 128))
    # at the published widths: the issue's estimates, by the same functions
    real = real_config()
    assert family.kv_bytes_per_token_layer(real) == 1024
    assert family.state_bytes_per_layer(real) == (2_097_152, 36_864)
    least = family.decode_step_min_bytes(real, 128, 128 * 1500, 320)
    assert least == family.matrix_bytes_per_step(real) \
        + 320 * 19_955_712 + 2 * 128 * 6 * (2_097_152 + 36_864) \
        + 128 * 1500 * 2 * 1024
    assert 6.38e9 < 320 * family.expert_bytes(real) < 6.39e9
    assert 3.2e9 < 2 * 128 * 6 * 2_097_152 < 3.3e9
    assert 11.0e9 < least < 11.5e9              # 13.6 ms at 819 GB/s
    # the head is 3 % of such a step's bytes
    assert 0.03 < 2688 * 65536 * 2 / least < 0.035
    assert family.ssm_step_bytes(real, 128) == 4 * 128 * 8 * (
        2 * 128 * 512 + 2 * 128 + 3 * 512)
    # a sequence of 1,500 tokens: 80 % of what it holds is its state
    assert 78 < family.cache_state_share(real, 94, 1) < 82


def span(name, ts, dur, **attrs):
    return {"name": name, "ts": ts, "dur": dur, "attrs": attrs}


def test_the_new_readers_arithmetic_on_hand_made_input(added):
    cell = added()
    family = cell.module("families", "nemotron_h_lm")
    trace = {"modules": [(0.0100, 0.002, "jit_serving_decode(1)"),
                         (0.0200, 0.002, "jit_serving_decode(1)"),
                         (0.0300, 0.001, "jit_serving_prefill(2)")],
             "clock": (0, 0),
             "ops": {"ssm_step.1": 0.0005, "ssm_step": 0.0003, "fusion.3": 1.0}}
    spans = [span("serving.decode", 9000, 4000, batch=2, live_max=50,
                  live_full=80, state_rows=2, moe_pairs=4,
                  moe_experts_touched=3),
             span("serving.decode", 19000, 4000, batch=3, live_max=51,
                  live_full=120, state_rows=3, moe_pairs=8,
                  moe_experts_touched=99),
             span("serving.prefill", 29500, 2000, length=40, bucket=64,
                  moe_pairs=70)]
    counters = {"state_high_water_slots": 3, "state_num_slots": 4,
                "kv_blocks_at_high_water": [20, 3], "block_size": 16,
                "moe_expert_tokens_window": [[3, 1, 0, 0], [2, 2, 2, 2]]}
    peaks = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e10}
    ctx = context.Context(cell=cell, record={}, spans=spans, trace=trace,
                          family=family, counters=counters, peaks=peaks)
    real, tr.to_trace_s = tr.to_trace_s, lambda reduced, s: s
    try:
        # the second step's count is capped at the experts held: 4 x 2 layers
        shares = [100.0 * family.decode_step_min_bytes(TINY, rows, full, hit)
                  / 1e9 / 0.002
                  for rows, full, hit in ((2, 80, 3), (3, 120, 8))]
        got = cell.reader("decode_hbm_share.ssm_moe").read(ctx)
        assert got == pytest.approx(sum(shares) / 2) and got < 100
        assert cell.reader("prefill_mxu_share").read(ctx) == pytest.approx(
            100.0 * family.prefill_flops(TINY, 64, 70) / 1e10 / 0.001)
        # two decode programs of TWO state layers each (five layers in all),
        # the median step's rows, over the seconds of the kernel's operations
        got = cell.reader("ssm_step_hbm_share.hybrid").read(ctx)
        assert got == pytest.approx(
            100.0 * 2 * 2 * family.ssm_step_bytes(TINY, 2.5) / 1e9 / 0.0008)
        assert got < 100
    finally:
        tr.to_trace_s = real
    # 6 pairs a step over 4 held x 2 expert layers
    assert cell.reader("moe_rows_per_expert.hybrid").read(ctx) \
        == pytest.approx(6 / 8)
    assert cell.reader("moe_load_max_over_mean").read(ctx) \
        == pytest.approx(3 * 8 / 12)
    # no file of their own: the reader named before the last dot
    assert cell.reader("state_slots_peak.hybrid").read(ctx) \
        == pytest.approx(75.0)
    assert cell.reader("state_slots_peak.hybrid").read.__module__.endswith(
        "state_slots_peak")
    assert cell.reader("cache_state_share.hybrid").read(ctx) \
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
    other = context.Context(
        cell=types.SimpleNamespace(config={"num_hidden_layers": 6}),
        record={}, spans=spans, trace=trace, family=family, counters={},
        peaks=peaks)
    for name in NEW[:3]:
        assert cell.reader(name).read(other) is None, name
    untraced = context.Context(cell=cell, record={}, spans=spans, trace=None,
                               family=family, counters={}, peaks={})
    for name in ("decode_hbm_share.ssm_moe", "ssm_step_hbm_share.hybrid",
                 "state_slots_peak.hybrid", "cache_state_share.hybrid"):
        assert cell.reader(name).read(untraced) is None, name
    # the XLA fallback runs no kernel: nothing named for it in the trace
    no_kernel = context.Context(
        cell=cell, record={}, spans=spans, family=family, counters=counters,
        trace=dict(trace, ops={"fusion.3": 1.0}), peaks=peaks)
    assert cell.reader("ssm_step_hbm_share.hybrid").read(no_kernel) is None


def test_the_manifest_gains_the_configuration_the_cell_and_its_readers():
    """By NAME: a later PR appends its own entries behind these."""
    book = manifest.load()
    metrics = {m["name"]: m for m in book["per_layer"]}
    for name in NEW:
        assert metrics[name]["workloads"] == [CELL], name
    assert [metrics[n]["moves"] for n in NEW] == [
        "tpot_p90_ms", "tpot_p90_ms", "serve_tok_per_s", "tpot_p90_ms",
        "tpot_p90_ms"]
    assert [metrics[n]["unit"] for n in NEW] == ["%", "%", "rows", "%", "%"]
    assert [metrics[n]["layer"] for n in NEW] == [
        "kernels", "kernels", "expert layer", "cache manager", "cache manager"]
    assert [metrics[n]["source"] for n in NEW] == [
        "device_trace", "device_trace", "program_span", "program_counter",
        "program_counter"]
    for name in SHARED:
        assert CELL in metrics[name]["workloads"], name
    for m in book["end_to_end"]:
        if m["name"] in ("serve_tok_per_s", "tpot_p90_ms"):
            assert CELL in m["workloads"]
    work = {w["name"]: w for w in book["workloads"]}[CELL]
    assert work == {"name": CELL, "config": "nemotron-3-nano-30b-a3b",
                    "traffic": "reason_closed_128", "chips": 1,
                    "why": work["why"]}
    assert len(work["why"]) <= 200
    real = real_config()
    entry = {c["name"]: c for c in book["configs"]}["nemotron-3-nano-30b-a3b"]
    assert entry["reduced"] == real["reduced"] == list(real["published"]) \
        == ["num_hidden_layers", "hybrid_override_pattern",
            "n_routed_experts", "vocab_size"]
    assert entry["source"] == real["source"] and len(entry["why"]) <= 200
    assert entry["file"] == "chipbench/configs/nemotron-3-nano-30b-a3b.json"
    where = {m["name"]: [w["name"] for w in book["workloads"]
                         if manifest.reads_in(m, w["name"], book["end_to_end"])]
             for m in book["per_layer"]}
    for name in NEW + SHARED + ["batch_occupancy", "kv_blocks_peak",
                                "decode_step_ms_p50", "device_idle_share.serve"]:
        assert CELL in where[name], name
    # the lists other tests pin to their own cells are left as they were
    for name in ("decode_hbm_share", "decode_hbm_share.moe",
                 "decode_hbm_share.swa", "decode_hbm_share.ssm",
                 "moe_rows_per_expert", "moe_rows_per_expert.swa",
                 "ssm_step_hbm_share", "state_slots_peak", "cache_state_share",
                 "itl_p50_ms", "itl_p99_ms", "itl_prefill_stall_share",
                 "kv_window_blocks_peak", "mxu_share.train"):
        assert CELL not in where[name], name
    cell = manifest.cell(book, CELL)
    assert cell.chips == 1 and cell.config["family"] == "nemotron_h_lm"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "serve_tok_per_s",
                                                   "tpot_p90_ms"]
    for name in NEW[:3]:
        assert cell.reader(name).read.__module__.endswith(
            name.replace(".", "_"))
    mix = cell.traffic
    assert (mix["generator"], mix["clients"], mix["max_total_tokens"],
            mix["schedule_length"], mix["schedule_seed"]) \
        == ("closed_loop", 128, 3072, 256, 20261002)
    assert mix["prompt_tokens"] == {"kind": "uniform", "min": 128, "max": 1024}
    assert mix["output_tokens"] == {"kind": "uniform", "min": 512, "max": 2048}
    assert cell.config["server"] == {"max_batch": 128, "max_len": 3072,
                                     "max_queue": 128}
    # still one four-chip cell
    assert [w["chips"] for w in book["workloads"]].count(4) == 1


def test_the_configurations_file_holds_the_published_widths():
    real = real_config()
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            rows = [json.loads(line) for line in f]
    except OSError:
        pytest.skip("no catalog here")
    row = next(r for r in rows
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert real["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in real["reduced"]:
            assert real["published"][key] == value
        else:
            assert real[key] == value, key
    assert (real["hidden_size"], real["num_attention_heads"],
            real["num_key_value_heads"], real["head_dim"]) == (2688, 32, 2, 128)
    assert (real["mamba_num_heads"], real["mamba_head_dim"],
            real["ssm_state_size"], real["n_groups"], real["conv_kernel"],
            real["chunk_size"]) == (64, 64, 128, 8, 4, 128)
    assert (real["moe_intermediate_size"],
            real["moe_shared_expert_intermediate_size"],
            real["num_experts_per_tok"], real["routed_scaling_factor"],
            real["mlp_hidden_act"]) == (1856, 3712, 6, 2.5, "relu2")
    assert (real["num_hidden_layers"], real["hybrid_override_pattern"],
            real["n_routed_experts"], real["vocab_size"]) \
        == (13, "MEMEM*EMEMEM*", 64, 65536)
    # the cut is the published pattern's first thirteen letters
    assert real["published"]["hybrid_override_pattern"].startswith(
        real["hybrid_override_pattern"])
    assert len(real["hybrid_override_pattern"]) == real["num_hidden_layers"]
    assert (real["n_routed_experts_published"], real["expert_parallel"],
            real["expert_rank"]) == (128, 2, 0)
    assert real["published"]["n_routed_experts"] == 128
    assert real["state_dtype"] == "float32" and real["dtype"] == "bfloat16"
    assert "PP4 x EP2" in real["deployment"]
    for key in ("weights", "mixer_init", "time_step", "state_dtype",
                "state_layout", "gated_norm", "positions", "experts"):
        assert real["assumed"][key], key
    assert "NO positions" in real["assumed"]["positions"]
    # every limit is written beside the readings it came from
    check = real["check"]
    assert set(check["reasons"]) == {"served_gap_mean", "served_gap_p99",
                                     "served_gap_max", "sample_requests"}
    assert all("control" in check["reasons"][n] and "sound" in
               check["reasons"][n] for n in (
                   "served_gap_mean", "served_gap_p99", "served_gap_max"))
    # each between the readings written beside it, but the widest gap's
    assert 0.0395 < check["served_gap_mean"] < 0.1768
    assert 0.750 < check["served_gap_p99"] < 1.226
    assert check["control_weight_bits"] == 8
    # the program's configuration reads the widths as published
    family = manifest.cell(manifest.load(), CELL).module("families",
                                                         "nemotron_h_lm")
    cfg = family.program_config(real, 3072)
    assert (cfg.d_ssm, cfg.conv_channels, cfg.d_in_proj) == (4096, 6144, 10304)
    assert (cfg.n_experts, cfg.top_k, cfg.route_scale, cfg.experts_held,
            cfg.n_groups, cfg.top_groups) == (128, 6, 2.5, (0, 64), 1, 1)
    assert (cfg.d_expert, cfg.d_shared, cfg.chunk, cfg.conv_taps) \
        == (1856, 3712, 128, 4)
