"""The `afmoe_lm` family added as files (ISSUE 31): a toy cell of it runs whole
on the CPU stand-in for the chip, is `correct` against its plain reference and
reports the cache's and the walk's metrics; the control (the reference one
precision down) fails the comparison; the configuration's bytes by count from
shapes; `decode_step_min_bytes`, `prefill_flops` and each new reader's
arithmetic on hand-made input; the manifest's new entries; each option the
family cannot take, with its reason."""
import json
import shutil
import types

import jax
import pytest

from chipbench import run
from chipbench.harness import context, manifest
from chipbench.trace import reduce as tr

from test_chipbench_cells import stand_in_for_the_chip

TYPES = ["sliding_attention", "sliding_attention", "full_attention",
         "sliding_attention"]
TINY = {
    "family": "afmoe_lm", "hidden_size": 64, "num_attention_heads": 6,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_shared_experts": 1, "num_experts": 4,
    "num_experts_published": 16, "expert_parallel": 4, "expert_rank": 1,
    "num_experts_per_tok": 4, "route_scale": 2.448, "route_norm": True,
    "score_func": "sigmoid", "num_hidden_layers": 4, "num_dense_layers": 1,
    "layer_types": TYPES, "sliding_window": 32, "vocab_size": 512,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "mup_enabled": True, "dtype": "bfloat16",
    "server": {"max_batch": 4, "max_len": 128},
    # limits read at this size on the CPU (seeds 1-6, 24 requests of 40
    # served tokens): the bf16 server's 99th percentile gap is at most 3.2e-3,
    # the int8 control's at least 8.4e-3; the mean (at most 2.5e-4 against at
    # least 4.0e-4) separates less over so few tokens and the widest gap
    # (0.081 against 0.036) not at all, so they are held only loosely here
    "check": {"sample_requests": 16, "served_gap_max": 0.5,
              "served_gap_p99": 0.0055, "served_gap_mean": 0.00038,
              "control_weight_bits": 8}}
MIX = {"generator": "closed_loop", "clients": 4, "schedule_seed": 1,
       "schedule_length": 24,
       "prompt_tokens": {"kind": "uniform", "min": 8, "max": 72},
       "output_tokens": {"kind": "uniform", "min": 8, "max": 40}}
NEW = ["kv_window_blocks_peak", "kv_held_over_full", "attn_walk_over_live",
       "decode_hbm_share.swa", "prefill_mxu_share", "moe_rows_per_expert.swa"]
CELL = "trinity_mixed_closed"
BOOK = {
    "paths": ["chipbench"],
    "configs": [{"name": "tiny", "file": "chipbench/configs/tiny_afmoe.json"}],
    "workloads": [{"name": "tiny_afmoe_closed", "config": "tiny",
                   "traffic": "tiny_afmoe_closed", "chips": 1}],
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "tpot_p90_ms", "unit": "ms"},
                   {"name": "serve_tok_per_s", "unit": "tokens/s"}],
    "per_layer": [{"name": n, "unit": "1", "moves": "tpot_p90_ms"}
                  for n in ["batch_occupancy", "kv_blocks_peak",
                            "decode_step_ms_p50", "decode_copy_share",
                            "moe_load_max_over_mean", "decode_ahead_share"]
                  + NEW]}


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """A root with a manifest of its own and a copy of the benchmark's
    directory, to which the toy cell's configuration and mix are added."""
    root = tmp_path_factory.mktemp("added_afmoe")
    bench = root / "chipbench"
    shutil.copytree(manifest.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs" / "tiny_afmoe.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny_afmoe_closed.json").write_text(json.dumps(MIX))
    (root / "BENCHMARK.json").write_text(json.dumps(BOOK))

    def cell(seed=2**31 + 11, seconds=1.0):
        return manifest.cell(manifest.load(str(root)), "tiny_afmoe_closed",
                             root=str(root), seed=seed, seconds=seconds)
    return cell


def real_config():
    return manifest.read_json(
        manifest.ROOT + "/chipbench/configs/trinity-large-preview.json")


def test_a_toy_cell_of_the_family_is_correct_and_reports_its_metrics(
        added, monkeypatch):
    res = run.run_cell(added(), False, jax.devices()[:1])
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "serve_tok_per_s", "tpot_p90_ms"}
    stand_in_for_the_chip(monkeypatch)
    traced = run.run_cell(added(), True, jax.devices()[:1])
    got = traced["metrics"]
    # the fixture's device trace has no serving program: the two readers of
    # the device's time find nothing of theirs and leave their metric out
    assert {"batch_occupancy", "kv_blocks_peak", "decode_step_ms_p50",
            "decode_copy_share", "moe_load_max_over_mean",
            "decode_ahead_share", "kv_window_blocks_peak", "kv_held_over_full",
            "attn_walk_over_live", "moe_rows_per_expert.swa"} <= set(got)
    assert 0 < got["kv_window_blocks_peak"]["value"] <= 100
    # three window layers of four keep 32 + 16 tokens at most of up to 112
    assert 25 < got["kv_held_over_full"]["value"] <= 100
    assert got["attn_walk_over_live"]["value"] >= 1.0
    assert got["decode_ahead_share"]["value"] > 50
    assert 0.2 < got["moe_rows_per_expert.swa"]["value"] < 3.0


def test_the_lower_precision_fails_the_familys_comparison(added):
    cell = added(seed=5)
    family = cell.module("families", "afmoe_lm")
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
    assert counters["pool_kinds"] == ["full", "window"] and not counters["paged"]
    assert [len(layer) for layer in counters["moe_expert_tokens"]] == [4, 4, 4]
    assert sum(map(sum, counters["moe_expert_tokens"])) > 0
    # a ring of 32 / 16 + 1 blocks a sequence, four sequences at once
    assert 0 < counters["kv_window_high_water_blocks"] <= 4 * 3
    assert counters["kv_window_num_blocks"] == 4 * 3
    assert counters["kv_num_blocks"] == 4 * 8
    assert counters["kv_window_recycled_blocks"] > 0
    record = {"requests": requests}
    sound, control = server.check(record), server.control(record)
    assert all(c["ok"] for c in sound), sound
    assert not all(c["ok"] for c in control), control
    print("sound", sound[:3], "control", control[:3])


def test_the_configurations_bytes_by_count_from_shapes(added):
    family = added().module("families", "afmoe_lm")
    real = real_config()
    weights = jax.eval_shape(lambda: family.make_weights(real, 1))
    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(weights))
    assert 5.01e9 < total < 5.03e9
    assert family.weight_bytes(real) == pytest.approx(total, rel=1e-4)  # gains
    full, window = family.pool_bytes(real)
    assert full == 1 * (32 * 608 + 1) * 16 * 4096
    assert window == 4 * (32 * 257 + 1) * 16 * 4096
    assert round(full / 1e9, 2) == 1.28 and round(window / 1e9, 2) == 2.16
    # five layers that kept every token would hold five full pools
    assert round(5 * full / 1e9, 1) == 6.4
    assert family.expert_bytes(real) == 3 * 3072 * 3072 * 2
    # what the engine makes is what was counted
    from mxnet_tpu.serving import kv_cache
    spec = kv_cache.CacheSpec(
        5, "bfloat16", n_heads=8, head_dim=128, n_q_heads=48,
        layer_kinds=tuple(family.KINDS[t] for t in real["layer_types"]),
        window=4096)
    assert spec.kinds == ("full", "window") and spec.ring("window", 16) == 257
    assert spec.layers_of("full") == (2,)
    assert spec.layers_of("window") == (0, 1, 3, 4)


def test_decode_step_min_bytes_and_prefill_flops_by_hand(added):
    family = added().module("families", "afmoe_lm")
    cfg = dict(TINY)
    attn = 64 * 96 * 3 + 64 * 32 * 2                  # wq, wg, wo; wk, wv
    dense_layer = attn + 3 * 64 * 128
    outside_experts = attn + 64 * 16 + 3 * 64 * 32      # router, shared expert
    dense = dense_layer + 3 * outside_experts
    assert family.dense_params(cfg) == dense
    every_step = 2 * (dense + 64 * 512)
    assert family.dense_bytes_per_step(cfg) == every_step
    assert family.expert_bytes(cfg) == 2 * 3 * 64 * 32
    assert family.kv_bytes_per_token_layer(cfg) == 2 * 2 * 16 * 2
    # one full layer, three window layers
    assert family.decode_step_min_bytes(cfg, 100, 60, 5) \
        == every_step + 5 * 2 * 3 * 64 * 32 + (100 + 3 * 60) * 128
    # 64 rows: the matrices, 20 pairs, and the band: a full layer sees
    # 1 + ... + 64 keys, a window layer 1 + ... + 32 and then 32 a query
    keys = 64 * 65 // 2 + 3 * (32 * 33 // 2 + 32 * 32)
    assert family.prefill_flops(cfg, 64, pairs=20) \
        == 2 * 64 * dense + 20 * 6 * 64 * 32 + keys * 4 * 6 * 16
    # without the step's own count: rows x 4 chosen x 4 held of 16
    assert family.prefill_flops(cfg, 64) - family.prefill_flops(cfg, 64, 0) \
        == 64 * 6 * 64 * 32
    # rows of the bucket x chunks of 128 keys, the ring's 3 blocks one chunk
    assert family.decode_keys_walked(cfg, 3, 300) == 4 * 128 * (3 + 3 * 1)
    assert family.decode_keys_live(cfg, 100, 60) == 100 + 3 * 60
    assert family.held_over_full(cfg, 40, 12) \
        == pytest.approx(100 * (40 + 3 * 12) / (4 * 40))
    # at the published widths: the issue's estimates, by the same functions
    real = real_config()
    assert family.kv_bytes_per_token_layer(real) == 4096
    # 1.09 GFLOP a row in the matrices; the band adds 0.05 at 1,024 rows and
    # 0.4 at 8,192
    assert 1.1e9 < family.prefill_flops(real, 1024) / 1024 < 1.2e9
    assert 1.45e9 < family.prefill_flops(real, 8192) / 8192 < 1.55e9
    weights_and_head = family.dense_bytes_per_step(real)
    assert 1.2e9 < weights_and_head < 1.3e9
    assert family.decode_step_min_bytes(real, 32 * 5100, 32 * 3600, 26) \
        == weights_and_head + 26 * family.expert_bytes(real) \
        + (32 * 5100 + 4 * 32 * 3600) * 4096
    # all 32 rows walk 9,000 keys on the full layer, the ring on the others
    assert family.decode_keys_walked(real, 32, 9000) \
        == 32 * 128 * (71 + 4 * 33)


def span(name, ts, dur, **attrs):
    return {"name": name, "ts": ts, "dur": dur, "attrs": attrs}


def test_the_new_readers_arithmetic_on_hand_made_input(added):
    cell = added()
    family = cell.module("families", "afmoe_lm")
    trace = {"modules": [(0.0100, 0.002, "jit_serving_decode(1)"),
                         (0.0200, 0.002, "jit_serving_decode(1)"),
                         (0.0300, 0.001, "jit_serving_prefill(2)")],
             "clock": (0, 0), "ops": {}}
    spans = [span("serving.decode", 9000, 4000, batch=2, live_max=50,
                  live_full=80, live_window=62, moe_pairs=6,
                  moe_experts_touched=3),
             span("serving.decode", 9010, 4000, position=30),
             span("serving.decode", 19000, 4000, batch=2, live_max=51,
                  live_full=82, live_window=63, moe_pairs=18,
                  moe_experts_touched=50),
             span("serving.prefill", 29500, 2000, length=40, bucket=64,
                  moe_pairs=33),
             span("serving.prefill", 49500, 2000, length=40, bucket=64)]
    ctx = context.Context(
        cell=cell, record={}, spans=spans, trace=trace, family=family,
        counters={"kv_window_high_water_blocks": 9, "kv_window_num_blocks": 12,
                  "kv_blocks_at_high_water": [20, 9]},
        peaks={"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e10})
    real, tr.to_trace_s = tr.to_trace_s, lambda reduced, s: s
    try:
        # the second step's 50 touched is more than 3 layers x 4 held: capped
        shares = [100.0 * family.decode_step_min_bytes(TINY, full, win, touched)
                  / 1e9 / 0.002
                  for full, win, touched in ((80, 62, 3), (82, 63, 12))]
        assert cell.reader("decode_hbm_share.swa").read(ctx) \
            == pytest.approx(sum(shares) / 2)
        # one prefill's program lies in the slice: 1 ms
        assert cell.reader("prefill_mxu_share").read(ctx) == pytest.approx(
            100.0 * family.prefill_flops(TINY, 64, 33) / 1e10 / 0.001)
    finally:
        tr.to_trace_s = real
    assert cell.reader("kv_window_blocks_peak").read(ctx) == pytest.approx(75.0)
    assert cell.reader("kv_held_over_full").read(ctx) \
        == pytest.approx(100 * (20 + 3 * 9) / (4 * 20))
    # two rows walk one chunk of 128 keys on each of four layers
    assert cell.reader("attn_walk_over_live").read(ctx) == pytest.approx(
        2 * (2 * 128 * 4) / ((80 + 3 * 62) + (82 + 3 * 63)))
    # (6 + 18) / 2 pairs a step over 4 held experts x 3 expert layers
    assert cell.reader("moe_rows_per_expert.swa").read(ctx) == pytest.approx(1.0)
    # a program without the family's spans and counters (the parent commit):
    # the readers find nothing and return None, they do not raise
    bare = context.Context(cell=cell, record={}, spans=[
        span("serving.decode", 9000, 4000, batch=2, live_max=50),
        span("serving.prefill", 29500, 2000, prompt_len=40)], trace=trace,
        family=types.SimpleNamespace(), counters={},
        peaks={"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e10})
    for name in NEW:
        assert cell.reader(name).read(bare) is None, name
    untraced = context.Context(cell=cell, record={}, spans=spans, trace=None,
                               family=family, counters={}, peaks={})
    assert cell.reader("prefill_mxu_share").read(untraced) is None
    assert cell.reader("decode_hbm_share.swa").read(untraced) is None


def test_the_manifest_gains_the_configuration_the_cell_and_its_readers():
    book = manifest.load()
    assert [m["name"] for m in book["per_layer"]][-len(NEW):] == NEW
    assert all(m["workloads"] == [CELL] for m in book["per_layer"][-len(NEW):])
    assert book["workloads"][-1] == {
        "name": CELL, "config": "trinity-large-preview",
        "traffic": "batch_closed_mixed_long", "chips": 1,
        "why": book["workloads"][-1]["why"]}
    assert len(book["workloads"][-1]["why"]) <= 200
    real = real_config()
    assert book["configs"][-1]["name"] == "trinity-large-preview"
    assert book["configs"][-1]["reduced"] == real["reduced"] \
        == list(real["published"])
    assert book["configs"][-1]["source"] == real["source"]
    where = {m["name"]: [w["name"] for w in book["workloads"]
                         if manifest.reads_in(m, w["name"], book["end_to_end"])]
             for m in book["per_layer"]}
    for name in ("decode_copy_share", "decode_ahead_share",
                 "prefill_device_share", "moe_load_max_over_mean",
                 "batch_occupancy", "kv_blocks_peak"):
        assert where[name][-1] == CELL
    assert CELL not in where["moe_rows_per_expert"]    # the latent key names
    assert CELL not in where["decode_hbm_share.moe"]
    cell = manifest.cell(book, CELL)
    assert cell.chips == 1 and cell.config["family"] == "afmoe_lm"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "serve_tok_per_s",
                                                   "tpot_p90_ms"]
    for name in NEW:
        assert cell.reader(name).read.__module__.endswith(
            name.replace(".", "_"))
    mix = cell.traffic
    assert (mix["generator"], mix["clients"], mix["max_total_tokens"],
            mix["schedule_length"]) == ("closed_loop", 32, 9728, 96)
    assert mix["prompt_tokens"] == {"kind": "uniform", "min": 1024, "max": 8192}
    assert mix["output_tokens"] == {"kind": "uniform", "min": 512, "max": 1536}
    assert cell.config["server"] == {"max_batch": 32, "max_len": 9728}


def test_the_configurations_file_holds_the_published_widths():
    real = real_config()
    catalog = {}
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            rows = [json.loads(line) for line in f]
        catalog = next(r["config"] for r in rows
                       if r["name"] == "Trinity-Large-Preview")
    except OSError:
        pytest.skip("no catalog here")
    for key, value in catalog.items():
        if key in real["reduced"]:
            assert real["published"][key] == value
        else:
            assert real[key] == value, key
    assert real["layer_types"] == real["published"]["layer_types"][5:10]
    assert (real["num_hidden_layers"], real["num_dense_layers"],
            real["num_experts"], real["vocab_size"]) == (5, 1, 16, 25024)
    assert real["vocab_size"] * 8 == real["published"]["vocab_size"]
    assert real["num_experts"] * real["expert_parallel"] \
        == real["num_experts_published"] == real["published"]["num_experts"]


def test_each_option_the_family_cannot_take_falls_back_with_its_reason(added):
    from mxnet_tpu import serving
    cell = added()
    family = cell.module("families", "afmoe_lm")
    cfg = dict(TINY, dtype="float32")
    weights = family.make_weights(cfg, 3)
    model = lambda: (family.program_params(weights),
                     family.program_config(cfg, 128))
    eng = serving.LMServer(model(), max_batch=2, max_len=128, paged=True,
                           kv_quant=True, prefix_cache=True).engine
    assert not eng.paged and "6 query heads read 2 cached heads" \
        in eng.paged_fallback
    assert not eng.kv_quant and "needs the paged path" in eng.kv_quant_fallback
    assert eng.prefix_cache is None \
        and "chunked-prefill paged path" in eng.prefix_cache_fallback
    assert eng.prefill_chunk == 0 and eng.sync_reason is None
    eng.close()
    eng = serving.LMServer(model(), max_batch=2, max_len=128, tp=2).engine
    assert eng.tp == 1 and "paged path off/ineligible" in eng.tp_fallback
    eng.close()
    eng = serving.LMServer(model(), max_batch=2, max_len=128, spec=True).engine
    assert not eng.spec and eng.spec_fallback
    eng.close()
    # grouped heads aside, kinds alone refuse it too
    from mxnet_tpu.serving import kv_cache
    spec = kv_cache.CacheSpec(2, "float32", n_heads=2, head_dim=16,
                              layer_kinds=("window", "full"), window=32)
    assert "recycled, so they cannot be shared" in spec.paged_unfit()
