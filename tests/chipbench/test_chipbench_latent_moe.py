"""The `latent_moe_lm` family added as files: a toy cell of it runs whole on
the CPU stand-in for the chip, is `correct` against its plain reference and
reports the expert layer's metrics; the control (the reference one precision
down) fails the comparison; `decode_step_min_bytes` and each new reader's
arithmetic on hand-made input."""
import json
import shutil
import types

import jax
import pytest

from chipbench import run
from chipbench.harness import context, manifest
from chipbench.trace import reduce as tr

from test_chipbench_cells import stand_in_for_the_chip

TINY = {
    "family": "latent_moe_lm", "hidden_size": 64, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 8, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "expert_parallel": 4, "expert_rank": 1, "num_experts_per_tok": 4,
    "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.5,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "vocab_size": 512,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "dtype": "bfloat16",
    "server": {"max_batch": 4, "num_blocks": 65, "max_len": 128},
    # limits read at this size on the CPU (seeds 1-6, 16 requests of 40
    # served tokens): the bf16 server's mean gap is at most 6.4e-6, the
    # int8 control's at least 1.9e-5; over so few tokens the 99th percentile
    # (up to 2.2e-4 against 4.4e-4) and the widest gap (1.5e-3 against
    # 2.2e-3) lie too close and are held only loosely here
    "check": {"sample_requests": 16, "served_gap_max": 0.02,
              "served_gap_p99": 0.002, "served_gap_mean": 0.000012,
              "control_weight_bits": 8}}
MIX = {"generator": "closed_loop", "clients": 4, "schedule_seed": 1,
       "schedule_length": 24,
       "prompt_tokens": {"kind": "uniform", "min": 4, "max": 16},
       "output_tokens": {"kind": "uniform", "min": 8, "max": 24}}
NEW = ["decode_hbm_share.moe", "moe_rows_per_expert", "moe_load_max_over_mean",
       "prefill_device_share"]
BOOK = {
    "paths": ["chipbench"],
    "configs": [{"name": "tiny", "file": "chipbench/configs/tiny_latent.json"}],
    "workloads": [{"name": "tiny_latent_closed", "config": "tiny",
                   "traffic": "tiny_latent_closed", "chips": 1}],
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "tpot_p90_ms", "unit": "ms"},
                   {"name": "serve_tok_per_s", "unit": "tokens/s"}],
    "per_layer": [{"name": n, "unit": "1", "moves": "tpot_p90_ms"}
                  for n in ["batch_occupancy", "kv_blocks_peak",
                            "decode_step_ms_p50", "decode_copy_share"] + NEW]}


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """A root with a manifest of its own and a copy of the benchmark's
    directory, to which the toy cell's configuration and mix are added."""
    root = tmp_path_factory.mktemp("added_latent")
    bench = root / "chipbench"
    shutil.copytree(manifest.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs" / "tiny_latent.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny_latent_closed.json").write_text(json.dumps(MIX))
    (root / "BENCHMARK.json").write_text(json.dumps(BOOK))

    def cell(seed=2**31 + 11, seconds=1.0):
        return manifest.cell(manifest.load(str(root)), "tiny_latent_closed",
                             root=str(root), seed=seed, seconds=seconds)
    return cell


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
            "decode_copy_share", "moe_rows_per_expert",
            "moe_load_max_over_mean", "prefill_device_share"} <= set(got)
    # 4 rows x 4 of 16 experts chosen, 4 held: about a row an expert a step
    assert 0.2 < got["moe_rows_per_expert"]["value"] < 3.0
    assert got["moe_load_max_over_mean"]["value"] >= 1.0
    assert got["prefill_device_share"]["value"] == 0.0


def test_the_lower_precision_fails_the_familys_comparison(added):
    cell = added(seed=5)
    family = cell.module("families", "latent_moe_lm")
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
    assert counters["pool_layout"] == "latent" and not counters["paged"]
    # two expert layers of four held experts; 128-lane rows of bf16, 3 layers
    assert [len(layer) for layer in counters["moe_expert_tokens"]] == [4, 4]
    assert sum(map(sum, counters["moe_expert_tokens"])) > 0
    assert counters["kv_bytes_per_token"] == 3 * 128 * 2
    record = {"requests": requests}
    sound, control = server.check(record), server.control(record)
    assert all(c["ok"] for c in sound), sound
    assert not all(c["ok"] for c in control), control
    print("sound", sound[:3], "control", control[:3])


def test_decode_step_min_bytes_by_hand(added):
    family = added().module("families", "latent_moe_lm")
    cfg = dict(TINY)
    attn = 64 * 24 + 24 * 4 * 16 + 64 * 24 + 16 * 32 + 16 * 32 + 32 * 64
    dense_layer = attn + 3 * 64 * 128
    outside_experts = attn + 64 * 16 + 3 * 64 * 32      # router, shared expert
    every_step = 2 * (dense_layer + 2 * outside_experts + 64 * 512)
    assert family.dense_bytes_per_step(cfg) == every_step
    assert family.expert_bytes(cfg) == 2 * 3 * 64 * 32
    assert family.kv_bytes_per_token(cfg) == 3 * 24 * 2
    assert family.decode_step_min_bytes(cfg, 100, 5) \
        == every_step + 5 * 2 * 3 * 64 * 32 + 100 * 3 * 24 * 2
    # at the published widths: the issue's table, by the same functions
    real = manifest.read_json(manifest.ROOT + "/chipbench/configs/deepseek-v3.json")
    assert family.expert_bytes(real) == 88080384
    assert family.kv_bytes_per_token(real) == 6 * 1152
    weights = jax.eval_shape(lambda: family.make_weights(real, 1))
    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(weights))
    assert 10.9e9 < total < 11.1e9
    assert family.dense_bytes_per_step(real) + 5 * 16 * family.expert_bytes(real) \
        + 7168 * 16160 * 2 == pytest.approx(total, rel=1e-3)   # + the embedding


def span(name, ts, dur, **attrs):
    return {"name": name, "ts": ts, "dur": dur, "attrs": attrs}


def test_the_new_readers_arithmetic_on_hand_made_input(added):
    cell = added()
    family = cell.module("families", "latent_moe_lm")
    # two decode steps of 2 ms device time each, 10 ms apart; the first
    # advanced sequences at positions 30 and 50 and touched 3 experts
    trace = {"modules": [(0.0100, 0.002, "jit_serving_decode(1)"),
                         (0.0200, 0.002, "jit_serving_decode(1)"),
                         (0.0300, 0.001, "jit_serving_prefill(2)")],
             "clock": (0, 0), "ops": {}}
    spans = [span("serving.decode", 9000, 4000, batch=2, moe_pairs=6,
                  moe_experts_touched=3),
             span("serving.decode", 9010, 4000, position=30),
             span("serving.decode", 9010, 4000, position=50),
             span("serving.decode", 19000, 4000, batch=2, moe_pairs=10,
                  moe_experts_touched=5),
             span("serving.decode", 19010, 4000, position=31),
             span("serving.decode", 19010, 4000, position=51)]
    ctx = context.Context(
        cell=cell, record={}, spans=spans, trace=trace, family=family,
        counters={"moe_expert_tokens_window": [[4, 0, 2, 2], [1, 1, 1, 5]]},
        peaks={"hbm_bytes_per_s": 1e9})
    ctx.trace["clock"] = (0, 0)
    monkey = lambda reduced, s: s          # host seconds are trace seconds
    real, tr.to_trace_s = tr.to_trace_s, monkey
    try:
        shares = [100.0 * family.decode_step_min_bytes(TINY, live, touched)
                  / 1e9 / 0.002 for live, touched in ((80, 3), (82, 5))]
        assert cell.reader("decode_hbm_share.moe").read(ctx) \
            == pytest.approx(sum(shares) / 2)
    finally:
        tr.to_trace_s = real
    # (6 + 10) / 2 pairs a step over 4 held experts x 2 expert layers
    assert cell.reader("moe_rows_per_expert").read(ctx) == pytest.approx(1.0)
    assert cell.reader("moe_load_max_over_mean").read(ctx) \
        == pytest.approx(5 / (16 / 8))
    assert cell.reader("prefill_device_share").read(ctx) == pytest.approx(20.0)
    # a program without the family's spans and counters (the parent commit):
    # the readers find nothing and return None, they do not raise
    bare = context.Context(cell=cell, record={}, spans=[
        span("serving.decode", 9000, 4000, batch=2)], trace=trace,
        family=types.SimpleNamespace(), counters={}, peaks={"hbm_bytes_per_s": 1e9})
    assert cell.reader("decode_hbm_share.moe").read(bare) is None
    assert cell.reader("moe_rows_per_expert").read(bare) is None
    assert cell.reader("moe_load_max_over_mean").read(bare) is None


def test_the_manifest_gains_the_cell_and_its_metrics_at_the_end_of_its_lists():
    book = manifest.load()
    entry = [m for m in book["per_layer"] if m["name"] == "decode_copy_share"]
    assert len(entry) == 1
    assert entry[0]["workloads"] == ["opt6b7_batch_closed", "dsv3_batch_closed"]
    assert entry[0]["moves"] == "tpot_p90_ms"
    assert entry[0]["layer"] == "engine step"
    # what ISSUE 27 adds comes after everything the benchmark had, in order
    assert [m["name"] for m in book["per_layer"]][-5:] \
        == ["decode_copy_share"] + NEW
    assert all(m["workloads"] == ["dsv3_batch_closed"]
               for m in book["per_layer"][-4:])
    assert book["workloads"][-1]["name"] == "dsv3_batch_closed"
    assert book["configs"][-1]["name"] == "deepseek-v3"
    assert book["configs"][-1]["reduced"] == manifest.read_json(
        manifest.ROOT + "/chipbench/configs/deepseek-v3.json")["reduced"]


def test_the_manifest_reads_each_metric_in_the_new_cell_as_issue_27_says():
    book = manifest.load()
    where = {m["name"]: [w["name"] for w in book["workloads"]
                         if manifest.reads_in(m, w["name"], book["end_to_end"])]
             for m in book["per_layer"]}
    for name in ("batch_occupancy", "kv_blocks_peak", "decode_step_ms_p50",
                 "device_idle_share.serve", "decode_host_turn_ms_p50",
                 "decode_build_ms_p50", "loop_account_ms_p50",
                 "loop_admit_ms_p50"):   # list no cells: wherever `tpot` is
        assert where[name] == ["opt6b7_batch_closed", "dsv3_batch_closed"]
    for name in NEW:
        assert where[name] == ["dsv3_batch_closed"]
    assert where["train_dispatch_span_ms_p50"] == ["resnet50_train",
                                                   "resnet50_train_dp4"]
    assert where["allreduce_exposed_share"] == ["resnet50_train_dp4"]
    assert where["mxu_share.train"] == ["resnet50_train", "resnet50_train_dp4"]
    new = manifest.cell(book, "dsv3_batch_closed")
    assert new.chips == 1 and new.traffic["clients"] == 32
    assert [m["name"] for m in new.end_to_end] == ["setup_s", "serve_tok_per_s",
                                                   "tpot_p90_ms"]
