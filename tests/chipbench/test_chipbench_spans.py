"""The readers of the program's span tree (ISSUE 24) on hand-made spans: the
host's turn between two decode steps, the loop's bookkeeping, admission's
self time, the trainer's dispatch span; and the same readers over the spans
a tiny served model and a tiny `TrainStep` really record."""
import pytest

from chipbench.harness import context, manifest, spans as sp


def span(id_, name, ts, dur, parent=None, **attrs):
    rec = {"id": id_, "parent": parent, "name": name, "ts": ts, "dur": dur}
    if attrs:
        rec["attrs"] = attrs
    return rec


def iteration(base, it, t, prefill=0, admit_own=100, build=400, dispatch=300,
              wait=50000, append=200, account=500, batch=2):
    """One decoding pass of the serving loop starting at `t` (us), as the
    program records it; ids from `base`. Returns (spans, end)."""
    out, at = [], t + 10
    admit_at = at
    at += admit_own
    if prefill:
        out.append(span(base + 2, "serving.prefill", at, prefill, base + 1,
                        prompt_len=5))
        at += prefill
    out.append(span(base + 1, "serving.admit", admit_at, at - admit_at, base,
                    batch=batch, admitted=int(bool(prefill)), expired=0))
    step_at = at = at + 5
    for k, (name, dur) in enumerate((("build", build), ("dispatch", dispatch),
                                     ("readback", wait))):
        out.append(span(base + 4 + k, "serving.decode." + name, at, dur,
                        base + 3, batch=batch))
        at += dur
    out.append(span(base + 3, "serving.decode", step_at, at - step_at, base,
                    batch=batch))
    for b in range(batch):      # the copies, one per request, ring only
        out.append(span(base + 10 + b, "serving.decode", step_at,
                        at - step_at, base + 3, position=7))
    out.append(span(base + 7, "serving.decode.append", at, append, base,
                    batch=batch))
    at += append
    out.append(span(base + 8, "serving.account", at, account, base,
                    batch=batch))
    at += account + 10
    out.append(span(base, "serving.loop", t, at - t, None, batch=batch, it=it))
    return out, at


def ctx_of(spans):
    return context.Context(cell=None, record={}, counters={}, spans=spans,
                           trace=None, peaks={})


def reader(name):
    cell = manifest.cell(manifest.load(), "opt6b7_batch_closed")
    return cell.reader(name)


def three_steps(prefill_in_second=0, its=(1, 2, 3)):
    spans, at = [], 1000
    for n, it in enumerate(its):
        more, at = iteration(100 * (n + 1), it, at,
                             prefill=prefill_in_second if n == 1 else 0)
        spans += more
    return spans


def test_the_host_turn_runs_from_one_readback_to_the_next_dispatch():
    # append 200 + account 500 + 10 to the loop's end, then 10 + admit 100
    # + 5 + build 400 + dispatch 300 of the next pass
    turn = reader("decode_host_turn_ms_p50").read(ctx_of(three_steps()))
    assert turn == pytest.approx(1.525)


def test_a_prefill_between_two_steps_is_left_out_of_the_turn():
    """The second pass admits a request and prefills it for 30 ms: the pair
    (1, 2) is not a turn, the pair (2, 3) is."""
    spans = three_steps(prefill_in_second=30000)
    r = reader("decode_host_turn_ms_p50")
    assert r.read(ctx_of(spans)) == pytest.approx(1.525)
    first_two = [s for s in spans if s["id"] < 300]
    assert r.read(ctx_of(first_two)) is None


def test_passes_that_are_not_consecutive_make_no_turn():
    assert reader("decode_host_turn_ms_p50").read(
        ctx_of(three_steps(its=(1, 3, 5)))) is None


def test_the_loops_bookkeeping_is_append_plus_account_of_one_pass():
    assert reader("loop_account_ms_p50").read(ctx_of(three_steps())) \
        == pytest.approx(0.7)
    assert reader("decode_build_ms_p50").read(ctx_of(three_steps())) \
        == pytest.approx(0.4)


def test_admissions_self_time_subtracts_its_children_once():
    spans = three_steps(prefill_in_second=30000)
    assert reader("loop_admit_ms_p50").read(ctx_of(spans)) == pytest.approx(0.1)
    admit = next(s for s in spans if s["id"] == 201)
    assert sp.self_us(admit, spans) == 100
    # a second child over the same stretch is not subtracted twice, one that
    # sticks out is clipped, and a queue wait filed at admission (it started
    # before its parent) is no work done inside it
    spans += [span(290, "prefix.lookup", admit["ts"] + 150, 20000, 201),
              span(291, "serving.prefill", admit["ts"] + 29000, 5000, 201),
              span(292, "serving.queue", admit["ts"] - 90000, 90050, 201)]
    assert sp.self_us(admit, spans) == 100
    assert sp.self_us(span(1, "leaf", 0, 70), spans) == 70


def test_admission_is_read_only_in_passes_that_decoded():
    spans = [span(1, "serving.loop", 0, 900, None, batch=0, it=1),
             span(2, "serving.admit", 10, 800, 1, batch=0, admitted=0,
                  expired=1)]
    assert reader("loop_admit_ms_p50").read(ctx_of(spans)) is None


def test_the_trainers_dispatch_span_is_its_median():
    spans = [span(i, "train.dispatch", 1000 * i, dur, None, step=i,
                  first_call=False)
             for i, dur in enumerate((6400, 6600, 6500, 90000), 1)]
    assert reader("train_dispatch_span_ms_p50").read(ctx_of(spans)) \
        == pytest.approx(6.55)


NEW = ("decode_host_turn_ms_p50", "decode_build_ms_p50", "loop_account_ms_p50",
       "loop_admit_ms_p50", "train_dispatch_span_ms_p50")


@pytest.mark.parametrize("name", NEW)
def test_no_spans_and_a_parents_spans_give_none(name):
    """The parent commit's program records neither `parent` nor the new
    names: the reader finds nothing and the line leaves the metric out."""
    assert reader(name).read(ctx_of([])) is None
    old = [{"id": 1, "name": "serving.decode", "ts": 0, "dur": 55000,
            "attrs": {"batch": 16}},
           {"id": 2, "name": "serving.decode", "ts": 0, "dur": 55000,
            "attrs": {"position": 9}},
           {"id": 3, "name": "serving.prefill", "ts": 60000, "dur": 20000,
            "attrs": {"prompt_len": 5}}]
    assert reader(name).read(ctx_of(old)) is None


def test_the_manifest_reads_each_new_metric_where_the_issue_says():
    book = manifest.load()
    where = {m["name"]: [w["name"] for w in book["workloads"]
                         if manifest.reads_in(m, w["name"], book["end_to_end"])]
             for m in book["per_layer"]}
    for name in NEW[:4]:
        assert where[name] == ["opt6b7_batch_closed"]
    assert where["train_dispatch_span_ms_p50"] == ["resnet50_train",
                                                   "resnet50_train_dp4"]
    assert where["allreduce_exposed_share"] == ["resnet50_train_dp4"]
    assert where["mxu_share.train"] == ["resnet50_train", "resnet50_train_dp4"]
    dp4 = manifest.cell(book, "resnet50_train_dp4")
    assert dp4.chips == 4 and dp4.traffic["mesh"] == {"dp": 4}
    assert dp4.traffic["batch"] == 1024
    assert [m["name"] for m in dp4.end_to_end] == ["setup_s",
                                                   "train_samples_per_s"]


def test_the_readers_read_what_a_served_model_and_a_trainstep_record():
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, serving, telemetry
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params)
    from mxnet_tpu.parallel.trainer import TrainStep
    cfg = TransformerConfig(vocab=48, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=64)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    telemetry.tracing.clear()
    srv = serving.serve((params, cfg), max_batch=4, num_blocks=64)
    try:
        for h in [srv.submit([1 + i, 2, 3], max_new_tokens=8) for i in range(2)]:
            h.result(timeout=120)
    finally:
        srv.close()
    net = gluon.nn.Dense(2, in_units=3)
    net.initialize()
    step = TrainStep(net, gluon.loss.L2Loss(), "sgd", {"learning_rate": 0.1})
    for _ in range(3):
        step(mx.nd.ones((4, 3)), mx.nd.zeros((4, 2)))
    ctx = ctx_of(telemetry.spans())
    telemetry.tracing.clear()
    got = {name: reader(name).read(ctx) for name in NEW}
    assert all(v is not None and v > 0 for v in got.values()), got
    step_ms = reader("decode_step_ms_p50").read(ctx)
    assert got["decode_build_ms_p50"] < step_ms
    # seven decoding passes, the first with the two prefills: six turns
    trees = sp.iterations(ctx.spans)
    assert len([t for t in trees if "serving.decode" in t]) == 7
    assert got["decode_host_turn_ms_p50"] > got["decode_build_ms_p50"]
