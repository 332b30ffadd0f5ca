"""Persistent fused-RNN scan kernel tests (ops/pallas_rnn.py).

Interpreter mode on CPU: the lax.scan path in ops/nn.py is the parity
oracle — every test pins the fused kernel's forward AND backward against
it; `chip_smoke.py` compiles the kernel with Mosaic and compares it with
the same path on the chip. Tolerance contract: f32 at 1e-5; bf16 (kernel accumulates in
f32 VMEM scratch) at dtype tolerance.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import nn
from mxnet_tpu.ops import pallas_rnn


def _layer_args(mode, T, N, C, H, dtype, seed=0):
    rng = np.random.RandomState(seed)
    G = {"lstm": 4, "gru": 3, "rnn_relu": 1, "rnn_tanh": 1}[mode]
    return (jnp.asarray(rng.randn(T, N, C), dtype),          # xs
            jnp.asarray(rng.randn(N, H) * 0.1, dtype),       # h0
            jnp.asarray(rng.randn(N, H) * 0.1, dtype),       # c0
            jnp.asarray(rng.randn(G * H, C) * 0.2, dtype),   # wi
            jnp.asarray(rng.randn(G * H, H) * 0.2, dtype),   # wh
            jnp.asarray(rng.randn(G * H) * 0.1, dtype),      # bi
            jnp.asarray(rng.randn(G * H) * 0.1, dtype))      # bh


def _tol(dtype):
    return (dict(rtol=1e-5, atol=1e-5) if jnp.dtype(dtype) == jnp.float32
            else dict(rtol=3e-2, atol=3e-2))


@pytest.mark.parametrize("mode", ["lstm", "rnn_tanh", "rnn_relu"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("T", [1, 35])
def test_fused_layer_fwd_bwd_matches_scan(mode, dtype, reverse, T):
    """fwd + every gradient (xs, h0, c0, wi, wh, bi, bh) vs the scan
    oracle, uni (reverse=False) and the bidirectional reverse leg."""
    args = _layer_args(mode, T, 3, 5, 8, dtype)

    def loss(fused, *a):
        ys, hT, cT = nn._scan_layer(mode, *a, reverse=reverse, fused=fused)
        s = (jnp.sum((ys * ys).astype(jnp.float32))
             + jnp.sum(hT.astype(jnp.float32))
             + 3.0 * jnp.sum(cT.astype(jnp.float32)))
        return s, (ys, hT, cT)

    grad = jax.value_and_grad(loss, argnums=tuple(range(1, 8)),
                              has_aux=True)
    (l0, outs0), g0 = grad(False, *args)
    (l1, outs1), g1 = grad(True, *args)
    tol = _tol(dtype)
    for a, b in zip(outs0, outs1):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)
    gtol = (dict(rtol=1e-4, atol=1e-5) if jnp.dtype(dtype) == jnp.float32
            else dict(rtol=5e-2, atol=5e-1))
    for a, b, name in zip(g0, g1, "xs h0 c0 wi wh bi bh".split()):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   err_msg="grad %s" % name, **gtol)


@pytest.mark.parametrize("mode", ["lstm", "rnn_tanh"])
def test_fused_bidirectional_multilayer_op(mode):
    """The full RNN op: 2 layers x 2 directions, state outputs, grads
    through the packed flat parameter vector AND h0/c0."""
    rng = np.random.RandomState(1)
    T, N, C, H, L = 4, 4, 6, 8, 2
    size = nn.rnn_param_size(L, C, H, True, mode)
    params = jnp.asarray(rng.randn(size) * 0.1, jnp.float32)
    data = jnp.asarray(rng.randn(T, N, C), jnp.float32)
    h0 = jnp.asarray(rng.randn(L * 2, N, H) * 0.1, jnp.float32)
    c0 = jnp.asarray(rng.randn(L * 2, N, H) * 0.1, jnp.float32)

    def loss(fused, p, h, c):
        ret = nn.RNN(data, p, h, c, state_size=H, num_layers=L,
                     mode=mode, bidirectional=True,
                     state_outputs=True, fused=fused)
        out, hT = ret[0], ret[1]
        cT = ret[2] if mode == "lstm" else jnp.zeros(())
        return (jnp.sum(out * out) + jnp.sum(hT) + jnp.sum(cT),
                (out, hT, cT))

    grad = jax.value_and_grad(loss, argnums=(1, 2, 3), has_aux=True)
    (l0, outs0), g0 = grad(False, params, h0, c0)
    (l1, outs1), g1 = grad(True, params, h0, c0)
    np.testing.assert_allclose(l0, l1, rtol=1e-5, atol=1e-5)
    for a, b in zip(outs0, outs1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    for a, b, name in zip(g0, g1, ["params", "h0", "c0"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg="grad %s" % name)


def test_dwh_accumulates_across_batch_tiles():
    """N=512 forces nb > 1 (bn caps at 256): the dWh VMEM accumulator
    must carry across batch-tile boundaries of the grid, not reset."""
    args = _layer_args("lstm", 3, 512, 4, 8, jnp.float32)
    assert pallas_rnn._batch_tile("lstm", 512, 8, 4) == 256

    def loss(fused, wh):
        a = list(args)
        a[4] = wh
        ys, _, _ = nn._scan_layer("lstm", *a, fused=fused)
        return jnp.sum(ys * ys)

    g0 = jax.grad(lambda w: loss(False, w))(args[4])
    g1 = jax.grad(lambda w: loss(True, w))(args[4])
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1),
                               rtol=1e-4, atol=1e-4)


def test_eligibility_gate():
    """gru and exotic/mixed dtypes fall back; interpret mode has no lane
    constraint, real TPUs require H % 128 == 0; VMEM-overflowing hidden
    sizes and oversized grids fall back."""
    ok = dict(interpret=True)
    assert pallas_rnn.fused_eligible("lstm", 35, 32, 8, jnp.float32,
                                     jnp.float32, jnp.float32, **ok)
    assert not pallas_rnn.fused_eligible("gru", 35, 32, 8, jnp.float32,
                                         jnp.float32, jnp.float32, **ok)
    assert not pallas_rnn.fused_eligible("lstm", 35, 32, 8, jnp.float16,
                                         jnp.float16, jnp.float16, **ok)
    # mixed dtypes fall back (the kernel assumes one compute dtype)
    assert not pallas_rnn.fused_eligible("lstm", 35, 32, 8, jnp.float32,
                                         jnp.bfloat16, jnp.float32, **ok)
    # Mosaic lane constraint only on real TPUs
    assert not pallas_rnn.fused_eligible("lstm", 35, 32, 200, jnp.float32,
                                         jnp.float32, jnp.float32,
                                         interpret=False)
    assert pallas_rnn.fused_eligible("lstm", 35, 32, 256, jnp.float32,
                                     jnp.float32, jnp.float32,
                                     interpret=False)
    # sublane constraint on real TPUs: the batch tile must be a multiple
    # of 8 (f32) / 16 (bf16); batches with no such divisor fall back
    # instead of failing the Mosaic compile
    assert not pallas_rnn.fused_eligible("lstm", 35, 12, 256, jnp.float32,
                                         jnp.float32, jnp.float32,
                                         interpret=False)
    assert not pallas_rnn.fused_eligible("lstm", 35, 24, 256, jnp.bfloat16,
                                         jnp.bfloat16, jnp.bfloat16,
                                         interpret=False)
    assert pallas_rnn.fused_eligible("lstm", 35, 32, 256, jnp.bfloat16,
                                     jnp.bfloat16, jnp.bfloat16,
                                     interpret=False)
    assert pallas_rnn.fused_eligible("lstm", 35, 12, 8, jnp.float32,
                                     jnp.float32, jnp.float32, **ok)
    # a hidden size whose weights cannot fit VMEM falls back
    assert not pallas_rnn.fused_eligible("lstm", 35, 32, 4096, jnp.float32,
                                         jnp.float32, jnp.float32, **ok)
    # grid cap (interpreter loop) falls back
    assert not pallas_rnn.fused_eligible("lstm", 5000, 32, 8, jnp.float32,
                                         jnp.float32, jnp.float32, **ok)
    # gru layer requests fall back silently through the same gate
    args = _layer_args("gru", 3, 4, 5, 8, jnp.float32)
    ys0 = nn._scan_layer("gru", *args, fused=False)[0]
    ys1 = nn._scan_layer("gru", *args, fused=True)[0]
    np.testing.assert_array_equal(np.asarray(ys0), np.asarray(ys1))


def test_env_flag_off_keeps_scan_path_byte_for_byte(monkeypatch):
    """MXNET_FUSED_RNN unset/0 must leave today's path untouched: the
    kernel entry point is never reached, and the op output is bitwise
    identical to the direct scan computation."""
    monkeypatch.delenv("MXNET_FUSED_RNN", raising=False)

    def boom(*a, **k):
        raise AssertionError("fused kernel entered with the flag off")

    args = _layer_args("lstm", 5, 3, 4, 8, jnp.float32)
    ref = nn._scan_layer("lstm", *args, fused=False)
    monkeypatch.setattr(pallas_rnn, "fused_scan_layer", boom)
    got = nn._scan_layer("lstm", *args)            # fused=None -> env
    monkeypatch.setenv("MXNET_FUSED_RNN", "0")
    got0 = nn._scan_layer("lstm", *args)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(ref, got0):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_env_flag_on_routes_through_kernel(monkeypatch):
    """MXNET_FUSED_RNN=1 reaches the kernel (trace-time read)."""
    called = {}
    real = pallas_rnn.fused_scan_layer

    def spy(*a, **k):
        called["yes"] = True
        return real(*a, **k)

    monkeypatch.setenv("MXNET_FUSED_RNN", "1")
    monkeypatch.setattr(pallas_rnn, "fused_scan_layer", spy)
    args = _layer_args("lstm", 5, 3, 4, 8, jnp.float32)
    ref = nn._scan_layer("lstm", *args, fused=False)
    got = nn._scan_layer("lstm", *args)
    assert called.get("yes")
    for a, b in zip(ref, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_gluon_lstm_layer_fused_parity():
    """The gluon layer path (pack params -> RNN op) with fused=True."""
    x = mx.nd.array(np.random.RandomState(0).randn(5, 3, 6)
                    .astype(np.float32))
    outs = {}
    for fused in (False, True):
        mx.random.seed(0)
        lstm = mx.gluon.rnn.LSTM(8, 2, input_size=6, fused=fused)
        lstm.initialize(mx.init.Xavier())
        outs[fused] = lstm(x).asnumpy()
    np.testing.assert_allclose(outs[False], outs[True],
                               rtol=1e-5, atol=1e-5)


def test_export_model_fused_round_trip(tmp_path):
    """predict.py export with the kernel traced in: the .mxtpu artifact
    replays the fused program and matches the eager output."""
    net = mx.models.RNNModel(mode="lstm", vocab_size=20, num_embed=6,
                             num_hidden=8, num_layers=1, dropout=0.0,
                             fused=True)
    net.initialize(mx.init.Xavier())
    toks = mx.nd.array(np.random.RandomState(1).randint(0, 20, (4, 2))
                       .astype(np.float32))
    ref = net(toks).asnumpy()
    p = str(tmp_path / "m.mxtpu")
    mx.predict.export_model(net, [("data", (4, 2))], p)
    pred = mx.predict.load_exported(p)
    out = pred.forward(data=toks.asnumpy())
    out = out[0] if isinstance(out, (list, tuple)) else out
    out = out.asnumpy() if hasattr(out, "asnumpy") else np.asarray(out)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_symbol_export_fused_attr_round_trip(tmp_path):
    """gluon .export serializes the fused attr into the symbol JSON and
    the reloaded executor replays it."""
    net = mx.models.RNNModel(mode="lstm", vocab_size=20, num_embed=6,
                             num_hidden=8, num_layers=1, dropout=0.0,
                             fused=True)
    net.initialize(mx.init.Xavier())
    toks = mx.nd.array(np.random.RandomState(1).randint(0, 20, (4, 2))
                       .astype(np.float32))
    ref = net(toks).asnumpy()
    net.export(str(tmp_path / "m"))
    assert '"fused"' in (tmp_path / "m-symbol.json").read_text()
    sym, args, aux = mx.model.load_checkpoint(str(tmp_path / "m"), 0)
    exe = sym.simple_bind(mx.cpu(), data=(4, 2), grad_req="null")
    exe.copy_params_from(args, aux)
    exe.forward(data=toks)
    np.testing.assert_allclose(exe.outputs[0].asnumpy(), ref,
                               rtol=1e-5, atol=1e-5)


def test_word_lm_trainstep_end_to_end(monkeypatch):
    """The word-LM TrainStep (the bench.py lstm config in miniature),
    fused vs plain: same seed, same data, losses match at dtype tol for
    two optimization steps — the kernel's VJP drives a real update."""
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep

    vocab, emb, hid, layers, bptt, batch = 50, 16, 16, 2, 6, 4
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, vocab, (bptt, batch))
                    .astype(np.float32))
    y = jnp.asarray(rng.randint(0, vocab, (bptt * batch,))
                    .astype(np.int32))

    losses = {}
    for fused in (False, True):
        monkeypatch.setenv("MXNET_FUSED_RNN", "1" if fused else "0")
        mx.random.seed(0)
        np.random.seed(0)
        net = mx.models.RNNModel(mode="lstm", vocab_size=vocab,
                                 num_embed=emb, num_hidden=hid,
                                 num_layers=layers, dropout=0.0)
        net.initialize(mx.init.Xavier())
        net(mx.nd.zeros((bptt, batch)))
        step = TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                         {"learning_rate": 0.1})
        losses[fused] = [float(step(x, y)) for _ in range(3)]
    np.testing.assert_allclose(losses[False], losses[True],
                               rtol=1e-4, atol=1e-5)
    assert losses[True][2] < losses[True][0]  # it actually learns
