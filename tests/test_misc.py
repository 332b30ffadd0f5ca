"""Coverage for the small parity modules: monitor, visualization, callback,
rtc (Pallas mapping of CudaModule), attribute scopes.

Reference: python/mxnet/monitor.py, visualization.py, callback.py, rtc.py,
attribute.py.
"""
import logging
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def _bound_mlp(batch=32):
    mod = mx.mod.Module(mx.models.get_mlp(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch, 784))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier())
    return mod


def test_monitor_collects_stats():
    mod = _bound_mlp()
    mon = mx.monitor.Monitor(interval=1, pattern=".*")
    mon.install(mod._exec)
    mon.tic()
    batch = mx.io.DataBatch(data=[nd.ones((32, 784))],
                            label=[nd.zeros((32,))])
    mod.forward(batch, is_train=False)
    rows = mon.toc()
    assert rows, "monitor must collect per-output stats"
    names = [r[1] for r in rows]
    assert any("fc" in n.lower() or "output" in n.lower() or
               "softmax" in n.lower() for n in names), names
    for _, _, val in rows:
        assert np.isfinite(float(val.asnumpy() if hasattr(val, "asnumpy")
                                 else val))


def test_print_summary_and_plot(capsys):
    sym = mx.models.get_mlp()
    mx.viz.print_summary(sym, shape={"data": (1, 784)})
    out = capsys.readouterr().out
    assert "Total params" in out or "params" in out.lower()
    assert "fullyconnected" in out.lower() or "fc" in out.lower()
    # plot_network needs the graphviz binaries only at render time; the
    # call itself must succeed (or raise the documented ImportError when
    # the python package is absent)
    try:
        g = mx.viz.plot_network(sym, shape={"data": (1, 784)})
        assert g is not None
    except ImportError:
        pass


def test_speedometer_and_log_metric():
    logging.getLogger().setLevel(logging.INFO)
    metric = mx.metric.create("acc")
    metric.update([nd.array([0, 1])], [nd.array([[0.9, 0.1], [0.2, 0.8]])])

    class P:
        pass

    p = P()
    p.epoch, p.nbatch, p.eval_metric, p.locals = 0, 1, metric, None
    sp = mx.callback.Speedometer(batch_size=32, frequent=1)
    sp(p)  # must not raise
    cb = mx.callback.log_train_metric(period=1)
    cb(p)
    bar = mx.callback.ProgressBar(total=2)
    bar(p)


def test_do_checkpoint_callback(tmp_path):
    mod = _bound_mlp()
    prefix = os.path.join(str(tmp_path), "chk")
    cb = mx.callback.do_checkpoint(prefix, period=1)
    arg, aux = mod.get_params()
    cb(0, mod._symbol, arg, aux)
    assert os.path.exists(prefix + "-symbol.json")
    assert os.path.exists(prefix + "-0001.params")
    sym, arg2, aux2 = mx.model.load_checkpoint(prefix, 1)
    for k in arg:
        np.testing.assert_allclose(arg[k].asnumpy(), arg2[k].asnumpy())


def test_rtc_pallas_module():
    """CudaModule -> PallasModule mapping (rtc.py): a user-defined kernel
    runs through pallas_call on CPU interpret mode / TPU compiled."""
    import jax.numpy as jnp

    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    mod = mx.rtc.PallasModule(body, out_shape=None)
    x = nd.array(np.arange(8, dtype=np.float32))
    y = mod(x)
    np.testing.assert_allclose(y.asnumpy(), np.arange(8) * 2.0)


def test_cuda_module_raises_helpfully():
    with pytest.raises(Exception) as e:
        mx.rtc.CudaModule("__global__ void k(float*x){}")
    assert "pallas" in str(e.value).lower() or "cuda" in str(e.value).lower()


def test_attr_scope_applies_to_symbols():
    import mxnet_tpu.symbol as S
    with mx.AttrScope(ctx_group="dev1", mood="x"):
        v = S.Variable("data")
    attrs = v.attr_dict().get("data", {})
    assert attrs.get("ctx_group") == "dev1"
    v2 = S.Variable("plain")
    assert v2.attr_dict().get("plain", {}).get("ctx_group") is None


def test_log_libinfo_kvstore_server_torch_modules():
    """Small parity modules: log.get_logger, libinfo, kvstore_server shim,
    torch converters (reference python/mxnet/{log,libinfo,kvstore_server,
    torch}.py)."""
    import mxnet_tpu.log as mlog
    lg = mlog.get_logger("mxtest", level=logging.INFO)
    lg.info("hello")  # must not raise
    assert mlog.get_logger("mxtest") is lg

    import mxnet_tpu.libinfo as libinfo
    assert libinfo.__version__
    paths = libinfo.find_lib_path()
    assert all(p.endswith(".so") for p in paths)

    import mxnet_tpu.kvstore_server as kvs_srv
    kvs_srv._init_kvstore_server_module()  # worker role: no-op

    torch = pytest.importorskip("torch")
    import mxnet_tpu.torch as mxt
    t = mxt.to_torch(nd.array([1.0, 2.0]))
    assert t.shape == (2,)
    back = mxt.from_torch(t * 2)
    np.testing.assert_allclose(back.asnumpy(), [2.0, 4.0])
    assert mxt.TorchBlock is not None


def test_notebook_callbacks_log_training():
    from mxnet_tpu.notebook.callback import (PandasLogger, LiveLearningCurve,
                                             args_wrapper)
    import mxnet_tpu as mx
    train, val = mx.test_utils.get_mnist_iterator(batch_size=100,
                                                  input_shape=(784,))
    logger = PandasLogger(batch_size=100, frequent=1)
    curve = LiveLearningCurve(metric_name="accuracy", frequent=1)
    kwargs = args_wrapper(logger, curve)
    assert set(kwargs) == {"batch_end_callback", "eval_end_callback",
                           "epoch_end_callback"}
    mod = mx.mod.Module(mx.models.get_mlp(), context=mx.cpu())
    mod.fit(train, eval_data=val, optimizer="sgd",
            initializer=mx.init.Xavier(),
            optimizer_params={"learning_rate": 0.1}, num_epoch=1, **kwargs)
    assert len(logger.train_df) > 0
    assert "samples/sec" in logger.train_df.columns
    assert len(logger.epoch_df) == 1
    assert len(curve.train_series) > 0
    fig = curve.figure()
    assert fig is not None


def test_notebook_callbacks_unit():
    """Fast-tier notebook coverage: callbacks fed synthetic BatchEndParams
    (the fit-integrated version is slow-tier)."""
    import collections
    from mxnet_tpu.notebook.callback import (PandasLogger, LiveLearningCurve,
                                             args_wrapper)
    import mxnet_tpu as mx
    Param = collections.namedtuple("Param", ["epoch", "nbatch", "eval_metric"])
    m = mx.metric.Accuracy()
    m.update([mx.nd.array(np.array([1.0], np.float32))],
             [mx.nd.array(np.array([[0.1, 0.9]], np.float32))])
    logger = PandasLogger(batch_size=4, frequent=1)
    curve = LiveLearningCurve(metric_name="accuracy", frequent=1)
    for i in range(3):
        p = Param(epoch=0, nbatch=i, eval_metric=m)
        logger.train_cb(p)
        curve.train_cb(p)
    logger.eval_cb(Param(epoch=0, nbatch=0, eval_metric=m))
    curve.eval_cb(Param(epoch=0, nbatch=0, eval_metric=m))
    logger.epoch_cb()
    assert len(logger.train_df) == 3 and len(logger.eval_df) == 1
    assert list(logger.train_df["accuracy"]) == [1.0] * 3
    assert len(curve.train_series) == 3 and len(curve.eval_series) == 1
    assert set(args_wrapper(logger, curve)) == {
        "batch_end_callback", "eval_end_callback", "epoch_end_callback"}


def test_mon_alias_and_quantize_reference_kwargs():
    import mxnet_tpu as mx
    assert mx.mon.Monitor is mx.monitor.Monitor
    from mxnet_tpu.contrib.quantization import quantize_model
    import inspect
    sig = inspect.signature(quantize_model)
    for kw in ("data_names", "label_names", "ctx", "calib_layer", "logger",
               "num_calib_examples"):
        assert kw in sig.parameters, kw


def test_attr_scope_and_name_prefix_semantics():
    """Explicit attrs beat AttrScope; name.Prefix applies per thread
    (parity: reference test_attr.py / test_thread_local.py)."""
    import threading
    import mxnet_tpu as mx
    with mx.AttrScope(group="4", data="great"):
        d = mx.sym.Variable("data", attr={"dtype": "data", "group": "1"})
        s = mx.sym.Variable("sdata")
    assert d.attr("group") == "1" and s.attr("group") == "4"
    assert d.attr("dtype") == "data"

    results = {}

    def worker():
        with mx.name.Prefix("thread_"):
            results["t"] = mx.sym.FullyConnected(
                mx.sym.Variable("x"), num_hidden=2).name

    t = threading.Thread(target=worker)
    with mx.name.Prefix("main_"):
        t.start()
        t.join()
        results["m"] = mx.sym.FullyConnected(
            mx.sym.Variable("y"), num_hidden=2).name
    assert results["t"].startswith("thread_")
    assert results["m"].startswith("main_")


def test_exception_recovery_imperative():
    """A failed op must raise and leave the session usable (parity:
    reference test_exc_handling.py)."""
    import mxnet_tpu as mx
    import pytest as _pytest
    with _pytest.raises(Exception):
        mx.nd.Reshape(mx.nd.zeros((2, 3)), shape=(7,))
    out = mx.nd.zeros((2, 2)) + 1
    assert float(out.asnumpy().sum()) == 4.0


def test_tool_rec2idx_roundtrip(tmp_path):
    """tools/rec2idx.py (reference rec2idx role): the generated .idx must
    let MXIndexedRecordIO random-access every record of a plain .rec."""
    import mxnet_tpu as mx
    from tools.rec2idx import build_index
    rec = str(tmp_path / "a.rec")
    w = mx.recordio.MXRecordIO(rec, "w")
    payloads = [("rec%03d" % i).encode() * (i + 1) for i in range(7)]
    for i, b in enumerate(payloads):
        w.write(mx.recordio.pack(mx.recordio.IRHeader(0, 0.0, i, 0), b))
    w.close()
    idx = str(tmp_path / "a.idx")
    assert build_index(rec, idx) == 7
    r = mx.recordio.MXIndexedRecordIO(idx, rec, "r")
    for i in (6, 0, 3):  # out of order: true random access
        _, blob = mx.recordio.unpack(r.read_idx(i))
        assert blob == payloads[i]
    r.close()


def test_tool_parse_log():
    """tools/parse_log.py parses the fit loop's own log lines."""
    from tools.parse_log import parse, render
    lines = [
        "INFO:root:Epoch[0] Train-accuracy=0.5",
        "INFO:root:Epoch[0] Time cost=1.5",
        "INFO:root:Epoch[0] Validation-accuracy=0.6",
        "Epoch[1] Train-accuracy=0.9",
        "Epoch[1] Time cost=1.25",
        "noise line",
    ]
    epochs, table, cols = parse(lines)
    assert epochs == [0, 1]
    assert table[0]["val-accuracy"] == 0.6
    assert table[1]["train-accuracy"] == 0.9
    md = render(epochs, table, cols, "markdown")
    assert "| epoch |" in md and "0.9" in md
    csv = render(epochs, table, cols, "csv")
    assert csv.splitlines()[0].startswith("epoch,")
    # epoch 1 has no validation column value -> empty cell, not a crash
    assert csv.splitlines()[-1].endswith(",")


def test_tool_diagnose_runs():
    import subprocess, sys, os
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join("tools", "diagnose.py"),
         "--no-device-probe"],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr
    assert "mxnet_tpu" in out.stdout and "Native extension" in out.stdout


def test_tool_bandwidth_runs():
    import subprocess, sys, os
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.join("tools", "bandwidth.py"),
         "--size-mb", "1", "--iters", "2"],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr
    assert "host->device staging" in out.stdout
    assert "allreduce over 4 dev" in out.stdout


def test_api_parity_fills_round5():
    """Round-5 function-level parity audit fills: load_frombuffer,
    sparse namespace arithmetic/constructors, image RandomOrderAug +
    scale_down, init.register, data batchify aliases."""
    import mxnet_tpu as mx

    # nd.load_frombuffer round-trips nd.save bytes
    import tempfile, os as _os
    a = {"w": mx.nd.array([[1.0, 2.0]]), "b": mx.nd.array([3.0])}
    fd, path = tempfile.mkstemp(suffix=".params")
    _os.close(fd)
    try:
        mx.nd.save(path, a)
        got = mx.nd.load_frombuffer(open(path, "rb").read())
    finally:
        _os.unlink(path)
    np.testing.assert_allclose(got["w"].asnumpy(), [[1.0, 2.0]])
    with pytest.raises(TypeError):
        mx.nd.load_frombuffer(path)  # a PATH is not a buffer

    # sparse namespace: array/empty/subtract/multiply/divide
    sp = mx.nd.sparse
    dense = mx.nd.array([[0.0, 1.0], [2.0, 0.0]])
    csr = dense.tostype("csr")
    copy = sp.array(csr)
    np.testing.assert_allclose(copy.asnumpy(), dense.asnumpy())
    assert sp.empty("row_sparse", (4, 2)).asnumpy().sum() == 0
    np.testing.assert_allclose(sp.subtract(csr, dense).asnumpy(), 0)
    np.testing.assert_allclose(sp.multiply(csr, 2.0).asnumpy(),
                               2 * dense.asnumpy())
    np.testing.assert_allclose(sp.divide(csr, 2.0).asnumpy(),
                               dense.asnumpy() / 2)
    with pytest.raises(TypeError):
        sp.array(dense)  # dense sources belong to tostype()

    # image: scale_down + RandomOrderAug
    assert mx.image.scale_down((360, 1000), (480, 500)) == (360, 375)
    assert mx.image.scale_down((100, 100), (50, 50)) == (50, 50)
    calls = []
    augs = [type("A", (mx.image.Augmenter,), {
        "__call__": lambda self, src, _i=i: calls.append(_i) or src})()
        for i in range(4)]
    out = mx.image.RandomOrderAug(augs)(mx.nd.zeros((4, 4, 3)))
    assert sorted(calls) == [0, 1, 2, 3] and out.shape == (4, 4, 3)

    # init.register: a custom initializer through the registry
    @mx.init.register
    class _MyConst5(mx.init.Initializer):
        def _init_weight(self, name, arr):
            arr[:] = 5.0
    made = mx.initializer.create("_myconst5")
    assert isinstance(made, _MyConst5)

    # data batchify aliases
    from mxnet_tpu.gluon import data as gdata
    assert gdata.default_mp_batchify_fn is gdata.default_batchify_fn
    b = gdata.default_batchify_fn([np.ones(3), np.zeros(3)])
    assert b.shape == (2, 3)


def test_symbolic_conv_rnn_cells():
    """Legacy symbolic conv cells (parity: rnn_cell.py Conv*Cell): each
    unrolls over feature-map states with shape preserved and executes."""
    import mxnet_tpu as mx
    import mxnet_tpu.symbol as S

    C, H, W = 3, 8, 8
    for cls, n_states in ((mx.rnn.ConvRNNCell, 1),
                          (mx.rnn.ConvLSTMCell, 2),
                          (mx.rnn.ConvGRUCell, 1)):
        cell = cls((C, H, W), num_hidden=4)
        x = S.Variable("x")
        states = [S.Variable("s%d" % i) for i in range(n_states)]
        out, next_states = cell(x, states)
        assert len(next_states) == n_states
        exe = S.Group([out] + next_states).simple_bind(
            mx.cpu(), x=(2, C, H, W),
            **{"s%d" % i: (2, 4, H, W) for i in range(n_states)})
        feed = {"x": mx.nd.ones((2, C, H, W))}
        feed.update({"s%d" % i: mx.nd.zeros((2, 4, H, W))
                     for i in range(n_states)})
        outs = exe.forward(is_train=False, **feed)
        for o in outs:
            assert o.shape == (2, 4, H, W)
            assert np.isfinite(o.asnumpy()).all()
    # odd-kernel invariant is enforced
    with pytest.raises(ValueError):
        mx.rnn.ConvRNNCell((C, H, W), 4, h2h_kernel=(2, 2))


def test_parity_fills_profiler_base_operator_testutils(tmp_path):
    """Round-5 tail fills: profiler Event/Marker/deprecated aliases, base
    ctypes/doc helpers, deprecated NumpyOp/NDArrayOp adapters, and the
    test_utils helper battery."""
    import ctypes
    import mxnet_tpu as mx
    from mxnet_tpu import base, profiler, test_utils as tu

    # profiler: Event context + Marker + deprecated aliases
    profiler.set_state("run")
    with profiler.Event("unit_evt"):
        pass
    profiler.Marker(profiler.Domain("unit"), "m").mark()
    profiler.profiler_set_state("stop")
    assert "unit_evt" in profiler.dumps()

    # base: ctypes helpers round-trip
    arr = base.c_array(ctypes.c_int, [1, 2, 3])
    assert list(arr) == [1, 2, 3]
    import array as _array
    assert list(base.c_array_buf(ctypes.c_int,
                                 _array.array("i", [1, 2]))) == [1, 2]
    f = (ctypes.c_float * 4)(1, 2, 3, 4)
    shared = base.ctypes2numpy_shared(
        ctypes.cast(f, ctypes.POINTER(ctypes.c_float)), (2, 2))
    np.testing.assert_allclose(shared, [[1, 2], [3, 4]])
    doc = base.build_param_doc(["a"], ["int"], ["the a"])
    assert "a : int" in doc and "the a" in doc
    with pytest.raises(base.MXNetError):
        raise base.NotImplementedForSymbol(len, "nd_len")

    # deprecated NumpyOp: a square op trains through a symbol graph
    import mxnet_tpu.symbol as S
    import mxnet_tpu.operator as op_mod

    class SquareOp(op_mod.NumpyOp):
        def __init__(self):
            super().__init__(need_top_grad=True)

        def forward(self, in_data, out_data):
            out_data[0][:] = in_data[0] ** 2

        def backward(self, out_grad, in_data, out_data, in_grad):
            in_grad[0][:] = 2 * in_data[0] * out_grad[0]

    sq = SquareOp().get_symbol(S.Variable("data"))
    exe = sq.simple_bind(mx.cpu(), data=(2, 3), grad_req="write")
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    out = exe.forward(is_train=True, data=mx.nd.array(x))[0].asnumpy()
    np.testing.assert_allclose(out, x ** 2, rtol=1e-6)
    exe.backward(out_grads=[mx.nd.ones((2, 3))])
    np.testing.assert_allclose(exe.grad_dict["data"].asnumpy(), 2 * x,
                               rtol=1e-6)

    # test_utils battery
    assert tu.get_rtol(None, np.float16) == 1e-2
    assert tu.almost_equal_ignore_nan([1.0, np.nan], [1.0, 5.0])
    tu.assert_exception(lambda: 1 / 0, ZeroDivisionError)
    a = mx.nd.ones((2,))
    assert tu.same_array(a, a) and not tu.same_array(a, mx.nd.ones((2,)))
    np.testing.assert_allclose(
        tu.assign_each([1.0, 2.0], lambda v: v + 1).asnumpy(), [2, 3])
    picks = tu.random_sample(list(range(10)), 4)
    assert len(picks) == 4 and picks == sorted(picks)
    sp = tu.create_sparse_array((4, 6), "csr", density=0.5)
    assert sp.asnumpy().shape == (4, 6)
    assert tu.create_sparse_array_zd((4, 6), "csr", 0).asnumpy().sum() == 0
    # statistical checks on a known-good generator
    rng = np.random.RandomState(0)
    assert tu.mean_check(lambda n: rng.normal(0, 1, n), 0, 1,
                         nsamples=200000)
    assert tu.var_check(lambda n: rng.normal(0, 1, n), 1, nsamples=200000)
    buckets, probs = tu.gen_buckets_probs_with_ppf(
        lambda q: float(np.clip(2 * q - 1, -0.9999, 0.9999)), 4)
    p, obs, exp = tu.chi_square_check(
        lambda n: rng.uniform(-1, 1, n), buckets, probs, nsamples=50000)
    # edges are clipped to +-0.9999, so a handful of samples fall outside
    assert p > 1e-6 and 49000 < obs.sum() <= 50000
    tu.verify_generator(lambda n: rng.uniform(-1, 1, n), buckets, probs,
                        nsamples=50000, nrepeat=2)
    # hermetic data fetchers produce the reference file layouts
    d = str(tmp_path)
    assert os.path.exists(os.path.join(tu.get_mnist_ubyte(d),
                                       "train-images-idx3-ubyte"))
    assert os.path.basename(tu.get_im2rec_path()) == "im2rec.py"
    cif = tu.get_cifar10(d)
    assert os.path.exists(os.path.join(cif, "train.rec"))
    # DummyIter repeats one batch forever
    it = mx.io.NDArrayIter(np.zeros((8, 4)), np.zeros(8), batch_size=4)
    dummy = tu.DummyIter(it)
    b1, b2 = next(dummy), next(dummy)
    assert b1 is b2


def test_symbol_ndarray_only_methods_raise_and_fluent_astype():
    """Symbol parity for the NDArray-mirror surface (reference
    symbol.py:1789,2381+): astype is a fluent Cast, list_attr returns the
    node's own attrs, and NDArray-only calls raise
    NotImplementedForSymbol (duck-typed code must fail identically)."""
    import mxnet_tpu as mx
    import mxnet_tpu.symbol as S
    from mxnet_tpu import base

    v = S.Variable("v", attr={"grp": "7"})
    assert v.list_attr() == {"grp": "7"}
    exe = v.astype("float16").bind(mx.cpu(), {"v": mx.nd.array([1.5])},
                                   grad_req="null")
    assert str(exe.forward()[0].dtype) == "float16"
    for m in ("asnumpy", "asscalar", "wait_to_read", "copy",
              "as_in_context", "detach", "backward"):
        with pytest.raises(base.NotImplementedForSymbol):
            getattr(v, m)()
    with pytest.raises(base.MXNetError):
        v.gradient(["v"])


def test_class_method_parity_fills_round5():
    """Method-level audit fills: Optimizer.learning_rate (scheduler-
    aware), Executor.debug_str, HybridBlock.infer_type, Module.prepare,
    BucketingModule state/prepare delegation, RNN cell pack/unpack
    weights + state_shape, CSR asscipy/copyto."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    opt = mx.optimizer.create("sgd", learning_rate=0.3)
    assert opt.learning_rate == 0.3
    with pytest.raises(DeprecationWarning):
        opt.set_lr_scale({})
    sched = mx.lr_scheduler.FactorScheduler(step=1, factor=0.5)
    opt2 = mx.optimizer.create("sgd", learning_rate=1.0, lr_scheduler=sched)
    assert opt2.learning_rate == sched(opt2.num_update)

    mod = mx.mod.Module(mx.models.get_mlp(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 784))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params(mx.init.Xavier())
    mod.prepare(mx.io.DataBatch(data=[nd.ones((4, 784))],
                                label=[nd.zeros((4,))]))
    dump = mod._exec.debug_str()
    assert "FullyConnected" in dump and "var data" in dump

    net = gluon.nn.Dense(3)
    net.initialize()
    net.infer_type(nd.zeros((1, 4), dtype="float16"))
    assert str(net.weight.dtype) == "float16"

    cell = mx.rnn.LSTMCell(4, prefix="l_")
    rng = np.random.RandomState(0)
    fused = {"l_%s_%s" % (g, k): nd.array(
        rng.randn(16, 5 if (g, k) == ("i2h", "weight") else 4)
        if k == "weight" else rng.randn(16))
        for g in ("i2h", "h2h") for k in ("weight", "bias")}
    unpacked = cell.unpack_weights(fused)
    assert set(n for n in unpacked if "_i_" in n) == \
        {"l_i2h_i_weight", "l_i2h_i_bias", "l_h2h_i_weight", "l_h2h_i_bias"}
    repacked = cell.pack_weights(unpacked)
    for k in fused:
        np.testing.assert_allclose(repacked[k].asnumpy(),
                                   fused[k].asnumpy())
    assert cell.state_shape == [(0, 4), (0, 4)]

    csr = nd.array([[1.0, 0], [0, 2]]).tostype("csr")
    np.testing.assert_allclose(csr.asscipy().toarray(), [[1, 0], [0, 2]])
    out = nd.zeros((2, 2))
    csr.copyto(out)
    np.testing.assert_allclose(out.asnumpy(), [[1, 0], [0, 2]])


def test_model_store_short_hash_and_resolution(tmp_path, monkeypatch):
    """model_store parity: short_hash errors clearly for unknown models,
    and get_model_file resolves BOTH the plain naming and the reference's
    name-<short_hash>.params cache naming when a hash is registered."""
    from mxnet_tpu.gluon.model_zoo import model_store

    with pytest.raises(ValueError):
        model_store.short_hash("nonexistent_model")
    monkeypatch.setitem(model_store._model_sha1, "tiny_net",
                        "abcdef0123456789")
    assert model_store.short_hash("tiny_net") == "abcdef01"
    hashed = tmp_path / "tiny_net-abcdef01.params"
    hashed.write_bytes(b"x")
    assert model_store.get_model_file(
        "tiny_net", root=str(tmp_path)) == str(hashed)
    plain = tmp_path / "tiny_net.params"
    plain.write_bytes(b"y")
    assert model_store.get_model_file(
        "tiny_net", root=str(tmp_path)) == str(plain)  # plain wins
    with pytest.raises(IOError):
        model_store.get_model_file("absent_model", root=str(tmp_path))


def test_next_key_inside_foreign_trace():
    """next_key() called inside someone else's jit trace (no
    trace_key_scope) must (a) hand out DISTINCT keys per call, (b) not
    poison the eager RNG state with a tracer — the second trace and the
    following eager draw both used to die with UnexpectedTracerError."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import random as mxrand

    def f(x):
        u1 = jax.random.uniform(mxrand.next_key(), ())
        u2 = jax.random.uniform(mxrand.next_key(), ())
        return x + u1, u2

    r1, u2 = jax.jit(f)(jnp.float32(0.0))
    assert float(r1) != float(u2)           # distinct keys per call
    jax.jit(f)(jnp.zeros((2,)))             # 2nd trace: no tracer leak
    eager = jax.random.uniform(mxrand.next_key(), ())  # eager still fine
    assert 0.0 <= float(eager) <= 1.0
