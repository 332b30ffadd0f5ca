"""Parallelism tests on the virtual 8-device CPU mesh (parity: the reference's
nightly dist tests — dist_sync_kvstore.py shapes — plus the TPU-native
capability upgrades: tensor/sequence parallelism, ring attention)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu.parallel import mesh as pmesh
from mxnet_tpu.parallel import collectives as coll
from mxnet_tpu.test_utils import assert_almost_equal


def rand(*shape):
    return np.random.uniform(-1, 1, shape).astype(np.float32)


def test_build_mesh():
    m = pmesh.build_mesh({"dp": 4, "tp": 2})
    assert m.shape == {"dp": 4, "tp": 2}
    m2 = pmesh.build_mesh({"dp": -1})
    assert m2.shape == {"dp": 8}
    m3 = pmesh.build_mesh({"dp": 2, "tp": -1})
    assert m3.shape == {"dp": 2, "tp": 4}


def test_shard_batch_and_replicate():
    m = pmesh.data_parallel_mesh()
    x = rand(16, 3)
    sharded = pmesh.shard_batch(m, jnp.asarray(x))
    assert sharded.sharding.spec[0] == "dp"
    rep = pmesh.replicate(m, jnp.asarray(x))
    assert_almost_equal(np.asarray(rep), x)


def test_collectives_psum_allgather():
    m = pmesh.build_mesh({"dp": 8})
    x = jnp.arange(8.0)

    out = jax.shard_map(lambda v: coll.allreduce(v, "dp"), mesh=m,
                    in_specs=P("dp"), out_specs=P("dp"))(x)
    assert_almost_equal(np.asarray(out), np.full(8, x.sum()))

    mean = jax.shard_map(lambda v: coll.allreduce_mean(v, "dp"), mesh=m,
                     in_specs=P("dp"), out_specs=P("dp"))(x)
    assert_almost_equal(np.asarray(mean), np.full(8, float(np.mean(
        np.arange(8.0)))))

    # all_gather output is replicated, which the static VMA checker can't
    # infer — disable it (the value check below proves replication)
    gath = jax.shard_map(lambda v: coll.all_gather(v, "dp"), mesh=m,
                     in_specs=P("dp"), out_specs=P(),
                     check_vma=False)(x)
    assert_almost_equal(np.asarray(gath), np.arange(8.0))


def test_ring_permute():
    m = pmesh.build_mesh({"dp": 8})
    x = jnp.arange(8.0)
    out = jax.shard_map(lambda v: coll.ring_permute(v, "dp", shift=1), mesh=m,
                    in_specs=P("dp"), out_specs=P("dp"))(x)
    # each shard receives its left neighbor's value
    assert_almost_equal(np.asarray(out), np.roll(np.arange(8.0), 1))


def test_reduce_scatter():
    m = pmesh.build_mesh({"dp": 8})
    x = jnp.asarray(rand(8, 8))
    # each device holds one row; psum_scatter leaves device i with element i
    # of the row-sum
    out = jax.shard_map(lambda v: coll.reduce_scatter(v[0], "dp"), mesh=m,
                    in_specs=P("dp", None), out_specs=P("dp"))(x)
    assert_almost_equal(np.asarray(out), np.asarray(x).sum(0), rtol=1e-5,
                        atol=1e-5)


def test_ring_attention_matches_reference():
    from mxnet_tpu.parallel.ring_attention import (ring_attention_sharded,
                                                   attention_reference)
    m = pmesh.build_mesh({"sp": 8})
    B, H, S, D = 2, 2, 32, 8  # S sharded 8-way -> 4 per device
    np.random.seed(3)
    q, k, v = rand(B, H, S, D), rand(B, H, S, D), rand(B, H, S, D)
    out = ring_attention_sharded(m, jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v))
    ref = attention_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    assert_almost_equal(np.asarray(out), np.asarray(ref), rtol=1e-3,
                        atol=1e-4)


def test_ring_attention_causal():
    from mxnet_tpu.parallel.ring_attention import (ring_attention_sharded,
                                                   attention_reference)
    m = pmesh.build_mesh({"sp": 8})
    B, H, S, D = 1, 2, 16, 4
    np.random.seed(4)
    q, k, v = rand(B, H, S, D), rand(B, H, S, D), rand(B, H, S, D)
    out = ring_attention_sharded(m, jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), causal=True)
    ref = attention_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=True)
    assert_almost_equal(np.asarray(out), np.asarray(ref), rtol=1e-3,
                        atol=1e-4)


def test_trainstep_dp_matches_single_device():
    """Data-parallel fused step over the mesh == single-device step."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep

    def build():
        np.random.seed(0)
        net = nn.HybridSequential(prefix="n_")
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
        net.initialize(mx.init.Xavier())
        net(nd.zeros((1, 6)))
        return net

    lossfn = gloss.SoftmaxCrossEntropyLoss()
    x = rand(16, 6)
    y = np.random.randint(0, 4, (16,)).astype(np.float32)

    mx.random.seed(0)
    net_a = build()
    step_a = TrainStep(net_a, lossfn, "sgd", {"learning_rate": 0.1})
    for _ in range(3):
        la = float(step_a(x, y))

    mx.random.seed(0)
    net_b = build()
    m = pmesh.build_mesh({"dp": 8})
    step_b = TrainStep(net_b, lossfn, "sgd", {"learning_rate": 0.1}, mesh=m)
    for _ in range(3):
        lb = float(step_b(x, y))
    assert abs(la - lb) < 1e-4, (la, lb)
    step_a.sync_params()
    step_b.sync_params()
    for (n1, p1), (n2, p2) in zip(net_a.collect_params().items(),
                                  net_b.collect_params().items()):
        assert_almost_equal(p1.data().asnumpy(), p2.data().asnumpy(),
                            rtol=1e-4, atol=1e-5)


def test_trainstep_tensor_parallel_matches():
    """dp x tp sharded step == unsharded step (GSPMD inserts collectives)."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep

    def build():
        np.random.seed(1)
        net = nn.HybridSequential(prefix="t_")
        with net.name_scope():
            net.add(nn.Dense(8, activation="tanh"), nn.Dense(4))
        net.initialize(mx.init.Xavier())
        net(nd.zeros((1, 5)))
        return net

    lossfn = gloss.SoftmaxCrossEntropyLoss()
    x = rand(8, 5)
    y = np.random.randint(0, 4, (8,)).astype(np.float32)

    net_a = build()
    step_a = TrainStep(net_a, lossfn, "sgd", {"learning_rate": 0.1})
    la = float(step_a(x, y))

    net_b = build()
    m = pmesh.build_mesh({"dp": 4, "tp": 2})
    shardings = {n: P("tp", None) for n in net_b.collect_params()
                 if n.endswith("weight")}
    step_b = TrainStep(net_b, lossfn, "sgd", {"learning_rate": 0.1},
                       mesh=m, param_shardings=shardings)
    lb = float(step_b(x, y))
    assert abs(la - lb) < 1e-4


def test_kvstore_tpu_on_mesh():
    kv = mx.kv.create("tpu")
    kv.init(0, nd.ones((4, 4)))
    kv.push(0, [nd.ones((4, 4)) * (i + 1) for i in range(4)])
    out = nd.zeros((4, 4))
    kv.pull(0, out=out)
    assert_almost_equal(out.asnumpy(), np.full((4, 4), 1 + 2 + 3 + 4 + 1.0))


def test_dist_sync_shapes():
    """The reference nightly test pushes shapes around the big-array bound
    (dist_sync_kvstore.py:36-60); here the analogous large/small keys flow
    through the same aggregation path."""
    kv = mx.kv.create("device")
    big = (1200, 1100)  # > bigarray bound in the reference
    kv.init("big", nd.zeros(big))
    kv.push("big", [nd.ones(big)] * 2)
    out = nd.zeros(big)
    kv.pull("big", out=out)
    assert float(out.asnumpy()[0, 0]) == 2.0


def test_multichip_dryrun_entry():
    import importlib
    import sys
    sys.path.insert(0, "/root/repo")
    try:
        g = importlib.import_module("__graft_entry__")
        g.dryrun_multichip(8)
    finally:
        sys.path.pop(0)


# ---------------- transformer LM: tp/sp/ep ----------------

def test_transformer_dp_tp_sp_trains():
    from mxnet_tpu.models.transformer import TransformerConfig, \
        make_train_step
    m = pmesh.build_mesh({"dp": 2, "tp": 2, "sp": 2})
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_len=16)
    run, params = make_train_step(m, cfg, lr=0.1)
    toks = np.random.randint(0, 64, (4, 16))
    params, l0 = run(params, toks)
    for _ in range(5):
        params, l = run(params, toks)
    assert float(l) < float(l0)


def test_transformer_moe_ep_trains():
    from mxnet_tpu.models.transformer import TransformerConfig, \
        make_train_step
    m = pmesh.build_mesh({"dp": 2, "tp": 2, "ep": 2})
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=1,
                            d_ff=64, n_experts=4, max_len=16)
    run, params = make_train_step(m, cfg, lr=0.1)
    toks = np.random.randint(0, 64, (4, 16))
    params, l0 = run(params, toks)
    for _ in range(5):
        params, l = run(params, toks)
    assert float(l) < float(l0)


def test_transformer_sharded_matches_single_device():
    """The sharded forward must equal the single-device forward."""
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params,
                                              transformer_apply,
                                              transformer_shardings)
    cfg = TransformerConfig(vocab=32, d_model=16, n_heads=2, n_layers=1,
                            d_ff=32, max_len=8)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.randint(0, 32, (2, 8)), jnp.int32)
    ref = transformer_apply(params, toks, cfg)  # no mesh

    m = pmesh.build_mesh({"dp": 2, "tp": 2, "sp": 2})
    sh = transformer_shardings(cfg)
    placed = {k: jax.device_put(v, NamedSharding(m, sh[k]))
              for k, v in params.items()}
    toks_sharded = jax.device_put(toks, NamedSharding(m, P("dp", "sp")))
    out = jax.jit(lambda p, t: transformer_apply(p, t, cfg, mesh=m))(
        placed, toks_sharded)
    assert_almost_equal(np.asarray(out), np.asarray(ref), rtol=2e-3,
                        atol=2e-4)


# ---------------- pipeline parallelism ----------------

def test_gpipe_matches_sequential():
    from mxnet_tpu.parallel.pipeline import gpipe_apply
    m = pmesh.build_mesh({"pp": 2})
    rng = np.random.RandomState(0)
    W = jnp.asarray(rng.uniform(-0.5, 0.5, (2, 8, 8)).astype(np.float32))
    x = jnp.asarray(rng.uniform(-1, 1, (8, 8)).astype(np.float32))

    def stage(p, v):
        return jnp.tanh(v @ p)

    out = gpipe_apply(stage, W, x, n_microbatches=4, mesh=m)
    ref = jnp.tanh(jnp.tanh(x @ W[0]) @ W[1])
    assert_almost_equal(np.asarray(out), np.asarray(ref), rtol=1e-5,
                        atol=1e-6)


def test_gpipe_grads_match():
    from mxnet_tpu.parallel.pipeline import gpipe_apply
    m = pmesh.build_mesh({"pp": 4})
    rng = np.random.RandomState(1)
    W = jnp.asarray(rng.uniform(-0.5, 0.5, (4, 6, 6)).astype(np.float32))
    x = jnp.asarray(rng.uniform(-1, 1, (8, 6)).astype(np.float32))

    def stage(p, v):
        return jnp.tanh(v @ p)

    def ploss(W):
        return jnp.sum(gpipe_apply(stage, W, x, 4, m) ** 2)

    def sloss(W):
        v = x
        for i in range(4):
            v = jnp.tanh(v @ W[i])
        return jnp.sum(v ** 2)

    g = jax.grad(ploss)(W)
    gref = jax.grad(sloss)(W)
    assert_almost_equal(np.asarray(g), np.asarray(gref), rtol=1e-4,
                        atol=1e-5)


def test_sharded_embedding_matches_single_device():
    """Row-sharded embedding over the mesh == unsharded training (the PS
    row_sparse embedding-sharding capability, kvstore_dist.h:437, as GSPMD
    gather/scatter-add sharding)."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep
    from mxnet_tpu.parallel import shard_embedding_params, row_sharded_spec

    vocab, dim = 64, 8

    def build():
        np.random.seed(3)
        net = nn.HybridSequential(prefix="e_")
        with net.name_scope():
            net.add(nn.Embedding(vocab, dim))
            net.add(nn.Dense(4, flatten=True))
        net.initialize(mx.init.Xavier())
        net(nd.array(np.zeros((1, 5), np.float32)))
        return net

    lossfn = gloss.SoftmaxCrossEntropyLoss()
    ids = np.random.RandomState(0).randint(0, vocab, (16, 5)) \
        .astype(np.float32)
    y = np.random.RandomState(1).randint(0, 4, (16,)).astype(np.float32)

    mx.random.seed(0)
    net_a = build()
    step_a = TrainStep(net_a, lossfn, "sgd", {"learning_rate": 0.1})
    for _ in range(3):
        la = float(step_a(ids, y))

    mx.random.seed(0)
    net_b = build()
    shardings = shard_embedding_params(net_b, "tp")
    assert len(shardings) == 1 and \
        list(shardings.values())[0] == row_sharded_spec("tp")
    m = pmesh.build_mesh({"dp": 2, "tp": 4})
    step_b = TrainStep(net_b, lossfn, "sgd", {"learning_rate": 0.1},
                       mesh=m, param_shardings=shardings)
    for _ in range(3):
        lb = float(step_b(ids, y))
    assert abs(la - lb) < 1e-4, (la, lb)
    step_a.sync_params()
    step_b.sync_params()
    for (n1, p1), (n2, p2) in zip(net_a.collect_params().items(),
                                  net_b.collect_params().items()):
        assert_almost_equal(p1.data().asnumpy(), p2.data().asnumpy(),
                            rtol=1e-4, atol=1e-5)


def test_remat_recomputes_forward():
    """MXNET_BACKWARD_DO_MIRROR capability: segmented jax.checkpoint makes
    the backward recompute forward matmuls (more dot_generals + barriers in
    the lowered program) and trains identically. XLA:CPU CSEs the recompute
    away post-optimization, so the assertion is on the lowered StableHLO —
    on TPU the barriers hold and peak activation memory shrinks."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep

    def build():
        np.random.seed(5)
        net = nn.HybridSequential(prefix="r_")
        with net.name_scope():
            for _ in range(6):
                net.add(nn.Dense(128, activation="relu"))
            net.add(nn.Dense(4))
        net.initialize(mx.init.Xavier())
        net(nd.zeros((1, 64)))
        return net

    lossfn = gloss.SoftmaxCrossEntropyLoss()
    x = rand(32, 64)
    y = np.random.randint(0, 4, (32,)).astype(np.float32)
    stats, losses = {}, {}
    for remat in (False, True):
        mx.random.seed(0)
        step = TrainStep(build(), lossfn, "sgd", {"learning_rate": 0.1},
                         remat=remat)
        losses[remat] = [float(step(x, y)) for _ in range(3)]
        txt = step.lowered_stablehlo()
        stats[remat] = (txt.count("dot_general"),
                        txt.count("optimization_barrier"))
    assert stats[True][0] > stats[False][0], stats  # recompute dots
    assert stats[True][1] > stats[False][1], stats  # barriers present
    # numerics are unchanged by rematerialisation
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)
    # memory accounting API works (the shrink itself materializes on TPU)
    assert step.memory_analysis().temp_size_in_bytes > 0


def test_wait_all_scoped_to_framework_buffers():
    from mxnet_tpu import engine
    a = nd.ones((64, 64))
    b = nd.dot(a, a)
    assert len(engine._PENDING) > 0
    mx.nd.waitall()
    assert len(engine._PENDING) == 0
    assert b.asnumpy()[0, 0] == 64.0


def test_waitall_after_trainstep_with_donation():
    """The benchmark pattern: steps then waitall — donated (deleted)
    buffers in the pending registry must not raise."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep
    net = nn.Dense(4, in_units=6)
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, gloss.L2Loss(), "sgd", {"learning_rate": 0.1})
    for _ in range(3):
        step(rand(8, 6), rand(8, 4))
    mx.nd.waitall()  # must not raise on donated param buffers


def test_state_dict_survives_next_step():
    """state_dict is host-materialized: the next (donating) step must not
    invalidate a held checkpoint."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep
    net = nn.Dense(4, in_units=6)
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, gloss.L2Loss(), "sgd", {"learning_rate": 0.1})
    step(rand(8, 6), rand(8, 4))
    state = step.state_dict()
    step(rand(8, 6), rand(8, 4))  # donates the buffers state snapshotted
    w = np.asarray(state["grad_vals"][0])  # still readable
    assert np.isfinite(w).all()
    # and restoring rewinds to the snapshot
    step.load_state_dict(state)
    assert step._t == int(state["t"])


def test_remat_applies_to_hybridized_children():
    """Segmented remat must not be bypassed by hybridize()'s CachedOp."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep
    np.random.seed(6)
    net = nn.HybridSequential(prefix="h_")
    with net.name_scope():
        for _ in range(3):
            net.add(nn.Dense(64, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1, 16)))
    net.hybridize()
    step = TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1}, remat=True)
    step(rand(8, 16), np.zeros((8,), np.float32))
    txt = step.lowered_stablehlo()
    assert txt.count("optimization_barrier") > 0, "remat bypassed"


def test_memory_analysis_after_resume():
    """load_state_dict builds the step early; the analysis APIs must still
    work after the first real dispatch."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep
    net = nn.Dense(4, in_units=6)
    net.initialize(mx.init.Xavier())
    step = TrainStep(net, gloss.L2Loss(), "sgd", {"learning_rate": 0.1})
    step(rand(8, 6), rand(8, 4))
    state = step.state_dict()

    net2 = nn.Dense(4, in_units=6)
    net2.initialize(mx.init.Xavier())
    step2 = TrainStep(net2, gloss.L2Loss(), "sgd", {"learning_rate": 0.1})
    step2.load_state_dict(state)  # builds before any dispatch
    step2(rand(8, 6), rand(8, 4))
    assert step2.memory_analysis().temp_size_in_bytes >= 0


def test_trainstep_sharded_optimizer_states_match_replicated():
    """ZeRO-style weight-update sharding (arXiv:2004.13336): optimizer
    state sharded over 'dp' must train bit-comparably to replicated state,
    with the state arrays actually distributed."""
    import jax
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.mesh import build_mesh
    from mxnet_tpu.parallel.trainer import TrainStep
    from mxnet_tpu.gluon import loss as gloss, nn

    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (32, 16)).astype(np.float32)
    Y = rng.randint(0, 4, (32,)).astype(np.int32)

    def make_step(shard):
        mx.random.seed(3)
        np.random.seed(3)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
        net.initialize(mx.init.Xavier())
        net(mx.nd.zeros((1, 16)))
        mesh = build_mesh({"dp": 8}, jax.devices()[:8])
        return TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "adam",
                         {"learning_rate": 0.05}, mesh=mesh,
                         data_axis="dp", shard_optimizer_states=shard)

    ref, zer = make_step(False), make_step(True)
    for i in range(10):
        lr = float(ref(X, Y))
        lz = float(zer(X, Y))
        np.testing.assert_allclose(lr, lz, rtol=1e-5, atol=1e-6)
    # the adam moments really are sharded over dp
    sharded = [s for st in zer._opt_state for s in st
               if hasattr(s, "sharding") and s.ndim > 0 and
               s.sharding.spec == P("dp")]
    assert sharded, "no optimizer state was dp-sharded"
    # and training states stay equal after sync-back
    ref.sync_params(); zer.sync_params()
    pr = ref._net.collect_params()
    pz = zer._net.collect_params()
    for (nr, vr), (nz, vz) in zip(sorted(pr.items()), sorted(pz.items())):
        np.testing.assert_allclose(vr.data().asnumpy(),
                                   vz.data().asnumpy(),
                                   rtol=1e-5, atol=1e-6, err_msg=nr)


def test_resnetish_dp_tp_matches_single_device():
    """Strided convs + BatchNorm + global pool at 64x64 trained 2 steps
    under dp x tp must match the single-device step: GSPMD makes BN's
    batch-axis reduction global (sync-BN semantics), so dp sharding does
    not change training numerics (unlike the reference's per-device
    stats)."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep
    from jax.sharding import PartitionSpec as P

    def build():
        mx.random.seed(3)
        np.random.seed(3)
        r = mx.models.get_resnetish()
        r.initialize(mx.init.Xavier())
        r(nd.zeros((2, 3, 64, 64)))
        return r

    x = np.random.RandomState(5).uniform(-1, 1, (16, 3, 64, 64)) \
        .astype(np.float32)
    y = np.random.RandomState(6).randint(0, 10, (16,)).astype(np.int32)

    def run(mesh, shard):
        net = build()
        sh = {}
        if shard:
            for name in net.collect_params():
                if "dense" in name and name.endswith("weight"):
                    sh[name] = P("tp", None)
        step = TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                         {"learning_rate": 0.1}, mesh=mesh,
                         data_axis="dp" if mesh else None,
                         param_shardings=sh)
        losses = [float(step(x, y)) for _ in range(2)]
        step.sync_params()
        return losses, {k: v.data().asnumpy()
                        for k, v in net.collect_params().items()}

    l_ref, p_ref = run(None, False)
    mesh = pmesh.build_mesh({"dp": 4, "tp": 2})
    l_par, p_par = run(mesh, True)
    np.testing.assert_allclose(l_ref, l_par, rtol=1e-4)
    for k in p_ref:
        assert_almost_equal(p_ref[k], p_par[k], rtol=1e-3, atol=1e-4)
    # BN moving stats (aux) included in the comparison above proves the
    # cross-replica stat accumulation matches the global computation
    assert any("batchnorm" in k and "running_mean" in k for k in p_ref)


def test_moe_topk_equals_dense_when_k_is_all_experts():
    """With k = n_experts and ample capacity, no token is dropped and the
    renormalized top-k combine IS the full softmax gate - the sparse
    dispatch must reproduce the dense-dispatch MoE exactly."""
    from mxnet_tpu.models.transformer import _moe_ffn, _moe_ffn_topk
    rng = np.random.RandomState(0)
    B, S, D, E, F = 2, 8, 16, 4, 32
    x = jnp.asarray(rng.uniform(-1, 1, (B, S, D)).astype(np.float32))
    wg = jnp.asarray(rng.uniform(-1, 1, (D, E)).astype(np.float32))
    w1 = jnp.asarray(rng.uniform(-0.5, 0.5, (E, D, F)).astype(np.float32))
    w2 = jnp.asarray(rng.uniform(-0.5, 0.5, (E, F, D)).astype(np.float32))
    dense = _moe_ffn(x, wg, w1, w2)
    sparse, _ = _moe_ffn_topk(x, wg, w1, w2, k=E, capacity_factor=1.0)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(sparse),
                               rtol=2e-4, atol=2e-5)


def test_moe_topk_capacity_drops_overflow_not_nan():
    """Tight capacity must drop routes (tokens fall back to the residual
    path = zero FFN contribution), never corrupt the output."""
    from mxnet_tpu.models.transformer import _moe_ffn_topk
    rng = np.random.RandomState(1)
    B, S, D, E, F = 1, 16, 8, 2, 16
    # positive features + gate weights favoring expert 0: EVERY token
    # routes to expert 0 -> guaranteed overflow of its capacity
    x = jnp.asarray(rng.uniform(0.1, 1, (B, S, D)).astype(np.float32))
    wg = jnp.asarray(np.stack([np.full(D, 5.0), np.full(D, -5.0)], 1)
                     .astype(np.float32))
    w1 = jnp.asarray(rng.uniform(-0.5, 0.5, (E, D, F)).astype(np.float32))
    w2 = jnp.asarray(rng.uniform(-0.5, 0.5, (E, F, D)).astype(np.float32))
    out, _ = _moe_ffn_topk(x, wg, w1, w2, k=1, capacity_factor=0.25)
    a = np.asarray(out)
    assert np.isfinite(a).all()
    # capacity 0.25 * 16 / 2 = 2 slots on the hot expert: at most 2
    # tokens produce nonzero output, the overflow rows must be exactly 0
    nonzero_rows = (np.abs(a[0]) > 1e-7).any(axis=-1).sum()
    assert nonzero_rows <= 2, nonzero_rows


def test_transformer_moe_topk_ep_trains():
    """Top-k sparse routing under a real dp x tp x ep mesh: the full
    train step compiles with GSPMD and the loss drops."""
    from mxnet_tpu.models.transformer import TransformerConfig, \
        make_train_step
    m = pmesh.build_mesh({"dp": 2, "tp": 2, "ep": 2})
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=1,
                            d_ff=64, n_experts=4, moe_top_k=2, max_len=16)
    run, params = make_train_step(m, cfg, lr=0.1)
    toks = np.random.randint(0, 64, (4, 16))
    params, l0 = run(params, toks)
    for _ in range(5):
        params, l = run(params, toks)
    assert float(l) < float(l0)


def test_moe_topk_bf16_routing_counts_exact():
    """Routing bookkeeping must be integer: in bf16, >256 tokens on one
    expert would collide capacity slots if counts were float. Route 512
    tokens to one expert in bf16 and check each kept token matches its
    own f32 expert output (collided slots would corrupt pairs)."""
    from mxnet_tpu.models.transformer import _moe_ffn_topk
    rng = np.random.RandomState(2)
    B, S, D, E, F = 1, 512, 8, 2, 8
    x32 = rng.uniform(0.1, 1, (B, S, D)).astype(np.float32)
    wg = np.stack([np.full(D, 5.0), np.full(D, -5.0)], 1).astype(np.float32)
    # positive weights with positive inputs: every pre-activation sits
    # far from the relu boundary, so bf16 cannot flip a unit on/off and
    # any ~100% per-element error can only come from a slot collision
    w1 = rng.uniform(0.1, 0.5, (E, D, F)).astype(np.float32)
    w2 = rng.uniform(-0.5, 0.5, (E, F, D)).astype(np.float32)
    out16, _ = _moe_ffn_topk(jnp.asarray(x32, jnp.bfloat16),
                             jnp.asarray(wg, jnp.bfloat16),
                             jnp.asarray(w1, jnp.bfloat16),
                             jnp.asarray(w2, jnp.bfloat16),
                             k=1, capacity_factor=2.0)
    out32, _ = _moe_ffn_topk(jnp.asarray(x32), jnp.asarray(wg),
                             jnp.asarray(w1), jnp.asarray(w2),
                             k=1, capacity_factor=2.0)
    a16 = np.asarray(out16, np.float32)[0]
    a32 = np.asarray(out32)[0]
    # all 512 tokens fit (capacity 2.0 * 512 / 2 = 512): every row kept
    assert (np.abs(a32) > 1e-7).any(axis=-1).all()
    assert (np.abs(a16) > 1e-7).any(axis=-1).all()
    # bf16 tracks f32 within arithmetic tolerance (mixed bound: bf16 dot
    # products carry ~1% relative + small absolute error). A capacity
    # slot COLLISION sums two different tokens' activations — an O(1)
    # absolute miss that this bound catches with 10x margin.
    err = np.abs(a16 - a32)
    assert (err <= 0.05 + 0.05 * np.abs(a32)).all(), err.max()


def test_moe_topk_aux_loss_balancing():
    """The Switch-style auxiliary is minimized (=1) at uniform routing
    and grows when routing collapses onto one expert."""
    from mxnet_tpu.models.transformer import _moe_ffn_topk
    rng = np.random.RandomState(3)
    B, S, D, E, F = 1, 64, 8, 4, 8
    x = jnp.asarray(rng.uniform(0.1, 1, (B, S, D)).astype(np.float32))
    w1 = jnp.asarray(rng.uniform(-0.5, 0.5, (E, D, F)).astype(np.float32))
    w2 = jnp.asarray(rng.uniform(-0.5, 0.5, (E, F, D)).astype(np.float32))
    # collapsed: every token's gate mass on expert 0
    wg_bad = jnp.asarray(
        np.concatenate([np.full((D, 1), 5.0), np.full((D, E - 1), -5.0)],
                       1).astype(np.float32))
    _, aux_bad = _moe_ffn_topk(x, wg_bad, w1, w2, k=1)
    # genuinely spread routing: small random logits give each token an
    # independent (near-uniform over tokens) top-1 choice — ties at
    # exactly-zero logits would all route to expert 0 and test nothing
    wg_spread = jnp.asarray(
        0.01 * rng.standard_normal((D, E)).astype(np.float32))
    _, aux_uniform = _moe_ffn_topk(x, wg_spread, w1, w2, k=1)
    assert float(aux_bad) > 3.5, float(aux_bad)        # ~E at collapse
    assert 0.9 < float(aux_uniform) < 1.6, float(aux_uniform)


def test_moe_topk_grouped_matches_ungrouped():
    """GShard token grouping (ADVICE r4): with ample capacity no token
    drops in either regime, and since routing is per-token independent
    the grouped dispatch must reproduce the single-group output."""
    from mxnet_tpu.models.transformer import _moe_ffn_topk, _moe_groups
    rng = np.random.RandomState(4)
    B, S, D, E, F = 2, 16, 8, 4, 16
    x = jnp.asarray(rng.uniform(-1, 1, (B, S, D)).astype(np.float32))
    wg = jnp.asarray(rng.uniform(-1, 1, (D, E)).astype(np.float32))
    w1 = jnp.asarray(rng.uniform(-0.5, 0.5, (E, D, F)).astype(np.float32))
    w2 = jnp.asarray(rng.uniform(-0.5, 0.5, (E, F, D)).astype(np.float32))
    # cf=4 >= E/k=2 guarantees per-group capacity >= group tokens: no drops
    one, aux1 = _moe_ffn_topk(x, wg, w1, w2, k=2, capacity_factor=4.0,
                              group_size=0)
    grp, aux2 = _moe_ffn_topk(x, wg, w1, w2, k=2, capacity_factor=4.0,
                              group_size=8)
    np.testing.assert_allclose(np.asarray(one), np.asarray(grp),
                               rtol=2e-4, atol=2e-5)
    assert np.isfinite(float(aux1)) and np.isfinite(float(aux2))
    # group count: smallest divisor of 32 tokens with groups <= 8 -> 4
    assert _moe_groups(32, 8) == 4
    assert _moe_groups(32, 0) == 1       # disabled
    assert _moe_groups(30, 8) == 5       # non-power-of-two divisor hunt
    assert _moe_groups(7, 8) == 1        # already fits


def test_remat_io_policy_saves_mxu_outputs():
    """remat="io" (MXNET_REMAT_POLICY=io): matmul/conv outputs are tagged
    saveable (checkpoint_name in ops/nn.py), so backward does NOT
    recompute dots — only the cheap elementwise chains — while "full"
    recomputes everything. Numerics are identical across all modes."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep

    def build():
        np.random.seed(5)
        net = nn.HybridSequential(prefix="rio_")
        with net.name_scope():
            for _ in range(4):
                net.add(nn.Dense(64, activation="relu"))
            net.add(nn.Dense(4))
        net.initialize(mx.init.Xavier())
        net(nd.zeros((1, 32)))
        return net

    lossfn = gloss.SoftmaxCrossEntropyLoss()
    x = rand(16, 32)
    y = np.random.randint(0, 4, (16,)).astype(np.float32)
    dots, losses = {}, {}
    for remat in ("none", "full", "io"):
        mx.random.seed(0)
        step = TrainStep(build(), lossfn, "sgd", {"learning_rate": 0.1},
                         remat=remat if remat != "none" else False)
        losses[remat] = [float(step(x, y)) for _ in range(3)]
        txt = step.lowered_stablehlo()
        dots[remat] = (txt.count("dot_general"),
                       txt.count("optimization_barrier"))
    assert dots["full"][0] > dots["none"][0], dots   # full recomputes dots
    assert dots["io"][0] < dots["full"][0], dots     # io keeps MXU outputs
    assert dots["io"][1] > 0, dots                   # but is a real remat
    np.testing.assert_allclose(losses["io"], losses["none"], rtol=1e-5)
    np.testing.assert_allclose(losses["full"], losses["none"], rtol=1e-5)


def test_remat_bn_aux_threads_through_checkpoint():
    """BatchNorm blocks are now remat-eligible: running stats thread
    through jax.checkpoint as explicit aux inputs/outputs. The remat step
    must update moving stats AND match the non-remat step's losses and
    final stats exactly."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep

    def build():
        np.random.seed(7)
        net = nn.HybridSequential(prefix="rbn_")
        with net.name_scope():
            net.add(nn.Conv2D(8, 3, padding=1, in_channels=3))
            net.add(nn.BatchNorm())
            net.add(nn.Activation("relu"))
            net.add(nn.Conv2D(8, 3, padding=1, in_channels=8))
            net.add(nn.BatchNorm())
            net.add(nn.GlobalAvgPool2D())
            net.add(nn.Dense(4))
        net.initialize(mx.init.Xavier())
        net(nd.zeros((1, 3, 8, 8)))
        return net

    lossfn = gloss.SoftmaxCrossEntropyLoss()
    x = rand(8, 3, 8, 8)
    y = np.random.randint(0, 4, (8,)).astype(np.float32)
    runs = {}
    for remat in (False, "io", "full"):
        mx.random.seed(0)
        net = build()
        before = {k: v._data.asnumpy().copy()
                  for k, v in net.collect_params().items()
                  if v.grad_req == "null"}
        step = TrainStep(net, lossfn, "sgd", {"learning_rate": 0.1},
                         remat=remat)
        ls = [float(step(x, y)) for _ in range(3)]
        step.sync_params()
        after = {k: v._data.asnumpy() for k, v in
                 net.collect_params().items() if v.grad_req == "null"}
        # running stats moved (BN executed in training mode inside remat)
        assert any(not np.allclose(before[k], after[k]) for k in after)
        runs[remat] = (ls, after)
    for mode in ("io", "full"):
        np.testing.assert_allclose(runs[mode][0], runs[False][0], rtol=1e-5)
        for k in runs[False][1]:
            np.testing.assert_allclose(runs[mode][1][k], runs[False][1][k],
                                       rtol=1e-5, atol=1e-7,
                                       err_msg="%s/%s" % (mode, k))


def test_remat_applies_through_hybridized_containers():
    """A hybridized container above the segments must not bypass remat
    via its warmed CachedOp: _segment_remat deactivates the WHOLE tree
    for the step trace. Pin: barrier count matches the non-hybridized
    build (review finding r5)."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep

    def build(hybridize):
        np.random.seed(11)
        net = nn.HybridSequential(prefix="rh_")
        with net.name_scope():
            for _ in range(3):
                net.add(nn.Dense(32, activation="relu"))
            net.add(nn.Dense(4))
        net.initialize(mx.init.Xavier())
        if hybridize:
            net.hybridize()
        # warm the CachedOp with the training batch shape under record()
        from mxnet_tpu import autograd as ag
        with ag.record():
            net(nd.zeros((8, 16)))
        return net

    x = rand(8, 16)
    y = np.random.randint(0, 4, (8,)).astype(np.float32)
    barriers = {}
    for hyb in (False, True):
        step = TrainStep(build(hyb), gloss.SoftmaxCrossEntropyLoss(),
                         "sgd", {"learning_rate": 0.1}, remat="full")
        float(step(x, y))
        barriers[hyb] = step.lowered_stablehlo().count(
            "optimization_barrier")
    assert barriers[True] == barriers[False] and barriers[True] > 0, \
        barriers


def test_remat_aux_reference_identity_preserved():
    """NDArray references to BN running stats taken BEFORE a remat step
    must stay valid after it (in-place write-back, not rebinding): the
    non-remat path preserves identity and remat must too."""
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel.trainer import TrainStep

    np.random.seed(13)
    net = nn.HybridSequential(prefix="rid_")
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"))
        net.add(nn.BatchNorm())
        net.add(nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1, 6)))
    params = net.collect_params()
    aux_name = [k for k, v in params.items() if v.grad_req == "null"][0]
    ref = params[aux_name].data()          # taken before the step
    step = TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1}, remat="io")
    x = rand(8, 6)
    y = np.random.randint(0, 4, (8,)).astype(np.float32)
    float(step(x, y))
    step.sync_params()
    got = ref.asnumpy()                    # dead tracer would raise here
    np.testing.assert_allclose(got, params[aux_name].data().asnumpy())
