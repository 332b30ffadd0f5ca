"""The serving steps update the KV pools in place (ISSUE 26).

Load-bearing claims: (1) every step program `TransformerLM.bind` builds
(eight on one device over its four configurations, six tensor-parallel)
donates the pools it is handed: the compiled executable aliases at least
their bytes, and the arrays handed in are deleted by a call; (2) what
`serve` emits is token for token what the pure step functions
(`engine.prefill`, `engine.decode` over the live-gather view) give under a plain
non-donating `jax.jit` driven here, in the test; (3) a step that fails
AFTER it consumed the pools costs every sequence its cache, not the server:
the engine makes the pools anew, drops its prefix cache and raises
`PoolsLost`, the server replays everything running and prefilling, and
every request finishes with the tokens of an undisturbed run; a fault
raised before the launch keeps the narrower handling it had.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

import mxnet_tpu as mx
from mxnet_tpu import serving
from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params)
from mxnet_tpu.serving import engine as engine_mod
from mxnet_tpu.serving import kv_cache
from mxnet_tpu.serving import tp as tp_mod

L, NB, H, BS, DH = 2, 12, 4, 8, 8
B, C = 2, 8                # decode batch; chunk (and dense prompt) length


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = TransformerConfig(vocab=48, d_model=H * DH, n_heads=H, n_layers=L,
                            d_ff=64, max_len=64)
    return init_transformer_params(jax.random.PRNGKey(0), cfg), cfg


def arith_prompt(start, stride, n, vocab=48):
    return [(start + stride * t) % vocab for t in range(n)]


# ---------------------------------------------------------------------------
# (1) every step program consumes its pools
# ---------------------------------------------------------------------------

i32 = jnp.int32
#: operation -> the arguments after (params, *pools), at the sizes above; a
#: paged step takes a table of the live width, the gather decode the full one
STEP_ARGS = {
    "prefill": lambda paged: (jnp.zeros((C,), i32), i32(5),
                              jnp.arange(1, 9, dtype=i32)),
    "decode": lambda paged: (           # the carry at max_batch, then tokens
        jnp.zeros((2 * B,), i32), jnp.zeros((B,), i32),
        jnp.asarray([3, 9], i32),
        jnp.asarray([[1, 2], [3, 4]] if paged
                    else [[1, 2] + [0] * 6, [3, 4] + [0] * 6], i32)),
    "prefill_chunk": lambda paged: (jnp.zeros((C,), i32), i32(0), i32(5),
                                    i32(4), jnp.asarray([1, 2], i32)),
    "spec_score": lambda paged: (jnp.zeros((B, 3), i32),
                                 jnp.asarray([3, 9], i32),
                                 jnp.asarray([3, 2], i32),
                                 jnp.asarray([[1, 2], [3, 4]], i32)),
}
#: configuration -> what `bind` is told
CONFIGS = {"gather": dict(), "paged": dict(paged=True),
           "paged_q8": dict(paged=True, kv_quant=True)}
PAGED_OPS = ["decode", "prefill_chunk", "spec_score"]
#: the fourteen programs, by (configuration, operation);
#: tests/test_span_tree.py lists the name, site and tags of each
ONE_DEVICE = [("gather", "prefill"), ("gather", "decode")] \
    + [(c, op) for c in ("paged", "paged_q8") for op in PAGED_OPS]
TENSOR_PARALLEL = [(c, op) for c in ("paged", "paged_q8") for op in PAGED_OPS]


def step_call(model, config, op, mesh=None):
    """Bind `model` to `config` (over `mesh`) and give (jit, args, indices
    of the pool arguments) for its step program `op`."""
    opts = CONFIGS[config]
    model.bind(BS, mesh=mesh, **opts)
    assert sorted(model.programs) == sorted(
        PAGED_OPS if opts.get("paged") else ["prefill", "decode"])
    quant = bool(opts.get("kv_quant"))
    put = (lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))) \
        if mesh is not None else (lambda x, spec: x)
    dt = jnp.int8 if quant else jnp.float32
    pools = [put(jnp.zeros((L, NB, H, BS, DH), dt), tp_mod.kv_pool_spec())
             for _ in range(2)]
    if quant:
        pools += [put(jnp.zeros((L, NB, H), jnp.float32),
                      tp_mod.kv_scale_spec()) for _ in range(2)]
    # the pools first, as `PagedKVCache.arrays()` orders them
    args = [model.step_params,
            *pools, *STEP_ARGS[op](bool(opts.get("paged")))]
    return model.programs[op], args, list(range(1, 1 + len(pools)))


def assert_consumes_its_pools(jit, args, donated):
    # the analysis is one device's: over a tp mesh, a chip's shard of each
    pool_bytes = sum(args[i].addressable_shards[0].data.nbytes
                     for i in donated)
    # what `InstrumentedJit` compiles and the AOT cache stores
    compiled = jit.lower(*args).compile()
    aliased = compiled.memory_analysis().alias_size_in_bytes
    assert aliased >= pool_bytes, (aliased, pool_bytes)
    out = jit(*args)
    assert all(args[i].is_deleted() for i in donated)
    assert not any(a.is_deleted() for a in jax.tree_util.tree_leaves(args[0]))
    # the pools come back first, in the order they went in, as they were laid
    for i, o in zip(donated, out):
        assert o.shape == args[i].shape and o.dtype == args[i].dtype
        assert o.sharding.is_equivalent_to(args[i].sharding, o.ndim)


@pytest.mark.parametrize("config, op", ONE_DEVICE)
def test_step_program_aliases_and_consumes_its_pools(tiny_lm, config, op):
    params, cfg = tiny_lm
    model = serving.TransformerLM(params, cfg)
    assert_consumes_its_pools(*step_call(model, config, op))


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="tp steps need >= 2 (emulated) devices")
@pytest.mark.parametrize("config, op", TENSOR_PARALLEL)
def test_tp_step_program_aliases_and_consumes_its_pools(tiny_lm, config, op):
    params, cfg = tiny_lm
    model = serving.TransformerLM(params, cfg)
    mesh = tp_mod.build_tp_mesh(2, None)
    assert_consumes_its_pools(*step_call(model, config, op, mesh))


def test_a_warm_loaded_step_consumes_its_pools_too(tiny_lm, tmp_path):
    """The AOT cache stores what `lower().compile()` gave: an executable
    read back from it aliases and deletes like the one that was compiled."""
    from jax.experimental.compilation_cache import compilation_cache
    from mxnet_tpu import aot
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()
    aot.configure(str(tmp_path))
    try:
        params, cfg = tiny_lm
        for warm in (False, True):
            model = serving.TransformerLM(params, cfg)
            jit, args, donated = step_call(model, "gather", "decode")
            jit(*args)
            assert jit.warm_loads == int(warm) and jit.compiles == int(not warm)
            assert all(args[i].is_deleted() for i in donated)
    finally:
        aot.configure(None)
        jax.config.update("jax_compilation_cache_dir", old)
        compilation_cache.reset_cache()


# ---------------------------------------------------------------------------
# (2) served tokens == the pure step functions under a plain jit
# ---------------------------------------------------------------------------


def oracle_tokens(params, cfg, prompt, max_new, block_size=BS):
    """Greedy tokens of one sequence from the step functions `prefill` /
    `decode` (over the live-gather view) under plain `jax.jit` (nothing
    donated; the pools are rebound from the results, as a functional
    update), on a pool of this test's own."""
    nblk = cfg.max_len // block_size
    shape = (cfg.n_layers, nblk + 1, cfg.n_heads, block_size,
             cfg.d_model // cfg.n_heads)
    k, v = jnp.zeros(shape), jnp.zeros(shape)
    row = jnp.arange(1, nblk + 1, dtype=i32)
    prefill = jax.jit(lambda p, k, v, t, n, tb: engine_mod.prefill(
        p, (k, v), t, n, tb, cfg))
    decode = jax.jit(lambda p, k, v, t, pos, tb: engine_mod.decode(
        p, (k, v), jnp.zeros((1,), i32), t, pos, tb, cfg, block_size,
        kv_cache.LiveGatherView))
    s_pad = engine_mod.pow2_bucket(len(prompt), lo=8, hi=cfg.max_len)
    toks = np.zeros((s_pad,), np.int32)
    toks[:len(prompt)] = prompt
    k0 = k
    k, v, logits = prefill(params, k, v, jnp.asarray(toks),
                           i32(len(prompt)), row)
    assert not k0.is_deleted()                  # the oracle donates nothing
    out = list(prompt) + [int(np.argmax(np.asarray(logits)))]
    while len(out) < len(prompt) + max_new:
        k, v, _, nxt = decode(params, k, v, jnp.asarray(out[-1:], i32),
                              jnp.asarray([len(out) - 1], i32), row[None])
        out.append(int(nxt[0]))
    return out[len(prompt):]


def test_served_tokens_are_the_pure_step_functions_tokens(tiny_lm):
    params, cfg = tiny_lm
    prompts = [arith_prompt(1, 1, 9), arith_prompt(5, 2, 4),
               arith_prompt(7, 3, 17)]
    want = [oracle_tokens(params, cfg, p, 12) for p in prompts]
    srv = serving.serve((params, cfg), max_batch=4, block_size=BS)
    try:
        assert not srv.engine.paged             # the default, gather path
        reqs = [srv.submit(p, max_new_tokens=12) for p in prompts]
        assert [r.result(timeout=120) for r in reqs] == want
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# (3) a fault after the pools were consumed
# ---------------------------------------------------------------------------


def fail_once(model, name, consumed, after=0):
    """Wrap the model's step function `name`: its call number `after`
    raises, after deleting the pool arrays it was handed if `consumed`
    (what an executable that fails after its launch leaves behind)."""
    real = getattr(model, name)
    state = {"calls": 0, "fired": 0}

    def step(*args):
        state["calls"] += 1
        if state["calls"] == after + 1:
            state["fired"] += 1
            if consumed:
                for a in args:
                    if isinstance(a, jax.Array) and a.ndim >= 3:
                        a.delete()              # k, v and the scale sidecars
            raise RuntimeError("injected fault in %s" % name)
        return real(*args)

    setattr(model, name, step)
    return state


def undisturbed(params, cfg, prompts, max_new, **kw):
    srv = serving.serve((params, cfg), **kw)
    try:
        reqs = [srv.submit(p, max_new_tokens=max_new) for p in prompts]
        return [r.result(timeout=120) for r in reqs]
    finally:
        srv.close()


def test_engine_remakes_the_pools_under_the_same_placement(tiny_lm):
    params, cfg = tiny_lm
    dev = jax.devices()[-1]
    eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=2,
                         block_size=BS, paged=True, prefix_cache=True,
                         kv_quant=True, devices=[dev])
    assert eng.kv_quant and eng.prefix_cache is not None
    seq = eng.start(arith_prompt(1, 1, 20), max_new=6)
    want = list(seq.tokens)
    eng.release(seq)                            # its blocks go to the cache
    assert len(eng.prefix_cache) > 0
    seq = eng.start(arith_prompt(1, 1, 20), max_new=6)
    assert seq.cache_hit_tokens > 0
    fail_once(eng.model, "decode", consumed=True)
    with pytest.raises(serving.PoolsLost, match="replay every"):
        eng.decode_step([seq])
    assert eng.pools_lost == 1 and not eng.cache.lost()
    for a in eng.cache.arrays():                # anew, empty, where they were
        assert a.devices() == {dev} and not np.asarray(a).any()
    assert len(eng.prefix_cache) == 0
    eng.release(seq, reusable=False)
    assert eng.cache.pool.in_use == 0           # the cache's refs went too
    # the next step does not raise, and the tokens are what they were
    seq = eng.start(arith_prompt(1, 1, 20), max_new=6)
    assert seq.cache_hit_tokens == 0
    assert list(seq.tokens) == want
    eng.decode_step([seq])
    eng.release(seq, reusable=False)


def test_a_fault_before_the_launch_leaves_the_pools_and_the_cache(tiny_lm):
    params, cfg = tiny_lm
    eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=2,
                         block_size=BS, paged=True, prefix_cache=True)
    seq = eng.start(arith_prompt(1, 1, 20), max_new=6)
    eng.release(seq)
    resident = len(eng.prefix_cache)
    seq = eng.start(arith_prompt(1, 1, 20), max_new=6)
    k = eng.cache.k
    fail_once(eng.model, "decode", consumed=False)
    with pytest.raises(RuntimeError, match="injected"):     # as it is
        eng.decode_step([seq])
    assert eng.pools_lost == 0 and eng.cache.k is k
    assert len(eng.prefix_cache) == resident
    eng.decode_step([seq])                      # the history is still there
    eng.release(seq)


def test_a_lost_copy_on_write_gives_the_admissions_blocks_back(tiny_lm):
    """The copy-on-write op donates like a step: when it loses the pools
    during an admission, the blocks that admission took (shared, tail and
    fresh) go back, since no sequence owns them yet."""
    params, cfg = tiny_lm
    eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=2,
                         block_size=BS, paged=True, prefix_cache=True)
    base = arith_prompt(3, 1, 20)
    seq = eng.start(base + [7, 9], max_new=4)
    while not seq.done:
        eng.decode_step([seq])
    eng.release(seq)                    # full blocks and a partial tail stay
    assert len(eng.prefix_cache) > 0

    def cow(k, v, src, dst):
        k.delete()
        v.delete()
        raise RuntimeError("injected fault in the copy-on-write")

    eng._cow_jit = cow
    with pytest.raises(serving.PoolsLost):
        eng.begin(base + [7, 11], 4)    # diverges inside the cached tail
    assert eng.pools_lost == 1 and not eng.cache.lost()
    assert len(eng.prefix_cache) == 0 and eng.cache.pool.in_use == 0


SERVERS = {
    "gather_decode": (dict(), "decode", 3),
    "gather_prefill": (dict(), "prefill", 2),
    "paged_decode": (dict(paged=True, prefix_cache=True, prefill_chunk=8),
                     "decode", 3),
    "paged_chunk": (dict(paged=True, prefix_cache=True, prefill_chunk=8),
                    "prefill_chunk", 4),
}


@pytest.mark.parametrize("case", sorted(SERVERS))
def test_server_replays_everything_after_a_step_lost_the_pools(tiny_lm, case):
    """Three requests in flight; the step named fails with its pools
    consumed. Every request is replayed and finishes with the tokens of an
    undisturbed run; the prefix cache was empty when the replay began; the
    server serves on."""
    params, cfg = tiny_lm
    opts, step, after = SERVERS[case]
    opts = dict(opts, max_batch=4, block_size=BS)
    prompts = [arith_prompt(1, 1, 9), arith_prompt(5, 2, 20),
               arith_prompt(7, 3, 17)]
    want = undisturbed(params, cfg, prompts, 10, **opts)
    srv = serving.serve((params, cfg), **opts)
    try:
        eng = srv.engine
        if eng.prefix_cache is not None:        # something to lose
            srv.generate(prompts[1], max_new_tokens=2, timeout=120)
            assert len(eng.prefix_cache) > 0
        state = fail_once(eng.model, step, consumed=True, after=after)
        seen = {}
        real_replay = srv._replay_all

        def replay_all(err, req=None):
            seen.update(entries=0 if eng.prefix_cache is None
                        else len(eng.prefix_cache), lost=eng.cache.lost(),
                        held=len(srv.scheduler.running)
                        + len(srv.scheduler.prefilling) + (req is not None))
            return real_replay(err, req)

        srv._replay_all = replay_all
        reqs = [srv.submit(p, max_new_tokens=10) for p in prompts]
        assert [r.result(timeout=120) for r in reqs] == want
        assert state["fired"] == 1 and eng.pools_lost == 1
        assert seen["entries"] == 0 and seen["lost"] is False
        snap = srv.snapshot()["requests"]
        assert snap["failed"] == 0 and snap["engine_failures"] == 1
        assert snap["failovers"] == seen["held"] >= 1
        # the step in flight when the pools went (ISSUE 30; none yet where
        # the first prefills fail) is dropped, and its tokens with it: what
        # the replays resumed from were the tokens of collected steps
        drains = srv.snapshot()["throughput"]["decode_drains"]
        assert drains.get("fault", 0) == (0 if case == "gather_prefill" else 1)
        assert srv._flight is None or srv._flight.seqs
        # the next step does not raise: a fresh request decodes, and the
        # blocks are all back but the prefix cache's
        assert srv.generate(prompts[0], max_new_tokens=10,
                            timeout=120) == want[0]
        assert srv.health()["ok"] is True
        deadline = time.time() + 10
        while srv.scheduler.running and time.time() < deadline:
            time.sleep(0.01)
        eng.audit_quiescent()
    finally:
        srv.close()


@pytest.mark.parametrize("step, failed", [("decode", 0), ("prefill", 1)])
def test_server_keeps_the_narrow_handling_for_a_fault_before_launch(
        tiny_lm, step, failed):
    """The same wrapped step raising with the pools whole: a decode fault
    replays the batch and nothing else, a prefill fault fails its one
    request; nothing is remade."""
    params, cfg = tiny_lm
    opts = dict(max_batch=4, block_size=BS)
    prompts = [arith_prompt(1, 1, 9), arith_prompt(5, 2, 20)]
    want = undisturbed(params, cfg, prompts, 10, **opts)
    srv = serving.serve((params, cfg), **opts)
    try:
        state = fail_once(srv.engine.model, step, consumed=False, after=1)
        reqs = [srv.submit(p, max_new_tokens=10) for p in prompts]
        got = []
        for r in reqs:
            try:
                got.append(r.result(timeout=120))
            except mx.MXNetError as e:
                assert "prefill failed" in str(e)
                got.append(None)
        assert state["fired"] == 1 and srv.engine.pools_lost == 0
        assert got.count(None) == failed
        assert all(g == w for g, w in zip(got, want) if g is not None)
        snap = srv.snapshot()["requests"]
        assert snap["failed"] == failed and snap["engine_failures"] == 1
        assert srv.engine.cache.pool.in_use == 0
    finally:
        srv.close()
