"""mxnet_tpu.serving tests: paged KV-cache invariants, decode-vs-dense
equivalence, continuous-batching fairness, and the jit recompile bound.

The load-bearing claims: (1) the block pool never double-hands-out or
leaks blocks; (2) a paged-cache decode step produces the SAME logits as
the dense full-sequence forward (fp32 tolerance); (3) a late request is
admitted as soon as a batch slot frees (no starvation); (4) a mixed-
length multi-client run stays within the bucketed compile bound (<= 4
distinct decode compilations).
"""
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import serving
from mxnet_tpu.serving import kv_cache
from mxnet_tpu.models.transformer import (TransformerConfig,
                                          init_transformer_params,
                                          transformer_apply)


def tiny_cfg(**kw):
    base = dict(vocab=48, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                max_len=64)
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = tiny_cfg()
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


def arith_prompt(start, stride, n, vocab=48):
    return [(start + stride * t) % vocab for t in range(n)]


# ---------------------------------------------------------------------------
# block pool / block table invariants
# ---------------------------------------------------------------------------


def test_block_pool_alloc_free_reuse():
    pool = kv_cache.BlockPool(8)            # ids 1..7 allocatable
    assert pool.available == 7 and pool.in_use == 0
    a = pool.try_alloc(3)
    b = pool.try_alloc(2)
    assert len(set(a) | set(b)) == 5        # all distinct
    assert 0 not in a + b                   # null block never handed out
    assert pool.in_use == 5 and pool.available == 2
    # transient exhaustion -> None (backpressure), not an exception
    assert pool.try_alloc(3) is None
    pool.free(a)
    assert pool.available == 5
    c = pool.try_alloc(3)
    assert set(c) <= set(a)                 # freed blocks are reused
    # double-free and foreign-id free both raise
    pool.free(b)
    with pytest.raises(mx.MXNetError):
        pool.free(b)
    with pytest.raises(mx.MXNetError):
        pool.free([0])
    # a request larger than the whole pool can never succeed
    with pytest.raises(kv_cache.CacheOverflow):
        pool.try_alloc(8)


def test_block_pool_rejects_degenerate():
    with pytest.raises(mx.MXNetError):
        kv_cache.BlockPool(1)               # only the null block


def test_engine_releases_blocks(tiny_lm):
    params, cfg = tiny_lm
    eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=2,
                         block_size=8)
    seqs = [eng.start(arith_prompt(i, 1, 5 + i), max_new=4)
            for i in range(2)]
    assert eng.cache.pool.in_use > 0
    while any(not s.done for s in seqs):
        eng.decode_step(seqs)
    for s in seqs:
        eng.release(s)
    assert eng.cache.pool.in_use == 0       # no leaked blocks
    assert eng.cache.pool.available == eng.cache.num_blocks - 1


# ---------------------------------------------------------------------------
# decode equivalence vs the dense full-sequence forward
# ---------------------------------------------------------------------------


def test_paged_decode_matches_dense_forward(tiny_lm):
    """Every decode step's logits must equal the dense causal forward
    over the full token history — the paged cache is a pure layout
    change, not an approximation. Two sequences of different lengths run
    batched to exercise per-row masking."""
    params, cfg = tiny_lm
    eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=4,
                         block_size=8, keep_logits=True)
    s1 = eng.start(arith_prompt(1, 1, 9), max_new=6)    # crosses blocks
    s2 = eng.start(arith_prompt(5, 2, 4), max_new=6)

    def dense_last(tokens):
        toks = jnp.asarray([tokens], jnp.int32)
        return np.asarray(transformer_apply(params, toks, cfg),
                          np.float32)[0, -1]

    # prefill logits == dense logits at the prompt's last position
    for s in (s1, s2):
        np.testing.assert_allclose(
            s.last_logits, dense_last(s.tokens[:s.prompt_len]),
            rtol=1e-4, atol=1e-5)
    for _ in range(5):
        eng.decode_step([s1, s2])
        for s in (s1, s2):
            np.testing.assert_allclose(
                s.last_logits, dense_last(s.tokens[:-1]),
                rtol=1e-4, atol=1e-5)
    for s in (s1, s2):
        eng.release(s)


def test_decode_greedy_tokens_match_dense_rollout(tiny_lm):
    """The whole generated string (argmax chain) matches a dense
    re-forward rollout."""
    params, cfg = tiny_lm
    eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=1,
                         block_size=8)
    prompt = arith_prompt(3, 1, 7)
    seq = eng.start(list(prompt), max_new=8)
    while not seq.done:
        eng.decode_step([seq])
    eng.release(seq)

    ref = list(prompt)
    for _ in range(8):
        logits = np.asarray(transformer_apply(
            params, jnp.asarray([ref], jnp.int32), cfg))[0, -1]
        ref.append(int(np.argmax(logits)))
    assert seq.tokens == ref


# ---------------------------------------------------------------------------
# continuous batching: fairness, backpressure, recompile bound
# ---------------------------------------------------------------------------


def test_late_request_gets_admitted(tiny_lm):
    """max_batch=2 with both slots busy: a third request queued later
    must be admitted when a slot frees and complete — continuous
    batching, not run-to-completion batches."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8)
    try:
        early = [srv.submit(arith_prompt(i, 1, 6), max_new_tokens=12)
                 for i in range(2)]
        late = srv.submit(arith_prompt(9, 2, 6), max_new_tokens=4)
        out = late.result(timeout=120)
        assert len(out) == 4
        for r in early:
            assert len(r.result(timeout=120)) == 12
        # the late request entered while an early one was still running
        assert late.t_admit is not None
        snap = srv.snapshot()
        assert snap["requests"]["completed"] == 3
        assert snap["cache"]["blocks_in_use"] == 0   # all recycled
    finally:
        srv.close()


def test_queue_backpressure(tiny_lm):
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=1, max_queue=2,
                        block_size=8)
    try:
        reqs = []
        with pytest.raises(serving.QueueFull):
            for _ in range(16):             # 1 running + 2 queued max
                reqs.append(srv.submit([1, 2, 3], max_new_tokens=32))
        assert len(reqs) >= 2
        assert srv.snapshot()["requests"]["rejected"] >= 1
        for r in reqs:
            r.result(timeout=120)
    finally:
        srv.close()


def test_oversized_prompt_rejected_not_fatal(tiny_lm):
    """A prompt longer than max_len is the client's error: submit raises
    immediately and the serving loop keeps serving everyone else."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8)
    try:
        with pytest.raises(mx.MXNetError):
            srv.submit(list(range(cfg.max_len + 1)), max_new_tokens=4)
        # the server survived: a normal request still completes
        out = srv.generate(arith_prompt(1, 1, 5), max_new_tokens=3,
                           timeout=120)
        assert len(out) == 3
    finally:
        srv.close()


def test_queue_timeout_counts_once(tiny_lm):
    """An expired request fails exactly once in the metrics (expired=1,
    failed=1 — not double-counted)."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=1, block_size=8,
                        queue_timeout=0.02)
    try:
        # the blocker is admitted instantly (empty queue) and holds the
        # only slot for 40 host-synced decode steps — far longer than the
        # 20 ms the victim is allowed to wait behind it
        blocker = srv.submit(arith_prompt(0, 1, 17), max_new_tokens=40)
        time.sleep(0.05)
        victim = srv.submit(arith_prompt(1, 1, 5), max_new_tokens=4)
        with pytest.raises(serving.RequestTimeout):
            victim.result(timeout=120)
        blocker.result(timeout=120)
        snap = srv.snapshot()
        assert snap["requests"]["expired"] == 1
        assert snap["requests"]["failed"] == 1
        assert snap["requests"]["completed"] == 1
    finally:
        srv.close()


def test_decode_recompile_bound_mixed_lengths(tiny_lm):
    """Three clients with different prompt lengths, staggered so the
    active batch crosses 1 -> 2 -> 3: the bucketed decode step must stay
    within <= 4 distinct jit compilations (the acceptance bound)."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=4, block_size=8)
    try:
        results = {}

        def client(i, delay, plen):
            time.sleep(delay)
            results[i] = srv.generate(arith_prompt(i, 1, plen),
                                      max_new_tokens=10, timeout=120)

        threads = [threading.Thread(target=client, args=(i, 0.05 * i, p))
                   for i, p in enumerate((5, 9, 17))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(len(results[i]) == 10 for i in range(3))
        eng = srv.engine
        assert eng.decode_compilations <= 4, (
            "decode recompiled %d times" % eng.decode_compilations)
        # cross-check the proxy counter against jax's own jit cache
        jit_fn = eng.model.programs["decode"]
        if hasattr(jit_fn, "_cache_size"):
            assert jit_fn._cache_size() <= 4
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# full-forward adapters: exported artifact and Gluon Block
# ---------------------------------------------------------------------------


def test_exported_artifact_serving_matches_live(tiny_lm, tmp_path):
    """A .mxtpu artifact (predict.export_model) serves through the same
    scheduler and reproduces the live paged-cache engine's greedy
    tokens."""
    from mxnet_tpu import predict
    from mxnet_tpu.ndarray import NDArray
    params, cfg = tiny_lm

    class FullForward:
        def __call__(self, toks):
            return NDArray(transformer_apply(
                params, toks._data.astype(jnp.int32), cfg))

    art = str(tmp_path / "lm.mxtpu")
    predict.export_model(FullForward(), [("tokens", (2, cfg.max_len))],
                         art, input_dtypes={"tokens": "int32"})

    prompts = [arith_prompt(2, 1, 6), arith_prompt(11, 2, 9)]
    live = serving.serve((params, cfg), max_batch=2, block_size=8)
    try:
        want = [live.generate(p, max_new_tokens=5, timeout=120)
                for p in prompts]
    finally:
        live.close()
    srv = serving.serve(art, max_batch=2)
    try:
        got = [srv.generate(p, max_new_tokens=5, timeout=120)
               for p in prompts]
    finally:
        srv.close()
    assert got == want


def test_gluon_block_serving_runs(tiny_lm):
    """Any Gluon causal LM Block serves through the full-forward path
    (here the word-LM RNN, time-major)."""
    net = mx.models.RNNModel(mode="lstm", vocab_size=32, num_embed=16,
                             num_hidden=16, num_layers=1, dropout=0.0)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((4, 2)))                 # materialize params
    srv = serving.serve(net, vocab=32, max_len=32, time_major=True,
                        max_batch=2)
    try:
        out = srv.generate([1, 2, 3, 4], max_new_tokens=6, timeout=120)
        assert len(out) == 6
        assert all(0 <= t < 32 for t in out)
    finally:
        srv.close()


def test_http_frontend(tiny_lm):
    import json
    import urllib.request
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8)
    try:
        host, port = srv.serve_http(port=0, block=False)
        url = "http://%s:%d" % (host, port)
        req = urllib.request.Request(
            url + "/v1/generate",
            data=json.dumps({"tokens": arith_prompt(4, 1, 6),
                             "max_new_tokens": 5}).encode(),
            headers={"Content-Type": "application/json"})
        body = json.loads(urllib.request.urlopen(req, timeout=60).read())
        assert len(body["tokens"]) == 5 and body["prompt_len"] == 6
        met = json.loads(urllib.request.urlopen(
            url + "/v1/metrics", timeout=10).read())
        assert met["requests"]["completed"] == 1
        health = json.loads(urllib.request.urlopen(
            url + "/healthz", timeout=10).read())
        assert health["ok"] is True and health["loop_alive"] is True
        assert health["last_beat_age_s"] < 5.0
        assert health["engine_failures"] == 0
    finally:
        srv.close()


def test_eos_stops_generation(tiny_lm):
    params, cfg = tiny_lm
    eng = serving.Engine(serving.TransformerLM(params, cfg), max_batch=1,
                         block_size=8)
    seq = eng.start(arith_prompt(0, 1, 6), max_new=32)
    # the trained-free model is deterministic; whatever it emits next,
    # declaring THAT token as eos must stop generation at length 1
    first = seq.tokens[-1]
    eng.release(seq)
    seq2 = eng.start(arith_prompt(0, 1, 6), max_new=32, eos_id=first)
    assert seq2.done and len(seq2.generated) == 1
    eng.release(seq2)


def test_serving_metrics_snapshot(tiny_lm):
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8)
    try:
        srv.generate(arith_prompt(1, 1, 5), max_new_tokens=4, timeout=120)
        snap = srv.snapshot()
        assert snap["throughput"]["tokens_generated"] >= 3
        assert snap["latency_ms"]["total_mean"] > 0
        assert snap["batch"]["mean_occupancy"] <= 1.0
        assert snap["engine"]["decode_compilations"] >= 1
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# fault isolation: an engine exception fails requests, never the loop
# ---------------------------------------------------------------------------


def test_engine_prefill_exception_fails_request_not_loop(tiny_lm):
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8)
    try:
        real_start = srv.engine.start
        boom = {"armed": True}

        def flaky_start(*a, **kw):
            if boom.pop("armed", None):
                raise RuntimeError("injected prefill fault")
            return real_start(*a, **kw)

        srv.engine.start = flaky_start
        req = srv.submit(arith_prompt(2, 1, 5), max_new_tokens=4)
        with pytest.raises(mx.MXNetError, match="prefill failed"):
            req.result(timeout=60)
        # the loop survived: the next request completes normally
        out = srv.generate(arith_prompt(3, 1, 5), max_new_tokens=4,
                           timeout=120)
        assert len(out) == 4
        snap = srv.snapshot()
        assert snap["requests"]["engine_failures"] == 1
        assert snap["requests"]["failed"] == 1
        assert snap["requests"]["completed"] == 1
        assert srv.health()["ok"] is True
    finally:
        srv.close()


def test_engine_decode_exception_resumes_batch_not_loop(tiny_lm):
    """ISSUE 11: a decode fault poisons the STEP, not the history — the
    batch's requests are re-queued as failover replays (prompt +
    generated-so-far re-prefills, decode continues) and complete
    token-identically to an undisturbed run; the loop survives and the
    faulted sequences' blocks are recycled."""
    params, cfg = tiny_lm
    oracle = serving.serve((params, cfg), max_batch=2, block_size=8)
    try:
        want = oracle.generate(arith_prompt(4, 1, 5), max_new_tokens=4,
                               timeout=120)
    finally:
        oracle.close()
    srv = serving.serve((params, cfg), max_batch=2, block_size=8)
    try:
        real_decode = srv.engine.decode_pass
        boom = {"armed": True}

        def flaky_decode(*args, **kw):
            if boom.pop("armed", None):
                raise RuntimeError("injected decode fault")
            return real_decode(*args, **kw)

        srv.engine.decode_pass = flaky_decode
        req = srv.submit(arith_prompt(4, 1, 5), max_new_tokens=4)
        assert req.result(timeout=120) == want
        snap = srv.snapshot()
        assert snap["requests"]["engine_failures"] == 1
        assert snap["requests"]["failovers"] == 1
        assert snap["requests"]["failed"] == 0
        # blocks recycled, loop alive: a fresh request decodes fine and
        # /healthz stays green
        out = srv.generate(arith_prompt(5, 1, 5), max_new_tokens=4,
                           timeout=120)
        assert len(out) == 4
        h = srv.health()
        assert h["ok"] is True and h["engine_failures"] == 1
        pool = srv.engine.cache.pool
        assert pool.in_use == 0  # everything released despite the fault
    finally:
        srv.close()


def test_engine_decode_fault_budget_exhausted_surfaces_error(tiny_lm):
    """A PERSISTENT decode fault must not bounce a request between
    resume hops forever: after max_failovers replays the engine error
    surfaces to the client."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8)
    try:
        def dead_decode(*args, **kw):
            raise RuntimeError("persistent decode fault")

        srv.engine.decode_pass = dead_decode
        req = srv.submit(arith_prompt(4, 1, 5), max_new_tokens=4)
        with pytest.raises(mx.MXNetError, match="decode failed"):
            req.result(timeout=120)
        snap = srv.snapshot()
        assert snap["requests"]["engine_failures"] >= 3
        assert snap["requests"]["failed"] == 1
        assert srv.engine.cache.pool.in_use == 0
        assert srv.health()["ok"] is True
    finally:
        srv.close()


def test_health_reports_closed_loop(tiny_lm):
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8)
    srv.generate(arith_prompt(6, 1, 5), max_new_tokens=2, timeout=120)
    h = srv.health()
    assert h["ok"] and h["last_step_age_s"] is not None
    srv.close()
    assert srv.health()["ok"] is False
    assert srv.health()["loop_alive"] is False


# ---------------------------------------------------------------------------
# paged-attention path: contiguous-per-layer pool, chunked prefill,
# token-budget co-scheduling (MXNET_PAGED_ATTENTION / Engine(paged=True))
# ---------------------------------------------------------------------------


def test_block_pool_high_water_and_layout():
    """Contiguous-per-layer layout invariants: the pool carries an
    explicit (num_blocks, block_size) split, a write through flat slots
    lands in the block a table gather reads back, and the free list's
    high-water mark tracks peak in-use across alloc/free cycles."""
    pool = kv_cache.BlockPool(8)
    assert pool.high_water == 0
    a = pool.try_alloc(5)
    assert pool.high_water == 5
    pool.free(a[:3])
    assert pool.high_water == 5             # high water survives frees
    b = pool.try_alloc(4)
    assert pool.high_water == 6
    pool.free(a[3:] + b)
    assert pool.in_use == 0 and pool.high_water == 6

    cache = kv_cache.PagedKVCache(n_layers=2, n_heads=2, head_dim=4,
                                  block_size=4, num_blocks=6)
    assert cache.k.shape == (2, 6, 2, 4, 4)     # (L, nb, H, bs, Dh)
    # write positions 0..5 of a sequence whose table is [3, 1] and read
    # them back by table: position order must round-trip exactly
    table = np.asarray([3, 1], np.int32)
    pos = jnp.arange(6)
    slots = jnp.asarray(table)[pos // 4] * 4 + pos % 4
    kv = jnp.arange(6 * 2 * 4, dtype=jnp.float32).reshape(6, 2, 4)
    k, v = kv_cache.write_kv(cache.k, cache.v, 1, slots, kv, 2 * kv)
    ks, vs = kv_cache.gather_kv(k, v, 1, jnp.asarray(table[None]))
    assert ks.shape == (1, 2, 2, 4, 4)          # the blocks as they lie

    def by_position(blocks):                    # (nblk,H,bs,Dh) -> (T,H,Dh)
        return np.asarray(blocks).transpose(0, 2, 1, 3).reshape(8, 2, 4)

    np.testing.assert_array_equal(by_position(ks[0])[:6], np.asarray(kv))
    np.testing.assert_array_equal(by_position(vs[0])[:6], 2 * np.asarray(kv))
    # layer 0 untouched
    assert float(jnp.abs(k[0]).sum()) == 0.0


def test_paged_decode_recompile_bound_mixed_lengths(tiny_lm):
    """The paged-path analogue of the decode-recompile-bound test: three
    staggered clients with prompt lengths 5/9/17. Chunked prefill must
    stay within <= 2 distinct prefill signatures (ONE chunk shape x two
    table-width buckets — down from one dense signature per length
    bucket), and the width-bucketed decode step within <= 6 (batch
    buckets x width buckets, both bounded by traffic-independent
    powers of two)."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=4, block_size=8,
                        paged=True)
    try:
        assert srv.engine.paged
        results = {}

        def client(i, delay, plen):
            time.sleep(delay)
            results[i] = srv.generate(arith_prompt(i, 1, plen),
                                      max_new_tokens=10, timeout=120)

        threads = [threading.Thread(target=client, args=(i, 0.05 * i, p))
                   for i, p in enumerate((5, 9, 17))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(len(results[i]) == 10 for i in range(3))
        eng = srv.engine
        assert eng.prefill_compilations <= 2, (
            "chunked prefill compiled %d signatures: %r"
            % (eng.prefill_compilations, sorted(eng._sigs)))
        assert eng.decode_compilations <= 6, (
            "paged decode compiled %d signatures: %r"
            % (eng.decode_compilations, sorted(eng._sigs)))
    finally:
        srv.close()


def test_chunked_prefill_does_not_starve_decode(tiny_lm):
    """Fairness: a long prompt streaming through prefill chunks under a
    token budget cannot starve in-flight decode sequences — the loop
    runs a decode step between chunk batches, so the short request keeps
    generating while the long prompt prefills."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8,
                        paged=True, prefill_chunk=8, token_budget=9)
    try:
        events = []
        real_chunk = srv.engine.prefill_step
        real_decode = srv.engine.decode_pass

        def chunk_spy(seq):
            events.append(("chunk", seq.request.id
                           if seq.request else None))
            return real_chunk(seq)

        def decode_spy(*args, **kw):
            events.append(("decode", None))
            return real_decode(*args, **kw)

        srv.engine.prefill_step = chunk_spy
        srv.engine.decode_pass = decode_spy
        # the short request decodes while the long prompt prefills
        short = srv.submit(arith_prompt(1, 1, 4), max_new_tokens=60)
        deadline = time.perf_counter() + 60
        while srv.snapshot()["throughput"]["tokens_generated"] < 2:
            assert time.perf_counter() < deadline
            time.sleep(0.01)
        long_req = srv.submit(arith_prompt(2, 1, 40), max_new_tokens=2)
        out = long_req.result(timeout=120)
        assert len(out) == 2
        # budget 9 = 1 decode token + 1 chunk: the 5 chunks of the long
        # prompt spread across iterations with decode steps in between
        chunk_idx = [i for i, (kind, rid) in enumerate(events)
                     if kind == "chunk" and rid == long_req.id]
        assert len(chunk_idx) == 5, events
        decodes_between = sum(
            1 for i in range(chunk_idx[0], chunk_idx[-1])
            if events[i][0] == "decode")
        assert decodes_between >= 2, events
        assert len(short.result(timeout=120)) == 60
    finally:
        srv.close()


def test_token_budget_bounds_admission():
    """Scheduler unit test: admission stops once the decode batch plus
    pending prefill chunks would exceed the token budget, FIFO order
    preserved; with nothing running the head is always admitted
    (progress)."""

    class FakeEngine:
        def can_admit(self, plen, max_new):
            return True

        def prefill_tokens_per_step(self, plen):
            return 8

    sched = serving.Scheduler(max_batch=8, token_budget=16)
    reqs = [serving.Request([1, 2, 3]) for _ in range(4)]
    for r in reqs:
        sched.submit(r)
    sched.running = [object(), object()]     # 2 decode tokens committed
    admitted, expired = sched.admit(FakeEngine())
    assert not expired
    assert [r.id for r in admitted] == [reqs[0].id]  # 2+8=10; +8 > 16
    assert sched.pending() == 3
    # progress guarantee: an over-budget head is admitted when idle
    sched2 = serving.Scheduler(max_batch=8, token_budget=4)
    r = serving.Request([1, 2, 3])
    sched2.submit(r)
    admitted, _ = sched2.admit(FakeEngine())
    assert [a.id for a in admitted] == [r.id]


def test_paged_prefill_fault_releases_blocks(tiny_lm):
    """A fault inside a prefill CHUNK fails that request, recycles its
    already-allocated blocks, and leaves the loop serving (the paged
    analogue of the dense prefill fault-isolation test)."""
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8,
                        paged=True, prefill_chunk=8)
    try:
        real_step = srv.engine.prefill_step
        boom = {"armed": True}

        def flaky_step(seq):
            if boom.pop("armed", None):
                raise RuntimeError("injected chunk fault")
            return real_step(seq)

        srv.engine.prefill_step = flaky_step
        req = srv.submit(arith_prompt(3, 1, 20), max_new_tokens=4)
        with pytest.raises(mx.MXNetError, match="prefill failed"):
            req.result(timeout=60)
        out = srv.generate(arith_prompt(4, 1, 5), max_new_tokens=4,
                           timeout=120)
        assert len(out) == 4
        snap = srv.snapshot()
        assert snap["requests"]["engine_failures"] == 1
        assert snap["requests"]["failed"] == 1
        assert snap["cache"]["blocks_in_use"] == 0   # fault-path recycle
        assert srv.health()["ok"] is True
    finally:
        srv.close()


def test_paged_metrics_in_http_output(tiny_lm):
    """The /metrics HTTP body carries the new observables: per-path
    decode counters, prefill-chunk count and queue depth, block-pool
    in-use/available/high-water, and the scheduler's token budget."""
    import json
    import urllib.request
    params, cfg = tiny_lm
    srv = serving.serve((params, cfg), max_batch=2, block_size=8,
                        paged=True, prefill_chunk=8, token_budget=32)
    try:
        host, port = srv.serve_http(port=0, block=False)
        url = "http://%s:%d" % (host, port)
        req = urllib.request.Request(
            url + "/v1/generate",
            data=json.dumps({"tokens": arith_prompt(4, 1, 12),
                             "max_new_tokens": 5}).encode(),
            headers={"Content-Type": "application/json"})
        body = json.loads(urllib.request.urlopen(req, timeout=60).read())
        assert len(body["tokens"]) == 5
        met = json.loads(urllib.request.urlopen(
            url + "/v1/metrics", timeout=10).read())
        assert met["paths"]["paged_decode_steps"] >= 4
        assert met["paths"]["gather_decode_steps"] == 0
        assert met["paths"]["prefill_chunks"] >= 2    # 12 tokens, chunk 8
        assert met["paths"]["prefill_queue_depth"] == 0
        assert met["cache"]["blocks_in_use"] == 0
        assert met["cache"]["blocks_high_water"] >= 1
        assert met["cache"]["blocks_available"] >= 1
        assert met["scheduler"]["token_budget"] == 32
        assert met["engine"]["paged_attention"] is True
        assert met["engine"]["prefill_chunk"] == 8
    finally:
        srv.close()


def test_paged_off_env_restores_gather_path(tiny_lm, monkeypatch):
    """MXNET_PAGED_ATTENTION=0 (or unset) keeps the PR 1 gather decode:
    no paged steps, no chunked prefill, dense prefill signatures."""
    params, cfg = tiny_lm
    monkeypatch.setenv("MXNET_PAGED_ATTENTION", "0")
    srv = serving.serve((params, cfg), max_batch=2, block_size=8)
    try:
        assert srv.engine.paged is False
        out = srv.generate(arith_prompt(8, 1, 9), max_new_tokens=3,
                           timeout=120)
        assert len(out) == 3
        snap = srv.snapshot()
        assert snap["paths"]["paged_decode_steps"] == 0
        assert snap["paths"]["gather_decode_steps"] >= 2
        assert snap["paths"]["prefill_chunks"] == 0
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# the whole-prompt prefill's attention: which program holds the kernel
# (ops/pallas_prompt_attention.py), and who says so (ISSUE 41)
# ---------------------------------------------------------------------------


def _tiny_family(name):
    """The old family, a Trinity-shaped model (grouped queries, window
    and full layers) and a Falcon-H1-shaped one (a recurrent state
    beside its keys and values), each at its own tiny defaults."""
    from mxnet_tpu.models import afmoe, falcon_h1
    key = jax.random.PRNGKey(3)
    if name == "dense":
        cfg = tiny_cfg()
        return init_transformer_params(key, cfg), cfg
    if name == "kinds":
        cfg = afmoe.AfmoeConfig(max_len=64)
        return afmoe.init_afmoe_params(key, cfg), cfg
    cfg = falcon_h1.FalconH1Config(max_len=64)
    return falcon_h1.init_falcon_h1_params(key, cfg), cfg


@pytest.mark.parametrize("name", ["dense", "kinds", "state"])
def test_a_cpu_engine_scores_prompts_with_xla_and_says_why(name):
    from mxnet_tpu import telemetry
    telemetry.tracing.clear()
    srv = serving.serve(_tiny_family(name), max_batch=2, block_size=8)
    try:
        srv.submit(arith_prompt(1, 5, 11), max_new_tokens=3).result(
            timeout=120)
        eng, snap = srv.engine, srv.snapshot()
        assert "the backend is cpu" in eng.prompt_attn_fallback
        assert snap["engine"]["prompt_attn_fallback"] \
            == eng.prompt_attn_fallback
        prefills = [s["attrs"] for s in telemetry.spans()
                    if s["name"] == "serving.prefill"]
        assert [(a["bucket"], a["attn"]) for a in prefills] == [(16, "xla")]
        assert snap["throughput"]["prefills_attn_kernel"] == 0
        assert "serving_prefills_attn_kernel_total" in srv.prometheus_text()
    finally:
        srv.close()


def test_the_prompt_attention_gates_reasons():
    from mxnet_tpu.ops.pallas_prompt_attention import (
        MIN_BUCKET, prompt_attention_unfit)
    bf16 = jnp.bfloat16
    assert prompt_attention_unfit(8192, 128, 6, bf16, "tpu") is None
    assert prompt_attention_unfit(MIN_BUCKET, 128, 5, bf16, "tpu") is None
    # what an engine knows once leaves the bucket out
    assert prompt_attention_unfit(None, 128, 1, bf16, "tpu") is None
    assert prompt_attention_unfit(8192, 128, 6, bf16, "cpu").startswith(
        "the backend is cpu: the kernel is compiled for the TPU")
    assert prompt_attention_unfit(8192, 64, 6, bf16, "tpu") \
        == "head_dim 64 is not a multiple of the 128-lane tile"
    assert prompt_attention_unfit(MIN_BUCKET // 2, 128, 6, bf16, "tpu") \
        == ("a bucket of %d rows is not a power of two of at least %d: "
            "XLA's own is no slower there" % (MIN_BUCKET // 2, MIN_BUCKET))
    assert "do not fit the kernel's VMEM" in prompt_attention_unfit(
        8192, 128, 64, bf16, "tpu")
    # a latent pool's prompts are another function's
    assert "`expanded_attention`" in kv_cache.prompt_attn_unfit(
        jnp.zeros((1, 2, 8, 640), bf16), 1, layout="latent")
