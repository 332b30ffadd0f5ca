#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process, `python chip_smoke.py` from the root of a checkout. It drives
the two main paths through the entry points a user calls — a trainer
(`mx.parallel.TrainStep`) and a server (`mx.serving.serve`) — at the full
width of the configurations the benchmark uses (depth is what the model
zoo and `bench.py` use; weights are random, from a seed), compiles every
Pallas kernel with Mosaic, and checks what comes out by the repo's own
means: finite losses that fall, parameters that live on the device, greedy
tokens that agree with the gather path and with the plain f32 forward of
`models/transformer.py`, kernels that agree with their XLA references.

It prints one JSON line per leg and, last, `{"ok": true, "device": ...}`;
any failed check raises, so nothing is printed after it and the exit code
is not 0. Without a TPU it exits non-zero before building anything. The
only other mode is `--rehearse`: every size shrunk, the CPU and the Pallas
interpreter allowed, every line labelled `"rehearsal": true` — for
debugging the script before chip time is spent, and for tier-1. A
rehearsal proves the control flow; it says nothing about the device.

No number printed here is a benchmark: seconds are reported so that a run
that suddenly takes ten times as long is seen, not to be compared.
"""
import argparse
import json
import math
import sys
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes; CPU and interpret mode allowed; "
                         "every line says \"rehearsal\": true")
    rehearse = ap.parse_args().rehearse

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not rehearse:
        print("chip_smoke: jax found no TPU (platform %r); nothing was run. "
              "--rehearse runs a shrunken copy on this backend."
              % dev.platform, file=sys.stderr)
        return 1

    from mxnet_tpu.base import enable_compile_cache
    Smoke(device, rehearse, enable_compile_cache()).run()
    return 0


class Smoke:
    def __init__(self, device, rehearse, cache_dir):
        self.device = device
        self.rehearse = rehearse
        self.cache_dir = cache_dir

    def emit(self, leg, **fields):
        line = {"leg": leg, "platform": self.device["platform"],
                "device_kind": self.device["kind"],
                "device_count": self.device["count"]}
        if self.rehearse:
            line["rehearsal"] = True
        line.update(fields)
        print(json.dumps(line), flush=True)

    def run(self):
        import jax
        t0 = time.perf_counter()
        self.emit("device", jax=jax.__version__,
                  compile_cache_dir=self.cache_dir)
        self.trainer("trainer")
        self.server()
        self.kernels()
        if self.device["count"] >= 4:
            self.four_chips()
        else:
            self.emit("four_chips", ran=False,
                      reason="needs jax.device_count() >= 4, have %d; "
                             "not a pass" % self.device["count"])
        self.emit("total", seconds=round(time.perf_counter() - t0, 1))
        last = {"ok": True, "device": self.device}
        if self.rehearse:
            last["rehearsal"] = True
        print(json.dumps(last), flush=True)

    # -- compile accounting: the watchdog every framework jit goes through --

    def compiles(self):
        from mxnet_tpu.telemetry import introspect
        return introspect.watchdog().mark()

    def compiles_since(self, mark):
        """(count, seconds) of the compilations recorded after `mark`."""
        from mxnet_tpu.telemetry import introspect
        evs = [e for e in introspect.watchdog().events() if e["seq"] > mark]
        return len(evs), round(sum(e["seconds"] for e in evs), 2)

    # -- leg: trainer -----------------------------------------------------

    def trainer(self, leg, mesh=None):
        """Model-zoo ResNet-50, batch 256, 224 px, bf16 compute over f32
        masters, SGD momentum: bench.py's `bench_resnet` construction."""
        import jax
        import jax.numpy as jnp
        import mxnet_tpu as mx
        from mxnet_tpu.gluon import loss as gloss
        from mxnet_tpu.gluon.model_zoo import vision
        from mxnet_tpu.parallel.trainer import TrainStep

        name, batch, image = (("resnet18_v1", 8, 32) if self.rehearse
                              else ("resnet50_v1", 256, 224))
        mx.random.seed(0)
        net = getattr(vision, name)()
        net.initialize(mx.init.Xavier())
        net(mx.nd.zeros((1, 3, image, image)))
        step = TrainStep(net, gloss.SoftmaxCrossEntropyLoss(), "sgd",
                         {"learning_rate": 0.05, "momentum": 0.9,
                          "wd": 1e-4}, dtype="bfloat16", mesh=mesh)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.uniform(-1, 1, (batch, 3, image, image))
                        .astype(np.float32))
        y = jnp.asarray(rng.randint(0, 1000, (batch,)).astype(np.int32))

        mark = self.compiles()
        t0 = time.perf_counter()
        losses = [float(step(x, y))]                    # compile + warm-up
        first_s = time.perf_counter() - t0
        n_compiles, compile_s = self.compiles_since(mark)
        steps = 4
        t0 = time.perf_counter()
        device_losses = [step(x, y) for _ in range(steps)]
        jax.block_until_ready((device_losses, step._grad_vals))
        run_s = time.perf_counter() - t0
        losses += [float(v) for v in device_losses]

        check(all(math.isfinite(v) for v in losses),
              "non-finite loss: %r" % losses)
        check(losses[-1] < losses[0],
              "loss did not fall on a repeated batch: %r" % losses)
        check(n_compiles >= 1, "the watchdog saw no train.step compile")
        homes = {d for v in step._grad_vals for d in v.devices()}
        check({d.platform for d in homes} == {self.device["platform"]},
              "parameters live on %r" % homes)
        if mesh is not None:
            check(homes == set(mesh.devices.flat),
                  "parameters on %r, mesh is %r" % (homes, mesh))
        self.emit(leg, model=name, batch=batch, image=image,
                  dtype="bfloat16", masters="float32",
                  mesh=dict(mesh.shape) if mesh is not None else None,
                  param_devices=len(homes), compile_total=n_compiles,
                  compile_s=compile_s, first_step_s=round(first_s, 2),
                  steps=steps, run_s=round(run_s, 3),
                  losses=[round(v, 4) for v in losses])

    # -- leg: server ------------------------------------------------------

    def lm(self):
        """The serving configuration every bench leg uses, its random f32
        parameters, and a mixed wave of (prompt, new tokens)."""
        import jax
        from mxnet_tpu.models.transformer import (TransformerConfig,
                                                  init_transformer_params)
        if self.rehearse:
            cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                    n_layers=1, d_ff=64, max_len=64)
            lens, news = (3, 20, 40), (8, 12, 8)
        else:
            cfg = TransformerConfig(vocab=8192, d_model=512, n_heads=4,
                                    n_layers=4, d_ff=2048, max_len=1024)
            lens = (8, 40, 130, 300, 520, 900, 64, 700)
            news = (128, 96, 128, 64, 100, 64, 128, 80)
        params = init_transformer_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        wave = [([int(t) for t in rng.randint(1, cfg.vocab, n)], m)
                for n, m in zip(lens, news)]
        return cfg, params, wave

    def serve_wave(self, leg, model, wave, **kw):
        """One server through `serving.serve`, the wave submitted at once
        (so prefill chunks and decode steps of different requests
        interleave), then the same wave again once every shape is
        compiled. Returns the tokens of both waves."""
        import jax
        from mxnet_tpu import serving
        kw.setdefault("max_batch", 8)
        mark = self.compiles()
        srv = serving.serve(model, **kw)
        try:
            engines = [r.engine for r in getattr(srv, "replicas", [srv])]
            self.last_engines = engines
            for eng in engines:
                check(eng.paged is kw["paged"], "engine.paged is %r (%s)"
                      % (eng.paged, eng.paged_fallback))
                bad = {k: v for k, v in vars(eng).items()
                       if k.endswith("_fallback") and v is not None}
                if self.rehearse:
                    # off the chip the XLA loop is the gather walk's
                    # own answer (`walk_fallback_reason`), as XLA's
                    # blocks are a prompt's
                    bad.pop("walk_fallback", None)
                    bad.pop("prompt_attn_fallback", None)
                    bad.pop("state_step_fallback", None)
                    bad.pop("moe_fallback", None)
                elif "float32" in (eng.moe_fallback or ""):
                    # float32 experts keep the loop on the chip too: the
                    # grouped-product kernel's operands are bf16
                    bad.pop("moe_fallback")
                check(not bad, "fallbacks: %r" % bad)
                check(eng.tp == (kw.get("tp") or 1), "engine.tp is %r"
                      % eng.tp)
                check(eng.kv_quant is bool(kw.get("kv_quant")),
                      "engine.kv_quant is %r" % eng.kv_quant)
            pools = [d for eng in engines for d in eng.cache.k.devices()]
            check({d.platform for d in pools} == {self.device["platform"]},
                  "pools live on %r" % pools)
            # every replica on chips of its own
            check(len(set(pools)) == len(pools), "pools share devices: %r"
                  % pools)

            def run():
                t0 = time.perf_counter()
                reqs = [srv.submit(p, max_new_tokens=n) for p, n in wave]
                outs = [r.result(timeout=900) for r in reqs]
                return outs, time.perf_counter() - t0

            outs, first_s = run()
            n_compiles, compile_s = self.compiles_since(mark)
            mark = self.compiles()
            again, run_s = run()
            steady_compiles, _ = self.compiles_since(mark)
        finally:
            srv.close()
        for (p, n), o in zip(wave + wave, outs + again):
            check(len(o) == n and all(0 <= t < model[1].vocab for t in o),
                  "prompt of %d: asked %d tokens, got %r" % (len(p), n, o))
        self.emit(leg, d_model=model[1].d_model, n_heads=model[1].n_heads,
                  n_layers=model[1].n_layers, max_len=model[1].max_len,
                  vocab=model[1].vocab,
                  param_dtype=str(model[0]["embed"].dtype),
                  pool_dtype=str(engines[0].cache.k.dtype),
                  matmul_precision=jax.config.jax_default_matmul_precision,
                  options={k: v for k, v in kw.items() if k != "max_batch"},
                  max_batch=kw["max_batch"],
                  block_size=engines[0].cache.block_size,
                  prefill_chunk=engines[0].prefill_chunk,
                  prompts=[len(p) for p, _ in wave],
                  new_tokens=[n for _, n in wave],
                  pool_devices=sorted(d.id for d in pools),
                  compile_total=n_compiles, compile_s=compile_s,
                  first_wave_s=round(first_s, 2),
                  steady_wave_compiles=steady_compiles,
                  steady_wave_identical=again == outs,
                  run_s=round(run_s, 3))
        return outs, again

    def margin(self, cfg, params, wave, waves, forward=None):
        """How far the served tokens are from the argmax of the plain f32
        forward (`transformer_apply`, or `forward(params, tokens (B, S))`,
        teacher-forced, full precision): 0
        where a server agrees with the reference, the size of the tie it
        broke otherwise; the worst over `waves`. Batches are padded to one
        shape, so one compile. Two waves may differ from each other where
        they were batched differently, and only at such ties."""
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.models.transformer import transformer_apply
        forward = forward or (lambda p, t: transformer_apply(p, t, cfg))
        worst = 0.0
        for outs in waves:
            toks = np.zeros((len(wave), cfg.max_len), np.int32)
            for i, ((p, _), o) in enumerate(zip(wave, outs)):
                toks[i, :len(p) + len(o)] = p + o
            with jax.default_matmul_precision("highest"):
                logits = np.asarray(jax.jit(forward)(
                    params, jnp.asarray(toks)), np.float32)
            for i, ((p, _), o) in enumerate(zip(wave, outs)):
                rows = logits[i, len(p) - 1:len(p) + len(o) - 1]
                worst = max(worst, float(np.max(
                    rows.max(-1) - rows[np.arange(len(o)), o])))
        return worst

    def server(self):
        import jax
        import jax.numpy as jnp
        cfg, params, wave = self.lm()
        # f32: the XLA gather path and the Mosaic kernel can only be asked
        # for the same tokens when both multiply in f32 — at the TPU's
        # default precision an f32 dot is rounded through bf16 passes, and
        # differently in each. The serving thread traces the steps, so the
        # setting is the process-wide one, not the context manager.
        jax.config.update("jax_default_matmul_precision", "highest")
        try:
            gather = self.serve_wave("server_f32_gather", (params, cfg),
                                     wave, paged=False)
            paged = self.serve_wave("server_f32_paged", (params, cfg),
                                    wave, paged=True)
            self.kinds(wave, 1e-3)
            self.state(wave, 1e-3)
        finally:
            jax.config.update("jax_default_matmul_precision", None)
        # a token may differ from the gather path's only where the
        # reference itself shows a tie within float error
        tie = 1e-3
        m_gather = self.margin(cfg, params, wave, gather)
        m_paged = self.margin(cfg, params, wave, paged)
        differ = [i for i, (a, b) in enumerate(zip(gather[0], paged[0]))
                  if a != b]
        check(m_gather <= tie, "gather path strays from the f32 reference "
              "by %g" % m_gather)
        check(m_paged <= tie, "paged path strays from the f32 reference "
              "by %g" % m_paged)
        self.emit("server_f32_parity", requests=len(wave),
                  identical_to_gather=len(wave) - len(differ),
                  broke_a_tie=differ, tie_tolerance=tie,
                  max_margin_gather=m_gather, max_margin_paged=m_paged)

        # the pool dtype the bench uses, and the int8 pool: compiled
        # kernel, no fallback, tokens within the precision's budget of the
        # f32 reference over the same (rounded) parameters
        bf16 = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
        rounded = {k: v.astype(jnp.float32) for k, v in bf16.items()}
        for leg, model, kw, budget in (
                ("server_bf16_paged", (bf16, cfg), {}, 0.25),
                ("server_int8_kv", (params, cfg),
                 {"kv_quant": True, "block_size": 32}, 0.05)):
            waves = self.serve_wave(leg, model, wave, paged=True, **kw)
            ref = rounded if model[0] is bf16 else params
            m = self.margin(cfg, ref, wave, waves)
            check(m <= budget, "%s strays from the f32 reference by %g "
                  "(budget %g)" % (leg, m, budget))
            self.emit(leg + "_check", max_margin=m, budget=budget)

    def kinds(self, wave, tie):
        """The family of window and full layers over a cache of two kinds
        (`models/afmoe.py`), small: the same wave through `serving.serve`
        on its gather path, prompts longer than the window and rings that
        wrap, and its margin against its own dense forward in f32. Runs
        inside `server`'s full-precision stretch."""
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.models.afmoe import (AfmoeConfig, afmoe_apply,
                                            init_afmoe_params)
        if self.rehearse:
            cfg = AfmoeConfig(vocab=64, d_model=32, n_heads=4, n_kv_heads=2,
                              head_dim=8, n_layers=3, n_dense_layers=1,
                              layer_kinds=("window", "full", "window"),
                              window=16, d_ff=64, d_expert=16, n_experts=8,
                              experts_held=(2, 4), max_len=64)
        else:
            cfg = AfmoeConfig(vocab=8192, d_model=512, n_heads=8,
                              n_kv_heads=2, head_dim=128, n_layers=3,
                              n_dense_layers=1,
                              layer_kinds=("window", "full", "window"),
                              window=256, d_ff=2048, d_expert=512,
                              n_experts=32, experts_held=(8, 16),
                              max_len=1024)
        params = init_afmoe_params(jax.random.PRNGKey(1), cfg)
        waves = self.serve_wave("server_f32_kinds", (params, cfg), wave,
                                paged=False)
        m = self.margin(
            cfg, params, wave, waves,
            forward=lambda p, toks: jnp.stack(
                [afmoe_apply(p, t, cfg)[0] for t in toks]))
        check(m <= tie, "the two-kind cache strays from its f32 forward by "
              "%g" % m)
        self.emit("server_f32_kinds_check", max_margin=m, tie_tolerance=tie,
                  window=cfg.window, kv_heads=cfg.n_kv_heads,
                  experts_held=list(cfg.experts_held))

    def state(self, wave, tie):
        """The family with a recurrent state beside its keys and values in
        every layer (`models/falcon_h1.py`), small: the same wave through
        `serving.serve` on its gather path (prefill as a chunked scan,
        decode one recurrence step a row through the kernel where the gate
        lets it), twice, so every slot is given again, and its margin against
        its own dense forward in f32. Runs inside `server`'s full-precision
        stretch."""
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.models.falcon_h1 import (FalconH1Config,
                                                falcon_h1_apply,
                                                init_falcon_h1_params)
        if self.rehearse:
            cfg = FalconH1Config(vocab=64, max_len=64)
        else:
            cfg = FalconH1Config(vocab=8192, d_model=512, n_heads=8,
                                 n_kv_heads=2, head_dim=128, n_layers=2,
                                 d_ff=2048, ssm_heads=16, ssm_head_dim=128,
                                 ssm_state=64, ssm_groups=2, chunk=128,
                                 key_multiplier=0.5, ssm_in_multiplier=0.25,
                                 ssm_multipliers=(0.35, 0.25, 0.18, 0.5, 0.35),
                                 max_len=1024)
        params = init_falcon_h1_params(jax.random.PRNGKey(2), cfg)
        waves = self.serve_wave("server_f32_state", (params, cfg), wave,
                                paged=False)
        engines = self.last_engines
        m = self.margin(
            cfg, params, wave, waves,
            forward=lambda p, toks: jnp.stack(
                [falcon_h1_apply(p, t, cfg) for t in toks]))
        check(m <= tie, "the cache with a recurrent state strays from its "
              "f32 forward by %g" % m)
        self.emit("server_f32_state_check", max_margin=m, tie_tolerance=tie,
                  state_dtype=str(engines[0].cache.ssm_state.dtype),
                  state_shape=list(engines[0].cache.spec.state_shape),
                  state_step_fallback=engines[0].state_step_fallback,
                  walk_fallback=engines[0].walk_fallback)

    # -- leg: kernels -----------------------------------------------------

    def kernels(self):
        """Each Pallas kernel once with `interpret=False` (the interpreter
        only when rehearsing) at a shape its own gate calls eligible,
        against its XLA reference. The paged kernel already ran under the
        server in f32, bf16 and int8."""
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.ops import nn, pallas_fused, pallas_rnn
        from mxnet_tpu.ops.pallas_attention import (flash_attention,
                                                    _reference)
        interpret = self.rehearse
        rng = np.random.RandomState(0)

        def rand(*shape, dtype=jnp.float32, scale=1.0):
            return jnp.asarray(rng.randn(*shape) * scale, dtype)

        def rel(a, b):
            a, b = (np.asarray(v, np.float32) for v in (a, b))
            return float(np.linalg.norm(a - b)
                         / max(np.linalg.norm(b), 1e-30))

        def timed(fn, *args):
            t0 = time.perf_counter()
            out = jax.block_until_ready(jax.jit(fn)(*args))
            return out, round(time.perf_counter() - t0, 2)

        # flash attention: the transformer bench's head shape
        B, H, T, D = (1, 2, 32, 16) if self.rehearse else (2, 4, 1024, 128)
        q, k, v = (rand(B, H, T, D, dtype=jnp.bfloat16) for _ in range(3))

        def flash(q):
            return flash_attention(q, k, v, causal=True,
                                   interpret=interpret)

        def flash_ref(q):
            return _reference(*(a.reshape(B * H, T, D) for a in (q, k, v)),
                              1.0 / math.sqrt(D), True).reshape(q.shape)

        (out, grad), secs = timed(
            lambda q: (flash(q), jax.grad(
                lambda q: flash(q).astype(jnp.float32).sum())(q)), q)
        ref, gref = jax.jit(lambda q: (flash_ref(q), jax.grad(
            lambda q: flash_ref(q).astype(jnp.float32).sum())(q)))(q)
        errs = {"fwd": rel(out, ref), "grad": rel(grad, gref)}
        check(max(errs.values()) < 2e-2, "flash_attention: %r" % errs)
        self.emit("kernel_flash_attention", shape=[B, H, T, D],
                  dtype="bfloat16", interpret=interpret, rel_err=errs,
                  compile_and_run_s=secs)

        # fused BN + residual + ReLU epilogue: ResNet-50's 14x14 stage at
        # the trainer's batch. XLA reference: ops/nn.py's own BatchNorm
        # math, written out. dx is compared by norm: one element whose
        # pre-activation rounds across zero flips its ReLU mask.
        shape = (4, 16, 7, 7) if self.rehearse else (256, 1024, 14, 14)
        x, res, w = (rand(*shape, dtype=jnp.bfloat16) for _ in range(3))
        gamma, beta = rand(shape[1]) * 0.1 + 1.0, rand(shape[1]) * 0.1
        check(pallas_fused.fuse_eligible(x, interpret=interpret),
              "fused_bn_act's gate refuses %r" % (shape,))

        def bn_xla(x, gamma, beta):
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=(0, 2, 3), keepdims=True)
            var = jnp.maximum(jnp.mean(xf * xf, axis=(0, 2, 3),
                                       keepdims=True) - mean * mean, 0.0)
            y = (xf - mean) * jax.lax.rsqrt(var + 1e-5) \
                * gamma[None, :, None, None] + beta[None, :, None, None]
            return jnp.maximum(y + res.astype(jnp.float32), 0.0) \
                .astype(x.dtype)

        def bn_fused(x, gamma, beta):
            return pallas_fused.fused_bn_act(
                x, gamma, beta, act="relu", residual=res,
                interpret=interpret)[0]

        def both(f):
            def loss(x, gamma, beta):
                return (f(x, gamma, beta).astype(jnp.float32)
                        * w.astype(jnp.float32)).sum()
            return lambda *a: (f(*a), jax.grad(loss, argnums=(0, 1, 2))(*a))

        (out, grads), secs = timed(both(bn_fused), x, gamma, beta)
        ref, grefs = jax.jit(both(bn_xla))(x, gamma, beta)
        errs = {"fwd": rel(out, ref)}
        errs.update({n: rel(a, b) for n, a, b
                     in zip(("dx", "dgamma", "dbeta"), grads, grefs)})
        check(max(errs.values()) < 2e-2, "fused_bn_act: %r" % errs)
        self.emit("kernel_fused_bn_act", shape=list(shape),
                  dtype="bfloat16", interpret=interpret, rel_err=errs,
                  blocks=list(pallas_fused._blocks_for(
                      (shape[0], shape[1], shape[2] * shape[3]), x.dtype)),
                  compile_and_run_s=secs)

        # fused LSTM scan: the word-LM's bptt and batch at a hidden size
        # the lane tile divides; reference is ops/nn.py's lax.scan path
        T, N, Hd = (3, 8, 8) if self.rehearse else (35, 32, 256)
        args = [rand(T, N, Hd), rand(N, Hd, scale=0.1),
                rand(N, Hd, scale=0.1), rand(4 * Hd, Hd, scale=0.05),
                rand(4 * Hd, Hd, scale=0.05), rand(4 * Hd, scale=0.1),
                rand(4 * Hd, scale=0.1)]
        check(pallas_rnn.fused_eligible("lstm", T, N, Hd, jnp.float32,
                                        interpret=interpret),
              "fused_scan_layer's gate refuses T=%d N=%d H=%d" % (T, N, Hd))

        def lstm(fused):
            def loss(wh):
                ys, hT, cT = nn._scan_layer(
                    "lstm", *args[:4], wh, *args[5:], fused=fused)
                return jnp.sum(ys * ys) + jnp.sum(hT) + jnp.sum(cT)
            return jax.value_and_grad(loss)

        with jax.default_matmul_precision("highest"):
            (loss, grad), secs = timed(lstm(True), args[4])
            lref, gref = jax.jit(lstm(False))(args[4])
        errs = {"loss": abs(float(loss) - float(lref)) / abs(float(lref)),
                "dwh": rel(grad, gref)}
        check(max(errs.values()) < 1e-3, "fused_scan_layer: %r" % errs)
        self.emit("kernel_fused_scan_layer", mode="lstm", T=T, N=N, H=Hd,
                  dtype="float32", interpret=interpret, rel_err=errs,
                  compile_and_run_s=secs)

        self.decode_walk(interpret)
        self.ssm_step(interpret)

    def ssm_step(self, interpret):
        """The recurrence-step kernel (ops/pallas_ssm_step.py) at the
        `falconh1_chat_closed` cell's shape (64 rows of 2 groups x 256 x 16
        heads x 128, two layers of its six), against `state_update` on
        states gathered by hand: y, the rows' new states, and the slots no
        row names untouched (the two padded rows share the null slot, which
        the second reads while the first writes it: they are not compared). Then the call alone, timed, the plane donated:
        its bytes over its time is what `ssm_step_hbm_share` reads. And at
        the `nemotron3_reason_closed` cell's (128 rows of 8 groups x 128 x
        8 heads x 64: `ssm_step_hbm_share.hybrid`)."""
        for shape in ((64, 65, 2, 256, 16, 128), (128, 129, 8, 128, 8, 64)):
            self.ssm_step_at(interpret, *(
                (4, 6, 2, 16, 8, shape[-1]) if self.rehearse else shape))

    def ssm_step_at(self, interpret, R, slots_n, G, N, hpg, P):
        """`ssm_step` at one shape, the plane laid as the cache lays it:
        (groups, N, heads, P) for heads the lanes wide, (groups, N, heads x
        P) for narrower ones side by side (`falcon_h1.state_layout`; the
        `nemotron3_reason_closed` cell's 8 heads of 64)."""
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.models.falcon_h1 import (FalconH1Config, state_layout,
                                                state_update)
        from mxnet_tpu.ops import pallas_ssm_step as step
        keys = jax.random.split(jax.random.PRNGKey(3), 5)
        layout = state_layout(FalconH1Config(
            ssm_groups=G, ssm_state=N, ssm_heads=G * hpg, ssm_head_dim=P))
        plane = jax.random.normal(keys[0], (2, slots_n) + layout)
        slots = np.random.RandomState(2).permutation(slots_n - 1)[:R] + 1
        slots[-2:] = 0                               # padded rows
        slots = jnp.asarray(slots, jnp.int32)
        decay = jnp.exp(-jax.random.uniform(keys[1], (R, G, hpg, 1)))
        dtx = jax.random.normal(keys[2], (R, G, hpg, P))
        Bm, Cm = (jax.random.normal(k, (R, G, N)) for k in keys[3:])
        with jax.default_matmul_precision("highest"):
            want_h, want_y = jax.jit(state_update)(plane[1, slots], decay,
                                                   dtx, Bm, Cm)
        other = np.asarray(plane[0, 1])
        call = jax.jit(lambda pl, *a: step.ssm_step(
            pl, jnp.int32(1), *a, interpret=interpret), donate_argnums=0)
        t0 = time.perf_counter()
        new, y = jax.block_until_ready(call(plane, slots, decay, dtx, Bm, Cm))
        secs = round(time.perf_counter() - t0, 2)
        m_y = float(jnp.max(jnp.abs(y[:-2] - want_y[:-2]))
                    / jnp.max(jnp.abs(want_y)))
        m_h = float(jnp.max(jnp.abs(new[1, slots[:-2]] - want_h[:-2])))
        check(m_y <= 1e-4 and m_h <= 1e-5, "ssm_step strays from the "
              "gathered update by %g (y) and %g (states)" % (m_y, m_h))
        check(np.array_equal(np.asarray(new[0, 1]), other),
              "ssm_step touched another layer's states")
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            new, y = jax.block_until_ready(call(new, slots, decay, dtx, Bm,
                                                Cm))
            times.append(time.perf_counter() - t0)
        best = min(times)
        self.emit("kernel_ssm_step", rows=R, groups=G, state=N, heads=hpg,
                  head_dim=P, dtype="float32", interpret=interpret,
                  rel_err_y=m_y, max_err_state=m_h, compile_and_run_s=secs,
                  call_ms=round(best * 1e3, 3),
                  bytes=step.step_bytes(R, G, N, hpg, P),
                  gb_per_s=round(step.step_bytes(R, G, N, hpg, P)
                                 / best / 1e9, 1))

    def decode_walk(self, interpret):
        """The decode-walk kernel (ops/pallas_decode_walk.py) at the two
        serving cells' shapes, bf16 planes, against the dense float32
        reference: `opt-6.7b`'s 32 cached heads a query head each over a
        full table, `trinity-large-preview`'s 8 cached heads a group of 6
        over a ring of 257 that has wrapped, ragged rows and padded ones.
        The margin is the largest gap of an output; the budget is bf16's:
        probabilities rounded to bf16 for the second product, as XLA's
        default precision rounds them."""
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.ops import pallas_decode_walk as walk
        rng = np.random.RandomState(1)
        shapes = {"opt-6.7b": dict(B=16, Hkv=32, G=1, W=128, window=0,
                                   layers=2, longest=2047),
                  "trinity-large-preview": dict(B=32, Hkv=8, G=6, W=257,
                                                window=4096, layers=2,
                                                longest=9727)}
        if self.rehearse:
            shapes = {"opt-6.7b": dict(B=4, Hkv=4, G=1, W=8, window=0,
                                       layers=2, longest=127),
                      "trinity-large-preview": dict(
                          B=4, Hkv=2, G=3, W=5, window=64, layers=2,
                          longest=300)}
        bs, Dh = 16, 8 if self.rehearse else 128
        for name, c in shapes.items():
            B, W = c["B"], c["W"]
            blocks = B * W + 1
            k, v = (jax.random.normal(
                key, (c["layers"], blocks, c["Hkv"], bs, Dh), jnp.bfloat16)
                for key in jax.random.split(jax.random.PRNGKey(B)))
            q = jnp.asarray(rng.randn(B, c["Hkv"] * c["G"], Dh),
                            jnp.bfloat16)
            # the shortest row, one block exactly, one token past it,
            # ragged ones, the longest; the last two rows padded
            pos = rng.randint(0, c["longest"], B)
            pos[:4] = (0, bs - 1, bs, c["longest"])
            pos[-2:] = 0
            tables = (rng.permutation(blocks - 1)[:B * W] + 1).reshape(B, W)
            tables[-2:] = 0
            args = (q, k, v, jnp.asarray(tables, jnp.int32),
                    jnp.asarray(pos, jnp.int32))
            t0 = time.perf_counter()
            out = jax.block_until_ready(walk.decode_walk(
                *args, jnp.int32(1), scale=1.0 / math.sqrt(Dh),
                window=c["window"], ring=W if c["window"] else 0,
                interpret=interpret))
            secs = round(time.perf_counter() - t0, 2)
            ref = jax.jit(walk.reference, static_argnums=(5, 6))(
                *args, 1, c["window"])
            m = float(jnp.max(jnp.abs(out - ref)))
            check(np.isfinite(m) and m <= 0.05, "decode_walk at %s's shape "
                  "strays from the dense reference by %g" % (name, m))
            self.emit("kernel_decode_walk", shape_of=name, batch=B,
                      kv_heads=c["Hkv"], group=c["G"], columns=W,
                      window=c["window"], dtype="bfloat16",
                      interpret=interpret, max_margin=m, budget=0.05,
                      compile_and_run_s=secs)

    # -- leg: four chips --------------------------------------------------

    def four_chips(self):
        from mxnet_tpu.parallel.mesh import build_mesh
        self.trainer("four_chips_trainer_dp4", mesh=build_mesh({"dp": 4}))
        cfg, params, wave = self.lm()
        for leg, kw in (("four_chips_server_tp2_x2",
                         {"tp": 2, "replicas": 2}),
                        ("four_chips_server_replicas4", {"replicas": 4})):
            waves = self.serve_wave(leg, (params, cfg), wave, paged=True,
                                    **kw)
            m = self.margin(cfg, params, wave, waves)
            # default precision: f32 dots round through bf16 on the chip
            check(m <= 0.25, "%s strays from the f32 reference by %g"
                  % (leg, m))
            self.emit(leg + "_check", max_margin=m, budget=0.25)


def check(ok, message):
    if not ok:
        raise SystemExit("chip_smoke FAILED: " + message)


if __name__ == "__main__":
    sys.exit(main())
