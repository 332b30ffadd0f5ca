"""TrainStep: the fully-fused XLA training step.

This is the TPU performance path that the eager Trainer (gluon/trainer.py)
API-matches: forward + loss + backward + optimizer update compile into ONE
XLA program with buffer donation, so parameters update in-place in HBM and
nothing round-trips to the host. Under a mesh, the batch shards over 'dp'
(GSPMD inserts the gradient psum — the KVStore('tpu') allreduce), while
parameters stay replicated (or sharded for tensor parallelism via
param_shardings).

Every registered optimizer fuses: the update math lives once, as pure rules
in mxnet_tpu.optimizer_rules, shared with the eager classes — the analog of
the reference's fused optimizer kernels (src/operator/optimizer_op-inl.h)
covering the full optimizer list instead of a subset.

Mixed precision (dtype="bfloat16"): forward/backward compute in bf16 on the
MXU with float32 master weights and optimizer state; logits are promoted to
f32 before the loss for a stable softmax. This is the reference's
multi_precision fp16 capability (optimizer.py:483) in its TPU-native form.

Rematerialisation (remat=True/"full"): wraps each compute block's forward
in jax.checkpoint so the backward pass recomputes activations instead of
storing them — the MXNET_BACKWARD_DO_MIRROR capability
(docs/faq/env_var.md:93). remat="io" (or MXNET_REMAT_POLICY=io) keeps the
MXU outputs (conv/matmul, tagged checkpoint_name in ops/nn.py) and BN batch
stats, recomputing only the cheap elementwise chains — trading a few FLOPs
for HBM bytes on a bandwidth-bound step.

Parity note: the reference overlapped backward with kvstore pushes via
engine priorities (src/kvstore/comm.h:171); XLA's latency-hiding scheduler
performs the same overlap inside this single program.
"""
from __future__ import annotations

import contextlib

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ndarray import NDArray
from .. import autograd
from .. import random as _random
from .. import optimizer_rules as _rules


#: remat modes -> jax.checkpoint policies. "full" is the reference's
#: MXNET_BACKWARD_DO_MIRROR trade (save only segment boundaries, recompute
#: everything). "io" is the HBM-traffic policy: SAVE what the MXU produced
#: (conv/matmul outputs, tagged in ops/nn.py via checkpoint_name) plus the
#: tiny BN batch statistics, and RECOMPUTE the cheap elementwise chains
#: (BN normalize, relu, residual adds) in backward instead of writing them
#: out in forward and re-reading them — the bandwidth-roofline lever for a
#: step measured at 95% of the HBM floor (2026-07-31, BENCH_LAST_TPU.json).
#: Composes with MXNET_FUSED_BN_EPILOGUE=1 (ops/pallas_fused.py): the
#: fused op's custom-VJP residuals are exactly this save set (conv_out +
#: bn_stats), so under "io" its relu outputs are never stored — backward
#: replays the Pallas epilogue kernel from the saved conv output.
_REMAT_POLICIES = {
    "full": lambda: None,  # jax.checkpoint default: nothing saveable
    "io": lambda: jax.checkpoint_policies.save_only_these_names(
        "conv_out", "bn_stats", "fc_out"),
}


def _remat_mode(remat):
    """Normalize the TrainStep remat argument / env vars to a mode string
    in {"none", "full", "io"}."""
    import os
    if remat is None:
        mode = os.environ.get("MXNET_REMAT_POLICY", "").lower()
        if mode:
            if mode != "none" and mode not in _REMAT_POLICIES:
                # a typo must not silently measure a different config
                raise ValueError(
                    "MXNET_REMAT_POLICY must be none, full or io, got %r"
                    % (mode,))
            return mode
        # parity: MXNET_BACKWARD_DO_MIRROR (docs/faq/env_var.md:93) —
        # trade recompute for activation memory by default when set
        if os.environ.get("MXNET_BACKWARD_DO_MIRROR", "0") == "1":
            return "full"
        return "none"
    if remat is True:
        return "full"
    if not remat or remat == "none":
        return "none"
    if remat in _REMAT_POLICIES:
        return remat
    raise ValueError(
        "remat must be bool, 'none', 'full' or 'io', got %r" % (remat,))


def _remat_segments(net):
    """Checkpoint segments: walk the block tree, recursing through
    Sequential-style containers so boundaries land at real compute blocks
    (a ResNet's 16 bottlenecks, an MLP's Dense layers) rather than one
    whole-feature-stack segment. Blocks that mutate auxiliary state
    (BatchNorm running stats) are fine: _segment_remat threads the aux
    buffers through the checkpoint as explicit inputs/outputs."""
    from ..gluon.nn.basic_layers import Sequential, HybridSequential
    segs = []

    def walk(block):
        for child in getattr(block, "_children", {}).values():
            if isinstance(child, (Sequential, HybridSequential)):
                walk(child)
            else:
                segs.append(child)

    walk(net)
    return segs


@contextlib.contextmanager
def _segment_remat(blocks, policy=None, net=None):
    """Wrap each block's forward in jax.checkpoint for the duration of the
    step trace. Whole-function checkpoint saves nothing at peak (the
    backward's recompute carries the same live set); per-segment checkpoint
    keeps only segment boundaries + policy-saveable values alive — the real
    MXNET_BACKWARD_DO_MIRROR/memonger trade.

    Aux-state blocks (BatchNorm running stats, grad_req 'null' params) are
    checkpointable: their buffers enter the checkpointed function as
    explicit arguments and the mutated values return as explicit outputs,
    written back in place — no inner tracer ever leaks through
    Parameter._data, and NDArray references taken before the step stay
    valid (same object identity as the non-remat path).

    `net` (when given) has its WHOLE tree's CachedOps deactivated for the
    trace: a hybridized container above the segments would otherwise route
    through its warmed jit cache and bypass every wrapped forward,
    silently skipping remat.
    """
    saved = []
    active = []

    def _collect_active(b):
        if getattr(b, "_active", False):
            active.append(b)
            b._active = False

    if net is not None and hasattr(net, "apply"):
        # deactivate hybridized blocks ANYWHERE in the tree (containers
        # included), not just the wrapped segments — inside the step
        # everything is jitted anyway, the CachedOp adds nothing
        net.apply(_collect_active)
    for block in blocks:
        _collect_active(block)
        orig = block.forward
        aux_params = [p for p in block.collect_params().values()
                      if p.grad_req == "null"]

        def wrapped(*args, _orig=orig, _aux=aux_params):
            if len(args) == 1 and isinstance(args[0], NDArray):
                # single trace through checkpoint — no retry path, so the
                # stateful trace-key counter advances exactly once and
                # remat numerics match the non-remat step bit for bit
                def pure(xv, aux_in):
                    for p, v in zip(_aux, aux_in):
                        p._data = NDArray(v)
                    out = _orig(NDArray(xv))
                    outs = out._data if isinstance(out, NDArray) \
                        else tuple(o._data for o in out)
                    return outs, tuple(p._data._data for p in _aux)
                aux_in = tuple(p._data._data for p in _aux)
                orig_nd = [p._data for p in _aux]
                res, aux_out = jax.checkpoint(pure, policy=policy)(
                    args[0]._data, aux_in)
                # write back IN PLACE on the pre-call NDArray objects:
                # rebinding p._data to a fresh NDArray would orphan any
                # reference taken before the step with a dead inner tracer
                for p, nd_, v in zip(_aux, orig_nd, aux_out):
                    nd_._data = v
                    p._data = nd_
                if isinstance(res, tuple):
                    return tuple(NDArray(r) for r in res)
                return NDArray(res)
            return _orig(*args)

        saved.append((block, orig))
        block.forward = wrapped
    try:
        yield
    finally:
        for block, orig in saved:
            block.forward = orig
        for block in active:
            block._active = True


class TrainStep:
    """Compile net+loss+optimizer into one donated XLA program.

    Usage:
        step = TrainStep(net, loss_fn, 'sgd',
                         {'learning_rate': 0.1, 'momentum': 0.9}, mesh=mesh)
        loss = step(x_batch, y_batch)   # params update in device memory
        step.sync_params()              # write back before eval/save
    """

    def __init__(self, net, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh=None, data_axis="dp", param_shardings=None,
                 dtype="float32", remat=None, shard_optimizer_states=False,
                 sharded_update=None, guard=False,
                 quantized_collectives=None):
        import os as _os
        from .. import optimizer as _opt_mod
        remat = _remat_mode(remat)
        self._net = net
        self._loss = loss_fn
        if isinstance(optimizer, str):
            optimizer = _opt_mod.create(optimizer,
                                        **dict(optimizer_params or {}))
        elif optimizer_params:
            raise ValueError("pass optimizer_params only with a string name")
        if optimizer.rule_name is None:
            raise ValueError("optimizer %s has no pure update rule"
                             % type(optimizer).__name__)
        self._opt = optimizer
        self._mesh = mesh
        self._data_axis = data_axis
        self._param_shardings = param_shardings or {}
        self._compute_dtype = jnp.dtype(dtype)
        self._remat = remat
        # ZeRO-style weight-update sharding (arXiv:2004.13336): optimizer
        # state shards over the data axis, GSPMD turning the grad all-reduce
        # into reduce-scatter + the post-update all-gather automatically
        if sharded_update is None:
            sharded_update = _os.environ.get("MXNET_SHARDED_UPDATE",
                                             "0") == "1"
        # sharded_update goes further than state *placement*: the step
        # itself pins the ZeRO-1 dataflow with sharding constraints —
        # grads reduce-scatter over dp, the optimizer applies to the
        # local 1/N shard of (weight, grad, state), updated params
        # all-gather back to replicated. Semantically identical to the
        # unsharded step (the constraints only re-place the same global
        # values), which tests pin bit-for-bit against the unsharded
        # oracle; per-chip it trades the full optimizer-state footprint
        # for 1/N + an all-gather. Implies sharded state placement, and
        # makes per-host sharded checkpoints (utils/recovery.py) the
        # natural way to save the now per-host optimizer state.
        self._sharded_update = bool(sharded_update)
        self._shard_opt = bool(shard_optimizer_states) or \
            self._sharded_update
        # bad-step guard (parallel/resilient.py): when on, the jitted step
        # also computes the global grad norm + a finiteness flag and
        # SELECTS the old (params, opt state, aux) when the step is bad —
        # the state protection itself needs no host round-trip.
        # Numerically transparent while every step is finite: the select
        # picks the identical new values. Note the POLICY layer
        # (ResilientLoop) reads last_step_ok on the host each step to
        # react, which serializes dispatch; policy="off" keeps full
        # async overlap, and BENCH_CONFIGS=resilience tracks the cost.
        self._guard = bool(guard)
        # int8 grad all-reduce (ISSUE 20 training leg): the dp gradient
        # collective carries int8 payload with per-tensor global scales
        # and kvstore-style error-feedback residuals
        # (_TwoBitCompressor's algorithm at the XLA collective seam).
        # Flag switches the COLLECTIVE's precision, never the training
        # contract: ineligible configs record the reason on
        # `collective_quant_fallback` and run the f32 psum verbatim.
        if quantized_collectives is None:
            quantized_collectives = _os.environ.get(
                "MXNET_QUANTIZED_COLLECTIVES", "").strip() or None
        self._qcoll_req = quantized_collectives
        self.collective_quant = None
        self.collective_quant_fallback = None
        self._quant_residuals = None
        self.last_step_ok = None     # device bool of the latest step
        self.last_grad_norm = None   # device f32 of the latest step
        self._lr_schedule = None
        self._t = 0
        self._step_fn = None
        self._probe_fn = None
        self._compiled = False

    def set_lr_schedule(self, fn):
        self._lr_schedule = fn

    @property
    def warm_loads(self):
        """Fused-step executables warm-loaded from the persistent AOT
        cache (mxnet_tpu/aot) instead of compiled — a supervised
        relaunch (tools/train_supervise.py --prewarm-cmd) lands here."""
        fn = self._step_fn
        return getattr(fn, "warm_loads", 0) if fn is not None else 0

    @property
    def t(self):
        """Completed optimizer steps (the checkpoint step number)."""
        return self._t

    def _build(self):
        params = self._net.collect_params()
        names, plist = [], []
        for n, p in params.items():
            if p._data is None:
                raise RuntimeError("initialize parameters before TrainStep "
                                   "(missing %s)" % n)
            names.append(n)
            plist.append(p)
        grad_mask = [p.grad_req != "null" for p in plist]
        net, loss_fn = self._net, self._loss
        opt = self._opt
        init_rule, apply_rule = _rules.get(opt.rule_name)
        hyper = opt.rule_hyper()
        stochastic_rule = opt.rule_name in _rules.STOCHASTIC
        rescale, clip = opt.rescale_grad, opt.clip_gradient
        # per-param lr/wd multipliers resolve to static floats at build time;
        # Parameter-level attrs take priority over name dicts, matching the
        # eager Optimizer._get_lr/_get_wd param_dict branch
        gparams = [(n, p) for n, p, m in zip(names, plist, grad_mask) if m]
        gnames_all = [n for n, _ in gparams]

        def _mult(p, n, dct, attr):
            v = getattr(p, attr, 1.0)
            if v != 1.0:
                return v
            return dct.get(n, 1.0)

        lr_mults = [_mult(p, n, opt.lr_mult, "lr_mult") for n, p in gparams]
        wd_mults = [_mult(p, n, opt.wd_mult, "wd_mult") for n, p in gparams]
        base_wd = opt.wd
        cdtype = self._compute_dtype
        mixed = cdtype != jnp.float32
        remat_on = self._remat != "none"
        remat_policy = _REMAT_POLICIES[self._remat]() if remat_on else None
        remat_blocks = _remat_segments(net) if remat_on else []
        # ZeRO-1 (arXiv:2004.13336) shard specs, one per grad param: the
        # first dp-divisible axis of each REPLICATED weight (tensor-
        # parallel params already shard their own way; scalars and
        # indivisible shapes stay replicated). Used both to place the
        # optimizer state and to pin the in-step dataflow below.
        mesh_obj = self._mesh
        dp_ax = self._data_axis
        dp_size = mesh_obj.shape.get(dp_ax, 0) \
            if (mesh_obj is not None and dp_ax) else 0
        zero_specs = []
        for n, p in gparams:
            pspec = self._param_shardings.get(n, P())
            replicated = all(ax is None for ax in pspec)
            w0 = p._data._data
            z = None
            if dp_size > 1 and replicated and np.ndim(w0) > 0:
                for axis in range(np.ndim(w0)):
                    if w0.shape[axis] % dp_size == 0:
                        z = P(*([None] * axis + [dp_ax]))
                        break
            zero_specs.append(z)
        szd = self._sharded_update and dp_size > 1 and \
            any(z is not None for z in zero_specs)
        # int8-collective eligibility: the compression targets the
        # replicated-parameter dp all-reduce, so ZeRO's reduce-scatter
        # dataflow and tensor-sharded params keep their f32 collectives
        self.collective_quant = None
        self.collective_quant_fallback = None
        if self._qcoll_req:
            if str(self._qcoll_req) != "int8":
                # a typo must not silently measure a different config
                raise ValueError(
                    "MXNET_QUANTIZED_COLLECTIVES must be int8 or unset, "
                    "got %r" % (self._qcoll_req,))
            if dp_size <= 1:
                self.collective_quant_fallback = (
                    "needs a data-parallel mesh (dp > 1); a single-chip "
                    "step has no gradient collective to compress")
            elif self._sharded_update:
                self.collective_quant_fallback = (
                    "sharded_update reshapes the grad all-reduce into "
                    "reduce-scatter + all-gather (ZeRO-1); int8 "
                    "compression targets the replicated all-reduce")
            elif any(any(ax is not None
                         for ax in self._param_shardings.get(n, P()))
                     for n in gnames_all):
                self.collective_quant_fallback = (
                    "tensor-sharded parameters reduce over their own "
                    "mesh axes; int8 compression targets "
                    "replicated-parameter dp gradients")
            else:
                self.collective_quant = "int8"
        qcoll = self.collective_quant is not None
        if qcoll:
            # each chip quantizes into [-cap, cap] so the int8 psum of
            # dp_size addends stays within int8 by construction
            _cap = float(max(1, 127 // dp_size))
            _dpn = dp_ax

            def _qcoll_grads(grad_vals, nograd_vals, x, y, key,
                             residuals):
                """Per-chip grads + error-feedback int8 all-reduce.
                Runs under shard_map: x/y are the chip's batch shard,
                `residuals` the chip's (1, *shape) quantization-error
                carry (the kvstore _TwoBitCompressor algorithm — the
                error a round drops is added back the next round, so
                the compression bias averages out instead of
                accumulating). The per-tensor scale is GLOBAL (pmax of
                the local amax): every chip quantizes onto the same
                grid, making the int8 psum a faithful sum."""
                (loss_local, aux_upd), grads = jax.value_and_grad(
                    forward_loss, has_aux=True)(grad_vals, nograd_vals,
                                                x, y, key)
                loss_val = jax.lax.pmean(loss_local, _dpn)
                aux_upd = {i: jax.lax.pmean(v, _dpn)
                           for i, v in aux_upd.items()}
                out_g, out_r = [], []
                for g, r in zip(grads, residuals):
                    gf = g.astype(jnp.float32) + r[0]
                    amax = jax.lax.pmax(jnp.max(jnp.abs(gf)), _dpn)
                    s = jnp.maximum(amax, 1e-30) / _cap
                    q = jnp.clip(jnp.rint(gf / s), -_cap,
                                 _cap).astype(jnp.int8)
                    out_r.append((gf - q.astype(jnp.float32) * s)[None])
                    total = jax.lax.psum(q, _dpn)  # the s8 all-reduce
                    out_g.append((total.astype(jnp.float32) * s
                                  / dp_size).astype(g.dtype))
                return loss_val, aux_upd, tuple(out_g), tuple(out_r)

            _qcoll_sm = jax.shard_map(
                _qcoll_grads, mesh=mesh_obj,
                in_specs=(P(), P(), P(dp_ax), P(dp_ax), P(), P(dp_ax)),
                out_specs=(P(), P(), P(), P(dp_ax)), check_vma=False)

        def forward_loss(grad_vals, nograd_vals, x, y, key):
            """Trace the eager net with tracer-backed parameter buffers.
            Returns (mean_loss, {plist_index: mutated_value}) where the aux
            dict carries BatchNorm running-stat writes."""
            merged = []
            gi = ni = 0
            for has_grad in grad_mask:
                if has_grad:
                    merged.append(grad_vals[gi])
                    gi += 1
                else:
                    merged.append(nograd_vals[ni])
                    ni += 1
            if mixed:
                # bf16 compute, f32 master weights: cast the traced buffers,
                # so grads flow back through the cast in f32
                merged = [v.astype(cdtype)
                          if jnp.issubdtype(v.dtype, jnp.floating) else v
                          for v in merged]
                x = x.astype(cdtype) if jnp.issubdtype(
                    jnp.asarray(x).dtype, jnp.floating) else x
            from .functional import swap_param_buffers
            remat_ctx = _segment_remat(remat_blocks, remat_policy, net) \
                if remat_blocks else contextlib.nullcontext()
            with swap_param_buffers(plist, merged) as injected:
                with autograd._RecordingStateScope(False, True), \
                        _random.trace_key_scope(key), remat_ctx:
                    out = net.forward(NDArray(x))
                    if mixed:
                        # f32 softmax/loss for numerical stability
                        out = NDArray(out._data.astype(jnp.float32))
                    loss = loss_fn(out, NDArray(y))
                loss_val = jnp.mean(loss._data.astype(jnp.float32))
                aux_upd = {i: p._data._data for i, p in enumerate(plist)
                           if p._data._data is not injected[i]}
            return loss_val, aux_upd

        if remat_on and not remat_blocks:
            # no segmentable children: whole-forward checkpoint (weaker —
            # peak is unchanged, but recompute semantics are preserved)
            forward_loss = jax.checkpoint(forward_loss, policy=remat_policy)

        # kept for the donation-free SDC parity probe (probe()): the
        # same forward/loss trace the step differentiates, minus the
        # optimizer update and the buffer donation
        self._forward_loss = forward_loss

        guard = self._guard

        def train_step(grad_vals, nograd_vals, opt_state, x, y, key, lr,
                       t, poison, residuals=None):
            # independent streams: forward-trace keys (dropout masks etc.)
            # derive from fwd_key; optimizer noise (SGLD) from noise_key —
            # fold_in on the SAME base key would collide with the trace keys
            fwd_key, noise_key = jax.random.split(key)
            if qcoll:
                # grads arrive PRE-REDUCED through the int8 collective
                # (per-chip local grads quantized with error feedback,
                # s8 psum, global-scale dequant); loss and BN stats
                # pmean over dp. The optimizer below sees ordinary
                # replicated f32 grads either way.
                loss_val, aux_upd, grads, new_resid = _qcoll_sm(
                    grad_vals, nograd_vals, x, y, fwd_key, residuals)
            else:
                (loss_val, aux_upd), grads = jax.value_and_grad(
                    forward_loss, has_aux=True)(grad_vals, nograd_vals,
                                                x, y, fwd_key)
            # chaos seam: `poison` is 0.0 on every real step; the chaos
            # harness passes NaN to fault a chosen step's gradients
            # without retracing (utils/chaos.grad_poison)
            grads = [g + poison.astype(g.dtype) for g in grads]
            if guard:
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in grads))
                ok = jnp.isfinite(loss_val) & jnp.isfinite(gnorm)
            new_grad_vals, new_state = [], []
            for i, (w, g, s) in enumerate(zip(grad_vals, grads, opt_state)):
                g = g.astype(w.dtype) * rescale
                if clip is not None:
                    g = jnp.clip(g, -clip, clip)
                k = jax.random.fold_in(noise_key, i) if stochastic_rule \
                    else None
                # ZeRO-1 dataflow (sharded_update): the grad's allreduce
                # becomes reduce-scatter (constrain it dp-sharded — XLA
                # materializes only the 1/N shard per device), the
                # optimizer applies to the local shard of (w, g, state),
                # and only the UPDATED param all-gathers back. The
                # constraints re-place, never re-value: the unsharded
                # step is the bit-exact parity oracle (tests pin it).
                z = zero_specs[i] if szd else None
                w_in = w
                if z is not None:
                    zs = NamedSharding(mesh_obj, z)
                    g = jax.lax.with_sharding_constraint(g, zs)
                    w_in = jax.lax.with_sharding_constraint(w, zs)
                w2, s2 = apply_rule(w_in, g, s, lr * lr_mults[i],
                                    base_wd * wd_mults[i], t, hyper, k)
                if z is not None:
                    w2 = jax.lax.with_sharding_constraint(
                        w2, NamedSharding(mesh_obj, P()))
                    s2 = jax.tree.map(
                        lambda a: jax.lax.with_sharding_constraint(a, zs)
                        if jnp.shape(a) == jnp.shape(w) else a, s2)
                if guard:
                    # bad step -> drop the whole update: params AND
                    # optimizer state stay exactly as they were
                    w2 = jnp.where(ok, w2, w)
                    s2 = jax.tree.map(lambda a, b: jnp.where(ok, a, b),
                                      s2, s)
                new_grad_vals.append(w2)
                new_state.append(s2)
            new_nograd_vals = list(nograd_vals)
            ni = 0
            for i, has_grad in enumerate(grad_mask):
                if not has_grad:
                    if i in aux_upd:
                        upd = aux_upd[i].astype(nograd_vals[ni].dtype)
                        if guard:  # BN running stats also roll back
                            upd = jnp.where(ok, upd, nograd_vals[ni])
                        new_nograd_vals[ni] = upd
                    ni += 1
            out = (loss_val, tuple(new_grad_vals), tuple(new_nograd_vals),
                   tuple(new_state))
            if guard:
                out = out + (ok, gnorm)
            if qcoll:
                out = out + (new_resid,)
            return out

        # the compile watchdog (telemetry/introspect.py) owns the
        # executable cache: every (re)compilation of the fused step is an
        # attributed `compile` event with memory/cost accounting, and
        # MXNET_COMPILE_BUDGET / MXNET_HBM_BUDGET_GB apply. `.lower` and
        # `.__wrapped__` still reach the underlying jit (bench cost
        # probes, bytes reports, export_train_step).
        from ..telemetry import introspect as _introspect
        argnames = ("grad_vals", "nograd_vals", "opt_state", "x", "y",
                    "key", "lr", "t", "poison")
        donate = (0, 1, 2)
        if qcoll:
            # the error-feedback carry is step state: donated through,
            # like the params and optimizer state it rides with
            argnames = argnames + ("residuals",)
            donate = donate + (9,)
        self._step_fn = _introspect.instrument(
            jax.jit(train_step, donate_argnums=donate), site="train.step",
            phase="train", argnames=argnames, variant="train_step")
        self._names = names
        self._plist = plist
        self._grad_mask = grad_mask
        grad_vals = tuple(p._data._data
                          for p, m in zip(plist, grad_mask) if m)
        nograd_vals = tuple(p._data._data
                            for p, m in zip(plist, grad_mask) if not m)
        opt_state = tuple(init_rule(w, hyper) for w in grad_vals)
        if self._mesh is not None:
            def place(name, v):
                spec = self._param_shardings.get(name, P())
                if v.ndim == 0:  # scalar state (e.g. nadam m_schedule)
                    spec = P()
                return jax.device_put(v, NamedSharding(self._mesh, spec))

            dp = self._data_axis
            dp_size = self._mesh.shape.get(dp, 0) if dp else 0

            def place_state(name, s):
                """Optimizer state placement: with weight-update sharding
                on, a state whose weight is replicated shards its first
                divisible axis over the data axis (ZeRO-1)."""
                spec = self._param_shardings.get(name, P())
                replicated = all(ax is None for ax in spec)  # P() or P(None,)
                if self._shard_opt and dp_size > 1 and replicated \
                        and s.ndim > 0:
                    for axis in range(s.ndim):
                        if s.shape[axis] % dp_size == 0:
                            zspec = P(*([None] * axis + [dp]))
                            return jax.device_put(
                                s, NamedSharding(self._mesh, zspec))
                if s.ndim == 0:
                    spec = P()
                return jax.device_put(s, NamedSharding(self._mesh, spec))

            gnames = gnames_all
            nnames = [n for n, m in zip(self._names, grad_mask) if not m]
            grad_vals = tuple(place(n, v) for n, v in zip(gnames, grad_vals))
            nograd_vals = tuple(place(n, v)
                                for n, v in zip(nnames, nograd_vals))
            opt_state = tuple(
                tuple(place_state(n, s) for s in st)
                for n, st in zip(gnames, opt_state))
        self._grad_vals = grad_vals
        self._nograd_vals = nograd_vals
        self._opt_state = opt_state
        if qcoll:
            # per-chip error-feedback carries, zero at start: one
            # (dp, *shape) f32 array per grad param, dp-sharded so each
            # chip owns exactly its own residual (not checkpointed —
            # a resume restarts the feedback loop from zero, costing
            # one round of dropped error, never correctness)
            self._quant_residuals = tuple(
                jax.device_put(
                    jnp.zeros((dp_size,) + tuple(jnp.shape(w)),
                              jnp.float32),
                    NamedSharding(mesh_obj, P(dp_ax)))
                for w in grad_vals)

    def __call__(self, x, y):
        # one span from the first line to the return: the host's share of
        # a step (placing the batch, the dispatch), a child of
        # ResilientLoop's `train.device_step` where that loop drives it
        from .. import telemetry as _telemetry
        with _telemetry.span("train.dispatch", category="trainstep") as sp:
            return self._dispatch(x, y, sp)

    def _dispatch(self, x, y, sp):
        from .. import profiler as _profiler
        xv = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        yv = y._data if isinstance(y, NDArray) else jnp.asarray(y)
        if self._step_fn is None:
            self._build()
        # first DISPATCH (not first build — load_state_dict also builds)
        # pays XLA compilation and captures the example specs
        first_call = not self._compiled
        if self._mesh is not None:
            from .mesh import shard_batch
            xv = shard_batch(self._mesh, xv, self._data_axis)
            yv = shard_batch(self._mesh, yv, self._data_axis)
        self._t += 1
        # compile vs run split in the profiler table: the first dispatch pays
        # XLA compilation, later ones are cached executions (parity with the
        # reference's symbolic bind-vs-run accounting)
        sp.alias = "TrainStep::compile" if first_call else "TrainStep::run"
        sp.attrs.update(step=self._t, first_call=first_call)
        if self._lr_schedule is not None:
            lr = self._lr_schedule(self._t)
        elif self._opt.lr_scheduler is not None:
            lr = self._opt.lr_scheduler(self._t)
        else:
            lr = self._opt.lr
        key = _random.next_key()
        from ..utils import chaos as _chaos
        poison = jnp.float32(_chaos.grad_poison(self._t))
        call_args = (self._grad_vals, self._nograd_vals, self._opt_state,
                     xv, yv, key, jnp.float32(lr), jnp.int32(self._t),
                     poison)
        if self.collective_quant:
            call_args = call_args + (self._quant_residuals,)
        if first_call:
            self._example_args = jax.tree.map(
                lambda v: jax.ShapeDtypeStruct(jnp.shape(v),
                                               jnp.asarray(v).dtype),
                call_args)
        out = self._step_fn(*call_args)
        if self.collective_quant:
            out, self._quant_residuals = out[:-1], out[-1]
        if self._guard:
            (loss, self._grad_vals, self._nograd_vals, self._opt_state,
             self.last_step_ok, self.last_grad_norm) = out
        else:
            loss, self._grad_vals, self._nograd_vals, self._opt_state \
                = out
        if _profiler.profile_sync():
            jax.block_until_ready(loss)
        self._compiled = True
        # register the step's output buffers so mx.nd.waitall() blocks on
        # in-flight optimizer updates (the benchmark timing pattern)
        from .. import engine as _engine
        jax.tree.map(_engine.note, (loss, self._grad_vals,
                                    self._nograd_vals, self._opt_state))
        return loss

    def probe(self, x, y, seed=0):
        """Deterministic, donation-free parity probe (ISSUE 15): compute
        `(loss, global_grad_norm)` for the given batch under a FIXED RNG
        seed against the live parameters — without mutating params,
        optimizer state, the RNG key chain, or the step counter, and
        without donating any buffer. Two calls with the same batch and
        seed return bit-identical floats, and two HOSTS holding
        replicated parameters return bit-identical floats — which is
        what lets the SDC parity probe (parallel/supervisor.py)
        cross-check digests and attribute a divergence to one chip.
        Compiled once (its own non-donating executable, watchdog site
        `train.probe`); reuses the step's forward/loss trace verbatim.
        """
        from ..telemetry import introspect as _introspect
        if self._step_fn is None:
            self._build()
        if self._probe_fn is None:
            fwd = self._forward_loss

            def probe_fn(grad_vals, nograd_vals, x, y, key):
                (loss_val, _aux), grads = jax.value_and_grad(
                    fwd, has_aux=True)(grad_vals, nograd_vals, x, y, key)
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in grads))
                return loss_val, gnorm

            self._probe_fn = _introspect.instrument(
                jax.jit(probe_fn), site="train.probe", phase="train",
                argnames=("grad_vals", "nograd_vals", "x", "y", "key"),
                variant="train_probe")
        xv = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        yv = y._data if isinstance(y, NDArray) else jnp.asarray(y)
        if self._mesh is not None:
            from .mesh import shard_batch
            xv = shard_batch(self._mesh, xv, self._data_axis)
            yv = shard_batch(self._mesh, yv, self._data_axis)
        key = jax.random.PRNGKey(int(seed))
        loss, gnorm = self._probe_fn(self._grad_vals, self._nograd_vals,
                                     xv, yv, key)
        return float(np.asarray(loss)), float(np.asarray(gnorm))

    def memory_analysis(self):
        """XLA memory accounting of the compiled step (requires one prior
        call). `temp_size_in_bytes` is the live-activation footprint — the
        number the MXNET_BACKWARD_DO_MIRROR/remat trade shrinks on TPU
        (reference: memonger's measurement, docs/faq/env_var.md:93). Note
        XLA:CPU CSEs rematerialization away, so the difference shows on
        device backends; `lowered_stablehlo()` shows the program-level
        recompute on any backend."""
        if self._step_fn is None or not hasattr(self, "_example_args"):
            raise RuntimeError("run at least one step first")
        return self._step_fn.lower(*self._example_args).compile() \
            .memory_analysis()

    def lowered_stablehlo(self):
        """Pre-optimization StableHLO of the step (requires one prior
        call) — e.g. for auditing remat recompute + optimization barriers."""
        if self._step_fn is None or not hasattr(self, "_example_args"):
            raise RuntimeError("run at least one step first")
        return self._step_fn.lower(*self._example_args).as_text()

    def _lr_sched_obj(self):
        """The stateful schedule driving this step's lr, if any.
        `_lr_schedule_base` (set by ResilientLoop when it wraps the
        schedule with its rollback LR-scale) takes priority: the wrapper
        lambda has no state, the underlying scheduler does."""
        for cand in (getattr(self, "_lr_schedule_base", None),
                     self._lr_schedule, self._opt.lr_scheduler):
            if cand is not None and hasattr(cand, "state_dict"):
                return cand
        return None

    def state_dict(self, device=False):
        """Full resumable training state (params + optimizer state + step
        counter + RNG key chain + LR-schedule state) for
        utils.recovery.CheckpointManager. Materialized to host arrays —
        the live device buffers get donated by the next step, so handing
        out references would leave the caller with deleted arrays.

        device=True returns the LIVE device arrays instead (shardings
        intact — what sharded checkpointing needs to know which shards
        this host owns). The caller must copy out everything it keeps
        BEFORE the next step runs: CheckpointManager.save() does its
        host copies synchronously, so `mgr.save(t, step.state_dict(
        device=True))` is safe; holding the tree across a step is not.
        """
        if self._step_fn is None:
            self._build()
        # np.array (not np.asarray): on the CPU backend asarray can be a
        # ZERO-COPY view of the XLA buffer, and the next step DONATES
        # that buffer — an async checkpoint writer would then serialize
        # memory the t+1 update already overwrote (a checkpoint labeled
        # step t with step t+1's params breaks step-exact resume)
        live = (tuple(self._grad_vals), tuple(self._nograd_vals),
                tuple(self._opt_state))
        host = live if device else jax.tree.map(lambda v: np.array(v), live)
        out = {"t": np.int64(self._t), "grad_vals": host[0],
               "nograd_vals": host[1], "opt_state": host[2],
               # the global key stream feeds per-step dropout masks / SGLD
               # noise — without it a resume would replay early-step keys
               "rng_key": _random.get_state()}
        sched = self._lr_sched_obj()
        if sched is not None:
            # stateful schedulers (FactorScheduler's decayed base_lr etc.)
            # must not restart from scratch after a relaunch; JSON-encode
            # the tiny state into the array tree
            import json as _json
            out["lr_sched"] = np.frombuffer(
                _json.dumps(sched.state_dict()).encode(), np.uint8).copy()
        return out

    def load_state_dict(self, state):
        if self._step_fn is None:
            self._build()
        for name, tmpl in (("grad_vals", self._grad_vals),
                           ("nograd_vals", self._nograd_vals),
                           ("opt_state", self._opt_state)):
            if len(state[name]) != len(tmpl):
                raise ValueError(
                    "checkpoint %s has %d entries but the model expects %d "
                    "— wrong or since-modified model" %
                    (name, len(state[name]), len(tmpl)))
            # logical-shape gate for elastic resume: a checkpoint written
            # under ANY mesh shape holds the same GLOBAL arrays, so a
            # shape mismatch means a different model, never a different
            # mesh — refuse rather than let device_put fail cryptically
            # (or broadcast silently) mid-restore
            for t, v in zip(jax.tree.leaves(tuple(tmpl)),
                            jax.tree.leaves(tuple(state[name]))):
                if tuple(np.shape(v)) != tuple(jnp.shape(t)):
                    raise ValueError(
                        "checkpoint %s entry has shape %s but the model "
                        "expects %s — wrong model or a lossy resume"
                        % (name, tuple(np.shape(v)), tuple(jnp.shape(t))))
        self._t = int(state["t"])
        if "rng_key" in state:
            _random.set_state(state["rng_key"])
        if "lr_sched" in state:
            sched = self._lr_sched_obj()
            if sched is not None:
                import json as _json
                sched.load_state_dict(_json.loads(
                    bytes(bytearray(np.asarray(state["lr_sched"])
                                    .astype(np.uint8))).decode()))

        def place(tmpl, v):
            # jnp.array (copy), not asarray: a zero-copy alias of the
            # checkpoint's numpy buffer would be DONATED by the next
            # step — XLA would scribble outputs over external memory
            arr = jnp.array(np.asarray(v), dtype=jnp.asarray(tmpl).dtype)
            if self._mesh is not None:
                arr = jax.device_put(arr, tmpl.sharding)
            return arr

        self._grad_vals = tuple(
            place(t, v) for t, v in zip(self._grad_vals,
                                        state["grad_vals"]))
        self._nograd_vals = tuple(
            place(t, v) for t, v in zip(self._nograd_vals,
                                        state["nograd_vals"]))
        self._opt_state = jax.tree.map(place, tuple(self._opt_state),
                                       tuple(state["opt_state"]))

    def sync_params(self):
        """Write device buffers back into the Parameters (for eval/save)."""
        gi = ni = 0
        for p, m in zip(self._plist, self._grad_mask):
            if m:
                p._data._data = self._grad_vals[gi]
                gi += 1
            else:
                p._data._data = self._nograd_vals[ni]
                ni += 1
            p._data._version += 1
