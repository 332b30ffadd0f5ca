"""Persistent Pallas fused-RNN scan kernels.

Why a hand kernel: the word-LM LSTM trained at MFU 0.0023 on 2026-07-31
(BENCH_LAST_TPU.json: 36.9k tok/s) and the latency-floor analysis of that
run pins the cause: after the cuDNN-style input-projection hoist (ops/nn.py
`_scan_layer`), the `lax.scan` body still launches one tiny `h @ wh.T`
matmul per timestep — T=35 times per layer-direction per step — with the
h/c carry round-tripping HBM between XLA while-loop iterations. Each
iteration is microseconds of MXU work under ~100 µs of loop overhead: a
latency-bound loop, not a compute-bound one. This is the same
fusion-beats-launch-overhead argument TVM makes for small-operator
chains (arXiv:1802.04799) and the reason the reference shells out to
cuDNN's fused RNN (`src/operator/cudnn_rnn-inl.h`) instead of composing
ops.

The fix: run one entire layer-direction of the recurrence as a SINGLE
`pallas_call`.

- Grid `(batch-tiles, T)`, time innermost — TPU grid execution is
  sequential, so the recurrence order is preserved.
- The recurrent weight `wh` has a constant BlockSpec index, so it is
  DMA'd into VMEM ONCE and stays resident across all T steps
  (revisit-elision — the same trick `pallas_paged.py` uses for dead
  table slots).
- The h/c carry lives in f32 VMEM scratch for the whole sequence: it
  never touches HBM mid-sequence. The scan path moves
  ~4·N·H·itemsize of carry bytes per step; here that term is zero
  (benchmarks/rnn_bytes_report.py is the A/B instrument).
- The pre-hoisted input projections `px` stream through the BlockSpec
  index map one `(1, bn, G·H)` time-block per grid step, and the gate
  nonlinearities + cell update are fused into the same kernel — one
  launch per sequence instead of ~T launches.

Training runs through a jax.custom_vjp: forward saves the per-step
(h, c) sequence; backward is a second persistent kernel scanning time in
REVERSE (via the index map), fusing the dGates/dCell/dH chains and
accumulating `dWh` in VMEM scratch across the whole grid. The gradient
for `wi`/`bi`/`bh` flows through the hoisted projection outside the
kernel (`dpx` is a kernel output), so every parameter is covered.

Modes: `lstm` first-class, `rnn_relu`/`rnn_tanh` cheaply (their backward
needs no gate recompute at all); `gru` falls back to the scan path (its
reset-gate product needs the hidden bias inside the cell — not worth a
third kernel until a workload demands it).

Selection: `MXNET_FUSED_RNN=1` (read at trace time) or
`RNN(..., fused=True)` routes `ops/nn.py _scan_layer` through these
kernels; everything else — gru, non-Mosaic-tileable hidden sizes
(H % 128 on real TPUs), exotic dtypes, VMEM-overflowing shapes — keeps
the `lax.scan` path, which is preserved verbatim as the fallback and
parity oracle. On CPU the kernels run in Pallas interpreter mode; the
equality tests in tests/test_pallas_rnn.py prove forward + VJP against
the scan path there; `chip_smoke.py` compiles the LSTM kernels with
Mosaic and compares them with the scan path on the chip.

Every pallas_call declares a `CostEstimate` (house pattern from
`pallas_fused.py`/`pallas_paged.py`): on TPU the kernel is an opaque
custom call, and without a declared cost the XLA cost model — the
bytes-A/B instrument — would count it as moving zero bytes.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_attention import default_interpret
from .pallas_fused import _cost


def fuse_rnn_enabled():
    """MXNET_FUSED_RNN=1 — read at trace time (docs/ENV_VARS.md)."""
    return os.environ.get("MXNET_FUSED_RNN", "0") == "1"


def use_fused(fused):
    """Resolve the per-call `fused` override against the env default."""
    return fuse_rnn_enabled() if fused is None else bool(fused)


_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4}
#: grid cap for the interpreter, whose grid is a python loop (CPU tests):
#: beyond this the scan fallback is the better path. The chip has no such
#: limit.
_MAX_GRID = 4096
#: VMEM budget for resident weights + streamed blocks + scratch; the
#: physical VMEM is ~16 MB but the pipeline double-buffers streamed blocks
_VMEM_BUDGET = 10 << 20


def _batch_tile(mode, N, H, itemsize, sublane=1):
    """Largest batch tile bn (divisor of N, <= 256, multiple of `sublane`
    — the Mosaic second-to-minor tile on real TPUs, 1 in interpret mode)
    whose bwd-pass VMEM footprint fits: wh + the f32 dWh accumulator stay
    resident; px/dpx and the four [bn, H] sequence blocks are
    double-buffered by the pipeline; dh/dc carries are f32 scratch.
    None = no tile fits (fallback)."""
    G = _GATES[mode]
    resident = G * H * H * (itemsize + 4)        # wh + f32 dWh scratch
    for bn in range(min(N, 256), 0, -1):
        if N % bn or bn % sublane:
            continue
        streamed = 2 * (2 * bn * G * H + 4 * bn * H) * itemsize
        scratch = 2 * bn * H * 4
        if resident + streamed + scratch <= _VMEM_BUDGET:
            return bn
    return None


def _sublane(dtype, interpret):
    """Mosaic sublane tile for the batch dim on real TPUs (8 f32 /
    16 bf16); the interpreter has no tiling constraint."""
    if interpret:
        return 1
    return 16 if jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16) else 8


def fused_eligible(mode, T, N, H, *dtypes, interpret=None):
    """Gate for the fused kernels; callers fall back to the lax.scan path
    when False. On real TPUs H must be Mosaic-tile eligible (lane dim a
    multiple of 128 — the kernel splits gates at H boundaries); interpret
    mode (CPU tests) has no lane constraint but caps the grid so the
    python-loop interpreter stays usable."""
    if mode not in _GATES:
        return False  # gru: hidden bias feeds the reset-gate product
    if T < 1 or N < 1 or H < 1:
        return False
    dts = {jnp.dtype(d) for d in dtypes}
    if len(dts) != 1 or dts - {jnp.dtype(jnp.float32),
                               jnp.dtype(jnp.bfloat16)}:
        return False
    if interpret is None:
        interpret = default_interpret()
    if not interpret and H % 128 != 0:
        return False
    # bn must also be sublane-aligned on real TPUs (batch sizes with no
    # 8/16-multiple divisor fall back instead of failing Mosaic compile)
    bn = _batch_tile(mode, N, H, jnp.dtype(dtypes[0]).itemsize,
                     _sublane(dtypes[0], interpret))
    if bn is None:
        return False
    return not interpret or (N // bn) * T <= _MAX_GRID


def fwd_declared_cost(mode, T, N, H, dtype):
    """(flops, bytes, transcendentals) the FORWARD kernel declares via
    CostEstimate — what the TPU cost model counts for the custom call,
    and the single source of truth benchmarks/rnn_bytes_report.py prints.
    The bytes term is the kernel's true HBM traffic: wh read ONCE, px
    streamed once, ys (+cs) written once, h0/hT (+c0/cT) once — and NO
    per-step h/c carry term (the carry lives in VMEM scratch)."""
    G = _GATES[mode]
    GH = G * H
    sz = jnp.dtype(dtype).itemsize
    n_states = 2 if mode == "lstm" else 1
    nbytes = (GH * H * sz + T * N * GH * sz
              + n_states * (T * N + 2 * N) * H * sz)
    flops = T * N * (2 * GH * H + 10 * GH)
    trans = T * N * (5 * H if mode == "lstm" else
                     (H if mode == "rnn_tanh" else 0))
    return flops, nbytes, trans


def bwd_declared_cost(mode, T, N, H, dtype):
    """(flops, bytes, transcendentals) the BACKWARD kernel declares.
    wh + the f32 dWh accumulator cross HBM once for the whole sequence;
    the sequence streams (px/dpx + hprev/cprev/cs/dys for lstm, ys/hprev/
    dys/dpx for the simple modes) once each; dh/dc carries stay in VMEM."""
    G = _GATES[mode]
    GH = G * H
    sz = jnp.dtype(dtype).itemsize
    if mode == "lstm":
        flops = T * N * (6 * GH * H + 20 * GH)
        npasses = 2 * T * N * GH + 4 * T * N * H
        trans = T * N * 5 * H
    else:
        flops = T * N * (4 * GH * H + 4 * H)
        npasses = T * N * GH + 3 * T * N * H
        trans = 0
    nbytes = GH * H * (sz + 4) + npasses * sz + 4 * N * H * sz
    return flops, nbytes, trans


def _dot_t(a, b):
    """a [m, k] @ b.T for b [n, k] -> [m, n], f32 accumulation (MXU)."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _dot(a, b):
    """a [m, k] @ b [k, n] -> [m, n], f32 accumulation (MXU)."""
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _outer_acc(a, b):
    """a [n, m].T @ b [n, k] -> [m, k] — the dWh per-step contribution."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward kernel: the whole sequence in one launch
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, mode):
    """One grid step = one timestep of one batch tile. wh is VMEM-resident
    (constant block index); h/c carry in f32 scratch across all T steps —
    the carry never touches HBM mid-sequence."""
    from jax.experimental import pallas as pl

    if mode == "lstm":
        (px_ref, h0_ref, c0_ref, wh_ref,
         ys_ref, cs_ref, hT_ref, cT_ref, h_scr, c_scr) = refs
    else:
        px_ref, h0_ref, wh_ref, ys_ref, hT_ref, h_scr = refs
        c0_ref = c_scr = None
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)
        if mode == "lstm":
            c_scr[...] = c0_ref[...].astype(jnp.float32)

    w = wh_ref[...]
    h = h_scr[...]
    pre = px_ref[0].astype(jnp.float32) + _dot_t(h.astype(w.dtype), w)
    if mode == "lstm":
        i, f, g, o = jnp.split(pre, 4, axis=-1)
        i = jax.nn.sigmoid(i)
        f = jax.nn.sigmoid(f)
        o = jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c2 = f * c_scr[...] + i * g
        h2 = o * jnp.tanh(c2)
        c_scr[...] = c2
        cs_ref[0] = c2.astype(cs_ref.dtype)

        @pl.when(t == pl.num_programs(1) - 1)
        def _emit_cT():
            cT_ref[...] = c2.astype(cT_ref.dtype)
    elif mode == "rnn_relu":
        h2 = jnp.maximum(pre, 0.0)
    else:  # rnn_tanh
        h2 = jnp.tanh(pre)
    h_scr[...] = h2
    ys_ref[0] = h2.astype(ys_ref.dtype)

    # only the final state is observable (constant block index): emit once
    # instead of T redundant stores (the `_emit` pattern below)
    @pl.when(t == pl.num_programs(1) - 1)
    def _emit_hT():
        hT_ref[...] = h2.astype(hT_ref.dtype)


def _fwd_call(mode, px, h0, c0, wh, reverse, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, N, GH = px.shape
    H = wh.shape[1]
    dt = px.dtype
    sz = jnp.dtype(dt).itemsize
    bn = _batch_tile(mode, N, H, sz, _sublane(dt, interpret))
    nb = N // bn

    # direction lives ENTIRELY in the time index map (grid step t touches
    # timestep T-1-t for the reverse leg of a bidirectional layer) — no
    # jnp.flip copies of the [T, N, ·] sequences
    tmap = (lambda i, t: (T - 1 - t, i, 0)) if reverse \
        else (lambda i, t: (t, i, 0))
    seq = pl.BlockSpec((1, bn, GH), tmap)
    seq_h = pl.BlockSpec((1, bn, H), tmap)
    vec = pl.BlockSpec((bn, H), lambda i, t: (i, 0))
    whole = pl.BlockSpec((GH, H), lambda i, t: (0, 0))

    in_specs = [seq, vec, whole]
    args = [px, h0, wh]
    out_shape = [jax.ShapeDtypeStruct((T, N, H), dt)]
    out_specs = [seq_h]
    scratch = [pltpu.VMEM((bn, H), jnp.float32)]
    if mode == "lstm":
        in_specs = [seq, vec, vec, whole]
        args = [px, h0, c0, wh]
        out_shape += [jax.ShapeDtypeStruct((T, N, H), dt)]
        out_specs += [seq_h]
        scratch += [pltpu.VMEM((bn, H), jnp.float32)]
    out_shape += [jax.ShapeDtypeStruct((N, H), dt)]
    out_specs += [vec]
    if mode == "lstm":
        out_shape += [jax.ShapeDtypeStruct((N, H), dt)]
        out_specs += [vec]

    # the declared cost IS the claim the bytes A/B tests — see
    # fwd_declared_cost (no per-step h/c HBM carry, wh read once)
    flops, nbytes, trans = fwd_declared_cost(mode, T, N, H, dt)
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, mode=mode),
        out_shape=out_shape,
        grid=(nb, T),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        interpret=interpret,
        **_cost(flops, nbytes, trans),
    )(*args)
    if mode == "lstm":
        ys, cs, hT, cT = outs
        return ys, cs, hT, cT
    ys, hT = outs
    return ys, None, hT, None


# ---------------------------------------------------------------------------
# backward kernel: persistent reverse-time scan
# ---------------------------------------------------------------------------


def _bwd_kernel(*refs, mode, T, nb):
    """Persistent scan opposite to the forward direction (the index maps
    in _bwd_call feed blocks in reversed time order). Fuses the
    dGates/dCell/dH chain; dWh accumulates in f32 VMEM scratch across the
    ENTIRE grid and is emitted once at the last grid step (the
    `_stats_kernel` accumulator pattern)."""
    from jax.experimental import pallas as pl

    if mode == "lstm":
        (px_ref, hp_ref, cp_ref, cs_ref, wh_ref, dys_ref, dhT_ref, dcT_ref,
         dpx_ref, dh0_ref, dc0_ref, dwh_ref, dh_scr, dc_scr, dwh_scr) = refs
    else:
        (ys_ref, hp_ref, wh_ref, dys_ref, dhT_ref,
         dpx_ref, dh0_ref, dwh_ref, dh_scr, dwh_scr) = refs
        px_ref = cp_ref = cs_ref = dcT_ref = dc0_ref = dc_scr = None
    i = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init_carry():
        dh_scr[...] = dhT_ref[...].astype(jnp.float32)
        if mode == "lstm":
            dc_scr[...] = dcT_ref[...].astype(jnp.float32)

    @pl.when((t == 0) & (i == 0))
    def _init_acc():
        dwh_scr[...] = jnp.zeros_like(dwh_scr)

    w = wh_ref[...]
    hp = hp_ref[0]
    dh = dh_scr[...] + dys_ref[0].astype(jnp.float32)
    if mode == "lstm":
        # recompute the gates from the saved (h, c) sequence — one matmul,
        # instead of storing the 4·H gate tensor in forward
        pre = px_ref[0].astype(jnp.float32) + _dot_t(hp.astype(w.dtype), w)
        ig, fg, gg, og = jnp.split(pre, 4, axis=-1)
        ig = jax.nn.sigmoid(ig)
        fg = jax.nn.sigmoid(fg)
        og = jax.nn.sigmoid(og)
        gg = jnp.tanh(gg)
        tc = jnp.tanh(cs_ref[0].astype(jnp.float32))
        do = dh * tc
        dc = dc_scr[...] + dh * og * (1.0 - tc * tc)
        dpre = jnp.concatenate(
            [dc * gg * ig * (1.0 - ig),
             dc * cp_ref[0].astype(jnp.float32) * fg * (1.0 - fg),
             dc * ig * (1.0 - gg * gg),
             do * og * (1.0 - og)], axis=-1)
        dc_prev = dc * fg
        dc_scr[...] = dc_prev

        @pl.when(t == T - 1)
        def _emit_dc0():
            dc0_ref[...] = dc_prev.astype(dc0_ref.dtype)
    elif mode == "rnn_relu":
        # relu'(pre) == [y > 0] — no recompute matmul needed (compared
        # in f32: the v5e vector unit has no bf16 compare)
        dpre = jnp.where(ys_ref[0].astype(jnp.float32) > 0, dh, 0.0)
    else:  # rnn_tanh: tanh'(pre) = 1 - y^2
        y = ys_ref[0].astype(jnp.float32)
        dpre = dh * (1.0 - y * y)
    dpx_ref[0] = dpre.astype(dpx_ref.dtype)
    dh_prev = _dot(dpre.astype(w.dtype), w)
    dh_scr[...] = dh_prev

    @pl.when(t == T - 1)
    def _emit_dh0():
        dh0_ref[...] = dh_prev.astype(dh0_ref.dtype)

    dwh_scr[...] = dwh_scr[...] + _outer_acc(dpre.astype(hp.dtype), hp)

    @pl.when((t == T - 1) & (i == nb - 1))
    def _emit():
        dwh_ref[...] = dwh_scr[...]


def _bwd_call(mode, px, ys, hprev, cprev, cs, wh, dys, dhT, dcT, reverse,
              interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, N, GH = px.shape
    H = wh.shape[1]
    dt = px.dtype
    sz = jnp.dtype(dt).itemsize
    bn = _batch_tile(mode, N, H, sz, _sublane(dt, interpret))
    nb = N // bn

    # backward walks time OPPOSITE to forward, again purely in the index
    # map: grid step t touches timestep T-1-t for a forward layer, t for
    # a reverse one
    tmap = (lambda i, t: (t, i, 0)) if reverse \
        else (lambda i, t: (T - 1 - t, i, 0))
    rseq = pl.BlockSpec((1, bn, GH), tmap)
    rseq_h = pl.BlockSpec((1, bn, H), tmap)
    vec = pl.BlockSpec((bn, H), lambda i, t: (i, 0))
    whole = pl.BlockSpec((GH, H), lambda i, t: (0, 0))
    acc = pl.BlockSpec((GH, H), lambda i, t: (0, 0))

    kern = functools.partial(_bwd_kernel, mode=mode, T=T, nb=nb)
    scratch = [pltpu.VMEM((bn, H), jnp.float32)]
    if mode == "lstm":
        in_specs = [rseq, rseq_h, rseq_h, rseq_h, whole, rseq_h, vec, vec]
        args = (px, hprev, cprev, cs, wh, dys, dhT, dcT)
        out_shape = [jax.ShapeDtypeStruct((T, N, GH), dt),
                     jax.ShapeDtypeStruct((N, H), dt),
                     jax.ShapeDtypeStruct((N, H), dt),
                     jax.ShapeDtypeStruct((GH, H), jnp.float32)]
        out_specs = [rseq, vec, vec, acc]
        scratch += [pltpu.VMEM((bn, H), jnp.float32)]
    else:
        in_specs = [rseq_h, rseq_h, whole, rseq_h, vec]
        args = (ys, hprev, wh, dys, dhT)
        out_shape = [jax.ShapeDtypeStruct((T, N, GH), dt),
                     jax.ShapeDtypeStruct((N, H), dt),
                     jax.ShapeDtypeStruct((GH, H), jnp.float32)]
        out_specs = [rseq, vec, acc]
    scratch += [pltpu.VMEM((GH, H), jnp.float32)]
    flops, nbytes, trans = bwd_declared_cost(mode, T, N, H, dt)
    outs = pl.pallas_call(
        kern,
        out_shape=out_shape,
        grid=(nb, T),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        interpret=interpret,
        **_cost(flops, nbytes, trans),
    )(*args)
    if mode == "lstm":
        dpx, dh0, dc0, dwh = outs
        return dpx, dh0, dc0, dwh
    dpx, dh0, dwh = outs
    return dpx, dh0, None, dwh


# ---------------------------------------------------------------------------
# custom-VJP assembly
# ---------------------------------------------------------------------------


def _shift_prev(state0, seq, reverse):
    """The h_{prev}/c_{prev} stream the backward kernel reads: the saved
    sequence shifted one step along the scan direction, with the initial
    state at the entry end — [h0, ys[0..T-2]] forward, [ys[1..], h0] for
    a reverse layer (whose scan enters at t = T-1)."""
    if reverse:
        return jnp.concatenate([seq[1:], state0[None]], axis=0)
    return jnp.concatenate([state0[None], seq[:-1]], axis=0)


@functools.lru_cache(maxsize=None)
def _make_fused(mode, reverse, interpret):
    """Build the custom-VJP fused scan for one (mode, reverse, interpret)
    static configuration — cached so repeated layers/directions share one
    traced op (the `pallas_fused._make_fused` pattern). Residuals are the
    per-step (h, c) sequence; backward replays the gates from them."""

    if mode == "lstm":
        @jax.custom_vjp
        def f(px, h0, c0, wh):
            ys, _cs, hT, cT = _fwd_call(mode, px, h0, c0, wh, reverse,
                                        interpret)
            return ys, hT, cT

        def fwd(px, h0, c0, wh):
            ys, cs, hT, cT = _fwd_call(mode, px, h0, c0, wh, reverse,
                                       interpret)
            return (ys, hT, cT), (px, h0, c0, wh, ys, cs)

        def bwd(res, cts):
            px, h0, c0, wh, ys, cs = res
            dys, dhT, dcT = cts
            hprev = _shift_prev(h0, ys, reverse)
            cprev = _shift_prev(c0, cs, reverse)
            dpx, dh0, dc0, dwh = _bwd_call(
                mode, px, ys, hprev, cprev, cs, wh,
                dys.astype(px.dtype), dhT.astype(px.dtype),
                dcT.astype(px.dtype), reverse, interpret)
            return dpx, dh0, dc0, dwh.astype(wh.dtype)
    else:
        @jax.custom_vjp
        def f(px, h0, wh):
            ys, _cs, hT, _cT = _fwd_call(mode, px, h0, None, wh, reverse,
                                         interpret)
            return ys, hT

        def fwd(px, h0, wh):
            ys, _cs, hT, _cT = _fwd_call(mode, px, h0, None, wh, reverse,
                                         interpret)
            return (ys, hT), (px, h0, wh, ys)

        def bwd(res, cts):
            px, h0, wh, ys = res
            dys, dhT = cts
            hprev = _shift_prev(h0, ys, reverse)
            dpx, dh0, _dc0, dwh = _bwd_call(
                mode, px, ys, hprev, None, None, wh,
                dys.astype(px.dtype), dhT.astype(px.dtype), None,
                reverse, interpret)
            return dpx, dh0, dwh.astype(wh.dtype)

    f.defvjp(fwd, bwd)
    return f


def fused_scan_layer(mode, pxs, h0, c0, wh, reverse=False, interpret=None):
    """One (direction of one) RNN layer from the PRE-PROJECTED inputs
    `pxs` [T, N, G·H] — the drop-in replacement for the `lax.scan` in
    ops/nn.py `_scan_layer`, same (ys, hT, cT) contract.

    The reverse direction lives entirely in the kernels' time index maps
    (forward reads/writes timestep T-1-t; backward walks the opposite
    order), so a bidirectional layer pays no jnp.flip copies of the
    [T, N, ·] sequences. Callers gate on `fused_eligible()`.
    """
    if interpret is None:
        interpret = default_interpret()
    f = _make_fused(mode, bool(reverse), bool(interpret))
    if mode == "lstm":
        ys, hT, cT = f(pxs, h0, c0, wh)
    else:
        ys, hT = f(pxs, h0, wh)
        cT = c0  # parity with the scan path: c is carried through unchanged
    return ys, hT, cT
