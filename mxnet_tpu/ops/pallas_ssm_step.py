"""Pallas recurrence step over the state plane as it lies.

One decode step of a state-space layer (models/falcon_h1.py `mix_step`)
reads each row's state, `H = exp(dt A) H + dt x (x) B`, `y = H C`, and
writes the state back: per layer `rows x heads x P x N` float32 values
that are touched exactly once each way, 268 MB a layer at the
`falconh1_chat_closed` cell's widths. As plain XLA the read is a gather
of 4 MB slices, which the chip's compiler splits along the minor axis by
first writing the WHOLE plane out as two halves (every layer, every
step: the plane is 1.6 GB) and then copying a row at a time, and the
write is a scatter of what the step computed into a third buffer
(PERF.md, PR 37: found in the step compiled for a described v5e, before
any chip run). Here one grid step is one (row, group, block of heads):
the state block is brought to VMEM by the pipeline, updated, and written
back to the SAME slot of the SAME plane (the plane is aliased to the
kernel's result; blocks no row names are not touched). The plane is
handed in WHOLE and stays in HBM; the layer's index and the rows' slots
are data (scalar-prefetch operands), so every layer is a call site of
one jitted function.

The state lies (layers, slots, groups, N, heads of the group, P): for one
n the slab (heads, P) is whole tiles with P on the lanes, `B[n]` and
`C[n]` are SCALARS of the group (read from SMEM) and `dt x` and the decay
are (heads, P) rows, so the update is scalar-times-vector and
vector-times-vector with nothing moved between lanes and sublanes, and
`y` accumulates over n in the layout it is written in.

`mix_step`'s own arithmetic is the fallback (the CPU, shapes off the
tiles) and the tests' reference; the interpreter runs both forms on the CPU
for parity. Heads narrower than the lanes: the second form, at the end.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_fused import _cost

#: bytes of state one grid step brings to VMEM at most (as many again go
#: back, each double-buffered: four times this is held)
BLOCK_BYTES = 2 * 1024 * 1024


def step_fallback_reason(state_plane, backend=None):
    """Gate of the kernel, decided while tracing from what the code can
    observe: a float32 plane whose last two axes (either form's, below)
    are whole (8, 128) tiles, on a compiled TPU backend. Returns None where
    the kernel runs, else why `mix_step`'s XLA does."""
    backend = backend or jax.default_backend()
    if backend != "tpu":
        return ("the backend is %s: the kernel is compiled for the TPU, "
                "elsewhere XLA updates the gathered states" % backend)
    if state_plane.dtype != jnp.float32:
        return "the state plane is %s, the kernel's is float32" \
            % state_plane.dtype.name
    rows, lanes = state_plane.shape[-2:]        # (heads, P) or (N, heads x P)
    if lanes % 128 or rows % 8:
        return ("a group's (%d, %d) slab is not whole (8, 128) tiles"
                % (rows, lanes))
    return None


def heads_a_block(n_state, hpg, head_dim, block_bytes=BLOCK_BYTES):
    """Heads of a group one grid step takes: all of them where their
    states fit `block_bytes`, else the largest multiple of 8 that does
    and divides them."""
    for hb in range(hpg, 0, -1):
        if hpg % hb == 0 and (hb == hpg or hb % 8 == 0) \
                and n_state * hb * head_dim * 4 <= block_bytes:
            return hb
    return 8 if hpg % 8 == 0 else hpg


def _kernel(layer_ref, slots_ref, b_ref, c_ref, decay_ref, dtx_ref, h_ref,
            h_out_ref, y_ref, *, n_state, unroll):
    del layer_ref, slots_ref            # the index maps read them
    decay, dtx = decay_ref[0, 0], dtx_ref[0, 0]              # (hb, P)

    def fold(i, y):
        # `unroll` values of n a pass, written out: the loop's own unroll
        # is all or nothing in a kernel
        for k in range(unroll):
            n = i * unroll + k
            h = decay * h_ref[0, 0, 0, n] + b_ref[0, 0, n] * dtx
            h_out_ref[0, 0, 0, n] = h
            y = y + c_ref[0, 0, n] * h
        return y

    y_ref[0, 0] = jax.lax.fori_loop(0, n_state // unroll, fold,
                                    jnp.zeros_like(dtx))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_rows(plane, layer, slots, decay, dtx, Bm, Cm, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, _, G, N, hpg, P = plane.shape
    R = slots.shape[0]
    hb = heads_a_block(N, hpg, P)

    def state_at(b, g, j, layer_ref, slots_ref):
        return (layer_ref[0], slots_ref[b], g, 0, j, 0)

    rows = pl.BlockSpec((1, 1, hb, P), lambda b, g, j, *_: (b, g, j, 0))
    scalars = pl.BlockSpec((1, 1, N), lambda b, g, j, *_: (b * G + g, 0, 0),
                           memory_space=pltpu.SMEM)
    state = pl.BlockSpec((1, 1, 1, N, hb, P), state_at)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(R, G, hpg // hb),
        in_specs=[scalars, scalars, rows, rows, state],
        out_specs=[state, rows])
    return pl.pallas_call(
        functools.partial(_kernel, n_state=N, unroll=8 if N % 8 == 0 else 1),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(plane.shape, plane.dtype),
                   jax.ShapeDtypeStruct((R, G, hpg, P), jnp.float32)],
        # operands count the scalar-prefetch pair: the plane is the seventh
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_step",
        **_cost(5 * R * G * N * hpg * P, step_bytes(R, G, N, hpg, P)),
    )(layer, slots, Bm.reshape(R * G, 1, N), Cm.reshape(R * G, 1, N), decay,
      dtx, plane)


def step_bytes(rows, groups, n_state, hpg, head_dim):
    """Bytes one call must move: each row's state read once and written
    once, and per (row, group) B, C, the decays, `dt x` and `y`."""
    return 4 * rows * groups * (2 * n_state * hpg * head_dim
                                + 2 * n_state + 3 * hpg * head_dim)


# ---------------------------------------------------------------------------
# the second form: heads narrower than the lanes. It lies below the first so
# that the first form's lines stay where they were (a Mosaic module carries
# its file's lines, and its callers': PERF.md, PRs 41 and 42).
#
# The plane's own shape says which form a state lies in (models/falcon_h1.py
# `state_layout`). Where a head is narrower than a tile's 128 lanes (8 heads of
# 64 a group), a group's heads lie side by side: (layers, slots, groups, N,
# heads x P), the state index on the sublanes. A grid step takes as many whole
# groups as fit its block, `B` and `C` are COLUMNS (handed in as (N, 2 x groups)
# a step: a column a group), `dt x` and the decay rows, the update is
# column-times-row over whole tiles and `y` a sum over sublanes.
# ---------------------------------------------------------------------------


def groups_a_block(groups, n_state, lanes, block_bytes=BLOCK_BYTES):
    """Whole groups one grid step takes where their heads lie side by
    side: the most that divide them and whose states fit `block_bytes`."""
    return max([gb for gb in range(1, groups + 1) if groups % gb == 0
                and gb * n_state * lanes * 4 <= block_bytes] or [1])


def _kernel_lanes(layer_ref, slots_ref, cols_ref, decay_ref, dtx_ref, h_ref,
                  h_out_ref, y_ref, *, groups):
    del layer_ref, slots_ref            # the index maps read them
    cols = cols_ref[0, 0]                                    # (N, 2 gb)
    for g in range(groups):
        decay, dtx = decay_ref[0, g:g + 1], dtx_ref[0, g:g + 1]    # (1, W)
        h = decay * h_ref[0, 0, g] + cols[:, g:g + 1] * dtx        # (N, W)
        h_out_ref[0, 0, g] = h
        y_ref[0, g:g + 1] = jnp.sum(
            cols[:, groups + g:groups + g + 1] * h, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_rows_lanes(plane, layer, slots, decay, dtx, Bm, Cm, *, interpret):
    """`_step_rows` where a group's heads lie side by side: plane (layers,
    slots, G, N, W); decay, dtx and the y returned (R, G, heads, P), rows
    of W inside; Bm, Cm (R, G, N)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, _, G, N, W = plane.shape
    R = slots.shape[0]
    shape, decay, dtx = dtx.shape, decay.reshape(R, G, W), dtx.reshape(R, G, W)
    gb = groups_a_block(G, N, W)
    # a step's B and C as columns, a group each: (R, G / gb, N, 2 gb)
    cols = jnp.concatenate([
        t.reshape(R, G // gb, gb, N).transpose(0, 1, 3, 2) for t in (Bm, Cm)],
        axis=-1)

    def state_at(b, j, layer_ref, slots_ref):
        return (layer_ref[0], slots_ref[b], j, 0, 0)

    rows = pl.BlockSpec((1, gb, W), lambda b, j, *_: (b, j, 0))
    state = pl.BlockSpec((1, 1, gb, N, W), state_at)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(R, G // gb),
        in_specs=[pl.BlockSpec((1, 1, N, 2 * gb),
                               lambda b, j, *_: (b, j, 0, 0)),
                  rows, rows, state],
        out_specs=[state, rows])
    new, y = pl.pallas_call(
        functools.partial(_kernel_lanes, groups=gb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(plane.shape, plane.dtype),
                   jax.ShapeDtypeStruct((R, G, W), jnp.float32)],
        # operands count the scalar-prefetch pair: the plane is the sixth
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_step",
        **_cost(5 * R * G * N * W, step_bytes(R, G, N, 1, W)),
    )(layer, slots, cols, decay, dtx, plane)
    return new, y.reshape(shape)


def ssm_step(plane, layer, slots, decay, dtx, Bm, Cm, *, interpret=False):
    """One recurrence step a row over one layer of the state plane, in
    place.

    plane: (layers, slots, G, N, heads of a group, P) float32, whole, or
           (layers, slots, G, N, heads of a group x P) where narrow heads
           lie side by side (`falcon_h1.state_layout`).
    layer: int32 scalar, the layer's index in the plane, as DATA.
    slots: (R,) int32, each row's slot; padded rows name the null slot.
    decay: (R, G, hpg, 1 or P) float32, `exp(dt A)` a head.
    dtx:   (R, G, hpg, P) float32, `dt x`.
    Bm, Cm: (R, G, N) float32, a group's.
    Returns the plane with the rows' states updated (`decay H + B (x) dt
    x`; the same buffer where the plane is donated) and y (R, G, hpg, P)
    float32, `sum_n C[n] H[n]` of the new states."""
    step = _step_rows if plane.ndim == 6 else _step_rows_lanes
    return step(
        plane, jnp.reshape(layer, (1,)).astype(jnp.int32),
        slots.astype(jnp.int32),
        jnp.broadcast_to(decay, dtx.shape).astype(jnp.float32),
        dtx.astype(jnp.float32), Bm.astype(jnp.float32),
        Cm.astype(jnp.float32), interpret=interpret)
