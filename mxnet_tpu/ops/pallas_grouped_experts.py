"""Pallas grouped product: the held experts over the pairs that chose
them, one kernel a layer.

What it replaces in an expert layer (`models/latent_moe.py`
`grouped_experts`, whose loop stays as the fallback and as the tests'
reference) is a `fori_loop` of one pass a tile whose bodies XLA runs one
after another: a pass finds its expert, gathers its rows, multiplies and
writes, and the next expert's matrices cannot start to arrive meanwhile.
In the `nemotron3_reason_closed` cell that was 2.2-2.3 ms a layer for
1.28 GB of experts, 560 GB/s, and 56 % of a decode step (PERF.md §6,
PRs 42 and 43). Here:

- the pairs sorted by expert lie in a buffer in which each expert starts
  on a tile boundary, gathered ONCE a layer by the caller; the grid runs
  over (tile, block of the expert width). Which expert a tile belongs to
  and how many tiles the call has are DATA (scalar-prefetch operands: the
  first is read by the matrices' index maps, the second is the grid's
  extent), so the pipeline's own double buffering fetches the NEXT
  expert's blocks while this tile multiplies: the touched experts' bytes
  stream back to back. Consecutive tiles of one expert whose matrices
  are one block keep it and read nothing; an expert no pair chose is
  never read;
- a tile's rows come from the shapes (`tile_rows`): a few sublanes at
  decode, where an expert gets a handful of rows and a product costs the
  matrix unit what its weights cost to load whatever the rows, 128 at a
  prefill;
- an expert's form comes from its weights as in `expert_ffn`: two
  matrices a squared ReLU, three a SwiGLU. The expert width is blocked
  where it is whole lanes (`block_width`): a step multiplies the tile by
  a column block of the up (and gate) matrices, applies the activation
  in float32, rounds the hidden rows ONCE to the operands' dtype and
  adds their product with the matching row block of the down matrix to
  a float32 sum in VMEM. A width that is not whole lanes (1,856) is
  blocked by whole sublanes, its up matrix taken by the transpose
  (`_by_rows`). The gate (`experts_unfit`) lets through the experts that
  are ONE block: wider ones gain nothing over the loop on the chip;
- bf16 operands, products summed in float32: XLA's own path rounds the
  hidden rows twice (after the product, after the activation).

It enters a step program as the other serving kernels do
(ops/pallas_splice.py): traced and lowered once a process and a shape,
every expert layer of a program a call site of one jitted function.

The interpreter runs the same kernel on the CPU for the parity tests
(tests/test_grouped_experts_kernel.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_fused import _cost
from .pallas_paged import _SUBLANES
from .pallas_splice import Spliced

#: what a grid step may hold in VMEM (the compiler's own limit is 16 MiB
#: of the chip's 128)
VMEM_LIMIT = 100 * 1024 * 1024
#: bytes of ONE expert's matrices a grid step brings to VMEM at most (as
#: many again are on their way: twice this is held)
BLOCK_BYTES = 24 * 1024 * 1024
#: the most rows of one tile
MAX_TILE = 128


def tile_rows(pairs, n_held, dtype=jnp.bfloat16):
    """Rows of one tile for `pairs` (token, choice) pairs over `n_held`
    experts: the power of two that holds the rows an expert gets if every
    pair is held here, no less than the dtype's sublane packing and no
    more than `MAX_TILE`. The buffer pads every held expert to a tile, so
    a tile past the expert's rows is buffer nobody reads."""
    least = _SUBLANES[jnp.dtype(dtype).itemsize]
    mean = -(-pairs // n_held)
    return int(min(MAX_TILE, max(least, 1 << (mean - 1).bit_length())))


def block_width(d_model, d_expert, n_mats, itemsize, block_bytes=None):
    """Columns of the expert width one grid step takes: the widest divisor
    of it that is whole 128-lane tiles (whole sublanes where the width is
    not whole lanes and lies on the sublanes, `_by_rows`) and whose
    `n_mats` blocks fit `block_bytes` (`BLOCK_BYTES`). None where there
    is none."""
    block_bytes = block_bytes or BLOCK_BYTES
    quantum = _SUBLANES[itemsize] if _by_rows(d_expert) else 128
    for width in range(d_expert - d_expert % quantum, 0, -quantum):
        if d_expert % width == 0 \
                and n_mats * d_model * width * itemsize <= block_bytes:
            return width
    return None


def _by_rows(d_expert):
    """Whether the up (and gate) matrices are handed to the kernel by
    their transposes, (E, F, D) as the down matrix lies: where the expert
    width F is not whole lanes. The chip keeps an array whose last axis is
    not whole lanes but whose second-last is with THAT one innermost (no
    padding), so the transpose is the array as it lies and (E, D, F) as
    the kernel's operand would be a copy of every expert, every call
    (found in the step compiled for a described v5e, PERF.md §6, PR 43;
    `tests/test_serving_live_width.py` pins it)."""
    return d_expert % 128 != 0


def experts_unfit(d_model, d_expert, n_mats, dtype, backend=None):
    """Gate of the kernel, from what the code can observe: a compiled TPU
    backend (the interpreter is the tests' tool), bf16 operands (the
    float32 paths keep the loop, which the kernel is tested against), a
    model width of whole lanes and an expert whose matrices are ONE block
    of a grid step. Wider experts (DeepSeek's 88 MB, Trinity's 57) the
    kernel takes by blocks of their width, and wins nothing: a pass of
    XLA's loop already reads such an expert near the kernel's rate, what
    surrounds the pass is a tenth of it, and the kernel's buffer of
    sorted rows and its lowering cost a prefill and a set-up more than
    the loop's (on the chip, PERF.md §6, PR 43: both cells 1-2 % slower
    through it, `setup_s` + 6 %). Returns None where the kernel runs, else
    why `grouped_experts`' loop does."""
    backend = backend or jax.default_backend()
    if backend != "tpu":
        return ("the backend is %s: the kernel is compiled for the TPU, "
                "elsewhere XLA loops over the experts' tiles" % backend)
    if jnp.dtype(dtype) != jnp.bfloat16:
        return ("the experts are %s, the kernel's operands are bfloat16"
                % jnp.dtype(dtype).name)
    if d_model % 128:
        return "the model width %d is not a multiple of the 128-lane tile" \
            % d_model
    if block_width(d_model, d_expert, n_mats, 2) != d_expert:
        return ("an expert's %d matrices of %d x %d are %d MiB, more than "
                "the %d MiB block of a grid step: a pass of XLA's loop reads "
                "such an expert near the kernel's rate already"
                % (n_mats, d_model, d_expert,
                   n_mats * d_model * d_expert * 2 // 2 ** 20,
                   BLOCK_BYTES // 2 ** 20))
    return None


def _kernel(expert_ref, n_ref, x_ref, *refs, steps, by_rows):
    """Grid step (t, j): tile t against block j of its expert's width."""
    from jax.experimental import pallas as pl

    del expert_ref, n_ref       # the index maps and the grid read them
    if steps > 1:
        *refs, acc_ref = refs
    *w_refs, down_ref, o_ref = refs
    f32 = jnp.float32
    x = x_ref[...]                                           # (tile, D)

    def hidden(w_ref):                                       # (tile, block)
        return jax.lax.dot_general(
            x, w_ref[0], (((1,), (1 if by_rows else 0,)), ((), ())),
            preferred_element_type=f32)

    hid = hidden(w_refs[-1])
    if len(w_refs) == 2:
        hid = jax.nn.silu(hidden(w_refs[0])) * hid
    else:
        hid = jnp.square(jnp.maximum(hid, 0.0))
    part = jnp.dot(hid.astype(x.dtype), down_ref[0],
                   preferred_element_type=f32)               # (tile, D)
    if steps == 1:
        o_ref[...] = part.astype(o_ref.dtype)
        return
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _first():
        acc_ref[...] = part

    @pl.when(j > 0)
    def _further():
        acc_ref[...] += part

    @pl.when(j == steps - 1)
    def _last():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_call(tile_expert, n_tiles, x, *weights, tile, block, by_rows,
                 interpret):
    """The kernel over x (tiles x `tile`, D), tile t's rows one expert's:
    `tile_expert` (tiles,) int32 names it, `n_tiles` (1,) int32 says how
    many tiles are real (DATA: the grid's extent; at least one), `weights`
    are ([gate,] up, down (E, F, D)), up and gate (E, D, F) or, `by_rows`,
    (E, F, D), and `block` the part of F a step takes. Returns (tiles x
    `tile`, D) in x's dtype; the rows of tiles past `n_tiles` are not
    written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    D = x.shape[1]
    n_held, F = weights[-1].shape[:2]
    steps = F // block

    def rows(t, j, *_):
        return t, 0

    def by_column(t, j, expert_ref, n_ref):
        return expert_ref[t], 0, j

    def by_row(t, j, expert_ref, n_ref):
        return expert_ref[t], j, 0

    down = pl.BlockSpec((1, block, D), by_row)
    up = down if by_rows else pl.BlockSpec((1, D, block), by_column)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles[0], steps),
        in_specs=[pl.BlockSpec((tile, D), rows)]
        + [up] * (len(weights) - 1) + [down],
        out_specs=pl.BlockSpec((tile, D), rows),
        scratch_shapes=[pltpu.VMEM((tile, D), jnp.float32)] * (steps > 1))
    # declared for XLA's scheduler as every held expert read once: what
    # a call moves follows the experts the pairs chose, known on the
    # device alone
    return pl.pallas_call(
        functools.partial(_kernel, steps=steps, by_rows=by_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="grouped_experts",
        **_cost(2 * len(weights) * x.shape[0] * D * F,
                (len(weights) * n_held * D * F + 2 * x.size)
                * x.dtype.itemsize,
                x.shape[0] * F if len(weights) == 3 else 0),
    )(tile_expert, n_tiles, x, *weights)


_spliced = Spliced(
    "grouped_experts", _kernel_call,
    lambda tile_expert, n_tiles, x, *weights, **static:
    jax.core.ShapedArray(x.shape, x.dtype))
_lowered_once = _spliced.lowered_once


@functools.partial(jax.jit, static_argnames=("tile", "block", "by_rows",
                                             "interpret"))
def _experts(*operands, interpret, **static):
    """One function a shape and a process: every expert layer of a step
    program is a call site of it."""
    if interpret:
        return _kernel_call(*operands, interpret=True, **static)
    return _spliced(*operands, **static)


def grouped_experts(x, tile_expert, n_tiles, we_gate, we_up, we_down, *,
                    tile, block=None, interpret=False):
    """Every tile of sorted pairs against its expert's matrices.

    x:           (tiles x `tile`, D), the pairs' rows sorted by expert,
                 each expert's run starting on a tile boundary; what lies
                 past a run's end in its last tile is any finite row.
    tile_expert: (tiles,) int32, the expert of each tile, inside the
                 stacks; past the real tiles, anything inside them.
    n_tiles:     int32 scalar, DATA: the tiles that hold pairs. With none
                 the first tile is multiplied all the same.
    we_gate:     (E, D, F) or None; we_up (E, D, F); we_down (E, F, D):
                 `expert_ffn`'s forms, stacked by expert.
    block:       columns of F a grid step takes, `block_width`'s by
                 default (the tests scale it down with their shapes).
    Returns (tiles x `tile`, D) in x's dtype: row r is its expert's output
    for x[r] where tile r // `tile` is real, unspecified elsewhere."""
    D, F = we_up.shape[1:]
    by_rows = _by_rows(F)
    ups = [w.swapaxes(1, 2) if by_rows else w
           for w in (we_gate, we_up) if w is not None]
    block = block or block_width(D, F, len(ups) + 1, x.dtype.itemsize)
    return _experts(
        tile_expert.astype(jnp.int32),
        jnp.maximum(jnp.reshape(n_tiles, (1,)), 1).astype(jnp.int32),
        x, *ups, we_down, tile=tile, block=block, by_rows=by_rows,
        interpret=interpret)
