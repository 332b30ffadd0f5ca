"""A Pallas kernel as a serving step program holds it: imported ahead of
the first trace, traced and lowered once a process and a shape, and merged
into every program that calls it.

Why (PERF.md §6, "`setup_s`, learned twice"): a warm process traces and
lowers every step program anew (the persistent cache's key is made from
the module), so whatever is traced or lowered per layer, or per program,
is paid in every `setup_s`; on the chip's host one trace of a kernel and
its lowering to Mosaic take about 0.45 s. So a kernel's module is lowered
ONCE for its operands' shapes and kept as text (`Spliced.lowered_once`),
and a program parses it, merges it in and calls it. (`jax.export` keeps
such a module too, but a program that calls an exported one returns its
arrays COMMITTED to their device, and an unplaced engine's programs
would then meet a second signature after the first step.)
"""
from __future__ import annotations

import functools
import importlib
import threading

import jax
import jax.extend.core
import jax.numpy as jnp
from jax.interpreters import mlir
from jaxlib.mlir.dialects import func


def preload():
    """Start importing Pallas on a thread of its own and return at once.
    The import is a second or two of Python once a process (most of it
    the GPU half of the package, which nothing here uses), and the first
    step that holds a kernel cannot be traced without it: found in the
    FIRST decode program of a warm process, 1.65 s of the
    `opt6b7_batch_closed` cell's `setup_s` (PERF.md §6, PR 33). An engine
    whose gates let a kernel run calls this as it is built, so the import
    runs beside what comes before that trace and holds no lock the
    interpreter needs: the first prefill program's read from the compile
    cache. A trace that gets there first waits on the module's import
    lock, as any importer does."""
    threading.Thread(target=importlib.import_module,
                     args=("jax.experimental.pallas.tpu",),
                     name="pallas-preload", daemon=True).start()


class Spliced:
    """`kernel_call(*operands, interpret, **static)` (a `pallas_call` and
    what is round it) as a primitive named `name`: bound by calling the
    object, lowered for the TPU by merging in the module `lowered_once`
    keeps. `out_aval(*avals, **static)` is the result's abstract value."""

    def __init__(self, name, kernel_call, out_aval):
        self.name, self.kernel_call = name, kernel_call
        #: the module of `kernel_call` at `shapes` ((shape, dtype name) an
        #: operand), as text. `precision` (the process's default for a
        #: float32 dot, which the kernel's lowering reads) is part of what
        #: was lowered, so of the key
        self.lowered_once = functools.lru_cache(maxsize=None)(self._lower)
        self.primitive = jax.extend.core.Primitive(name)
        self.primitive.def_abstract_eval(out_aval)
        mlir.register_lowering(self.primitive, self._splice, platform="tpu")

    def __call__(self, *operands, **static):
        return self.primitive.bind(
            *operands, precision=jax.config.jax_default_matmul_precision,
            **static)

    def _lower(self, shapes, precision, **static):
        return jax.jit(functools.partial(
            self.kernel_call, interpret=False, **static)).trace(
                *(jax.ShapeDtypeStruct(s, jnp.dtype(d)) for s, d in shapes)
            ).lower(lowering_platforms=("tpu",)).as_text()

    def _splice(self, ctx, *operands, precision, **static):
        shapes = tuple((a.shape, a.dtype.name) for a in ctx.avals_in)
        kernel = mlir.ir.Module.parse(
            self.lowered_once(shapes, precision, **static))
        results = mlir.ir.SymbolTable(kernel.operation)["main"].type.results
        name = mlir.merge_mlir_modules(
            ctx.module_context.module, self.name, kernel,
            dst_symtab=ctx.module_context.symbol_table)
        return func.CallOp(results, mlir.ir.FlatSymbolRefAttr.get(name),
                           operands).results
