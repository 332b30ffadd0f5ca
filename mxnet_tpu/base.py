"""Base utilities: errors, dtype handling, string/registry helpers.

Capability parity with the reference's `python/mxnet/base.py` (error type,
registry glue) and dmlc-core's logging/param machinery, redesigned for a
pure-Python + JAX stack (no C ABI marshalling needed).
"""
from __future__ import annotations

import ctypes
import os
import numpy as np

import jax
import jax.numpy as jnp

#: the one jax this code is written and tested against (the installed
#: one); there are no shims for any other, so another version is refused
#: at import instead of failing somewhere inside a trace
SUPPORTED_JAX = "0.9.0"
if jax.__version__ != SUPPORTED_JAX:
    raise ImportError("mxnet_tpu supports jax %s, found jax %s"
                      % (SUPPORTED_JAX, jax.__version__))


class MXNetError(RuntimeError):
    """Error raised by the framework (parity: reference MXNetError)."""


def enable_compile_cache():
    """Point jax's persistent compilation cache at a directory that can be
    placed from outside, and return it. Entry points (chip_smoke.py,
    bench.py, tools/serve.py, tests/conftest.py) call this before their
    first compile. Where `JAX_COMPILATION_CACHE_DIR` is set jax has
    already read it and nothing is touched; otherwise the cache lives at
    `<checkout>/.jax_cache` — a fixed path derived from this file, because
    the path is part of what a later process must find again.
    `jax_persistent_cache_min_compile_time_secs` stays at jax's default,
    so only compiles worth keeping are written."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


# ---------------------------------------------------------------------------
# dtype registry (parity: mshadow type codes used across the reference C ABI)
# ---------------------------------------------------------------------------
_DTYPE_NP_TO_CODE = {
    np.dtype(np.float32): 0,
    np.dtype(np.float64): 1,
    np.dtype(np.float16): 2,
    np.dtype(np.uint8): 3,
    np.dtype(np.int32): 4,
    np.dtype(np.int8): 5,
    np.dtype(np.int64): 6,
    jnp.bfloat16.dtype: 7,
    np.dtype(np.bool_): 8,
}
_DTYPE_CODE_TO_NP = {v: k for k, v in _DTYPE_NP_TO_CODE.items()}


def dtype_np(dtype):
    """Normalize a user dtype spec (str/np.dtype/jnp dtype) to a numpy dtype."""
    if dtype is None:
        return np.dtype(np.float32)
    if dtype == "bfloat16" or dtype is jnp.bfloat16:
        return jnp.bfloat16.dtype
    return np.dtype(dtype)


def default_dtype():
    return np.dtype(np.float32)


def getenv_int(name, default):
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def getenv_bool(name, default=False):
    v = os.environ.get(name)
    if v is None:
        return default
    return v not in ("0", "false", "False", "")


def check_call(ret):  # parity shim: no C ABI, nothing to check
    return ret


class classproperty:
    def __init__(self, f):
        self.f = f

    def __get__(self, obj, owner):
        return self.f(owner)


# ---------------------------------------------------------------------------
# reference-API compatibility surface (parity: base.py) — exceptions, the
# ctypes helpers reference-era extension code calls, and doc utilities.
# There is no libmxnet C handle here, so the ctypes helpers are generic
# array/buffer conversions.
# ---------------------------------------------------------------------------


class NotImplementedForSymbol(MXNetError):
    """An NDArray-only API was called on a Symbol (parity: base.py)."""

    def __init__(self, function, alias=None, *args):
        super().__init__()
        self.function = getattr(function, "__name__", str(function))
        self.alias = alias
        self.args_rep = str(args)

    def __str__(self):
        msg = "Function %s is not implemented for Symbol" % self.function
        if self.alias:
            msg += " (use %s instead)" % self.alias
        return msg


class NotSupportedForSparseNDArray(MXNetError):
    """A dense-only API was called on a sparse ndarray (parity: base.py)."""

    def __init__(self, function, alias=None, *args):
        super().__init__()
        self.function = getattr(function, "__name__", str(function))
        self.alias = alias

    def __str__(self):
        msg = "Function %s is not supported for sparse ndarray" \
            % self.function
        if self.alias:
            msg += " (use %s instead)" % self.alias
        return msg


class MXCallbackList(ctypes.Structure):
    """C callback-list struct layout (parity: base.py MXCallbackList);
    kept for source compatibility with reference extension code."""
    _fields_ = [("num_callbacks", ctypes.c_int),
                ("callbacks", ctypes.POINTER(ctypes.CFUNCTYPE(
                    ctypes.c_int))),
                ("contexts", ctypes.POINTER(ctypes.c_void_p))]


def c_str(string):
    return ctypes.c_char_p(string.encode("utf-8"))


def c_str_array(strings):
    return (ctypes.c_char_p * len(strings))(
        *[s.encode("utf-8") for s in strings])


def c_array(ctype, values):
    """Create a ctypes array from a Python sequence (parity: base.py)."""
    out = (ctype * len(values))()
    out[:] = values
    return out


def c_array_buf(ctype, buf):
    """Create a ctypes array from a buffer (parity: base.py)."""
    return (ctype * len(buf)).from_buffer(buf)


def c_handle_array(objs):
    """Array of the objects' .handle fields (parity: base.py); handles
    here are opaque void pointers (may be None for pure-Python objects)."""
    arr = (ctypes.c_void_p * len(objs))()
    arr[:] = [getattr(o, "handle", None) for o in objs]
    return arr


def ctypes2buffer(cptr, length):
    """Copy a ctypes char pointer to a Python bytearray (parity)."""
    if not isinstance(cptr, ctypes.POINTER(ctypes.c_char)):
        raise TypeError("expected char pointer")
    res = bytearray(length)
    rptr = (ctypes.c_char * length).from_buffer(res)
    if not ctypes.memmove(rptr, cptr, length):
        raise RuntimeError("memmove failed")
    return res


def ctypes2numpy_shared(cptr, shape):
    """View a ctypes float pointer as a shared numpy array (parity)."""
    import numpy as _np
    if not isinstance(cptr, ctypes.POINTER(ctypes.c_float)):
        raise TypeError("expected float pointer")
    size = 1
    for s in shape:
        size *= s
    dbuffer = (ctypes.c_float * size).from_address(
        ctypes.addressof(cptr.contents))
    return _np.frombuffer(dbuffer, dtype=_np.float32).reshape(shape)


def build_param_doc(arg_names, arg_types, arg_descs, remove_dup=True):
    """Assemble a numpydoc Parameters section (parity: base.py)."""
    param_keys = set()
    lines = ["Parameters", "----------"]
    for name, ptype, desc in zip(arg_names, arg_types, arg_descs):
        if name in param_keys and remove_dup:
            continue
        if name == "num_args":
            continue
        param_keys.add(name)
        lines.append("%s : %s" % (name, ptype))
        if desc:
            lines.append("    " + desc)
    return "\n".join(lines)


def add_fileline_to_docstring(module, incursive=True):
    """Append 'From:file:line' to the docstrings of a module's functions
    (parity: base.py; best-effort — objects without source stay as-is)."""
    import inspect

    def _add(obj):
        try:
            fname = inspect.getsourcefile(obj)
            _, line = inspect.getsourcelines(obj)
        except (TypeError, OSError):
            return
        if obj.__doc__ and "From:" not in obj.__doc__:
            obj.__doc__ += "\n\nFrom:%s:%d" % (fname, line)

    if isinstance(module, str):
        import sys as _sys
        module = _sys.modules[module]
    for _, obj in module.__dict__.items():
        if inspect.isfunction(obj) and obj.__module__ == module.__name__:
            _add(obj)
        elif inspect.isclass(obj) and incursive:
            for _, m in obj.__dict__.items():
                if inspect.isfunction(m):
                    _add(m)


def with_metaclass(meta, *bases):
    """py2/3 metaclass shim the reference API exposed (parity: base.py)."""
    class _Meta(meta):
        def __new__(cls, name, this_bases, d):
            return meta(name, bases, d)
    return type.__new__(_Meta, "temporary_class", (), {})
