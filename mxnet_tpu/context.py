"""Device contexts.

Parity: reference `python/mxnet/context.py` (Context class, cpu()/gpu(),
default-context scope). TPU-native redesign: a Context maps to a concrete
`jax.Device`; `gpu()` is accepted for script compatibility and aliases the
accelerator (TPU) when one is present. Placement of NDArrays is
`jax.device_put`; multi-device placement is handled by `mxnet_tpu.parallel`
(Mesh/NamedSharding), which the reference did per-executor-copy instead.
"""
from __future__ import annotations

import threading

import jax


class Context:
    """A device context (cpu/tpu; 'gpu' aliases the accelerator).

    Parity: reference `python/mxnet/context.py:23-141`.
    """

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "cpu_shared", 5: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 4, "tpu": 5}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    @property
    def is_accelerator(self):
        return self.device_type in ("gpu", "tpu")

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __repr__(self):
        return self.__str__()

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    def empty_cache(self):  # parity: Context.empty_cache; XLA manages pools
        pass


Context._default_ctx.value = Context("cpu", 0)


def cpu(device_id=0):
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Accepted for reference-script compatibility; aliases the accelerator."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def num_gpus():
    """Number of accelerator chips visible (parity: mx.context.num_gpus)."""
    return len([d for d in jax.devices() if d.platform != "cpu"])


def num_tpus():
    return num_gpus()


def current_context():
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value
