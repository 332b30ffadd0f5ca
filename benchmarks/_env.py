"""Pre-jax environment setup for virtual-CPU-mesh entry points.

Importable WITHOUT pulling in jax or mxnet_tpu, so callers can fix the
platform before any backend initializes. Shared by
benchmarks/scaling_report.py and __graft_entry__.dryrun_multichip
(tests/conftest.py keeps its own lighter variant: it must NOT override
an explicitly-set device count).
"""
import os
import re


def force_virtual_cpu_devices(n):
    """Point jax at n virtual CPU devices, overriding any prior count.

    Must run before jax initializes a backend. An explicit CPU mode: the
    callers are instruments over virtual devices, never a fallback.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=%d" % n).strip()
