#!/usr/bin/env python
"""Environment diagnostics for bug reports (parity: reference
tools/diagnose.py — platform/version/connectivity dump, re-targeted at
the TPU stack): OS, Python, numpy/jax/framework versions, the visible
accelerator devices, native-extension status, and the relevant env vars.

One process: a chip belongs to the process that first touches it, so the
devices are listed here, last, not in a child. Run it while nothing else
on the host holds the chip.
"""
import argparse
import os
import platform
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

_ENV_PREFIXES = ("MXNET_", "JAX_", "XLA_", "DMLC_", "TPU_", "PALLAS_")


def section(title):
    print("\n----- %s -----" % title)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--no-device-probe", action="store_true",
                    help="do not initialise a backend (leaves the chip "
                         "to whoever holds it)")
    args = ap.parse_args()

    section("Platform")
    print("system      :", platform.platform())
    print("machine     :", platform.machine())
    print("python      :", sys.version.replace("\n", " "))

    section("Versions")
    import numpy
    print("numpy       :", numpy.__version__)
    try:
        import jax
        import jaxlib
        print("jax         :", jax.__version__)
        print("jaxlib      :", jaxlib.__version__)
    except ImportError as e:
        print("jax         : MISSING (%s)" % e)
    import mxnet_tpu
    print("mxnet_tpu   :", getattr(mxnet_tpu, "__version__", "dev"))

    section("Native extension")
    from mxnet_tpu import native
    print("available   :", native.AVAILABLE)
    if not native.AVAILABLE:
        print("(build with: make -C native)")

    section("Environment")
    for k in sorted(os.environ):
        if k.startswith(_ENV_PREFIXES):
            print("%s=%s" % (k, os.environ[k]))

    if not args.no_device_probe:
        section("Accelerator")
        import jax
        print("backend:", jax.default_backend())
        print("devices:", jax.devices())
    return 0


if __name__ == "__main__":
    sys.exit(main())
