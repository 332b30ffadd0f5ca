"""`chip_smoke.py` and what it rests on (ISSUE 21).

The smoke itself only means something on the chip; what tier-1 can pin is
its contract off the chip: without a TPU it (and `bench.py`) refuse to run
and print nothing, the labelled rehearsal walks every leg, the compile
cache is placed by one rule, and `serve(replicas=N)` puts N one-chip
replicas on N devices.
"""
import json
import os
import subprocess
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, **env):
    """Run a root script on ONE CPU device (the conftest's 8-device flag
    dropped), as the driver's sandbox would."""
    base = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "BENCH_SMOKE")}
    base.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, os.path.join(REPO, script)]
                          + list(args), capture_output=True, text=True,
                          timeout=600, env=base, cwd=REPO)


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_no_chip_no_result(script):
    """On the CPU, without the explicit small mode, nothing runs: a
    non-zero exit before any model is built and not one line of output
    that could be read as a result."""
    out = _run(script)
    assert out.returncode != 0
    assert out.stdout.strip() == "", out.stdout
    assert "no TPU" in out.stderr
    assert "Traceback" not in out.stderr


def test_bench_spawns_no_python_child():
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    assert "subprocess" not in src and "BENCH_INNER" not in src


def test_unknown_device_kind_has_no_peak():
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    assert bench._peak_flops("TPU v5 lite", "bfloat16") == 197e12
    assert bench._peak_flops("cpu", "float32") is None
    with pytest.raises(ValueError, match="no peak"):
        bench._peak_flops("TPU v99", "bfloat16")


def test_rehearsal_walks_every_leg():
    out = _run("chip_smoke.py", "--rehearse")
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    lines = [json.loads(l) for l in out.stdout.splitlines()]
    assert all(l.get("rehearsal") is True for l in lines), lines
    assert lines[-1] == {"ok": True, "rehearsal": True,
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "count": 1}}
    legs = {l["leg"]: l for l in lines[:-1]}
    for leg in ("device", "trainer", "server_f32_gather",
                "server_f32_paged", "server_f32_parity",
                "server_bf16_paged", "server_int8_kv",
                "kernel_flash_attention", "kernel_fused_bn_act",
                "kernel_fused_scan_layer", "four_chips"):
        assert leg in legs, (leg, sorted(legs))
        assert legs[leg]["platform"] == "cpu"
    assert legs["trainer"]["compile_total"] >= 1
    assert legs["server_int8_kv"]["pool_dtype"] == "int8"
    assert legs["server_f32_parity"]["max_margin_paged"] <= 1e-3
    # one device: the four-chip leg says it did not run, and why
    assert legs["four_chips"]["ran"] is False
    assert "device_count" in legs["four_chips"]["reason"]


def test_compile_cache_is_placed_by_one_rule(monkeypatch):
    from mxnet_tpu.base import enable_compile_cache
    old = jax.config.jax_compilation_cache_dir
    try:
        # unset: <checkout>/.jax_cache, derived from the package's path
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jax.config.update("jax_compilation_cache_dir", None)
        assert enable_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache")
        # set: jax read it at import and nothing here touches it
        jax.config.update("jax_compilation_cache_dir", "/x")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        assert enable_compile_cache() == "/x"
        assert jax.config.jax_compilation_cache_dir == "/x"
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
    # ... in a fresh process too, tests' own set-up included
    code = ("import tests.conftest, jax; "
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120,
                         env=dict(os.environ,
                                  JAX_COMPILATION_CACHE_DIR="/x"))
    assert out.stdout.split()[-1] == "/x", out.stdout + out.stderr[-2000:]


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs >= 4 (emulated) devices")
def test_four_replicas_hold_four_devices():
    """`serve(replicas=4)` at tp=1 used to put every engine on device 0:
    each replica now commits its parameters and its pool to its own
    window, and the steps run there."""
    from mxnet_tpu import serving
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              init_transformer_params)
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=1,
                            d_ff=64, max_len=32)
    params = init_transformer_params(jax.random.PRNGKey(0), cfg)
    srv = serving.serve((params, cfg), replicas=4, max_batch=2,
                        block_size=8, paged=True)
    try:
        reqs = [srv.submit([1 + i, 2, 3], max_new_tokens=4)
                for i in range(8)]
        outs = [r.result(timeout=300) for r in reqs]
        assert all(len(o) == 4 for o in outs)
        pools = [d for r in srv.replicas
                 for d in r.engine.cache.k.devices()]
        weights = [d for r in srv.replicas
                   for d in r.engine.model.params["embed"].devices()]
        assert pools == weights == jax.devices()[:4]
        assert [r.engine.device for r in srv.replicas] == jax.devices()[:4]
    finally:
        srv.close()
