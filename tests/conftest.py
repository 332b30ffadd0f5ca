"""Test configuration: run on a virtual 8-device CPU mesh so sharding tests
exercise multi-chip code paths without TPU hardware (set before jax import)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
# the suite's time is XLA:CPU compile time (thousands of small programs,
# each run a few times); unoptimised CPU code takes ~25% off tier-1, and
# the CPU backend is the test vehicle here, not what users run
if "xla_backend_optimization_level" not in flags:
    flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = flags.strip()

import numpy as np
import pytest

import jax

# tests compare against float64 numpy references; keep MXU-style low-precision
# matmuls out of the correctness suite (bench keeps the fast default)
jax.config.update("jax_default_matmul_precision", "highest")

# persistent XLA compile cache, where the package puts it for every entry
# point (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache): shared
# across runs, so the fast tier pays each conv-net compile once per
# machine. Tests also keep the sub-second compiles.
from mxnet_tpu.base import enable_compile_cache

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


# Two-tier suite (reference pattern: tests/python/unittest vs tests/nightly):
# `pytest -m "not slow"` is the fast tier (<120 s, every subsystem);
# the slow tier holds multiprocess/subprocess and example-smoke tests.
_SLOW_FILES = {
    "test_examples.py",       # subprocess example smokes
    "test_kvstore_dist.py",   # multiprocess dist kvstore
    "test_env_vars.py",       # subprocess per-env-var reimports
    "test_recovery.py",       # kill/resume subprocess drills
    "test_converge.py",       # trains to accuracy/perplexity/AUC bars
    "test_cpp_package.py",    # g++ build + subprocess CLI runs
}

# Individual compile-heavy tests (>~30 s on the 8-worker CPU tier). Every
# subsystem they cover retains at least one light test in the fast tier.
_SLOW_TESTS = {
    "test_tool_diagnose_runs", "test_tool_bandwidth_runs",
    "test_psroi_pooling", "test_deformable_psroi_grad",
    "test_deformable_convolution_grad",
    "test_ssd_end_to_end",
    "test_multichip_dryrun_entry",
    "test_model_zoo_all_families_forward", "test_model_zoo_constructs",
    "test_transformer_moe_ep_trains", "test_transformer_dp_tp_sp_trains",
    "test_transformer_sharded_matches_single_device",
    "test_gpipe_grads_match",
    "test_symbolic_cell_stack_trains_via_module",
    "test_bucketing_lstm_lm_converges", "test_bucketing_module_mesh",
    "test_tensorboard_callback",
    "test_multisample_nb_draws",
    "test_transformer_uses_flash", "test_flash_gradients_match_reference",
    "test_quantized_model_binds_via_module",
    "test_module_mesh_fit_converges",
    "test_trainstep_sharded_optimizer_states_match_replicated",
    "test_random_moments",
    "test_notebook_callbacks_log_training",
    "test_export_model_zoo_resnet",
    "test_module_mesh_matches_single_device",
    "test_resnetish_dp_tp_matches_single_device",
    "test_custom_op_trains_inside_module",
    "test_model_zoo_get_model",
    "test_live_rollout_end_to_end_zero_loss",
}

# fused-optimizer equality: sgd stays in the fast tier as the smoke for the
# TrainStep fusion path; the other 16 rules are slow-tier (~35 s each)
_SLOW_PARAMS = {
    "test_fused_matches_eager": lambda param: param != "sgd",
    "test_flash_matches_reference": lambda param: param.endswith("True"),
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multiprocess/subprocess/example/compile-heavy "
        "tests (excluded from the fast tier; run with -m slow)")


def pytest_collection_modifyitems(items):
    for item in items:
        base, _, param = item.name.partition("[")
        if item.path.name in _SLOW_FILES or base in _SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
        elif base in _SLOW_PARAMS and _SLOW_PARAMS[base](param.rstrip("]")):
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _seed_all():
    """Determinism per test (parity: reference @with_seed(),
    tests/python/unittest/common.py:97)."""
    import mxnet_tpu as mx
    np.random.seed(0)
    mx.random.seed(0)
    yield


@pytest.fixture(autouse=True)
def _serving_pool_audit():
    """Shared block-pool leak audit (ISSUE 11): every serving Engine a
    test creates must end the test quiescent — allocated blocks are
    exactly the prefix-cache residents, each pinned only by the cache.
    `Engine.close()` runs the same audit on clean server shutdown and
    removes the engine from the live set; engines torn down on a crash
    path are excluded the same way. Anything still live here leaked."""
    import sys
    eng_mod = sys.modules.get("mxnet_tpu.serving.engine")
    # STRONG refs: holding the pre-test engines alive for the test's
    # duration means a new engine can never reuse a dead one's id and
    # slip past the audit by identity-collision
    before = list(eng_mod._LIVE) if eng_mod is not None else []
    yield
    eng_mod = sys.modules.get("mxnet_tpu.serving.engine")
    if eng_mod is None:
        return
    for eng in list(eng_mod._LIVE):
        if any(eng is b for b in before):
            continue
        eng.audit_quiescent()
