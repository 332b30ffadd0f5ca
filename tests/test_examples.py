"""Smoke-run every example end-to-end as a subprocess (the reference's
tests/python/train pattern: small configs, convergence asserted by the
examples themselves where applicable).

Each example is hermetic (synthetic data) and must exit 0 with a tiny
config on the CPU backend.
"""
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EX = os.path.join(_REPO, "examples")

_CASES = [
    ("train_mnist.py", ["--network", "mlp", "--num-epochs", "1",
                        "--batch-size", "96"]),
    ("image_classification_gluon.py", ["--model", "resnet18_v1",
                                       "--batch-size", "8",
                                       "--image-size", "32",
                                       "--num-batches", "4"]),
    ("word_lm.py", ["--epochs", "1", "--vocab", "50", "--emsize", "16",
                    "--nhid", "32", "--nlayers", "1", "--bptt", "8",
                    "--batch-size", "4"]),
    ("lstm_bucketing.py", ["--epochs", "1", "--batch-size", "8"]),
    ("sparse_linear_classification.py", ["--num-features", "100",
                                         "--batch-size", "16",
                                         "--num-batches", "8"]),
    ("train_ssd.py", ["--epochs", "1", "--batch-size", "4"]),
    ("benchmark_score.py", ["--models", "resnet18_v1", "--image-size", "32",
                            "--batch-sizes", "2"]),
    ("model_parallel_lstm.py", ["--steps", "50", "--batch-size", "8"]),
    ("train_transformer_lm.py", ["--steps", "40", "--d-model", "32",
                                 "--seq-len", "16"]),
    ("serve_lm.py", ["--steps", "200", "--max-new", "6", "--clients", "3"]),
    ("dcgan.py", ["--iters", "4", "--batch-size", "16"]),
    ("adversary_fgsm.py", ["--epochs", "1"]),
    ("matrix_factorization.py", ["--steps", "60"]),
    ("cnn_text_classification.py", ["--epochs", "5"]),
    ("vae.py", ["--epochs", "1"]),
    ("dqn_gridworld.py", []),
    ("quantize_int8.py", ["--num-epochs", "1", "--num-calib-batches", "2"]),
    ("custom_op.py", ["--num-epochs", "2"]),
    ("multi_task.py", ["--num-epochs", "1"]),
    ("bi_lstm_sort.py", ["--steps", "150", "--seq-len", "6"]),
    ("nce_word_embeddings.py", ["--steps", "250"]),
    ("neural_style.py", ["--steps", "80"]),
    ("conv_autoencoder.py", []),
    ("capsnet.py", ["--num-batches", "60"]),
    ("stochastic_depth.py", []),
    ("dsd_training.py", []),
]


@pytest.mark.parametrize("script,args", _CASES,
                         ids=[c[0] for c in _CASES])
def test_example_runs(script, args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_EX, script)] + args,
        capture_output=True, text=True, timeout=900, env=env, cwd=_REPO)
    assert proc.returncode == 0, (
        "%s failed:\nstdout: %s\nstderr: %s"
        % (script, proc.stdout[-2000:], proc.stderr[-2000:]))


# CLI tools that are themselves end-to-end drills (CPU backend). The
# training chaos drill trains LeNet through SIGTERM preemption, a
# mid-save kill, and an injected-NaN rollback, asserting the final
# state is bit-identical to an undisturbed run. The serving chaos
# drill (ISSUE 11) drives a 3-replica fleet through a fault storm —
# wedge, thread kill, decode poison, pool exhaustion, crash loop —
# asserting >=99% availability, greedy-token-identical failover, zero
# leaked blocks, and every fault on the postmortem timeline.
_TOOL_CASES = [
    ("chaos_train.py", []),
    ("chaos_serve.py", []),
]


@pytest.mark.parametrize("script,args", _TOOL_CASES,
                         ids=[c[0] for c in _TOOL_CASES])
def test_tool_runs(script, args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", script)] + args,
        capture_output=True, text=True, timeout=900, env=env, cwd=_REPO)
    assert proc.returncode == 0, (
        "%s failed:\nstdout: %s\nstderr: %s"
        % (script, proc.stdout[-2000:], proc.stderr[-2000:]))
