"""End-to-end example runs (reference ``test/integration`` tier: real
subprocess jobs).  Each example executes with tiny settings on the
8-device virtual CPU mesh."""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.integration

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run_example(script, *args, timeout=420):
    env = dict(os.environ)
    env.update({
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "cpu",
        # PYTHONPATH is the repo ONLY, so nothing the caller had on its
        # path can register another PJRT backend in the example process.
        "PYTHONPATH": REPO,
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert proc.returncode == 0, (
        f"{script} failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
    )
    return proc.stdout


def test_mnist_example():
    out = _run_example("mnist.py", "--epochs", "1", "--batch-size", "8",
                       "--num-samples", "256")
    assert "loss" in out.lower()


def test_torch_mnist_example():
    pytest.importorskip("torch")
    out = _run_example("torch_mnist.py", "--epochs", "1",
                       "--batch-size", "16", "--num-samples", "256")
    assert "loss" in out.lower()


def test_lightning_mnist_example(tmp_path):
    pytest.importorskip("torch")
    out = _run_example("lightning_mnist.py", "--epochs", "1",
                       "--batch-size", "32", "--num-samples", "256",
                       "--store", str(tmp_path / "ls"))
    assert "val_loss" in out


def test_estimator_mnist_example(tmp_path):
    pytest.importorskip("torch")
    out = _run_example("estimator_mnist.py", "--epochs", "1",
                       "--store", str(tmp_path / "es"), timeout=600)
    assert "keras-style history" in out
    assert "resumed for 1 new epoch(s)" in out


def test_tf2_keras_mnist_example():
    pytest.importorskip("tensorflow")
    out = _run_example("tf2_keras_mnist.py", "--epochs", "1",
                       "--batch-size", "16", "--num-samples", "256",
                       timeout=600)
    assert "loss" in out.lower()


def test_tf2_keras_mnist_fit_mode_example():
    """model.fit + DistributedOptimizer(backward_passes_per_step=2) +
    BroadcastGlobalVariablesCallback — the reference keras recipe,
    exercising the compiled-fit (tf.cond) aggregation path."""
    pytest.importorskip("tensorflow")
    out = _run_example("tf2_keras_mnist.py", "--use-fit", "--epochs", "1",
                       "--batch-size", "16", "--num-samples", "256",
                       "--backward-passes-per-step", "2", timeout=600)
    assert "final loss" in out


def test_process_sets_example():
    out = _run_example("process_sets.py")
    assert "even-team avg: 3.0" in out
    assert "odd-team avg: 4.0" in out


def test_synthetic_benchmark_example():
    out = _run_example(
        "synthetic_benchmark.py", "--model", "resnet50",
        "--image-size", "32", "--batch-size", "2",
        "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
        "--num-iters", "1",
    )
    assert "Img/sec per chip" in out


def test_imagenet_resnet50_example():
    """North-star example end to end (tiny shapes: full ResNet-50 depth
    at 32px, one epoch) — compile dominates, hence the long timeout."""
    out = _run_example(
        "imagenet_resnet50.py", "--epochs", "1", "--batch-size", "2",
        "--image-size", "32", "--num-samples", "32",
        "--warmup-epochs", "1", timeout=560,
    )
    assert "loss" in out.lower()


def test_embedding_sparse_example():
    out = _run_example("embedding_sparse.py", "--steps", "120",
                       "--batch-size", "16", "--lr", "2.0",
                       "--num-samples", "32768")
    lines = [l for l in out.splitlines() if l.startswith("step")]
    assert lines, out
    first = float(lines[0].split()[3])
    last = float(lines[-1].split()[3])
    assert last < first, (first, last)
    assert "sparse reduction" in lines[-1]


def test_embedding_sparse_as_dense_example():
    out = _run_example("embedding_sparse.py", "--steps", "10",
                       "--batch-size", "8", "--num-samples", "2048",
                       "--sparse-as-dense")
    assert "dense reduction" in out


def test_fsdp_gpt_example():
    out = _run_example("fsdp_gpt.py", "--steps", "20")
    lines = [l for l in out.splitlines() if l.startswith("step")]
    assert lines
    first = float(lines[0].split()[-1])
    last = float(lines[-1].split()[-1])
    assert last < first, (first, last)
    assert "gathered eval logits" in out


def test_gpt_pretrain_example():
    out = _run_example(
        "gpt_pretrain.py", "--dp", "2", "--sp", "2", "--tp", "2",
        "--steps", "3", "--seq-per-sp", "32",
    )
    assert "mesh dp2/sp2/tp2" in out


@pytest.mark.multiproc
def test_spark_elastic_example():
    out = _run_example(
        "spark_elastic.py", "--local", "--simulate-loss", "--epochs", "5",
    )
    import re

    # round >= 2 (recovery happened); the exact count is timing-dependent
    assert re.search(r"job finished on round [2-9] with 2 worker\(s\)", out)
    assert "rank 1:" in out


def test_gpt_pretrain_packed_example():
    out = _run_example(
        "gpt_pretrain.py", "--dp", "4", "--tp", "2", "--attn", "flash",
        "--packed", "--steps", "3", "--seq-per-sp", "64",
    )
    assert "efficiency" in out
    assert "mesh dp4/sp1/tp2" in out
