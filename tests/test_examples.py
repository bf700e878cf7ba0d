"""Examples smoke tests (the reference CI runs its examples as gates,
benchmark_master.sh:110-153; these run the fast ones on the CPU mesh)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(script, *args, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # XLA:CPU aborts the process when the 8 device threads of one
    # collective do not all arrive within 40 s — which a loaded host (the
    # tier-1 run's six workers, eight virtual devices each) does not
    # promise: the subprocess gets the test's own limit instead
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
        f" --xla_cpu_collective_call_terminate_timeout_seconds={timeout}"
    )
    env["BAGUA_SERVICE_PORT"] = "-1"
    # scripts run by path get examples/ as sys.path[0]
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env,
    )
    sys.stderr.write(out.stdout[-1500:] + out.stderr[-1500:])
    assert out.returncode == 0
    return out.stdout


def test_communication_primitives_example():
    out = _run_example("communication_primitives.py")
    assert "communication primitives OK (world=8)" in out


@pytest.mark.slow
def test_moe_mnist_example():
    out = _run_example("moe_mnist.py", "--steps", "15", "--batch", "32")
    assert "final_loss" in out


@pytest.mark.slow
def test_squad_finetune_example_tiny():
    out = _run_example(
        "squad_finetune.py", "--tiny", "--steps", "4", "--batch", "1",
        "--seq", "64", "--algorithm", "qadam", "--lr", "1e-3",
    )
    assert "final_loss" in out


@pytest.mark.slow
def test_imagenet_resnet_example_tiny():
    out = _run_example(
        "imagenet_resnet.py", "--steps", "2", "--tiny",
        "--batch-per-device", "1",
    )
    assert "final_loss" in out and "cache_entries" in out


@pytest.mark.slow
def test_parallelism_zoo_example():
    out = _run_example("parallelism_zoo.py", timeout=900)
    assert "all parallelism axes ran" in out


def test_generate_lm_example():
    out = _run_example("generate_lm.py")
    assert "generate_lm OK" in out


@pytest.mark.slow
def test_mnist_mlp_real_data_example():
    """The flagship example trains on the REAL vendored digit scans by
    default and must report a passing held-out accuracy (the reference CI
    gates its mnist example on real data, benchmark_master.sh:83-108)."""
    out = _run_example("mnist_mlp.py", "--steps", "150")
    assert "test_accuracy" in out
    acc = float(out.split("test_accuracy")[1].strip().split()[0])
    assert acc >= 0.95, out
