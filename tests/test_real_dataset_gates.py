"""Opt-in real-dataset gates (VERDICT r3 #7; reference bar:
/root/reference/.buildkite/scripts/benchmark_master.sh:83-153, which trains
real workloads with hard loss gates in CI).

Zero-egress environments cannot download ImageNet/SQuAD, so these gates are
conditional: point ``BAGUA_REAL_DATA_DIR`` at a directory holding

- ``squad_train.npz`` — tokenized SQuAD rows (``input_ids``,
  ``start_positions``, ``end_positions``), and/or
- ``imagenet/{class}/{img}.npy`` — decoded image arrays per class dir

and the gates run the real examples end to end with convergence/accuracy
thresholds; without data they skip cleanly (CI stays green).  The gate
MACHINERY itself is always exercised: the ``_selfcheck`` tests synthesize a
tiny learnable dataset in the same file formats and run the exact same
example code paths.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DATA_DIR = os.environ.get("BAGUA_REAL_DATA_DIR", "")


def _run_example(script, *argv, timeout=1800):
    env = dict(os.environ)
    env.pop("BAGUA_SERVICE_PORT", None)
    # scripts run by path get examples/ as sys.path[0]; keep the repo
    # importable
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, os.path.join(REPO, "examples", script), *argv]
    out = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                         text=True, timeout=timeout)
    sys.stderr.write(out.stdout[-1500:] + out.stderr[-1500:])
    return out


# ---- real-data gates (opt-in) ---------------------------------------------

squad_npz = os.path.join(DATA_DIR, "squad_train.npz") if DATA_DIR else ""
imagenet_dir = os.path.join(DATA_DIR, "imagenet") if DATA_DIR else ""


@pytest.mark.slow
@pytest.mark.skipif(
    not os.path.exists(squad_npz),
    reason="BAGUA_REAL_DATA_DIR/squad_train.npz not present",
)
@pytest.mark.parametrize("algorithm", ["bytegrad", "qadam"])
def test_squad_real_gate(algorithm):
    """Real tokenized SQuAD: the compressed families must show a learning
    signal over the real rows (the example's built-in assert) and finish."""
    out = _run_example(
        "squad_finetune.py", "--algorithm", algorithm,
        "--dataset", squad_npz, "--steps", "50",
    )
    assert out.returncode == 0
    assert "final_loss" in out.stdout


@pytest.mark.slow
@pytest.mark.skipif(
    not os.path.isdir(imagenet_dir),
    reason="BAGUA_REAL_DATA_DIR/imagenet/ not present",
)
def test_imagenet_real_gate():
    """Real image subset: ResNet must reach the gated held-out accuracy
    (threshold via BAGUA_IMAGENET_GATE_ACC, default 0.5 for small subsets)."""
    gate = os.environ.get("BAGUA_IMAGENET_GATE_ACC", "0.5")
    out = _run_example(
        "imagenet_resnet.py", "--data-dir", imagenet_dir,
        "--epochs", os.environ.get("BAGUA_IMAGENET_GATE_EPOCHS", "3"),
        "--gate-accuracy", gate,
    )
    assert out.returncode == 0
    assert "eval_accuracy" in out.stdout


# ---- always-on self-checks of the gate machinery ---------------------------

@pytest.mark.slow
def test_imagenet_gate_selfcheck(tmp_path):
    """The --data-dir/--gate-accuracy path runs end to end on a synthesized
    learnable dataset in the exact real-data layout ({class}/*.npy)."""
    rng = np.random.default_rng(0)
    # two linearly separable classes of 24x24 images
    for label, mean in (("class_a", -1.0), ("class_b", 1.0)):
        d = tmp_path / "imagenet" / label
        d.mkdir(parents=True)
        for i in range(48):
            img = rng.normal(mean, 0.3, size=(24, 24, 3)).astype(np.float32)
            np.save(d / f"{i}.npy", img)
    out = _run_example(
        "imagenet_resnet.py", "--data-dir", str(tmp_path / "imagenet"),
        "--tiny", "--epochs", "6", "--batch-per-device", "1",
        "--gate-accuracy", "0.8", "--lr", "0.1",
        timeout=900,
    )
    assert out.returncode == 0, out.stdout[-800:] + out.stderr[-800:]
    assert "eval_accuracy" in out.stdout


@pytest.mark.slow
def test_squad_gate_selfcheck(tmp_path):
    """The --dataset path runs end to end on a synthesized .npz in the real
    tokenized-SQuAD format, cycling through multiple batches."""
    rng = np.random.default_rng(0)
    n, seq = 64, 64
    ids = rng.integers(0, 1000, (n, seq)).astype(np.int32)
    starts = rng.integers(0, seq, n).astype(np.int32)
    ends = np.minimum(starts + rng.integers(1, 8, n), seq - 1).astype(np.int32)
    npz = tmp_path / "squad_train.npz"
    np.savez(npz, input_ids=ids, start_positions=starts, end_positions=ends)
    out = _run_example(
        "squad_finetune.py", "--tiny", "--dataset", str(npz),
        "--steps", "12", "--seq", str(seq), "--batch", "1", "--lr", "3e-4",
        timeout=900,
    )
    assert out.returncode == 0, out.stdout[-800:] + out.stderr[-800:]
    assert "final_loss" in out.stdout
