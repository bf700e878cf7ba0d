"""The first two CPU rehearsals of the on-chip guide: the one command end to
end for every cell at tiny widths (``--rehearse``), on as many virtual CPU
devices as the cell has chips — and the rule that a CPU run prints no time,
rate or share, and no result at all without ``--rehearse``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import cells

ROOT = cells.BENCH_DIR.parent
BENCH = cells.load_benchmark()
COUNTS = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
          if m["source"] == "program_counter"}


def run_cell(*args, cwd=ROOT, run_py=ROOT / "perfbench" / "run.py", env=None):
    return subprocess.run(
        [sys.executable, str(run_py), *args], capture_output=True, text=True,
        cwd=cwd, timeout=600,
        env={k: v for k, v in (env or os.environ).items() if k != "XLA_FLAGS"})


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_rehearsal_end_to_end(workload, trace):
    chips = next(w["chips"] for w in BENCH["workloads"] if w["name"] == workload)
    done = run_cell("--workload", workload, "--seed", "3", "--seconds", "4",
                    "--trace", str(trace), "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 10
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": chips, "memory_peak_bytes": 0}
    # a CPU run names counts only, and only with --trace 1 are there any
    assert set(result["metrics"]) == (COUNTS if trace else set())
    if trace:
        assert result["metrics"]["compiles_in_window"]["value"] == 0
        moved = result["metrics"]["comm_bytes_per_step"]["value"]
        assert (moved > 0) == (chips > 1)
    # the earlier line: set-up parts, both loss triples, the reference's time
    parts = json.loads(lines[-2])
    assert {"import_s", "weights_s", "compile_or_load_s", "warmup_s",
            "reference_check_s"} <= set(parts["parts"])
    assert len(parts["trainer_losses"]) == len(parts["reference_losses"]) == 3
    assert parts["trainer_losses"][0] > parts["trainer_losses"][2]


def test_same_seed_same_inputs_other_seed_other_inputs():
    def losses(seed):
        done = run_cell("--workload", "bert-large.squad384-dp1", "--seed", seed,
                        "--seconds", "1", "--rehearse")
        assert done.returncode == 0, done.stderr[-2000:]
        return json.loads(done.stdout.strip().splitlines()[-2])["trainer_losses"]

    assert losses("5") == losses("5") != losses("6")


def test_without_a_tpu_there_is_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = run_cell("--workload", "bert-large.squad384-dp1", "--seed", "1",
                    "--seconds", "1", "--trace", "0", env=env)
    assert done.returncode == 3
    assert done.stdout.strip() == ""
    assert "not a TPU" in done.stderr


def test_an_unknown_cell_is_exit_code_2():
    done = run_cell("--workload", "no-such.cell")
    assert done.returncode == 2 and done.stdout.strip() == ""
    assert "unknown workload" in done.stderr


def test_the_benchmark_alone_is_not_a_checkout(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` the program under test is missing: no result, exit code != 0."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(cells.BENCH_DIR, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = run_cell("--workload", "bert-large.squad384-dp1", "--rehearse",
                    cwd=tmp_path, run_py=tmp_path / "perfbench" / "run.py",
                    env=env)
    assert done.returncode == 3
    assert done.stdout.strip() == ""
    assert "not importable" in done.stderr
