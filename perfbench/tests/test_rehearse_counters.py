"""The two counts that read the program from inside (PR 24), through the one
command on the CPU rehearsal: ``buckets_per_step`` is the program's gauge,
``comm_calls_compiled`` a count over the compiled text; neither is a time,
so both print under ``--rehearse``."""

import json

import pytest

from perfbench.tests.test_rehearse import run_cell


@pytest.mark.parametrize("workload, exchanges", [
    ("bert-large.squad384-dp4", True),
    ("bert-large.squad384-dp1", False),
])
def test_rehearsal_prints_the_bucket_counts(workload, exchanges):
    done = run_cell("--workload", workload, "--seed", "3", "--seconds", "2",
                    "--trace", "1", "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    metrics = json.loads(done.stdout.strip().splitlines()[-1])["metrics"]
    assert metrics["buckets_per_step"]["unit"] == "count"
    buckets = metrics["buckets_per_step"]["value"]
    calls = metrics["comm_calls_compiled"]["value"]
    if exchanges:
        # XLA may combine the buckets' collectives, never multiply them
        # (the loss's all-reduce is the one more)
        assert buckets >= 1 and 1 <= calls <= buckets + 1
    else:
        # one chip: the communication layer hands every bucket back
        assert buckets == 0
