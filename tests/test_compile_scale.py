"""Mesh-scale compile gates (VERDICT r3 #5/#10).

shift_one's rotating pairing precompiles one ppermute per period step into a
``lax.switch`` (communication.py exchange_with_peer): wire cost stays one
ppermute, but program metadata grows with the mesh.  These tests pin that
the growth is benign at the v5p-32/64 shapes (compile time flat, bounded)
and that the far-out hazard is an explicit, actionable error instead of a
multi-minute compile.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from bagua_tpu.communication import BaguaCommunicator

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _audit(devices, families):
    cmd = [
        sys.executable, os.path.join(REPO, "benchmarks", "compile_audit.py"),
        "--devices", str(devices), "--families", *families,
    ]
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the audit sets its own device count
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=560,
                         cwd=REPO, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    return [
        json.loads(line) for line in out.stdout.splitlines()
        if line.strip().startswith("{")
    ]


@pytest.mark.slow
def test_shift_one_step_compile_flat_at_scale():
    """The full shift_one train step compiles on 32- AND 64-way meshes in
    bounded, flat time (measured ~0.35/0.48 s; bound leaves CI headroom)."""
    recs = {
        r["n_devices"]: r
        for d in (32, 64)
        for r in _audit(d, ["decentralized_shift_one"])
    }
    assert recs[32]["compile_s"] < 30 and recs[64]["compile_s"] < 30, recs
    # flat: doubling the mesh may not blow up compile time superlinearly
    assert recs[64]["compile_s"] < 10 * max(recs[32]["compile_s"], 0.1), recs


def test_exchange_period_cap_is_explicit_error(monkeypatch):
    """Past the precompile cap the failure mode is a clear ValueError with
    the env-var escape hatch, not an unbounded compile."""
    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("dp",))
    comm = BaguaCommunicator("dp", mesh)
    monkeypatch.setattr(BaguaCommunicator, "MAX_EXCHANGE_PERIOD", 2)

    def rotate_peer(rank, nranks, step):  # period == nranks//2 == 4 > 2
        half = nranks // 2
        if rank < half:
            return (step + rank) % half + half
        return (rank - half - step) % half

    def f(x, step):
        return comm.exchange_with_peer(x, rotate_peer, step)

    from jax import shard_map

    fn = jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P("dp"), P()), out_specs=P("dp"),
        check_vma=False,
    ))
    with pytest.raises(ValueError, match="BAGUA_MAX_EXCHANGE_PERIOD"):
        fn(jnp.zeros((8, 16), jnp.float32), jnp.zeros((), jnp.int32))


def test_artifact_exists_and_has_all_families():
    """BENCH_COMPILE.json (driver-visible artifact) covers every family at
    both mesh sizes."""
    path = os.path.join(REPO, "BENCH_COMPILE.json")
    assert os.path.exists(path), "run benchmarks/compile_audit.py --out BENCH_COMPILE.json"
    records = json.load(open(path))
    fams = {(r["family"], r["n_devices"]) for r in records}
    for fam in ("gradient_allreduce", "bytegrad", "qadam", "decentralized",
                "decentralized_shift_one", "low_precision_decentralized",
                "zero", "async", "flagship_transformer_dp_tp"):
        assert (fam, 32) in fams and (fam, 64) in fams, fam
    assert all(r["compile_s"] < 60 for r in records), records
