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


def _compile_shift_one(devices):
    cmd = [
        sys.executable,
        os.path.join(REPO, "tests", "workers", "compile_scale_worker.py"),
        str(devices),
    ]
    env = dict(os.environ, PYTHONPATH=REPO)  # the worker sets XLA_FLAGS itself
    out = subprocess.run(cmd, capture_output=True, text=True, timeout=560,
                         cwd=REPO, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    return next(json.loads(line) for line in out.stdout.splitlines()
                if line.strip().startswith("{"))


@pytest.mark.slow
def test_shift_one_step_compile_flat_at_scale():
    """The full shift_one train step compiles on 32- AND 64-way meshes in
    bounded, flat time (measured ~0.35/0.48 s; bound leaves CI headroom)."""
    recs = {d: _compile_shift_one(d) for d in (32, 64)}
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
