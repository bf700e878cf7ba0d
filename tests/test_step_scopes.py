"""The step seen from inside: phase scopes in the compiled program, kernel
names, and the program's spans on the profiler's clock.

Everything here reads what an operator (or the benchmark) reads: the
optimized HLO text of ``BaguaTrainer.compiled_step`` — where every
instruction's ``op_name`` carries the ``bagua.*`` scope it was traced under,
wrapped by JAX's own ``jvp(...)`` / ``transpose(...)`` / ``rematted_computation``
— and a ``jax.profiler`` capture read back with ``ProfileData``.
"""

import ast
import glob
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import optax
import pytest

import bench
from bagua_tpu.algorithms import (
    ByteGradAlgorithm, GradientAllReduceAlgorithm, ZeroOptimizerAlgorithm,
)
from bagua_tpu.core.backend import BaguaTrainer
from bagua_tpu.obs import spans as obs_spans
from bagua_tpu.parallel.mesh import build_mesh
from bagua_tpu.telemetry import counters

N_DEVICES = 8
ROOT = pathlib.Path(__file__).resolve().parents[1]

_INSTRUCTION = re.compile(
    r"\s*(?:ROOT )?%?([\w.\-]+) = (?:\(.*?\)|\S+) ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
                "collective-permute")

ALGORITHMS = {
    "gradient_allreduce": lambda: GradientAllReduceAlgorithm(),
    "zero": lambda: ZeroOptimizerAlgorithm(optax.sgd(0.1)),
    "bytegrad": lambda: ByteGradAlgorithm(hierarchical=False),
}


@pytest.fixture
def obs_on():
    obs_spans.set_enabled(True)
    obs_spans.recorder.clear()
    yield
    obs_spans.set_enabled(None)
    obs_spans.recorder.clear()


def golden_trainer(algorithm="gradient_allreduce", **kw):
    loss_fn, params, batch = bench.golden_task()
    # 600-byte buckets: the golden MLP's 1024-byte kernel stands alone in
    # its own shape, the three smaller leaves share a 1-D flat (at 256 every
    # leaf would be its own bucket and nothing would run under bagua.layout)
    trainer = BaguaTrainer(
        loss_fn, optax.sgd(0.1), ALGORITHMS[algorithm](),
        mesh=build_mesh({"dp": N_DEVICES}), autotune=False, bucket_bytes=600,
        **kw)
    state = trainer.init(params)
    return trainer, state, trainer.shard_batch(batch)


def op_names(text):
    """[(instruction, opcode, op_name)] of an optimized HLO text."""
    out = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            path = _OP_NAME.search(line)
            out.append((m.group(1), m.group(2), path.group(1) if path else ""))
    return out


def has(paths, *needles, without=()):
    return any(all(n in p for n in needles)
               and not any(w in p for w in without) for p in paths)


# ---- A: phase scopes inside the compiled step -------------------------------


@pytest.mark.parametrize("accum_steps", [1, 2])
@pytest.mark.parametrize("overlap", ["off", "on"])
@pytest.mark.parametrize("algorithm", sorted(ALGORITHMS))
def test_compiled_step_names_its_phases(algorithm, overlap, accum_steps):
    trainer, state, batch = golden_trainer(algorithm, overlap=overlap,
                                           accum_steps=accum_steps)
    compiled = trainer.compiled_step(state, batch)
    assert isinstance(compiled, jax.stages.Compiled)
    instructions = op_names(compiled.as_text())
    paths = [p for _, _, p in instructions]
    # forward and backward come from ONE scope and JAX's transform wrappers
    assert has(paths, "jvp(bagua.loss)", without=("transpose(",))
    assert has(paths, "transpose(jvp(bagua.loss))")
    assert has(paths, "bagua.optimizer")
    assert has(paths, "bagua.layout")
    assert not has(paths, "rematted_computation")
    collectives = [(name, path) for name, opcode, path in instructions
                   if opcode.startswith(_COLLECTIVES)]
    assert collectives
    for name, path in collectives:
        assert "bagua.comm/" in path, (name, path)
    assert has([p for _, p in collectives], "bagua.comm/bucket_")
    # the gauge says what the plan asks of the wire; XLA may combine
    assert len(trainer._plan.buckets) > 1
    assert counters.get("comm/buckets_per_step") == len(trainer._plan.buckets)


def test_remat_replay_is_named_by_jax_itself():
    from bagua_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss_fn,
    )

    model = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=16, remat=True))
    tokens = jnp.zeros((N_DEVICES, 9), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:1, :8])["params"]
    trainer = BaguaTrainer(lm_loss_fn(model), optax.sgd(0.1),
                           GradientAllReduceAlgorithm(),
                           mesh=build_mesh({"dp": N_DEVICES}), autotune=False)
    state = trainer.init(params)
    batch = trainer.shard_batch({"tokens": tokens})
    paths = [p for _, _, p in
             op_names(trainer.compiled_step(state, batch).as_text())]
    assert has(paths, "bagua.loss", "rematted_computation")
    # flax's own module names stay inside the scope
    assert has(paths, "jvp(bagua.loss)", "TransformerLM")


def test_one_chip_world_exchanges_no_bucket():
    loss_fn, params, batch = bench.golden_task(batch_size=8)
    trainer = BaguaTrainer(
        loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
        mesh=build_mesh({"dp": 1}, jax.devices()[:1]), autotune=False)
    state = trainer.init(params)
    text = trainer.compiled_step(state, trainer.shard_batch(batch)).as_text()
    # (XLA:CPU keeps a one-member all-reduce; the TPU compiler drops it)
    assert counters.get("comm/buckets_per_step") == 0
    assert has([p for _, _, p in op_names(text)], "bagua.optimizer")


# ---- B: program spans on the profiler's clock --------------------------------


def host_events(trace_dir):
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert files, "the capture wrote no .xplane.pb"
    events = []
    for plane in ProfileData.from_file(max(files, key=os.path.getmtime)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("bagua/", "bagua_train")):
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   dict(ev.stats), line.name))
    return events


def capture_two_steps(tmp_path):
    trainer, state, batch = golden_trainer()
    state, loss = trainer.train_step(state, batch)   # compile outside
    float(loss)
    first = trainer._step_counter + 1
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(2):
            state, loss = trainer.train_step(state, batch)
        float(loss)
    finally:
        jax.profiler.stop_trace()
    return first, host_events(str(tmp_path))


def test_profile_holds_the_step_and_its_spans(obs_on, tmp_path):
    first, events = capture_two_steps(tmp_path)
    steps = sorted((e for e in events if e[0] == "bagua_train"),
                   key=lambda e: e[1])
    assert [e[3].get("step_num") for e in steps] == [first, first + 1]
    roots = [e for e in events if e[0] == "bagua/step/train_step"]
    dispatches = [e for e in events if e[0] == "bagua/step/dispatch"]
    assert len(roots) == len(dispatches) == 2
    for _, lo, hi, _, line in dispatches:
        # each dispatch sits inside one root span of the same thread, and
        # each root inside one step annotation: one clock for all of them
        assert sum(r[1] <= lo and hi <= r[2] and r[4] == line
                   for r in roots) == 1
    for _, lo, hi, _, _ in roots:
        assert sum(s[1] <= lo and hi <= s[2] for s in steps) == 1
    names = {e[0] for e in events}
    assert {"bagua/step/hooks", "bagua/step/watchdog_handoff"} <= names
    # the ring tells the same story: dispatch is the root span's child
    ring = obs_spans.span_ring.snapshot()
    dispatch = [s for s in ring if s["name"] == "step/dispatch"][-1]
    root = [s for s in ring if s["name"] == "step/train_step"][-1]
    assert (dispatch["parent"], dispatch["depth"]) == ("step/train_step", 1)
    assert (root["parent"], root["depth"]) == (None, 0)
    assert root["step"] == dispatch["step"] == first + 1
    assert root["dur_s"] >= dispatch["dur_s"]


def test_obs_off_writes_no_annotation_and_keeps_the_scopes(tmp_path):
    obs_spans.set_enabled(False)
    obs_spans.recorder.clear()
    try:
        _, events = capture_two_steps(tmp_path)
        assert events == []
        trainer, state, batch = golden_trainer()
        paths = [p for _, _, p in
                 op_names(trainer.compiled_step(state, batch).as_text())]
        assert has(paths, "jvp(bagua.loss)") and has(paths, "bagua.optimizer")
        assert obs_spans.span_ring.snapshot() == []
    finally:
        obs_spans.set_enabled(None)


def test_prefetch_divides_the_wait_for_input(obs_on):
    from bagua_tpu.contrib.prefetch import prefetch_to_device

    trainer, _, _ = golden_trainer()
    _, _, batch = bench.golden_task()
    batches = prefetch_to_device(iter([batch] * 3), trainer=trainer, size=1)
    obs_spans.set_current_step(None)
    assert len(list(batches)) == 3
    names = [s["name"] for s in obs_spans.span_ring.snapshot()
             if s["name"].startswith("input/")]
    # the fourth pull finds the iterator exhausted: a source span, no place
    assert names.count("input/place") == 3
    assert names.count("input/source") == 4


def test_a_span_without_jax_mirrors_nothing(obs_on, monkeypatch):
    """spans.py imports no jax; a process that has none (the launcher) pays
    for no annotation."""
    import sys

    monkeypatch.setitem(sys.modules, "jax", None)
    with obs_spans.trace_span("launcher/tick") as span:
        assert span.annotations == ()
    assert obs_spans.span_ring.snapshot()[-1]["name"] == "launcher/tick"


# ---- kernel names -------------------------------------------------------------

KERNEL_FILES = ("bagua_tpu/ops/flash_attention.py", "bagua_tpu/ops/gmm.py",
                "bagua_tpu/compression/pallas_codec.py")


def pallas_call_names(path):
    names = []
    for node in ast.walk(ast.parse((ROOT / path).read_text())):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "pallas_call"):
            name = next((k.value for k in node.keywords if k.arg == "name"),
                        None)
            # a literal, or a choice between two literals (a windowed
            # flash call's own name): every name is there to grep for
            choices = ([name.body, name.orelse]
                       if isinstance(name, ast.IfExp) else [name])
            for choice in choices:
                assert isinstance(choice, ast.Constant) and isinstance(
                    choice.value, str), (
                        f"{path}:{node.lineno} has no literal name=")
                names.append(choice.value)
    return names


@pytest.mark.parametrize("path, count", zip(KERNEL_FILES, (6, 2, 9)))
def test_every_pallas_call_has_a_literal_name(path, count):
    names = pallas_call_names(path)
    assert len(names) == count
    assert all(re.fullmatch(r"[a-z][a-z0-9_]*", n) for n in names)
    everywhere = [n for p in KERNEL_FILES for n in pallas_call_names(p)]
    assert len(set(everywhere)) == len(everywhere)
    if path.endswith("flash_attention.py"):
        # the three names the gpt2 cell's readers key on, each beside
        # its windowed twin
        assert names == ["flash_fwd", "flash_win_fwd", "flash_bwd_dkv",
                         "flash_win_bwd_dkv", "flash_bwd_dq",
                         "flash_win_bwd_dq"]


def test_no_pallas_call_outside_the_named_files():
    for path in (ROOT / "bagua_tpu").rglob("*.py"):
        rel = str(path.relative_to(ROOT))
        if "pallas_call(" in path.read_text() and rel not in KERNEL_FILES:
            pallas_call_names(rel)  # must be named as well
