"""The trainer's one observer seam (``bagua_tpu.obs.step_observer``): one
window-class fact with three readers, the same class with its readers absent
when the plane is off, and the import arrows pointing one way."""

import ast
import os
import time

import numpy as np
import pytest

from bagua_tpu.obs import spans as obs_spans
from bagua_tpu.obs.step_observer import StepObserver

BACKEND = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bagua_tpu", "core", "backend.py")
BATCH = {"x": np.zeros((16, 4), np.float32)}


class _Ledger:
    def __init__(self):
        self.windows = []

    def note_step_window(self, step, raw, cls):
        self.windows.append((step, cls))

    def note_class_window(self, cls, seconds):
        self.windows.append((cls, seconds))


class _Detector:
    def __init__(self):
        self.seen = []

    def observe(self, step, raw, phases, sample=None):
        self.seen.append(step)

    def cut_s(self):
        return None


class _Speed:
    def __init__(self):
        self.samples = []

    def record(self, value):
        self.samples.append(value)


@pytest.fixture()
def plane_on():
    obs_spans.set_enabled(True)
    yield
    obs_spans.set_current_step(None)
    obs_spans.set_enabled(None)


def _watched(monkeypatch):
    """An observer whose three readers record what they are handed."""
    monkeypatch.delenv("BAGUA_OBS_EXPORT_DIR", raising=False)
    monkeypatch.delenv("BAGUA_OBS_HTTP_PORT", raising=False)
    obs = StepObserver()
    obs.ledger, obs.anomaly_detector = _Ledger(), _Detector()
    obs._speed_tracker = _Speed()
    return obs


def _step(obs, n, notes=()):
    obs.begin_step(n)
    for cls in notes:
        obs.note_window_class(cls)
    time.sleep(0.002)
    obs.end_step(BATCH, track_speed=True)


@pytest.mark.parametrize("notes,booked", [
    (("compile",), "compile"),
    (("state_migration",), "state_migration"),
    # a migration usually brings a recompile, which claims the window —
    # whichever of the two is noted first
    (("state_migration", "compile"), "compile"),
    (("compile", "state_migration"), "compile"),
], ids=["compile", "migration", "migration_then_compile",
        "compile_then_migration"])
def test_one_call_classes_the_window_for_all_three_readers(
        plane_on, monkeypatch, notes, booked):
    obs = _watched(monkeypatch)
    _step(obs, 1)                 # opens window 1 (no window closes yet)
    _step(obs, 2, notes)          # window 2 holds the compile / migration
    _step(obs, 3)                 # a productive window
    obs.begin_step(4)             # closes window 3
    assert obs.ledger.windows == [
        (1, "productive_step"), (2, booked), (3, "productive_step")]
    # the detector neither flags window 2 nor takes it into its baseline
    assert obs.anomaly_detector.seen == [1, 3]
    # the speed tracker dropped the sample of the step that opened it:
    # steps 1 and 3 sampled, step 2 not
    assert len(obs._speed_tracker.samples) == 2
    # consumed with the window: nothing leaks into the next one
    assert obs._window_class is None


def test_the_three_mirrored_flags_are_gone():
    src = open(BACKEND).read()
    for name in ("_skip_next_speed_sample", "_anomaly_skip_window",
                 "_ledger_window_class"):
        assert name not in src
    obs = StepObserver.__new__(StepObserver)
    assert not any(hasattr(obs, n) for n in (
        "_skip_next_speed_sample", "_anomaly_skip_window",
        "_ledger_window_class"))


@pytest.mark.parametrize("plane", ["off", "on"])
def test_plane_off_is_the_same_class_with_its_readers_absent(
        monkeypatch, tmp_path, plane):
    from bagua_tpu.elastic import membership
    from bagua_tpu.obs import anomaly, export, ledger

    made = []
    monkeypatch.setenv("BAGUA_ELASTIC_HEALTH_FILE", str(tmp_path / "beacon"))
    monkeypatch.delenv("BAGUA_OBS_HTTP_PORT", raising=False)
    monkeypatch.setattr(export, "maybe_start_global_exporter",
                        lambda who=None: made.append("exporter"))
    real_install, real_detector = ledger.install, anomaly.StepAnomalyDetector
    monkeypatch.setattr(ledger, "install",
                        lambda: made.append("ledger") or real_install())
    monkeypatch.setattr(
        anomaly, "StepAnomalyDetector",
        lambda *a, **k: made.append("detector") or real_detector(*a, **k))
    real_beacon = membership.write_health_beacon
    monkeypatch.setattr(
        membership, "write_health_beacon",
        lambda *a, **k: made.append("beacon") or real_beacon(*a, **k))
    obs_spans.set_enabled(plane == "on")
    try:
        obs = StepObserver()
        assert type(obs) is StepObserver
        for n in (1, 2, 3):
            _step(obs, n)
        # the cadence is measured either way: maybe_straggle and the async
        # family read it with the plane off
        assert obs.measured_step_dt() is not None
        assert 0 < obs.measured_step_dt() < 1.0
        obs.note_injected_stall(0.05)
        obs.begin_step(4)
        assert obs.measured_step_dt() < 0.05      # the stall is subtracted
        if plane == "off":
            assert made == []
            assert obs.ledger is None and obs.anomaly_detector is None
            assert not os.path.exists(str(tmp_path / "beacon"))
            assert obs.autotune_window() is None
        else:
            assert set(made) == {"exporter", "ledger", "detector", "beacon"}
            assert obs.ledger is not None
            assert os.path.exists(str(tmp_path / "beacon"))
    finally:
        obs_spans.set_current_step(None)
        obs_spans.set_enabled(None)
        ledger.ledger.reset()
        export.reset_local_summary()


def test_the_lines_that_kernel_bodies_embed_keep_their_numbers():
    """A Mosaic kernel's body embeds the innermost ten frames that traced
    it, file, line and column, and the compile cache keys on the body.  A
    backward kernel (``embed_grad``, in every cell) is traced from a shallow
    stack: its ten frames reach through ``bagua_step`` into ``_train_step``'s
    dispatch line and ``train_step``'s call, so an edit that moves either
    costs every benchmark cell one cold compile (PR 52 compiled gpt2's
    lowered step with both files from one path: five payloads, one changed,
    none once the two lines were back).  A PR that has to move them re-pins
    the numbers here and says so (ROADMAP: a moved line is a cold compile)."""
    lines = open(BACKEND).read().splitlines()
    assert lines[1384 - 1].strip().startswith("def bagua_step(")
    assert lines[1863 - 1] == "            return self._train_step(state, batch)"
    assert lines[1936 - 1] == "                out = fn(state, batch)"


def test_backend_imports_only_spans_and_the_observer_from_the_planes_above():
    """Arrows point one way: ``core/backend.py`` may import
    ``bagua_tpu.obs.spans`` and ``bagua_tpu.obs.step_observer`` — nothing
    else of ``obs/``, nothing of ``elastic/`` — at module level or inside
    any function."""
    tree = ast.parse(open(BACKEND).read())
    package = ["bagua_tpu", "core"]          # backend.py's own package
    seen = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            seen.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level \
                else []
            mod = ".".join(base + ([node.module] if node.module else []))
            # ``from ..obs import export`` names a submodule too
            seen.update(f"{mod}.{a.name}" for a in node.names)
    planes = sorted(m for m in seen
                    if m.startswith(("bagua_tpu.obs", "bagua_tpu.elastic")))
    assert planes, "the walk found no obs import at all: it is broken"
    allowed = ("bagua_tpu.obs.spans", "bagua_tpu.obs.step_observer")
    offenders = [m for m in planes
                 if not any(m == a or m.startswith(a + ".") for a in allowed)]
    assert offenders == [], offenders
