"""The interpreter's pauses, as spans of the program's own ring — and a
record of what every thread was doing, taken while a stall lasts.

A step that takes seven seconds for 0.2 leaves nothing in the ring when no
span was open: the trainer was not running, and what kept it from running
is not the trainer's to time.  Two things can be seen from inside the
process all the same, on the ring's clock (``time.monotonic()``):

``host/gc``
    a collection of Python's cyclic collector, timed by a ``gc.callbacks``
    hook between its ``start`` and ``stop`` phases.  Every collection adds
    to the counters ``host/gc_collections`` and ``host/gc_pause_s``; one of
    generation 2, or any of :data:`GC_SPAN_MIN_S` or more, is also a rare
    span (attrs ``generation``, ``collected``; ``thread`` is the thread the
    collector ran on).  A full collection is mirrored to the profiler as
    ``bagua/host/gc`` like every other span: the mirror opens at the start,
    where only the generation is known, so a young collection that turns out
    long is a ring span alone.

``host/blocked``
    an interval in which no Python thread could run: a C call that kept the
    interpreter lock, the process descheduled, a page-fault storm.  One
    daemon thread (``bagua-obs-heartbeat``, pure Python, no jax) sleeps
    :data:`HEARTBEAT_S` at a time and measures how late it wakes; late by
    :data:`BLOCKED_MIN_S` or more is a rare span from the due time to the
    wake-up.  Its attrs tell the causes apart: ``cpu_s``, the process's CPU
    seconds (``time.process_time()``, every thread of the process, the
    runtime's workers too) from the previous wake-up to this one — about the
    wall time when one of our own threads computed under the lock, about
    nothing when the process did not run; ``involuntary_switches`` and
    ``major_faults`` (``resource.getrusage`` deltas over the same interval);
    and ``gc_s``, the seconds of it the collector accounts for (a collection
    holds the lock, so the heartbeat is late under it: those seconds count
    once, as ``host/gc``, and the counter ``host/blocked_s`` adds the
    lateness outside them).  The mirror ``bagua/host/blocked`` is an event at
    the wake-up — an annotation cannot be dated back — whose ``late_s`` says
    how far back the interval reaches.

The heartbeat also watches the step window the observer last opened
(:func:`watch_window`): once it has outlasted the anomaly detector's own cut
and :data:`STALL_SAMPLE_MIN_S`, the thread samples — once a window — the
innermost :data:`STACK_FRAMES` frames of every thread and the ring's open
spans.  The observer takes the sample when the window closes
(:func:`take_stall_sample`) and hands it to the detector, which puts it into
the suspect, the ``step/stall`` span and the flight dump.  Where the lock
was held the thread could not sample: the ``host/blocked`` span is then the
record.

Nothing here takes a lock inside the collector's callback: a collection
starts at any bytecode boundary, also on a thread that holds the counters'
lock or the ring's, so the callback adds to the counters only when their
lock is free (what it could not add waits for the next collection) and
records into the ring's lock-free rare deque.  Import-light (no jax).
Everything rides ``BAGUA_OBS``: :func:`install` and
:func:`ensure_heartbeat` are called by the step observer only while the
plane is on.
"""

from __future__ import annotations

import atexit
import gc
import os
import resource
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..telemetry import counters
from . import spans as _spans

__all__ = ["install", "uninstall", "ensure_heartbeat", "gc_seconds",
           "blocked_seconds", "paused_seconds", "watch_window",
           "take_stall_sample", "GC_SPAN_MIN_S", "HEARTBEAT_S",
           "BLOCKED_MIN_S", "STALL_SAMPLE_MIN_S", "STACK_FRAMES",
           "HEARTBEAT_THREAD"]

#: a young collection this long is a span too (a full one always is)
GC_SPAN_MIN_S = 1e-3
#: the heartbeat's sleep
HEARTBEAT_S = 0.020
#: a wake-up this late is a ``host/blocked`` span
BLOCKED_MIN_S = 0.050
#: a window is sampled once it has outlasted the detector's cut AND this
STALL_SAMPLE_MIN_S = 0.5
#: innermost frames kept of every thread's stack
STACK_FRAMES = 8
HEARTBEAT_THREAD = "bagua-obs-heartbeat"

# ---- the collector ---------------------------------------------------------
#
# Module globals and no lock: collections are serial (the collector does not
# re-enter), and the callback must not wait for anything.

_gc_t0 = 0.0
#: between the two phases of a collection (read by the heartbeat, which can
#: get the interpreter before the ``stop`` phase's first line has run)
_gc_running = False
_gc_mirror: tuple = ()
#: cumulative, never reset: what the observer differences per window
_gc_pause_s = 0.0
#: not yet added to the counters (their lock was taken when we tried)
_gc_unpublished_n = 0
_gc_unpublished_s = 0.0


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    global _gc_t0, _gc_running, _gc_mirror, _gc_pause_s
    global _gc_unpublished_n, _gc_unpublished_s
    if phase == "start":
        if not _spans.enabled():
            return  # the plane was switched off in-process (tests)
        if info["generation"] == 2:
            _gc_mirror = _spans._open_annotations("host/gc", None)
        _gc_t0 = time.monotonic()
        _gc_running = True
        return
    if not _gc_running:
        return
    t1 = time.monotonic()
    dur = t1 - _gc_t0
    _gc_running = False
    _gc_pause_s += dur
    _gc_unpublished_n += 1
    _gc_unpublished_s += dur
    if counters.add_nowait("host/gc_collections", _gc_unpublished_n):
        _gc_unpublished_n = 0
    if counters.add_nowait("host/gc_pause_s", _gc_unpublished_s):
        _gc_unpublished_s = 0.0
    if _gc_mirror:
        for annotation in _gc_mirror:
            annotation.__exit__(None, None, None)
        _gc_mirror = ()
    if info["generation"] == 2 or dur >= GC_SPAN_MIN_S:
        _spans.recorder.record_rare(_spans.finished_span(
            "host/gc", _gc_t0, t1, generation=info["generation"],
            collected=info["collected"]))


def install() -> None:
    """Hook the collector (once a process).  Called by whatever installs
    the obs plane: the step observer, while ``BAGUA_OBS`` is on."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def uninstall() -> None:
    """Take the hook out and stop the heartbeat (tests)."""
    global _gc_running, _gc_mirror, _WINDOW, _SAMPLE
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
    _gc_running = False
    _gc_mirror = ()
    _stop_heartbeat()
    _WINDOW = _SAMPLE = None


def gc_seconds() -> float:
    """Seconds the collector has taken so far (cumulative)."""
    return _gc_pause_s


def _gc_seconds_at(now: float) -> float:
    """:func:`gc_seconds` with the collection still running counted up to
    ``now``.  For the heartbeat: a waiting thread gets the interpreter at
    the first bytecode boundary after the collector proper, which is the
    entry of the ``stop`` callback, before it has added anything."""
    if _gc_running:
        return _gc_pause_s + max(0.0, now - _gc_t0)
    return _gc_pause_s


def blocked_seconds() -> float:
    """Seconds no Python thread could run so far, outside collections
    (cumulative; what the heartbeat saw of it)."""
    return _blocked_s


def paused_seconds() -> float:
    """:func:`gc_seconds` + :func:`blocked_seconds`: difference it around a
    section to know how much of the section the interpreter was paused."""
    return _gc_pause_s + _blocked_s


# ---- the heartbeat -----------------------------------------------------------

_blocked_s = 0.0
_HEARTBEAT: Optional["_Heartbeat"] = None
_HEARTBEAT_LOCK = threading.Lock()
_stops_at_exit = False

#: the step window now open: (its start on the ring's clock, the seconds
#: after which it is a stall, or None while the detector warms up)
_WINDOW: Optional[Tuple[float, Optional[float]]] = None
#: the sample taken of the open window, if it has stalled
_SAMPLE: Optional[Dict[str, Any]] = None


def watch_window(t0: float, cut_s: Optional[float]) -> None:
    """A step window opened at ``t0``; past ``cut_s`` (and
    :data:`STALL_SAMPLE_MIN_S`) the heartbeat samples every thread once."""
    global _WINDOW
    _WINDOW = (t0, cut_s)


def take_stall_sample(t0: float) -> Optional[Dict[str, Any]]:
    """The sample taken while the window that opened at ``t0`` lasted
    (None: it did not stall, or the interpreter lock was held throughout)."""
    global _SAMPLE
    sample = _SAMPLE
    if sample is None or sample["window_t0"] != t0:
        return None
    _SAMPLE = None
    return sample


def _frame_lines(frame) -> List[str]:
    lines = []
    while frame is not None and len(lines) < STACK_FRAMES:
        code = frame.f_code
        lines.append(f"{os.path.basename(code.co_filename)}:"
                     f"{frame.f_lineno} {code.co_name}")
        frame = frame.f_back
    return lines


def sample_threads(window_t0: float, now: float) -> Dict[str, Any]:
    """What every other thread is doing now, innermost frame first, and
    the spans open in the ring."""
    names = {t.ident: t.name for t in threading.enumerate()}
    own = threading.get_ident()
    stacks: Dict[str, List[str]] = {}
    for ident, frame in sys._current_frames().items():
        if ident == own:
            continue
        name = names.get(ident, f"thread-{ident}")
        if name in stacks:  # two threads of one name
            name = f"{name}#{ident}"
        stacks[name] = _frame_lines(frame)
    return {
        "window_t0": window_t0,
        "sampled_after_s": round(now - window_t0, 6),
        "stacks": stacks,
        "open_spans": [
            {"name": s["name"], "thread": s["thread"],
             "open_for_s": round(now - s["t0"], 6)}
            for s in _spans.recorder.active_snapshot()],
    }


class _Heartbeat(threading.Thread):
    def __init__(self):
        super().__init__(name=HEARTBEAT_THREAD, daemon=True)
        self._halt = threading.Event()

    def halt(self) -> None:
        self._halt.set()

    def run(self) -> None:
        global _blocked_s, _SAMPLE
        cpu0, gc0 = time.process_time(), _gc_pause_s
        usage0 = resource.getrusage(resource.RUSAGE_SELF)
        due = time.monotonic() + HEARTBEAT_S
        while not self._halt.wait(max(0.0, due - time.monotonic())):
            woke = time.monotonic()
            cpu1, gc1 = time.process_time(), _gc_seconds_at(woke)
            usage1 = resource.getrusage(resource.RUSAGE_SELF)
            late = woke - due
            if late >= BLOCKED_MIN_S and _spans.enabled():
                # the collector's seconds since the last wake-up count once,
                # as host/gc (they cannot lie in the 20 ms we slept: a
                # collection holds the lock, and we woke on time or late)
                in_gc = min(late, gc1 - gc0)
                if late > in_gc:
                    _blocked_s += late - in_gc
                    counters.incr("host/blocked_s", late - in_gc)
                _spans.recorder.record_rare(_spans.finished_span(
                    "host/blocked", due, woke,
                    cpu_s=round(cpu1 - cpu0, 6), gc_s=round(in_gc, 6),
                    involuntary_switches=usage1.ru_nivcsw - usage0.ru_nivcsw,
                    major_faults=usage1.ru_majflt - usage0.ru_majflt))
                for annotation in _spans._open_annotations(
                        "host/blocked", None, late_s=round(late, 6)):
                    annotation.__exit__(None, None, None)
            window = _WINDOW
            if (window is not None and window[1] is not None
                    and woke - window[0] > max(window[1], STALL_SAMPLE_MIN_S)
                    and (_SAMPLE is None
                         or _SAMPLE["window_t0"] != window[0])):
                _SAMPLE = sample_threads(window[0], woke)
            cpu0, gc0, usage0 = cpu1, gc1, usage1
            due = time.monotonic() + HEARTBEAT_S


def ensure_heartbeat() -> None:
    """Start the heartbeat thread unless it runs (again after a fork: a
    child has none of its parent's threads)."""
    global _HEARTBEAT, _stops_at_exit
    beat = _HEARTBEAT
    if beat is not None and beat.is_alive():
        return
    if not _stops_at_exit:
        _stops_at_exit = True
        atexit.register(_stop_heartbeat)
    with _HEARTBEAT_LOCK:
        if _HEARTBEAT is None or not _HEARTBEAT.is_alive():
            _HEARTBEAT = _Heartbeat()
            _HEARTBEAT.start()


def _stop_heartbeat() -> None:
    global _HEARTBEAT
    with _HEARTBEAT_LOCK:
        beat, _HEARTBEAT = _HEARTBEAT, None
    if beat is not None and beat.is_alive():
        beat.halt()
        beat.join(timeout=2.0)
