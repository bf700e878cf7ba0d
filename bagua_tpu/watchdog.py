"""Hang watchdog: convert silent stalls into crashes a launcher can restart.

Counterpart of the reference's comm monitor thread, which panics the process
when a scheduled comm op exceeds 300 s
(/root/reference/rust/bagua-core/bagua-core-internal/src/lib.rs:255-265), and
of its panic-escalation hook (bagua-core-py/src/lib.rs:518-523) — under XLA
the analogous failure is a collective deadlock across ranks (e.g. one rank
compiled a different program) that blocks forever.  A hung worker holds the
whole gang; killing it lets ``bagua_tpu.distributed.run``'s gang restart
recover from the checkpoint.

ON BY DEFAULT at the reference's 300 s (``BAGUA_COMM_TIMEOUT_S``; set 0/off
to disable).  Always-on is affordable because watching is asynchronous: the
trainer hands each step's loss array to a background *waiter* thread that
performs the reliable host readback inside a watched section — the main
thread keeps dispatching at full speed, and a wedged collective surfaces as
the waiter stuck past the timeout.  (The readback is one scalar per step,
and it doubles as the value the non-finite-loss check needs on the host.)

On firing, the watchdog raises the cooperative abort flag
(:func:`bagua_tpu.communication.abort`) so control loops stop, then dumps
all thread stacks and terminates (``action="exit"``).  ``action="abort"``
stops at the flag (in-process recovery; tests), ``action="log"`` only
records.
"""

from __future__ import annotations

import atexit
import faulthandler
import logging
import os
import queue
import sys
import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional

from .faults import inject as _inject

logger = logging.getLogger(__name__)

DEFAULT_TIMEOUT_S = 300.0  # the reference's comm monitor bound (lib.rs:255)


def get_comm_timeout_s() -> Optional[float]:
    """Watchdog timeout in seconds, or None when disabled.  The off-value
    semantics (``0``/``off``/``false``/``no``/``none``/empty) live in the
    env registry's :func:`bagua_tpu.env.env_seconds_or_off` accessor, so
    ``bagua-lint``'s registry coverage stays total."""
    from . import env

    return env.get_comm_timeout_s()


class HangWatchdog:
    """Monitors watched sections; if one runs past ``timeout_s``, raises the
    global comm abort flag, then terminates the process (``action="exit"``),
    stops at the flag (``action="abort"``), or just records
    (``action="log"``, for tests).

    Two watching styles:

    * :meth:`watch` — context manager around blocking host work.
    * :meth:`watch_result` — non-blocking: enqueue an async step result; the
      internal waiter thread reads it back inside a watched section.
    """

    _CHECK_INTERVAL_S = 1.0
    _QUEUE_MAX = 64  # backlog cap; a hang pins the waiter on ONE item anyway

    def __init__(self, timeout_s: float = DEFAULT_TIMEOUT_S,
                 action: str = "exit"):
        assert action in ("exit", "abort", "log")
        self.timeout_s = timeout_s
        self.action = action
        self.fired = threading.Event()  # informational latch (never cleared)
        self._armed = True  # re-arms when all overdue sections clear
        self._active: Dict[object, tuple] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._queue: "queue.Queue" = queue.Queue(maxsize=self._QUEUE_MAX)
        self._waiter: Optional[threading.Thread] = None
        self._readback_warned = False
        self._thread = threading.Thread(
            target=self._monitor, name="bagua-watchdog", daemon=True
        )
        self._thread.start()

    @contextmanager
    def watch(self, label: str = "comm"):
        # token is a fresh object per entry, NOT the thread id: keying by
        # get_ident() made an inner (nested) watch clobber the outer entry
        # and its exit pop the shared key — leaving the outer section
        # unwatched for the rest of its run
        from .obs.spans import trace_span

        token = object()
        with self._lock:
            self._active[token] = (label, time.monotonic())
        try:
            # the watched section doubles as a span: a post-mortem's span
            # tail shows exactly which section the waiter was pinned in
            with trace_span(f"watchdog/{label}"):
                yield
        finally:
            with self._lock:
                self._active.pop(token, None)

    def watch_result(self, array, label: str = "step") -> None:
        """Watch an async result without blocking the caller.  When the
        backlog is full the item is dropped — safe, because a wedged
        collective pins the waiter on whichever item it is currently
        reading back, and every later step queues behind the same hang."""
        if self._waiter is None:
            with self._lock:
                if self._waiter is None:
                    self._waiter = threading.Thread(
                        target=self._wait_loop, name="bagua-watchdog-waiter",
                        daemon=True,
                    )
                    self._waiter.start()
        try:
            self._queue.put_nowait((label, array))
        except queue.Full:
            pass

    def _wait_loop(self):
        import numpy as np

        while not self._stop.is_set():
            try:
                label, array = self._queue.get(timeout=0.5)
            except queue.Empty:
                continue
            with self.watch(label):
                # chaos hook: an armed ``collective.hang`` fault wedges
                # this readback inside the watched section — exactly the
                # signature of a cross-rank collective deadlock (bounded
                # by the spec's duration; the stop event cuts it short)
                _inject.maybe_hang(stop_event=self._stop)
                try:
                    # host readback: the reliable fence.  Multi-process
                    # global arrays can't be fetched whole — their LOCAL
                    # shard is the per-process fence instead.
                    if (
                        hasattr(array, "is_fully_addressable")
                        and not array.is_fully_addressable
                    ):
                        np.asarray(array.addressable_shards[0].data)
                    else:
                        np.asarray(array)
                except Exception as e:
                    # runtime errors surface on the main thread's own use
                    # of the result; the watchdog only cares about hangs.
                    # BUT an instantly-failing readback (donated/deleted
                    # buffer, non-replicated global array) silently disarms
                    # hang detection — make the degradation visible once.
                    if not self._readback_warned:
                        self._readback_warned = True
                        logger.warning(
                            "watchdog: readback of %r failed (%s: %s) — "
                            "sections from watch_result() no longer fence "
                            "device work; hang detection may be degraded",
                            label, type(e).__name__, e,
                        )

    def _monitor(self):
        while not self._stop.wait(self._CHECK_INTERVAL_S):
            now = time.monotonic()
            with self._lock:
                overdue = [
                    (label, now - t0)
                    for label, t0 in self._active.values()
                    if now - t0 > self.timeout_s
                ]
            if overdue:
                label, dt = overdue[0]
                logger.error(
                    "watchdog: section %r stuck for %.0f s (timeout %.0f s) — "
                    "dumping stacks", label, dt, self.timeout_s,
                )
                self.fired.set()
                if self._armed:
                    # cooperative abort first: control loops (async model
                    # average) stop launching work even in abort mode
                    if self.action != "log":
                        from .communication import abort

                        abort(f"watchdog: {label} stuck for {dt:.0f} s")
                        # flight recorder: the post-mortem artifact for
                        # this hang episode — host-only reads (span ring,
                        # counters), so a wedged device cannot block it
                        from .obs.recorder import dump_flight_record

                        dump_flight_record(
                            "watchdog_abort",
                            reason=f"section {label!r} stuck for {dt:.0f} s "
                                   f"(timeout {self.timeout_s:.0f} s)",
                        )
                    # dump stacks once per hang episode, not every tick
                    faulthandler.dump_traceback(file=sys.stderr)
                    self._armed = False
                if self.action == "exit":
                    # elastic jobs: tell the membership registry this is a
                    # DELIBERATE departure, so the coordinator logs a leave
                    # (watchdog kill) rather than a silent hang/crash.
                    # No-op outside elastic mode; bounded; never raises.
                    try:
                        from .elastic.membership import publish_leave_intent

                        publish_leave_intent(
                            f"watchdog: {label} stuck for {dt:.0f} s"
                        )
                    except Exception:
                        pass
                    # flush queued async checkpoint saves first — os._exit
                    # skips atexit handlers, and the whole point of dying is
                    # to restart from the freshest durable checkpoint.
                    # Bounded: a wedged flush cannot block the exit.
                    try:
                        from .checkpoint import flush_all_checkpoints

                        flush_all_checkpoints(timeout_s=10.0)
                    except Exception:
                        pass
                    # the gang-restart contract: die loudly, let the
                    # launcher respawn from the checkpoint
                    os._exit(3)
                # abort/log modes: keep monitoring (later hangs surface too)
            elif not self._armed:
                # hang episode over (sections cleared, e.g. after
                # reset_abort recovery): re-arm so the NEXT hang re-raises
                # the abort flag and dumps stacks again
                self._armed = True

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)
        if self._waiter is not None:
            self._waiter.join(timeout=5)


_GLOBAL: Optional[HangWatchdog] = None
_GLOBAL_LOCK = threading.Lock()


def get_global_watchdog(timeout_s: float) -> HangWatchdog:
    """Process-wide watchdog (one monitor thread no matter how many trainers
    exist — the reference also runs ONE comm monitor per backend process,
    lib.rs:255-265).  When later callers ask for a different timeout the
    STRICTER (smaller) one is adopted — silently keeping the first caller's
    looser bound would leave the later trainer under-protected — and the
    difference is logged either way."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = HangWatchdog(timeout_s)
            # stop the waiter BEFORE interpreter teardown: a daemon thread
            # killed mid-readback inside PJRT aborts the whole process at
            # exit (SIGABRT after a perfectly good run)
            atexit.register(_GLOBAL.stop)
        elif float(timeout_s) != _GLOBAL.timeout_s:
            adopted = min(float(timeout_s), _GLOBAL.timeout_s)
            logger.warning(
                "get_global_watchdog: requested timeout %.0f s differs from "
                "the active %.0f s (one watchdog per process); adopting the "
                "stricter %.0f s",
                timeout_s, _GLOBAL.timeout_s, adopted,
            )
            _GLOBAL.timeout_s = adopted
        return _GLOBAL
