"""Checkpoint / resume.

The reference has no framework-level checkpointing — its elastic example
hand-rolls ``torch.save`` of ``{epoch, model_state_dict, optimizer_state_dict}``
on rank 0 and reloads on (re)start
(/root/reference/examples/elastic_training/main.py:238-259), relying on
``_bagua_broadcast_parameters`` to re-sync.  On TPU the state is a sharded
pytree, so this is a real subsystem here: orbax-backed, optionally async
(saves overlap training), with retention pruning — the piece SURVEY.md §5.4
calls out as required for the elastic workload.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import weakref
from typing import Any, List, Optional, Tuple

import jax

logger = logging.getLogger(__name__)

# live managers, so emergency paths (watchdog exit) can flush queued async
# saves instead of losing them to os._exit skipping atexit handlers
_LIVE_MANAGERS: "weakref.WeakSet" = weakref.WeakSet()


# ---- storage quarantine (docs/autopilot.md) -------------------------------
#
# Repeated integrity failures / fallback restores on one checkpoint
# directory mean the STORAGE under it is rotting — continuing to save there
# burns wall clock writing checkpoints that will not verify at the next
# restore.  The autopilot's ckpt_integrity rule (or an operator, via
# BAGUA_CKPT_QUARANTINED_PATHS) quarantines the path: every live
# BaguaCheckpointManager on it redirects subsequent SAVES to a
# `<dir>.redirect` sibling, while RESTORES keep walking both directories
# (newest-first across the union — reads of already-verified old steps are
# exactly what quarantine must not break).

_QUARANTINE_LOCK = threading.Lock()
_QUARANTINED: set = set()
_QUARANTINE_SEEDED = False


def _normalize_storage_path(path: str) -> str:
    p = str(path)
    if "://" in p:  # gs:// etc. — keep verbatim minus trailing slashes
        return p.rstrip("/")
    return os.path.abspath(p).rstrip("/")


def _seed_quarantine_from_env() -> None:
    """One-time seed from ``BAGUA_CKPT_QUARANTINED_PATHS`` — the channel
    the elastic launcher uses to carry the autopilot's quarantine verdicts
    into respawned workers at the restart boundary."""
    global _QUARANTINE_SEEDED
    if _QUARANTINE_SEEDED:
        return
    _QUARANTINE_SEEDED = True
    from . import env as _env

    for p in _env.get_ckpt_quarantined_paths():
        _QUARANTINED.add(_normalize_storage_path(p))


def quarantine_storage_path(path: str) -> bool:
    """Quarantine a checkpoint directory (idempotent; returns True when
    newly quarantined).  Live managers on the path redirect their next
    save; future managers resolve the redirect at construction."""
    with _QUARANTINE_LOCK:
        _seed_quarantine_from_env()
        p = _normalize_storage_path(path)
        if p in _QUARANTINED:
            return False
        _QUARANTINED.add(p)
    logger.warning(
        "checkpoint storage QUARANTINED: %s — saves redirect to %s",
        p, redirect_directory(p),
    )
    return True


def is_quarantined(path: str) -> bool:
    with _QUARANTINE_LOCK:
        _seed_quarantine_from_env()
        return _normalize_storage_path(path) in _QUARANTINED


def quarantined_paths() -> List[str]:
    with _QUARANTINE_LOCK:
        _seed_quarantine_from_env()
        return sorted(_QUARANTINED)


def clear_quarantine() -> None:
    """Forget every quarantine (test isolation)."""
    global _QUARANTINE_SEEDED
    with _QUARANTINE_LOCK:
        _QUARANTINED.clear()
        _QUARANTINE_SEEDED = True


def redirect_directory(path: str) -> str:
    """Where saves for a quarantined ``path`` land."""
    return _normalize_storage_path(path) + ".redirect"


def active_directory(path: str) -> str:
    """Resolve a requested checkpoint directory through the quarantine
    registry (chasing redirect-of-redirect up to a small bound — a
    redirect that rots too gets quarantined like any other path)."""
    p = _normalize_storage_path(path)
    for _ in range(4):
        if not is_quarantined(p):
            return p
        p = redirect_directory(p)
    return p


class CheckpointIntegrityError(RuntimeError):
    """A checkpoint failed content verification at restore (recorded sha256
    digest vs the restored leaves) — torn write, bit rot, or an injected
    ``ckpt.write`` fault.  :meth:`BaguaCheckpointManager.restore` treats it
    (like an unreadable checkpoint) as a fallback trigger when no explicit
    step was requested."""


def compute_state_digest(state: Any) -> Optional[dict]:
    """Content checksum of a state pytree: sha256 over every leaf's path,
    shape, dtype, and raw bytes, in tree-flatten order.  Sharding- and
    layout-agnostic w.r.t. the MESH (global logical values are hashed), so
    an elastic restore at a different topology verifies against the digest
    recorded at save time.  Returns None when the state cannot be fetched
    whole (multi-process non-addressable arrays) — verification is then
    skipped with a log line rather than hashing a partial view."""
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    flat = jax.tree_util.tree_flatten_with_path(state)[0]
    for path, leaf in flat:
        h.update(jax.tree_util.keystr(path).encode())
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            if getattr(leaf, "is_fully_addressable", True) is False:
                logger.info(
                    "checkpoint integrity: %s is not fully addressable on "
                    "this process; digest skipped", jax.tree_util.keystr(path),
                )
                return None
            arr = np.asarray(leaf)
            h.update(str(arr.shape).encode())
            h.update(str(arr.dtype).encode())
            h.update(arr.tobytes())
        else:
            h.update(repr(leaf).encode())
    return {"algo": "sha256", "digest": h.hexdigest(), "leaves": len(flat)}


def flush_all_checkpoints(timeout_s: float = 10.0) -> None:
    """Best-effort flush of every live manager's queued async saves, bounded
    by ``timeout_s`` — called by the watchdog before it terminates a wedged
    process, where an unbounded ``wait_until_finished`` could itself hang."""
    managers = list(_LIVE_MANAGERS)
    if not managers:
        return

    def flush():
        for m in managers:
            try:
                m.wait()
            except Exception as e:  # pragma: no cover - backend-dependent
                logger.warning("checkpoint flush failed: %s", e)

    t = threading.Thread(target=flush, name="bagua-ckpt-flush", daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        logger.error(
            "checkpoint flush did not finish within %.0f s — queued async "
            "saves may be lost", timeout_s,
        )


class BaguaCheckpointManager:
    """Save/restore ``TrainState`` (or any pytree) with retention + async.

    Thin policy layer over ``orbax.checkpoint.CheckpointManager``; all ranks
    must call :meth:`save`/:meth:`restore` collectively (orbax coordinates
    the multi-host barrier itself).
    """

    def __init__(
        self,
        directory: str,
        max_to_keep: int = 3,
        save_interval_steps: int = 1,
        async_save: bool = True,
        integrity: bool = True,
    ):
        """``integrity=True`` (default) records a content checksum
        (:func:`compute_state_digest`) in every save's layout sidecar and
        verifies it on restore — a corrupted/torn checkpoint then degrades
        to the previous verified step (loud warning) instead of restoring
        garbage.  Costs one host readback of the state per save; set False
        to opt out (e.g. states too large to fetch per save)."""
        import orbax.checkpoint as ocp

        self._ocp = ocp
        #: the directory the CALLER asked for — quarantine verdicts name
        #: this path; ``self.directory`` is the ACTIVE (possibly
        #: redirected) one
        self.requested_directory = str(directory)
        self.directory = active_directory(self.requested_directory)
        if self.directory != _normalize_storage_path(
                self.requested_directory):
            logger.warning(
                "checkpoint directory %s is quarantined; using %s",
                self.requested_directory, self.directory,
            )
        self._options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            save_interval_steps=save_interval_steps,
            enable_async_checkpointing=async_save,
        )
        self._mgr = ocp.CheckpointManager(self.directory,
                                          options=self._options)
        #: read-only managers over earlier directories in the quarantine
        #: redirect chain (oldest first) — a mid-life redirect appends the
        #: displaced manager, and a manager CONSTRUCTED on an already-
        #: quarantined path wires the whole chain here, so restores always
        #: keep walking the verified pre-quarantine history
        self._fallbacks: List[Tuple[Any, str]] = []
        chain = _normalize_storage_path(self.requested_directory)
        while chain != self.directory:
            self._fallbacks.append(
                (ocp.CheckpointManager(chain, options=self._options), chain)
            )
            chain = redirect_directory(chain)
        self._async_save = bool(async_save)
        self._integrity = bool(integrity)
        # fleet view: the storage path this rank saves to rides the obs
        # summary, so the autopilot can name WHICH path to quarantine
        try:
            from .obs.export import note_ckpt_directory

            note_ckpt_directory(self.directory)
        except Exception:  # noqa: BLE001 - obs is never load-bearing here
            pass
        # layout sidecars whose orbax save is not yet known-durable:
        # written only once the async save finishes (wait()/close()/next
        # save), so a crash mid-save can't leave a sidecar pointing at a
        # checkpoint that never became readable
        self._pending_layouts: dict = {}
        # steps whose durable files the chaos ``ckpt.write`` hook has not
        # yet had a chance to corrupt (same durability gating as sidecars)
        self._uncorrupted_steps: list = []
        _LIVE_MANAGERS.add(self)

    def save(self, step: int, state: Any, metadata: Optional[dict] = None) -> bool:
        """Queue a save (async by default); returns False when skipped by the
        save-interval policy.

        ``metadata``: an optional JSON-serializable layout descriptor stored
        alongside the state (use ``trainer.checkpoint_layout_metadata()``) and
        validated on :meth:`restore` via ``expect_metadata=``.  Required in
        practice for the flat-resident ZeRO layout, whose on-disk shapes are
        bucket-plan- and world-size-dependent.

        The descriptor is a SIDECAR file (``<dir>/<step>.layout.json``), not
        an orbax item: orbax locks a manager to one item structure on first
        use, so a composite item would make mixing metadata and plain saves
        (or resuming an old checkpoint, then saving) an opaque error.  The
        state's on-disk format is identical with and without metadata.

        Async saves defer the sidecar write until the orbax save is
        DURABLE: orbax finalizes the previous async save before starting a
        new one, so the pending sidecar flushes at the next :meth:`save`,
        or in :meth:`wait`/:meth:`close` — never ahead of its checkpoint."""
        from .obs.spans import trace_span

        self._ensure_active_manager()
        with trace_span("ckpt/save", step=int(step),
                        async_save=self._async_save):
            saved = self._mgr.save(
                int(step), args=self._ocp.args.StandardSave(state)
            )
        if saved:
            # orbax finalizes the PREVIOUS async save inside a proceeding
            # _mgr.save() (its internal wait_until_finished runs after the
            # should_save early-return), so only a save that actually
            # proceeded proves the stashed sidecars point at durable
            # checkpoints — flushing on a skipped save would reopen the
            # crash window this deferral exists to close
            self._flush_pending_layouts()
            self._run_chaos_corruption()
        if saved:
            # integrity chain: the content digest rides the layout sidecar
            # (computed here, while the state is still live — donation in
            # the next train step may invalidate these buffers)
            meta = dict(metadata) if metadata is not None else {}
            if self._integrity and "integrity" not in meta:
                digest = compute_state_digest(state)
                if digest is not None:
                    meta["integrity"] = digest
            if meta:
                if self._async_save:
                    # stashed on EVERY process (written by process 0 only):
                    # a restore of a not-yet-flushed step must see the same
                    # metadata on all processes, or a layout mismatch would
                    # raise on process 0 alone and strand the others in the
                    # collective orbax restore
                    self._pending_layouts[int(step)] = meta
                else:
                    self._write_layout(int(step), meta)
            self._uncorrupted_steps.append(int(step))
            if not self._async_save:
                self._run_chaos_corruption()
        return saved

    def _ensure_active_manager(self) -> None:
        """Re-resolve the quarantine registry: when the active directory
        was quarantined since the last call (the autopilot's
        ``quarantine_storage`` action, in-process), flush what the old
        manager has queued, keep it around READ-ONLY (its verified history
        must stay restorable), and point saves at the redirect."""
        active = active_directory(self.requested_directory)
        if active == self.directory:
            return
        logger.warning(
            "checkpoint storage quarantine: redirecting saves %s -> %s "
            "(restores keep walking both)", self.directory, active,
        )
        try:
            self.wait()  # flush queued async saves + sidecars on old storage
        except Exception as e:  # noqa: BLE001 - rotting storage may throw
            logger.warning("flush of quarantined checkpoint dir failed: %s",
                           e)
        # APPEND, never overwrite: a redirect-of-redirect must keep the
        # original directory's verified history in the restore walk too
        self._fallbacks.append((self._mgr, self.directory))
        self.directory = active
        self._mgr = self._ocp.CheckpointManager(active,
                                                options=self._options)
        try:
            from .obs.export import note_ckpt_directory

            note_ckpt_directory(self.directory)
        except Exception:  # noqa: BLE001
            pass

    @contextlib.contextmanager
    def _using(self, mgr, directory: str):
        """Temporarily point this manager's restore path at another
        (manager, directory) pair — how the newest-first integrity walk
        reaches the pre-quarantine history without changing the
        ``restore_one(step)`` contract ``BaguaTrainer.restore_checkpoint``
        also relies on."""
        if mgr is self._mgr:
            yield
            return
        prev = (self._mgr, self.directory)
        self._mgr, self.directory = mgr, directory
        try:
            yield
        finally:
            self._mgr, self.directory = prev

    def _candidate_steps(self) -> List[Tuple[int, Any, str]]:
        """(step, manager, directory) restore candidates, newest-first;
        at equal steps the active directory shadows every fallback, and a
        newer link of the redirect chain shadows an older one."""
        out = {int(s): (self._mgr, self.directory)
               for s in self._mgr.all_steps()}
        for mgr, d in reversed(self._fallbacks):
            for s in mgr.all_steps():
                out.setdefault(int(s), (mgr, d))
        return [(s,) + out[s] for s in sorted(out, reverse=True)]

    def _run_chaos_corruption(self) -> None:
        """Apply any armed ``ckpt.write`` fault to steps whose orbax files
        are now durable (cheap no-op while nothing is armed).  Gated like
        the sidecar flush: corrupting a still-in-flight async save would
        race the writer instead of modeling post-publish rot."""
        from .faults import inject as _inject

        pending, self._uncorrupted_steps = self._uncorrupted_steps, []
        for step in pending:
            _inject.maybe_corrupt_checkpoint(self.directory, step)

    def _write_layout(self, step: int, metadata: dict) -> None:
        import json

        from .faults import inject as _inject

        if jax.process_index() != 0:
            return
        path = self._layout_path(step)
        # atomic publish (tmp + replace, the native_build.py:71 pattern): a
        # crash mid-write must leave either no sidecar or a complete one —
        # a torn sidecar would fail JSON parsing and discard the layout AND
        # integrity record of a perfectly good checkpoint
        tmp = path.parent / f".{path.name}.tmp"
        tmp.write_text(json.dumps(metadata))
        tmp.replace(path)
        _inject.maybe_corrupt_sidecar(path, step)  # chaos: ckpt.sidecar
        self._prune_layout_sidecars()

    def _flush_pending_layouts(self) -> None:
        """Write sidecars whose orbax save has since become durable.  Call
        only at points where queued async saves are known finished (after
        ``wait_until_finished``, or after the next proceeding ``save``).
        Entries are dropped only on a successful write — a transient
        shared-fs error keeps the stash so wait()/close()/the next save
        retry it."""
        for step in list(self._pending_layouts):
            try:
                self._write_layout(step, self._pending_layouts[step])
                del self._pending_layouts[step]
            except Exception as e:  # pragma: no cover - fs-backend dependent
                logger.warning("layout sidecar write failed for step %s "
                               "(kept for retry): %s", step, e)

    def _prune_layout_sidecars(self) -> None:
        """Best-effort: drop sidecars for steps orbax retention has pruned."""
        try:
            live = {int(s) for s in self._mgr.all_steps()}
            for p in self._layout_path(0).parent.glob("*.layout.json"):
                if int(p.name.split(".")[0]) not in live:
                    p.unlink()
        except Exception as e:  # pragma: no cover - fs-backend dependent
            logger.debug("layout sidecar pruning skipped: %s", e)

    def latest_step(self) -> Optional[int]:
        latest = self._mgr.latest_step()
        for mgr, _ in self._fallbacks:
            old = mgr.latest_step()
            if old is not None and (latest is None or int(old) > int(latest)):
                latest = old
        return latest

    def _layout_path(self, step: int):
        # epath (an orbax dependency) resolves gs://, s3:// etc. — a raw
        # os.path probe would silently skip layout validation on the remote
        # checkpoint directories orbax itself supports
        from etils import epath

        return epath.Path(self.directory) / f"{int(step)}.layout.json"

    def _read_layout(self, step: int) -> Optional[dict]:
        import json

        if int(step) in self._pending_layouts:
            # restoring a step whose async save hasn't been waited on yet:
            # the stashed metadata is authoritative
            return self._pending_layouts[int(step)]
        path = self._layout_path(step)
        if not path.exists():
            return None
        try:
            return json.loads(path.read_text())
        except (ValueError, UnicodeDecodeError) as e:
            # a torn/garbage sidecar makes the step unverifiable — surface
            # it as an integrity failure so a latest-step restore degrades
            # to the previous verified checkpoint instead of crashing here
            raise CheckpointIntegrityError(
                f"layout sidecar for step {step} is unreadable ({e}) — "
                "torn write or corruption"
            ) from e

    def read_layout(self, step: int) -> Optional[dict]:
        """The layout sidecar saved with ``step`` (None when the step was
        saved without ``metadata=``).  ``BaguaTrainer.restore_checkpoint``
        reads this to decide whether a flat-resident checkpoint needs a
        relayout or leaf conversion before it can feed the live trainer."""
        return self._read_layout(int(step))

    #: metadata keys that carry layout PAYLOAD (the full bucket layout
    #: descriptor) or side-channel records (the integrity digest), not
    #: compatibility constraints — never compared.  "ef" (the
    #: error-feedback residual's plan/world descriptor) is payload too:
    #: BaguaTrainer.restore_checkpoint adapts on it explicitly (relayout /
    #: zero-reset), so a residual difference must not fail the strict
    #: comparison that guards the rest of the state
    _LAYOUT_PAYLOAD_KEYS = ("flat_layout", "stacked", "integrity", "ef")

    @classmethod
    def _normalize_layout(cls, meta: Optional[dict]) -> Optional[dict]:
        if meta is None:
            return None
        m = {k: v for k, v in meta.items()
             if k not in cls._LAYOUT_PAYLOAD_KEYS}
        if m.get("layout") == "zero_flat":
            # pre-r6 sidecars named the (then ZeRO-only) flat-resident
            # layout "zero_flat"; it is the same on-disk layout
            m["layout"] = "flat"
        return m

    @classmethod
    def _check_layout(cls, saved: Optional[dict],
                      expected: Optional[dict]) -> None:
        # gossip state carries a leading rank axis, so ITS shapes depend on
        # the world size even under an identical plan — read before
        # normalization strips the payload keys
        stacked = bool((saved or {}).get("stacked")) or bool(
            (expected or {}).get("stacked")
        )
        saved = cls._normalize_layout(saved)
        expected = cls._normalize_layout(expected)
        if (
            saved is not None
            and expected is not None
            and saved.get("plan_signature")
            and saved.get("plan_signature") == expected.get("plan_signature")
        ):
            # the signature pins the CONCRETE layout (tensor order, dtypes,
            # alignment padding): the bucket_bytes KNOB may differ while
            # splitting identically (small models land in the same buckets
            # under many sizes), and — for UNSTACKED state — a world-size
            # change leaves alignment-1 flat buffers byte-identical (an
            # elastic resume of the default allreduce layout).
            # ``opt_shards`` — the key that pins sharded (ZeRO) chunk-state
            # stacking — is still compared, so topology changes that DO
            # reshape state keep raising.
            keys = ("bucket_bytes",) if stacked else ("bucket_bytes",
                                                      "world_size")
            for k in keys:
                saved.pop(k, None)
                expected.pop(k, None)
        if expected is None:
            if saved is not None and saved.get("plan_dependent"):
                logger.warning(
                    "checkpoint was saved in a plan-dependent layout (%s) but "
                    "no expect_metadata was passed — restore cannot verify the "
                    "bucket plan/world size still match", saved.get("layout"),
                )
            return
        if saved is None:
            logger.warning(
                "expect_metadata given but the checkpoint carries no layout "
                "metadata (saved before metadata support, or without "
                "metadata=) — cannot verify layout compatibility"
            )
            return
        missing = [k for k in expected if k not in saved]
        if missing:
            # keys added after the checkpoint was written (e.g. opt_shards,
            # r5): legacy sidecars must stay restorable at the same topology
            logger.warning(
                "checkpoint layout metadata predates field(s) %s — cannot "
                "verify those; restoring", ", ".join(sorted(missing)),
            )
        mismatched = {
            k: (saved[k], expected[k])
            for k in expected
            if k in saved and saved[k] != expected[k]
        }
        if not mismatched:
            return
        detail = ", ".join(
            f"{k}: checkpoint={a!r} vs current={b!r}"
            for k, (a, b) in sorted(mismatched.items())
        )
        plan_dependent = (
            saved.get("plan_dependent")
            or expected.get("plan_dependent")
            or "layout" in mismatched
        )
        if not plan_dependent:
            # leaf-layout state is genuinely plan/world-size independent:
            # an elastic restart at a different topology restores fine —
            # surface the difference, don't block it
            logger.info(
                "checkpoint layout metadata differs (%s) but both layouts "
                "are plan-independent; restoring", detail,
            )
            return
        raise ValueError(
            "checkpoint layout mismatch — this checkpoint cannot restore "
            f"directly into the current trainer ({detail}).  Flat-resident "
            "layouts are bucket-plan- and world-size-dependent: an "
            "elastic restart at a different process count or a "
            "bucket_bytes change produces different flat-buffer shapes.  "
            "Use trainer.restore_checkpoint(manager, state_like) — it "
            "re-lays-out or leaf-converts flat checkpoints across plans "
            "(sharded-opt-state ZeRO excepted) — or restart with the "
            "original world size/bucket_bytes, or re-save in the "
            "plan-independent leaf layout "
            "(trainer.unstack_params(state)) before changing the topology."
        )

    def restore(
        self,
        state_like: Any,
        step: Optional[int] = None,
        expect_metadata: Optional[dict] = None,
        mesh: Optional[Any] = None,
    ) -> Tuple[int, Any]:
        """Restore the given (or latest) step.  ``state_like`` provides the
        target pytree structure/shapes/shardings — pass a freshly-initialized
        ``TrainState``; its buffers are replaced by the checkpoint values.

        Shardings are rebuilt for the live mesh, not taken verbatim from
        ``state_like``: leaves produced by the jitted step carry a
        ``NamedSharding`` and keep it, but host-created leaves (the step
        counter, replicated params fed straight into ``trainer.init``) only
        carry a ``SingleDeviceSharding`` — restoring those as-is would commit
        them to one device and the sharded train step would then reject the
        state.  Any leaf without a ``NamedSharding`` is restored replicated
        over ``mesh`` (pass the live mesh explicitly — essential on an
        ELASTIC restart, where orbax's fallback of reading shardings from
        the checkpoint file would silently resurrect the OLD topology),
        falling back to the mesh harvested from sibling leaves, then to the
        global mesh.  Elastic restores verify the integrity digest too —
        the digest hashes global logical values, so it is topology-free.

        Integrity chain: with no explicit ``step``, restore walks steps
        NEWEST-FIRST and lands on the first one that verifies — an
        unreadable checkpoint, a torn/garbage sidecar, or a content-digest
        mismatch each disqualify a step with a loud warning and fall back
        to the previous one.  An EXPLICIT ``step`` never falls back: a
        verification failure raises :class:`CheckpointIntegrityError`.
        Layout mismatches (``expect_metadata``) are configuration errors,
        not corruption — they raise immediately in both modes.
        """
        self._ensure_active_manager()
        if step is not None:
            for s, mgr, d in self._candidate_steps():
                if s == int(step):
                    with self._using(mgr, d):
                        return self._restore_step(
                            int(step), state_like, expect_metadata, mesh
                        )
            return self._restore_step(
                int(step), state_like, expect_metadata, mesh
            )
        return self._restore_newest_verified(
            lambda s: self._restore_step(s, state_like, expect_metadata,
                                         mesh)
        )

    def _restore_newest_verified(self, restore_one):
        """The ONE integrity-fallback policy: walk steps newest-first and
        return the first result ``restore_one(step)`` produces without a
        :class:`CheckpointIntegrityError` — also used by
        ``BaguaTrainer.restore_checkpoint`` so the trainer's layout-aware
        restore cannot drift from the manager's."""
        from .faults import inject as _inject

        self._ensure_active_manager()
        candidates = self._candidate_steps()
        if not candidates:
            raise FileNotFoundError(f"no checkpoint under {self.directory}")
        last_err: Optional[Exception] = None
        for i, (s, mgr, d) in enumerate(candidates):
            try:
                with self._using(mgr, d):
                    result = restore_one(s)
            except CheckpointIntegrityError as e:
                from .telemetry import counters

                counters.incr("ckpt/integrity_failures")
                logger.error(
                    "checkpoint step %d FAILED verification (%s) — falling "
                    "back to the previous checkpoint", s, e,
                )
                last_err = e
                continue
            if i > 0:
                from .telemetry import counters

                counters.incr("ckpt/fallback_restores")
                logger.warning(
                    "checkpoint integrity: restored step %d after %d newer "
                    "checkpoint(s) failed verification — training resumes "
                    "from an OLDER state than the last save", s, i,
                )
                _inject.record_recovery("ckpt.write")
                _inject.record_recovery("ckpt.sidecar")
            return result
        raise CheckpointIntegrityError(
            f"no checkpoint under {self.directory} passed verification "
            f"({len(candidates)} candidate step(s) tried)"
        ) from last_err

    def _restore_step(
        self,
        step: int,
        state_like: Any,
        expect_metadata: Optional[dict],
        mesh: Optional[Any],
    ) -> Tuple[int, Any]:
        from .obs.spans import trace_span

        with trace_span("ckpt/restore", ckpt_step=int(step)):
            return self._restore_step_inner(step, state_like,
                                            expect_metadata, mesh)

    def _restore_step_inner(
        self,
        step: int,
        state_like: Any,
        expect_metadata: Optional[dict],
        mesh: Optional[Any],
    ) -> Tuple[int, Any]:
        from jax.sharding import NamedSharding, PartitionSpec

        if mesh is None:
            for leaf in jax.tree.leaves(state_like):
                s = getattr(leaf, "sharding", None)
                if isinstance(s, NamedSharding):
                    mesh = s.mesh
                    break
        if mesh is None:
            from .parallel.mesh import get_global_mesh_if_set

            mesh = get_global_mesh_if_set()
        replicated = (
            NamedSharding(mesh, PartitionSpec()) if mesh is not None else None
        )

        def abstract_leaf(x):
            if not hasattr(x, "shape"):
                return x
            s = getattr(x, "sharding", None)
            if not isinstance(s, NamedSharding):
                s = replicated
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)

        abstract = jax.tree.map(abstract_leaf, state_like)
        # validate the layout sidecar FIRST: the actionable mismatch error
        # must fire before orbax hits an opaque flat-shape mismatch.  A
        # corrupted sidecar raises CheckpointIntegrityError from the read
        # itself (fallback trigger); a layout MISMATCH is a configuration
        # error and propagates as ValueError (never a fallback)
        sidecar = self._read_layout(step)
        self._check_layout(sidecar, expect_metadata)
        try:
            restored = self._mgr.restore(
                int(step), args=self._ocp.args.StandardRestore(abstract)
            )
        except Exception as e:
            # orbax could not materialize the step (missing/truncated/
            # garbage files): corruption class, not configuration.
            # Deliberate tradeoff: a transient fs error or a stale
            # state_like structure is reclassified too — the walk then
            # tries older steps and the terminal error chains this one, so
            # the root cause stays visible; distinguishing "transient" from
            # "corrupt" generically across orbax/tensorstore backends is
            # not feasible here
            raise CheckpointIntegrityError(
                f"checkpoint step {step} is unreadable "
                f"({type(e).__name__}: {e})"
            ) from e
        self._verify_integrity(step, sidecar, restored)
        return int(step), restored

    def _verify_integrity(self, step: int, sidecar: Optional[dict],
                          restored: Any) -> None:
        """Compare the restored state's content digest against the one
        recorded at save time (no-op for checkpoints saved without one, or
        when the manager opted out of integrity)."""
        from .obs.spans import trace_span

        recorded = (sidecar or {}).get("integrity")
        if not self._integrity or not recorded:
            return
        with trace_span("ckpt/verify", ckpt_step=int(step)):
            actual = compute_state_digest(restored)
        if actual is None:  # multi-process partial view: cannot verify
            logger.info("checkpoint integrity: step %d not verifiable on "
                        "this process (non-addressable state)", step)
            return
        if actual["digest"] != recorded.get("digest"):
            raise CheckpointIntegrityError(
                f"checkpoint step {step} content digest mismatch "
                f"(saved {recorded.get('digest', '?')[:12]}…, restored "
                f"{actual['digest'][:12]}…) — on-disk corruption"
            )
        from .telemetry import counters

        counters.incr("ckpt/verified_restores")

    def try_restore(
        self,
        state_like: Any,
        expect_metadata: Optional[dict] = None,
        mesh: Optional[Any] = None,
    ) -> Tuple[Optional[int], Any]:
        """Restore latest if present, else return (None, state_like) —
        the launcher's resume-on-restart entry point."""
        if self.latest_step() is None:
            return None, state_like
        return self.restore(
            state_like, expect_metadata=expect_metadata, mesh=mesh
        )

    def wait(self) -> None:
        """Block until queued async saves are durable, then write their
        deferred layout sidecars."""
        self._mgr.wait_until_finished()
        self._flush_pending_layouts()
        self._run_chaos_corruption()

    def close(self) -> None:
        self._mgr.wait_until_finished()
        self._flush_pending_layouts()
        self._run_chaos_corruption()
        self._mgr.close()
        for mgr, _ in self._fallbacks:
            mgr.close()
