#!/usr/bin/env python
"""Chaos drill: the full fault matrix, in-process, on the cpu-sim mesh.

Unlike ``scripts/elastic_drill.py`` (which SIGKILLs real launcher process
groups — high fidelity, slow, non-repeatable), this drill arms the seeded
injection registry (:mod:`bagua_tpu.faults.inject`) inside ONE process on
the 8-device virtual CPU mesh and proves every defense end-to-end,
deterministically:

1. **store flake → retry**: an injected ``store.op`` failure on a live
   TCPStore connection recovers through ``_RestartStore``'s
   reconnect-and-retry.
2. **heartbeat loss → lease expiry (shrink signal)**: dropped beats starve
   the lease; the coordinator-side tracker expires it — the event that
   shrinks an elastic world.
3. **checkpoint corruption → fallback restore**: the newest checkpoint's
   data file is corrupted post-publish; restore degrades to the previous
   step and the content checksum verifies it.
4. **NaN gradient → skip-and-continue**: ``grad.poison`` fires inside the
   compiled train step; ``BAGUA_GRAD_GUARD=skip`` rewinds the step and the
   final loss is BIT-IDENTICAL to a clean run of one fewer step on
   ``golden.golden_task()`` (loss continuity).
5. **collective hang → watchdog abort + reset recovery**: the waiter's
   readback wedges; the monitor fires, raises the abort flag, and after
   ``reset_abort`` training resumes — twice, proving re-arming.
6. **10× straggler → degraded but alive**: a ``step.straggle`` peer dilates
   every synchronous step; throughput degrades by roughly the dilation
   factor yet every step completes with a finite loss — and the async
   family under the SAME fault retains most of its throughput (it gates on
   the straggler only at negotiated boundaries).
7. **async partition → bounded-staleness catch-up**: ``async.partition``
   drops every negotiation round; the applied-round counter stalls, the
   staleness tracker catches it at the cap, and the forced synchronous
   catch-up re-syncs the replicas bit-identically while training continues.
8. **chronic bad health → coordinator fence**: unhealthy worker beacons
   ride the lease heartbeat; the tracker names the node, the production
   fence path (``distributed.run.publish_health_fence``) publishes the
   ``health_fenced`` stop — and the coordinator-side fleet snapshot
   records every rank's obs summary.

Every fault-driven failure mode must also leave a **schema-valid
flight-recorder dump** (``bagua_tpu.obs.recorder``) naming the firing
fault point — asserted per drill and recorded in the matrix.

Writes ``CHAOS_DRILL.json`` (schema-gated in ``tests/test_drill_records.py``);
exit code 0 iff every fault was detected AND recovered.

Usage: python scripts/chaos_drill.py [--only DRILL ...]
       (--only runs a subset — the CI smoke trace — and does NOT rewrite
       CHAOS_DRILL.json unless --out is given)
"""

import argparse
import glob
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
# the hang drill uses its OWN HangWatchdog instance; the process-global
# watchdog's waiter runs the same collective.hang hook, and its readbacks
# of earlier drills' step losses would race the drill for the single
# armed fire — keep it out of the picture
os.environ["BAGUA_COMM_TIMEOUT_S"] = "off"
# flight-recorder dumps land here; every drill asserts its failure mode
# left a schema-valid artifact naming the firing fault point.  Always a
# FRESH directory — an inherited BAGUA_OBS_DUMP_DIR could hold stale
# flight_*.json from a previous run, and a stale artifact satisfying a
# drill's expectation would mask a broken recorder (the exact regression
# this gate exists to catch).  --dump-dir NAMES the fresh directory (the
# CI timeline stage assembles a fleet trace from these dumps afterwards)
# but must still be empty — it is parsed here, before jax imports, because
# the env var must be set before any bagua module reads it.
def _early_dump_dir():
    d = None
    for i, arg in enumerate(sys.argv):
        if arg == "--dump-dir" and i + 1 < len(sys.argv):
            d = sys.argv[i + 1]
        elif arg.startswith("--dump-dir="):  # argparse's = form too
            d = arg.split("=", 1)[1]
    if d:
        os.makedirs(d, exist_ok=True)
        if os.listdir(d):
            sys.exit(f"--dump-dir {d} is not empty — flight "
                     "expectations need a fresh directory")
        return d
    return tempfile.mkdtemp(prefix="chaos_obs_")


DUMP_DIR = os.environ["BAGUA_OBS_DUMP_DIR"] = _early_dump_dir()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))  # golden.py

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import optax  # noqa: E402

import bagua_tpu  # noqa: E402
from bagua_tpu import telemetry  # noqa: E402
from bagua_tpu.faults import inject  # noqa: E402
from bagua_tpu.faults.inject import FaultSpec, fault_scope  # noqa: E402

OUT = os.path.join(REPO, "CHAOS_DRILL.json")


def _counter_deltas(before):
    after = telemetry.counters.snapshot()
    keys = set(before) | set(after)
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in sorted(keys)
            if after.get(k, 0) != before.get(k, 0)}


#: drill name -> the goodput-ledger badput class its defense path must
#: FEED (ISSUE 10): a rewound step's wall lands in `rewind`, a forced
#: catch-up in `catchup_sync`, the fallback-restore walk in `checkpoint` —
#: asserted as a class-delta across the drill, so an efficiency regression
#: in a recovery path can't hide behind a passing recovery verdict.  One
#: mapping, shared with the test_drill_records artifact gate.
from bagua_tpu.obs.ledger import (  # noqa: E402
    DRILL_BADPUT_EXPECTATIONS as LEDGER_EXPECTATIONS,
)

#: drill name -> the fault point (or non-fault trigger) whose
#: flight-recorder dump the drill must leave behind
FLIGHT_EXPECTATIONS = {
    "store_flake_retry": {"fault_point": "store.op"},
    "heartbeat_loss_lease_expiry": {"fault_point": "elastic.heartbeat"},
    "checkpoint_corruption_fallback_restore": {"fault_point": "ckpt.write"},
    "nan_grad_skip_loss_continuity": {"fault_point": "grad.poison"},
    "collective_hang_watchdog_recovery": {"fault_point": "collective.hang",
                                          "trigger": "watchdog_abort"},
    "straggler_throughput_degrades": {"fault_point": "step.straggle",
                                      "trigger": "step_anomaly"},
    "async_partition_staleness_catchup": {"fault_point": "async.partition"},
    "health_fence_flight_record": {"trigger": "health_fence"},
    # fleet autopilot (docs/autopilot.md): every decided action leaves an
    # `autopilot_action` flight dump with its triggering evidence
    "autopilot_straggler_fence_resize": {"fault_point": "step.straggle",
                                         "trigger": "autopilot_action"},
    "autopilot_victim_retune_hint": {"fault_point": "step.straggle",
                                     "trigger": "autopilot_action"},
    "autopilot_slo_escalation_ladder": {"trigger": "autopilot_action"},
    "autopilot_ckpt_quarantine": {"fault_point": "ckpt.write",
                                  "trigger": "autopilot_action"},
    "autopilot_trend_rules": {"trigger": "autopilot_action"},
}


def _ledger_class_check(cls, before, after):
    """The class-delta verdict the drill matrix records: the drill's
    defense path must have added wall seconds to its badput class (and,
    for rewind, one reclassified window per grad-guard skip)."""
    before_classes = (before or {}).get("classes") or {}
    after_classes = (after or {}).get("classes") or {}
    delta = round(after_classes.get(cls, 0.0)
                  - before_classes.get(cls, 0.0), 6)
    verdict = {"badput_class": cls, "delta_s": delta,
               "surfaced": delta > 0}
    if cls == "rewind":
        verdict["rewind_windows_delta"] = (
            (after or {}).get("rewind_windows", 0)
            - (before or {}).get("rewind_windows", 0)
        )
    return verdict


def _flight_record_check(expect):
    """Scan the dump dir for a schema-valid flight record matching the
    expectation (fault point and/or trigger); returns the verdict dict the
    drill matrix records."""
    from bagua_tpu.obs import recorder as obs_recorder

    point = expect.get("fault_point")
    trigger = expect.get("trigger")
    found_point = found_trigger = False
    problems = []
    for path in sorted(glob.glob(os.path.join(DUMP_DIR, "flight_*.json"))):
        try:
            rec = json.load(open(path))
        except (OSError, ValueError) as e:
            problems.append(f"{os.path.basename(path)}: unreadable ({e})")
            continue
        bad = obs_recorder.validate_flight_record(rec)
        if bad:
            problems.append(f"{os.path.basename(path)}: {bad}")
            continue
        if point and (rec.get("fault_point") == point
                      or point in rec.get("fired_faults", {})):
            found_point = True
        if trigger and rec.get("trigger") == trigger:
            found_trigger = True
    # a match only counts when its containing dump schema-validated (the
    # loop skips invalid dumps before matching), so found == schema-valid
    ok = (found_point or not point) and (found_trigger or not trigger)
    verdict = {"schema_valid": ok, "found": ok}
    if point:
        verdict["fault_point"] = point
    if trigger:
        verdict["trigger"] = trigger
    if problems:
        verdict["problems"] = problems[:5]
    return verdict


def drill_store_flake():
    """store.op flake on a real TCPStore connection → retry recovers."""
    from bagua_tpu.contrib.utils.tcp_store import TCPStore, start_tcp_store
    from bagua_tpu.distributed import run as run_mod

    server = start_tcp_store("127.0.0.1", 0)
    try:
        host, port = server.address

        class _Args:
            master_addr = host
            restart_coordinator_port = port

        orig = run_mod._connect_restart_store
        run_mod._connect_restart_store = (
            lambda args, timeout_s=60.0: TCPStore(host, port,
                                                  timeout_s=timeout_s)
        )
        try:
            store = run_mod._RestartStore(args=_Args())
            store.set("drill/k", "v1")
            with fault_scope(FaultSpec("store.op")):
                got = store.get("drill/k")
                recovered = got == b"v1"
                fired = inject.get_plan().fired("store.op")
        finally:
            run_mod._connect_restart_store = orig
        return {"injected": True, "detected": fired, "recovered": recovered,
                "details": f"get returned {got!r} after injected flake + "
                           "reconnect-and-retry"}
    finally:
        server.stop()


def drill_heartbeat_loss():
    """Dropped heartbeats starve the lease → tracker expiry (the elastic
    shrink trigger), then beats resume and the next epoch re-admits."""
    from bagua_tpu.contrib.utils.store import InMemoryStore
    from bagua_tpu.elastic.membership import (
        LeaseHeartbeat,
        LeaseTracker,
        MembershipClient,
    )

    store = InMemoryStore()
    client = MembershipClient(store, node_id=0, max_nnodes=1)
    hb = LeaseHeartbeat(lambda: store, node_id=0, epoch=0,
                        interval_s=0.05).start()
    try:
        deadline = time.time() + 10
        while client.read_beats(0, [0])[0] is None and time.time() < deadline:
            time.sleep(0.05)
        tracker = LeaseTracker(client, epoch=0, member_ids=[0], ttl_s=0.4)
        healthy_before = tracker.poll() == []
        with fault_scope(FaultSpec("elastic.heartbeat", count=-1)):
            expired = []
            deadline = time.time() + 10
            while not expired and time.time() < deadline:
                time.sleep(0.1)
                expired = tracker.poll()
            detected = expired == [0]
            inject.record_recovery("elastic.heartbeat")
        # beats resume once the fault disarms: a fresh epoch's tracker sees
        # the node alive again (the rejoin half of shrink→regrow)
        seq0 = client.read_beats(0, [0])[0]
        deadline = time.time() + 10
        recovered = False
        while time.time() < deadline:
            time.sleep(0.1)
            seq = client.read_beats(0, [0])[0]
            if seq is not None and seq0 is not None and seq > seq0:
                recovered = True
                break
        return {"injected": True, "detected": detected,
                "recovered": bool(healthy_before and recovered),
                "details": "lease expired under beat starvation; beats "
                           "resumed after disarm"}
    finally:
        hb.stop()


def drill_checkpoint_corruption(tmp):
    """Corrupt the newest checkpoint post-publish → restore falls back to
    the previous step and the content digest verifies it."""
    import jax.numpy as jnp

    from bagua_tpu.checkpoint import BaguaCheckpointManager

    def state(v):
        return {"w": jnp.arange(4096, dtype=jnp.float32) * v,
                "step": jnp.int32(0)}

    mgr = BaguaCheckpointManager(os.path.join(tmp, "ckpt"),
                                 async_save=False, max_to_keep=5)
    mgr.save(1, state(1.0))
    mgr.save(2, state(2.0))
    with fault_scope(FaultSpec("ckpt.write", step=3)):
        mgr.save(3, state(3.0))
        before = telemetry.counters.snapshot()
        step, restored = mgr.try_restore(state(0.0))
        deltas = _counter_deltas(before)
    mgr.close()
    ok = (
        step == 2
        and np.array_equal(np.asarray(restored["w"]),
                           np.asarray(state(2.0)["w"]))
        and deltas.get("ckpt/verified_restores", 0) >= 1
    )
    return {"injected": True,
            "detected": deltas.get("ckpt/integrity_failures", 0) >= 1,
            "recovered": bool(ok),
            "details": f"latest (3) corrupted; restore landed on step "
                       f"{step} with verified checksum"}


def drill_nan_grad_skip():
    """grad.poison at step 3 under BAGUA_GRAD_GUARD=skip: the rewound run
    of n steps must be bit-identical to a clean run of n-1 steps on the
    golden task (same batch every step ⇒ skipping one update IS running
    one fewer), proving exact loss continuity."""
    import golden
    from bagua_tpu.algorithms import GradientAllReduceAlgorithm
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.parallel.mesh import build_mesh

    loss_fn, params, batch = golden.golden_task()
    mesh = build_mesh({"dp": 8})

    def run(n, guard="off", poison=None):
        import contextlib

        cm = (fault_scope(FaultSpec("grad.poison", step=poison))
              if poison is not None else contextlib.nullcontext())
        with cm:
            t = BaguaTrainer(loss_fn, optax.sgd(0.1),
                             GradientAllReduceAlgorithm(), mesh=mesh,
                             autotune=False, grad_guard=guard)
            s = t.init(params)
            b = t.shard_batch(batch)
            loss = None
            for _ in range(n):
                s, loss = t.train_step(s, b)
            if guard != "off":
                t.flush_grad_health()
            fired = (inject.get_plan().fired("grad.poison")
                     if poison is not None else False)
        return float(loss), jax.tree.leaves(t.unstack_params(s)), fired

    before = telemetry.counters.snapshot()
    l_clean, p_clean, _ = run(9)
    l_skip, p_skip, fired = run(10, guard="skip", poison=5)
    deltas = _counter_deltas(before)
    exact = all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(p_clean, p_skip))
    return {"injected": True,
            "detected": bool(fired
                             and deltas.get("grad_guard/skipped_steps",
                                            0) == 1),
            "recovered": bool(exact and np.isfinite(l_skip)),
            "details": f"poisoned 10-step run final loss {l_skip:.6f} == "
                       f"clean 9-step run {l_clean:.6f}; params "
                       f"bit-identical: {exact}"}


def drill_guard_on_goldens():
    """No faults + BAGUA_GRAD_GUARD=skip must reproduce the exact loss
    goldens for every deterministic family (flat and leaf layouts ride the
    same ``loss_goldens`` sweep) — the guard's selects pass healthy state
    through bitwise.  ``async`` is excluded: its final loss is
    host-timing-dependent even without the guard (see test_loss_goldens)."""
    import golden

    def goldens(guard):
        os.environ["BAGUA_GRAD_GUARD"] = guard
        try:
            return golden.loss_goldens()
        finally:
            os.environ.pop("BAGUA_GRAD_GUARD", None)

    off, on = goldens("off"), goldens("skip")
    families = sorted(k for k in off if k != "async")
    diffs = {k: (off[k], on[k]) for k in families if off[k] != on[k]}
    return {"injected": True,  # the guard itself is the intervention
            "detected": True,
            "recovered": not diffs,
            "details": (f"guard-on goldens equal for {len(families)} "
                        f"deterministic families: {families}" if not diffs
                        else f"goldens diverged under guard: {diffs}")}


def drill_collective_hang():
    """Wedged readback → watchdog fires + aborts → reset_abort resumes a
    live overlap+flat trainer; a second episode proves re-arming."""
    import jax.numpy as jnp

    from bagua_tpu.algorithms import GradientAllReduceAlgorithm
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.models.mlp import MLP
    from bagua_tpu.parallel.mesh import build_mesh
    from bagua_tpu.watchdog import HangWatchdog

    mesh = build_mesh({"dp": 8})
    model = MLP(features=(16, 8))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    y = jnp.zeros((16,), jnp.int32)
    params = model.init(jax.random.PRNGKey(1), x[:2])["params"]

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]).mean()

    t = BaguaTrainer(loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
                     mesh=mesh, autotune=False, accum_steps=2,
                     overlap="on", flat_resident="on")
    s = t.init(params)
    b = t.shard_batch({"x": x, "y": y})
    s, _ = t.train_step(s, b)

    wd = HangWatchdog(timeout_s=0.3, action="abort")
    episodes = []
    try:
        for episode in range(2):
            deadline = time.time() + 10
            while not wd._armed and time.time() < deadline:
                time.sleep(0.05)
            with fault_scope(FaultSpec("collective.hang", duration_s=1.5)):
                wd.fired.clear()
                wd.watch_result(np.zeros(()), f"wedged-step-{episode}")
                deadline = time.time() + 15
                while not bagua_tpu.is_aborted() and time.time() < deadline:
                    time.sleep(0.05)
                fired = wd.fired.is_set() and bagua_tpu.is_aborted()
                failed_fast = False
                try:
                    # rebind: if the abort flag was NOT up (drill failure),
                    # this dispatch consumes (donates) s and the verdict
                    # below must keep using the returned state
                    s, _ = t.train_step(s, b)
                except bagua_tpu.BaguaAborted:
                    failed_fast = True
                deadline = time.time() + 15
                while wd._active and time.time() < deadline:
                    time.sleep(0.05)
                # reset INSIDE the armed scope so the recovery is
                # attributed to the injected hang in the counters
                bagua_tpu.reset_abort()
            s, loss = t.train_step(s, b)
            episodes.append(fired and failed_fast
                            and bool(np.isfinite(float(loss))))
    finally:
        wd.stop()
        bagua_tpu.reset_abort()
    plan_fired = telemetry.counters.get("faults/collective.hang/fired") >= 2
    return {"injected": True, "detected": bool(all(episodes) and plan_fired),
            "recovered": bool(all(episodes) and len(episodes) == 2),
            "details": f"2 hang episodes: abort+fail-fast+resume each time "
                       f"({episodes})"}


def _golden_trainer(algo, **kw):
    import golden
    import optax
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.parallel.mesh import build_mesh

    loss_fn, params, batch = golden.golden_task()
    t = BaguaTrainer(loss_fn, optax.sgd(0.1), algo,
                     mesh=build_mesh({"dp": 8}), autotune=False, **kw)
    s = t.init(params)
    return t, s, t.shard_batch(batch)


def _anomaly_leg(straggle_rank, sim_rank, base_ms, factor, tmp):
    """One real trainer run for the straggler anomaly detector: clean
    baseline steps, then an armed ``step.straggle`` window, on the async
    family — its ``async/negotiate`` boundaries are both where a slow
    peer gates this rank AND the anchor spans the fleet timeline aligns
    on.  Returns the suspects flagged DURING the straggle window, the
    health-beacon path (the worker half of the fleet view), and writes
    this leg's span-ring slice to the dump dir as simulated rank
    ``sim_rank``'s ring dump (``spans_rank<r>.json``) for the timeline
    assembly."""
    from bagua_tpu.algorithms import AsyncModelAverageAlgorithm
    from bagua_tpu.elastic.membership import write_health_beacon
    from bagua_tpu.obs import export as obs_export
    from bagua_tpu.obs import spans as obs_spans

    obs_export.reset_local_summary()

    def _key(sp):
        return (sp.get("name"), sp.get("t0"), sp.get("t1"),
                sp.get("thread"))

    ring_before = {_key(sp) for sp in obs_spans.recorder.snapshot()}
    dropped_before = obs_spans.recorder.dropped
    algo = AsyncModelAverageAlgorithm(warmup_steps=0, period_steps=4)
    t, s, b = _golden_trainer(algo)
    for _ in range(10):
        s, _ = t.train_step(s, b)
    straggle_start = t._step_counter
    with fault_scope(FaultSpec("step.straggle", rank=straggle_rank,
                               count=-1, base_ms=base_ms, factor=factor)):
        for _ in range(6):
            s, _ = t.train_step(s, b)
    # one clean step so the LAST straggled window is observed too (the
    # detector inspects each window when the next step opens)
    s, _ = t.train_step(s, b)
    s = algo.barrier(t, s)
    suspects = [sp for sp in (t.anomaly_detector.suspects
                              if t.anomaly_detector else [])
                if sp["step"] >= straggle_start]
    beacon = os.path.join(tmp, f"straggler_beacon.r{sim_rank}")
    write_health_beacon(beacon)
    # this leg's ring slice, relabeled as the simulated rank: both legs
    # count steps from 1, so their async/negotiate boundary spans share
    # (name, step) anchor keys — the timeline aligns leg B's clock window
    # onto leg A's exactly the way a real fleet's blocking gather would
    leg_spans = [dict(sp, rank=sim_rank)
                 for sp in obs_spans.recorder.snapshot()
                 if _key(sp) not in ring_before]
    ring_dump = os.path.join(DUMP_DIR, f"spans_rank{sim_rank}.json")
    with open(ring_dump, "w") as f:
        json.dump({"rank": sim_rank, "spans": leg_spans,
                   # the leg's REAL drop delta: a rotated ring means
                   # leg_spans is a tail, and the timeline must say so
                   "spans_dropped":
                       obs_spans.recorder.dropped - dropped_before,
                   "simulated": True}, f, indent=1)
    return suspects, beacon


def drill_straggler_throughput(tmp):
    """A 10× peer straggler gates every synchronous step: throughput
    degrades by roughly the dilation yet every step completes — while the
    async family under the SAME armed fault keeps its steps ungated and
    pays only at negotiated boundaries.  The anomaly detector must additionally flag the slow
    window on BOTH sides of the fault — collective-dominant on the gated
    peer, dispatch-dominant on the straggler itself — and the
    coordinator-side fleet snapshot must name the straggling rank from
    the ``straggler_suspect`` phase breakdowns riding the beacons."""
    from bagua_tpu.algorithms import (
        AsyncModelAverageAlgorithm,
        GradientAllReduceAlgorithm,
    )

    base_ms, factor, steps = 10.0, 10.0, 12

    def timed_run(algo):
        t, s, b = _golden_trainer(algo)
        s, loss = t.train_step(s, b)  # compile outside the timer
        float(loss)
        t0 = time.time()
        n_finite = 0
        for _ in range(steps):
            s, loss = t.train_step(s, b)
            n_finite += bool(np.isfinite(float(loss)))
        dt = time.time() - t0
        if hasattr(algo, "barrier"):
            s = algo.barrier(t, s)
        return dt, n_finite

    before = telemetry.counters.snapshot()
    clean_dt, _ = timed_run(GradientAllReduceAlgorithm())
    with fault_scope(FaultSpec("step.straggle", rank=1, count=-1,
                               base_ms=base_ms, factor=factor)):
        sync_dt, sync_ok = timed_run(GradientAllReduceAlgorithm())
        async_dt, async_ok = timed_run(
            AsyncModelAverageAlgorithm(warmup_steps=0, period_steps=4)
        )
        deltas = _counter_deltas(before)
        stall = (factor - 1.0) * base_ms / 1000.0
        detected = (
            deltas.get("faults/step.straggle/fired", 0) >= steps
            and sync_dt >= clean_dt + steps * stall * 0.9  # dilation landed
        )
        # alive-under-degradation IS the recovery: every step completed
        # with a finite loss, and the async family dodged the per-step
        # gating.  Recorded INSIDE the scope — record_recovery is a no-op
        # once the plan is disarmed.
        recovered = (
            sync_ok == steps and async_ok == steps and async_dt < sync_dt
        )
        if detected and recovered:
            inject.record_recovery("step.straggle")

    # --- anomaly extension: the detector must flag the slow window on
    # both sides of the fault and the fleet snapshot must NAME the
    # straggling rank from the phase breakdowns ---
    anomaly_env = {"BAGUA_OBS_ANOMALY_WARMUP": "4",
                   "BAGUA_OBS_ANOMALY_WINDOW": "24"}
    saved = {k: os.environ.get(k) for k in anomaly_env}
    os.environ.update(anomaly_env)
    try:
        # this process as the gated PEER of straggling rank 1: the wait
        # files under `collective`
        victim_suspects, victim_beacon = _anomaly_leg(
            1, 0, base_ms, factor, tmp)
        # this process as the straggler ITSELF (spec names our rank): the
        # local slowness files under `dispatch`
        self_suspects, straggler_beacon = _anomaly_leg(
            0, 1, base_ms, factor, tmp)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    victim_ok = bool(victim_suspects) and \
        victim_suspects[-1]["dominant_phase"] == "collective"
    self_ok = bool(self_suspects) and \
        self_suspects[-1]["dominant_phase"] == "dispatch"

    # both legs ran in THIS process (env rank 0); relabel the second
    # beacon as simulated rank 1 — the identity is the only hand-made part
    # of the fleet path below (beacons -> merged heartbeat payload ->
    # tracker -> fleet snapshot -> straggler naming are all production)
    fleet_ok = False
    fleet_suspects = {}
    if victim_ok and self_ok:
        from bagua_tpu.elastic import membership as mb
        from bagua_tpu.obs import export as obs_export
        from bagua_tpu.obs.anomaly import fleet_straggler_suspects

        rec = json.load(open(straggler_beacon))
        rec["obs"]["rank"] = 1
        rec["obs"]["straggler_suspect"]["rank"] = 1
        with open(straggler_beacon, "w") as f:
            json.dump(rec, f)
        payload = mb.merged_health_source(
            [victim_beacon, straggler_beacon])()
        fleet_path = os.path.join(tmp, "straggler_fleet.json")
        obs_export.write_fleet_snapshot(fleet_path, 0, {0: payload})
        fleet = json.load(open(fleet_path))
        fleet_suspects = fleet_straggler_suspects(fleet)
        fleet_ok = (
            not obs_export.validate_fleet_snapshot(fleet)
            and [s["rank"] for s in fleet_suspects["stragglers"]] == [1]
            and 0 in [s["rank"] for s in fleet_suspects["victims"]]
        )

    # the fleet timeline over the two legs' ring dumps: a schema-valid,
    # CLOCK-ALIGNED multi-rank Perfetto trace whose anchors are the legs'
    # shared async/negotiate boundary steps
    timeline_verdict = {"assembled": False}
    try:
        from bagua_tpu.obs import timeline as obs_timeline

        recs = obs_timeline.load_rank_records(
            [os.path.join(DUMP_DIR, "spans_rank0.json"),
             os.path.join(DUMP_DIR, "spans_rank1.json")])
        trace = obs_timeline.assemble_timeline(recs)
        problems = obs_timeline.validate_timeline(trace)
        trace_path = os.path.join(tmp, "straggler_timeline.json")
        with open(trace_path, "w") as f:
            json.dump(trace, f)
        meta = trace["metadata"]
        timeline_verdict = {
            "assembled": True,
            "schema_valid": not problems,
            "problems": problems[:5],
            "ranks": sorted(meta["ranks"]),
            "aligned": meta["aligned"],
            "anchor_spans_rank1": meta["ranks"].get("1", {}).get(
                "anchor_spans", 0),
            "events": len(trace["traceEvents"]),
        }
    except Exception as e:  # noqa: BLE001 - verdict, not crash
        timeline_verdict["error"] = f"{type(e).__name__}: {e}"
    timeline_ok = (
        timeline_verdict.get("schema_valid") is True
        and timeline_verdict.get("aligned") is True
        and timeline_verdict.get("ranks") == ["0", "1"]
        and timeline_verdict.get("anchor_spans_rank1", 0) >= 2
    )

    return {"injected": True,
            "detected": bool(detected and victim_ok and self_ok),
            "recovered": bool(recovered and fleet_ok and timeline_ok),
            "timeline": timeline_verdict,
            "anomaly": {
                "victim_flagged": victim_ok,
                "victim_dominant_phase": (victim_suspects[-1]
                                          ["dominant_phase"]
                                          if victim_suspects else None),
                "straggler_flagged": self_ok,
                "straggler_dominant_phase": (self_suspects[-1]
                                             ["dominant_phase"]
                                             if self_suspects else None),
                "fleet_names_straggler_rank": ([s["rank"] for s in
                                                fleet_suspects.get(
                                                    "stragglers", [])]
                                               if fleet_suspects else []),
                "fleet_ok": fleet_ok,
            },
            "details": f"{steps} steps: clean {clean_dt:.2f}s, sync+straggle "
                       f"{sync_dt:.2f}s (all finite: {sync_ok == steps}), "
                       f"async+straggle {async_dt:.2f}s — async retained "
                       f"{sync_dt / async_dt:.1f}x sync throughput; anomaly "
                       f"detector flagged peer(collective)="
                       f"{victim_ok} self(dispatch)={self_ok}, fleet named "
                       f"rank 1: {fleet_ok}"}


def drill_async_partition_catchup():
    """Persistent ``async.partition`` drops: the applied-round counter
    stalls, the negotiated gather sees the lag hit ``max_staleness_rounds``
    and forces a synchronous catch-up average — replicas bit-identical at
    the sync point, training continues, telemetry records the round trip."""
    import jax

    from bagua_tpu.algorithms import AsyncModelAverageAlgorithm

    cap = 2
    algo = AsyncModelAverageAlgorithm(warmup_steps=2, period_steps=2,
                                      max_staleness_rounds=cap)
    t, s, b = _golden_trainer(algo)

    synced_rows_ok = []
    orig = algo._catchup_sync

    def spy(tr, state, watchdog, step, reason):
        out = orig(tr, state, watchdog, step, reason)
        rows = [np.asarray(x) for x in jax.tree.leaves(out.params)]
        synced_rows_ok.append(all(
            np.array_equal(a[0], a[r])
            for a in rows for r in range(1, a.shape[0])
        ))
        return out

    algo._catchup_sync = spy
    before = telemetry.counters.snapshot()
    lags = []
    with fault_scope(FaultSpec("async.partition", count=-1)):
        loss = None
        for _ in range(20):
            s, loss = t.train_step(s, b)
            lags.append(algo._rounds_launched - algo._rounds_applied)
    s = algo.barrier(t, s)
    deltas = _counter_deltas(before)
    detected = (
        deltas.get("faults/async.partition/fired", 0) >= 1
        and deltas.get("async/missed_boundaries", 0) >= 1
        and deltas.get("async/catchup_syncs", 0) >= 1
    )
    recovered = (
        deltas.get("faults/async.partition/recovered", 0) >= 1
        and max(lags) <= cap                 # the bounded-staleness invariant
        and bool(synced_rows_ok) and all(synced_rows_ok)
        and np.isfinite(float(loss))
        and deltas.get("async/rounds_launched", 0)
        >= deltas.get("async/catchup_syncs", 0)
    )
    return {"injected": True, "detected": bool(detected),
            "recovered": bool(recovered),
            "details": f"{deltas.get('async/rounds_dropped', 0)} rounds "
                       f"dropped, {deltas.get('async/catchup_syncs', 0)} "
                       f"catch-up sync(s), max lag {max(lags)} <= cap {cap}, "
                       f"replicas bit-identical at every sync point: "
                       f"{all(synced_rows_ok)}"}


def drill_health_fence(tmp):
    """Chronic bad worker health → the coordinator's fence, end-to-end
    through the PRODUCTION pieces: per-rank beacon files → the launcher's
    merged heartbeat payload → LeaseTracker harvesting →
    ``publish_health_fence`` (the exact function monitor_elastic calls),
    which publishes the ``health_fenced`` stop AND dumps the flight
    record; the coordinator-side fleet snapshot is written and
    schema-validated alongside."""
    from bagua_tpu.contrib.utils.store import InMemoryStore
    from bagua_tpu.distributed.run import publish_health_fence
    from bagua_tpu.elastic import membership as mb
    from bagua_tpu.obs import export as obs_export

    store = InMemoryStore()
    client = mb.MembershipClient(store, node_id=0, max_nnodes=2)
    # node 1's workers report non-finite-gradient steps via their beacons
    beacons = [os.path.join(tmp, f"fence_beacon.r{i}") for i in range(2)]
    with open(beacons[0], "w") as f:
        json.dump({"grad_unhealthy": 2,
                   "obs": {"rank": 2, "step": 41, "step_dt_p50": 0.01,
                           "step_dt_p90": 0.02}}, f)
    with open(beacons[1], "w") as f:
        json.dump({"async_missed": 1,
                   "obs": {"rank": 3, "step": 40, "step_dt_p50": 0.01,
                           "step_dt_p90": 0.03}}, f)
    hb = mb.LeaseHeartbeat(
        lambda: store, node_id=1, epoch=0, interval_s=0.05, max_nnodes=2,
        health_source=mb.merged_health_source(beacons),
    ).start()
    try:
        client.beat(0, 1)  # the coordinator's own (healthy) heartbeat
        tracker = mb.LeaseTracker(client, epoch=0, member_ids=[1],
                                  ttl_s=30.0, fence_unhealthy_after=3,
                                  observe_only_ids=[0])
        unhealthy = []
        deadline = time.time() + 10
        while not unhealthy and time.time() < deadline:
            time.sleep(0.1)
            tracker.poll()
            unhealthy = tracker.unhealthy_members()
        detected = unhealthy == [1]
        if detected:
            publish_health_fence(client, 0, tracker, unhealthy)
        stop = client.read_stop(0)
        fenced = bool(stop and stop["kind"] == mb.STOP_HEALTH
                      and stop["nodes"] == [1])
        fleet_path = os.path.join(tmp, "fleet_snapshot.json")
        obs_export.write_fleet_snapshot(
            fleet_path, 0, {nid: tracker.health_of(nid) for nid in (0, 1)})
        fleet = json.load(open(fleet_path))
        fleet_ok = (
            not obs_export.validate_fleet_snapshot(fleet)
            and fleet["ranks"]["1"]["obs"].get("2", {}).get("step") == 41
            and fleet["ranks"]["1"]["health"].get("grad_unhealthy") == 2
        )
    finally:
        hb.stop()
    return {"injected": True, "detected": bool(detected),
            "recovered": bool(fenced and fleet_ok),
            "fleet_snapshot_valid": bool(fleet_ok),
            "details": f"tracker named node(s) {unhealthy}; stop event "
                       f"{stop and stop['kind']}; fleet snapshot carries "
                       f"per-rank obs summaries (valid: {fleet_ok})"}


# ---- fleet autopilot drills (docs/autopilot.md) ---------------------------
#
# The policy matrix end-to-end, each rule injected -> detected -> DECIDED ->
# ACTUATED -> recovered: the autopilot consumes fleet snapshots built by the
# production merge (beacons -> merged_health_source -> build_fleet_record),
# decides through the pure core, and actuates through the pre-existing
# machinery only — the health-fence stop event, AutotuneClient perf hints
# with service-side consumption, the autotune recommendation path for the
# family switch (the trainer's switch is a re-jit + a queued state
# migration), and the checkpoint storage-quarantine registry.


def _autopilot_engine(mode="act", actuators=None, **cfg):
    from bagua_tpu.autopilot import AutopilotEngine, PolicyConfig

    base = dict(mode=mode, sustain=2, cooldown_s=0.0, budget=8,
                staleness_s=60.0, slo_goodput=0.0, straggler_ratio=3.0,
                suspect_ttl_s=600.0, ckpt_failures=3, switch_family="async")
    base.update(cfg)
    return AutopilotEngine(config=PolicyConfig(**base), actuators=actuators)


def _fleet_record_from_beacon(beacon_path, node_id=1):
    """The production coordinator merge over one worker beacon: node 0 is
    the (payload-less) coordinator, ``node_id`` the reporting worker."""
    from bagua_tpu.elastic import membership as mb
    from bagua_tpu.obs.export import build_fleet_record

    payload = mb.merged_health_source([beacon_path])()
    return build_fleet_record(0, {0: None, node_id: payload})


def _relabel_beacon_rank(beacon_path, rank):
    """Both anomaly legs run in THIS process (env rank 0); relabeling the
    beacon's rank is the only hand-made part of the fleet path (same
    convention as the straggler drill)."""
    rec = json.load(open(beacon_path))
    rec["obs"]["rank"] = rank
    if "straggler_suspect" in rec["obs"]:
        rec["obs"]["straggler_suspect"]["rank"] = rank
    with open(beacon_path, "w") as f:
        json.dump(rec, f)


def _actuate_autopilot_stop(action):
    """The monitor loop's fence/resize half on a live membership client:
    ``publish_autopilot_stop`` (the production publisher) converts the
    action into the ``health_fenced`` stop event the epoch/resize
    machinery rides; returns (stop_event, survivor_set) for a 2-node
    world."""
    from bagua_tpu.contrib.utils.store import InMemoryStore
    from bagua_tpu.distributed.run import publish_autopilot_stop
    from bagua_tpu.elastic import membership as mb

    client = mb.MembershipClient(InMemoryStore(), node_id=0, max_nnodes=2)
    nodes = [int(n) for n in action.target]
    publish_autopilot_stop(client, 0, action, nodes)
    stop = client.read_stop(0)
    survivors = {0, 1} - set(stop["nodes"]) if stop else {0, 1}
    return stop, survivors


def drill_autopilot_straggler_fence(tmp):
    """Chronic dispatch-dominant straggler -> autopilot fence + resize:
    a REAL self-straggled trainer run flags dispatch-dominant suspects
    (the production detector), the beacon rides the production merge into
    a fleet snapshot, the policy engine sustains the evidence over two
    snapshots and decides the fence, and the action actuates through the
    same ``health_fenced`` stop event lease expiry rides — the world
    resizes down to the survivors."""
    from bagua_tpu import telemetry as _t
    from bagua_tpu.elastic import membership as mb

    anomaly_env = {"BAGUA_OBS_ANOMALY_WARMUP": "4",
                   "BAGUA_OBS_ANOMALY_WINDOW": "24"}
    saved = {k: os.environ.get(k) for k in anomaly_env}
    os.environ.update(anomaly_env)
    before = telemetry.counters.snapshot()
    try:
        # self-straggle on the async family: local slowness files under
        # `dispatch` — the straggler's own signature
        suspects, beacon = _anomaly_leg(0, 1, 10.0, 10.0, tmp)
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else os.environ.update(
                {k: v})
    deltas = _counter_deltas(before)
    detected = (
        bool(suspects)
        and suspects[-1]["dominant_phase"] == "dispatch"
        and deltas.get("faults/step.straggle/fired", 0) >= 1
    )
    _relabel_beacon_rank(beacon, 1)

    engine = _autopilot_engine(sustain=2)
    actions = []
    for _ in range(2):
        time.sleep(0.02)  # distinct snapshot time_unix per poll
        actions = engine.observe_snapshot(_fleet_record_from_beacon(beacon))
    decided = (
        len(actions) == 1 and actions[0].kind == "fence"
        and actions[0].rule == "chronic_straggler"
        and actions[0].target == [1]
    )
    stop, survivors = (None, None)
    if decided:
        stop, survivors = _actuate_autopilot_stop(actions[0])
        engine.note_actuated(actions[0])
        if detected:
            inject.record_recovery("step.straggle")
    actuated = bool(
        stop and stop["kind"] == mb.STOP_HEALTH and stop["nodes"] == [1]
        and stop["rejoin"] is False
    )
    return {"injected": True,
            "detected": bool(detected and decided),
            "recovered": bool(actuated and survivors == {0}),
            "decided_actions": [a.kind for a in actions],
            "details": f"dispatch-dominant suspect (ratio "
                       f"{suspects[-1]['ratio'] if suspects else None}) "
                       f"sustained 2 snapshots -> fence node 1; stop "
                       f"{stop and stop['kind']} rejoin={stop and stop['rejoin']}; "
                       f"world resizes to {sorted(survivors or [])}"}


def drill_autopilot_victim_retune(tmp):
    """Collective-dominant victim -> retune hint CONSUMED: the gated-peer
    leg flags a collective-dominant suspect, the engine decides a retune
    hint and delivers it through ``AutotuneClient.report_metrics`` as the
    controller rank, and the live autotune service provably consumes it —
    the hinted sampling window is RE-MEASURED instead of scored."""
    import threading

    from bagua_tpu.autopilot import default_engine_actuators
    from bagua_tpu.service.autotune_service import (
        AutotuneService,
        make_server,
    )

    anomaly_env = {"BAGUA_OBS_ANOMALY_WARMUP": "4",
                   "BAGUA_OBS_ANOMALY_WINDOW": "24"}
    saved = {k: os.environ.get(k) for k in anomaly_env}
    os.environ.update(anomaly_env)
    before = telemetry.counters.snapshot()
    try:
        # peer-of-rank-1 straggle on the async family: the WAIT files
        # under `collective` — the victim's signature
        suspects, beacon = _anomaly_leg(1, 0, 10.0, 10.0, tmp)
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None) if v is None else os.environ.update(
                {k: v})
    deltas = _counter_deltas(before)
    detected = (
        bool(suspects)
        and suspects[-1]["dominant_phase"] == "collective"
        and deltas.get("faults/step.straggle/fired", 0) >= 1
    )

    service = AutotuneService(
        world_size=1, autotune_level=1, max_samples=10,
        sampling_confidence_time_s=0.0, warmup_time_s=0.0,
    )
    server = make_server(0, service)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        model = "autopilot_victim_drill"
        # open a sampling window: one scored sample, window restarts
        service.report_metrics({"model_name": model, "rank": 0,
                                "train_iter": 1, "hyperparameters": {},
                                "speed": 100.0})
        service.ask_hyperparameters({"model_name": model, "rank": 0,
                                     "train_iter": 1})
        task = service._task(model)
        samples_before = task.n_samples

        engine = _autopilot_engine(
            sustain=2,
            actuators=default_engine_actuators(
                model_name=model, autotune_addr=f"127.0.0.1:{port}"),
        )
        actions = []
        for _ in range(2):
            time.sleep(0.02)
            actions = engine.observe_snapshot(
                _fleet_record_from_beacon(beacon))
        decided = (
            len(actions) == 1 and actions[0].kind == "retune_hint"
            and actions[0].rule == "collective_victim"
        )
        with task.lock:
            delivered = task.perf_hints_total >= 1 and any(
                h.get("kind") == "autopilot_retune_hint"
                and h.get("reported_by") == -1 for h in task.perf_hints
            )
        # the service CONSUMES the hint: the next confidence-gated
        # decision re-measures the window instead of scoring it
        service.report_metrics({"model_name": model, "rank": 0,
                                "train_iter": 2, "hyperparameters": {},
                                "speed": 100.0})
        service.ask_hyperparameters({"model_name": model, "rank": 0,
                                     "train_iter": 2})
        consumed = (task.n_samples == samples_before
                    and task.sample_retried is True)
        if detected and decided and consumed:
            inject.record_recovery("step.straggle")
    finally:
        server.shutdown()
    return {"injected": True,
            "detected": bool(detected and decided),
            "recovered": bool(delivered and consumed),
            "decided_actions": [a.kind for a in actions],
            "details": f"collective-dominant victim sustained 2 snapshots "
                       f"-> retune hint; delivered as controller rank -1: "
                       f"{delivered}; service re-measured the hinted "
                       f"window (n_samples {samples_before} unchanged, "
                       f"retry armed): {consumed}"}


def drill_autopilot_slo_ladder(tmp):
    """Sustained goodput-SLO breach -> the escalation ladder walked IN
    ORDER (hint -> retune -> family switch -> resize), with the switch
    actuated END-TO-END: the engine pins the family through the autotune
    service's recommendation path, and a LIVE autotuned trainer applies it
    at its next check-in — a re-jit plus the queued replicated->stacked
    state migration, never a restart.  The terminal resize actuates
    through the same stop event the fence rides."""
    import threading

    import optax

    import golden
    from bagua_tpu.algorithms import GradientAllReduceAlgorithm
    from bagua_tpu.autopilot import LADDER, default_engine_actuators
    from bagua_tpu.communication import get_hyperparameters_service_client
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.obs.export import build_fleet_record
    from bagua_tpu.parallel.mesh import build_mesh
    from bagua_tpu.service.autotune_service import (
        AutotuneService,
        make_server,
    )

    model = "autopilot_ladder_drill"
    service = AutotuneService(
        world_size=1, autotune_level=1, max_samples=50,
        sampling_confidence_time_s=0.0, warmup_time_s=0.0,
    )
    server = make_server(0, service)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    env_save = {k: os.environ.get(k) for k in
                ("BAGUA_SERVICE_PORT", "MASTER_ADDR", "BAGUA_AUTOTUNE")}
    os.environ.update(BAGUA_SERVICE_PORT=str(port),
                      MASTER_ADDR="127.0.0.1", BAGUA_AUTOTUNE="1")
    get_hyperparameters_service_client.cache_clear()
    try:
        loss_fn, params, batch = golden.golden_task()
        trainer = BaguaTrainer(
            loss_fn, optax.sgd(0.1), GradientAllReduceAlgorithm(),
            mesh=build_mesh({"dp": 8}), model_name=model,
            flat_resident="off",
        )
        state = trainer.init(params)
        b = trainer.shard_batch(batch)
        for _ in range(100):  # past the first check-in (step 100)
            state, loss = trainer.train_step(state, b)

        # the injected degradation: a fleet whose worst rank sits far
        # below the goodput SLO, sustained — each poll re-merges a fresh
        # snapshot the way the coordinator writer does
        engine = _autopilot_engine(
            sustain=1, slo_goodput=0.5, switch_family="async",
            actuators=default_engine_actuators(
                model_name=model, autotune_addr=f"127.0.0.1:{port}"),
        )
        fired = []
        for _ in range(len(LADDER)):
            time.sleep(0.02)
            record = build_fleet_record(0, {0: None, 1: {"obs": {
                "1": {"rank": 1, "step": 100, "goodput_fraction": 0.12},
            }}})
            fired.extend(engine.observe_snapshot(record))
        ladder_order = [a.kind for a in fired]
        decided = ladder_order == list(LADDER)
        task = service._task(model)
        with task.lock:
            pinned = task.pinned_algorithm == "async"

        # the switch lands at the trainer's next check-in, then the queued
        # replication migration converts the live state before the
        # re-jitted stacked step consumes it
        for _ in range(110):
            state, loss = trainer.train_step(state, b)
        switched = type(trainer.algorithm).__name__ == \
            "AsyncModelAverageAlgorithm"
        stacked = jax.tree.leaves(state.params)[0].shape[0] == 8
        if switched and hasattr(trainer.algorithm, "barrier"):
            state = trainer.algorithm.barrier(trainer, state)
        finite = bool(np.isfinite(float(loss)))

        stop, survivors = (None, None)
        resize = [a for a in fired if a.kind == "resize"]
        if resize:
            stop, survivors = _actuate_autopilot_stop(resize[0])
            engine.note_actuated(resize[0])
        actuated_resize = bool(stop and stop["rejoin"] is False
                               and stop["nodes"] == [1])
    finally:
        for k, v in env_save.items():
            os.environ.pop(k, None) if v is None else os.environ.update(
                {k: v})
        get_hyperparameters_service_client.cache_clear()
        server.shutdown()
    return {"injected": True,
            "detected": bool(decided and pinned),
            "recovered": bool(switched and stacked and finite
                              and actuated_resize),
            "ladder_order": ladder_order,
            "details": f"ladder walked {ladder_order} (in order: {decided}); "
                       f"service pinned family async: {pinned}; trainer "
                       f"switched via re-jit+migration: {switched} "
                       f"(stacked: {stacked}, finite loss: {finite}); "
                       f"terminal resize stop published: {actuated_resize}"}


def drill_autopilot_off_noop():
    """BAGUA_AUTOPILOT=off (the default) changes NOTHING: the launcher's
    engine-construction gate stays closed (run_elastic builds no engine —
    the coordinator monitor path is the pre-autopilot one), no
    ``autopilot/*`` counter moves, and the compiled train step is
    jaxpr-IDENTICAL across off/observe/act — the autopilot is
    coordinator-side by construction and never reaches the traced
    program."""
    from bagua_tpu import env as _env
    from bagua_tpu.algorithms import GradientAllReduceAlgorithm

    saved = os.environ.get("BAGUA_AUTOPILOT")
    os.environ.pop("BAGUA_AUTOPILOT", None)
    before = telemetry.counters.snapshot()
    try:
        default_off = _env.get_autopilot_mode() == "off"
        # run_elastic's gate, verbatim: mode off -> no engine exists
        engine_gate_closed = not (_env.get_autopilot_mode() != "off")
        t, s, b = _golden_trainer(GradientAllReduceAlgorithm())
        jaxprs = {}
        for mode in ("off", "observe", "act"):
            os.environ["BAGUA_AUTOPILOT"] = mode
            jaxprs[mode] = str(t.trace_step(s, b))
    finally:
        os.environ.pop("BAGUA_AUTOPILOT", None)
        if saved is not None:
            os.environ["BAGUA_AUTOPILOT"] = saved
    deltas = _counter_deltas(before)
    no_autopilot_counters = not any(
        k.startswith("autopilot/") for k in deltas)
    pinned = jaxprs["off"] == jaxprs["observe"] == jaxprs["act"]
    return {"injected": True,  # the mode flip itself is the intervention
            "detected": bool(pinned),
            "recovered": bool(default_off and engine_gate_closed
                              and no_autopilot_counters),
            "jaxpr_identical": bool(pinned),
            "details": f"default mode off: {default_off}; engine gate "
                       f"closed: {engine_gate_closed}; step jaxpr "
                       f"identical across off/observe/act: {pinned}; no "
                       f"autopilot counters moved: {no_autopilot_counters}"}


def drill_autopilot_ckpt_quarantine(tmp):
    """Torn checkpoints xN -> storage quarantine: repeated armed
    ``ckpt.write`` corruption drives the REAL integrity counters up, the
    per-rank obs summary carries them (with the manager's storage path)
    through the production beacon merge, the engine decides
    ``quarantine_storage``, the actuator quarantines the path in the
    checkpoint registry — and the SAME live manager's next save redirects,
    after which restore lands on a verified step again."""
    import jax.numpy as jnp

    from bagua_tpu import checkpoint as ck
    from bagua_tpu.autopilot import default_engine_actuators
    from bagua_tpu.elastic import membership as mb
    from bagua_tpu.obs import export as obs_export
    from bagua_tpu.obs.export import build_fleet_record

    ck.clear_quarantine()
    obs_export.reset_local_summary()
    d = os.path.join(tmp, "autopilot_ckpt")

    def state(v):
        return {"w": jnp.arange(4096, dtype=jnp.float32) * v,
                "step": jnp.int32(0)}

    mgr = ck.BaguaCheckpointManager(d, async_save=False, max_to_keep=8)
    mgr.save(1, state(1.0))
    before = telemetry.counters.snapshot()
    with fault_scope(FaultSpec("ckpt.write", count=3)):
        for i, v in ((2, 2.0), (3, 3.0), (4, 4.0)):
            mgr.save(i, state(v))
        step, restored = mgr.try_restore(state(0.0))
        deltas = _counter_deltas(before)
        detected = (
            step == 1
            and deltas.get("ckpt/integrity_failures", 0) >= 3
            and deltas.get("ckpt/fallback_restores", 0) >= 1
        )

        # the evidence reaches the fleet snapshot through the production
        # path: obs summary (integrity counters + storage path) -> beacon
        # -> merged heartbeat payload -> coordinator merge
        obs_export.note_step(4, 0.01)
        beacon = os.path.join(tmp, "quarantine_beacon.r1")
        mb.write_health_beacon(beacon)
        record = build_fleet_record(
            0, {0: None, 1: mb.merged_health_source([beacon])()})

        engine = _autopilot_engine(
            sustain=1, ckpt_failures=3,
            actuators=default_engine_actuators(autotune_addr=None),
        )
        actions = engine.observe_snapshot(record)
        decided = (
            len(actions) == 1
            and actions[0].kind == "quarantine_storage"
            and str(actions[0].target) == ck._normalize_storage_path(d)
        )
        actuated = decided and ck.is_quarantined(d)

        # recovery: the live manager's next save redirects off the rotten
        # storage, and restore verifies again (no more fallback walking)
        recovered = False
        if actuated:
            mgr.save(5, state(5.0))
            redirected = mgr.directory == ck.redirect_directory(d)
            before2 = telemetry.counters.snapshot()
            step2, restored2 = mgr.try_restore(state(0.0))
            deltas2 = _counter_deltas(before2)
            recovered = (
                redirected and step2 == 5
                and np.array_equal(np.asarray(restored2["w"]),
                                   np.asarray(state(5.0)["w"]))
                and deltas2.get("ckpt/integrity_failures", 0) == 0
                and deltas2.get("ckpt/verified_restores", 0) >= 1
            )
            if detected and recovered:
                inject.record_recovery("ckpt.write")
    mgr.close()
    ck.clear_quarantine()
    return {"injected": True,
            "detected": bool(detected and decided),
            "recovered": bool(actuated and recovered),
            "decided_actions": [a.kind for a in actions],
            "details": f"3 torn saves -> restore fell back to step {step} "
                       f"with {deltas.get('ckpt/integrity_failures', 0)} "
                       f"integrity failures; engine quarantined {d}; next "
                       f"save redirected and restore verified step 5: "
                       f"{recovered}"}


def drill_autopilot_trend_rules(tmp):
    """Historian trend windows close the loop over DCN and HBM signals
    (ISSUE 14): a synthetic degradation stream — node 1's HBM headroom
    shrinking toward exhaustion, node 2's steps DCN-dominated, node 3 a
    flat control — flows through the LIVE telemetry historian (restart-
    store-persisted) into the act-mode engine.  The pre-OOM resize
    decides from the projected-exhaustion window and actuates through
    the production stop publisher (the world resizes BEFORE the OOM);
    the compression-escalation hint is delivered to a live autotune
    service as the controller rank and re-grants the re-measure; the
    flat control never fires; and a relaunched historian resumes its
    rings from the store."""
    import threading

    from bagua_tpu.autopilot import default_engine_actuators
    from bagua_tpu.contrib.utils.store import InMemoryStore
    from bagua_tpu.elastic import membership as mb
    from bagua_tpu.obs.historian import Historian
    from bagua_tpu.service.autotune_service import (
        AutotuneService,
        make_server,
    )

    service = AutotuneService(
        world_size=1, autotune_level=1, max_samples=10,
        sampling_confidence_time_s=0.0, warmup_time_s=0.0,
    )
    server = make_server(0, service)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    model = "autopilot_trend_drill"
    store = InMemoryStore()
    historian = Historian(capacity=64, window_s=600.0, store=store,
                          persist_every=1)
    engine = _autopilot_engine(
        sustain=2, cooldown_s=300.0,
        actuators=default_engine_actuators(
            model_name=model, autotune_addr=f"127.0.0.1:{port}"),
    )

    def rank_obs(rank, step, headroom, dcn):
        return {"rank": rank, "step": step, "goodput_fraction": 0.9,
                "step_dt_p50": 0.1, "hbm_headroom_bytes": headroom,
                "device_comm_dcn_s_per_step": dcn,
                "device_comm_ici_s_per_step": 0.01}

    def fleet_record(i):
        from bagua_tpu.obs.export import build_fleet_record

        record = build_fleet_record(0, {0: None})
        record["ranks"] = {
            # node 1: headroom collapsing — the polls are ~20 ms apart,
            # so the fitted slope is steep and exhaustion projects well
            # inside the 600 s horizon
            "1": {"health": {}, "obs": {"1": rank_obs(
                1, 100 + i, 4.0e9 - i * 3.0e8, 0.005)}},
            # node 2: 70% of the step wall is DCN device seconds
            "2": {"health": {}, "obs": {"2": rank_obs(
                2, 100 + i, 8.0e9, 0.07)}},
            # node 3: flat control — must never fire a rule
            "3": {"health": {}, "obs": {"3": rank_obs(
                3, 100 + i, 8.0e9, 0.005)}},
        }
        record["nnodes"] = 3
        return record

    all_actions = []
    try:
        task = service._task(model)
        task.sample_retried = True  # a spent re-measure the hint re-grants
        for i in range(8):
            time.sleep(0.02)  # distinct snapshot time_unix per poll
            record = historian.ingest(fleet_record(i))
            all_actions.extend(engine.observe_snapshot(record))
        kinds = [a.kind for a in all_actions]
        resize = [a for a in all_actions if a.kind == "resize"]
        compress = [a for a in all_actions if a.kind == "compress_dcn"]
        trends = record["ranks"]["1"]["obs"]["1"].get("trends") or {}
        detected = (
            trends.get("hbm_headroom_slope", 0) < 0
            and trends.get("hbm_headroom_eta_s") is not None
            and (record["ranks"]["2"]["obs"]["2"]["trends"]
                 ["dcn_comm_share"]) >= 0.5
        )
        decided = (
            kinds == ["resize", "compress_dcn"]
            and resize[0].rule == "hbm_exhaustion"
            and resize[0].target == [1]
            and compress[0].rule == "dcn_dominance"
            and compress[0].target == "bytegrad"
            and not any("3" == str(n) for a in all_actions
                        for n in (a.target if isinstance(a.target, list)
                                  else []))
        )
        stop, survivors = (None, None)
        delivered = regranted = False
        if decided:
            stop, survivors = _actuate_autopilot_stop(resize[0])
            engine.note_actuated(resize[0])
            with task.lock:
                delivered = any(
                    h.get("kind") == "autopilot_compress_dcn"
                    and h.get("family") == "bytegrad"
                    and h.get("reported_by") == -1
                    for h in task.perf_hints
                )
            regranted = task.sample_retried is False
        actuated = bool(
            stop and stop["kind"] == mb.STOP_HEALTH and stop["nodes"] == [1]
            and stop["rejoin"] is False
        )
        # a relaunched coordinator's historian resumes the trend windows
        resumed = Historian(capacity=64, window_s=600.0, store=store)
        persisted = (
            resumed.slope("1", "hbm_headroom_bytes") is not None
            and resumed.slope("1", "hbm_headroom_bytes") < 0
        )
    finally:
        server.shutdown()
    recovered = bool(actuated and survivors == {0} and delivered
                     and regranted and persisted)
    return {"injected": True,
            "detected": bool(detected and decided),
            "recovered": recovered,
            "decided_actions": kinds,
            "details": f"historian trends (headroom slope "
                       f"{trends.get('hbm_headroom_slope')} B/s, eta "
                       f"{trends.get('hbm_headroom_eta_s')}s) -> "
                       f"pre-OOM resize of node 1 (world -> "
                       f"{sorted(survivors or [])}); DCN share 0.7 -> "
                       f"bytegrad compression hint delivered={delivered} "
                       f"re-measure re-granted={regranted}; historian "
                       f"resumed from store={persisted}"}


def drill_autopilot_compress_codec(tmp):
    """The ``compress_dcn`` trend hint ACTUATES a real wire-byte reduction
    (ISSUE 15): delivered to a live autotune service as the controller
    rank, the hint sets the recommended ``compress_inter`` codec; a LIVE
    autotuned trainer on the 2-slice hierarchical mesh applies it at its
    next check-in — a re-jit whose cross-slice tier now rides the
    COMPRESSED ring (quantized u8 ppermute hops, fp32 accumulation) —
    and the traced step's DCN wire bytes provably drop >= 3x while
    training stays finite."""
    import threading

    import optax

    import golden
    from bagua_tpu.algorithms import GradientAllReduceAlgorithm
    from bagua_tpu.analysis.jaxpr_check import iter_collectives
    from bagua_tpu.autopilot import default_engine_actuators
    from bagua_tpu.autopilot.policy import Action
    from bagua_tpu.communication import get_hyperparameters_service_client
    from bagua_tpu.core.backend import BaguaTrainer
    from bagua_tpu.parallel.mesh import build_mesh
    from bagua_tpu.service.autotune_service import (
        AutotuneService,
        make_server,
    )

    def dcn_wire_bytes(trainer, state, batch):
        jaxpr = trainer.trace_step(state, batch)
        total = 0
        for c in iter_collectives(jaxpr):
            if "inter" in c.axes:
                total += c.nbytes
        return total

    model = "autopilot_compress_drill"
    # autotune_level=0: the recommendation is served verbatim (no BO
    # sampling that could flip is_hierarchical_reduce between the two
    # byte measurements) — controller hints still actuate through
    # report_metrics regardless of level
    service = AutotuneService(
        world_size=1, autotune_level=0, max_samples=50,
        sampling_confidence_time_s=0.0, warmup_time_s=0.0,
    )
    server = make_server(0, service)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    env_save = {k: os.environ.get(k) for k in
                ("BAGUA_SERVICE_PORT", "MASTER_ADDR", "BAGUA_AUTOTUNE")}
    os.environ.update(BAGUA_SERVICE_PORT=str(port),
                      MASTER_ADDR="127.0.0.1", BAGUA_AUTOTUNE="1")
    get_hyperparameters_service_client.cache_clear()
    try:
        loss_fn, params, batch = golden.golden_task()
        trainer = BaguaTrainer(
            loss_fn, optax.sgd(0.1),
            GradientAllReduceAlgorithm(hierarchical=True),
            mesh=build_mesh({"inter": 2, "intra": 4}), model_name=model,
            flat_resident="off",
        )
        state = trainer.init(params)
        b = trainer.shard_batch(batch)
        # step 1: registration applies the service's default
        # recommendation (is_hierarchical_reduce=False); pin the
        # hierarchical path in the recommendation so the check-in at step
        # 100 restores the two-level form this drill compresses
        state, loss = trainer.train_step(state, b)
        task = service._task(model)
        with task.lock:
            task.recommended.is_hierarchical_reduce = True
        for _ in range(105):  # past the step-100 check-in
            state, loss = trainer.train_step(state, b)
        assert trainer.algorithm.hierarchical, "check-in did not restore " \
            "the hierarchical recommendation"
        codec_before = trainer.compress_inter
        dcn_before = dcn_wire_bytes(trainer, state, b)

        # the hint, delivered exactly as the engine's actuator delivers a
        # decided compress_dcn action (controller rank -1)
        actuators = default_engine_actuators(
            model_name=model, autotune_addr=f"127.0.0.1:{port}")
        with task.lock:
            task.sample_retried = True  # a spent re-measure to re-grant
        delivered = actuators["compress_dcn"](Action(
            kind="compress_dcn", rule="dcn_dominance", target="bytegrad",
            reason="drill: sustained DCN dominance",
            evidence={"codec": "minmax_uint8"},
        ))
        with task.lock:
            service_actuated = (
                task.recommended.compress_inter == "minmax_uint8")
            regranted = task.sample_retried is False

        # the codec lands at the trainer's next check-in: a re-jit keyed
        # by the step cache, never a restart
        for _ in range(110):
            state, loss = trainer.train_step(state, b)
        flipped = trainer.compress_inter == "minmax_uint8"
        dcn_after = dcn_wire_bytes(trainer, state, b)
        ratio = dcn_before / max(dcn_after, 1)
        finite = bool(np.isfinite(float(loss)))
    finally:
        for k, v in env_save.items():
            os.environ.pop(k, None) if v is None else os.environ.update(
                {k: v})
        get_hyperparameters_service_client.cache_clear()
        server.shutdown()
    return {"injected": True,
            "detected": bool(delivered and service_actuated and regranted),
            "recovered": bool(flipped and ratio >= 3.0 and finite),
            "dcn_wire_bytes_before": int(dcn_before),
            "dcn_wire_bytes_after": int(dcn_after),
            "dcn_reduction_ratio": round(ratio, 2),
            "details": f"hint delivered={delivered}, service set "
                       f"compress_inter=minmax_uint8: {service_actuated} "
                       f"(re-measure re-granted={regranted}); live trainer "
                       f"codec {codec_before!r} -> "
                       f"{trainer.compress_inter!r} at check-in; traced "
                       f"DCN wire bytes {dcn_before} -> {dcn_after} "
                       f"({ratio:.2f}x, gate >= 3x); loss finite={finite}"}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", nargs="+", default=None, metavar="DRILL",
                    help="run only the named drill(s) — the CI smoke trace; "
                         "CHAOS_DRILL.json is NOT rewritten unless --out is "
                         "also given")
    ap.add_argument("--out", default=None,
                    help="output path (default: CHAOS_DRILL.json for the "
                         "full matrix, none for --only subsets)")
    ap.add_argument("--dump-dir", default=None,
                    help="flight-recorder dump directory (must be empty; "
                         "default: a fresh tempdir) — consumed before "
                         "argparse so the env var precedes jax imports")
    args = ap.parse_args(argv)
    if args.dump_dir and \
            os.path.abspath(args.dump_dir) != os.path.abspath(DUMP_DIR):
        # a programmatic main(argv=[... , "--dump-dir", d]) cannot be
        # honored: the env var was consumed from sys.argv at import time,
        # before jax — fail loudly instead of dumping into a tempdir the
        # caller never looks at
        ap.error(f"--dump-dir must appear on the PROCESS command line "
                 f"(dumps already bound to {DUMP_DIR} at import)")

    t0 = time.time()
    tmp = tempfile.mkdtemp(prefix="chaos_drill_")
    counters_before = telemetry.counters.snapshot()
    drills = {
        "store_flake_retry": drill_store_flake,
        "heartbeat_loss_lease_expiry": drill_heartbeat_loss,
        "checkpoint_corruption_fallback_restore":
            lambda: drill_checkpoint_corruption(tmp),
        "nan_grad_skip_loss_continuity": drill_nan_grad_skip,
        "grad_guard_on_goldens_unchanged": drill_guard_on_goldens,
        "collective_hang_watchdog_recovery": drill_collective_hang,
        "straggler_throughput_degrades":
            lambda: drill_straggler_throughput(tmp),
        "async_partition_staleness_catchup": drill_async_partition_catchup,
        "health_fence_flight_record": lambda: drill_health_fence(tmp),
        # the fleet autopilot's policy matrix (docs/autopilot.md):
        # injected -> detected -> DECIDED -> ACTUATED -> recovered
        "autopilot_straggler_fence_resize":
            lambda: drill_autopilot_straggler_fence(tmp),
        "autopilot_victim_retune_hint":
            lambda: drill_autopilot_victim_retune(tmp),
        "autopilot_slo_escalation_ladder":
            lambda: drill_autopilot_slo_ladder(tmp),
        "autopilot_ckpt_quarantine":
            lambda: drill_autopilot_ckpt_quarantine(tmp),
        "autopilot_trend_rules":
            lambda: drill_autopilot_trend_rules(tmp),
        "autopilot_compress_actuates_codec":
            lambda: drill_autopilot_compress_codec(tmp),
        "autopilot_off_noop": drill_autopilot_off_noop,
    }
    if args.only:
        unknown = [n for n in args.only if n not in drills]
        if unknown:
            ap.error(f"unknown drill(s) {unknown}; choose from "
                     f"{sorted(drills)}")
        drills = {n: drills[n] for n in args.only}
    # the goodput ledger observes every drill's defense path (the span
    # sink is normally installed by the first trainer; install explicitly
    # so span-only drills — the checkpoint walk — feed it too)
    from bagua_tpu.obs import ledger as obs_ledger

    obs_ledger.install()
    results = {}
    for name, fn in drills.items():
        print(f"=== {name} ===", flush=True)
        ledger_before = obs_ledger.ledger.report()
        try:
            results[name] = fn()
        except Exception as e:  # noqa: BLE001 - drill verdicts, not crashes
            results[name] = {"injected": True, "detected": False,
                             "recovered": False,
                             "details": f"drill crashed: "
                                        f"{type(e).__name__}: {e}"}
        expect = FLIGHT_EXPECTATIONS.get(name)
        if expect is not None:
            # the failure mode must have left its post-mortem artifact: a
            # schema-valid flight dump naming the firing fault point
            results[name]["flight_record"] = _flight_record_check(expect)
        ledger_cls = LEDGER_EXPECTATIONS.get(name)
        if ledger_cls is not None:
            # the drill's badput must have SURFACED in its ledger class
            results[name]["ledger"] = _ledger_class_check(
                ledger_cls, ledger_before, obs_ledger.ledger.report())
        print(f"    {results[name]}", flush=True)
        inject.clear_plan()
        bagua_tpu.reset_abort()

    passed = all(
        r["detected"] and r["recovered"]
        and r.get("flight_record", {}).get("schema_valid", True)
        and r.get("ledger", {}).get("surfaced", True)
        for r in results.values()
    )
    record = {
        "drill": "chaos",
        "pass": passed,
        "platform": "cpu-sim",
        "n_devices": len(jax.devices()),
        "elapsed_s": round(time.time() - t0, 1),
        "faults": results,
        "counters": _counter_deltas(counters_before),
    }
    out = args.out or (None if args.only else OUT)
    if out:
        with open(out, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {out} (pass={passed})")
    else:
        print(f"subset pass={passed} (no artifact written; use --out)")
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
