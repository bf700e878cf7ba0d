"""Elastic membership subsystem tests — tier-1 fast (pure CPU, ephemeral
ports, no subprocesses): lease registry + epoch fencing, rendezvous rounds
(join window, exclusion, timeouts), launcher monitor teardown paths, and
worker-side resize hooks.  The full launcher protocol (kill a node, resize
down, rejoin, resize up) runs in tests/test_launcher.py (slow) and
scripts/elastic_drill.py."""

import threading
import time
from types import SimpleNamespace

import pytest

from bagua_tpu.contrib.utils.tcp_store import TCPStore, TCPStoreServer
from bagua_tpu.elastic.coordinator import (
    ElasticCoordinator,
    ExcludedFromRound,
    RendezvousTimeout,
    join_round,
    wait_for_next_epoch,
)
from bagua_tpu.elastic.membership import (
    STOP_FAIL,
    STOP_LEASE_EXPIRED,
    STOP_RESIZE,
    LeaseHeartbeat,
    LeaseTracker,
    MembershipClient,
    WorldSpec,
    publish_leave_intent,
)
from bagua_tpu.elastic.resize import ElasticContext, shard_bounds


@pytest.fixture()
def store_server():
    # python backend: the unit tests must not depend on a g++ build
    server = TCPStoreServer(backend="python")
    yield server
    server.stop()


def _client(server, node_id, max_nnodes=4) -> MembershipClient:
    host, port = server.address
    return MembershipClient(TCPStore(host, port), node_id, max_nnodes)


def _spec(server, epoch=0, ids=(0,), min_nnodes=1, max_nnodes=4) -> WorldSpec:
    return WorldSpec(
        epoch=epoch, ranks={i: r for r, i in enumerate(sorted(ids))},
        min_nnodes=min_nnodes, max_nnodes=max_nnodes,
        master_addr=server.address[0], master_port=12345,
    )


# ---------------------------------------------------------------------------
# membership: registry, leases, fencing
# ---------------------------------------------------------------------------


def test_world_spec_roundtrip_and_ranks():
    spec = WorldSpec(epoch=7, ranks={0: 0, 3: 1}, min_nnodes=1, max_nnodes=4,
                     master_addr="10.0.0.1", master_port=29400)
    back = WorldSpec.from_json(spec.to_json())
    assert back == spec
    assert back.nnodes == 2
    assert back.rank_of(3) == 1 and back.rank_of(2) is None


def test_join_registry_enumerates_ids(store_server):
    c0, c2 = _client(store_server, 0), _client(store_server, 2)
    c0.join(0)
    c2.join(0, info={"note": "standby"})
    assert c0.joined_ids(0) == [0, 2]
    assert c0.joined_ids(1) == []  # other epochs are separate keyspaces


def test_lease_expires_when_heartbeat_stops(store_server):
    c0 = _client(store_server, 0)
    host, port = store_server.address
    hb = LeaseHeartbeat(lambda: TCPStore(host, port), node_id=1, epoch=0,
                        interval_s=0.05, max_nnodes=4).start()
    tracker = LeaseTracker(c0, epoch=0, member_ids=[1], ttl_s=0.5)
    deadline = time.time() + 3.0
    while c0.read_beats(0, [1])[1] is None and time.time() < deadline:
        time.sleep(0.02)
    assert c0.read_beats(0, [1])[1] is not None, "no heartbeat arrived"
    assert tracker.poll() == []  # alive while beating
    hb.stop()
    deadline = time.time() + 5.0
    while tracker.poll() == [] and time.time() < deadline:
        time.sleep(0.05)
    assert tracker.poll() == [1], "lease did not expire after beats stopped"


def test_zombie_heartbeat_fenced_out_by_epoch_bump(store_server):
    """A heartbeater from attempt N stops itself once the coordinator opens
    attempt N+1 — the zombie cannot keep a stale lease alive."""
    c0 = _client(store_server, 0)
    host, port = store_server.address
    hb = LeaseHeartbeat(lambda: TCPStore(host, port), node_id=1, epoch=0,
                        interval_s=0.05, max_nnodes=4).start()
    deadline = time.time() + 3.0
    while c0.read_beats(0, [1])[1] is None and time.time() < deadline:
        time.sleep(0.02)
    c0.open_epoch(1)  # fence: epoch moved on
    hb._thread.join(timeout=3.0)
    assert not hb._thread.is_alive(), "zombie kept beating past the fence"
    assert c0.read_beats(1, [1])[1] is None  # never wrote into the new epoch
    hb.stop()


def test_leave_intent_via_env(store_server, monkeypatch):
    host, port = store_server.address
    monkeypatch.setenv("BAGUA_ELASTIC_STORE_ADDR", f"{host}:{port}")
    monkeypatch.setenv("BAGUA_ELASTIC_EPOCH", "2")
    monkeypatch.setenv("BAGUA_ELASTIC_NODE_ID", "3")
    assert publish_leave_intent("watchdog: step stuck for 30 s")
    c0 = _client(store_server, 0)
    assert c0.read_leave(2, 3) == "watchdog: step stuck for 30 s"
    assert c0.read_leave(2, 1) is None


def test_leave_intent_noop_outside_elastic(monkeypatch):
    monkeypatch.delenv("BAGUA_ELASTIC_STORE_ADDR", raising=False)
    assert publish_leave_intent("whatever") is False


# ---------------------------------------------------------------------------
# coordinator: rendezvous rounds
# ---------------------------------------------------------------------------


def _coordinator(server, min_nnodes=1, max_nnodes=4, **kw) -> ElasticCoordinator:
    c0 = _client(server, 0, max_nnodes)
    kw.setdefault("join_window_s", 0.5)
    kw.setdefault("timeout_s", 10.0)
    kw.setdefault("poll_s", 0.02)
    return ElasticCoordinator(c0, min_nnodes, max_nnodes,
                              server.address[0], 12345, **kw)


def test_round_admits_members_within_window(store_server):
    coord = _coordinator(store_server, min_nnodes=1, max_nnodes=4)
    c1 = _client(store_server, 1)
    c1.join(0)
    spec = coord.run_round(0)
    assert spec.ranks == {0: 0, 1: 1}
    assert spec.nnodes == 2
    # members read the same spec back
    assert join_round(c1, 0, timeout_s=2.0) == spec


def test_round_closes_early_when_full(store_server):
    coord = _coordinator(store_server, min_nnodes=1, max_nnodes=2,
                         join_window_s=30.0)
    c1 = _client(store_server, 1, max_nnodes=2)
    c1.join(0)
    t0 = time.monotonic()
    spec = coord.run_round(0)
    assert spec.nnodes == 2
    assert time.monotonic() - t0 < 5.0, "full round should not wait the window"


def test_round_closes_early_on_expected_survivors(store_server):
    """Crash restarts don't pay the join window: once every expected
    survivor re-registered the round closes."""
    coord = _coordinator(store_server, min_nnodes=1, max_nnodes=4,
                         join_window_s=30.0)
    c1 = _client(store_server, 1)
    c1.join(3)
    t0 = time.monotonic()
    spec = coord.run_round(3, expect={0, 1})
    assert sorted(spec.ranks) == [0, 1]
    assert time.monotonic() - t0 < 5.0


def test_node_missing_join_window_is_excluded_not_hung(store_server):
    coord = _coordinator(store_server, min_nnodes=1, max_nnodes=4,
                         join_window_s=0.3)
    spec = coord.run_round(0)  # closes with just the coordinator
    assert sorted(spec.ranks) == [0]
    late = _client(store_server, 2)
    t0 = time.monotonic()
    with pytest.raises(ExcludedFromRound) as e:
        join_round(late, 0, timeout_s=5.0)
    assert time.monotonic() - t0 < 2.0, "excluded node must not hang"
    assert "missed the join window" in str(e.value)
    # ...and the coordinator sees it as a standby asking for a scale-up
    assert coord.standby_ids(spec) == [2]


def test_expect_early_close_respects_min_floor(store_server):
    """Survivor-based early close must not under-shrink the job: with
    MIN=2 and only the coordinator surviving, the round may NOT assemble a
    1-node world just because every expected survivor is present."""
    coord = _coordinator(store_server, min_nnodes=2, max_nnodes=4,
                         join_window_s=0.2, timeout_s=0.8)
    with pytest.raises(RendezvousTimeout):
        coord.run_round(1, expect={0})  # expect satisfied, but below MIN


def test_rendezvous_timeout_below_min_nnodes(store_server):
    coord = _coordinator(store_server, min_nnodes=2, max_nnodes=4,
                         join_window_s=0.1, timeout_s=0.5)
    with pytest.raises(RendezvousTimeout) as e:
        coord.run_round(0)
    msg = str(e.value)
    assert "min_nnodes=2" in msg and "timed out" in msg


def test_member_join_timeout_when_no_world_published(store_server):
    c1 = _client(store_server, 1)
    with pytest.raises(RendezvousTimeout) as e:
        join_round(c1, 0, timeout_s=0.4, poll_s=0.05)
    assert "coordinator gone" in str(e.value)


def test_join_round_follows_epoch_fence(store_server):
    """A member rejoining with a stale epoch lands in the live round."""
    c0 = _client(store_server, 0)
    c0.open_epoch(5)
    c0.publish_world(_spec(store_server, epoch=5, ids=(0, 1)))
    c1 = _client(store_server, 1)
    spec = join_round(c1, 0, timeout_s=2.0, poll_s=0.02)
    assert spec.epoch == 5 and spec.rank_of(1) == 1
    assert c0.joined_ids(5) == [1]  # the re-registration followed the fence


def test_wait_for_next_epoch(store_server):
    c1 = _client(store_server, 1)

    def bump():
        time.sleep(0.2)
        _client(store_server, 0).open_epoch(4)

    t = threading.Thread(target=bump)
    t.start()
    assert wait_for_next_epoch(c1, 3, timeout_s=3.0, poll_s=0.02) == 4
    t.join()
    with pytest.raises(RendezvousTimeout):
        wait_for_next_epoch(c1, 9, timeout_s=0.3, poll_s=0.05)


# ---------------------------------------------------------------------------
# launcher integration: monitor teardown paths
# ---------------------------------------------------------------------------


class _FakeProc:
    """Popen stand-in: runs forever until killed."""

    def __init__(self, code=None):
        self._code = code
        self.signals = []

    def poll(self):
        return self._code

    def send_signal(self, sig):
        self.signals.append(sig)
        self._code = -int(sig)

    def wait(self, timeout=None):
        return self._code

    def kill(self):
        self._code = -9


def _elastic_args(server, **overrides):
    from bagua_tpu.distributed.run import parse_args

    host, port = server.address
    args = parse_args([
        "--nnodes", "1:4", "--master_addr", host,
        "--restart_coordinator_port", str(port),
        "--monitor_interval", "0.05",
        "--lease_ttl", str(overrides.pop("lease_ttl", 0.5)),
        "x.py",
    ])
    for k, v in overrides.items():
        setattr(args, k, v)
    return args


def test_monitor_elastic_remote_stop_tears_down(store_server):
    from bagua_tpu.distributed.run import _GangStop, monitor_elastic

    args = _elastic_args(store_server, node_rank=1)
    c1 = _client(store_server, 1)
    spec = _spec(store_server, ids=(0, 1))
    procs = [_FakeProc()]
    _client(store_server, 0).publish_stop(0, STOP_FAIL, 0, "worker exit 1")
    with pytest.raises(_GangStop) as e:
        monitor_elastic(args, procs, c1, spec, None, None)
    assert e.value.kind == STOP_FAIL and e.value.node == 0
    assert procs[0].poll() is not None, "local gang must be killed"


def test_monitor_elastic_lease_expiry_triggers_gang_teardown(store_server):
    """A lease that expires mid-attempt kills the local gang, publishes a
    lease_expired stop event (rejoin=False), and surfaces as _GangStop."""
    from bagua_tpu.distributed.run import _GangStop, monitor_elastic

    args = _elastic_args(store_server, node_rank=0, lease_ttl=0.3)
    c0 = _client(store_server, 0)
    spec = _spec(store_server, ids=(0, 1))
    coord = _coordinator(store_server)
    tracker = LeaseTracker(c0, 0, [1], ttl_s=0.3)  # node 1 never beats
    procs = [_FakeProc()]
    with pytest.raises(_GangStop) as e:
        monitor_elastic(args, procs, c0, spec, coord, tracker)
    assert e.value.kind == STOP_LEASE_EXPIRED
    assert e.value.node == 1 and e.value.rejoin is False
    assert procs[0].poll() is not None
    stop = c0.read_stop(0)
    assert stop["kind"] == STOP_LEASE_EXPIRED and stop["rejoin"] is False


def test_monitor_elastic_simultaneous_lease_expiries_exclude_all(store_server):
    """A rack loss expires several leases in one poll: EVERY dead node must
    be named non-rejoining, or the next round waits the full window for
    launchers that are permanently gone."""
    from bagua_tpu.distributed.run import _GangStop, monitor_elastic

    args = _elastic_args(store_server, node_rank=0, lease_ttl=0.3)
    c0 = _client(store_server, 0)
    spec = _spec(store_server, ids=(0, 1, 2))
    coord = _coordinator(store_server)
    tracker = LeaseTracker(c0, 0, [1, 2], ttl_s=0.3)  # neither ever beats
    with pytest.raises(_GangStop) as e:
        monitor_elastic(args, [_FakeProc()], c0, spec, coord, tracker)
    assert e.value.kind == STOP_LEASE_EXPIRED
    assert sorted(e.value.nodes) == [1, 2]
    assert sorted(c0.read_stop(0)["nodes"]) == [1, 2]


def test_monitor_elastic_standby_forces_resize(store_server):
    from bagua_tpu.distributed.run import _GangStop, monitor_elastic

    args = _elastic_args(store_server, node_rank=0, lease_ttl=30.0)
    c0 = _client(store_server, 0)
    spec = _spec(store_server, ids=(0,), max_nnodes=4)
    coord = _coordinator(store_server)
    tracker = LeaseTracker(c0, 0, [], ttl_s=30.0)
    _client(store_server, 3).join(0)  # standby registers mid-attempt
    procs = [_FakeProc()]
    with pytest.raises(_GangStop) as e:
        monitor_elastic(args, procs, c0, spec, coord, tracker)
    assert e.value.kind == STOP_RESIZE and e.value.standby == [3]
    assert c0.read_stop(0)["kind"] == STOP_RESIZE


def test_monitor_elastic_local_failure_publishes_stop(store_server):
    from bagua_tpu.distributed.run import _GangStop, monitor_elastic

    args = _elastic_args(store_server, node_rank=1)
    c1 = _client(store_server, 1)
    spec = _spec(store_server, ids=(0, 1))
    with pytest.raises(_GangStop) as e:
        monitor_elastic(args, [_FakeProc(code=7)], c1, spec, None, None)
    assert e.value.kind == STOP_FAIL and e.value.code == 7
    stop = c1.read_stop(0)
    assert stop["node"] == 1 and "exit 7" in stop["reason"]


def test_monitor_elastic_leave_intent_reclassifies_failure(store_server):
    """A worker that published a leave intent before dying (watchdog exit)
    is reported as a LEAVE, not a crash."""
    from bagua_tpu.distributed.run import _GangStop, monitor_elastic
    from bagua_tpu.elastic.membership import STOP_LEAVE

    args = _elastic_args(store_server, node_rank=1)
    c1 = _client(store_server, 1)
    c1.publish_leave(0, "watchdog: step stuck for 31 s")
    spec = _spec(store_server, ids=(0, 1))
    with pytest.raises(_GangStop) as e:
        monitor_elastic(args, [_FakeProc(code=3)], c1, spec, None, None)
    assert e.value.kind == STOP_LEAVE
    assert "watchdog" in c1.read_stop(0)["reason"]


def test_store_barrier_timeout_raises_clear_message(store_server):
    """Fixed-size restart barrier (non-elastic multi-node path): expiry
    must raise with the prefix, the timeout, and the expected node count —
    not hang or raise something opaque."""
    from bagua_tpu.distributed.run import _store_barrier

    host, port = store_server.address
    store = TCPStore(host, port)
    store.set("restart/ready/0/0", b"1")  # node 1 never arrives
    with pytest.raises(RuntimeError) as e:
        _store_barrier(store, 2, "restart/ready/0", timeout_s=0.3)
    msg = str(e.value)
    assert "restart/ready/0" in msg and "2 nodes" in msg and "timed out" in msg


def test_restart_store_retries_client_timeout(store_server, monkeypatch):
    """_RestartStore._retry must refresh the connection when the client
    times out — under either spelling: futures-style timeouts are the
    builtin TimeoutError (an OSError) on every interpreter the repo
    targets — and complete the op it retried."""
    import concurrent.futures

    import bagua_tpu.distributed.run as run_mod

    args = _elastic_args(store_server)
    rs = run_mod._RestartStore(args, connect_timeout_s=5.0)

    class _FlakyClient:
        def __init__(self, real):
            self._real = real
            self.calls = 0

        def get(self, key):
            self.calls += 1
            raise concurrent.futures.TimeoutError("simulated client timeout")

    assert concurrent.futures.TimeoutError is TimeoutError
    assert run_mod._STORE_RETRY_ERRORS == (OSError,)
    rs.set("elastic-retry-test", b"v")
    flaky = _FlakyClient(rs._client)
    rs._client = flaky
    # the flaky client times out; _retry must reconnect and
    # complete the SAME op on the fresh connection
    assert rs.get("elastic-retry-test") == b"v"
    assert flaky.calls == 1
    assert rs._client is not flaky, "connection must have been refreshed"


def test_connect_restart_store_failure_chains_cause_and_counts_attempts():
    """When the restart store never comes up, the raised error must carry
    the attempt count and chain the last socket error as __cause__ —
    'Connection refused' alone doesn't say the launcher retried at all."""
    from bagua_tpu.distributed.run import _connect_restart_store
    from bagua_tpu.podsim.util import reserve_port

    # a reserved-but-unserved port: connects fail fast with ECONNREFUSED
    dead_port = reserve_port()
    args = SimpleNamespace(master_addr="127.0.0.1",
                           restart_coordinator_port=dead_port)
    with pytest.raises(ConnectionError) as e:
        _connect_restart_store(args, timeout_s=0.5)
    msg = str(e.value)
    assert f"127.0.0.1:{dead_port}" in msg
    assert "attempt" in msg and "last error" in msg
    assert isinstance(e.value.__cause__, OSError)


# ---------------------------------------------------------------------------
# resize hooks
# ---------------------------------------------------------------------------


def test_shard_bounds_balanced_partition():
    for total, world in [(16, 1), (16, 2), (16, 8), (17, 4), (3, 4)]:
        bounds = [shard_bounds(total, r, world) for r in range(world)]
        assert bounds[0][0] == 0 and bounds[-1][1] == total
        sizes = [hi - lo for lo, hi in bounds]
        assert sum(sizes) == total
        assert max(sizes) - min(sizes) <= 1  # balanced
        for (_, a_hi), (b_lo, _) in zip(bounds, bounds[1:]):
            assert a_hi == b_lo  # contiguous, no overlap
    with pytest.raises(ValueError):
        shard_bounds(16, 4, 4)


def test_elastic_context_from_env(monkeypatch):
    for k in ("BAGUA_ELASTIC", "BAGUA_ELASTIC_EPOCH", "BAGUA_ELASTIC_NODE_ID",
              "BAGUA_ELASTIC_STORE_ADDR", "RANK", "WORLD_SIZE"):
        monkeypatch.delenv(k, raising=False)
    ctx = ElasticContext.from_env()
    assert not ctx.enabled and ctx.world_size == 1 and ctx.rank == 0
    monkeypatch.setenv("BAGUA_ELASTIC", "1")
    monkeypatch.setenv("BAGUA_ELASTIC_EPOCH", "4")
    monkeypatch.setenv("BAGUA_ELASTIC_NODE_ID", "2")
    monkeypatch.setenv("BAGUA_ELASTIC_MIN_NNODES", "1")
    monkeypatch.setenv("BAGUA_ELASTIC_MAX_NNODES", "4")
    monkeypatch.setenv("BAGUA_ELASTIC_STORE_ADDR", "10.0.0.1:2")
    monkeypatch.setenv("RANK", "1")
    monkeypatch.setenv("WORLD_SIZE", "2")
    ctx = ElasticContext.from_env()
    assert ctx.enabled and ctx.epoch == 4 and ctx.node_id == 2
    assert ctx.rank == 1 and ctx.world_size == 2 and ctx.max_nnodes == 4


def test_parse_accepts_elastic_range():
    from bagua_tpu.distributed.run import parse_args

    args = parse_args(["--nnodes", "1:4", "x.py"])
    assert args.elastic and (args.min_nnodes, args.max_nnodes) == (1, 4)
    assert args.max_restarts == 3  # elastic default
    for bad in ("4:2", "0:3", "a:b"):
        with pytest.raises(SystemExit):
            parse_args(["--nnodes", bad, "x.py"])
    with pytest.raises(SystemExit):  # node id outside the slot range
        parse_args(["--nnodes", "1:2", "--node_rank", "5", "x.py"])


def test_telemetry_counters():
    from bagua_tpu.telemetry import TelemetryCounters

    c = TelemetryCounters()
    assert c.get("elastic/resizes") == 0
    c.incr("elastic/resizes")
    c.incr("elastic/resizes", 2)
    c.set_gauge("elastic/world_nnodes", 3)
    assert c.get("elastic/resizes") == 3
    snap = c.snapshot()
    assert snap == {"elastic/resizes": 3, "elastic/world_nnodes": 3}
    c.reset()
    assert c.snapshot() == {}
