"""Fleet layer: placement model, pool supervision, failover routing.

Stub workers only (no jax in the children — worker_main's stub spec
never imports it), so the whole suite is subprocess-cheap and runs in
tier-1 under ``JAX_PLATFORMS=cpu``. The chaos fault-injection suite
lives in test_fleet_chaos.py.
"""

import socket
import threading
import time

import pytest

from cap_tpu import telemetry
from cap_tpu.fleet import (
    FleetClient,
    FleetError,
    FleetExhaustedError,
    WorkerPool,
)
from cap_tpu.fleet.worker_main import StubKeySet, make_keyset
from cap_tpu.parallel.place import (
    PlacementError,
    WorkerPlacement,
    assert_single_owner,
    single_owner_placement,
)


# ---------------------------------------------------------------------------
# placement model
# ---------------------------------------------------------------------------

def test_single_owner_placement_disjoint():
    ps = single_owner_placement(4, 8, platform="cpu")
    assert [p.device_ids for p in ps] == [(0, 1), (2, 3), (4, 5), (6, 7)]
    assert_single_owner(ps)           # no device has two owners
    env = ps[1].env()
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["CAP_FLEET_CPU_DEVICES"] == "2"
    assert env["CAP_FLEET_DEVICE_GROUP"] == "2,3"
    assert env["CAP_FLEET_WORKER_ID"] == "1"


@pytest.mark.parametrize("n_workers,chips,bounds", [
    (2, "0,1", "1,2,1"), (4, "0", "1,1,1")])
def test_single_owner_placement_tpu_env(n_workers, chips, bounds):
    """Each TPU worker is its own one-process slice over its chips,
    on its own libtpu port."""
    ps = single_owner_placement(n_workers, 4, platform="tpu")
    env = ps[0].env()
    assert env["JAX_PLATFORMS"] == "tpu"
    assert env["TPU_VISIBLE_CHIPS"] == chips
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == bounds
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    ports = {p.env()["TPU_PROCESS_PORT"] for p in ps}
    assert len(ports) == n_workers
    assert env["TPU_PROCESS_ADDRESSES"] == (
        "localhost:" + env["TPU_PROCESS_PORT"])


def test_pool_requires_platform_for_device_keysets():
    """A real engine spec never defaults silently to the CPU."""
    for spec in ("jwks:/nonexistent.json", "jwks-url:http://x/",
                 "oidc:https://x/"):
        with pytest.raises(FleetError, match="explicit platform"):
            WorkerPool(1, keyset_spec=spec)


def test_pool_reports_and_checks_worker_devices(tmp_path):
    """A real-engine worker names the platform and device JAX gave it;
    one that comes up on another platform than its placement says is
    refused, never served from."""
    import json

    from cap_tpu import testing
    from cap_tpu.jwt.jwk import serialize_public_key

    _, pub = testing.generate_keys("ES256")
    path = tmp_path / "jwks.json"
    path.write_text(json.dumps({"keys": [serialize_public_key(pub,
                                                               kid="k")]}))
    spec = f"jwks:{path}"
    with WorkerPool(1, keyset_spec=spec, platform="cpu") as good:
        assert good.wait_all_ready(120)
        assert good.device_report() == {0: ("cpu", "0", None)}
    rec = telemetry.enable()
    try:
        # placement says tpu, but the worker is pinned to the CPU
        bad = WorkerPool(1, keyset_spec=spec, platform="tpu",
                         max_restarts=0,
                         env_extra={"JAX_PLATFORMS": "cpu",
                                    "CAP_FLEET_CPU_DEVICES": "1"})
        try:
            assert not bad.wait_all_ready(120)
            assert bad.endpoints() == {}
            assert rec.counters().get("fleet.platform_mismatch", 0) >= 1
        finally:
            bad.close()
    finally:
        telemetry.disable()


def test_placement_rejects_overcommit():
    with pytest.raises(PlacementError, match="double-book"):
        single_owner_placement(3, 4, devices_per_worker=2)
    with pytest.raises(PlacementError, match="no device"):
        single_owner_placement(5, 4)
    with pytest.raises(PlacementError, match="at least one"):
        single_owner_placement(0, 4)


def test_assert_single_owner_catches_overlap():
    ps = [WorkerPlacement(0, (0, 1)), WorkerPlacement(1, (1, 2))]
    with pytest.raises(PlacementError, match="device 1 owned by both"):
        assert_single_owner(ps)


def test_make_keyset_specs():
    ks = make_keyset("stub:batch_ms=1.5,token_us=2")
    assert isinstance(ks, StubKeySet)
    assert ks._batch_s == pytest.approx(0.0015)
    with pytest.raises(ValueError, match="unknown stub option"):
        make_keyset("stub:bogus=1")
    with pytest.raises(ValueError, match="unknown keyset spec"):
        make_keyset("nope")


# ---------------------------------------------------------------------------
# pool + router (live subprocesses)
# ---------------------------------------------------------------------------

@pytest.fixture
def pool():
    p = WorkerPool(2, keyset_spec="stub", ping_interval=0.2,
                   max_restarts=10)
    assert p.wait_all_ready(30), "fleet did not come up"
    yield p
    p.close()


def test_pool_spawns_with_disjoint_placement(pool):
    pm = pool.placement_map()
    assert len(pm) == 2
    assert set(pm[0]).isdisjoint(pm[1])
    eps = pool.endpoints()
    assert len(eps) == 2
    assert eps[0] != eps[1]           # two sockets, two processes
    assert pool.pid(0) != pool.pid(1)


def test_router_roundtrip_and_balance(pool):
    cl = FleetClient(pool, fallback=StubKeySet())
    for i in range(6):
        res = cl.verify_batch([f"t{i}.ok", "bad-token"])
        assert res[0] == {"sub": f"t{i}.ok"}
        assert isinstance(res[1], Exception)
    stats = pool.stats()
    served = {wid: (s or {}).get("counters", {}).get("worker.requests", 0)
              for wid, s in stats.items()}
    # round-robin: both workers saw traffic
    assert all(n >= 1 for n in served.values()), served


def test_pool_stats_aggregation(pool):
    cl = FleetClient(pool)
    cl.verify_batch(["a.ok"])
    stats = pool.stats()
    assert sorted(stats) == [0, 1]
    for s in stats.values():
        assert s is not None
        assert {"pid", "queued_tokens", "inflight_batches",
                "counters"} <= set(s)


def test_pool_graceful_restart_new_process(pool):
    old_pid = pool.pid(0)
    pool.restart(0, graceful=True)
    assert pool.wait_all_ready(30)
    assert pool.state(0) == "ready"
    assert pool.pid(0) != old_pid
    cl = FleetClient(pool, fallback=StubKeySet())
    assert cl.verify_batch(["r.ok"])[0] == {"sub": "r.ok"}


def test_router_skips_dead_endpoint_and_opens_breaker(pool):
    # A port with nothing listening, plus the live fleet.
    dead = socket.socket()
    dead.bind(("127.0.0.1", 0))
    dead_addr = dead.getsockname()
    dead.close()                       # nothing listens here now
    eps = [dead_addr] + list(pool.endpoints().values())
    cl = FleetClient(eps, fallback=StubKeySet(), attempt_timeout=1.0,
                     breaker_threshold=1, breaker_reset_s=30.0)
    with telemetry.recording() as rec:
        for i in range(4):
            assert cl.verify_batch([f"d{i}.ok"])[0] == {"sub": f"d{i}.ok"}
    # first batch failed over; later batches skip the open breaker
    assert rec.counters().get("fleet.failovers", 0) >= 1
    states = cl.breaker_states()
    assert states[dead_addr]["open_for_s"] > 0


def test_router_exhausted_without_fallback_raises():
    dead = socket.socket()
    dead.bind(("127.0.0.1", 0))
    dead_addr = dead.getsockname()
    dead.close()
    cl = FleetClient([dead_addr], attempt_timeout=0.5,
                     total_deadline=2.0, max_rounds=2)
    # No fallback: the batch RAISES — transport failure must never be
    # translated into per-token rejections (that would be a wrong
    # verdict for a valid token).
    with pytest.raises(FleetExhaustedError):
        cl.verify_batch(["x.ok"])


def test_router_empty_batch_no_network():
    cl = FleetClient([("127.0.0.1", 1)])   # nothing listening
    assert cl.verify_batch([]) == []


def test_router_concurrent_batches(pool):
    cl = FleetClient(pool, fallback=StubKeySet())
    results = {}

    def one(i):
        results[i] = cl.verify_batch([f"c{i}-{j}.ok" for j in range(4)])

    threads = [threading.Thread(target=one, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert len(results) == 8
    for i in range(8):
        assert [r["sub"] for r in results[i]] == [
            f"c{i}-{j}.ok" for j in range(4)]


def test_respawned_worker_rejoins_routing(pool):
    cl = FleetClient(pool, fallback=StubKeySet())
    cl.verify_batch(["warm.ok"])
    pool.restart(1, graceful=False)
    assert pool.wait_all_ready(30)
    # endpoints() re-polled per round: the NEW port serves traffic
    with telemetry.recording():
        for i in range(4):
            assert cl.verify_batch([f"n{i}.ok"])[0] == {"sub": f"n{i}.ok"}
    stats = pool.stats()
    assert stats[1] is not None
    assert stats[1]["counters"].get("worker.requests", 0) >= 1
