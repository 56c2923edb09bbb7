"""Object-plane fast path (PR 15): chunked multi-source pull over the
stream transport, locality-aware lease scheduling, arg prefetch, and
capacity governance on the pull ingest paths.

The raylets here get SEPARATE shm sessions (real multi-host has no shared
/dev/shm), so every cross-node read is a genuine transfer — same pattern
as test_native_transfer.py.
"""

import os
import shutil
import time
import uuid

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core.config import _config
from ray_tpu.core.scheduling_policy import (
    NodeView,
    locality_policy,
    locality_score,
)
from ray_tpu.core.resources import ResourceSet


# small chunks so a few-MB object exercises multi-chunk/striped/resumed
# transfer without tens of MB per test (daemons read these from the env,
# the driver process from the _config mutation below)
_CHUNK = 256 * 1024
_ENV = {
    "RAY_TPU_PULL_CHUNK_BYTES": str(_CHUNK),
    "RAY_TPU_PULL_STRIPE_MIN_BYTES": str(8 * _CHUNK),
}


def _start_split_cluster(specs):
    """GCS + one raylet per spec, each raylet in its OWN shm session."""
    from ray_tpu.core.cluster_backend import (
        ProcessGroup,
        _session_tmp_dir,
        start_gcs,
        start_raylet,
    )

    ray_tpu.shutdown()
    saved = {k: os.environ.get(k) for k in _ENV}
    os.environ.update(_ENV)
    sessions = []
    procs = ProcessGroup(_session_tmp_dir(f"s{uuid.uuid4().hex[:10]}"))
    gcs = start_gcs(procs)
    for spec in specs:
        session = f"s{uuid.uuid4().hex[:10]}"
        sessions.append(session)
        start_raylet(
            procs, gcs, session, spec["name"],
            num_cpus=spec.get("num_cpus", 1), num_tpus=0,
            resources=spec.get("resources"),
            object_store_memory_mb=spec.get("store_mb"),
        )
    return procs, gcs, sessions, saved


def _teardown_split_cluster(procs, sessions, saved):
    from ray_tpu.core.object_store.shm_store import session_dir

    ray_tpu.shutdown()
    procs.shutdown()
    for s in sessions:
        shutil.rmtree(session_dir(s), ignore_errors=True)
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


@pytest.fixture
def two_node_split():
    """node-a (driver) + node-b (producer, custom resource {"b": 1})."""
    procs, gcs, sessions, saved = _start_split_cluster([
        {"name": "node-a", "num_cpus": 1},
        {"name": "node-b", "num_cpus": 1, "resources": {"b": 1}},
    ])
    saved_chunk = (_config.pull_chunk_bytes, _config.pull_stripe_min_bytes)
    _config.pull_chunk_bytes = _CHUNK
    _config.pull_stripe_min_bytes = 8 * _CHUNK
    ray_tpu.init(address=gcs, _node_name="node-a")
    try:
        yield ray_tpu, gcs
    finally:
        (_config.pull_chunk_bytes, _config.pull_stripe_min_bytes) = saved_chunk
        _teardown_split_cluster(procs, sessions, saved)


def _core():
    from ray_tpu.api import _global_worker

    return _global_worker().backend.core


def _raylet_stats(core, addr=None):
    async def stats():
        if addr is None:
            return await core.raylet.call("scheduler_stats", timeout=30)
        conn = await core._conn_to(addr, kind="raylet")
        return await conn.call("scheduler_stats", timeout=30)

    return core.io.run(stats(), timeout=60)


def _raylet_addr_of(core, node_id):
    async def view():
        return await core.gcs.call("get_resource_view", timeout=30)

    nodes = core.io.run(view(), timeout=60)
    return nodes[node_id]["address"]


# --------------------------------------------------------------- unit level
def test_locality_score_and_policy():
    hints = [("aa", 8 * 1024 * 1024, "n1"), ("bb", 1024, "n2")]
    assert locality_score(hints, "n1") == 8 * 1024 * 1024
    assert locality_score(hints, "n3") == 0
    assert locality_score(None, "n1") == 0
    mk = lambda nid, used: NodeView(  # noqa: E731 - table-building lambda
        node_id=nid,
        total=ResourceSet({"CPU": 4}),
        available=ResourceSet({"CPU": 4 - used}),
    )
    demand = ResourceSet({"CPU": 1})
    # n1 holds the bytes: wins even while slightly busier
    pick = locality_policy(demand, [mk("n1", 1), mk("n2", 0)], hints, 0.5)
    assert pick == "n1"
    # weight 0 falls back to utilization packing
    pick = locality_policy(demand, [mk("n1", 1), mk("n2", 0)], hints, 0.0)
    assert pick == "n2"
    # a node that cannot fit the demand never wins on locality
    full = NodeView(node_id="n1", total=ResourceSet({"CPU": 1}),
                    available=ResourceSet({"CPU": 0}))
    pick = locality_policy(demand, [full, mk("n2", 0)], hints, 5.0)
    assert pick == "n2"


def test_transfer_timeout_scales():
    from ray_tpu.core.object_store.chunk_transfer import transfer_timeout

    base = _config.object_transfer_timeout_base_s
    assert transfer_timeout(None) == base
    assert transfer_timeout(0) == base
    one_gb = transfer_timeout(1 << 30)
    assert one_gb == pytest.approx(
        base + _config.object_transfer_timeout_per_gb_s
    )
    assert transfer_timeout(4 << 30) > one_gb


def test_chunk_split_is_disjoint_and_complete():
    from ray_tpu.core.object_store.pull_manager import _split

    idxs = list(range(11))
    parts = _split(idxs, 3)
    assert sum(parts, []) == idxs  # contiguous, ordered, complete
    assert len(parts) == 3
    assert _split([0], 4) == [[0]]


def test_capacity_reservation_prevents_overcommit():
    """Concurrent ingests must not all validate against the same free
    bytes: reserve() holds the promise until release_reservation."""
    from ray_tpu.core.object_store.shm_store import ObjectDirectory, ShmClient

    client = ShmClient(f"t{uuid.uuid4().hex[:8]}")
    try:
        d = ObjectDirectory(client, capacity_bytes=4 * 1024 * 1024)
        assert d.reserve(3 * 1024 * 1024)
        assert not d.reserve(3 * 1024 * 1024)  # would overcommit: refused
        assert not d.ensure_capacity(3 * 1024 * 1024)
        assert d.ensure_capacity(1024 * 1024)  # headroom left is fine
        d.release_reservation(3 * 1024 * 1024)
        assert d.reserve(3 * 1024 * 1024)
        d.release_reservation(3 * 1024 * 1024)
    finally:
        client.destroy()


# --------------------------------------------------------- transfer plane
def test_chunked_pull_lands_byte_identical(two_node_split):
    ray, gcs = two_node_split
    want = np.random.default_rng(7).integers(
        0, 255, size=3 * 1024 * 1024, dtype=np.uint8
    )

    @ray.remote(resources={"b": 1})
    def produce():
        import numpy as _np

        return _np.random.default_rng(7).integers(
            0, 255, size=3 * 1024 * 1024, dtype=_np.uint8
        )

    ref = produce.remote()
    got = ray.get(ref, timeout=120)
    np.testing.assert_array_equal(got, want)
    core = _core()
    stats = _raylet_stats(core)  # driver's raylet = the puller
    assert stats["pulls"]["chunked"] >= 1, stats
    assert stats["pulls"]["bytes_in"] >= want.nbytes
    # the pulled copy registered as a SECONDARY holder in the GCS
    # location table, so later pullers can fetch from this node

    async def holders():
        locs = {}
        for oid, loc in list(core.locations.items()):
            if loc.get("node_id") == "node-b":
                locs[oid.hex()] = await core.gcs.call(
                    "object_locations", oid_hex=oid.hex(), timeout=30
                )
        return locs

    registered = core.io.run(holders(), timeout=60)
    assert any(
        any(h["node_id"] == "node-a" for h in hs)
        for hs in registered.values()
    ), registered


def test_capacity_refusal_is_typed_and_get_still_works():
    """A pull into a full store must refuse TYPED (no silent shm
    overcommit); the caller's get() falls back to the direct fetch."""
    procs, gcs, sessions, saved = _start_split_cluster([
        {"name": "node-a", "num_cpus": 1, "store_mb": 2},
        {"name": "node-b", "num_cpus": 1, "resources": {"b": 1}},
    ])
    saved_chunk = _config.pull_chunk_bytes
    _config.pull_chunk_bytes = _CHUNK
    ray_tpu.init(address=gcs, _node_name="node-a")
    try:
        @ray_tpu.remote(resources={"b": 1})
        def produce():
            return np.full(4 * 1024 * 1024, 3, dtype=np.uint8)  # > 2 MB cap

        ref = produce.remote()
        got = ray_tpu.get(ref, timeout=120)  # falls back, still succeeds
        assert got.nbytes == 4 * 1024 * 1024 and got[0] == 3
        core = _core()
        stats = _raylet_stats(core)
        assert stats["pulls"]["capacity_refused"] >= 1, stats
        assert stats["pulls"]["chunked"] == 0, stats
    finally:
        _config.pull_chunk_bytes = saved_chunk
        _teardown_split_cluster(procs, sessions, saved)


def test_eviction_under_pull_pressure():
    """Sequential pulls past the store bound LRU-evict earlier pulls
    (spill-backed) instead of refusing, and evicted secondary copies are
    deregistered from the GCS location table."""
    procs, gcs, sessions, saved = _start_split_cluster([
        {"name": "node-a", "num_cpus": 1, "store_mb": 3},
        {"name": "node-b", "num_cpus": 1, "resources": {"b": 1}},
    ])
    saved_chunk = _config.pull_chunk_bytes
    _config.pull_chunk_bytes = _CHUNK
    ray_tpu.init(address=gcs, _node_name="node-a")
    try:
        @ray_tpu.remote(resources={"b": 1})
        def produce(fill):
            return np.full(1024 * 1024, fill, dtype=np.uint8)

        refs = [produce.remote(i) for i in range(5)]
        for i, ref in enumerate(refs):
            got = ray_tpu.get(ref, timeout=120)
            assert got[0] == i
        core = _core()

        async def store_stats():
            return await core.raylet.call("object_store_stats", timeout=30)

        st = core.io.run(store_stats(), timeout=60)
        assert st["num_evicted"] >= 1, st
        assert st["used_bytes"] <= st["capacity_bytes"], st
    finally:
        _config.pull_chunk_bytes = saved_chunk
        _teardown_split_cluster(procs, sessions, saved)


def test_chaos_sever_resumes_from_other_source():
    """Chaos point object.pull: sever a chunked pull mid-stream; the pull
    manager must resume exactly the missing chunks against ANOTHER holder
    and seal byte-identical content."""
    from ray_tpu.testing import chaos

    procs, gcs, sessions, saved = _start_split_cluster([
        {"name": "node-a", "num_cpus": 1},
        {"name": "node-b", "num_cpus": 1, "resources": {"b": 1}},
        {"name": "node-c", "num_cpus": 1, "resources": {"c": 1}},
    ])
    saved_chunk = _config.pull_chunk_bytes
    _config.pull_chunk_bytes = _CHUNK
    ray_tpu.init(address=gcs, _node_name="node-a")
    try:
        want = np.random.default_rng(11).integers(
            0, 255, size=6 * _CHUNK, dtype=np.uint8
        )

        @ray_tpu.remote(resources={"b": 1})
        def produce():
            import numpy as _np

            return _np.random.default_rng(11).integers(
                0, 255, size=6 * 256 * 1024, dtype=_np.uint8
            )

        ref = produce.remote()

        # seed a SECONDARY copy on node-c (a consumer there pulls it in)
        @ray_tpu.remote(resources={"c": 1})
        def checksum(x):
            return int(x.sum())

        assert ray_tpu.get(checksum.remote(ref), timeout=120) == int(want.sum())
        core = _core()
        c_addr = _raylet_addr_of(core, "node-c")
        assert _raylet_stats(core, c_addr)["pulls"]["chunked"] >= 1

        # now sever the NEXT chunk stream after 2 chunks, wherever it is
        # served from; activate() pushes the plan to the live daemons
        plan = chaos.plan(seed=5).sever_pull(after_chunks=2)
        assert chaos.activate(plan) >= 3  # gcs + raylets
        try:
            got = ray_tpu.get(ref, timeout=120)  # driver pulls to node-a
        finally:
            chaos.deactivate()
        np.testing.assert_array_equal(got, want)
        stats = _raylet_stats(core)  # node-a = the puller
        assert stats["pulls"]["chunked"] >= 1, stats
        assert stats["pulls"]["resumes"] >= 1, stats
        events = [e for e in plan.events() if e["point"] == "object.pull"]
        assert events, "chaos sever never fired"
        # resume crossed to the OTHER holder: both b and c served chunks
        b_addr = _raylet_addr_of(core, "node-b")
        served = (
            _raylet_stats(core, b_addr)["pushes_served"],
            _raylet_stats(core, c_addr)["pushes_served"],
        )
        assert min(served) >= 1, served
    finally:
        _config.pull_chunk_bytes = saved_chunk
        _teardown_split_cluster(procs, sessions, saved)


# ---------------------------------------------------------------- locality
def test_locality_lease_lands_on_arg_holding_node(two_node_split):
    ray, gcs = two_node_split
    core = _core()

    @ray.remote(resources={"b": 1})
    def produce():
        return np.zeros(6 * _CHUNK, dtype=np.uint8)

    ref = produce.remote()
    ray_tpu.wait([ref], timeout=60)
    # let produce's cached lease TTL out and the resource gossip refresh:
    # poll node-a's OWN cluster view (what its locality decision reads)
    # until it sees node-b's CPU free again
    deadline = time.monotonic() + 25
    while time.monotonic() < deadline:
        view = _raylet_stats(core)["view"]
        if view.get("node-b", {}).get("CPU", 0) >= 1:
            break
        time.sleep(0.25)
    else:
        pytest.fail(f"node-b never showed free CPU in node-a's view: {view}")

    @ray.remote
    def consume(x):
        return (os.environ.get("RAY_TPU_NODE_ID"), int(x.nbytes))

    node, nbytes = ray.get(consume.remote(ref), timeout=120)
    assert node == "node-b", node
    assert nbytes == 6 * _CHUNK
    # the lease landed next to the bytes: counter-asserted hit on node-b,
    # and ZERO transfer anywhere for that task
    b_addr = _raylet_addr_of(core, "node-b")
    b_stats = _raylet_stats(core, b_addr)
    assert b_stats["dispatch"].get("locality_hits", 0) >= 1, b_stats
    assert b_stats["pulls"]["pulls"] == 0, b_stats
    a_stats = _raylet_stats(core)
    assert a_stats["pulls"]["bytes_in"] == 0, a_stats
    assert a_stats["dispatch"].get("locality_spillbacks", 0) >= 1, a_stats


def test_arg_prefetch_kicks_on_queued_lease(two_node_split):
    """A hinted lease request starts pulling its REMOTE args the moment it
    queues on the raylet — before any worker decodes them. The prefetch
    counter on the driver's raylet proves the overlap; the dedup in the
    pull manager makes the worker's own arg pull (if any) free."""
    ray, gcs = two_node_split
    core = _core()

    @ray.remote(resources={"b": 1})
    def produce():
        return np.full(4 * 256 * 1024, 9, dtype=np.uint8)

    ref = produce.remote()
    ray_tpu.wait([ref], timeout=60)
    time.sleep(1.2)  # node-a's cluster view learns node-b's session

    # occupy node-b's only CPU: locality CANNOT move the consumer next to
    # the bytes, so node-a keeps the lease and must prefetch the arg
    @ray.remote(resources={"b": 1})
    def blocker():
        time.sleep(6.0)
        return True

    blocked = blocker.remote()
    # wait until node-a's OWN view shows node-b's CPU taken — a stale view
    # would let the locality check spill the consumer to node-b instead
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        view = _raylet_stats(core)["view"]
        # zero entries are dropped from the available dict: "registered
        # and no CPU key" IS the blocker holding node-b's only CPU
        if "node-b" in view and view["node-b"].get("CPU", 0) == 0:
            break
        time.sleep(0.2)
    else:
        pytest.fail("node-a never saw the blocker occupy node-b")

    @ray.remote
    def consume(x):
        return int(x[0])

    assert ray.get(consume.remote(ref), timeout=120) == 9
    assert ray.get(blocked, timeout=60) is True
    stats = _raylet_stats(core)
    assert stats["dispatch"].get("prefetches", 0) >= 1, stats
    assert stats["pulls"]["pulls"] >= 1, stats


# --------------------------------------------------------------- streaming
def test_streaming_overflow_spills_to_shm():
    """Owner-side overflow: pushed-but-unconsumed items past
    streaming_max_inflight_items spill to the shm store and restore
    transparently on consume."""
    ray_tpu.shutdown()
    saved = _config.streaming_max_inflight_items
    _config.streaming_max_inflight_items = 4
    ray_tpu.init(num_cpus=2, num_tpus=0)
    try:
        @ray_tpu.remote
        def stream(n):
            for i in range(n):
                yield bytes([i % 251]) * 2048

        n = 24
        gen = stream.options(
            num_returns="streaming",
            generator_backpressure_num_objects=n + 8,
        ).remote(n)
        from ray_tpu.util.metrics import get_registry

        def spilled():
            return sum(
                sum(series["points"].values())
                for series in get_registry().collect()
                if series["name"] == "streaming_spilled_items_total")

        # nothing is consumed until the producer has run past the in-flight
        # bound: the owner's own counter says when, not a fixed sleep
        deadline = time.monotonic() + 60
        while spilled() < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert spilled() >= 1, "no stream item ever spilled"
        got = [ray_tpu.get(r, timeout=60) for r in gen]
        assert len(got) == n
        for i, item in enumerate(got):
            assert item == bytes([i % 251]) * 2048
    finally:
        _config.streaming_max_inflight_items = saved
        ray_tpu.shutdown()
