"""Actor restart semantics + placement groups on the real cluster."""

import time

import pytest


@pytest.fixture(scope="module")
def ray_cluster2():
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


def _cpus_become(ray, want, within=10.0):
    """Wait until available_resources() reads ``want`` CPUs — the GCS's view,
    which follows the raylet's next resource report and not the call that
    changed it — or fail with everything that was read, and when."""
    start, read = time.monotonic(), []
    while True:
        cpus = ray.available_resources().get("CPU", 0)
        read.append((round(time.monotonic() - start, 2), cpus))
        if cpus == want:
            return
        assert read[-1][0] < within, (
            f"available CPUs never read {want} within {within} s: "
            f"(seconds, CPUs) {read}")
        time.sleep(0.1)


def test_actor_restart_after_crash(ray_cluster2):
    """max_restarts=1: kill the actor's worker process; the GCS must restart
    it (fresh state) and subsequent calls succeed (reference: actor.py:332
    max_restarts + GcsActorManager restart path)."""
    ray = ray_cluster2

    @ray.remote(max_restarts=1)
    class Phoenix:
        def __init__(self):
            self.calls = 0

        def bump(self):
            self.calls += 1
            return self.calls

        def crash(self):
            import os

            os._exit(42)

    p = Phoenix.remote()
    assert ray.get(p.bump.remote(), timeout=90) == 1
    assert ray.get(p.bump.remote(), timeout=90) == 2

    crash_ref = p.crash.remote()
    with pytest.raises(ray.exceptions.ActorError):
        ray.get(crash_ref, timeout=90)

    # post-restart: state reset, calls work again
    deadline = time.time() + 60
    val = None
    while time.time() < deadline:
        try:
            val = ray.get(p.bump.remote(), timeout=60)
            break
        except ray.exceptions.ActorError:
            time.sleep(1)
    assert val == 1, f"expected fresh state after restart, got {val}"


def test_actor_no_restart_stays_dead(ray_cluster2):
    ray = ray_cluster2

    @ray.remote(max_restarts=0)
    class Mortal:
        def crash(self):
            import os

            os._exit(1)

        def ping(self):
            return "pong"

    m = Mortal.remote()
    with pytest.raises(ray.exceptions.ActorError):
        ray.get(m.crash.remote(), timeout=90)
    with pytest.raises(ray.exceptions.ActorError):
        ray.get(m.ping.remote(), timeout=90)


def test_placement_group_reserve_and_run(ray_cluster2):
    ray = ray_cluster2
    from ray_tpu.util.placement_group import (
        PlacementGroupSchedulingStrategy,
        placement_group,
        remove_placement_group,
    )

    pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="PACK")
    assert pg.ready(timeout=60)

    @ray.remote(num_cpus=1)
    def inside():
        return "ran"

    ref = inside.options(
        scheduling_strategy=PlacementGroupSchedulingStrategy(
            placement_group=pg, placement_group_bundle_index=0
        ),
        placement_group=pg,
        placement_group_bundle_index=0,
    ).remote()
    assert ray.get(ref, timeout=90) == "ran"

    # PG holds both CPUs: a non-PG 1-CPU task must not find node resources
    _cpus_become(ray, 0)

    remove_placement_group(pg)
    _cpus_become(ray, 2.0)


def test_placement_group_infeasible_strict_spread(ray_cluster2):
    ray = ray_cluster2
    from ray_tpu.util.placement_group import placement_group

    # two bundles, one node → STRICT_SPREAD cannot place
    pg = placement_group(
        [{"CPU": 1}, {"CPU": 1}], strategy="STRICT_SPREAD"
    )
    assert not pg.ready(timeout=5)


def test_pg_actor_draws_from_bundle_not_node(ray_cluster2):
    """Round-3 regression: an actor placed in a PG must consume the bundle's
    reservation, not node availability — double-booking starved every plain
    task while a WorkerGroup was alive (the Train+Data deadlock)."""
    ray = ray_cluster2
    from ray_tpu.util.placement_group import placement_group, remove_placement_group

    pg = placement_group([{"CPU": 1}], strategy="PACK")
    assert pg.ready(timeout=30)

    @ray.remote
    class Holder:
        def ping(self):
            return 1

    a = Holder.options(
        placement_group=pg, placement_group_bundle_index=0, num_cpus=1
    ).remote()
    assert ray.get(a.ping.remote(), timeout=30) == 1

    # node had 2 CPUs; PG reserved 1; the actor lives INSIDE that bundle, so
    # 1 CPU must remain for plain tasks
    _cpus_become(ray, 1.0)

    @ray.remote
    def plain():
        return "ok"

    assert ray.get(plain.remote(), timeout=60) == "ok"

    ray.kill(a)
    remove_placement_group(pg)
    _cpus_become(ray, 2.0)
