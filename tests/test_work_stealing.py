"""Pipelined-task work stealing (run-slot stealing gap, PR-13).

A task that blocks OUT-OF-BAND (plain sleep / rendezvous — it never enters
get_blocking, so it holds its run slot) used to pin every spec pipelined
behind it until worker_requeue_after_ms expired. With stealing, the owner
reclaims queued specs the moment another leased worker goes idle, so they
complete in milliseconds instead. The old ``worker_max_tasks_in_flight=1``
workaround is retired.
"""

import os
import time

import pytest

import ray_tpu

# the fallback requeue timer is pinned above every wait below, so only
# stealing can move a queued spec off a worker whose task does not end
_REQUEUE_MS = "60000"


@pytest.fixture
def stealing_cluster():
    saved = os.environ.get("RAY_TPU_WORKER_REQUEUE_AFTER_MS")
    os.environ["RAY_TPU_WORKER_REQUEUE_AFTER_MS"] = _REQUEUE_MS
    from ray_tpu.core.config import _config

    saved_cfg = _config.worker_requeue_after_ms
    _config.worker_requeue_after_ms = int(_REQUEUE_MS)
    ray_tpu.init(num_cpus=2, num_tpus=0)
    yield
    ray_tpu.shutdown()
    if saved is None:
        os.environ.pop("RAY_TPU_WORKER_REQUEUE_AFTER_MS", None)
    else:
        os.environ["RAY_TPU_WORKER_REQUEUE_AFTER_MS"] = saved
    _config.worker_requeue_after_ms = saved_cfg


def _blocked_worker_and_burst(gate):
    """A task that holds its worker's run slot until ``gate`` exists, and 12
    quick tasks submitted once it runs: breadth-first placement stacks
    roughly half of them behind it. Returns the blocker's ref (its value:
    how many queued specs were stolen off its worker) and the burst's."""

    @ray_tpu.remote
    def blocker():
        open(gate + ".running", "w").close()
        # out-of-band block: never enters get_blocking, keeps the slot
        deadline = time.monotonic() + 90
        while not os.path.exists(gate) and time.monotonic() < deadline:
            time.sleep(0.02)
        from ray_tpu.api import _global_worker

        return _global_worker().backend.core._exec_slot.steals

    @ray_tpu.remote
    def quick(i):
        return i

    # warm the 2-worker pool so the burst is placed, not spawned
    ray_tpu.get([quick.remote(i) for i in range(8)], timeout=60)
    b = blocker.remote()
    deadline = time.monotonic() + 60
    while not os.path.exists(gate + ".running"):
        assert time.monotonic() < deadline, "the blocker never started"
        time.sleep(0.02)
    return b, [quick.remote(i) for i in range(12)]


def test_spec_queued_behind_blocked_worker_migrates(stealing_cluster,
                                                    tmp_path):
    """Specs committed to a busy worker complete on the idle one while the
    busy one's task is still running: with the requeue fallback out of
    reach only a steal can do that, and the worker counts it."""
    gate = str(tmp_path / "gate")
    b, burst = _blocked_worker_and_burst(gate)
    assert ray_tpu.get(burst, timeout=30) == list(range(12))
    ready, _ = ray_tpu.wait([b], timeout=0)
    assert not ready, "the blocker ended before the burst did"
    open(gate, "w").close()
    assert ray_tpu.get(b, timeout=30) >= 1, (
        "the burst finished beside a blocked worker and nothing was stolen "
        "off it: specs no longer queue behind the blocker, fix the shape")


def test_stealing_disabled_falls_back_to_requeue_timer(stealing_cluster,
                                                       tmp_path):
    """With stealing off, the same burst cannot finish until the blocker
    ends (or the requeue timer fires: pinned out of reach) — the contrast
    that proves the steal, not placement luck, rescued the specs above."""
    from ray_tpu.core.config import _config

    os.environ["RAY_TPU_WORKER_STEALING_ENABLED"] = "0"
    saved = _config.worker_stealing_enabled
    _config.worker_stealing_enabled = False
    try:
        gate = str(tmp_path / "gate")
        b, burst = _blocked_worker_and_burst(gate)
        ready, _ = ray_tpu.wait(burst, num_returns=len(burst), timeout=1.5)
        assert len(ready) < len(burst), (
            "the whole burst finished beside a blocked worker with stealing "
            "OFF: specs no longer queue behind the blocker, fix the shape")
        open(gate, "w").close()
        assert ray_tpu.get(burst, timeout=30) == list(range(12))
        assert ray_tpu.get(b, timeout=30) == 0
    finally:
        os.environ.pop("RAY_TPU_WORKER_STEALING_ENABLED", None)
        _config.worker_stealing_enabled = saved
