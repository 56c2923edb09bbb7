"""Distributed refcounting + lineage reconstruction (VERDICT r2 item 6).

(a) an object is physically deleted from the store after its last ref drops;
(b) a lost object (raylet SIGKILL) is recomputed from its creating task.
Parity: reference_count.h:61, task_manager.h:164, object_recovery_manager.h:41.
"""

import gc
import os
import signal
import time

import numpy as np
import pytest


@pytest.fixture
def ray2():
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


def _shm_path(ray, ref):
    from ray_tpu.api import _global_worker

    core = _global_worker().backend.core
    from ray_tpu.core.object_store import shm_store

    return os.path.join(shm_store.session_dir(core.session), ref.id.hex())


def test_a_ref_finalised_under_the_registrys_lock_does_not_wait_for_itself():
    """The collector can run `ObjectRef.__del__` on the thread that stands in
    `ObjectRef.__init__`'s critical section (ROADMAP D18: a whole tier-1 run
    hung there, in `Dataset.random_shuffle`'s submit): the registry's lock is
    re-entrant, the finaliser counts the ref out and returns."""
    from ray_tpu.core import refs
    from ray_tpu.core.ids import ObjectID

    ref = refs.ObjectRef(ObjectID.from_random())
    key = ref.binary()
    assert refs.local_ref_count(key) == 1
    with refs._reg_lock:
        del ref
    assert refs.local_ref_count(key) == 0
    assert key not in refs.live_refs()


def test_put_object_freed_after_last_ref(ray2):
    ray = ray2
    big = np.ones(1_000_000)  # 8 MB → shm, not inline
    ref = ray.put(big)
    path = _shm_path(ray, ref)
    assert ray.get(ref, timeout=30).sum() == 1_000_000
    assert os.path.exists(path)

    del ref
    gc.collect()
    deadline = time.time() + 20
    while os.path.exists(path) and time.time() < deadline:
        time.sleep(0.2)
    assert not os.path.exists(path), "shm file must be deleted after last ref"


def test_task_result_freed_after_last_ref(ray2):
    ray = ray2

    @ray.remote
    def make():
        return np.ones(1_000_000)

    ref = make.remote()
    assert ray.get(ref, timeout=60).sum() == 1_000_000
    path = _shm_path(ray, ref)
    assert os.path.exists(path)
    del ref
    gc.collect()
    deadline = time.time() + 20
    while os.path.exists(path) and time.time() < deadline:
        time.sleep(0.2)
    assert not os.path.exists(path)


def test_object_kept_alive_by_pending_task(ray2):
    ray = ray2
    data = ray.put(np.arange(1_000_000))
    path = _shm_path(ray, data)

    @ray.remote
    def slow_sum(arr):
        import time as t

        t.sleep(2)
        return int(arr.sum())

    result = slow_sum.remote(data)
    del data          # only the pending task pins it now
    gc.collect()
    time.sleep(0.5)
    assert os.path.exists(path), "arg must stay alive while the task runs"
    assert ray.get(result, timeout=60) == sum(range(1_000_000))


def test_lineage_reconstruction_after_store_loss(ray2):
    """Kill the object's shm copy out from under the owner; a get() must
    resubmit the creating task and return the value."""
    ray = ray2

    @ray.remote
    def produce():
        return np.full(1_000_000, 7.0)  # large → lives in shm

    ref = produce.remote()
    assert ray.get(ref, timeout=60)[0] == 7.0
    path = _shm_path(ray, ref)
    assert os.path.exists(path)

    # simulate losing the only copy (node death for that object): remove the
    # shm file AND the raylet's directory entry via the free path, keeping
    # the ref alive
    from ray_tpu.api import _global_worker

    core = _global_worker().backend.core
    os.unlink(path)

    got = ray.get(ref, timeout=120)
    assert got[0] == 7.0 and got.shape == (1_000_000,)


def test_lineage_reconstruction_after_raylet_sigkill():
    """Multi-node: object produced on node B; SIGKILL node B's raylet; the
    driver's get() reconstructs via lineage on a surviving node."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    cluster = Cluster(head_node_args={"num_cpus": 1})
    node_b = cluster.add_node(num_cpus=2)
    ray_tpu.init(address=cluster.address)
    try:
        @ray_tpu.remote(num_cpus=2, max_retries=2)
        def produce():
            return np.full(500_000, 3.0)

        # num_cpus=2 forces placement on node B
        ref = produce.remote()
        assert ray_tpu.get(ref, timeout=90)[0] == 3.0

        cluster.kill_node(node_b)  # SIGKILL the raylet holding the copy
        # the Cluster fixture shares one host (and thus one tmpfs session
        # dir); on a real deployment node B's shm dies with it — simulate
        # that by removing the file as well
        from ray_tpu.api import _global_worker
        from ray_tpu.core.object_store import shm_store

        core = _global_worker().backend.core
        path = os.path.join(shm_store.session_dir(core.session), ref.id.hex())
        if os.path.exists(path):
            os.unlink(path)
        time.sleep(1)
        cluster.add_node(num_cpus=2)      # capacity to re-run the task

        got = ray_tpu.get(ref, timeout=120)
        assert got[0] == 3.0 and got.shape == (500_000,)
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_worker_owned_ref_in_result_not_freed(ray2):
    """A task that puts an object and returns the REF must not free it when
    its frame exits: the reply pre-registers the caller as a borrower
    (worker_main._grant_result_borrows). Regression: round-3 review."""
    ray = ray2

    @ray.remote
    def producer():
        inner = ray.put(np.ones(1_000_000))  # worker-owned, lives in shm
        return inner                          # nested ref crosses the wire

    outer = producer.remote()
    inner_ref = ray.get(outer, timeout=60)
    # the producing worker's frame exited long ago; give any stray free a
    # moment to land before reading
    time.sleep(1.0)
    assert ray.get(inner_ref, timeout=60).sum() == 1_000_000

    # and the borrow releases: dropping BOTH refs eventually deletes the shm
    from ray_tpu.api import _global_worker
    from ray_tpu.core.object_store import shm_store

    core = _global_worker().backend.core
    path = os.path.join(
        shm_store.session_dir(core.session), inner_ref.id.hex()
    )
    assert os.path.exists(path)
    del inner_ref, outer
    gc.collect()
    deadline = time.time() + 20
    while os.path.exists(path) and time.time() < deadline:
        time.sleep(0.2)
    assert not os.path.exists(path), "borrowed ref must free after release"


def test_reconstruction_attempts_are_bounded(ray2):
    """A lost object whose copies keep vanishing must not loop resubmission
    forever: after max(1, max_retries) lineage resubmits the get() surfaces
    ObjectLostError instead of spinning. Regression: round-3 review."""
    ray = ray2
    from ray_tpu.api import _global_worker

    core = _global_worker().backend.core

    @ray.remote(max_retries=1)
    def produce():
        return np.full(1_000_000, 5.0)

    ref = produce.remote()
    assert ray.get(ref, timeout=60)[0] == 5.0
    path = _shm_path(ray, ref)

    # sabotage: every reconstruction lands back in shm; delete the file each
    # time so the location read keeps failing
    import ray_tpu.exceptions as exc

    os.unlink(path)
    with pytest.raises((exc.ObjectLostError, exc.GetTimeoutError)):
        for _ in range(6):  # bounded: must raise well before 6 rounds
            os.path.exists(path) and os.unlink(path)
            ray.get(ref, timeout=20)
            os.unlink(path)


def test_arg_object_freed_after_consumer_and_spec_drop(ray2):
    """x = f(); y = g(x); del x keeps x alive (g's retained spec pins its
    lineage args); del y must then free BOTH. Also regression for the
    release-before-add borrow race: the consuming worker's release can beat
    the task reply's add_borrow across connections."""
    ray = ray2

    @ray.remote
    def f():
        return np.ones(500_000)

    @ray.remote
    def g(a):
        return float(a.sum())

    x = f.remote()
    y = g.remote(x)
    assert ray.get(y, timeout=60) == 500_000
    xpath = _shm_path(ray, x)
    del x
    gc.collect()
    time.sleep(1.5)
    assert os.path.exists(xpath), "lineage args stay pinned while y lives"
    del y
    gc.collect()
    deadline = time.time() + 20
    while os.path.exists(xpath) and time.time() < deadline:
        time.sleep(0.2)
    assert not os.path.exists(xpath), "x must free after its consumer's ref drops"
