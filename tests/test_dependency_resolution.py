"""A task asks for a worker when its arguments exist (PR 40).

The owner holds a normal or streaming task until every object it takes by
reference from that owner has a value or an error (``CoreWorker.
_wait_for_args``; the reference's LocalDependencyResolver before
RequestNewWorkerIfNeeded): no lease request, no worker, no resources
meanwhile. What is held here: a burst of chains runs in dependency order with
no worker ever reporting blocked and no more processes than run at once; a
failed argument, a cancel, a deadline and a killed producer each end the wait
the right way; ``Dataset.split`` over hundreds of blocks is its tasks' own
work and ``fit()`` survives it.
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import exceptions as exc
from ray_tpu.tracing import names

NUM_CPUS = 2
CHAINS, SLEEP_S = 4, 0.4


def _core():
    from ray_tpu.api import _global_worker

    return _global_worker().backend.core


def _dispatch_stats():
    core = _core()
    return core.io.run(core.raylet.call("scheduler_stats"))["dispatch"]


# ----------------------------------------- one burst of chains, end to end
@pytest.fixture(scope="module")
def burst():
    """``CHAINS`` chains produce (sleeps) -> consume -> consume, submitted in
    one burst on ``NUM_CPUS`` CPUs; then the raylet's counters, and the
    session's record after shutdown()."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=NUM_CPUS, num_tpus=0)
    try:

        @ray_tpu.remote
        def produce(i):
            time.sleep(SLEEP_S)
            return i

        @ray_tpu.remote
        def consume(x):
            return x + 1

        firsts = [produce.remote(i) for i in range(CHAINS)]
        seconds = [consume.remote(r) for r in firsts]
        thirds = [consume.remote(r) for r in seconds]
        values = ray_tpu.get(thirds, timeout=120)
        stats = _dispatch_stats()
    finally:
        ray_tpu.shutdown()
    trace = ray_tpu.timeline()
    by_task = {}
    for e in trace:
        tid = (e.get("args") or {}).get("task_id")
        if tid is not None:
            by_task.setdefault(tid, []).append(e)
    return {
        "values": values, "stats": stats, "trace": trace, "by_task": by_task,
        "chains": [[r.task_id.hex() for r in chain]
                   for chain in zip(firsts, seconds, thirds)],
    }


def _ran(burst, task_id):
    """The task's slice: RUNNING -> its worker had the result."""
    (ran,) = [e for e in burst["by_task"][task_id]
              if e["ph"] == "X" and e["cat"] == "task"]
    return ran


def _made_at(burst, task_id):
    ran = _ran(burst, task_id)
    return ran["ts"] + ran["dur"]


def _instants(burst, task_id, state):
    return [e["ts"] for e in burst["by_task"][task_id]
            if e["ph"] == "i" and e["name"].endswith(":" + state)]


def test_burst_of_chains_gives_the_right_values(burst):
    assert burst["values"] == [i + 2 for i in range(CHAINS)]


def test_no_worker_reported_blocked(burst):
    assert burst["stats"]["worker_blocked"] == 0, burst["stats"]


def test_no_lease_before_the_argument_exists(burst):
    """A consumer is leased, dispatched and running only after the task
    that makes its argument ended: it never held a worker to wait in."""
    for chain in burst["chains"]:
        for producer, consumer in zip(chain, chain[1:]):
            made = _made_at(burst, producer)
            starts = (_instants(burst, consumer, "LEASED")
                      + _instants(burst, consumer, "DISPATCHED")
                      + [_ran(burst, consumer)["ts"]])
            assert min(starts) >= made, (producer, consumer, starts, made)


def test_waiting_for_arguments_is_a_state_of_its_own(burst):
    """Every consumer was submitted while its argument was being made: the
    owner records PENDING_ARGS_AVAIL between SUBMITTED and the lease; a
    producer, whose arguments are plain values, never does."""
    for chain in burst["chains"]:
        assert _instants(burst, chain[0], names.TASK_PENDING_ARGS_AVAIL) == []
        for consumer in chain[1:]:
            (submitted,) = _instants(burst, consumer, "SUBMITTED")
            (waiting,) = _instants(burst, consumer, names.TASK_PENDING_ARGS_AVAIL)
            # more than once if a busy worker bounced it to an idle one
            dispatched = _instants(burst, consumer, "DISPATCHED")
            assert submitted <= waiting <= min(dispatched)


def test_no_more_processes_than_run_at_once(burst):
    """The raylet starts a pooled process only for a task that can run: the
    twelve tasks of the burst never need more than one a CPU."""
    started = [e for e in burst["trace"]
               if e.get("cat") == "raylet" and e["name"] == "worker_start"]
    assert 1 <= len(started) <= NUM_CPUS, [e["args"] for e in started]


def test_a_stage_reuses_its_leases(burst):
    """Twelve tasks of one scheduling key on two CPUs: the owner's lease
    cache serves them, the raylet is asked a handful of times."""
    assert burst["stats"]["grants"] <= 2 * NUM_CPUS, burst["stats"]


# ------------------------------------------------- how a wait can end
@pytest.fixture(scope="module")
def cluster():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=NUM_CPUS, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


@ray_tpu.remote
def _slow(value, seconds):
    time.sleep(seconds)
    return value


@ray_tpu.remote
def _plus_one(x):
    return x + 1


def test_failed_producer_fails_the_consumer_with_its_error(cluster):
    @ray_tpu.remote
    def broken():
        time.sleep(0.3)
        raise ValueError("producer broke")

    ref = _plus_one.remote(_plus_one.remote(broken.remote()))
    with pytest.raises(ValueError, match="producer broke"):
        ray_tpu.get(ref, timeout=60)


def test_cancel_while_waiting_for_arguments(cluster):
    """The cancelled consumer never asks for a worker and its ref raises at
    once; the producer is untouched."""
    grants = _dispatch_stats()["grants"]
    producer = _slow.remote(1, 1.5)
    consumer = _plus_one.remote(producer)
    time.sleep(0.2)
    ray_tpu.cancel(consumer)
    t0 = time.monotonic()
    with pytest.raises(exc.TaskCancelledError):
        ray_tpu.get(consumer, timeout=30)
    assert time.monotonic() - t0 < 1.0  # not after the producer's 1.5 s
    assert ray_tpu.get(producer, timeout=30) == 1
    assert _dispatch_stats()["grants"] <= grants + 1  # the producer's alone
    assert _core()._arg_waits == {}, _core()._arg_waits


def test_cancel_fails_what_waits_on_the_cancelled_task(cluster):
    """A cancelled task's ref holds an error, and an error is a value: the
    task that takes it is released and fails with it."""
    consumer = _plus_one.remote(_slow.remote(1, 1.0))
    downstream = _plus_one.remote(consumer)
    time.sleep(0.2)
    ray_tpu.cancel(consumer)
    with pytest.raises(exc.TaskCancelledError):
        ray_tpu.get(downstream, timeout=30)


def test_cancel_of_a_task_that_runs_is_left_alone(cluster):
    ref = _slow.remote(7, 0.5)
    time.sleep(0.2)
    ray_tpu.cancel(ref)
    assert ray_tpu.get(ref, timeout=30) == 7


def test_deadline_passes_while_waiting_for_arguments(cluster):
    """The owner sheds the waiting consumer at its deadline, typed, and not
    when its producer finally finishes."""
    from ray_tpu import tracing

    producer = _slow.remote(1, 2.0)
    with tracing.deadline_context(time.time() + 0.4):
        consumer = _plus_one.remote(producer)
    t0 = time.monotonic()
    with pytest.raises(exc.DeadlineExceededError):
        ray_tpu.get(consumer, timeout=30)
    assert time.monotonic() - t0 < 1.5
    assert ray_tpu.get(producer, timeout=30) == 1


def test_streaming_task_with_a_pending_argument(cluster):
    @ray_tpu.remote(num_returns="streaming")
    def count_up(n):
        for i in range(n):
            yield i

    blocked = _dispatch_stats()["worker_blocked"]
    gen = count_up.remote(_slow.remote(3, 0.5))
    assert [ray_tpu.get(r, timeout=30) for r in gen] == [0, 1, 2]
    assert _dispatch_stats()["worker_blocked"] == blocked


def test_streaming_task_with_a_failed_argument(cluster):
    @ray_tpu.remote
    def broken():
        raise ValueError("no count")

    @ray_tpu.remote(num_returns="streaming")
    def count_up(n):
        yield from range(n)

    with pytest.raises(exc.TaskError, match="no count"):
        for r in count_up.remote(broken.remote()):
            ray_tpu.get(r, timeout=30)


def test_borrowed_and_made_arguments_pass_straight_through(cluster):
    """A ref that has its value, a put object and a plain value hold
    nothing up: no PENDING_ARGS_AVAIL wait is ever registered."""
    made = _plus_one.remote(1)
    assert ray_tpu.get(made, timeout=30) == 2
    put = ray_tpu.put(10)

    @ray_tpu.remote
    def add(a, b, c):
        return a + b + c

    core = _core()
    ref = add.remote(made, put, 5)
    spec = core.submitted_specs[ref.task_id]
    assert not any(core._arg_unmade(r) for r in spec.dependencies())
    assert ray_tpu.get(ref, timeout=30) == 17


def test_hints_are_computed_once_the_arguments_have_locations(cluster):
    """The first lease request of a consumer submitted beside its producer
    carries the location of the (shm-sized) argument: by then it exists.
    A task with no located argument caches that it has none."""
    @ray_tpu.remote
    def big():
        time.sleep(0.3)
        return np.zeros(1_000_000)

    @ray_tpu.remote
    def total(x):
        return float(x.sum())

    core = _core()
    producer = big.remote()
    consumer = total.remote(producer)
    spec = core.submitted_specs[consumer.task_id]
    assert ray_tpu.get(consumer, timeout=60) == 0.0
    ((oid_hex, nbytes, node_id),) = spec._arg_hints
    assert oid_hex == producer.id.hex() and nbytes >= 8_000_000
    assert node_id == core.node_id
    small = _plus_one.remote(1)
    assert ray_tpu.get(small, timeout=30) == 2
    assert core.submitted_specs[small.task_id]._arg_hints is None


# ------------------------------------------------ a producer's worker dies
@pytest.mark.chaos(timeout=180)
def test_consumers_complete_after_the_producers_retry():
    """The worker granted the first lease — the producer's — is SIGKILLed;
    the consumers that wait on it hold no worker meanwhile and complete
    once the retry has made their argument."""
    from ray_tpu.testing import chaos

    ray_tpu.shutdown()
    with chaos.plan(40).kill_worker(after_tasks=1) as p:
        ray_tpu.init(num_cpus=NUM_CPUS, num_tpus=0)
        try:

            @ray_tpu.remote(max_retries=3)
            def produce():
                time.sleep(0.3)
                return 5

            producer = produce.remote()
            consumers = [_plus_one.remote(producer) for _ in range(4)]
            last = _plus_one.remote(consumers[-1])
            assert ray_tpu.get(consumers, timeout=120) == [6] * 4
            assert ray_tpu.get(last, timeout=120) == 7
            assert any(e["point"] == "worker.lease" for e in p.events())
            assert _dispatch_stats()["worker_blocked"] == 0
        finally:
            ray_tpu.shutdown()


@pytest.mark.chaos(timeout=180)
def test_lost_argument_is_remade_for_a_consumer_submitted_later():
    """Lineage: the stored copy of a made argument is lost; the consumer's
    worker finds that out in its argument get(), the owner resubmits the
    producer, and the consumer completes."""
    import os

    from ray_tpu.core.object_store import shm_store

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=NUM_CPUS, num_tpus=0)
    try:

        @ray_tpu.remote(max_retries=3)
        def produce():
            return np.full(1_000_000, 7.0)

        @ray_tpu.remote
        def first(x):
            return float(x[0])

        ref = produce.remote()
        assert ray_tpu.get(first.remote(ref), timeout=60) == 7.0
        core = _core()
        os.unlink(os.path.join(shm_store.session_dir(core.session),
                               ref.id.hex()))
        assert ray_tpu.get(first.remote(ref), timeout=120) == 7.0
    finally:
        ray_tpu.shutdown()


# ------------------------------------ Dataset.split over hundreds of blocks
BLOCKS = 320


def _count_rows_loop(config):
    from ray_tpu import train

    rows = 0
    for batch in train.get_dataset_shard("train").iter_batches(batch_size=64):
        rows += len(batch["id"])
    train.report({"rows": rows})


def test_split_of_hundreds_of_blocks_and_fit_survive():
    """PERF.md §7, found by PR 22: a Dataset of 320 blocks killed fit()
    (every consumer held a worker, the raylet started up to 4 x cores
    processes, the node missed its health reports). Now the split starts a
    process a task that can run, and no worker reports blocked."""
    from ray_tpu import data, train

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        ds = data.range(BLOCKS * 8, parallelism=BLOCKS).map_batches(
            lambda b: {"id": b["id"] * 2})
        trainer = train.JaxTrainer(
            _count_rows_loop, train_loop_config={},
            scaling_config=train.ScalingConfig(num_workers=1),
            datasets={"train": ds})
        result = trainer.fit()
        stats = _dispatch_stats()
    finally:
        ray_tpu.shutdown()
    assert result.metrics["rows"] == BLOCKS * 8
    assert stats["worker_blocked"] == 0, stats
    trace = ray_tpu.timeline()
    started = [e for e in trace
               if e.get("cat") == "raylet" and e["name"] == "worker_start"]
    # 4 CPUs: at most 4 tasks of 1 CPU or 16 of 0.25 run at once, plus the
    # train worker; the parent started 24 for the same split
    assert len(started) <= 17, len(started)
