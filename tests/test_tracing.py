"""Tracing + timeline subsystem (ray_tpu/tracing/).

Parity model: src/ray/core_worker/task_event_buffer.h (bounded per-process
buffering, drop counting), gcs_task_manager.h (bounded aggregation, state
API), `ray timeline` (Chrome-trace export), and task-event-based debugging
of the serve/streaming/cgraph hot paths.
"""

import json
import time

import pytest

REQUIRED_TRACE_KEYS = {"pid", "tid", "ts", "ph", "name"}


# ---------------------------------------------------------------- unit level
def test_buffer_bounded_and_drop_counting():
    from ray_tpu.tracing import TaskEventBuffer

    buf = TaskEventBuffer(capacity=100)
    for i in range(150):
        buf.record(task_id=f"{i:032x}", name="t", state="SUBMITTED")
    assert len(buf) == 100
    assert buf.dropped == 50
    events, dropped = buf.drain()
    assert len(events) == 100 and dropped == 50
    assert len(buf) == 0
    # timestamps are strictly monotonic within the process
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts) and len(set(ts)) == len(ts)


# ------------------------------- the buffer never loses count (PR 68)
@pytest.mark.parametrize("path", ["acked", "dropped", "closed_in_flight",
                                  "overflowed"])
def test_buffer_accounts_for_every_event(path):
    """recorded = delivered + dropped + taken + pending, whichever way a
    batch leaves: acknowledged, lost with its flush, or still in flight —
    popped, not yet acknowledged — when the record is closed."""
    from ray_tpu.tracing import TaskEventBuffer

    buf = TaskEventBuffer(capacity=100)
    n = 150 if path == "overflowed" else 40
    for i in range(n):
        buf.record(task_id=f"{i:032x}", name="t", state="SUBMITTED")
    start = buf.counts()
    assert (start["recorded"], start["pending"], start["in_flight"]) == (
        n, min(n, 100), 0)
    batch, _ = buf.drain(max_batch=30)
    assert buf.counts()["in_flight"] == 30 and len(buf) == min(n, 100) - 30
    if path == "acked":
        buf.wal_flushed()
    elif path == "dropped":
        buf.note_dropped(len(batch))
    # whoever closes the record takes the in-flight batch FIRST, then the
    # rest: nothing popped is in neither place
    taken, in_flight = buf.take_unacked()
    c = buf.counts()
    assert c["pending"] == 0 and c["in_flight"] == 0
    if path in ("closed_in_flight", "overflowed"):
        assert in_flight == 30 and taken[:30] == batch
        assert len(taken) == min(n, 100)
    else:
        assert in_flight == 0 and len(taken) == 10
    assert c["delivered"] == (30 if path == "acked" else 0)
    assert c["dropped"] == {"dropped": 30, "overflowed": 50}.get(path, 0)
    assert c["recorded"] == c["delivered"] + c["dropped"] + c["taken"] == n
    # since an earlier counts(): what was pending then counts as recorded
    for i in range(5):
        buf.record(task_id=f"{i:032x}", name="u", state="SUBMITTED")
    mid = buf.counts()
    buf.record(task_id="f" * 32, name="u", state="SUBMITTED")
    since = buf.counts(since=mid)
    assert (since["recorded"], since["delivered"], since["dropped"],
            since["taken"], since["pending"]) == (6, 0, 0, 0, 6)


def test_an_overflowed_buffer_shows_in_the_summary_and_the_reader():
    """dropped > 0 at a source is `lost` in driver/record_summary and reads
    > 0 through session_record_lost_events; a sound row reads 0."""
    import os
    import sys

    from ray_tpu.core.cluster_backend import record_summary
    from ray_tpu.tracing import TaskEventBuffer, build_chrome_trace, names

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.layer_metrics import session_record_lost_events as reader
    from benchmarks.harness import session_record

    def lost(rows, account=None):
        summary = record_summary(rows, account or {}, {})
        assert tuple(summary) == names.DRIVER_RECORD_SUMMARY_ARGS
        trace = build_chrome_trace([{
            "name": "record_summary", "component": "driver", "ts": 5.0,
            "state": "PROFILE", "args": summary}])
        return summary, reader.read({
            "session_record": session_record.parse(trace), "notes": []})

    buf = TaskEventBuffer(capacity=100)
    at_init = buf.counts()
    for i in range(150):
        buf.record(task_id=f"{i:032x}", name="t", state="SUBMITTED")
    taken, _ = buf.take_unacked()
    row = {"recovered": len(taken), **buf.counts(since=at_init)}
    summary, read = lost({"driver-x": row})
    assert summary["sources"][0]["dropped"] == 50 == summary["sources"][0]["lost"]
    assert read == 50
    sound = {"recorded": 9, "delivered": 7, "recovered": 2, "dropped": 0}
    assert lost({"worker-y": sound})[1] == 0
    # a source last heard with events pending and no file to recover them
    # from (killed with -9), and set-up spans the aggregator pushed out
    killed = {"recorded": 9, "delivered": 5, "recovered": 0, "dropped": 1}
    summary, read = lost({"worker-y": sound, "worker-z": killed},
                         {"setup_evicted": 3, "truncated_events": 11})
    assert [r["lost"] for r in summary["sources"]] == [0, 4]
    assert read == 7 and summary["truncated_events"] == 11


@pytest.mark.parametrize("cap,n", [(256, 600), (5, 20), (8, 8)])
def test_a_tasks_span_name_keeps_its_newest_events(cap, n):
    """Past max_events_per_task a span name of one task is a ring: the
    OLDEST goes (600 step_counters leave steps 345..600), lifecycle events
    and the task's other span names stay, truncated_events counts."""
    from ray_tpu.tracing import TaskEventAggregator

    agg = TaskEventAggregator(max_tasks=10, max_events_per_task=cap)
    agg.ingest([{"task_id": "t", "name": "start_training", "state": "RUNNING",
                 "ts": 1.0},
                {"task_id": "t", "name": "loop_entered", "state": "PROFILE",
                 "ts": 1.5}])
    for step in range(1, n + 1):       # one flush a few steps, as it arrives
        agg.ingest([{"task_id": "t", "name": "step_counters",
                     "state": "PROFILE", "ts": 2.0 + step * 1e-3,
                     "args": {"step": step}}], source="worker-a")
    agg.ingest([{"task_id": "t", "name": "loop_done", "state": "PROFILE",
                 "ts": 9.0},
                {"task_id": "t", "name": "start_training", "state": "EXECUTED",
                 "ts": 9.5}])
    events = agg.get_task("t")["events"]
    steps = [e["args"]["step"] for e in events if e["name"] == "step_counters"]
    assert steps == list(range(max(1, n - cap + 1), n + 1))
    assert agg.truncated_events == max(0, n - cap)
    assert [e["name"] for e in events if e["name"] != "step_counters"] == [
        "start_training", "loop_entered", "loop_done", "start_training"]
    assert agg.accounting()["truncated_events"] == max(0, n - cap)
    # a restarted head keeps the ring where it was
    again = TaskEventAggregator(max_tasks=10, max_events_per_task=cap)
    again.restore(agg.dump())
    again.ingest([{"task_id": "t", "name": "step_counters", "state": "PROFILE",
                   "ts": 8.0, "args": {"step": n + 1}}])
    steps = [e["args"]["step"] for e in again.get_task("t")["events"]
             if e["name"] == "step_counters"]
    assert steps[-1] == n + 1 and len(steps) == min(cap, n + 1)


def test_aggregator_keeps_each_sources_last_counts_and_replays():
    """accounting(): a row a source with what it last reported (max-merged:
    a re-report changes nothing), a WAL replay counted as `recovered` for
    the source whose events carry that worker, set-up evictions counted."""
    from ray_tpu.tracing import TaskEventAggregator

    agg = TaskEventAggregator(max_setup_events=2)

    def ev(i, worker):
        return {"task_id": "t", "name": "x", "state": "PROFILE",
                "ts": 1.0 + i, "worker": worker}

    agg.ingest([ev(0, "w:1"), ev(1, "w:1")], source="worker-a", recorded=5,
               delivered=2, worker="w:1")
    agg.ingest([ev(2, "w:1")], source="worker-a", recorded=6, delivered=3,
               dropped=1, worker="w:1")
    agg.ingest([], source="worker-a", recorded=5, delivered=2, worker="w:1")
    # the raylet recovers the dead worker's file: one event is here already
    agg.ingest([ev(2, "w:1"), ev(3, "w:1"), ev(4, "w:1")],
               source="wal-n0-7")
    # a file of a worker that never reported has a row of its own
    agg.ingest([ev(9, "w:2")], source="wal-n0-8")
    rows = agg.accounting()["sources"]
    assert rows["worker-a"] == {"recorded": 6, "delivered": 3, "dropped": 1,
                                "recovered": 2, "worker": "w:1"}
    assert rows["wal-n0-8"]["recovered"] == 1 and "wal-n0-7" not in rows
    assert agg.summarize()["dropped_at_source"] == 1
    assert agg.stats()["task_events_dropped_at_source"] == 1
    # the set-up queue counts what it pushes out
    agg.ingest([{"name": "fit", "component": "train", "state": "PROFILE",
                 "ts": 1.0 + i} for i in range(5)], source="driver-d")
    assert agg.accounting()["setup_evicted"] == 3
    again = TaskEventAggregator(max_setup_events=2)
    again.restore(agg.dump())
    assert again.accounting() == agg.accounting()


def test_sampling_is_deterministic_per_trace():
    from ray_tpu.core.config import _config
    from ray_tpu.tracing import TaskEventBuffer

    buf = TaskEventBuffer(capacity=10_000)
    saved = _config.task_events_sample_rate
    _config.task_events_sample_rate = 0.5
    try:
        # all events of one trace keep or drop together, across repeats
        for trace in ("a" * 32, "b" * 32, "c" * 32, "d" * 32):
            first = buf.record(task_id="1" * 32, trace_id=trace,
                               name="x", state="SUBMITTED")
            for _ in range(5):
                assert buf.record(
                    task_id="2" * 32, trace_id=trace, name="x",
                    state="RUNNING",
                ) == first
    finally:
        _config.task_events_sample_rate = saved


def test_chrome_trace_builder_shapes():
    from ray_tpu.tracing import build_chrome_trace

    t0 = time.time()
    events = [
        {"task_id": "t1", "name": "f", "state": "SUBMITTED", "ts": t0,
         "attempt": 0, "node_id": "n1", "worker": "w1"},
        {"task_id": "t1", "name": "f", "state": "RUNNING", "ts": t0 + 0.01,
         "attempt": 0, "node_id": "n1", "worker": "w2"},
        {"task_id": "t1", "name": "f", "state": "EXECUTED", "ts": t0 + 0.05,
         "attempt": 0, "node_id": "n1", "worker": "w2"},
        {"task_id": "t1", "name": "f", "state": "FINISHED", "ts": t0 + 0.06,
         "attempt": 0, "node_id": "n1", "worker": "w1"},
        {"task_id": None, "name": "span", "state": "PROFILE",
         "ts": t0 + 0.02, "dur": 0.005, "worker": "w2", "node_id": "n1"},
    ]
    trace = build_chrome_trace(events)
    assert all(REQUIRED_TRACE_KEYS <= set(e) for e in trace)
    spans = [e for e in trace if e["ph"] == "X" and e["name"] == "f"]
    assert len(spans) == 1 and abs(spans[0]["dur"] - 40_000) < 1
    assert any(e["ph"] == "X" and e["name"] == "span" for e in trace)
    # valid JSON end to end
    assert json.loads(json.dumps(trace)) == trace


def test_aggregator_event_cap_never_drops_terminal_states():
    """A span-heavy task must not overflow its record into a phantom
    RUNNING: the per-task cap truncates PROFILE spans only."""
    from ray_tpu.tracing import TaskEventAggregator

    agg = TaskEventAggregator(max_tasks=10, max_events_per_task=5)
    events = [{"task_id": "t", "name": "f", "state": "SUBMITTED", "ts": 1.0}]
    events += [
        {"task_id": "t", "name": "s", "state": "PROFILE",
         "ts": 1.0 + i * 1e-3}
        for i in range(20)
    ]
    events += [
        {"task_id": "t", "name": "f", "state": "RUNNING", "ts": 2.0},
        {"task_id": "t", "name": "f", "state": "FINISHED", "ts": 3.0},
    ]
    agg.ingest(events)
    t = agg.get_task("t")
    assert t["state"] == "FINISHED"
    assert sum(1 for e in t["events"] if e["state"] == "PROFILE") == 5
    assert agg.truncated_events == 15


# --------------------------------------------------------------- local mode
def test_local_task_lifecycle_and_state_api(ray_start_local):
    ray = ray_start_local
    from ray_tpu.util import state

    @ray.remote
    def add(x):
        with ray.profile_span("inner-work", args={"x": x}):
            pass
        return x + 1

    refs = [add.remote(i) for i in range(3)]
    assert ray.get(refs) == [1, 2, 3]

    t = state.get_task(refs[0].task_id.hex())
    assert t is not None and t["state"] == "FINISHED"
    states = [e["state"] for e in t["events"]]
    assert states[0] == "SUBMITTED" and "RUNNING" in states
    assert states[-1] == "FINISHED"
    # the profile span landed inside the task's timeline
    assert any(
        e["state"] == "PROFILE" and e["name"] == "inner-work"
        for e in t["events"]
    )

    summary = state.summarize_tasks()
    assert summary["tasks"]["add"]["FINISHED"] == 3
    assert summary["dropped_at_source"] == 0

    rows = state.list_tasks()
    mine = [r for r in rows if r["name"] == "add"]
    assert len(mine) == 3
    assert all(isinstance(r["task_id"], str) for r in mine)  # hex, not bytes

    trace = ray.timeline()
    assert all(REQUIRED_TRACE_KEYS <= set(e) for e in trace)
    assert sum(1 for e in trace if e["name"] == "add" and e["ph"] == "X") >= 3


def test_local_nested_tasks_share_parent_and_trace(ray_start_local):
    ray = ray_start_local
    from ray_tpu.util import state

    @ray.remote
    def child():
        return 1

    @ray.remote
    def parent():
        return ray.get(child.remote())

    ref = parent.remote()
    assert ray.get(ref) == 1
    rows = state.list_tasks()
    child_row = next(r for r in rows if r["name"] == "child")
    t = state.get_task(child_row["task_id"])
    assert any(e.get("parent_id") == ref.task_id.hex() for e in t["events"])


def test_tracing_disabled_records_nothing(ray_start_local):
    ray = ray_start_local
    from ray_tpu.core.config import _config
    from ray_tpu.util import state

    saved = _config.task_events_enabled
    _config.task_events_enabled = False
    try:
        @ray.remote
        def ghost():
            return 0

        ref = ghost.remote()
        assert ray.get(ref) == 0
        assert state.get_task(ref.task_id.hex()) is None
    finally:
        _config.task_events_enabled = saved


@pytest.mark.chaos
def test_chaos_killed_actor_timeline_ends_failed_local(ray_start_local):
    """After an injected worker kill the task's timeline must end FAILED —
    no hang, no phantom RUNNING tail — and the drop counter must be
    accurate (nothing was dropped, so exactly 0)."""
    ray = ray_start_local
    from ray_tpu.testing import chaos
    from ray_tpu.util import state

    with chaos.plan(seed=11).kill_actor(match="Victim.work", after_calls=2):
        @ray.remote(max_restarts=0)
        class Victim:
            def work(self):
                return 1

        v = Victim.remote()
        assert ray.get(v.work.remote(), timeout=30) == 1
        dead_ref = v.work.remote()
        with pytest.raises(ray.exceptions.ActorDiedError):
            ray.get(dead_ref, timeout=30)

    t = state.get_task(dead_ref.task_id.hex())
    assert t is not None and t["state"] == "FAILED"
    lifecycle = [e["state"] for e in t["events"] if e["state"] != "PROFILE"]
    assert lifecycle[-1] == "FAILED", lifecycle
    assert t["dropped_at_source"] == 0
    summary = state.summarize_tasks()
    assert summary["tasks"]["work"].get("FAILED", 0) >= 1


# -------------------------------------------------------------- cluster mode
@pytest.fixture
def cluster():
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


def _flush_wait():
    # owner/worker/raylet buffers flush on independent ~1s loops
    time.sleep(2.5)


def test_cluster_full_lifecycle_events(cluster):
    ray = cluster
    from ray_tpu.util import state

    @ray.remote
    def work():
        return 1

    ref = work.remote()
    assert ray.get(ref, timeout=60) == 1
    _flush_wait()
    t = state.get_task(ref.task_id.hex())
    states = {e["state"] for e in t["events"]}
    # owner (SUBMITTED/DISPATCHED/FINISHED) + raylet (LEASED) + executing
    # worker (RUNNING/EXECUTED) all contributed to one timeline
    assert {"SUBMITTED", "DISPATCHED", "RUNNING", "FINISHED"} <= states
    assert t["state"] == "FINISHED"
    workers = {e["worker"] for e in t["events"] if e.get("worker")}
    assert len(workers) >= 2  # driver + executing worker


def test_serve_request_stitches_one_trace_across_processes(cluster):
    """Acceptance: a cluster-mode serve request produces a single stitched
    trace spanning >= 3 processes (handle/driver, ingress replica worker,
    nested replica worker), exported as valid Chrome-trace JSON."""
    ray = cluster
    from ray_tpu import serve
    from ray_tpu.util import state

    @serve.deployment
    class Model:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Ingress:
        def __init__(self, model):
            self.model = model

        def __call__(self, x):
            import ray_tpu

            return ray_tpu.get(self.model.remote(x), timeout=30) + 1

    try:
        handle = serve.run(Ingress.bind(Model.bind()))
        assert ray.get(handle.remote(5), timeout=90) == 11
        _flush_wait()

        events = state.timeline_events()
        serve_spans = [
            e for e in events
            if e["state"] == "PROFILE" and e["name"] == "serve.request"
            and e.get("trace_id")
        ]
        assert serve_spans, "serve dispatch recorded no request span"
        # the ingress dispatch span's trace must cover >= 3 processes
        by_trace = {}
        for e in events:
            if e.get("trace_id"):
                by_trace.setdefault(e["trace_id"], []).append(e)
        best = max(
            (evs for evs in by_trace.values()),
            key=lambda evs: len({e.get("worker") for e in evs
                                 if e.get("worker")}),
        )
        workers = {e.get("worker") for e in best if e.get("worker")}
        assert len(workers) >= 3, (
            f"trace spans only {len(workers)} processes: {workers}"
        )
        # the trace contains both replicas' task executions
        names = {e["name"] for e in best}
        assert "handle_request" in names

        # Chrome-trace export: valid JSON, every event fully addressed
        import tempfile

        out = tempfile.mktemp(suffix=".json")
        trace = ray.timeline(out)
        loaded = json.loads(open(out).read())
        assert loaded and loaded == trace
        assert all(REQUIRED_TRACE_KEYS <= set(e) for e in loaded)
    finally:
        serve.shutdown()


def test_serve_stream_backpressure_window_option(cluster):
    """Satellite: the hardcoded window 16 is now a per-deployment option,
    routing-table propagated, overridable per handle."""
    ray = cluster
    from ray_tpu import serve

    @serve.deployment(stream_backpressure_window=3)
    class Chunker:
        def __call__(self, n):
            def gen():
                for i in range(n):
                    yield i
            return gen()

    try:
        handle = serve.run(Chunker.bind())
        router = handle._router
        assert router.backpressure_for("Chunker") == 3
        assert list(handle.stream(5)) == list(range(5))
        # handle-level override plumbs through options()
        h2 = handle.options(stream_backpressure_window=7)
        assert h2._stream_backpressure_window == 7
        assert list(h2.stream(4)) == list(range(4))
        # default when the deployment doesn't set one
        from ray_tpu.serve.handle import DEFAULT_STREAM_BACKPRESSURE

        assert router.backpressure_for("nonexistent") == \
            DEFAULT_STREAM_BACKPRESSURE
    finally:
        serve.shutdown()


@pytest.mark.chaos(timeout=180)
def test_chaos_killed_worker_timeline_ends_failed_cluster():
    """Cluster variant of the chaos acceptance: a real SIGKILL of the actor
    worker mid-call. The dead worker's buffered events die with it (never
    counted as drops by a live source), the owner's FAILED event lands, and
    the aggregate drop counter stays accurate."""
    import ray_tpu
    from ray_tpu.testing import chaos
    from ray_tpu.util import state

    ray_tpu.shutdown()
    with chaos.plan(seed=23).kill_actor(match="Victim.work", after_calls=2):
        ray_tpu.init(num_cpus=2, num_tpus=0)
        try:
            @ray_tpu.remote(max_restarts=0)
            class Victim:
                def work(self):
                    return 1

            v = Victim.remote()
            assert ray_tpu.get(v.work.remote(), timeout=60) == 1
            dead_ref = v.work.remote()
            with pytest.raises(ray_tpu.exceptions.ActorDiedError):
                ray_tpu.get(dead_ref, timeout=60)
            _flush_wait()
            t = state.get_task(dead_ref.task_id.hex())
            assert t is not None and t["state"] == "FAILED"
            lifecycle = [
                e["state"] for e in t["events"] if e["state"] != "PROFILE"
            ]
            assert lifecycle[-1] == "FAILED", lifecycle
            assert isinstance(t["dropped_at_source"], int)
            assert t["dropped_at_source"] == 0
        finally:
            ray_tpu.shutdown()
