"""Set-up and teardown tell their own story (PR 35): one trace per ``fit()``
attempt, ``Dataset.split``'s phases, worker processes in the raylet, compile
events from the train worker, the session record that outlives ``shutdown()``,
the aggregator's retention rule, and the benchmark's readers of the record.

One real single-node session (driver, GCS, raylet, workers on the CPU) is run
once for the module; everything else is in-process.
"""

import gzip
import json
import os
import sys

import pytest

import ray_tpu
from ray_tpu import tracing
from ray_tpu.core.config import _config
from ray_tpu.tracing import names

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# what build_chrome_trace adds to a span's own args
BASE_ARGS = {"task_id", "state", "attempt", "trace_id", "actor_id", "parent"}
SPAN_CATS = ("train", "data", "raylet", "driver")


def _spans(trace, name):
    cat, _, short = name.partition("/")
    return [e for e in trace if e.get("cat") == cat and e["name"] == short]


def _own_args(event, name):
    """A span's args of its own vocabulary (`attempt` is also a base arg)."""
    return {k for k in event["args"]
            if k not in BASE_ARGS or k in names.SETUP_SPANS[name]}


# --------------------------------------------------- one session, end to end
def _loop(config):
    import jax
    import jax.numpy as jnp

    from ray_tpu import train

    @jax.jit
    def pr35_step(x):
        return x * 2 + 1

    rows = 0
    for batch in train.get_dataset_shard("train").iter_batches(batch_size=8):
        pr35_step(jnp.ones(3))
        rows += len(batch["id"])
    train.report({"rows": rows})


@pytest.fixture(scope="module")
def session():
    """init -> fit (one worker, a 4-block Dataset) -> shutdown; then the
    record through ray_tpu.timeline(), with no cluster running."""
    from ray_tpu import data, train

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        trainer = train.JaxTrainer(
            _loop, train_loop_config={},
            scaling_config=train.ScalingConfig(num_workers=1),
            datasets={"train": data.range(64, parallelism=4)
                      .map_batches(lambda b: b)})
        result = trainer.fit()
    finally:
        ray_tpu.shutdown()
    # asking the finished session must start nothing to ask
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ray_tpu.api, "init", lambda *a, **k: pytest.fail(
            "timeline() after shutdown() started a cluster"))
        trace = ray_tpu.timeline()
    return {"result": result, "trace": trace}


def test_timeline_after_shutdown_is_the_sessions_record(session):
    assert session["result"].error is None
    assert session["result"].metrics["rows"] == 64
    trace = session["trace"]
    assert trace and not ray_tpu.is_initialized()
    for e in trace:
        assert {"pid", "tid", "ts", "ph", "name"} <= set(e), e
    # the same list is the file shutdown() wrote, and it is plain JSON
    (init,) = _spans(trace, names.DRIVER_INIT)
    assert init["args"]["started_cluster"] is True
    with open(os.path.join("/tmp/ray_tpu", init["args"]["session"],
                           "timeline.json")) as f:
        assert json.load(f) == json.loads(json.dumps(trace, default=str))


ONCE = (names.TRAIN_FIT, names.TRAIN_WORKER_GROUP_START,
        names.TRAIN_SHARD_DATASETS, names.TRAIN_START_TRAINING,
        names.TRAIN_DRIVE, names.TRAIN_GROUP_SHUTDOWN,
        names.TRAIN_LOOP_ENTERED, names.TRAIN_LOOP_DONE, names.DATA_SPLIT,
        names.DATA_MATERIALIZE, names.DATA_COUNT_ROWS, names.DATA_SLICE,
        names.DRIVER_INIT, names.DRIVER_SHUTDOWN, names.TRAIN_BACKEND_INIT,
        names.DRIVER_RECORD_SUMMARY)


@pytest.mark.parametrize("name", ONCE)
def test_each_phase_is_one_span_with_its_args(session, name):
    (span,) = _spans(session["trace"], name)
    assert _own_args(span, name) == set(names.SETUP_SPANS[name])
    instant = name in (names.TRAIN_LOOP_ENTERED, names.TRAIN_LOOP_DONE,
                       names.DRIVER_RECORD_SUMMARY)
    assert span["ph"] == ("i" if instant else "X")


def test_one_worker_records_no_rendezvous(session):
    assert not _spans(session["trace"], names.TRAIN_RENDEZVOUS)
    assert not _spans(session["trace"], names.TRAIN_JAX_DISTRIBUTED_INIT)


def test_one_trace_per_fit_from_the_call_to_the_loop(session):
    trace = session["trace"]
    (fit,) = _spans(trace, names.TRAIN_FIT)
    trace_id = fit["args"]["trace_id"]
    assert trace_id
    assert fit["args"]["attempt"] == 0 and fit["args"]["num_workers"] == 1
    for name in ONCE:
        if name.startswith(("train/", "data/")):
            (span,) = _spans(trace, name)
            assert span["args"]["trace_id"] == trace_id, name
    # ... and the load of the actor's class, before its constructor
    (load,) = [e for e in _spans(trace, names.WORKER_LOAD_CLASS)
               if e["args"]["kind"] == "actor"]
    assert load["args"]["trace_id"] == trace_id
    # the tasks submitted under it carry the id: the actor's constructor,
    # the Dataset's tasks, start_training, every poll
    ran = {}
    for e in trace:
        if e["ph"] == "X" and e.get("cat") in ("task", "actor_task"):
            ran.setdefault(e["name"], []).append(e["args"].get("trace_id"))
    for task in ("TrainWorker.__init__", "start_training", "poll",
                 "block_num_rows", "_run_map_task"):
        assert ran[task] and set(ran[task]) == {trace_id}, task
    assert len(ran["block_num_rows"]) == 4
    # a compile inside the loop is the loop's, so the trace's
    mine = [c for c in _spans(trace, names.TRAIN_COMPILE)
            if c["args"]["fun_name"] == "jit(pr35_step)"]
    assert len(mine) == 1 and mine[0]["args"]["trace_id"] == trace_id
    assert _own_args(mine[0], names.TRAIN_COMPILE) == set(names.TRAIN_COMPILE_ARGS)
    assert mine[0]["dur"] > 0 and mine[0]["args"]["seconds"] > 0


def test_children_nest_in_time(session):
    trace = session["trace"]

    def edges(name):
        (s,) = _spans(trace, name)
        return s["ts"], s["ts"] + s.get("dur", 0.0)

    def inside(child, parent, slack_us=1000.0):
        (c0, c1), (p0, p1) = edges(child), edges(parent)
        return p0 - slack_us <= c0 and c1 <= p1 + slack_us

    for child in (names.TRAIN_WORKER_GROUP_START, names.TRAIN_SHARD_DATASETS,
                  names.TRAIN_START_TRAINING, names.TRAIN_DRIVE,
                  names.TRAIN_GROUP_SHUTDOWN, names.TRAIN_LOOP_ENTERED,
                  names.TRAIN_LOOP_DONE, names.DATA_SPLIT):
        assert inside(child, names.TRAIN_FIT), child
    for child in (names.DATA_MATERIALIZE, names.DATA_COUNT_ROWS,
                  names.DATA_SLICE):
        assert inside(child, names.DATA_SPLIT), child
    assert inside(names.DATA_SPLIT, names.TRAIN_SHARD_DATASETS)
    order = [edges(n)[0] for n in (
        names.TRAIN_WORKER_GROUP_START, names.TRAIN_SHARD_DATASETS,
        names.TRAIN_START_TRAINING, names.TRAIN_LOOP_ENTERED,
        names.TRAIN_LOOP_DONE, names.TRAIN_GROUP_SHUTDOWN)]
    assert order == sorted(order)
    (split,) = _spans(trace, names.DATA_SPLIT)
    assert (split["args"]["n"], split["args"]["blocks"],
            split["args"]["rows"]) == (1, 4, 64)


def test_worker_processes_started_and_reaped(session):
    """raylet/worker_start for the process that became the TrainWorker, and
    raylet/worker_reap for it once the group killed it."""
    trace = session["trace"]
    (entered,) = _spans(trace, names.TRAIN_LOOP_ENTERED)
    pid = entered["args"]["pid"]
    starts = _spans(trace, names.RAYLET_WORKER_START)
    reaps = _spans(trace, names.RAYLET_WORKER_REAP)
    for e in starts:
        assert _own_args(e, names.RAYLET_WORKER_START) == set(
            names.RAYLET_WORKER_START_ARGS)
    for e in reaps:
        assert _own_args(e, names.RAYLET_WORKER_REAP) == set(
            names.RAYLET_WORKER_REAP_ARGS)
    (mine,) = [e for e in starts if e["args"]["pid"] == pid]
    assert mine["args"]["kind"] == "actor" and mine["args"]["platform"] == "cpu"
    assert mine["dur"] > 0
    assert any(e["args"]["kind"] == "pooled" for e in starts)
    (gone,) = [e for e in reaps if e["args"]["pid"] == pid]
    assert gone["args"]["timed_out"] is False and gone["args"]["seconds"] >= 0
    # killed by the group, inside fit(): before the driver's shutdown began
    (down,) = _spans(trace, names.DRIVER_SHUTDOWN)
    assert gone["ts"] < down["ts"]
    # the pooled workers go with the raylet, after the GCS stopped answering:
    # their reaps reach the record by the file route
    # (a pooled worker that died by itself earlier was collected unseen)
    pooled = {e["args"]["pid"] for e in starts if e["args"]["kind"] == "pooled"}
    reaped = {e["args"]["pid"] for e in reaps}
    assert pooled & reaped and reaped <= pooled | {pid}


def test_shutdown_names_the_processes_it_waited_on(session):
    trace = session["trace"]
    (down,) = _spans(trace, names.DRIVER_SHUTDOWN)
    waits = _spans(trace, names.DRIVER_WAIT_PROCESS)
    assert sorted(w["args"]["name"].split("-")[0] for w in waits) == [
        "gcs", "raylet"]
    for w in waits:
        assert _own_args(w, names.DRIVER_WAIT_PROCESS) == set(
            names.DRIVER_WAIT_PROCESS_ARGS)
        assert w["args"]["killed"] is False
        assert down["ts"] <= w["ts"] and (
            w["ts"] + w["dur"] <= down["ts"] + down["dur"] + 1000.0)


def test_poll_span_is_gone_and_the_poll_task_is_a_slice(session):
    """`ray_tpu:train/poll` had no reader; each TrainWorker.poll actor task
    is already a slice with the same edges."""
    trace = session["trace"]
    assert not [e for e in trace
                if e.get("cat") == "train" and e["name"] == "poll"]
    polls = [e for e in trace if e["ph"] == "X" and e["name"] == "poll"
             and e.get("cat") == "actor_task"]
    assert polls and all(p["dur"] >= 0 for p in polls)
    assert not hasattr(names, "TRAIN_POLL")


# ------------------------------ what set-up and teardown used to hide (PR 68)
def test_actor_class_load_is_a_span_between_start_and_constructor(session):
    trace = session["trace"]
    (load,) = [e for e in _spans(trace, names.WORKER_LOAD_CLASS)
               if e["args"]["kind"] == "actor"]
    assert _own_args(load, names.WORKER_LOAD_CLASS) == set(
        names.WORKER_LOAD_CLASS_ARGS)
    args = load["args"]
    assert args["name"] == "TrainWorker" and args["bytes"] > 0
    # unpickling the class imports ray_tpu.train in the new process
    assert args["modules_imported"] > 0
    assert load["dur"] / 1e6 == pytest.approx(args["seconds"], abs=0.01)
    # the creating call's task: the constructor's slice starts where it ends
    (init,) = [e for e in trace if e["ph"] == "X"
               and e["name"] == "TrainWorker.__init__"]
    assert args["task_id"] == init["args"]["task_id"]
    assert load["ts"] + load["dur"] <= init["ts"] + 1000.0
    # on the worker's own row, after the raylet saw that process register
    (entered,) = _spans(trace, names.TRAIN_LOOP_ENTERED)
    assert (load["pid"], load["tid"]) == (entered["pid"], entered["tid"])
    (start,) = [e for e in _spans(trace, names.RAYLET_WORKER_START)
                if e["args"]["pid"] == entered["args"]["pid"]]
    assert start["ts"] + start["dur"] <= load["ts"] + 1000.0
    (group,) = _spans(trace, names.TRAIN_START_TRAINING)
    assert load["ts"] + load["dur"] <= group["ts"] + group["dur"]
    # a plain task's first load of a function is the same span, kind "task"
    tasks = [e for e in _spans(trace, names.WORKER_LOAD_CLASS)
             if e["args"]["kind"] == "task"]
    assert tasks and all(e["dur"] >= tracing.PROFILE_MIN_DUR_S * 1e6
                         for e in tasks)
    assert {e["args"]["name"] for e in tasks} <= {
        "_run_read_task", "_run_map_task", "block_num_rows", "_slice_block"}


def test_backend_init_is_observed_on_the_loops_thread(session):
    trace = session["trace"]
    (init,) = _spans(trace, names.TRAIN_BACKEND_INIT)
    (entered,) = _spans(trace, names.TRAIN_LOOP_ENTERED)
    (done,) = _spans(trace, names.TRAIN_LOOP_DONE)
    assert init["args"]["platform"] == "cpu" and init["args"]["rank"] == 0
    assert init["args"]["devices"] >= 1
    assert init["dur"] / 1e6 == pytest.approx(init["args"]["seconds"], abs=0.01)
    # the loop's own first use of JAX: on its task, inside the loop
    assert init["args"]["task_id"] == entered["args"]["task_id"]
    assert entered["ts"] <= init["ts"] and init["ts"] + init["dur"] <= done["ts"]


def test_a_kill_says_what_it_did(session):
    trace = session["trace"]
    kills = [e for e in _spans(trace, names.GCS_KILL_ACTOR)
             if e["args"]["class_name"] == "TrainWorker"]
    for e in kills:
        assert _own_args(e, names.GCS_KILL_ACTOR) == set(
            names.GCS_KILL_ACTOR_ARGS)
    (group,) = _spans(trace, names.TRAIN_GROUP_SHUTDOWN)
    first = kills[0]
    assert first["args"]["outcome"] == names.KILL_REAPED
    assert (first["args"]["forwarded"], first["args"]["node_alive"],
            first["args"]["had_address"]) == (True, True, True)
    assert group["ts"] <= first["ts"] and (
        first["ts"] + first["dur"] <= group["ts"] + group["dur"] + 1000.0)
    assert group["args"]["killed"] == group["args"]["gone_at_return"] == 1
    assert group["args"]["kill_errors"] == []
    # a later kill of the same, dead actor (its handle's release) finds no
    # address to forward to, and says so
    for e in kills[1:]:
        assert e["args"]["outcome"] == "not_forwarded"
        assert e["args"]["had_address"] is False
    # and the reaps say who killed: the group's kill, then the raylet's own
    # at its SIGTERM — or, of a pooled worker still leased to the driver
    # when it disconnected, that owner's exit
    (entered,) = _spans(trace, names.TRAIN_LOOP_ENTERED)
    causes = {e["args"]["pid"]: e["args"]["cause"]
              for e in _spans(trace, names.RAYLET_WORKER_REAP)}
    assert causes.pop(entered["args"]["pid"]) == names.REAP_KILL_ACTOR
    assert causes and set(causes.values()) <= {names.REAP_SIGTERM,
                                               names.REAP_EXIT}


def test_the_record_accounts_for_itself(session):
    trace = session["trace"]
    (summary,) = _spans(trace, names.DRIVER_RECORD_SUMMARY)
    assert summary is max(trace, key=lambda e: e["ts"])     # the last event
    args = summary["args"]
    for row in args["sources"]:
        assert tuple(row) == names.RECORD_SOURCE_ARGS
        assert row["lost"] == 0 == row["dropped"], row
    by_kind = {}
    for row in args["sources"]:
        by_kind.setdefault(row["source"].split("-")[0], []).append(row)
    (driver,) = by_kind["driver"]
    # the driver's shutdown spans at least never went through a flush
    assert driver["recovered"] >= 3
    assert driver["recorded"] == driver["delivered"] + driver["recovered"]
    (raylet,) = by_kind["raylet"]
    assert raylet["recovered"] > 0          # its file: the pooled reaps
    assert by_kind["worker"]
    assert (args["evicted_tasks"], args["truncated_events"],
            args["setup_evicted"]) == (0, 0, 0)
    assert names.TRAIN_FIT in args["unflushed_setup"] or args["in_flight"] == 0
    assert args["flush_age_s"] >= 0 and args["window_s"] >= 0


KILLS = {
    # case: (node alive, the actor's address, what the raylet's call does)
    "reaped": (True, "127.0.0.1:9", True),
    "not_found": (True, "127.0.0.1:9", False),
    "dead_node": (False, "127.0.0.1:9", None),
    "no_address": (True, None, None),
    "connection_lost": (True, "127.0.0.1:9", "ConnectionLost"),
    "rpc_error": (True, "127.0.0.1:9", "RpcError"),
    "unknown_actor": (False, None, None),      # no actor: no node known
}


@pytest.mark.parametrize("case", sorted(KILLS))
def test_handle_kill_actor_records_its_branch(case):
    """Driven directly: every branch of GcsServer.handle_kill_actor leaves
    one gcs/kill_actor span in the aggregator the GCS hosts, with what it
    knew and what came of it; what it swallows is in `error`; the reply is
    the outcome (False for an actor it never heard of), and the actor is
    marked dead whatever the raylet's call did, as ever."""
    import asyncio
    import pickle

    from ray_tpu.core import rpc, task_spec as ts
    from ray_tpu.core.gcs import server as gcs
    from ray_tpu.core.ids import TaskID

    alive, address, call = KILLS[case]
    calls = []

    class Conn:
        async def call(self, method, **kw):
            calls.append((method, kw["actor_id"]))
            if isinstance(call, str):
                raise getattr(rpc, call)("the raylet said " + "no " * 200)
            return call

    srv = gcs.GcsServer()
    aid = b"\x07" * 16
    spec = ts.TaskSpec(task_id=TaskID.from_random(), name="Victim.__init__",
                       fn_id=b"f" * 16, args=[], kwargs={}, num_returns=0,
                       resources={}, owner_addr="127.0.0.1:1")
    if case != "unknown_actor":
        srv.actors[aid] = gcs.ActorInfo(
            actor_id=aid, spec_blob=pickle.dumps(spec), state=gcs.ALIVE,
            address=address, node_id="n0")
    srv.nodes["n0"] = gcs.NodeInfo("n0", "127.0.0.1:8", "s", conn=Conn(),
                                   alive=alive)
    reply = asyncio.run(srv.handle_kill_actor(None, aid))
    (event,) = [e for e in srv.task_events.timeline_events()
                if (e["component"], e["name"]) == ("gcs", "kill_actor")]
    args = event["args"]
    assert tuple(args) == names.GCS_KILL_ACTOR_ARGS
    forwarded = case in ("reaped", "not_found", "connection_lost", "rpc_error")
    outcome = case if forwarded or case == "unknown_actor" else "not_forwarded"
    assert args["outcome"] == outcome and args["forwarded"] is forwarded
    assert calls == ([("kill_actor_worker", aid)] if forwarded else [])
    assert (args["node_alive"], args["had_address"]) == (alive, bool(address))
    assert args["actor_id"] == aid.hex() and args["no_restart"] is True
    assert args["state"] == (None if case == "unknown_actor" else gcs.ALIVE)
    assert event["dur"] == args["seconds"] >= 0
    if isinstance(call, str):
        assert args["error"].startswith(f"{call}: the raylet said no")
        assert len(args["error"]) == 200
    else:
        assert args["error"] is None
    if case == "unknown_actor":
        assert reply is False and args["class_name"] is None
    else:
        assert reply == outcome and args["class_name"] == "Victim"
        assert srv.actors[aid].state == gcs.DEAD
    # the GCS's events reach the aggregator it hosts directly
    assert len(tracing.get_buffer()) == 0 or all(
        e["component"] != "gcs" for e in tracing.get_buffer()._events)


def test_gcs_refuses_events_once_the_record_is_closed():
    """A flush that landed after the closing fetch and was acknowledged
    would be in no copy (the fetched one is made, the source's WAL shrinks
    on the ack): the GCS refuses it, so the source's WAL keeps the batch."""
    from ray_tpu.core.gcs import server as gcs

    srv = gcs.GcsServer()
    event = {"task_id": "t", "name": "loop_done", "component": "train",
             "state": "PROFILE", "ts": 1.0, "worker": "w:1"}
    assert srv.handle_report_task_events(
        None, [event], source="worker-a", recorded=1, delivered=1,
        worker="w:1") is True
    reply = srv.handle_close_session_record(None)
    assert reply["events"] == [event]
    assert reply["accounting"]["sources"]["worker-a"]["delivered"] == 1
    with pytest.raises(RuntimeError, match="record is closed"):
        srv.handle_report_task_events(None, [dict(event, ts=2.0)],
                                      source="worker-a", recorded=2,
                                      delivered=2, worker="w:1")
    assert srv.task_events.timeline_events() == [event]
    # the source's side of it: a refused flush counts its batch as dropped
    # and does not shrink the WAL
    buf = tracing.TaskEventBuffer(capacity=100)
    buf.record(task_id="t", name="x", state="SUBMITTED")
    batch, _ = buf.drain()
    buf.note_dropped(len(batch))
    assert buf.counts()["dropped"] == 1 and buf.counts()["in_flight"] == 0


@pytest.mark.parametrize("raises", [False, True])
def test_group_shutdown_says_what_its_kills_did(monkeypatch, raises):
    """WorkerGroup.shutdown swallows what a kill raises, as before, and
    hands back what it swallowed beside what the kills did."""
    from ray_tpu import api
    from ray_tpu.train.worker_group import WorkerGroup

    class Handle:
        def __init__(self, i):
            self._actor_id = i

    class Backend:
        def kill_actor(self, actor_id, no_restart):
            assert no_restart is True
            if raises and actor_id == 1:
                raise RuntimeError("gcs gone " + "x" * 300)
            return (names.KILL_REAPED, "not_forwarded", None)[actor_id]

    class Worker:
        backend = Backend()

    monkeypatch.setattr(api, "_global_worker", lambda: Worker)
    group = WorkerGroup.__new__(WorkerGroup)
    group.num_workers, group.placement_group = 3, None
    group.workers = [Handle(0), Handle(1), Handle(2)]
    told = group.shutdown()
    assert tuple(told) == names.TRAIN_GROUP_SHUTDOWN_ARGS
    assert (told["num_workers"], told["killed"], told["gone_at_return"]) == (
        3, 3, 1)
    if raises:
        (error,) = told["kill_errors"]
        assert error.startswith("RuntimeError: gcs gone x") and len(error) == 200
    else:
        assert told["kill_errors"] == []


def test_a_flush_held_between_drain_and_ack_loses_nothing(monkeypatch):
    """The parent's fault: flush_loop popped a batch, shutdown() fetched the
    aggregator's events and later drained the buffer, and a batch between
    the pop and the GCS's ingest was in neither — `train/fit`, closed a
    moment before shutdown(), most often. Here the driver's flush is HELD
    after its drain: the batch never reaches the GCS, and the record still
    has it, says where it came from and counts nothing lost."""
    import asyncio
    import time

    monkeypatch.setattr(_config, "task_events_flush_interval_ms", 100)
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=1, num_tpus=0)
    try:
        core = ray_tpu.api._global_worker().backend.core
        buf = tracing.get_buffer()
        real_call, held = core.gcs.call, []

        async def call(method, *a, **kw):
            if method != "report_task_events":
                return await real_call(method, *a, **kw)
            held.append(len(kw["events"]))
            await asyncio.Event().wait()         # the ack never comes

        # hold the flush loop at its sleep while the span is recorded, so
        # that the very next drain pops it
        deadline = time.monotonic() + 20
        while len(buf) and time.monotonic() < deadline:
            time.sleep(0.01)
        monkeypatch.setattr(core.gcs, "call", call)
        with tracing.trace_context("trace-pr68"), tracing.named_span(
                names.TRAIN_FIT, {"name": "held", "attempt": 0,
                                  "num_workers": 0, "tpus_per_worker": 0}):
            pass
        while (len(buf) or not held) and time.monotonic() < deadline:
            time.sleep(0.01)
        assert held and len(buf) == 0      # popped, sent, never acknowledged
    finally:
        ray_tpu.shutdown()
    trace = ray_tpu.timeline()
    (fit,) = [e for e in _spans(trace, names.TRAIN_FIT)
              if e["args"]["trace_id"] == "trace-pr68"]
    assert fit["args"]["name"] == "held"
    (summary,) = _spans(trace, names.DRIVER_RECORD_SUMMARY)
    args = summary["args"]
    assert args["in_flight"] == held[0] >= 1
    (driver,) = [r for r in args["sources"] if r["source"].startswith("driver-")]
    assert driver["recovered"] > args["in_flight"] - 1 and driver["lost"] == 0
    assert driver["recorded"] == driver["delivered"] + driver["recovered"]
    assert sum(r["lost"] for r in args["sources"]) + args["setup_evicted"] == 0
    from benchmarks.layer_metrics import session_record_lost_events

    assert session_record_lost_events.read(_record_facts(trace)) == 0


def _record_facts(trace):
    from benchmarks.harness import session_record, session_timeline

    return {"summary": {}, "notes": [],
            "session_timeline": session_timeline.parse(trace),
            "session_record": session_record.parse(trace) if trace else None}


NEW_READERS = ("actor_class_load_s", "program_backend_init_s",
               "session_record_lost_events")


@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_reader_on_the_session_and_on_a_record_without_its_span(
        session, metric):
    import importlib

    reader = importlib.import_module(f"benchmarks.layer_metrics.{metric}")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        entry["unit"], entry["layer"], entry["moves"], entry["source"])
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    trace = session["trace"]
    got = reader.read(_record_facts(trace))
    if metric == "session_record_lost_events":
        assert got == 0
    else:
        name = {"actor_class_load_s": names.WORKER_LOAD_CLASS,
                "program_backend_init_s": names.TRAIN_BACKEND_INIT}[metric]
        spans = [e for e in _spans(trace, name)
                 if e["args"].get("kind", "actor") == "actor"]
        assert got == pytest.approx(spans[-1]["dur"] / 1e6, abs=1e-6) and got > 0
    # the parent's record: every span of this PR taken out again
    new = {names.WORKER_LOAD_CLASS, names.TRAIN_BACKEND_INIT,
           names.GCS_KILL_ACTOR, names.DRIVER_RECORD_SUMMARY}
    parent = [e for e in trace if f"{e.get('cat')}/{e['name']}" not in new]
    assert reader.read(_record_facts(parent)) is None
    assert reader.read(_record_facts([])) is None


def test_chip_smoke_tells_the_sessions_story(session):
    import chip_smoke

    lines, failures = chip_smoke.session_story(session["trace"])
    assert failures == []
    text = "\n".join(lines)
    for name in (names.TRAIN_WORKER_GROUP_START, names.DATA_SPLIT,
                 names.DATA_COUNT_ROWS, names.TRAIN_START_TRAINING,
                 names.TRAIN_LOOP_ENTERED, names.TRAIN_LOOP_DONE,
                 names.RAYLET_WORKER_START, names.RAYLET_WORKER_REAP,
                 names.DRIVER_WAIT_PROCESS, names.DRIVER_SHUTDOWN):
        assert name in text, name
    for name in (names.WORKER_LOAD_CLASS, names.TRAIN_BACKEND_INIT,
                 names.GCS_KILL_ACTOR, names.DRIVER_RECORD_SUMMARY):
        assert name in text, name
    assert "TrainWorker: reaped" in text and "gone at return 1 / 1 killed" in text
    assert "lost 0; in flight at shutdown()" in text
    assert lines[0].startswith("fit() trace ") and "attempt 0" in lines[0]
    assert lines[-1].startswith("train/compile: ")
    # a record that lost events, or cannot say, is a failed smoke
    lossy = json.loads(json.dumps(session["trace"], default=str))
    (summary,) = _spans(lossy, names.DRIVER_RECORD_SUMMARY)
    summary["args"]["sources"][0]["lost"] = 7
    assert chip_smoke.session_story(lossy)[1] == [
        "the session's record lost 7 events (driver/record_summary)"]
    lossy.remove(summary)
    assert chip_smoke.session_story(lossy)[1] == [
        "the session's record holds no driver/record_summary: it cannot say "
        "what it lost"]
    # a session that recorded nothing is a failed smoke
    assert chip_smoke.session_story([]) == ([], [
        "the session's record holds no train/fit",
        "the session's record holds no train/loop_entered"])


# ------------------------------------------------------------- retry = trace
def test_a_retry_is_a_second_trace(tmp_path):
    from ray_tpu import train

    flag = tmp_path / "failed-once"

    def loop(config):
        if not os.path.exists(config["flag"]):
            open(config["flag"], "w").close()
            raise RuntimeError("first attempt")
        train.report({"ok": 1})

    ray_tpu.shutdown()
    ray_tpu.init(local_mode=True)
    try:
        result = train.JaxTrainer(
            loop, train_loop_config={"flag": str(flag)},
            scaling_config=train.ScalingConfig(num_workers=1),
            run_config=train.RunConfig(
                failure_config=train.FailureConfig(max_failures=1)),
        ).fit()
    finally:
        ray_tpu.shutdown()
    assert result.error is None and result.metrics["ok"] == 1
    trace = ray_tpu.timeline()           # the local backend's last record
    assert not ray_tpu.is_initialized()
    fits = _spans(trace, names.TRAIN_FIT)
    assert [f["args"]["attempt"] for f in fits] == [0, 1]
    ids = [f["args"]["trace_id"] for f in fits]
    assert all(ids) and ids[0] != ids[1]
    done = _spans(trace, names.TRAIN_LOOP_DONE)
    assert [d["args"]["trace_id"] for d in done] == ids
    assert "first attempt" in done[0]["args"]["error"]
    assert done[1]["args"]["error"] is None
    drives = _spans(trace, names.TRAIN_DRIVE)
    assert "first attempt" in drives[0]["args"]["error"]


# ------------------------------------------------------------ compile events
@pytest.fixture
def buffer(monkeypatch):
    monkeypatch.setattr(_config, "task_events_enabled", True)
    monkeypatch.setattr(_config, "task_events_sample_rate", 1.0)
    buf = tracing.get_buffer()
    buf.drain(10 ** 6)
    yield buf
    buf.drain(10 ** 6)


def _train_events(buf, name):
    return [e for e in buf.drain(10 ** 6)[0]
            if e["component"] == "train" and e["name"] == name]


def test_compile_in_a_train_loop_is_one_event_and_one_listener(buffer):
    import jax
    import jax.numpy as jnp

    from ray_tpu.train.worker_group import TrainWorker

    @jax.jit
    def pr35_unit_fn(x):
        return x * 3 - 1

    def loop(config):
        pr35_unit_fn(jnp.ones(5))

    def run(worker):
        worker.start_training(loop, {})
        worker._thread.join(timeout=60)
        assert not worker._thread.is_alive()
        return [e for e in _train_events(buffer, "compile")
                if e["args"]["fun_name"] == "jit(pr35_unit_fn)"]

    worker = TrainWorker(0, 1)
    (event,) = run(worker)
    assert tuple(event["args"]) == names.TRAIN_COMPILE_ARGS
    assert event["dur"] > 0 and event["args"]["seconds"] > 0
    assert event["args"]["cache"] in (None, "hit", "miss")
    listeners = len(jax._src.monitoring.get_event_duration_listeners())
    # the second call finds the compiled program: no event; the second
    # start_training on the same actor registers no second listener
    assert run(worker) == []
    assert len(jax._src.monitoring.get_event_duration_listeners()) == listeners


def test_loop_instants_carry_rank_pid_and_the_error(buffer):
    from ray_tpu.train.worker_group import TrainWorker

    def loop(config):
        raise ValueError("pr35 boom")

    worker = TrainWorker(3, 4)
    with tracing.task_context("task-35", "trace-35"):
        worker.start_training(loop, {})
    worker._thread.join(timeout=60)
    events = [e for e in buffer.drain(10 ** 6)[0] if e["component"] == "train"]
    (entered,) = [e for e in events if e["name"] == "loop_entered"]
    (done,) = [e for e in events if e["name"] == "loop_done"]
    assert tuple(entered["args"]) == names.TRAIN_LOOP_ENTERED_ARGS
    assert entered["args"] == {"rank": 3, "pid": os.getpid()}
    assert tuple(done["args"]) == names.TRAIN_LOOP_DONE_ARGS
    assert "pr35 boom" in done["args"]["error"]
    assert entered["trace_id"] == done["trace_id"] == "trace-35"
    assert entered["task_id"] == "task-35" and entered.get("dur") is None
    assert isinstance(worker.session.error, ValueError)
    assert [i[0] for i in worker.poll(timeout=0.5)] == ["done"]


# ------------------------------------------------------------------ retention
def _setup_then_polls(agg, polls):
    """A job's set-up (spans of no task, a creation, the split's tasks, one
    start_training with the loop's instants) and then `polls` poll tasks."""
    t = [1000.0]

    def at():
        t[0] += 0.001
        return t[0]

    agg.ingest([
        {"name": "fit", "component": "train", "state": "PROFILE", "ts": at(),
         "dur": 5.0, "trace_id": "tr"},
        {"name": "split", "component": "data", "state": "PROFILE", "ts": at(),
         "dur": 3.0, "trace_id": "tr"},
        {"name": "worker_start", "component": "raylet", "state": "PROFILE",
         "ts": at(), "dur": 1.0},
    ])
    setup_tasks = [("init", "TrainWorker.__init__"), ("start", "start_training")]
    setup_tasks += [(f"count-{i}", "block_num_rows") for i in range(16)]
    setup_tasks += [(f"map-{i}", "_run_map_task") for i in range(16)]
    for tid, name in setup_tasks:
        agg.ingest([{"task_id": tid, "name": name, "state": s, "ts": at(),
                     "job_id": "j", "trace_id": "tr"}
                    for s in ("SUBMITTED", "RUNNING", "FINISHED")])
    agg.ingest([{"task_id": "start", "name": n, "component": "train",
                 "state": "PROFILE", "ts": at(), "job_id": "j"}
                for n in ("loop_entered", "compile")])
    for i in range(polls):
        agg.ingest([{"task_id": f"poll-{i}", "name": "poll", "state": s,
                     "ts": at(), "job_id": "j", "trace_id": "tr"}
                    for s in ("SUBMITTED", "RUNNING", "FINISHED")]
                   + [{"name": "core.get", "component": "core",
                       "state": "PROFILE", "ts": at(), "dur": 0.5}])
    return [tid for tid, _ in setup_tasks]


def test_setup_survives_six_thousand_later_polls():
    agg = tracing.TaskEventAggregator()       # the configured caps
    assert _config.task_events_max_tasks_per_job < 6000
    setup = _setup_then_polls(agg, 6000)
    for tid in setup:
        task = agg.get_task(tid)
        assert task is not None and task["state"] == "FINISHED", tid
    start = agg.get_task("start")
    assert [e["name"] for e in start["events"] if e["state"] == "PROFILE"] == [
        "loop_entered", "compile"]
    # the polls evicted polls: the oldest, and as many as came too many
    assert agg.get_task("poll-0") is None
    assert agg.get_task("poll-5999") is not None
    summary = agg.summarize()
    cap = _config.task_events_max_tasks_per_job
    assert summary["tasks"]["poll"]["FINISHED"] == cap - len(setup)
    assert summary["evicted_per_job"]["j"] == 6000 + len(setup) - cap
    events = agg.timeline_events(limit=10 ** 9)
    kept = {(e.get("component"), e["name"]) for e in events
            if e["state"] == "PROFILE" and e.get("task_id") is None}
    assert {("train", "fit"), ("data", "split"),
            ("raylet", "worker_start")} <= kept


def test_setup_spans_outlive_the_other_spans_queue():
    agg = tracing.TaskEventAggregator(max_profile_events=50)
    agg.ingest([{"name": "fit", "component": "train", "state": "PROFILE",
                 "ts": 1.0, "dur": 1.0}])
    agg.ingest([{"name": "core.get", "component": "core", "state": "PROFILE",
                 "ts": 2.0 + i, "dur": 0.1} for i in range(500)])
    events = agg.timeline_events(limit=10 ** 9)
    assert len(events) == 51 and events[0]["name"] == "fit"
    # and the snapshot of a restarted head keeps both queues
    again = tracing.TaskEventAggregator(max_profile_events=50)
    again.restore(agg.dump())
    assert again.timeline_events(limit=10 ** 9) == events


def test_span_cap_of_a_task_is_per_span_name():
    agg = tracing.TaskEventAggregator(max_tasks=10, max_events_per_task=5)
    events = [{"task_id": "t", "name": "start_training", "state": "RUNNING",
               "ts": 1.0}]
    events += [{"task_id": "t", "name": "get_block", "state": "PROFILE",
                "ts": 1.0 + i * 1e-3} for i in range(20)]
    events += [{"task_id": "t", "name": "loop_done", "state": "PROFILE",
                "ts": 2.0}]
    agg.ingest(events)
    got = [e["name"] for e in agg.get_task("t")["events"]
           if e["state"] == "PROFILE"]
    assert got == ["get_block"] * 5 + ["loop_done"]
    assert agg.truncated_events == 15
    assert agg.get_task("t")["name"] == "start_training"


def test_a_span_that_arrives_first_does_not_name_the_task():
    agg = tracing.TaskEventAggregator(max_tasks=10, max_tasks_per_job=2)
    agg.ingest([{"task_id": "t", "name": "get_block", "state": "PROFILE",
                 "ts": 1.0, "job_id": "j"}])
    assert agg.get_task("t")["name"] == ""
    agg.ingest([{"task_id": "t", "name": "start_training", "state": "RUNNING",
                 "ts": 0.5, "job_id": "j"}])
    assert agg.get_task("t")["name"] == "start_training"
    # indexed under its name: two polls later it is the polls that go
    for i in range(3):
        agg.ingest([{"task_id": f"p{i}", "name": "poll", "state": "FINISHED",
                     "ts": 2.0 + i, "job_id": "j"}])
    assert agg.get_task("t") is not None
    assert [agg.get_task(f"p{i}") is not None for i in range(3)] == [
        False, False, True]


# --------------------------------------------- the raylet's pool, on its own
def test_pool_records_start_and_reap_of_a_real_process(buffer, monkeypatch):
    import subprocess
    import time

    from ray_tpu.core.raylet import worker_pool

    real_popen = subprocess.Popen

    def sleeper(argv, **kw):
        return real_popen([sys.executable, "-c", "import time; time.sleep(60)"])

    monkeypatch.setattr(worker_pool.subprocess, "Popen", sleeper)
    pool = worker_pool.WorkerPool("127.0.0.1:1", "127.0.0.1:2",
                                  "s-unit-pr35", "n0")
    handle = pool.start_worker(actor_id=b"a", platform="tpu")
    time.sleep(0.02)
    assert pool.on_register(handle.startup_token, "w0", "127.0.0.1:3", None)
    pool.kill_worker(handle)
    pool.reap(handle)
    pool.reap(handle)                      # gone already: no second event
    pool.shutdown()
    events = [e for e in buffer.drain(10 ** 6)[0] if e["component"] == "raylet"]
    (start,) = [e for e in events if e["name"] == "worker_start"]
    (reap,) = [e for e in events if e["name"] == "worker_reap"]
    assert tuple(start["args"]) == names.RAYLET_WORKER_START_ARGS
    assert start["args"] == {"pid": handle.proc.pid, "startup_token": 0,
                             "platform": "tpu", "kind": "actor"}
    assert start["dur"] >= 0.02
    assert tuple(reap["args"]) == names.RAYLET_WORKER_REAP_ARGS
    assert reap["args"]["pid"] == handle.proc.pid
    assert reap["args"]["platform"] == "tpu"
    assert reap["args"]["timed_out"] is False
    assert reap["dur"] == reap["args"]["seconds"] >= 0
    assert handle.proc.returncode is not None


def test_every_setup_name_has_its_args_tuple():
    for name, args in names.SETUP_SPANS.items():
        assert "/" in name and isinstance(args, tuple) and args, name
        assert "parent" not in args and "trace_id" not in args
    assert not set(names.SETUP_SPANS) & set(names.SPANS)


def test_write_wal_is_read_wal(tmp_path):
    path = str(tmp_path / "task_wal" / "raylet-n0.jsonl")
    events = [{"name": "worker_reap", "component": "raylet", "ts": 1.5,
               "dur": 0.25, "args": {"pid": 7, "timed_out": False},
               "task_id": None}]
    assert tracing.events.write_wal(path, events)
    assert tracing.events.write_wal(path, [])
    (back,) = tracing.read_wal(path)
    assert back == {k: v for k, v in events[0].items() if v is not None}


# ------------------------------------------------- the benchmark's readers
RECORD = os.path.join(ROOT, "benchmarks", "testdata",
                      "session-timeline.rehearsal.json.gz")
EXPECTED = os.path.join(ROOT, "benchmarks", "testdata",
                        "session-timeline.rehearsal.expected.json")
READERS = ("fit_to_loop_s", "worker_start_s", "start_training_wait_s",
           "shard_datasets_s", "split_first_task_wait_s", "split_tasks_busy_s",
           "setup_compile_s", "program_compiles_in_window")


def _facts(events):
    from benchmarks.harness import session_timeline

    with open(EXPECTED) as f:
        expected = json.load(f)
    facts = {"summary": expected["summary"], "notes": []}
    facts["session_timeline"] = session_timeline.parse(events) if events else None
    return facts, expected


@pytest.mark.parametrize("metric", READERS)
def test_reader_against_the_recorded_session(metric):
    import importlib

    with gzip.open(RECORD, "rt") as f:
        events = json.load(f)
    facts, expected = _facts(events)
    reader = importlib.import_module(f"benchmarks.layer_metrics.{metric}")
    entry = next(m for m in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
                 ["per_layer"] if m["name"] == metric)
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        entry["unit"], entry["layer"], entry["moves"], entry["source"])
    assert entry["workloads"]
    assert reader.read(facts) == pytest.approx(expected["metrics"][metric],
                                               rel=1e-9, abs=1e-9)


@pytest.mark.parametrize("metric", READERS)
def test_reader_finds_nothing_in_an_empty_record(metric):
    import importlib

    reader = importlib.import_module(f"benchmarks.layer_metrics.{metric}")
    facts, _ = _facts([])
    assert reader.read(facts) is None
    # a record with other things in it and none of the program's spans
    facts, _ = _facts([{"name": "f", "cat": "task", "ph": "X", "ts": 1e6,
                        "dur": 5.0, "pid": 1, "tid": 1, "args": {}}])
    assert reader.read(facts) is None


def test_recorded_session_agrees_with_itself():
    """What the numbers mean, on the recorded run: the program's launch and
    split times against the driver's clocks of the same run, and the split's
    parts inside the whole."""
    with gzip.open(RECORD, "rt") as f:
        facts, expected = _facts(json.load(f))
    m = expected["metrics"]
    assert abs(m["fit_to_loop_s"] - expected["driver"]["launch_s"]) < 0.3
    assert abs(m["shard_datasets_s"]
               - expected["driver"]["dataset_materialize_s"]) < 0.1
    assert (m["split_first_task_wait_s"] + m["split_tasks_busy_s"]
            <= m["shard_datasets_s"])
    assert m["program_compiles_in_window"] == expected["compiles_in_window"] == 0
    # the loop's own listener heard the same compiles (rounded to the ms)
    assert abs(m["setup_compile_s"] - expected["compile_events_seconds"]) < 0.01


def test_no_record_means_no_cluster_is_started(monkeypatch):
    """On a tree (or a run) that kept no record the readers must not ask
    ray_tpu.timeline(): disconnected, it would start a cluster to ask."""
    from benchmarks.harness import session_timeline

    ray_tpu.shutdown()
    monkeypatch.setattr(ray_tpu.api._global_worker(), "last_timeline", None)
    monkeypatch.setattr(ray_tpu, "timeline", lambda *a, **k: pytest.fail(
        "timeline() asked with no record"))
    facts = {"summary": {}, "notes": []}
    assert session_timeline.for_facts(facts) is None
    assert session_timeline.fit_to_loop_s(facts) is None
    assert not ray_tpu.is_initialized()
