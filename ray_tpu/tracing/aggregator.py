"""GCS-side task-event aggregation with bounded retention.

Parity: src/ray/gcs/gcs_server/gcs_task_manager.h — per-task event storage
with a global task cap, per-task event caps, and drop counters surfaced as
metrics. The same class backs local mode (the LocalBackend owns one and
drains the process buffer into it on query).

Retention rule
--------------
What is recorded once — a ``fit()``'s set-up spans, the lifecycle of its
set-up tasks — is still here after any number of step-rate records of the
same job (6,000 later ``TrainWorker.poll`` tasks evict polls, not the
set-up). Every bound evicts from whatever is most numerous, never simply
from the oldest:

- **Task records, a job** (``task_events_max_tasks_per_job``): over the cap,
  the job's most numerous task *name* loses its oldest record. A job's one
  ``start_training`` and sixteen ``count_rows`` outlive its polls.
- **Task records, all jobs** (``task_events_max_tasks``): oldest first — a
  job's own cap is lower, so one job never gets here alone.
- **PROFILE events of one task** (``max_events_per_task``): a ring a span
  name — beyond the cap each arrival drops that name's OLDEST event of the
  task (``truncated_events`` counts them). A loop's ``train/compile`` and
  ``train/loop_done`` are not crowded out by its ``data/get_block`` — nor by
  ``train/step_counters``, the one name that arrives every step (PR 52): a
  run of 100,000 steps keeps that name's NEWEST ``max_events_per_task``
  steps, the hour the question is about, and counts the rest as truncated.
  Lifecycle events are no span names and are never truncated.
- **Spans with no task** (the driver's, the raylet's, the GCS's): those named in
  ``tracing/names.SETUP_SPANS`` — once an attempt, a split, a process, a kill
  or a session — have a queue of their own (``max_setup_events``; what it
  pushes out is counted, ``setup_evicted``); every other
  span shares ``max_profile_events``, oldest first.

What the record lacks
---------------------
``accounting()`` is the aggregator's half of ``driver/record_summary``
(``tracing/names.py``): a row a source with the cumulative ``recorded`` /
``delivered`` / ``dropped`` its flush loop last reported and ``recovered`` —
the events a WAL replay (a ``wal-`` source: the raylet's recovery of a dead
worker's file) brought for it, matched by the events' ``worker`` —, and the
three retention counters. A source whose last counts never came (a process
killed with -9) stands there with what was last heard.

``timeline_events(limit)`` returns the newest ``limit`` events; the session
record written at ``shutdown()`` asks for all of them.
"""

from __future__ import annotations

import threading
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

from ray_tpu.analysis import sanitizers as _san
from ray_tpu.core.config import _config
from ray_tpu.tracing import events as ev
from ray_tpu.tracing import names


def _terminal_state(states: List[str]) -> Optional[str]:
    # terminal verdicts are sticky: a RUNNING that flushes late (independent
    # 1s flush loops in owner and worker) must never resurrect a task
    if ev.FAILED in states:
        return ev.FAILED
    if ev.FINISHED in states:
        return ev.FINISHED
    return None


_task_hists = None


def _observe_task_duration(rec: dict, e: dict) -> None:
    """Core task latency series, DERIVED at the aggregator from the
    lifecycle events already flowing here — zero additional hot-path cost.
    e2e (SUBMITTED -> terminal) pairs owner-side events, exec (RUNNING ->
    EXECUTED) pairs worker-side events, so each delta stays on one process's
    clock and is immune to cross-host skew."""
    from ray_tpu.core.config import _config

    if not _config.metrics_enabled:
        return
    global _task_hists
    if _task_hists is None:
        from ray_tpu.util import metrics as m

        bounds = [1, 2, 5, 10, 25, 50, 100, 250, 500,
                  1000, 2500, 5000, 10000, 30000, 60000]
        _task_hists = (
            m.Histogram("task_e2e_ms",
                        "task submit -> terminal state (owner clock)",
                        boundaries=bounds, tag_keys=("name",)),
            m.Histogram("task_exec_ms",
                        "task execution RUNNING -> EXECUTED (worker clock)",
                        boundaries=bounds, tag_keys=("name",)),
        )
    e2e_hist, exec_hist = _task_hists
    state = e.get("state")
    tags = {"name": rec.get("name") or "<unnamed>"}
    if state == ev.EXECUTED:
        run = max(
            (x["ts"] for x in rec["events"]
             if x.get("state") == ev.RUNNING
             and x.get("attempt", 0) == e.get("attempt", 0)
             and x.get("ts", 0) <= e.get("ts", 0)),
            default=None,
        )
        if run is not None:
            exec_hist.observe((e["ts"] - run) * 1000, tags)
    elif state in (ev.FINISHED, ev.FAILED):
        sub = min(
            (x["ts"] for x in rec["events"]
             if x.get("state") == ev.SUBMITTED),
            default=None,
        )
        if sub is not None:
            e2e_hist.observe(max(0.0, e["ts"] - sub) * 1000, tags)


def source_row(rows: Dict[str, Dict[str, Any]], source: str) -> Dict[str, Any]:
    """``source``'s row of the record's account, made where it is new."""
    return rows.setdefault(source, {
        "recorded": 0, "delivered": 0, "dropped": 0, "recovered": 0,
        "worker": None})


def credit_recovered(rows: Dict[str, Dict[str, Any]], source: str,
                     worker: Optional[str], n: int) -> None:
    """``n`` events came by a file (``source``: its name), not by a flush:
    they are recovered for the row of that name (the raylet's file is named
    as the raylet reports), else for the source whose events carry this
    ``worker`` (a worker's WAL), else for a row of the file's own — a
    process that never reported. The aggregator's rule for a WAL replay and
    the closing driver's for the files it reads."""
    row = rows.get(source) or next(
        (r for r in rows.values()
         if worker is not None and r.get("worker") == worker), None)
    if row is None:
        row = source_row(rows, source)
        row["worker"] = worker
    row["recovered"] += n


class TaskEventAggregator:
    """Bounded store of per-task event timelines + free-floating spans."""

    def __init__(self, max_tasks: Optional[int] = None,
                 max_events_per_task: int = 256,
                 max_profile_events: int = 20_000,
                 max_tasks_per_job: Optional[int] = None,
                 max_setup_events: int = 2_000):
        self._lock = _san.make_lock("tracing.aggregator")
        self._max_tasks = max_tasks or max(100, _config.task_events_max_tasks)
        self._max_tasks_per_job = max_tasks_per_job or max(
            10, _config.task_events_max_tasks_per_job
        )
        self._max_events_per_task = max_events_per_task
        # task_id -> {"task_id", "name", "actor_id", "job_id", "events": []}
        self._tasks: "OrderedDict[str, dict]" = OrderedDict()
        # per-job retention index: job_id -> task name -> OrderedDict[task_id,
        # None] — a chatty job evicts its OWN tasks before it can push
        # another job's history out of the global window, and of its own the
        # oldest of its most numerous name (module docstring)
        self._job_tasks: Dict[str, Dict[str, "OrderedDict[str, None]"]] = {}
        self._job_counts: Dict[str, int] = {}
        # spans with no task id: what is recorded once an attempt or a
        # session (names.SETUP_SPANS), and everything else (serve request
        # spans, ad-hoc profile spans, a process's bg/gc/core spans)
        self._setup: deque = deque(maxlen=max_setup_events)
        self._profile: deque = deque(maxlen=max_profile_events)
        # drop accounting, surfaced as metrics and in the session's record:
        # source -> its cumulative recorded / delivered / dropped as last
        # reported, the `worker` its events carry, and what WAL replays
        # recovered for that worker (module docstring)
        self._sources: Dict[str, Dict[str, Any]] = {}
        self.evicted_tasks = 0
        self.evicted_per_job: Dict[str, int] = {}
        self.truncated_events = 0
        self.setup_evicted = 0

    # ------------------------------------------------------------- ingestion
    def ingest(self, events: List[dict], dropped: int = 0,
               source: Optional[str] = None, recorded: int = 0,
               delivered: int = 0, worker: Optional[str] = None) -> None:
        with self._lock:
            # WAL recovery replays a dead worker's file; truncation races the
            # kill (flush delivered, worker died before wal_flushed), so a
            # replayed event may already be here. Per-process timestamps are
            # strictly monotonic, making (state, ts, attempt) a reliable
            # identity within one task — recovery is idempotent, duration
            # histograms never double-observe.
            dedup = source is not None and source.startswith("wal-")
            replayed = 0
            if source is not None and not dedup and (
                    dropped or recorded or delivered):
                # sources report cumulative counters; max() is idempotent
                row = source_row(self._sources, source)
                for k, v in (("dropped", dropped), ("recorded", recorded),
                             ("delivered", delivered)):
                    row[k] = max(row[k], int(v))
                row["worker"] = worker or row["worker"]
            for e in events:
                tid = e.get("task_id")
                if tid is None:
                    replayed += 1
                    once = f"{e.get('component')}/{e.get('name')}"
                    if once not in names.SETUP_SPANS:
                        self._profile.append(e)
                        continue
                    if len(self._setup) == self._setup.maxlen:
                        self.setup_evicted += 1
                    self._setup.append(e)
                    continue
                rec = self._tasks.get(tid)
                if dedup and rec is not None:
                    key = (e.get("state"), e.get("ts"), e.get("attempt", 0))
                    if any(
                        (x.get("state"), x.get("ts"), x.get("attempt", 0))
                        == key
                        for x in rec["events"]
                    ):
                        continue
                replayed += 1
                if rec is None:
                    rec = self._tasks[tid] = {
                        "task_id": tid,
                        # a span's name is its own, not the task's
                        "name": "" if e.get("state") == ev.PROFILE
                        else e.get("name") or "",
                        "actor_id": e.get("actor_id"),
                        "job_id": e.get("job_id"),
                        "events": [],
                        "profile_counts": {},
                    }
                    self._index_job_locked(tid, rec)
                    self._evict_locked()
                else:
                    self._tasks.move_to_end(tid)
                if rec.get("actor_id") is None and e.get("actor_id"):
                    rec["actor_id"] = e["actor_id"]
                name = rec["name"] or (
                    e.get("name") if e.get("state") != ev.PROFILE else "")
                job = rec.get("job_id") or e.get("job_id")
                if (name, job) != (rec["name"], rec.get("job_id")):
                    # the task's name or job arrived after its first event:
                    # index the record where it belongs
                    self._unindex_job_locked(tid, rec)
                    rec["name"], rec["job_id"] = name or "", job
                    self._index_job_locked(tid, rec)
                # the cap truncates PROFILE spans only, a span name: lifecycle
                # events are intrinsically bounded (a handful per attempt)
                # and dropping a terminal one would leave a phantom RUNNING
                if e.get("state") == ev.PROFILE:
                    counts = rec["profile_counts"]
                    span = e.get("name") or ""
                    if counts.get(span, 0) >= self._max_events_per_task:
                        # the name's ring is full: its oldest makes room
                        self.truncated_events += 1
                        kept = rec["events"]
                        del kept[next(
                            i for i, x in enumerate(kept)
                            if x.get("state") == ev.PROFILE
                            and (x.get("name") or "") == span)]
                    else:
                        counts[span] = counts.get(span, 0) + 1
                rec["events"].append(e)
                # WAL replays never drive the duration histograms: the
                # record-level dedup above can't see tasks already evicted
                # from retention, and a rare lost last-second observation
                # beats ever double-counting the SLO series
                if not dedup and e.get("state") in (
                        ev.EXECUTED, ev.FINISHED, ev.FAILED):
                    _observe_task_duration(rec, e)
            if dedup and replayed:
                credit_recovered(self._sources, source,
                                 events[0].get("worker"), replayed)

    def accounting(self) -> dict:
        """What the record lacks, as far as this aggregator knows (module
        docstring): the rows of ``driver/record_summary`` and its counters."""
        with self._lock:
            return {
                "sources": {s: dict(r) for s, r in self._sources.items()},
                "evicted_tasks": self.evicted_tasks,
                "truncated_events": self.truncated_events,
                "setup_evicted": self.setup_evicted,
            }

    def _index_job_locked(self, tid: str, rec: dict) -> None:
        """Record tid under its job and name and enforce the per-job cap:
        the job's most numerous name loses its oldest task (jobless events
        ride only the global cap)."""
        job = rec.get("job_id")
        if job is None:
            return
        by_name = self._job_tasks.setdefault(job, {})
        by_name.setdefault(rec["name"], OrderedDict())[tid] = None
        self._job_counts[job] = self._job_counts.get(job, 0) + 1
        while self._job_counts[job] > self._max_tasks_per_job:
            most = max(by_name.values(), key=len)
            old_tid = next(iter(most))
            self._unindex_job_locked(old_tid, self._tasks.pop(old_tid))
            self.evicted_tasks += 1
            self.evicted_per_job[job] = self.evicted_per_job.get(job, 0) + 1

    def _unindex_job_locked(self, tid: str, rec: dict) -> None:
        job = rec.get("job_id")
        by_name = self._job_tasks.get(job)
        if by_name is None or tid not in by_name.get(rec["name"], ()):
            return
        del by_name[rec["name"]][tid]
        if not by_name[rec["name"]]:
            del by_name[rec["name"]]
        self._job_counts[job] -= 1
        if not by_name:
            del self._job_tasks[job], self._job_counts[job]

    def _evict_locked(self) -> None:
        while len(self._tasks) > self._max_tasks:
            tid, rec = self._tasks.popitem(last=False)
            self._unindex_job_locked(tid, rec)
            self.evicted_tasks += 1

    # ------------------------------------------------- snapshot (durability)
    def dump(self) -> dict:
        """Copy-out of the whole aggregation state for the GCS snapshot
        (head-plane durability): a restarted GCS keeps per-job history and
        closed timelines instead of starting blind. Event dicts are never
        mutated after ingest, so per-record shallow copies suffice."""
        with self._lock:
            return {
                "tasks": [
                    (tid, {**rec, "events": list(rec["events"])})
                    for tid, rec in self._tasks.items()
                ],
                "profile": list(self._profile),
                "setup": list(self._setup),
                "sources": {s: dict(r) for s, r in self._sources.items()},
                "evicted_tasks": self.evicted_tasks,
                "evicted_per_job": dict(self.evicted_per_job),
                "truncated_events": self.truncated_events,
                "setup_evicted": self.setup_evicted,
            }

    def restore(self, state: Optional[dict]) -> None:
        """Load a dump() (restart restore). Replaces current state; the
        per-job retention index is rebuilt from the records."""
        if not state:
            return
        with self._lock:
            self._tasks.clear()
            self._job_tasks.clear()
            self._job_counts.clear()
            for tid, rec in state.get("tasks", []):
                rec.setdefault("profile_counts", {})
                self._tasks[tid] = rec
                self._index_job_locked(tid, rec)
            self._profile.clear()
            self._profile.extend(state.get("profile", ()))
            self._setup.clear()
            self._setup.extend(state.get("setup", ()))
            self._sources = {s: dict(r) for s, r in
                             state.get("sources", {}).items()}
            self.evicted_tasks = state.get("evicted_tasks", 0)
            self.evicted_per_job = dict(state.get("evicted_per_job", {}))
            self.truncated_events = state.get("truncated_events", 0)
            self.setup_evicted = state.get("setup_evicted", 0)

    # --------------------------------------------------------------- queries
    @staticmethod
    def _latest(rec: dict) -> dict:
        evs = sorted(rec["events"], key=lambda e: e.get("ts", 0))
        states = [e["state"] for e in evs if e["state"] != ev.PROFILE]
        state = _terminal_state(states) or (states[-1] if states else "UNKNOWN")
        last = evs[-1] if evs else {}
        return {
            "task_id": rec["task_id"],
            "name": rec["name"],
            "state": state,
            "actor_id": rec.get("actor_id"),
            "node_id": last.get("node_id"),
            "worker": last.get("worker"),
            "trace_id": next(
                (e["trace_id"] for e in evs if e.get("trace_id")), None
            ),
            "time": last.get("ts"),
            "num_events": len(evs),
        }

    def get_task(self, task_id: str) -> Optional[dict]:
        with self._lock:
            rec = self._tasks.get(task_id)
            if rec is None:
                return None
            out = self._latest(rec)
            out["events"] = sorted(
                rec["events"], key=lambda e: e.get("ts", 0)
            )
            out["dropped_at_source"] = self._dropped_locked()
            return out

    def list_tasks(self, limit: int = 1000) -> List[dict]:
        with self._lock:
            recs = list(self._tasks.values())[-limit:]
            return [self._latest(r) for r in recs]

    def summarize(self) -> dict:
        """Counts by function name and state (state-API summarize_tasks)."""
        with self._lock:
            by_name: Dict[str, Dict[str, int]] = {}
            for rec in self._tasks.values():
                row = self._latest(rec)
                per = by_name.setdefault(row["name"] or "<unnamed>", {})
                per[row["state"]] = per.get(row["state"], 0) + 1
            return {
                "tasks": by_name,
                "total_tasks": len(self._tasks),
                "dropped_at_source": self._dropped_locked(),
                "evicted_tasks": self.evicted_tasks,
                "evicted_per_job": dict(self.evicted_per_job),
                "truncated_events": self.truncated_events,
            }

    def _dropped_locked(self) -> int:
        return sum(r["dropped"] for r in self._sources.values())

    def timeline_events(self, limit: int = 50_000) -> List[dict]:
        """Flat, time-sorted event list for Chrome-trace export."""
        with self._lock:
            out: List[dict] = []
            for rec in self._tasks.values():
                out.extend(rec["events"])
            out.extend(self._setup)
            out.extend(self._profile)
        out.sort(key=lambda e: e.get("ts", 0))
        return out[-limit:]

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "task_events_tasks": len(self._tasks),
                "task_events_dropped_at_source": self._dropped_locked(),
                "task_events_evicted_tasks": self.evicted_tasks,
                "task_events_truncated": self.truncated_events,
            }
