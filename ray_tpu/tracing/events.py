"""Per-process task-event buffer + trace context propagation.

Parity: src/ray/core_worker/task_event_buffer.h — a bounded per-process
buffer of task state transitions, flushed to the GCS in batches, dropping
(and counting) instead of blocking when full.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
import uuid
import zlib
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.analysis import sanitizers as _san
from ray_tpu.core.config import _config
from ray_tpu.tracing.names import (BG, GC, SPAN_PREFIX,
                                   TASK_PENDING_ARGS_AVAIL)

# compact WAL line encoder: separators + no circular check shave ~40% off
# json.dumps on the per-event hot path; default=str keeps arbitrary span
# args writable
_WAL_ENCODE = json.JSONEncoder(
    separators=(",", ":"), check_circular=False, default=str
).encode

def _wal_line(e: dict) -> str:
    """One event as a WAL line. None fields are dropped: readers use .get(),
    and smaller lines keep the per-event cost down on the worker hot path."""
    return _WAL_ENCODE({k: v for k, v in e.items() if v is not None}) + "\n"


# Typed lifecycle states, in causal order. Not every task visits every
# state: PENDING_ARGS_AVAIL (the reference's name) fires only when the owner
# holds the task because an object it takes by reference is still being made
# — SUBMITTED → PENDING_ARGS_AVAIL is waiting for a producer, not for a
# worker —, LEASED fires only when the grant hits the raylet (cached-lease
# reuse skips it), EXECUTED is the worker-side end of execution (same clock
# as RUNNING, so spans are accurate), FINISHED/FAILED are the owner-side
# terminal verdicts.
SUBMITTED = "SUBMITTED"
PENDING_ARGS_AVAIL = TASK_PENDING_ARGS_AVAIL
LEASED = "LEASED"
DISPATCHED = "DISPATCHED"
RUNNING = "RUNNING"
EXECUTED = "EXECUTED"
FINISHED = "FINISHED"
FAILED = "FAILED"
PROFILE = "PROFILE"  # user/framework span, not a lifecycle transition

LIFECYCLE_STATES = (
    SUBMITTED, PENDING_ARGS_AVAIL, LEASED, DISPATCHED, RUNNING, EXECUTED,
    FINISHED, FAILED,
)
TERMINAL_STATES = (FINISHED, FAILED)


# --------------------------------------------------------------- trace context
# Thread-local (task_id, trace_id) of the task executing on this thread.
# Workers set it around task execution so nested submissions inherit the
# parent task id and the request's trace id; serve routers mint a fresh
# trace id per request when none is active.
_ctx = threading.local()


def current_task_id() -> Optional[str]:
    return getattr(_ctx, "task_id", None)


def current_trace_id() -> Optional[str]:
    return getattr(_ctx, "trace_id", None)


def current_job_id() -> Optional[str]:
    return getattr(_ctx, "job_id", None)


def current_deadline() -> Optional[float]:
    """Absolute wall-clock deadline (time.time() epoch seconds) of the
    request executing on this thread, or None when none is set."""
    return getattr(_ctx, "deadline", None)


def remaining_time_s() -> Optional[float]:
    """Seconds left until the current request's deadline (may be <= 0 once
    expired), or None when no deadline is active. User code running inside
    a deadline-carrying task can cooperate: checkpoint, return a partial
    result, or stop early instead of burning time nobody will wait for."""
    d = getattr(_ctx, "deadline", None)
    if d is None:
        return None
    return d - time.time()


def new_trace_id() -> str:
    return uuid.uuid4().hex


@contextlib.contextmanager
def task_context(task_id: Optional[str], trace_id: Optional[str],
                 job_id: Optional[str] = None,
                 deadline: Optional[float] = None):
    """Execute a task frame: nested submissions see this task as parent,
    ride the same trace, inherit the job (per-job retention), and carry
    the request deadline (overload protection: nested calls never outlive
    their root request's budget)."""
    prev = (getattr(_ctx, "task_id", None), getattr(_ctx, "trace_id", None),
            getattr(_ctx, "job_id", None), getattr(_ctx, "deadline", None))
    _ctx.task_id = task_id
    if trace_id is not None:
        _ctx.trace_id = trace_id
    if job_id is not None:
        _ctx.job_id = job_id
    if deadline is not None:
        _ctx.deadline = deadline
    try:
        yield
    finally:
        (_ctx.task_id, _ctx.trace_id, _ctx.job_id, _ctx.deadline) = prev


@contextlib.contextmanager
def deadline_context(deadline: Optional[float]):
    """Pin an absolute request deadline on the current thread. The
    EARLIER of `deadline` and any already-active deadline wins — a nested
    deployment call can tighten its parent's budget, never extend it."""
    prev = getattr(_ctx, "deadline", None)
    if deadline is not None and prev is not None:
        deadline = min(deadline, prev)
    _ctx.deadline = deadline if deadline is not None else prev
    try:
        yield _ctx.deadline
    finally:
        _ctx.deadline = prev


@contextlib.contextmanager
def trace_context(trace_id: str):
    """Pin a trace id on the current thread (every submission inside the
    block carries it)."""
    prev = getattr(_ctx, "trace_id", None)
    _ctx.trace_id = trace_id
    try:
        yield trace_id
    finally:
        _ctx.trace_id = prev


@contextlib.contextmanager
def ensure_trace():
    """Yield the active trace id, minting one for the duration of the block
    when none is active (the serve entry points use this: a request arriving
    with no trace starts one; a nested call keeps the caller's)."""
    existing = getattr(_ctx, "trace_id", None)
    if existing is not None:
        yield existing
        return
    _ctx.trace_id = tid = new_trace_id()
    try:
        yield tid
    finally:
        _ctx.trace_id = None


# ------------------------------------------------------------------- sampling
def _sampled(trace_id: Optional[str], task_id: Optional[str]) -> bool:
    """Deterministic keep/drop: hash the trace id (whole requests sample
    together across every process) or the task id. Events with neither key
    are always kept (rare: ad-hoc spans outside any task)."""
    rate = _config.task_events_sample_rate
    if rate >= 1.0:
        return True
    key = trace_id or task_id
    if key is None:
        return True
    if rate <= 0.0:
        return False
    return (zlib.crc32(key.encode()) & 0xFFFF) < int(rate * 0x10000)


# ------------------------------------------------------------------ the buffer
class TaskEventBuffer:
    """Bounded, drop-counting per-process event buffer.

    Timestamps are wall-clock but strictly monotonic within the process
    (clamped), so a process's own events always sort in causal order even
    under clock adjustments.
    """

    def __init__(self, capacity: Optional[int] = None):
        # re-entrant: a garbage collection triggered by an allocation made
        # under this lock runs the gc span hook, which records here
        self._lock = _san.make_rlock("tracing.buffer")
        self._capacity = capacity or max(100, _config.task_events_buffer_size)
        self._events: deque = deque()
        # what became of every event that passed the sampler, cumulative,
        # this process: recorded = delivered + dropped + taken + what is
        # still in _events and _in_flight — the buffer never loses count
        self._recorded = 0         # offered (an overflow's drop included)
        self._dropped = 0          # overflowed, or in a flush that failed
        self._delivered = 0        # acknowledged by whoever drained them
        self._taken = 0            # handed to whoever closed the record
        # the batch drain() popped last, until wal_flushed() or note_dropped():
        # in the buffer no longer and at the aggregator not yet
        self._in_flight: List[dict] = []
        self._last_drain = time.time()
        self._last_ts = 0.0
        # process identity defaults: events recorded without an explicit
        # node/worker (profile_span, serve/cgraph spans) are attributed to
        # THIS process, so the timeline renders them on the right row
        self._node_id: Optional[str] = None
        self._worker: Optional[str] = None
        # crash forensics WAL: when enabled (workers), every recorded event
        # is appended to a per-worker file BEFORE the periodic flush, so a
        # SIGKILL loses at most the event being written — the raylet
        # recovers the orphaned file into the aggregator (see
        # node_manager._recover_worker_wal)
        self._wal_path: Optional[str] = None
        self._wal_fd: Optional[int] = None

    def set_identity(self, node_id: Optional[str],
                     worker: Optional[str]) -> None:
        """Set this process's default node/worker attribution (called by
        the backend once its address is known)."""
        self._node_id = node_id
        self._worker = worker

    # ------------------------------------------------------------------- WAL
    def enable_wal(self, path: str) -> bool:
        """Append every subsequent event to ``path`` (JSON lines). O_APPEND
        writes of whole lines, no buffering: a torn final line at SIGKILL is
        tolerated by the reader."""
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        except OSError:
            return False
        with self._lock:
            self._wal_path = path
            self._wal_fd = fd
        return True

    def _wal_append_locked(self, e: dict) -> None:
        if self._wal_fd is None:
            return
        try:
            os.write(self._wal_fd, _wal_line(e).encode())
        except OSError:
            # a full/st-gone disk must never break the hot path; drop the
            # WAL, the in-memory plane keeps working
            try:
                os.close(self._wal_fd)
            except OSError:
                pass
            self._wal_fd = None

    def wal_flushed(self) -> None:
        """The flush loop delivered a drain to the aggregator (its
        acknowledgement is here): the in-flight batch is the aggregator's
        now, and the WAL shrinks to exactly the still-unflushed events.
        Empty buffer (the common
        case — a flush usually drains everything) truncates in place; a
        non-empty buffer REWRITES the file from the in-memory events (an
        atomic tmp+rename, re-opened for appends), so a busy worker's WAL
        never grows past one buffer and crash recovery never replays events
        the aggregator already has."""
        with self._lock:
            self._delivered += len(self._in_flight)
            self._in_flight = []
            if self._wal_fd is None:
                return
            try:
                if not self._events:
                    os.ftruncate(self._wal_fd, 0)
                    return
                tmp = self._wal_path + ".tmp"
                data = "".join(map(_wal_line, self._events)).encode()
                fd = os.open(tmp, os.O_WRONLY | os.O_CREAT | os.O_TRUNC,
                             0o644)
                try:
                    os.write(fd, data)
                finally:
                    os.close(fd)
                os.replace(tmp, self._wal_path)
                # clear BEFORE close/reopen: if either fails, the stale
                # (closed) descriptor number must never be written again —
                # the OS reuses fd numbers, and a later append would
                # corrupt whatever file/socket inherited it
                fd_old, self._wal_fd = self._wal_fd, None
                os.close(fd_old)
                self._wal_fd = os.open(
                    self._wal_path, os.O_WRONLY | os.O_APPEND
                )
            except OSError:
                # a failed shrink only costs WAL compactness, never events
                pass

    # ------------------------------------------------------------- recording
    def enabled(self) -> bool:
        return _config.task_events_enabled

    def _now_locked(self) -> float:
        ts = time.time()
        if ts <= self._last_ts:
            ts = self._last_ts + 1e-6
        self._last_ts = ts
        return ts

    def record(
        self,
        *,
        task_id: Optional[str] = None,
        name: str = "",
        state: str = PROFILE,
        attempt: int = 0,
        parent_id: Optional[str] = None,
        actor_id: Optional[str] = None,
        node_id: Optional[str] = None,
        worker: Optional[str] = None,
        trace_id: Optional[str] = None,
        job_id: Optional[str] = None,
        component: str = "core",
        dur: Optional[float] = None,
        args: Optional[dict] = None,
    ) -> bool:
        """Append one event; returns False when disabled, sampled out, or
        dropped at capacity."""
        if not _config.task_events_enabled:
            return False
        if not _sampled(trace_id, task_id):
            return False
        if job_id is None:
            job_id = current_job_id()
        with self._lock:
            self._recorded += 1
            if len(self._events) >= self._capacity:
                self._dropped += 1
                return False
            e: Dict[str, Any] = {
                "task_id": task_id,
                "name": name,
                "state": state,
                "ts": self._now_locked(),
                "attempt": attempt,
                "parent_id": parent_id,
                "actor_id": actor_id,
                "node_id": node_id if node_id is not None else self._node_id,
                "worker": worker if worker is not None else self._worker,
                "trace_id": trace_id,
                "job_id": job_id,
                "component": component,
            }
            if dur is not None:
                e["dur"] = dur
            if args:
                e["args"] = args
            self._events.append(e)
            self._wal_append_locked(e)
        return True

    def record_profile(self, name: str, dur: Optional[float] = None,
                       *, component: str = "user", node_id=None, worker=None,
                       args: Optional[dict] = None) -> bool:
        """Span/instant event tagged with the current task/trace context."""
        return self.record(
            task_id=current_task_id(), name=name, state=PROFILE,
            trace_id=current_trace_id(), component=component, dur=dur,
            node_id=node_id, worker=worker, args=args,
        )

    def note_dropped(self, n: int) -> None:
        """Count events lost outside the buffer: the in-flight batch of a
        flush whose GCS call failed after the drain (never retried)."""
        with self._lock:
            self._dropped += n
            self._in_flight = []

    # --------------------------------------------------------------- draining
    def drain(self, max_batch: int = 5000) -> Tuple[List[dict], int]:
        """Pop up to ``max_batch`` events plus the cumulative drop count.
        The drop count is CUMULATIVE (not a delta) so the aggregator can
        take a max per source — idempotent under re-reports. The popped
        batch stays with the buffer as its in-flight batch until
        ``wal_flushed()`` (the aggregator has it) or ``note_dropped()`` (it
        is lost, and counted): a batch still in flight when the record is
        closed is ``take_unacked()``'s, with the events not yet popped."""
        out: List[dict] = []
        with self._lock:
            # a batch nobody acknowledged (a drain outside a flush loop: a
            # test's, a reader's) went where its caller took it
            self._taken += len(self._in_flight)
            while self._events and len(out) < max_batch:
                out.append(self._events.popleft())
            self._in_flight = out
            self._last_drain = time.time()
            dropped = self._dropped
        return out, dropped

    def take_unacked(self) -> Tuple[List[dict], int]:
        """Close the record: every event no aggregator has acknowledged —
        the in-flight batch (it may have arrived all the same: whoever merges
        drops duplicates), then the buffer — and how many of them were in
        flight. They are counted as taken, not as delivered or dropped."""
        with self._lock:
            in_flight = len(self._in_flight)
            out = self._in_flight + list(self._events)
            self._in_flight = []
            self._events.clear()
            self._taken += len(out)
        return out, in_flight

    @property
    def worker(self) -> Optional[str]:
        """The ``worker`` this process's events carry (``set_identity``)."""
        return self._worker

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def counts(self, since: Optional[dict] = None) -> Dict[str, Any]:
        """This process's cumulative ``recorded`` / ``delivered`` /
        ``dropped`` / ``taken``, with ``pending`` = what the buffer and its
        in-flight batch hold now and ``flush_age_s`` since the last drain.
        With ``since`` (an earlier ``counts()``: a flush loop's start, a
        driver's ``init()``) the four are what happened after it — the buffer
        is process-global and outlives clusters —, what was pending then
        counted as recorded since."""
        with self._lock:
            out = {"recorded": self._recorded, "delivered": self._delivered,
                   "dropped": self._dropped, "taken": self._taken,
                   "pending": len(self._events) + len(self._in_flight),
                   "in_flight": len(self._in_flight),
                   "flush_age_s": time.time() - self._last_drain}
        if since is not None:
            for k in ("recorded", "delivered", "dropped", "taken"):
                out[k] -= since[k]
            out["recorded"] += since["pending"]
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)


_buffer: Optional[TaskEventBuffer] = None
_buffer_lock = _san.make_lock("tracing.buffers_global")


def get_buffer() -> TaskEventBuffer:
    """The process-wide buffer (one per process, like the metrics registry)."""
    global _buffer
    if _buffer is None:
        with _buffer_lock:
            if _buffer is None:
                _buffer = TaskEventBuffer()
    return _buffer


async def flush_task_events_loop(buf: TaskEventBuffer, get_conn,
                                 source: str, use_notify: bool = False):
    """Shared GCS flush loop (CoreWorker + raylet): drain → skip when there
    is no news (the drop counter is cumulative, so an unchanged value needs
    no re-report) → report; events that can't reach the GCS are counted as
    dropped, never retried (task_event_buffer.h semantics).

    ``get_conn`` returns the CURRENT GCS connection (reconnect loops swap
    it) or None; ``use_notify`` sends one-way frames for callers that must
    not block on the reply (the raylet).

    Drops are reported relative to this loop's START: the buffer is
    process-global and long-lived (a pytest driver outlives many clusters),
    and a fresh GCS must not be told about overflow that happened before it
    existed — ``dropped_at_source`` means "dropped during this cluster's
    lifetime". The reported value stays cumulative and monotonic, so the
    aggregator's per-source max() idempotence is unchanged. Beside it go
    the source's ``recorded`` and ``delivered`` (this batch counted: the
    aggregator that reads the numbers has it), from the same start: what
    the session's record says of a source it last heard from here.

    The drained batch is the buffer's in-flight batch from the drain to the
    acknowledgement: a loop cancelled in between (``shutdown()`` of the
    driver that closes the record) leaves it there for ``take_unacked()``."""
    import asyncio

    from ray_tpu.core import rpc

    period = max(_config.task_events_flush_interval_ms, 100) / 1000
    start = buf.counts()
    last_dropped = 0
    while True:
        await asyncio.sleep(period)
        with bg_span("task_event_flush") as tick:
            events, _ = buf.drain()
            tick.args = {"events": len(events)}
        now = buf.counts(since=start)
        dropped = now["dropped"]
        if not events and dropped == last_dropped:
            continue
        conn = get_conn()
        if conn is None or conn.closed:
            if events:
                buf.note_dropped(len(events))
            continue
        try:
            send = conn.notify if use_notify else conn.call
            await send("report_task_events", events=events, dropped=dropped,
                       source=source, recorded=now["recorded"],
                       delivered=now["delivered"] + len(events),
                       worker=buf.worker)
            last_dropped = dropped
            # flushed events are aggregated: the crash-forensics WAL only
            # needs to keep the unflushed tail
            buf.wal_flushed()
        except (rpc.RpcError, rpc.ConnectionLost):
            if events:
                buf.note_dropped(len(events))


def write_wal(path: str, events: List[dict]) -> bool:
    """Append ``events`` to ``path`` in the WAL's format (JSON lines): the
    file route for a process whose last events no flush can carry — the
    raylet after its SIGTERM, when the GCS is going down beside it."""
    if not events:
        return True
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "ab") as f:
            f.write("".join(map(_wal_line, events)).encode())
    except OSError:
        return False
    return True


def read_wal(path: str, max_bytes: Optional[int] = None) -> List[dict]:
    """Parse a worker's WAL file (JSON lines). Tolerates the torn final
    line a SIGKILL mid-write leaves behind; returns [] for a missing or
    empty file. With ``max_bytes``, only the file's final ``max_bytes``
    are decoded (the first, possibly mid-line, row is dropped) — the
    bounded read behind raylet→GCS WAL-tail shipping."""
    import json

    out: List[dict] = []
    try:
        with open(path, "rb") as f:
            if max_bytes is not None:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                if size > max_bytes:
                    f.seek(size - max_bytes)
                    f.readline()  # drop the partial first line
                else:
                    f.seek(0)
            for line in f:
                try:
                    e = json.loads(line)
                except ValueError:
                    continue  # torn tail (or garbage): skip, keep the rest
                if isinstance(e, dict):
                    out.append(e)
    except OSError:
        return []
    return out


# Spans on hot paths (core put/get, the Data iterator, train.report, the
# background loops) reach the buffer only when they lasted this long:
# sub-millisecond calls stay span-free so tight loops don't flood the bounded
# event buffer, and what does arrive is what could explain a slow step.
PROFILE_MIN_DUR_S = 0.001


class profile_span:
    """Time a block and record it as a span attached to the current task and
    trace — the one span primitive, for users and for the runtime's own
    layers::

        with ray_tpu.tracing.profile_span("tokenize"):
            ...

    Two sinks. (a) ``jax.profiler.TraceAnnotation("ray_tpu:<component>/<name>",
    **args)``, only when ``jax`` is already imported (a span never imports
    it): with a profiler session running the span lands on the same clock as
    the device's planes; with none the annotation is an inactive TraceMe.
    (b) the process's :class:`TaskEventBuffer`, as a PROFILE event with
    ``dur`` — when it lasted at least ``min_dur_s`` (hot paths pass
    ``PROFILE_MIN_DUR_S``) and ``task_events_enabled`` is on. A span opened
    inside another on the same thread carries ``parent`` in its event's
    args. ``args`` may be added to until the block ends (the buffer sees
    them all, the annotation those given at entry).
    """

    __slots__ = ("name", "args", "component", "min_dur_s", "_label", "_ann",
                 "_parent", "_t0")

    def __init__(self, name: str, args: Optional[dict] = None,
                 component: str = "user", min_dur_s: float = 0.0):
        self.name = name
        self.args = args
        self.component = component
        self.min_dur_s = min_dur_s
        self._label = f"{SPAN_PREFIX}{component}/{name}"
        self._ann = None

    def __enter__(self) -> "profile_span":
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is not None:
            args = self.args
            self._ann = profiler.TraceAnnotation(
                self._label, **(args if args and "name" not in args else {}))
            self._ann.__enter__()
        stack = getattr(_ctx, "spans", None)
        if stack is None:
            stack = _ctx.spans = []
        self._parent = stack[-1] if stack else None
        stack.append(self._label)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc_info) -> bool:
        dur = time.perf_counter() - self._t0
        _ctx.spans.pop()
        if self._ann is not None:
            self._ann.__exit__(*exc_info)
            self._ann = None
        if dur >= self.min_dur_s and _config.task_events_enabled:
            args = self.args
            if self._parent is not None:
                args = {**(args or {}), "parent": self._parent}
            get_buffer().record_profile(
                self.name, dur=dur, component=self.component, args=args,
            )
        return False


def named_span(full_name: str, args: Optional[dict] = None,
               min_dur_s: float = 0.0) -> profile_span:
    """A span under one of ``tracing/names.py``'s ``<component>/<name>``
    constants, always recorded (set-up and teardown: once an attempt, a
    split or a session, never a step) unless ``min_dur_s`` says from when."""
    component, _, name = full_name.partition("/")
    return profile_span(name, args, component=component, min_dur_s=min_dur_s)


def error_text(e: BaseException) -> str:
    """An exception a span says it swallowed: type and message, cut short."""
    return f"{type(e).__name__}: {e}"[:200]


def record_named(full_name: str, args: Optional[dict] = None,
                 dur: Optional[float] = None) -> bool:
    """An instant (or, with ``dur``, a span that has just ended) under one of
    ``tracing/names.py``'s ``<component>/<name>`` constants, in the buffer
    alone, tagged with the current task and trace."""
    component, _, name = full_name.partition("/")
    return get_buffer().record_profile(
        name, dur=dur, component=component, args=args)


def bg_span(loop: str, args: Optional[dict] = None) -> profile_span:
    """One tick of a periodic loop of this process (``ray_tpu:bg/<loop>``):
    wrap the tick's synchronous part — what holds the GIL against the
    threads doing the work — not its awaited RPC (``rpc.flush`` times
    that side)."""
    return profile_span(loop, args, component=BG,
                        min_dur_s=PROFILE_MIN_DUR_S)


class _GcSpans:
    """``gc.callbacks`` hook: one ``ray_tpu:gc/gen<N>`` span a collection, on
    whichever thread ran it (args generation, and collected once known).
    Reaches the buffer only above ``PROFILE_MIN_DUR_S``: a young-generation
    sweep is microseconds, a full one over a JAX process's heap is what can
    stop a train loop for a tenth of a second."""

    def __init__(self) -> None:
        self._span: Optional[profile_span] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            gen = info["generation"]
            self._span = profile_span(
                f"gen{gen}", {"generation": gen}, component=GC,
                min_dur_s=PROFILE_MIN_DUR_S)
            self._span.__enter__()
        elif self._span is not None:
            span, self._span = self._span, None
            span.args["collected"] = info.get("collected", 0)
            span.__exit__(None, None, None)


_gc_spans = _GcSpans()


def install_gc_spans() -> None:
    """Record garbage collections of this process as spans (idempotent)."""
    import gc

    if _gc_spans not in gc.callbacks:
        gc.callbacks.append(_gc_spans)


def remove_gc_spans() -> None:
    import gc

    if _gc_spans in gc.callbacks:
        gc.callbacks.remove(_gc_spans)
