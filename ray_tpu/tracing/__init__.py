"""Distributed task-event tracing: every process buffers per-task lifecycle
events into a bounded, drop-counting :class:`TaskEventBuffer`; the runtime
flushes batches to a GCS-side :class:`TaskEventAggregator` that backs the
state API (``get_task`` / ``summarize_tasks``) and ``ray_tpu.timeline()``
(Chrome-trace export, one row per node/worker).

Parity: src/ray/core_worker/task_event_buffer.h (per-worker bounded event
buffer, periodic GCS flush) + gcs_task_manager.h (bounded aggregation) +
``ray timeline``.

Model
-----
- Lifecycle states (``SUBMITTED → [PENDING_ARGS_AVAIL →] LEASED → DISPATCHED
  → RUNNING → EXECUTED → FINISHED | FAILED``) are recorded at the layer that
  observes them: the owner records submit/dispatch/terminal states — and
  ``PENDING_ARGS_AVAIL`` for a task it holds until the objects it takes by
  reference exist, which is waiting for a producer and holds no worker —,
  the raylet records the lease grant, the executing worker records
  run/executed.
- One ``trace_id`` is minted per logical request (e.g. a serve request) and
  propagated through ``TaskSpec`` into every nested submission, so a single
  request stitches across processes in the exported timeline.
- ``profile_span("name")`` records user spans into the same plane, tagged
  with the current task/trace — and, when JAX is loaded, onto the
  ``jax.profiler`` clock as ``ray_tpu:<component>/<name>``. The Train path
  (Data iterator, ``train.report``), the garbage collector of a train worker
  and the core worker's periodic loops record their own spans through it;
  ``tracing/names.py`` is the vocabulary of those spans and of the scopes and
  kernel names the model puts on the device.
- A step says what it did (PR 52). The step a factory returns
  (``train/train_step.py``) opens ``ray_tpu:train/step`` (args ``step``)
  around its jitted call, through ``profile_span`` like ``train/report``;
  and a model that offers counters — the expert families': passes over the
  row buffer, pairs landed on the held experts, the fullest expert, a layer
  — returns them from the compiled step as one small array, which
  ``tracing/step_counters.py`` records as ONE ``train/step_counters`` event a
  step, with the same ``step``, once the device has made it: fetched by a
  later step's call after its dispatch, or by the worker's loop thread
  before ``train/loop_done``, never waited for on the loop's path.
- Set-up and teardown are spans too, once an attempt, a split, a process or
  a session and never a step (``names.SETUP_SPANS``). Each attempt of
  ``DataParallelTrainer.fit()`` opens a trace of its own: ``train/fit`` and
  its phases on the driver, the tasks submitted under them (the actor's
  constructor, the Dataset's tasks, ``start_training``, every ``poll``),
  ``train/loop_entered`` / ``train/loop_done`` on the worker's loop thread
  and one ``train/compile`` a backend compile of the worker
  (``tracing/compiles.py``) share its ``trace_id``. ``Dataset.split`` records
  ``data/split`` and its three phases, the raylet ``raylet/worker_start`` and
  ``raylet/worker_reap`` (with its ``cause``) a worker process, the driver
  ``driver/init``, ``driver/shutdown`` and one ``driver/wait_process`` a
  daemon. Two more components since PR 68: ``worker/load_class`` — a
  worker's first load of a function id, the actor's class with every import
  it pulls in, under the creating call's task and trace
  (``core/worker_main.py``) — and ``gcs/kill_actor``, one span a
  ``handle_kill_actor`` call with what the GCS knew and what came of it,
  which the GCS hands straight to the aggregator it hosts. A train worker's
  first JAX backend coming up is ``train/backend_init``, observed from
  JAX's own two log lines and never called (``tracing/backend_init.py``).
- No event is in neither place. The batch ``drain()`` pops stays with the
  buffer as its IN-FLIGHT batch until the aggregator acknowledged it
  (``wal_flushed()``) or the flush failed and it is counted
  (``note_dropped()``: "never retried", as before). Whoever closes a
  process's record takes the in-flight batch with the unflushed events
  (``take_unacked()``): the driver's ``shutdown()``, the raylet's SIGTERM
  handler into its file; a worker's WAL holds both already. The buffer
  counts what became of every event — ``recorded`` = ``delivered`` +
  ``dropped`` + ``taken`` + what it still holds — and its flush loop
  reports ``recorded`` / ``delivered`` / ``dropped`` with each batch.
- The session's record outlives the session and accounts for itself.
  ``shutdown()`` of the driver that started the cluster STOPS ITS OWN FLUSH
  LOOP, then fetches the aggregator's events and its ``accounting()`` before
  it stops anything else — so no batch is popped, sent or acknowledged
  between the fetch and this process's loop going away —, appends what no
  aggregator acknowledged (a batch in flight at the stop, its own shutdown
  spans) and what the raylet and the workers left under the session's
  ``task_wal/`` (the workers' WALs; the raylet writes its last events there
  on SIGTERM), closes with ONE ``driver/record_summary`` event — a row a
  source (``recorded``, ``delivered``, ``recovered``, ``dropped``, ``lost``)
  and the aggregator's ``evicted_tasks`` / ``truncated_events`` /
  ``setup_evicted``: a reader can tell a record that lost events from a
  program that recorded none — and writes one Chrome trace,
  ``/tmp/ray_tpu/<session>/timeline.json``. After ``shutdown()``,
  ``ray_tpu.timeline()`` returns that record instead of starting a cluster
  to ask it (the local backend keeps its last record, and writes no file
  and no summary).
- Retention (``tracing/aggregator.py``) evicts what is most numerous, not
  what is oldest: a job's set-up spans and the lifecycle of its set-up tasks
  are still in the record after any number of later ``poll`` tasks; a
  task's events of ONE span name are a ring that keeps the newest
  (``train/step_counters`` of a long run: its last 256 steps).

Cheap by default: recording is a couple of dict writes behind one lock;
``task_events_enabled=False`` reduces it to a single attribute check, and
``task_events_sample_rate < 1`` keeps/drops whole traces deterministically
(hash of the trace/task id), so a sampled request is never half-recorded.
"""

from ray_tpu.tracing.events import (
    LIFECYCLE_STATES,
    PROFILE_MIN_DUR_S,
    TERMINAL_STATES,
    TaskEventBuffer,
    bg_span,
    current_deadline,
    current_job_id,
    current_task_id,
    current_trace_id,
    deadline_context,
    ensure_trace,
    get_buffer,
    install_gc_spans,
    named_span,
    new_trace_id,
    profile_span,
    read_wal,
    record_named,
    remaining_time_s,
    remove_gc_spans,
    task_context,
    trace_context,
)
from ray_tpu.tracing.aggregator import TaskEventAggregator
from ray_tpu.tracing.timeline import build_chrome_trace

__all__ = [
    "LIFECYCLE_STATES",
    "PROFILE_MIN_DUR_S",
    "TERMINAL_STATES",
    "TaskEventBuffer",
    "TaskEventAggregator",
    "bg_span",
    "build_chrome_trace",
    "current_deadline",
    "current_job_id",
    "current_task_id",
    "current_trace_id",
    "deadline_context",
    "remaining_time_s",
    "ensure_trace",
    "get_buffer",
    "install_gc_spans",
    "named_span",
    "new_trace_id",
    "profile_span",
    "read_wal",
    "record_named",
    "remove_gc_spans",
    "task_context",
    "trace_context",
]
