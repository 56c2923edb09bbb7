"""Runtime configuration flags.

Parity: the reference has a single flag registry (src/ray/common/ray_config_def.h,
205 RAY_CONFIG entries loaded from RAY_<name> env vars). Same pattern here: every
tunable lives in this table, overridable via ``RAY_TPU_<NAME>`` environment
variables, readable as ``ray_tpu._config.<name>``.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field, fields
from typing import Any, Dict


# RAY_TPU_* environment variables that are NOT config-knob overrides
# (addresses, tokens, chaos-plan propagation, sanitizer master switch).
# raylint RT006 checks every RAY_TPU_* literal in the tree against the
# Config fields plus this set, so a typo'd knob name can't silently read
# its default forever.
KNOWN_ENV_VARS = frozenset({
    "RAY_TPU_ADDRESS",
    "RAY_TPU_TOKEN",
    "RAY_TPU_GCS_ADDRESS",
    "RAY_TPU_RAYLET_ADDRESS",
    "RAY_TPU_SESSION",
    "RAY_TPU_NODE_ID",
    "RAY_TPU_STARTUP_TOKEN",
    "RAY_TPU_LOCAL_MODE",
    "RAY_TPU_CHAOS_PLAN",
    "RAY_TPU_CHAOS_LOG",
    "RAY_TPU_SANITIZE",
})


def _env(name: str, default):
    raw = os.environ.get(f"RAY_TPU_{name.upper()}")
    if raw is None:
        return default
    t = type(default)
    if t is bool:
        return raw.lower() in ("1", "true", "yes")
    if t is int:
        return int(raw)
    if t is float:
        return float(raw)
    return raw


@dataclass
class Config:
    # --- scheduling ---------------------------------------------------------
    # Hybrid scheduling: prefer local node until its utilization crosses this
    # threshold, then pack remote nodes (cold-start vs bin-packing tradeoff,
    # mirrors raylet/scheduling/policy/hybrid_scheduling_policy.h).
    scheduler_spread_threshold: float = 0.5
    scheduler_top_k_fraction: float = 0.2
    max_pending_lease_requests_per_scheduling_key: int = 10
    worker_lease_timeout_ms: int = 10_000
    # owner-side lease caching (SchedulingKey reuse): an idle cached lease
    # returns to its raylet after this long without a task
    worker_lease_idle_ttl_ms: int = 500
    # locality-aware lease scheduling: lease requests carry per-arg
    # (oid, nbytes, node) hints, and a raylet choosing between feasible
    # nodes subtracts locality_weight * (resident hinted bytes / total
    # hinted bytes) from each candidate's utilization score — a node
    # already holding the largest args wins ties instead of forcing a
    # transfer. 0 disables locality entirely (hints still ride the wire).
    locality_weight: float = 0.5

    # pipelined task submission (reference: max_tasks_in_flight_per_worker in
    # the direct task submitter, default 10): up to this many submissions
    # share one leased worker concurrently, overlapping the wire round trip
    # of task N+1 with the worker-side execution of task N. Execution stays
    # one-at-a-time via the worker's run slot; a task blocked in get() (or a
    # stream credit wait) hands its slot to the next queued task — the
    # in-process analog of the raylet's blocked-worker resource release — so
    # tasks-that-get-tasks make progress under pipelining. Tasks that block
    # OUTSIDE get() (e.g. on out-of-band rendezvous) no longer require
    # setting this to 1: work stealing migrates their queued peers to idle
    # workers (worker_stealing_enabled).
    worker_max_tasks_in_flight: int = 10
    # bounded commitment for pipelined pushes: a pushed task that cannot
    # START executing within this window bounces back ({"requeue": True})
    # and the owner resubmits it to another worker — the FALLBACK bound
    # behind work stealing (a steal bounces the task the moment an idle
    # worker shows up, this timer covers the no-idle-worker case)
    worker_requeue_after_ms: int = 200
    # pipelined-task work stealing: when a leased worker goes fully idle,
    # the owner asks its most-loaded leased worker (same scheduling key) to
    # give back queued-but-not-started specs, which resubmit to the idle
    # worker immediately instead of waiting out worker_requeue_after_ms
    # behind a long/out-of-band-blocking task
    worker_stealing_enabled: bool = True

    # --- object store -------------------------------------------------------
    object_store_memory_mb: int = 2048
    # objects smaller than this are returned in-band to the owner's memory
    # store instead of the shared-memory store (direct returns).
    max_direct_call_object_size: int = 100 * 1024
    object_spilling_dir: str = ""
    object_store_full_delay_ms: int = 100
    # --- object lifecycle (object_store/lifecycle.py, shm_store.py) ---------
    # proactive spill: a raylet background loop spills cold PRIMARY copies
    # to the session spill dir once in-memory use crosses this fraction of
    # capacity, so eviction under pressure is a cheap unlink and a node
    # death leaves disk copies behind for a survivor to adopt
    object_spill_threshold_frac: float = 0.8
    object_spill_interval_s: float = 1.0
    # owner pin leases: owners renew pins on the raylets holding their
    # primaries every renew interval; the raylet grants each renewal this
    # TTL. A pinned primary may be spilled but is never dropped by
    # pressure; a crashed owner's pins simply age out (ttl >> renew).
    object_pin_ttl_s: float = 30.0
    object_pin_renew_interval_s: float = 5.0

    # --- object plane: pull-based transfer (object_store/pull_manager.py) ---
    # chunked pulls over the stream transport: big objects cross nodes as
    # ~pull_chunk_bytes chunks landing straight into a pre-created
    # create->seal shm buffer, resumable from the next missing chunk after
    # a severed stream; False degrades to the native-daemon / rpc paths
    pull_chunked_enabled: bool = True
    pull_chunk_bytes: int = 4 * 1024 * 1024
    # credits per chunk stream (max unacked chunks in flight per source)
    pull_chunk_window: int = 8
    # objects at least this large with >1 known holder stripe disjoint
    # chunk ranges across sources instead of pulling from one
    pull_stripe_min_bytes: int = 16 * 1024 * 1024
    # max concurrent sources one pull stripes across
    pull_max_stripe: int = 2
    # PullManager admission: total bytes of concurrently-executing pulls on
    # one raylet; excess pulls queue (task-arg pulls ahead of prefetches)
    pull_max_inflight_bytes: int = 256 * 1024 * 1024
    # size-scaled transfer deadline: every fetch/pull call gets
    # base + nbytes/1GiB * per_gb seconds, so multi-GB objects on slow
    # links don't spuriously fail mid-transfer on a fixed timeout
    object_transfer_timeout_base_s: float = 60.0
    object_transfer_timeout_per_gb_s: float = 60.0
    # arg prefetch: a raylet starts pulling a queued lease's remote args
    # (from the request's locality hints) while the lease waits for a
    # worker, overlapping transfer with scheduling delay
    arg_prefetch_enabled: bool = True

    # --- rpc wire path (frame coalescing / zero-copy, core/rpc.py) ----------
    # outbox flushes once per loop tick; past this many buffered bytes it
    # flushes immediately instead of waiting for the tick (latency bound)
    rpc_max_coalesce_bytes: int = 256 * 1024
    # extra gather window before a scheduled flush (0 = next loop tick);
    # raising it trades per-frame latency for bigger gather-writes. With
    # adaptive coalescing on, this is the floor every connection gets; busy
    # connections stretch it up to rpc_adaptive_coalesce_max_ms.
    rpc_coalesce_delay_ms: float = 0.0
    # per-connection adaptive coalescing: a connection whose recent flushes
    # carried many frames each (an EWMA over the last flushes) delays its
    # next flush up to rpc_adaptive_coalesce_max_ms to gather a bigger
    # write; idle / request-response connections keep flushing immediately
    rpc_adaptive_coalesce: bool = True
    rpc_adaptive_coalesce_max_ms: float = 0.5
    # EWMA frames-per-flush at which a connection counts as busy enough to
    # trade latency for gather size
    rpc_adaptive_coalesce_min_frames: float = 6.0
    # backpressure: _send blocks once this many un-flushed bytes are queued
    # on one connection (bounds memory under a slow/stalled peer)
    rpc_max_outstanding_bytes: int = 64 * 1024 * 1024
    # buffers at least this large ride the frame's out-of-band segment
    # table (written from their source buffer, mapped zero-copy on receive)
    rpc_oob_threshold_bytes: int = 64 * 1024
    # owner-side metadata batches (object locations, ref-count releases,
    # shm frees) flush after at most this long off the submit path
    rpc_batch_flush_ms: float = 2.0
    # compiled-graph result reads return read-only numpy views over the
    # shm ring for large arrays (valid until the next execute() on that
    # channel); set False to always copy out
    cgraph_zero_copy_reads: bool = True

    # --- cross-node stream transport (core/transport, cgraph NetChannel) ----
    # host the per-process stream listener binds AND advertises; set
    # 0.0.0.0 (bind-all) plus transport_advertise_host for real multi-host
    transport_bind_host: str = "127.0.0.1"
    # host peers dial; empty = the bind host (or the node's raylet host
    # when binding 0.0.0.0)
    transport_advertise_host: str = ""
    # how long a channel writer waits for the reader's endpoint to appear
    # in the GCS registry + for the TCP connect/handshake
    transport_connect_timeout_s: float = 30.0
    # guard on a single blocking socket send/recv: a peer stalled longer
    # than this severs the stream (typed error, never a silent hang)
    transport_io_timeout_s: float = 120.0

    # --- head-plane durability (GCS snapshot + WAL, core/gcs/) -------------
    # master switch for the write-ahead log: every durable-table mutation
    # (kv, functions, detached actors/PGs, named actors, job counter,
    # channel endpoints) appends a framed record before the RPC reply, so
    # an unclean GCS death loses zero acknowledged mutations
    gcs_wal_enabled: bool = True
    # fsync every WAL record (survives machine power loss, not just process
    # death) — off by default: the page cache already survives SIGKILL, and
    # a per-mutation fsync caps kv throughput at disk latency
    gcs_wal_fsync: bool = False
    # compaction triggers: a full-table snapshot (which also captures the
    # metrics ring, task-event aggregator, and shipped node WAL tails)
    # replaces the log when the active segment outgrows this...
    gcs_wal_max_bytes: int = 8 * 1024 * 1024
    # ...or this much time passed since the last snapshot with mutations
    # pending (the old lossy 1s _snapshot_loop cadence, now only a bound on
    # replay length rather than on durability)
    gcs_snapshot_interval_s: float = 15.0
    # graceful close writes its final snapshot through the compaction
    # executor (never synchronously on the event loop) and waits at most
    # this long; on timeout the WAL alone carries the acknowledged state
    gcs_close_snapshot_timeout_s: float = 10.0
    # raylet -> GCS task-event WAL tail shipping (whole-node-loss
    # forensics): how often each raylet ships its workers' unflushed WAL
    # tails, and the per-worker byte bound on one shipment
    task_events_wal_ship_interval_ms: int = 2_000
    task_events_wal_ship_max_bytes: int = 256 * 1024

    # --- deadline clock-skew guard ------------------------------------------
    # absolute deadlines are wall-clock epoch seconds minted by the owner;
    # a receiving host whose clock disagrees with the owner's by more than
    # this (estimated from the spec's minted (wall, mono) pair) re-anchors
    # the remaining budget to its own clock instead of falsely shedding
    # (task_spec.effective_deadline)
    deadline_skew_tolerance_s: float = 5.0

    # --- timeouts / health --------------------------------------------------
    health_check_period_ms: int = 1_000
    health_check_failure_threshold: int = 5
    gcs_rpc_timeout_s: float = 30.0
    actor_restart_backoff_s: float = 0.5
    # max pipelined in-flight calls per actor (reference seq-no pipelining,
    # direct_actor_task_submitter.h; 1 = strict await-each-response)
    actor_max_inflight_calls: int = 64

    # --- workers ------------------------------------------------------------
    num_workers_soft_limit: int = 0  # 0 = num_cpus
    worker_startup_timeout_s: float = 30.0
    enable_worker_prestart: bool = True
    idle_worker_killing_time_ms: int = 300_000

    # --- retries ------------------------------------------------------------
    task_max_retries: int = 3
    actor_max_restarts: int = 0
    # exponential backoff between system-failure retries (task resubmits,
    # lineage reconstruction, serve failover): delay(n) =
    # min(max, base * multiplier^(n-1)) * (1 ± jitter), seeded deterministic
    # under an active chaos plan (util/backoff.py)
    retry_backoff_base_ms: float = 50.0
    retry_backoff_max_ms: float = 5_000.0
    retry_backoff_multiplier: float = 2.0
    retry_backoff_jitter: float = 0.5

    # --- fault tolerance ----------------------------------------------------
    # compiled graphs: how often a blocked execute()/get() probes participant
    # actor state, so a dead ring surfaces as ActorDiedError instead of
    # burning the caller's full timeout
    cgraph_probe_interval_s: float = 1.0
    # how long dag.recover()/auto_recover waits for RESTARTING participants
    cgraph_recover_timeout_s: float = 60.0
    # driver-side bound on buffered results for refs never get()'d (backstop
    # behind CompiledDAGRef-GC eviction)
    cgraph_result_cache_limit: int = 256
    # serve: retries of a request whose replica died mid-flight (each retry
    # routes to a different, healthy replica)
    serve_request_retries: int = 1
    # serve: default per-request timeout for handle/proxy dispatch and
    # per-chunk stream waits (overridable per deployment via
    # request_timeout_s and per handle via DeploymentHandle.options)
    serve_request_timeout_s: float = 60.0

    # --- serve overload protection ------------------------------------------
    # admission control: default bound on a deployment's router-side queue
    # (in-flight beyond replica capacity); overflow sheds typed
    # BackPressureError instead of queueing unboundedly. Per-deployment
    # override: Deployment.max_queued_requests.
    serve_max_queued_requests: int = 1_000
    # retry budget (SRE-style): every request deposits this fraction of a
    # retry token; failover/recompile retries spend one token each, so
    # total retries are bounded to ~ratio x request rate and cannot
    # amplify an outage
    serve_retry_budget_ratio: float = 0.1
    # the bucket's initial grant: a cold deployment can make this many
    # retries before any traffic has deposited tokens (afterwards the
    # budget is strictly rate-based — ratio x request volume)
    serve_retry_budget_min_tokens: float = 5.0
    # cap of the token bucket (a long quiet period cannot bank an
    # unbounded retry burst)
    serve_retry_budget_burst: float = 50.0
    # circuit breaking: consecutive replica-level failures (death,
    # unavailability, timeouts, slow calls) that eject a replica from
    # routing until a half-open probe succeeds
    serve_circuit_failure_threshold: int = 3
    # how long an open breaker keeps its replica ejected before one
    # half-open probe request is let through
    serve_circuit_cooldown_s: float = 5.0
    # a completed call slower than this counts as a breaker failure
    # (0 = slow-call detection off)
    serve_circuit_slow_call_ms: float = 0.0

    # routers that must agree a replica is circuit-open (each reports its
    # local breaker transitions to the controller) before the controller
    # ejects it FLEET-WIDE: kills the replica and starts a replacement.
    # One flaky router can't decimate a healthy fleet; 0 disables
    # aggregate ejection entirely (reports stay operator-visible only).
    serve_circuit_eject_quorum: int = 2

    # --- serve autoscaling (ray_tpu/autoscaling/) ---------------------------
    # how often the controller's autoscale engine evaluates the policy
    # (its OWN thread — the reconcile loop never blocks on metrics reads)
    serve_autoscale_interval_s: float = 1.0
    # metrics-time-series window the policy reads (QPS, ongoing, queue
    # wait, shed rate are computed over the last window_s of samples)
    serve_autoscale_window_s: float = 30.0
    # a deployment at zero replicas with arrival traffic in the window
    # scales to one immediately (ignoring upscale_delay_s): cold requests
    # are already queued at routers, waiting out a delay only adds latency
    serve_autoscale_zero_wake: bool = True
    # graceful drain: a replica marked DRAINING stops admitting (routers
    # drop it on the next routing-table version), finishes in-flight
    # requests, and is killed when idle — or force-killed at this deadline
    serve_drain_deadline_s: float = 10.0
    # regression bound asserted by tests: the reconcile loop must never
    # stall longer than this between ticks (the old _autoscale blocked it
    # on a 10s ray_tpu.get; the engine thread must not regress this)
    serve_reconcile_max_stall_s: float = 5.0

    # --- cluster autoscaler node tier (autoscaling/engine.py NodeTier) ------
    # demand-driven node loop poll period
    autoscaler_poll_interval_s: float = 1.0
    # node-count bounds the tier converges within
    autoscaler_min_nodes: int = 0
    autoscaler_max_nodes: int = 4
    # one node launch per this window while unserved demand persists
    autoscaler_upscale_delay_s: float = 1.0
    # a tier-launched node with no leases/pending work this long drains
    # (primaries proactively spilled for spill-adoption) and leaves
    autoscaler_idle_timeout_s: float = 30.0

    # --- serve fast-path dispatch (compiled/transport plane) ----------------
    # steady-state unary serve traffic dispatches over router-managed
    # compiled channels (cgraph shm/NetChannel) instead of per-request task
    # submission; the router keeps the slow path for cold start, streaming,
    # failover and admission-shed requests
    serve_fastpath_enabled: bool = True
    # successful routed dispatches to one (deployment, replica) pair before
    # the router warms a compiled channel for it (cold/bursty deployments
    # never pay the compile)
    serve_fastpath_warmup_requests: int = 32
    # pipelining depth of each fast-path channel (compiled-graph
    # max_in_flight); dispatch falls back to the slow path when full
    serve_fastpath_max_in_flight: int = 32
    # only pairs whose recent request latency (EWMA, ms) stays under this
    # warm a channel: slow handlers gain nothing from faster dispatch and
    # lose replica-side concurrency to the (serial) graph loop
    serve_fastpath_max_latency_ms: float = 25.0
    # after a fast-path failure (severed channel, replica death, failed
    # compile) the pair stays demoted to the slow path this long
    serve_fastpath_cooldown_s: float = 5.0
    # per-replica cap on concurrently-open streaming responses: a stream
    # stops debiting unary admission once its header arrives, so without a
    # cap stream fan-out could occupy every replica thread and starve
    # unary requests. 0 disables. Per-deployment: max_ongoing_streams.
    serve_max_ongoing_streams: int = 64

    # --- streaming generators ----------------------------------------------
    # un-acked stream_item pushes a producing worker keeps in flight when no
    # explicit generator_backpressure_num_objects is set (bounds owner-side
    # buffering without serializing the push pipeline)
    streaming_max_inflight_items: int = 64
    # train: per-round driver wait on worker polls before probing liveness
    train_poll_timeout_s: float = 120.0

    # --- logging / events ---------------------------------------------------
    log_to_driver: bool = True
    # tracing (ray_tpu/tracing/): master switch for task-event recording
    task_events_enabled: bool = True
    # deterministic trace/task sampling in [0, 1]: whole traces keep or drop
    # together (hash of the trace/task id), never half-recorded requests
    task_events_sample_rate: float = 1.0
    # per-process bounded buffer; overflow drops (and counts) instead of
    # blocking the hot path (task_event_buffer.h parity)
    task_events_buffer_size: int = 10_000
    task_events_flush_interval_ms: int = 1_000
    # GCS-side retention: max tasks kept in the aggregator (oldest evicted)
    task_events_max_tasks: int = 10_000
    # per-job retention: a chatty job evicts its own oldest tasks before it
    # can push another job's history out of the aggregator
    task_events_max_tasks_per_job: int = 5_000
    # crash forensics: workers append each recorded event to a per-worker
    # WAL file in the session dir before the periodic flush; the raylet
    # recovers a SIGKILLed worker's orphaned WAL into the aggregator so the
    # final second of spans still closes its timeline
    task_events_wal_enabled: bool = True
    metrics_report_interval_ms: int = 2_000
    # master switch for the built-in hot-path instrumentation (serve
    # latency histograms, raylet lease-grant latency, cgraph/streaming
    # series); user-defined metrics are unaffected
    metrics_enabled: bool = True
    # how many merged snapshots the GCS (and local backend) keep as the
    # metrics time series, sampled every metrics_report_interval_ms
    # (240 x 2s = 8 minutes of history by default)
    metrics_timeseries_depth: int = 240

    # --- dev-mode runtime sanitizers (RAY_TPU_SANITIZE=1, analysis/) -------
    # io-loop watchdog: a loop that fails to run a scheduled heartbeat for
    # this long is recorded as a stall violation (a blocking call is
    # squatting the loop). Generous by default: oversubscribed CI boxes
    # legitimately delay thread scheduling.
    sanitize_loop_stall_s: float = 5.0
    # how often the watchdog pings each registered EventLoopThread
    sanitize_loop_ping_interval_s: float = 1.0

    def __post_init__(self):
        for f in fields(self):
            object.__setattr__(self, f.name, _env(f.name, getattr(self, f.name)))

    def to_json(self) -> str:
        return json.dumps({f.name: getattr(self, f.name) for f in fields(self)})

    @staticmethod
    def from_json(s: str) -> "Config":
        cfg = Config()
        for k, v in json.loads(s).items():
            setattr(cfg, k, v)
        return cfg


_config = Config()
