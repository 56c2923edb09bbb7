"""Top-level API: init/shutdown/remote/get/put/wait/kill/cancel/get_actor.

Parity: python/ray/_private/worker.py — `init` (:1106), `get` (:2409), `put`
(:2524), `wait` (:2587); a process-global Worker singleton holds the active
backend. In cluster mode this process is the *driver* (drivers are workers too).
"""

from __future__ import annotations

import atexit
import inspect
import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Union

from ray_tpu.analysis import sanitizers as _san
from ray_tpu.actor import ActorClass, ActorHandle
from ray_tpu.core.backend import Backend
from ray_tpu.core.options import RemoteOptions, options_from_kwargs
from ray_tpu.core.refs import ObjectRef
from ray_tpu.remote_function import RemoteFunction


class Worker:
    """Process-global runtime context (driver or worker)."""

    def __init__(self):
        self.backend: Optional[Backend] = None
        self.mode: Optional[str] = None  # "local" | "cluster" | "worker"
        self.namespace: str = "default"
        # the last finished session's Chrome trace (Backend.session_timeline)
        self.last_timeline: Optional[List[dict]] = None

    @property
    def connected(self):
        return self.backend is not None


_worker = Worker()
_init_lock = _san.make_lock("api.init")


def _global_worker() -> Worker:
    return _worker


def is_initialized() -> bool:
    return _worker.connected


def _auto_init():
    if not _worker.connected:
        init()


def init(
    address: Optional[str] = None,
    *,
    local_mode: Optional[bool] = None,
    num_cpus: Optional[int] = None,
    num_tpus: Optional[int] = None,
    resources: Optional[Dict[str, float]] = None,
    object_store_memory: Optional[int] = None,
    namespace: Optional[str] = None,
    ignore_reinit_error: bool = False,
    log_to_driver: bool = True,
    _node_name: Optional[str] = None,
) -> "Worker":
    """Start (or connect to) a ray_tpu cluster.

    - ``address=None``: start a fresh single-node cluster in subprocesses
      (GCS + raylet + workers), like the reference's `ray.init()`.
    - ``address="host:port"``: connect this driver to an existing GCS.
    - ``local_mode=True``: no processes; run tasks on threads in-process.
    """
    with _init_lock:
        if _worker.connected:
            if ignore_reinit_error:
                return _worker
            raise RuntimeError("ray_tpu.init() called twice (pass ignore_reinit_error=True)")
        if local_mode is None:
            local_mode = os.environ.get("RAY_TPU_LOCAL_MODE", "0") == "1"
        if namespace:
            _worker.namespace = namespace
        if address and address.startswith("ray://"):
            # thin client: proxy everything to a ClientServer on the head
            # (parity: ray.init("ray://...") → util/client/worker.py:81)
            from ray_tpu.client import ClientBackend

            _worker.backend = ClientBackend(address)
            _worker.mode = "client"
        elif local_mode:
            from ray_tpu.core.local_backend import LocalBackend

            _worker.backend = LocalBackend()
            _worker.mode = "local"
        else:
            from ray_tpu.core.cluster_backend import ClusterBackend

            _worker.backend = ClusterBackend(
                address=address,
                num_cpus=num_cpus,
                num_tpus=num_tpus,
                resources=resources,
                object_store_memory=object_store_memory,
                node_name=_node_name,
                log_to_driver=log_to_driver,
            )
            _worker.mode = "cluster"
        atexit.register(shutdown)
        return _worker


def shutdown():
    import sys

    # compiled graphs first: their execution loops block inside channel
    # reads on actor threads — closing the channels releases those threads
    # before the backend tears the actors down (only if cgraph was imported)
    cgraph_mod = sys.modules.get("ray_tpu.cgraph.compiled_dag")
    if cgraph_mod is not None and _worker.backend is not None:
        try:
            cgraph_mod.teardown_all()
        except Exception:  # noqa: BLE001 - best-effort
            pass
    with _init_lock:
        if _worker.backend is not None:
            backend = _worker.backend
            try:
                backend.shutdown()
            finally:
                _worker.last_timeline = backend.session_timeline
                _worker.backend = None
                _worker.mode = None


def remote(*args, **kwargs):
    """@ray_tpu.remote decorator for functions and classes."""

    def make(target):
        if inspect.isclass(target):
            opts = options_from_kwargs(True, **kwargs)
            if opts.max_restarts is None:
                opts.max_restarts = 0
            return ActorClass(target, opts)
        opts = options_from_kwargs(False, **kwargs)
        return RemoteFunction(target, opts)

    if len(args) == 1 and callable(args[0]) and not kwargs:
        return make(args[0])
    if args:
        raise TypeError("@remote takes keyword options only, e.g. @remote(num_cpus=2)")
    return make


def put(value: Any) -> ObjectRef:
    if isinstance(value, ObjectRef):
        raise TypeError("Calling put() on an ObjectRef is not allowed")
    _auto_init()
    return _worker.backend.put(value)


def put_many(values: Sequence[Any]) -> List[ObjectRef]:
    """Batched put: one bookkeeping sweep for the whole list (dispatch-plane
    batching; the cluster backend coalesces location records into a single
    flush). Semantically identical to ``[put(v) for v in values]``."""
    values = list(values)
    for v in values:
        if isinstance(v, ObjectRef):
            raise TypeError("Calling put() on an ObjectRef is not allowed")
    _auto_init()
    return list(_worker.backend.put_batch(values))


def get(
    refs: Union[ObjectRef, Sequence[ObjectRef]], *, timeout: Optional[float] = None
):
    _auto_init()
    single = isinstance(refs, ObjectRef)
    ref_list = [refs] if single else list(refs)
    for r in ref_list:
        if not isinstance(r, ObjectRef):
            raise TypeError(f"get() expects ObjectRef(s), got {type(r)}")
    values = _worker.backend.get(ref_list, timeout)
    return values[0] if single else values


def wait(
    refs: Sequence[ObjectRef],
    *,
    num_returns: int = 1,
    timeout: Optional[float] = None,
    fetch_local: bool = True,
):
    _auto_init()
    refs = list(refs)
    if len(set(refs)) != len(refs):
        raise ValueError("wait() got duplicate ObjectRefs")
    if num_returns > len(refs):
        raise ValueError("num_returns exceeds number of refs")
    return _worker.backend.wait(refs, num_returns, timeout, fetch_local)


def kill(actor: ActorHandle, *, no_restart: bool = True):
    _auto_init()
    _worker.backend.kill_actor(actor._actor_id, no_restart)


def cancel(ref: ObjectRef, *, force: bool = False, recursive: bool = True):
    _auto_init()
    _worker.backend.cancel(ref, force, recursive)


def get_actor(name: str, namespace: Optional[str] = None) -> ActorHandle:
    _auto_init()
    actor_id = _worker.backend.get_named_actor(name, namespace or _worker.namespace)
    return ActorHandle(actor_id, RemoteOptions(), owned=False)


def cluster_resources() -> Dict[str, float]:
    _auto_init()
    return _worker.backend.cluster_resources()


def available_resources() -> Dict[str, float]:
    _auto_init()
    return _worker.backend.available_resources()


def nodes() -> List[dict]:
    _auto_init()
    return _worker.backend.nodes()


def timeline(filename: Optional[str] = None) -> List[dict]:
    """Chrome-trace export of task execution (parity: ray.timeline,
    python/ray/_private/state.py), backed by the tracing subsystem
    (ray_tpu/tracing/): one trace-process row per node, one thread row per
    worker; RUNNING→EXECUTED/FINISHED/FAILED pairs render as complete ("X")
    slices, other lifecycle transitions as instants, profile_span() spans
    as slices on the worker that recorded them. Open the file in
    chrome://tracing or Perfetto. Returns the event list; also writes JSON
    when `filename` is given.

    Connected, it asks the running cluster. After ``shutdown()`` it returns
    the finished session's record and starts nothing: the aggregator's events
    fetched as shutdown began, plus what teardown itself recorded
    (``driver/shutdown`` and the processes it waited on, each worker's
    ``raylet/worker_reap``) — the same list ``shutdown()`` wrote to
    ``/tmp/ray_tpu/<session>/timeline.json`` (cluster backend; the local
    backend has no session directory and only keeps the list). Each
    ``fit()`` attempt is one trace: filter on ``args.trace_id`` of its
    ``train/fit`` span. What a long job still holds of its set-up is the
    aggregator's retention rule (``tracing/aggregator.py``)."""
    import json

    from ray_tpu.tracing import build_chrome_trace
    from ray_tpu.util.state import timeline_events

    if not _worker.connected and _worker.last_timeline is not None:
        out = _worker.last_timeline
    else:
        out = build_chrome_trace(timeline_events())
    if filename:
        with open(filename, "w") as f:
            json.dump(out, f, default=str)
    return out
