"""Cluster backend: the driver/worker side of the multi-process runtime.

Driver mode with no address bootstraps a single-node cluster (GCS + raylet
subprocesses — parity: ray.init() starting gcs_server/raylet via
services.py:1280,1353), then connects a CoreWorker. With an address it
connects to an existing cluster. Worker mode wraps the WorkerAgent's
CoreWorker so nested @remote calls inside tasks submit through the same
runtime.
"""

from __future__ import annotations

import atexit
import concurrent.futures
import os
import subprocess
import sys
import time
import uuid
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ray_tpu import exceptions as exc
from ray_tpu import tracing
from ray_tpu.core import rpc
from ray_tpu.core.backend import Backend
from ray_tpu.core.core_worker import CoreWorker
from ray_tpu.core.ids import ActorID
from ray_tpu.core.options import RemoteOptions
from ray_tpu.core.refs import ObjectRef
from ray_tpu.tracing import aggregator, names


def _session_tmp_dir(session: str) -> str:
    d = os.path.join("/tmp", "ray_tpu", session)
    os.makedirs(os.path.join(d, "logs"), exist_ok=True)
    return d


class ProcessGroup:
    """Daemon subprocesses this driver spawned (killed on shutdown)."""

    def __init__(self, session_dir: str):
        self.session_dir = session_dir
        self.procs: List[subprocess.Popen] = []
        self.names: List[str] = []       # spawn()'s name of each of procs

    def spawn(self, name: str, argv: List[str], env=None) -> subprocess.Popen:
        log = open(os.path.join(self.session_dir, "logs", f"{name}.log"), "ab")
        env = dict(env or os.environ)
        # daemons must import ray_tpu regardless of the driver's cwd/sys.path
        import ray_tpu

        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
        p = subprocess.Popen(argv, stdout=log, stderr=subprocess.STDOUT, env=env)
        self.procs.append(p)
        self.names.append(name)
        return p

    def shutdown(self):
        for p in self.procs:
            try:
                p.terminate()
            except ProcessLookupError:
                pass
        # a raylet exits once its workers are reaped (node_manager.main)
        from ray_tpu.core.raylet.worker_pool import REAP_TIMEOUT_S

        deadline = time.monotonic() + REAP_TIMEOUT_S + 5
        for name, p in zip(self.names, self.procs):
            with tracing.named_span(names.DRIVER_WAIT_PROCESS, {
                    "name": name, "pid": p.pid}) as span:
                t0 = time.monotonic()
                killed = False
                try:
                    p.wait(timeout=max(0.1, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    p.kill()
                    killed = True
                span.args.update(seconds=time.monotonic() - t0, killed=killed)


def _token_path(gcs_address: str) -> str:
    safe = gcs_address.replace(":", "_").replace("/", "_")
    return os.path.join("/tmp", "ray_tpu", f"token-{safe}")


def load_cluster_token(gcs_address: str) -> None:
    """Same-host drivers joining by address pick up the cluster token from
    the file start_gcs wrote (cross-host joins must export RAY_TPU_TOKEN)."""
    if rpc.get_auth_token() is not None:
        return
    try:
        with open(_token_path(gcs_address)) as f:
            rpc.set_auth_token(f.read().strip())
    except OSError:
        pass


def start_gcs(pg: ProcessGroup, port: int = 0) -> str:
    # A fresh cluster mints its session auth token here, before the first
    # daemon spawns: set_auth_token exports RAY_TPU_TOKEN, and every daemon/
    # worker inherits it through its environment (rpc.py handshake). It is also
    # written 0600 to a per-address file so same-host drivers can join by
    # address alone.
    if rpc.get_auth_token() is None:
        import secrets

        rpc.set_auth_token(secrets.token_hex(16))
    port = port or _free_port()
    address = f"127.0.0.1:{port}"
    try:
        path = _token_path(address)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
        with os.fdopen(fd, "w") as f:
            f.write(rpc.get_auth_token())
    except OSError:
        pass
    # fault tolerance: durable tables snapshot next to the session logs, so
    # a restarted GCS on this address recovers KV/functions/detached actors
    store = os.path.join(pg.session_dir, "gcs_store.pkl")
    pg.spawn(
        "gcs",
        [sys.executable, "-m", "ray_tpu.core.gcs.server",
         "--port", str(port), "--store", store],
    )
    return address


def start_raylet(
    pg: ProcessGroup,
    gcs_address: str,
    session: str,
    node_id: str,
    num_cpus=None,
    num_tpus=None,
    resources=None,
    object_store_memory_mb=None,
    port: int = 0,
) -> None:
    import json

    argv = [
        sys.executable, "-m", "ray_tpu.core.raylet.node_manager",
        "--gcs", gcs_address, "--session", session, "--node-id", node_id,
        "--resources", json.dumps(resources or {}),
    ]
    # num_tpus=None: the raylet counts its own host's chips from the device
    # files (core/resources.py). Neither it nor this driver ever touches JAX
    # — a chip belongs to one process, and that process is the leased worker.
    if num_tpus is not None:
        argv += ["--num-tpus", str(num_tpus)]
    if port:
        argv += ["--port", str(port)]
    if num_cpus is not None:
        argv += ["--num-cpus", str(num_cpus)]
    if object_store_memory_mb:
        argv += ["--object-store-memory-mb", str(object_store_memory_mb)]
    pg.spawn(f"raylet-{node_id}", argv)


def _event_key(e: dict) -> tuple:
    """What makes a task event the same event in two copies of a record."""
    return (e.get("task_id"), e.get("name"), e.get("state"), e.get("ts"),
            e.get("worker"))


def record_summary(rows: Dict[str, dict], account: dict,
                   closing: dict) -> dict:
    """``driver/record_summary``'s args (``tracing/names.py``) from the
    sources' rows (source -> ``recorded`` / ``delivered`` / ``recovered`` /
    ``dropped``; ``lost`` is made here), the aggregator's ``accounting()``
    and what the driver noted of its own buffer as it closed the record."""
    sources = []
    for source, row in sorted(rows.items()):
        row = {**row, "source": source, "lost": max(
            row["dropped"],
            row["recorded"] - row["delivered"] - row["recovered"])}
        sources.append({k: row[k] for k in names.RECORD_SOURCE_ARGS})
    return {
        "sources": sources,
        **{k: account.get(k, 0) for k in (
            "evicted_tasks", "truncated_events", "setup_evicted")},
        "in_flight": closing.get("in_flight", 0),
        "unflushed_setup": closing.get("unflushed_setup", []),
        "flush_age_s": closing.get("flush_age_s"),
        "window_s": closing.get("window_s"),
    }


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class ClusterBackend(Backend):
    def __init__(
        self,
        address: Optional[str] = None,
        core_worker: Optional[CoreWorker] = None,
        num_cpus: Optional[int] = None,
        num_tpus: Optional[int] = None,
        resources: Optional[Dict[str, float]] = None,
        object_store_memory: Optional[int] = None,
        node_name: Optional[str] = None,
        log_to_driver: bool = True,
    ):
        self._procs: Optional[ProcessGroup] = None
        if core_worker is not None:  # worker mode
            self.core = core_worker
            return
        self._t_init = time.time()
        # this process's event counts as the session begins: the record's
        # own row is what happened since (`driver/record_summary`)
        self._events_at_init = tracing.get_buffer().counts()
        session = f"s{uuid.uuid4().hex[:10]}"
        with tracing.named_span(names.DRIVER_INIT, {
                "session": session, "started_cluster": address is None}):
            self._connect_driver(
                session, address, num_cpus, num_tpus, resources,
                object_store_memory, node_name)

    def _connect_driver(self, session, address, num_cpus, num_tpus, resources,
                        object_store_memory, node_name) -> None:
        """Start a single-node cluster (no address) or join one, and connect
        this driver's core worker to its GCS and local raylet."""
        node_id = node_name or f"node-{uuid.uuid4().hex[:8]}"
        if address is None:
            self._procs = ProcessGroup(_session_tmp_dir(session))
            gcs_address = start_gcs(self._procs)
            start_raylet(
                self._procs,
                gcs_address,
                session,
                node_id,
                num_cpus=num_cpus,
                num_tpus=num_tpus,
                resources=resources,
                object_store_memory_mb=(
                    object_store_memory // (1024 * 1024)
                    if object_store_memory
                    else None
                ),
            )
        else:
            gcs_address = address
            load_cluster_token(gcs_address)
        # connect driver core worker; discover the local raylet via GCS
        self.core = CoreWorker(
            gcs_address, None, session, node_id, mode="driver"
        )
        self.core.connect()
        raylet_addr, raylet_session, raylet_node = self._wait_local_raylet(
            prefer_node=node_id,
            # an EXPLICIT _node_name pin must wait for that raylet to
            # register, never silently adopt whichever node won the
            # registration race (split-session tests/benches depend on the
            # driver sitting on the named node)
            require=node_name is not None,
        )
        self.core.raylet_address = raylet_addr
        self.core.session = raylet_session
        self.core.node_id = raylet_node
        # rebind shm client to the raylet's session (objects shared on-node)
        from ray_tpu.core.object_store.shm_store import ShmClient

        self.core.shm = ShmClient(raylet_session)
        self.core.raylet = self.core.io.run(
            rpc.connect(raylet_addr, handler=self.core, name="driver->raylet")
        )

    def _wait_local_raylet(self, prefer_node: str, timeout=30.0,
                           require: bool = False):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for p in self._procs.procs if self._procs else ():
                if p.poll() is not None:
                    # e.g. the raylet found chips it cannot open: say so now,
                    # not as a registration timeout 30 s later
                    raise exc.RayTpuError(
                        f"daemon pid={p.pid} exited with {p.returncode} "
                        f"during start-up; its log is under "
                        f"{self._procs.session_dir}/logs"
                    )
            nodes = self.core.io.run(self.core.gcs.call("get_nodes"))
            if nodes:
                node = next(
                    (n for n in nodes if n["NodeID"] == prefer_node),
                    None if require else nodes[0],
                )
                if node is not None and node["Alive"]:
                    return (
                        node["NodeManagerAddress"],
                        node["Session"],
                        node["NodeID"],
                    )
            time.sleep(0.1)
        raise exc.RayTpuError(
            f"raylet {prefer_node!r} not registered within timeout"
            if require else "no raylet registered within timeout"
        )

    # ------------------------------------------------------------- Backend
    def submit_task(self, func, args, kwargs, options):
        return self.core.submit_task(func, args, kwargs, options)

    def create_actor(self, cls, args, kwargs, options):
        return self.core.create_actor(cls, args, kwargs, options)

    def submit_actor_task(self, actor_id, method_name, args, kwargs, options):
        return self.core.submit_actor_task(actor_id, method_name, args, kwargs, options)

    def put(self, value):
        return self.core.put(value)

    def put_batch(self, values):
        return self.core.put_batch(values)

    def get(self, refs, timeout):
        # nested get inside a task (worker mode): advise the raylet so our
        # lease's CPU frees while we block (see worker_main.get_blocking)
        blocking_get = getattr(self.core, "get_blocking", None)
        if blocking_get is not None:
            return blocking_get(refs, timeout)
        return self.core.get(refs, timeout)

    def wait(self, refs, num_returns, timeout, fetch_local):
        return self.core.wait(refs, num_returns, timeout, fetch_local)

    def as_future(self, ref: ObjectRef):
        out: concurrent.futures.Future = concurrent.futures.Future()

        async def resolve():
            try:
                data = await self.core._fetch_serialized(ref, None)
                if isinstance(data, BaseException):
                    e = data
                    if isinstance(e, exc.TaskError):
                        e = e.as_instanceof_cause()
                    out.set_exception(e)
                else:
                    from ray_tpu.core import serialization

                    out.set_result(serialization.loads(data))
            except BaseException as e:  # noqa: BLE001
                out.set_exception(e)

        self.core.io.spawn(resolve())
        return out

    def kill_actor(self, actor_id, no_restart):
        return self.core.kill_actor(actor_id, no_restart)

    # ------------------------------------------------- fault-tolerance plane
    def actor_state(self, actor_id) -> str:
        try:
            info = self.core.io.run(
                self.core._gcs_call_retrying(
                    "get_actor", actor_id=actor_id.binary(), timeout=30
                )
            )
        except (rpc.RpcError, rpc.ConnectionLost, exc.GcsUnavailableError):
            # a GCS blip must NOT read as actor death: callers treat
            # UNKNOWN as maybe-alive (retry/wait), never as terminal
            return "UNKNOWN"
        return "DEAD" if info is None else info["state"]

    def wait_actor_alive(self, actor_id, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        attempt = 0
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise exc.GetTimeoutError(
                    f"actor {actor_id.hex()[:16]} not ALIVE within {timeout}s"
                )
            try:
                info = self.core.io.run(
                    self.core._gcs_call_retrying(
                        "get_actor", actor_id=actor_id.binary(),
                        wait_alive=True,
                        wait_timeout=min(remaining, 10.0), timeout=30,
                    )
                )
                attempt = 0
            except (rpc.RpcError, rpc.ConnectionLost,
                    exc.GcsUnavailableError):
                # head restarting: wait out the reattach window behind the
                # standard jittered backoff instead of a fixed tick
                attempt += 1
                time.sleep(min(remaining,
                               self.core._backoff().delay(attempt)))
                continue
            if info is None or info["state"] == "DEAD":
                reason = (info or {}).get("death_reason", "") or "dead"
                raise exc.ActorDiedError(actor_id, reason)
            if info["state"] == "ALIVE":
                return

    def actor_node(self, actor_id) -> Optional[str]:
        try:
            info = self.core.io.run(
                self.core._gcs_call_retrying(
                    "get_actor", actor_id=actor_id.binary(), timeout=30
                )
            )
        except (rpc.RpcError, rpc.ConnectionLost, exc.GcsUnavailableError):
            return None
        return None if info is None else info.get("node_id")

    def add_actor_listener(self, cb) -> None:
        self.core.add_actor_listener(cb)

    def remove_actor_listener(self, cb) -> None:
        self.core.remove_actor_listener(cb)

    def create_deferred(self):
        from ray_tpu.core import serialization
        from ray_tpu.core.config import _config
        from ray_tpu.core.ids import ObjectID

        core = self.core
        oid = ObjectID.for_put(core.worker_id)
        core._own(oid)
        ref = ObjectRef(oid, owner_addr=core.address)

        def fulfill(value=None, error=None, serialized=None):
            """serialized: already-serialized bytes pass straight into the
            driver store — the serve failover chain uses this so the success
            path never deserializes + re-serializes the replica's response."""
            if error is not None:
                err = (
                    error if isinstance(error, exc.RayTpuError)
                    else exc.TaskError.from_exception(error)
                )
                core.memory_store.put_error(oid, err)
                return
            if serialized is not None:
                data = (
                    serialized if isinstance(serialized, bytes)
                    else bytes(serialized)
                )
            else:
                data = serialization.serialize(value).to_bytes()
            if len(data) <= _config.max_direct_call_object_size:
                core.memory_store.put_value(oid, data)
            else:
                core._put_shm(oid, data)

        return ref, fulfill

    def as_serialized_future(self, ref: ObjectRef):
        """Future resolving to the object's SERIALIZED bytes (exceptions are
        set as exceptions, task errors as their user-facing cause). Pairs
        with create_deferred's fulfill(serialized=...) so framework relays
        (serve failover) can pass bytes through without a decode/encode."""
        out: concurrent.futures.Future = concurrent.futures.Future()

        async def resolve():
            try:
                data = await self.core._fetch_serialized(ref, None)
                if isinstance(data, BaseException):
                    e = data
                    if isinstance(e, exc.TaskError):
                        e = e.as_instanceof_cause()
                    out.set_exception(e)
                else:
                    out.set_result(data)
            except BaseException as e:  # noqa: BLE001
                out.set_exception(e)

        self.core.io.spawn(resolve())
        return out

    def free_actor(self, actor_id):
        # fire-and-forget: this runs from ActorHandle.__del__, which GC may
        # invoke on ANY thread — including the io-loop thread itself, where
        # a blocking kill would deadlock the loop
        try:
            self.core.kill_actor(actor_id, True, wait=False)
        except Exception:  # noqa: BLE001 - interpreter shutdown
            pass

    def cancel(self, ref, force, recursive):
        # a task its owner still holds for its arguments is cancelled there;
        # cooperative cancellation of one a worker already runs lands with
        # the task event channel
        self.core.cancel_task(ref)

    def get_named_actor(self, name, namespace):
        return self.core.get_named_actor(name, namespace)

    def cluster_resources(self):
        nodes = self.core.io.run(self.core.gcs.call("get_nodes"))
        out: Dict[str, float] = {}
        for n in nodes:
            if n["Alive"]:
                for k, v in n["Resources"].items():
                    out[k] = out.get(k, 0) + v
        return out

    def available_resources(self):
        nodes = self.core.io.run(self.core.gcs.call("get_nodes"))
        out: Dict[str, float] = {}
        for n in nodes:
            if n["Alive"]:
                for k, v in n["Available"].items():
                    out[k] = out.get(k, 0) + v
        return out

    def nodes(self):
        return self.core.io.run(self.core.gcs.call("get_nodes"))

    # placement groups (used by util/placement_group.py)
    def create_placement_group(self, pg_id, bundles, strategy, timeout=30.0):
        return self.core.io.run(
            self.core.gcs.call(
                "create_placement_group",
                pg_id=pg_id,
                bundles=bundles,
                strategy=strategy,
                create_timeout=timeout,
                timeout=timeout + 10,
            )
        )

    def remove_placement_group(self, pg_id):
        return self.core.io.run(
            self.core.gcs.call("remove_placement_group", pg_id=pg_id)
        )

    def get_placement_group(self, pg_id):
        return self.core.io.run(
            self.core.gcs.call("get_placement_group", pg_id=pg_id)
        )

    def shutdown(self):
        if self._procs is None:
            # a worker, or a driver that joined a cluster others run: the
            # session and its record are not this process's to close
            self.core.shutdown()
            return
        # the session's record outlives the session. This driver's flush
        # loop is stopped first — from here on nothing leaves its buffer but
        # through _write_session_record, so no batch can be popped, sent or
        # acknowledged after the fetch and be in neither copy —, then what
        # the aggregator holds is fetched, before anything stops; what is
        # recorded from here on (this driver's shutdown spans, the raylet's
        # and the workers' last events in the WAL directory) is appended
        # once every process is gone
        from ray_tpu.core.config import _config

        keep = _config.task_events_enabled
        fetched, closing = {}, {}
        if keep:
            buf = tracing.get_buffer()
            try:
                self.core.io.run(self.core.stop_event_flush(), timeout=10)
            except Exception:  # noqa: BLE001 - a loop already gone
                pass
            at_stop = buf.counts()
            t_stop = time.time()
            closing = {"in_flight": at_stop["in_flight"],
                       "flush_age_s": at_stop["flush_age_s"], "t_stop": t_stop}
            fetched = self._fetch_session_record()
        try:
            with tracing.named_span(names.DRIVER_SHUTDOWN,
                                    {"session": self.core.session}):
                try:
                    self.core.shutdown()
                    if keep:
                        closing["window_s"] = time.time() - t_stop
                finally:
                    self._procs.shutdown()
            if keep:
                self._write_session_record(fetched, closing)
        finally:
            # reclaim tmpfs (real RAM): this driver owns the session
            try:
                from ray_tpu.core.object_store.shm_store import ShmClient

                ShmClient(self.core.session).destroy()
            except Exception:  # noqa: BLE001
                pass

    def _fetch_session_record(self) -> dict:
        """The aggregator's events and its account of what it lacks."""
        try:
            return self.core.io.run(self.core.gcs.call(
                "close_session_record", timeout=30))
        except Exception:  # noqa: BLE001 - a dead GCS: the files still tell
            return {}

    def _write_session_record(self, fetched: dict, closing: dict) -> None:
        """``<session_dir>/timeline.json``: the Chrome trace of the whole
        session — the aggregator's events, every event of this process no
        aggregator acknowledged (a batch in flight when the flush loop was
        stopped, and what is still in the buffer) and the files under the
        session's ``task_wal/`` —, closed by ``driver/record_summary``, the
        record's account of itself (``tracing/names.py``); kept in
        ``session_timeline`` for ``ray_tpu.timeline()`` after shutdown."""
        import glob
        import json

        from ray_tpu.core.object_store.shm_store import session_dir

        events = fetched.get("events") or []
        account = fetched.get("accounting") or {}
        rows = {s: dict(r) for s, r in (account.get("sources") or {}).items()}
        # a worker's last flush may have delivered what its file still holds
        seen = {_event_key(e) for e in events}

        def merge(late: List[dict]) -> int:
            new = [e for e in late if _event_key(e) not in seen]
            seen.update(_event_key(e) for e in new)
            events.extend(new)
            return len(new)

        buf = tracing.get_buffer()
        own, _ = buf.take_unacked()
        t_stop = closing.get("t_stop", 0.0)
        unflushed_setup = sorted({
            f"{e.get('component')}/{e.get('name')}" for e in own
            if e["ts"] < t_stop} & set(names.SETUP_SPANS))
        merge([e for e in own if e["ts"] >= self._t_init])
        mine = buf.counts(since=self._events_at_init)
        rows[self.core.event_source] = {
            "worker": buf.worker, "recorded": mine["recorded"],
            "delivered": mine["delivered"], "dropped": mine["dropped"],
            "recovered": len(own)}
        for path in sorted(glob.glob(os.path.join(
                session_dir(self.core.session), "task_wal", "*.jsonl"))):
            late = tracing.read_wal(path)
            if late:
                aggregator.credit_recovered(
                    rows, os.path.splitext(os.path.basename(path))[0],
                    late[0].get("worker"), merge(late))
        closing["unflushed_setup"] = unflushed_setup
        tracing.record_named(names.DRIVER_RECORD_SUMMARY,
                             record_summary(rows, account, closing))
        events.extend(buf.take_unacked()[0])
        self.session_timeline = tracing.build_chrome_trace(events)
        try:
            with open(os.path.join(self._procs.session_dir, "timeline.json"),
                      "w") as f:
                json.dump(self.session_timeline, f, default=str)
        except OSError:
            pass    # a full disk loses the file, not the shutdown
