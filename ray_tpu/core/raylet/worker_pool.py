"""Worker pool: spawn, track, and lease Python worker processes.

Parity: src/ray/raylet/worker_pool.h:152 — process startup with a startup
token, prestarting, idle tracking, dedicated actor workers, death detection.
"""

from __future__ import annotations

import asyncio
import logging
import os
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set

logger = logging.getLogger(__name__)

from ray_tpu import tracing
from ray_tpu.tracing import names

STARTING, IDLE, LEASED, ACTOR, DEAD = "STARTING", "IDLE", "LEASED", "ACTOR", "DEAD"
# a SIGKILLed process that held four v5e chips took 14 s to be gone (PR 21);
# what each process takes is the span `raylet/worker_reap` (PR 35)
REAP_TIMEOUT_S = 30


@dataclass
class WorkerHandle:
    startup_token: int
    proc: subprocess.Popen
    state: str = STARTING
    worker_id: Optional[str] = None
    address: Optional[str] = None    # worker's rpc server
    conn: object = None              # raylet<->worker connection
    actor_id: Optional[bytes] = None
    lease_id: Optional[str] = None
    started_at: float = field(default_factory=time.monotonic)
    platform: str = "cpu"            # worker_platform() of its lease
    killed_at: Optional[float] = None  # time.monotonic() of our signal
    kill_cause: str = names.REAP_EXIT  # who sent it (kill_worker's cause)


def worker_platform(demand: Optional[Dict[str, float]]) -> str:
    """The JAX platform a worker process is started on, decided from the
    resources its lease holds and from nothing else (not the driver's or the
    raylet's own ``JAX_PLATFORMS``). A chip belongs to one process: the worker
    leased ``TPU`` runs on ``tpu`` — JAX raises when a platform named
    explicitly cannot initialise, so it gets the chip or dies — and every
    other worker on the node runs on ``cpu`` and can never take the chip from
    under it."""
    return "tpu" if demand and demand.get("TPU", 0) > 0 else "cpu"


class WorkerPool:
    def __init__(self, raylet_address: str, gcs_address: str, session: str,
                 node_id: str, env: Optional[dict] = None):
        self.raylet_address = raylet_address
        self.gcs_address = gcs_address
        self.session = session
        self.node_id = node_id
        self.extra_env = env or {}
        self._next_token = 0
        self.workers: Dict[int, WorkerHandle] = {}
        self._registered: asyncio.Event = asyncio.Event()
        self.on_worker_death = None  # callback(handle)

    def start_worker(self, actor_id: Optional[bytes] = None,
                     platform: str = "cpu") -> WorkerHandle:
        """`platform` is worker_platform(<the lease's demand>): pooled task
        workers hold no TPU lease and keep the default."""
        token = self._next_token
        self._next_token += 1
        env = {
            **os.environ,
            **self.extra_env,
            "RAY_TPU_RAYLET_ADDRESS": self.raylet_address,
            "RAY_TPU_GCS_ADDRESS": self.gcs_address,
            "RAY_TPU_SESSION": self.session,
            "RAY_TPU_NODE_ID": self.node_id,
            "RAY_TPU_STARTUP_TOKEN": str(token),
            "JAX_PLATFORMS": platform,
        }
        log_dir = os.path.join("/tmp", "ray_tpu", self.session, "logs")
        os.makedirs(log_dir, exist_ok=True)
        log = open(os.path.join(log_dir, f"worker-{self.node_id}-{token}.log"), "ab")
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.core.worker_main"],
            env=env,
            stdout=log,
            stderr=subprocess.STDOUT,
        )
        handle = WorkerHandle(startup_token=token, proc=proc,
                              platform=platform)
        if actor_id is not None:
            handle.state = STARTING
            handle.actor_id = actor_id
        self.workers[token] = handle
        logger.info("started worker token=%d pid=%d", token, proc.pid)
        return handle

    def on_register(self, startup_token: int, worker_id: str, address: str, conn):
        handle = self.workers.get(startup_token)
        if handle is None:
            return None
        handle.worker_id = worker_id
        handle.address = address
        handle.conn = conn
        # start_worker -> registered: interpreter start, the package's
        # import, the connections to raylet and GCS
        tracing.record_named(names.RAYLET_WORKER_START, {
            "pid": handle.proc.pid, "startup_token": startup_token,
            "platform": handle.platform,
            "kind": "pooled" if handle.actor_id is None else "actor",
        }, dur=time.monotonic() - handle.started_at)
        if handle.state == STARTING and handle.actor_id is None:
            handle.state = IDLE
        return handle

    def idle_workers(self) -> List[WorkerHandle]:
        return [w for w in self.workers.values() if w.state == IDLE]

    def get_by_worker_id(self, worker_id: str) -> Optional[WorkerHandle]:
        for w in self.workers.values():
            if w.worker_id == worker_id:
                return w
        return None

    def get_actor_worker(self, actor_id: bytes) -> Optional[WorkerHandle]:
        for w in self.workers.values():
            if w.actor_id == actor_id and w.state != DEAD:
                return w
        return None

    async def poll_deaths(self):
        """Detect worker process exits (reference: raylet socket monitoring)."""
        for w in list(self.workers.values()):
            # poll() unconditionally: it also reaps zombies of workers we
            # killed ourselves (kill_worker marks DEAD before the process
            # is waited on)
            if w.proc.poll() is not None and w.state != DEAD:
                w.state = DEAD
                logger.warning(
                    "worker pid=%d token=%d died (exit %s)",
                    w.proc.pid, w.startup_token, w.proc.returncode,
                )
                if self.on_worker_death:
                    res = self.on_worker_death(w)
                    if asyncio.iscoroutine(res):
                        await res

    def kill_worker(self, handle: WorkerHandle, force: bool = True,
                    cause: str = names.REAP_EXIT):
        handle.killed_at = time.monotonic()
        handle.kill_cause = cause      # `raylet/worker_reap`'s, names.py
        try:
            handle.proc.kill() if force else handle.proc.terminate()
        except ProcessLookupError:
            pass
        handle.state = DEAD

    def chaos_on_lease(self, handle: WorkerHandle) -> bool:
        """Chaos injection point "worker.lease": fired by the raylet right
        after it grants ``handle`` a task lease; an active plan can SIGKILL
        the worker at the Nth grant (the owner's push then fails with
        ConnectionLost → WorkerCrashedError → task retry). Returns True when
        the worker was killed."""
        from ray_tpu.testing import chaos

        act = chaos.fire("worker.lease", key=str(handle.worker_id or ""))
        if act is not None and act.get("action") == "kill":
            logger.warning(
                "CHAOS: killing leased worker pid=%d token=%d",
                handle.proc.pid, handle.startup_token,
            )
            self.kill_worker(handle)
            return True
        return False

    def reap(self, handle: WorkerHandle) -> None:
        """Wait until a killed worker's process is gone. A worker that held
        the chips frees them only then — not when the signal was sent — and
        the next process to open them (a restarted trainer, whatever runs
        after the driver exits) fails or hangs if it comes sooner."""
        if handle.proc.returncode is not None:
            return    # collected before (poll_deaths, an earlier reap)
        t0 = handle.killed_at or time.monotonic()
        timed_out = False
        try:
            handle.proc.wait(timeout=REAP_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            timed_out = True
            logger.warning("worker pid=%d not gone %ds after SIGKILL",
                           handle.proc.pid, REAP_TIMEOUT_S)
        seconds = time.monotonic() - t0
        tracing.record_named(names.RAYLET_WORKER_REAP, {
            "pid": handle.proc.pid, "platform": handle.platform,
            "seconds": seconds, "timed_out": timed_out,
            "cause": handle.kill_cause}, dur=seconds)

    def shutdown(self):
        alive = [w for w in self.workers.values() if w.proc.poll() is None]
        for w in alive:
            if w.killed_at is None:
                self.kill_worker(w, cause=names.REAP_SIGTERM)
        # chip-less processes first: they are gone in milliseconds, and a
        # reap's seconds run until the process is SEEN gone — behind a
        # chip-holding one (seconds to die) they would read its time
        for w in sorted(alive, key=lambda w: w.platform == "tpu"):
            self.reap(w)
