"""Multi-node-on-one-host test cluster.

Parity: python/ray/cluster_utils.py:99 `class Cluster` — N raylets (separate
processes) against one GCS; THE multi-host simulator for scheduling, transfer,
and failure tests (SURVEY §4.3).
"""

from __future__ import annotations

import os
import subprocess
import time
import uuid
from typing import Dict, List, Optional

from ray_tpu.core.cluster_backend import (
    ProcessGroup,
    _free_port,
    _session_tmp_dir,
    start_gcs,
    start_raylet,
)


class Cluster:
    def __init__(self, initialize_head: bool = True,
                 head_node_args: Optional[dict] = None):
        self.session = f"s{uuid.uuid4().hex[:10]}"
        self.procs = ProcessGroup(_session_tmp_dir(self.session))
        self.gcs_address: Optional[str] = None
        self.node_ids: List[str] = []
        self._raylet_procs: Dict[str, subprocess.Popen] = {}
        self._gcs_proc: Optional[subprocess.Popen] = None
        if initialize_head:
            self.gcs_address = start_gcs(self.procs)
            self._gcs_proc = self.procs.procs[0]
            self.add_node(**(head_node_args or {}))

    @property
    def address(self) -> str:
        return self.gcs_address

    def add_node(self, num_cpus: int = 1, num_tpus: int = 0,
                 resources: Optional[Dict[str, float]] = None,
                 object_store_memory_mb: Optional[int] = None,
                 node_id: Optional[str] = None) -> str:
        node_id = node_id or f"node-{len(self.node_ids)}-{uuid.uuid4().hex[:6]}"
        before = set(self.procs.procs)
        start_raylet(
            self.procs,
            self.gcs_address,
            self.session,
            node_id,
            num_cpus=num_cpus,
            num_tpus=num_tpus,
            resources=resources,
            object_store_memory_mb=object_store_memory_mb,
        )
        new = [p for p in self.procs.procs if p not in before]
        self._raylet_procs[node_id] = new[0]
        self.node_ids.append(node_id)
        return node_id

    def kill_node(self, node_id: str):
        """SIGKILL a raylet (chaos testing)."""
        p = self._raylet_procs.get(node_id)
        if p is not None:
            p.kill()

    @property
    def gcs_store_path(self) -> str:
        """The head's durable store (snapshot + WAL segments live beside
        it) — what ``scripts head-state`` reads offline."""
        return os.path.join(self.procs.session_dir, "gcs_store.pkl")

    def kill_gcs(self):
        """SIGKILL the GCS process (fault-tolerance chaos testing). A real
        kill: there is no pre-exit snapshot flush anywhere anymore —
        acknowledged durability comes from the write-ahead log alone."""
        p = self._gcs_proc or self.procs.procs[0]  # start_gcs spawns first
        p.kill()
        p.wait(timeout=10)

    def wait_gcs_exit(self, timeout: float = 30.0) -> bool:
        """Wait for the GCS process to die (chaos plans kill it from the
        inside — the test must not restart over a still-running head)."""
        p = self._gcs_proc or self.procs.procs[0]
        deadline = time.monotonic() + timeout
        while p.poll() is None and time.monotonic() < deadline:
            time.sleep(0.05)
        return p.poll() is not None

    def restart_gcs(self):
        """Restart the GCS on the SAME port with the same snapshot store;
        raylets/drivers re-register through their reconnect loops and the
        WAL replay restores every acknowledged mutation."""
        import sys

        port = self.gcs_address.rsplit(":", 1)[1]
        self._gcs_proc = self.procs.spawn(
            "gcs-restarted",
            [sys.executable, "-m", "ray_tpu.core.gcs.server",
             "--port", port, "--store", self.gcs_store_path],
        )

    def wait_for_nodes(self, n: Optional[int] = None, timeout: float = 30.0):
        import ray_tpu

        n = n if n is not None else len(self.node_ids)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            alive = [x for x in ray_tpu.nodes() if x["Alive"]]
            if len(alive) >= n:
                return True
            time.sleep(0.2)
        raise TimeoutError(f"only {len(alive)} nodes alive, wanted {n}")

    def shutdown(self):
        self.procs.shutdown()
        from ray_tpu.core.object_store.shm_store import ShmClient

        ShmClient(self.session).destroy()
