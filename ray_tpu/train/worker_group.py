"""Train worker group: N actors, one per host, running user train loops.

Parity: train/_internal/worker_group.py:100 (WorkerGroup of plain actors) +
backend_executor.py:45 (BackendExecutor: start → rendezvous → start_training).
The rendezvous step is the TPU swap: instead of a torch NCCL/GLOO process
group (torch/config.py:69), workers call jax.distributed.initialize against
worker 0's coordinator port, after which jax.devices() spans all hosts and a
global mesh covers the slice.
"""

from __future__ import annotations

import os
import socket
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu import tracing
from ray_tpu.tracing import names, step_counters
from ray_tpu.train import session as session_mod
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.session import TrainContext, _Session, _set_session


class TrainWorker:
    """Actor hosting one rank's train loop (run on its own thread so poll()
    stays responsive on the actor's ordered queue)."""

    def __init__(self, rank: int, world_size: int, experiment_name: str = ""):
        self.rank = rank
        self.world_size = world_size
        self.experiment_name = experiment_name
        self.session: Optional[_Session] = None
        self._thread: Optional[threading.Thread] = None
        self._distributed_ready = False

    # ---------------------------------------------------------- rendezvous
    def host_info(self) -> Dict[str, Any]:
        ip = "127.0.0.1"
        try:
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.connect(("8.8.8.8", 80))
            ip = s.getsockname()[0]
            s.close()
        except OSError:
            pass
        free = socket.socket()
        free.bind(("", 0))
        port = free.getsockname()[1]
        free.close()
        return {"ip": ip, "port": port, "pid": os.getpid(),
                "node_id": os.environ.get("RAY_TPU_NODE_ID", "")}

    def setup_jax_distributed(self, coordinator: str, num_processes: int,
                              process_id: int) -> bool:
        """jax.distributed over ICI/DCN — the NCCL-rendezvous replacement.

        Re-entrant: a retried rendezvous round (coordinator port stolen on
        another rank) reaches workers that DID initialize in the failed
        round — tear that state down first or jax raises 'already
        initialized' and the retry loop can never succeed."""
        import jax

        if self._distributed_ready:
            try:
                jax.distributed.shutdown()
            except Exception:  # noqa: BLE001 - half-initialized state
                pass
            self._distributed_ready = False
        with tracing.named_span(names.TRAIN_JAX_DISTRIBUTED_INIT, {
                "rank": process_id, "num_processes": num_processes}):
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=num_processes,
                process_id=process_id,
            )
        self._distributed_ready = True
        return True

    # ------------------------------------------------------------ training
    def start_training(self, fn: Callable, config: Dict[str, Any],
                       latest_checkpoint: Optional[Checkpoint] = None,
                       dataset_shards: Optional[Dict[str, Any]] = None) -> bool:
        ctx = TrainContext(
            world_rank=self.rank,
            world_size=self.world_size,
            local_rank=0,
            experiment_name=self.experiment_name,
        )
        self.session = _Session(ctx, latest_checkpoint, dataset_shards)
        from ray_tpu.tracing.backend_init import record_backend_init
        from ray_tpu.tracing.compiles import record_compiles
        from ray_tpu.util.compile_cache import enable_compile_cache

        enable_compile_cache()
        record_compiles()
        record_backend_init(self.rank)

        # the loop's own thread inherits this actor task's ids, so that the
        # spans of Data and Train under it attach to the task and its trace
        task_ids = (tracing.current_task_id(), tracing.current_trace_id(),
                    tracing.current_job_id())

        def run():
            _set_session(self.session)
            error: Optional[BaseException] = None
            try:
                with tracing.task_context(*task_ids):
                    tracing.record_named(names.TRAIN_LOOP_ENTERED, {
                        "rank": self.rank, "pid": os.getpid()})
                    try:
                        fn(config) if config is not None else fn()
                    except BaseException as e:  # noqa: BLE001
                        traceback.print_exc()
                        error = e
                    # the last steps' counters: the loop is over, so this
                    # wait is on nobody's path
                    step_counters.drain(wait=True)
                    tracing.record_named(names.TRAIN_LOOP_DONE, {
                        "rank": self.rank,
                        "error": repr(error) if error else None})
                self.session.finish(error=error)
            finally:
                _set_session(None)

        self._thread = threading.Thread(target=run, daemon=True, name="train-fn")
        self._thread.start()
        return True

    def poll(self, timeout: float = 1.0) -> List[tuple]:
        """Drain pending (kind, metrics, checkpoint) events."""
        out = []
        if self.session is None:
            return out
        deadline = time.monotonic() + timeout
        while True:
            try:
                remaining = max(0.0, deadline - time.monotonic())
                item = self.session.result_queue.get(timeout=remaining)
                out.append(item)
                if item[0] == "done":
                    break
            except Exception:  # noqa: BLE001 - queue.Empty
                break
        return out

    def get_error(self):
        if self.session and self.session.error is not None:
            raise self.session.error
        return None

    def shutdown_worker(self) -> bool:
        return True


class WorkerGroup:
    def __init__(self, num_workers: int, resources_per_worker: Dict[str, float],
                 experiment_name: str = "", placement_strategy: str = "PACK"):
        self.num_workers = num_workers
        self.tpu_leased = bool(resources_per_worker.get("TPU"))
        self.placement_group = None
        actor_cls = ray_tpu.remote(TrainWorker)
        opts: Dict[str, Any] = {
            "num_cpus": resources_per_worker.get("CPU", 1),
            "resources": {
                k: v for k, v in resources_per_worker.items() if k not in ("CPU", "TPU")
            },
        }
        if resources_per_worker.get("TPU"):
            opts["num_tpus"] = resources_per_worker["TPU"]
        if num_workers > 1:
            from ray_tpu.util.placement_group import (
                PlacementGroupSchedulingStrategy,
                placement_group,
            )

            bundle = dict(resources_per_worker)
            bundle.setdefault("CPU", 1)
            self.placement_group = placement_group(
                [bundle] * num_workers, strategy=placement_strategy
            )
            self.placement_group.ready(timeout=60)
        self.workers = []
        for rank in range(num_workers):
            o = dict(opts)
            if self.placement_group is not None:
                o["placement_group"] = self.placement_group
                o["placement_group_bundle_index"] = rank
            self.workers.append(
                actor_cls.options(**o).remote(rank, num_workers, experiment_name)
            )

    def for_all(self, method: str, *args, timeout: Optional[float] = 120, **kwargs):
        refs = [
            getattr(w, method).remote(*args, **kwargs) for w in self.workers
        ]
        return ray_tpu.get(refs, timeout=timeout)

    def check_alive(self) -> None:
        """Raise a typed worker-death error if any worker actor is gone.

        The trainer's drive loop calls this when a poll round fails or
        times out, so a worker death surfaces as a catchable
        ActorDiedError into the FailureConfig retry loop — never as a bare
        hang or a raw RPC error string."""
        from ray_tpu.api import _global_worker

        backend = _global_worker().backend
        for rank, w in enumerate(self.workers):
            state = backend.actor_state(w._actor_id)
            if state == "DEAD":
                raise ray_tpu.exceptions.ActorDiedError(
                    w._actor_id,
                    f"train worker rank {rank} died mid-run",
                )

    def rendezvous(self, attempts: int = 3):
        """jax.distributed bootstrap across the group (no-op for 1 worker).

        The coordinator port is picked by probing a free port on worker 0 and
        releasing it — inherently TOCTOU — so the whole round retries with a
        fresh port if another process stole it between probe and bind
        (advisor finding r1/r2)."""
        if self.num_workers <= 1:
            return
        last_err: Optional[BaseException] = None
        for _ in range(attempts):
            infos = self.for_all("host_info")
            nodes = [i["node_id"] for i in infos]
            if self.tpu_leased and len(set(nodes)) < len(nodes):
                # a chip belongs to one process and nothing yet narrows a
                # process to its share of a host's chips: the second worker
                # would fail or hang opening them. Refuse before any rank
                # touches JAX (host_info does not).
                raise RuntimeError(
                    f"{len(nodes)} TPU-leased train workers on "
                    f"{len(set(nodes))} host(s) {sorted(set(nodes))}: use "
                    "one worker per host with tpus_per_worker=<all its chips>"
                )
            coordinator = f"{infos[0]['ip']}:{infos[0]['port']}"
            refs = [
                w.setup_jax_distributed.remote(
                    coordinator, self.num_workers, rank
                )
                for rank, w in enumerate(self.workers)
            ]
            try:
                ray_tpu.get(refs, timeout=300)
                return
            except Exception as e:  # noqa: BLE001 - port stolen / bind race
                last_err = e
                if "address" not in str(e).lower() and "bind" not in str(e).lower():
                    raise
        raise RuntimeError(
            f"rendezvous failed after {attempts} port attempts"
        ) from last_err

    def shutdown(self) -> Dict[str, Any]:
        """Kill every worker and free the placement group. Returns when it
        always did; what the kills did is what it hands back —
        ``train/group_shutdown``'s args (``tracing/names.py``): the calls
        made, what they raised (swallowed as before) and the workers whose
        process the raylet had confirmed gone when the call returned."""
        from ray_tpu.api import _global_worker

        backend = _global_worker().backend
        outcomes, errors = [], []
        for w in self.workers:
            try:
                # ray_tpu.kill(w), for its reply: gcs/kill_actor's outcome
                outcomes.append(backend.kill_actor(w._actor_id, True))
            except Exception as e:  # noqa: BLE001
                errors.append(tracing.events.error_text(e))
        told = {"num_workers": self.num_workers,
                "killed": len(outcomes) + len(errors), "kill_errors": errors,
                "gone_at_return": outcomes.count(names.KILL_REAPED)}
        if self.placement_group is not None:
            from ray_tpu.util.placement_group import remove_placement_group

            try:
                remove_placement_group(self.placement_group)
            except Exception:  # noqa: BLE001
                pass
        return told
