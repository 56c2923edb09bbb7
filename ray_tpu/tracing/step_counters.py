"""What a compiled step said it did, recorded when the device has made it and
never waited for on the loop's path.

A step whose model offers counters returns them as one small device array
(``metrics["counters"]``, train/train_step.py). The step's callable hands it
here right after the dispatch — ``watch`` — and the array is fetched, decoded
and recorded as ONE ``train/step_counters`` instant (tracing/names.py) by a
later ``drain`` that finds it ready: the next step's call, AFTER it has
dispatched (in a closed loop the previous step's array is ready by then, and
the device is busy with the new step while the host fetches), and with
``wait=True`` the worker's loop thread where it records ``train/loop_done``,
so that the last step's counters are in the record and an open loop loses
none. Not ``train.report``: a loop that fetches its loss and then reports has
an idle device behind it, and a fetch there (0.8 ms on a v5e's host, PERF.md
§6, PR 52) is a fetch on the step's critical path. Events go in the order of
the steps. With ``task_events_enabled`` off
nothing is watched and nothing is fetched. Like every other event the record
is the task-event buffer's: no second plane, no knob.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Callable, Deque, Dict, Tuple

from ray_tpu.analysis import sanitizers as _san
from ray_tpu.core.config import _config
from ray_tpu.tracing import names
from ray_tpu.tracing.events import record_named

# (step, time.time() at its dispatch, the device array, array on the host →
# the event's args but `step` and `t_dispatch`)
_pending: Deque[Tuple[int, float, Any, Callable[[Any], Dict[str, Any]]]] = deque()
_lock = _san.make_lock("tracing.step_counters")


def watch(step: int, t_dispatch: float, array, decode) -> None:
    """Remember the counters of step ``step``, dispatched at ``t_dispatch``:
    ``array`` is the step's output, still being made; ``decode(host array)``
    gives the event's other args (``kind``, ``layers``, a list a field, the
    kind's static ones)."""
    # (a tracer — the callable under `jax.make_jaxpr` — is nobody's step)
    if _config.task_events_enabled and hasattr(array, "is_ready"):
        with _lock:
            _pending.append((step, t_dispatch, array, decode))


def drain(wait: bool = False) -> int:
    """Record, in the steps' order, every watched entry whose array the
    device has made — every entry with ``wait``, which blocks for them — and
    stop at the first that is not ready. Returns how many were recorded. An
    array that cannot be fetched (its step failed: the loop's own fetch of
    the loss raises that error) is dropped, never raised from here."""
    if not _pending:
        return 0
    import jax

    ready = []
    with _lock:
        while _pending and (wait or _is_ready(_pending[0][2])):
            ready.append(_pending.popleft())
    done = 0
    for step, t_dispatch, array, decode in ready:
        try:
            args = decode(jax.device_get(array))
        except Exception:  # noqa: BLE001 - a recorder never raises
            continue
        done += record_named(names.TRAIN_STEP_COUNTERS, {
            "step": step, "kind": args.pop("kind"),
            "t_dispatch": t_dispatch, **args})
    return done


def _is_ready(array) -> bool:
    try:
        return array.is_ready()
    except Exception:  # noqa: BLE001 - a failed step's: drain drops it
        return True
