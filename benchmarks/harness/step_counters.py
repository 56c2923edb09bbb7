"""What the program's steps said they did, cut to the timed window (PR 52).

A step of a model that offers counters (``ray_tpu/models/*.step_counters``)
returns them as one device array; the program records it — when the device has
made it, off the loop's path (``ray_tpu/tracing/step_counters.py``) — as ONE
``train/step_counters`` instant a step in the session's record, args
``names.TRAIN_STEP_COUNTERS_ARGS`` + one list a field, an entry a layer
(``passes``, ``pairs``, ``max_per_expert`` for kind ``expert_load``) + the
kind's static args (``buffer_rows``, ``held``). ``t_dispatch`` is the
``time.time()`` of the step's call, the clock of the loop's ``t_window_wall``
/ ``t_end_wall``: the window's steps are those dispatched between the two.
The record is ``session_timeline.for_facts``', parsed once a run.

The readers of ``layer_metrics/`` call the functions at the bottom; each
returns ``None`` where the record holds no such event (a program that hands
nothing out of its step, a model without expert layers, tracing off).
"""

from __future__ import annotations

from statistics import fmean
from typing import Any, Dict, List, Optional

EVENT = "train/step_counters"
KIND = "expert_load"


def window_steps(facts: Dict[str, Any]) -> Optional[List[Dict[str, Any]]]:
    """The args of the timed window's ``train/step_counters`` events of kind
    ``expert_load``, in the steps' order; ``None`` where there are none."""
    if "step_counters_window" not in facts:
        from benchmarks.harness import session_timeline

        rec = session_timeline.for_facts(facts)
        summary = facts["summary"]
        steps = [e["args"] for e in (rec["spans"].get(EVENT, ()) if rec else ())
                 if e["args"].get("kind") == KIND
                 and summary["t_window_wall"] <= e["args"].get("t_dispatch", 0.0)
                 <= summary["t_end_wall"]]
        facts["step_counters_window"] = sorted(
            steps, key=lambda a: a["step"]) or None
    return facts["step_counters_window"]


# ------------------------------------------------------------ what is asked
def passes_per_step(facts: Dict[str, Any]) -> Optional[float]:
    """Mean over the window's steps of Σ over expert layers of ``passes``."""
    steps = window_steps(facts)
    return None if steps is None else fmean(
        sum(s["passes"]) for s in steps)


def multi_pass_steps(facts: Dict[str, Any]) -> Optional[float]:
    """Share (%) of the window's steps in which any layer ran > 1 pass."""
    steps = window_steps(facts)
    return None if steps is None else 100.0 * fmean(
        max(s["passes"]) > 1 for s in steps)


def load_imbalance(facts: Dict[str, Any]) -> Optional[float]:
    """Mean over the window's steps of the worst layer's fullest held expert
    over the layer's mean (``max_per_expert · held ÷ pairs − 1``), in %."""
    steps = window_steps(facts)
    if steps is None:
        return None
    return 100.0 * fmean(
        max(m * s["held"] / max(p, 1) - 1.0
            for m, p in zip(s["max_per_expert"], s["pairs"]))
        for s in steps)
