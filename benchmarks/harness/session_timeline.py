"""What the program's own record says of a run's set-up (PR 35).

``ray_tpu.shutdown()`` leaves the finished session's record — the Chrome
trace ``ray_tpu.timeline()`` returns once disconnected, also
``<session_dir>/timeline.json`` — with the spans of ``ray_tpu/tracing/names.py``
under ``SETUP_SPANS``: one trace an attempt of ``fit()`` (``train/fit`` and its
phases, ``train/loop_entered`` on the worker), ``data/split`` and its phases,
the raylet's ``raylet/worker_start`` a process, ``train/compile`` a backend
compile. Everything is on ``time.time()`` of one host, the clock of the loop's
``t_window_wall`` / ``t_end_wall``; a trace's ``ts`` and ``dur`` are
microseconds and come back here as seconds.

The readers of ``layer_metrics/`` call the functions at the bottom; each
returns ``None`` where the record or the span it reads is not there (a tree
that persists no record, tracing switched off): the metric is then left out
of the line. ``python benchmarks/harness/session_timeline.py <timeline.json>``
prints what a record holds.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List, Optional, Tuple

# categories of the program's spans (`<cat>/<name>` is a name of names.py)
SPAN_CATS = ("train", "data", "raylet", "driver")
TASK_CATS = ("task", "actor_task")


def parse(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """A Chrome trace's events by what the readers ask of them:
    ``spans[name]`` = [{start, end, args}] in start order, ``tasks`` = the
    slices of tasks that ran (start, end, task_id, trace_id, name),
    ``submitted`` = task_id -> the owner's SUBMITTED time."""
    spans: Dict[str, List[Dict[str, Any]]] = {}
    tasks: List[Dict[str, Any]] = []
    submitted: Dict[str, float] = {}
    for e in events:
        cat, ph = e.get("cat"), e.get("ph")
        if ph not in ("X", "i"):
            continue
        start = e["ts"] / 1e6
        end = start + e.get("dur", 0.0) / 1e6
        args = e.get("args") or {}
        if cat in SPAN_CATS:
            spans.setdefault(f"{cat}/{e['name']}", []).append(
                {"start": start, "end": end, "args": args})
        elif cat in TASK_CATS and ph == "X":
            tasks.append({"start": start, "end": end, "name": e["name"],
                          "task_id": args.get("task_id"),
                          "trace_id": args.get("trace_id")})
        elif cat == "lifecycle":
            name, _, state = e["name"].rpartition(":")
            if state == "SUBMITTED":
                submitted[args.get("task_id")] = start
            elif state == "RUNNING":
                # a task still running when the record closed: no slice
                tasks.append({"start": start, "end": start, "name": name,
                              "task_id": args.get("task_id"),
                              "trace_id": args.get("trace_id")})
    for group in spans.values():
        group.sort(key=lambda s: s["start"])
    tasks.sort(key=lambda t: t["start"])
    return {"spans": spans, "tasks": tasks, "submitted": submitted}


def load_record() -> Optional[List[Dict[str, Any]]]:
    """The finished session's record through ``ray_tpu.timeline()``, or
    ``None`` where the program keeps none: asking then would start a cluster
    to ask it."""
    try:
        import ray_tpu
        from ray_tpu.api import _global_worker
    except ImportError:
        return None
    worker = _global_worker()
    if worker.connected or getattr(worker, "last_timeline", None) is None:
        return None
    return ray_tpu.timeline()


def for_facts(facts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The run's record, parsed once; ``None`` where there is none."""
    if "session_timeline" not in facts:
        got = None
        try:
            events = load_record()
            if events:
                got = parse(events)
        except Exception as e:  # noqa: BLE001 - a reader never raises
            facts.setdefault("notes", []).append(
                f"session_timeline: cannot read the session's record: {e!r}")
        facts["session_timeline"] = got
    return facts["session_timeline"]


# ------------------------------------------------------------ what is asked
def _trace_id(rec: Dict[str, Any]) -> Optional[str]:
    """The trace of the run's last ``fit()`` attempt."""
    fits = rec["spans"].get("train/fit")
    return fits[-1]["args"].get("trace_id") if fits else None


def _attempt(rec: Dict[str, Any], name: str, **args) -> Optional[Dict[str, Any]]:
    """The last span ``name`` of that attempt's trace (with these args)."""
    trace = _trace_id(rec)
    mine = [s for s in rec["spans"].get(name, ())
            if trace is not None and s["args"].get("trace_id") == trace
            and all(s["args"].get(k) == v for k, v in args.items())]
    return mine[-1] if mine else None


def _loop_entered(rec: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    return _attempt(rec, "train/loop_entered", rank=0)


def span_seconds(facts: Dict[str, Any], name: str) -> Optional[float]:
    """Seconds of the span ``name`` in the run's ``fit()`` attempt."""
    rec = for_facts(facts)
    span = _attempt(rec, name) if rec else None
    return None if span is None else span["end"] - span["start"]


def fit_to_loop_s(facts: Dict[str, Any]) -> Optional[float]:
    """``train/fit`` opened -> rank 0's ``train/loop_entered``."""
    rec = for_facts(facts)
    if not rec:
        return None
    fit, entered = _attempt(rec, "train/fit"), _loop_entered(rec)
    if fit is None or entered is None:
        return None
    return entered["start"] - fit["start"]


def worker_start_s(facts: Dict[str, Any]) -> Optional[float]:
    """``raylet/worker_start`` of the process that became rank 0's worker."""
    rec = for_facts(facts)
    entered = _loop_entered(rec) if rec else None
    if entered is None:
        return None
    for s in rec["spans"].get("raylet/worker_start", ()):
        if s["args"].get("pid") == entered["args"].get("pid"):
            return s["end"] - s["start"]
    return None


def _split_tasks(rec: Dict[str, Any]) -> Optional[Tuple[Dict[str, Any], List]]:
    """The attempt's ``data/split`` and the tasks of its trace submitted
    while it was open, each cut to the span."""
    split = _attempt(rec, "data/split")
    if split is None:
        return None
    trace = split["args"].get("trace_id")
    mine = []
    for t in rec["tasks"]:
        at = rec["submitted"].get(t["task_id"])
        if (t["trace_id"] == trace and at is not None
                and split["start"] <= at <= split["end"]
                and t["start"] < split["end"]):
            mine.append((max(t["start"], split["start"]),
                         min(t["end"], split["end"])))
    return split, sorted(mine)


def split_first_task_wait_s(facts: Dict[str, Any]) -> Optional[float]:
    """``data/split`` opened -> the first task submitted under it RUNNING."""
    rec = for_facts(facts)
    got = _split_tasks(rec) if rec else None
    if not got or not got[1]:
        return None
    split, tasks = got
    return tasks[0][0] - split["start"]


def split_tasks_busy_s(facts: Dict[str, Any]) -> Optional[float]:
    """Seconds of ``data/split`` in which at least one of the tasks submitted
    under it was running (the union of their RUNNING -> end)."""
    rec = for_facts(facts)
    got = _split_tasks(rec) if rec else None
    if not got or not got[1]:
        return None
    busy, until = 0.0, float("-inf")
    for start, end in got[1]:
        if end > until:
            busy += end - max(start, until)
            until = end
    return busy


def _compiles(facts: Dict[str, Any], t0: float, t1: float) -> Optional[List]:
    """The attempt's ``train/compile`` events that ended in [t0, t1]."""
    rec = for_facts(facts)
    if not rec or _loop_entered(rec) is None:
        return None
    return [s for s in rec["spans"].get("train/compile", ())
            if s["args"].get("trace_id") == _trace_id(rec)
            and t0 <= s["end"] <= t1]


def setup_compile_s(facts: Dict[str, Any]) -> Optional[float]:
    """JAX's own seconds of every backend compile (cache loads included)
    between ``train/loop_entered`` and the window's first step."""
    rec = for_facts(facts)
    entered = _loop_entered(rec) if rec else None
    if entered is None:
        return None
    return sum(s["args"]["seconds"] for s in _compiles(
        facts, entered["start"], facts["summary"]["t_window_wall"]))


def program_compiles_in_window(facts: Dict[str, Any]) -> Optional[int]:
    """``train/compile`` events that ended inside the measured window."""
    got = _compiles(facts, facts["summary"]["t_window_wall"],
                    facts["summary"]["t_end_wall"])
    return None if got is None else len(got)


# ------------------------------------------------------------------- script
def describe(events: List[Dict[str, Any]]) -> List[str]:
    """A record's spans by name (count, seconds) and its tasks by name."""
    rec = parse(events)
    lines = []
    for name, group in sorted(rec["spans"].items()):
        total = sum(s["end"] - s["start"] for s in group)
        lines.append(f"{name}: {len(group)} x, {total:.3f} s")
    by_task: Dict[str, List[float]] = {}
    for t in rec["tasks"]:
        by_task.setdefault(t["name"], []).append(t["end"] - t["start"])
    for name, durs in sorted(by_task.items()):
        lines.append(f"task {name}: {len(durs)} x, {sum(durs):.3f} s")
    return lines


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        print("\n".join(describe(json.load(f))))
