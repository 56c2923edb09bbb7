"""What the session's record says of itself and of the waits it used to hide
(PR 68) — the spans `session_timeline.parse` does not keep: `worker/load_class`
(a worker's first load of a function id: the blob fetched and unpickled, every
import it pulls in), `gcs/kill_actor` (what one kill did), and
`driver/record_summary`, the LAST event `shutdown()` appends: a row a source
(recorded, delivered, recovered, dropped, lost) and the aggregator's
evictions. Read from the same `ray_tpu.timeline()` record, parsed once a run
into `facts["session_record"]`.

Each function returns `None` where the record or the span it reads is not
there — a tree from before these spans, tracing switched off —, and the
metric is then left out of the line.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmarks.harness import session_timeline

CATS = ("worker", "gcs")
SUMMARY = ("driver", "record_summary")
LOOP_ENTERED = ("train", "loop_entered")


def parse(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """``spans[name]`` = [{start, end, args, row}] in start order for the
    ``worker/*`` and ``gcs/*`` spans and ``train/loop_entered`` (``row`` =
    the trace's (pid, tid): one a process), ``summary`` = the args of the
    record's last ``driver/record_summary`` (``None``: it wrote none)."""
    spans: Dict[str, List[Dict[str, Any]]] = {}
    summary = None
    for e in events:
        cat, ph = e.get("cat"), e.get("ph")
        if ph not in ("X", "i"):
            continue
        if (cat, e.get("name")) == SUMMARY:
            summary = e.get("args") or {}
        elif cat in CATS or (cat, e.get("name")) == LOOP_ENTERED:
            start = e["ts"] / 1e6
            spans.setdefault(f"{cat}/{e['name']}", []).append(
                {"start": start, "end": start + e.get("dur", 0.0) / 1e6,
                 "args": e.get("args") or {},
                 "row": (e.get("pid"), e.get("tid"))})
    for group in spans.values():
        group.sort(key=lambda s: s["start"])
    return {"spans": spans, "summary": summary}


def for_facts(facts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The run's record, parsed once; ``None`` where there is none."""
    if "session_record" not in facts:
        got = None
        try:
            events = session_timeline.load_record()
            if events:
                got = parse(events)
        except Exception as e:  # noqa: BLE001 - a reader never raises
            facts.setdefault("notes", []).append(
                f"session_record: cannot read the session's record: {e!r}")
        facts["session_record"] = got
    return facts["session_record"]


def _attempt_trace(facts: Dict[str, Any]) -> Optional[str]:
    """The trace of the run's last ``fit()`` attempt."""
    timeline = session_timeline.for_facts(facts)
    return session_timeline._trace_id(timeline) if timeline else None


def actor_class_load_s(facts: Dict[str, Any]) -> Optional[float]:
    """``worker/load_class`` of the process that entered rank 0's loop: the
    `TrainWorker` class fetched and unpickled in the new worker,
    `ray_tpu.train` and JAX imported with it."""
    rec, trace = for_facts(facts), _attempt_trace(facts)
    if not rec or trace is None:
        return None
    entered = [s for s in rec["spans"].get("train/loop_entered", ())
               if s["args"].get("trace_id") == trace
               and s["args"].get("rank") == 0]
    if not entered:
        return None
    for s in rec["spans"].get("worker/load_class", ()):
        if s["args"].get("kind") == "actor" and s["row"] == entered[-1]["row"]:
            return s["end"] - s["start"]
    return None


def program_backend_init_s(facts: Dict[str, Any]) -> Optional[float]:
    """Rank 0's ``train/backend_init``: JAX's own `_init_backend`, first
    line to last, in the train worker's process."""
    timeline = session_timeline.for_facts(facts)
    span = session_timeline._attempt(
        timeline, "train/backend_init", rank=0) if timeline else None
    return None if span is None else span["end"] - span["start"]


def lost_events(facts: Dict[str, Any]) -> Optional[int]:
    """What the record says it lacks: Σ over its sources of ``lost`` (=
    max(dropped, recorded - delivered - recovered)) plus the set-up spans
    the aggregator's queue pushed out. ``None``: the record has no summary."""
    rec = for_facts(facts)
    if not rec or rec["summary"] is None:
        return None
    summary = rec["summary"]
    return (sum(row["lost"] for row in summary["sources"])
            + summary["setup_evicted"])
