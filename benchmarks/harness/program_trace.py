"""What the program says about its own work, read from a ``jax.profiler`` trace.

``trace_reduce.py`` reads a trace by what the *compiler* calls things (HLO
instruction names, opcodes). This file reads it by what the *program* calls
them (``ray_tpu/tracing/names.py``): every ``XLA Ops`` event of the first chip
carries, in its event **metadata**, the stat ``tf_op`` — the HLO ``op_name``,
e.g. ``jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/
rematted_computation/block/mlp/dot_general:`` — and the ``/host:CPU`` plane
carries the program's ``ray_tpu:<component>/<name>`` annotations beside the
benchmark loop's ``bench:<span>``. From those:

- device time a step by direction (``transpose(jvp`` = backward, ``jvp(`` =
  forward, the ``optimizer`` scope), by scope (any path element of the
  vocabulary), under ``rematted_computation`` (remat's recompute), by kernel
  name, and the layer scan's slice/stack traffic (``dynamic_slice`` /
  ``dynamic_update_slice`` / ``squeeze`` directly under ``while/body``, in
  no scope: how ``lax.scan`` takes a layer out of the stacked arrays and
  puts residuals and gradients back);
- host time a step by ``ray_tpu:*`` span, and chip 0's idle gaps labelled by
  the ``ray_tpu:*`` span under them, beneath the ``bench:`` label.

``jax.profiler.ProfileData`` does not expose event-metadata stats, and
TensorFlow's ``xplane_pb2`` may not be installed where this runs, so the five
message types (XSpace, XPlane, XLine, XEvent, XStat, and the two metadata
messages they point to) are read with the small wire-format reader below. It
runs in the driver after ``shutdown()``, parses once per run (cached in
``facts``), and imports no JAX backend. On a trace of a program that names
nothing (the parent of PR 24) every reader gets ``None``.

    python benchmarks/harness/program_trace.py --check
    python benchmarks/harness/program_trace.py --reduce <file.xplane.pb[.gz]>
    python benchmarks/harness/program_trace.py --cut <file> --steps 1 --out <tables.json.gz>
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import struct
import sys
from typing import Any, Dict, Iterator, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import trace_reduce as tr  # noqa: E402
from benchmarks.harness.spans import PREFIX as BENCH_PREFIX  # noqa: E402

try:                         # the vocabulary is the program's; a checkout
    from ray_tpu.tracing import names  # noqa: E402 - without it names nothing
except ImportError:          # pragma: no cover - the parent of PR 24
    names = None

PROGRAM_PREFIX = names.SPAN_PREFIX if names else "ray_tpu:"
SCOPES: Tuple[str, ...] = names.SCOPES if names else ()
KERNELS: Tuple[str, ...] = names.KERNELS if names else ()
REMAT = "rematted_computation"
STACK_OPS = ("dynamic_slice", "dynamic_update_slice", "squeeze")
# a long-poll that is open nearly always: it lies under every gap and so
# labels none (its edges show in the timeline)
NOT_A_GAP_LABEL = ("train/poll",)

Interval = Tuple[int, int]


# ------------------------------------------------------------- wire format
def _varint(buf: memoryview, pos: int) -> Tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message: ints for varints and
    fixed-width fields (as raw bytes views), a memoryview for length-delimited
    ones."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire == 1:
            value, pos = buf[pos:pos + 8], pos + 8
        elif wire == 5:
            value, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield field, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: memoryview) -> Tuple[int, Any, bool]:
    """XStat -> (metadata id, value, value is a reference to a stat name)."""
    key, value, ref = 0, None, False
    for field, wire, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = struct.unpack("<d", v)[0]
        elif field == 3:
            value = v
        elif field == 4:
            value = _signed(v)
        elif field == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif field == 6:
            value = bytes(v)
        elif field == 7:
            value, ref = v, True
    return key, value, ref


def _map_entry(buf: memoryview) -> Tuple[int, Optional[memoryview]]:
    key, value = 0, None
    for field, _, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _plane(buf: memoryview) -> Dict[str, Any]:
    """The top level of one XPlane, nothing below it parsed yet."""
    out: Dict[str, Any] = {"name": "", "lines": [], "event_md": [], "stat_md": []}
    for field, _, v in _fields(buf):
        if field == 2:
            out["name"] = bytes(v).decode()
        elif field == 3:
            out["lines"].append(v)
        elif field == 4:
            out["event_md"].append(v)
        elif field == 5:
            out["stat_md"].append(v)
    return out


def _stat_names(plane: Dict[str, Any]) -> Dict[int, str]:
    out: Dict[int, str] = {}
    for entry in plane["stat_md"]:
        key, value = _map_entry(entry)
        name = ""
        for field, _, v in _fields(value) if value is not None else ():
            if field == 2:
                name = bytes(v).decode("utf-8", "replace")
        out[key] = name
    return out


def _event_metadata(plane: Dict[str, Any], stat_names: Dict[int, str],
                    want: Tuple[str, ...]) -> Dict[int, Dict[str, Any]]:
    """id -> {"name": ..., <stat name>: value for the stats in ``want``}."""
    out: Dict[int, Dict[str, Any]] = {}
    for entry in plane["event_md"]:
        key, value = _map_entry(entry)
        md: Dict[str, Any] = {"name": ""}
        for field, _, v in _fields(value) if value is not None else ():
            if field == 2:
                md["name"] = bytes(v).decode("utf-8", "replace")
            elif field == 5:
                sid, sval, ref = _stat(v)
                sname = stat_names.get(sid, "")
                if sname in want:
                    md[sname] = stat_names.get(sval, "") if ref else sval
        out[key] = md
    return out


def _line(buf: memoryview) -> Tuple[str, int, List[memoryview]]:
    name, t0, events = "", 0, []
    for field, _, v in _fields(buf):
        if field == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif field == 3:
            t0 = _signed(v)
        elif field == 4:
            events.append(v)
    return name, t0, events


def _event(buf: memoryview, want_stats: bool = False
           ) -> Tuple[int, int, int, List[memoryview]]:
    mid = offset_ps = dur_ps = 0
    stats: List[memoryview] = []
    for field, _, v in _fields(buf):
        if field == 1:
            mid = v
        elif field == 2:
            offset_ps = _signed(v)
        elif field == 3:
            dur_ps = _signed(v)
        elif field == 4 and want_stats:
            stats.append(v)
    return mid, offset_ps, dur_ps, stats


# ------------------------------------------------------------------ loading
def load_tables(path: str) -> Dict[str, Any]:
    """The events this file needs, times in ns on the trace's one clock:
    ``{"chip": n, "ops": [[tf_op, name, opcode, kind, start, dur]],
    "modules": [[name, start, dur]], "host": [[label, thread, start, dur,
    batch | None]]}`` — ops and modules of the first chip only, host events
    those named ``ray_tpu:*`` or ``bench:*``. ``path`` may be gzipped."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = memoryview(f.read())
    planes = [_plane(v) for field, _, v in _fields(space) if field == 1]
    chips = sorted((int(m.group(1)), p) for p in planes
                   for m in [tr.DEVICE_PLANE.match(p["name"])] if m)
    out: Dict[str, Any] = {"chip": None, "ops": [], "modules": [], "host": []}
    parsed: Dict[int, Tuple[str, str, str, str]] = {}
    for chip, plane in chips:
        stat_names = _stat_names(plane)
        metadata = _event_metadata(plane, stat_names, ("tf_op",))
        for raw in plane["lines"]:
            line, t0, events = _line(raw)
            if line not in (tr.OPS_LINE, tr.MODULES_LINE):
                continue
            for ev in events:
                mid, offset_ps, dur_ps, _ = _event(ev)
                start, dur = t0 + offset_ps // 1000, dur_ps // 1000
                md = metadata.get(mid, {"name": ""})
                if line == tr.MODULES_LINE:
                    out["modules"].append([md["name"], start, dur])
                    continue
                if mid not in parsed:
                    parsed[mid] = (str(md.get("tf_op", "")),
                                   ) + tr.parse_instruction(md["name"])
                out["ops"].append(list(parsed[mid]) + [start, dur])
        if out["ops"]:
            out["chip"] = chip          # the first chip that ran anything
            break
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        stat_names = _stat_names(plane)
        metadata = _event_metadata(plane, stat_names, ())
        ours = {mid: md["name"] for mid, md in metadata.items()
                if md["name"].startswith((PROGRAM_PREFIX, BENCH_PREFIX))}
        for raw in plane["lines"]:
            line, t0, events = _line(raw)
            for ev in events:
                mid, offset_ps, dur_ps, stats = _event(ev, want_stats=True)
                label = ours.get(mid)
                if label is None:
                    continue
                batch = None
                for s in stats:
                    sid, sval, _ = _stat(s)
                    if stat_names.get(sid) == "batch":
                        batch = int(sval) if str(sval).lstrip("-").isdigit() else None
                # TraceMe writes its arguments into the name; the profiler
                # moves them to stats, but be robust to a plain `name#k=v#`
                out["host"].append([label.split("#", 1)[0], line,
                                    t0 + offset_ps // 1000, dur_ps // 1000,
                                    batch])
    out["host"].sort(key=lambda e: e[2])
    return out


def cut(tables: Dict[str, Any], steps: int) -> Dict[str, Any]:
    """The first ``steps`` runs of the step module and what lies within them
    (a trace too large to commit, cut to a table that is not)."""
    module = tr.step_module([tuple(m) for m in tables["modules"]])
    runs = sorted(m for m in tables["modules"] if m[0] == module)[:steps]
    lo, hi = runs[0][1], runs[-1][1] + runs[-1][2]
    return {
        "chip": tables["chip"],
        "ops": [e for e in tables["ops"] if lo <= e[4] and e[4] + e[5] <= hi],
        "modules": [list(m) for m in runs],
        "host": [e for e in tables["host"] if e[2] + e[3] >= lo and e[2] <= hi],
    }


def save_tables(tables: Dict[str, Any], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(tables, f, separators=(",", ":"))


def read_tables(path: str) -> Dict[str, Any]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ----------------------------------------------------------- classification
_WRAPPED = re.compile(r"^([\w.\-]+)\((.*)\)$")


def path_elements(tf_op: str) -> Tuple[List[str], List[str]]:
    """``jit(step)/transpose(jvp(ln_f))/while/body/block/mlp/dot_general:``
    -> (the plain elements, transformations unwrapped: ``step``, ``ln_f``,
    ``while``, ``body``, ``block``, ``mlp``, ``dot_general``; the
    transformations seen: ``jit``, ``transpose``, ``jvp``)."""
    elements, transforms = [], []
    for element in tf_op.rstrip(":").split("/"):
        m = _WRAPPED.match(element)
        while m:
            transforms.append(m.group(1))
            element = m.group(2)
            m = _WRAPPED.match(element)
        if element:
            elements.append(element)
    return elements, transforms


def classify(tf_op: str, hlo_name: str, kind: str) -> Dict[str, Any]:
    """Where one device instruction belongs, by the program's names."""
    elements, transforms = path_elements(tf_op)
    scopes = [e for e in elements if e in SCOPES]
    kernel = next((k for k in KERNELS if k in elements or k in hlo_name), None)
    if names is not None and names.OPTIMIZER in scopes:
        direction = "optimizer"
    elif "transpose" in transforms:
        direction = "bwd"
    elif "jvp" in transforms:
        direction = "fwd"
    else:
        direction = "other"
    stack = (not scopes and kernel is None and len(elements) >= 3
             and elements[-1] in STACK_OPS and elements[-3:-1] == ["while", "body"])
    return {"scopes": scopes, "kernel": kernel, "direction": direction,
            "remat": REMAT in elements, "stack": stack,
            "mosaic": kind == "mosaic"}


def scope_label(c: Dict[str, Any]) -> str:
    """One row of the by-scope table: direction, recompute, innermost scope
    (or kernel)."""
    where = c["kernel"] or (c["scopes"][-1] if c["scopes"] else
                            "layer_stack_traffic" if c["stack"] else "(no scope)")
    return f"{c['direction']}{'/recompute' if c['remat'] else ''}:{where}"


# ---------------------------------------------------------------- reduction
def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    return tr.subtract(a, tr.subtract(a, b))


def reduce_tables(tables: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers the per-layer metrics of PR 24 read, from one chip."""
    ops = [e for e in tables["ops"] if e[3] != "wrapper"]
    if not ops:
        return {"chip": None}
    module = tr.step_module([tuple(m) for m in tables["modules"]])
    steps = max(1, sum(1 for m in tables["modules"] if m[0] == module))
    ms = 1e-6 / steps                                 # ns in all -> ms a step
    cache: Dict[Tuple[str, str, str], Dict[str, Any]] = {}
    total = scoped = 0
    by: Dict[str, float] = {}
    rows: Dict[str, float] = {}
    for tf_op, hlo_name, _, kind, _, dur in ops:
        key = (tf_op, hlo_name, kind)
        c = cache.get(key)
        if c is None:
            c = cache[key] = classify(tf_op, hlo_name, kind)
            c["row"] = scope_label(c)
        total += dur
        if c["scopes"] or c["kernel"]:
            scoped += dur
        by[c["direction"]] = by.get(c["direction"], 0.0) + dur
        if c["remat"]:
            by["recompute"] = by.get("recompute", 0.0) + dur
        if c["stack"]:
            by["stack"] = by.get("stack", 0.0) + dur
        for s in set(c["scopes"]):
            by["scope:" + s] = by.get("scope:" + s, 0.0) + dur
        if c["kernel"]:
            by["kernel:" + c["kernel"]] = by.get("kernel:" + c["kernel"], 0.0) + dur
        elif c["mosaic"]:
            by["kernel:(unnamed)"] = by.get("kernel:(unnamed)", 0.0) + dur
        rows[c["row"]] = rows.get(c["row"], 0.0) + dur
    instrumented = scoped > 0
    out: Dict[str, Any] = {
        "chip": tables["chip"],
        "steps": steps,
        "instrumented": instrumented,
        "busy_ms_per_step": total * ms,
        "fwd_ms_per_step": by.get("fwd", 0.0) * ms,
        "bwd_ms_per_step": by.get("bwd", 0.0) * ms,
        "optimizer_ms_per_step": by.get("optimizer", 0.0) * ms,
        "other_direction_ms_per_step": by.get("other", 0.0) * ms,
        "recompute_ms_per_step": by.get("recompute", 0.0) * ms,
        "layer_stack_traffic_ms_per_step": by.get("stack", 0.0) * ms,
        "scope_coverage": 100.0 * scoped / total,
        "scope_ms_per_step": {k[6:]: v * ms for k, v in sorted(by.items())
                              if k.startswith("scope:")},
        "kernel_ms_per_step": {k[7:]: v * ms for k, v in sorted(by.items())
                               if k.startswith("kernel:")},
        "device_rows": [[k, v * ms] for k, v in
                        sorted(rows.items(), key=lambda kv: -kv[1])[:10]],
    }

    # ---- the host: the program's spans, and the idle gaps under them
    spans: Dict[str, List[Interval]] = {}
    bench: Dict[str, List[Interval]] = {}
    for label, _, start, dur, _ in tables["host"]:
        if label.startswith(PROGRAM_PREFIX):
            spans.setdefault(label[len(PROGRAM_PREFIX):], []).append(
                (start, start + dur))
        elif label.startswith(BENCH_PREFIX):
            bench.setdefault(label[len(BENCH_PREFIX):], []).append(
                (start, start + dur))
    out["host_span_ms"] = {k: tr.total(v) * 1e-6 for k, v in sorted(spans.items())}
    out["host_span_count"] = {k: len(v) for k, v in sorted(spans.items())}
    out["bench_span_ms"] = {k: tr.total(v) * 1e-6 for k, v in sorted(bench.items())}
    out["bench_span_count"] = {k: len(v) for k, v in sorted(bench.items())}
    busy = tr.union((s, s + d) for *_, s, d in ops)
    gaps = tr.subtract([(busy[0][0], busy[-1][1])], busy)
    gap_labels = [k for k in sorted(spans) if k not in NOT_A_GAP_LABEL]
    table: Dict[str, float] = {}
    for outer in tr.GAP_SPANS + ("other",):
        if outer == "other":
            part = gaps
        else:
            part = _intersect(gaps, tr.union(bench.get(outer, [])))
            gaps = tr.subtract(gaps, part)
        for inner in gap_labels:
            under = _intersect(part, tr.union(spans[inner]))
            if under:
                table[f"{outer} > {PROGRAM_PREFIX}{inner}"] = tr.total(under)
                part = tr.subtract(part, under)
        if part:
            table[f"{outer} > (no program span)"] = tr.total(part)
    out["idle_gaps_ms"] = [[k, v * 1e-6] for k, v in
                           sorted(table.items(), key=lambda kv: -kv[1]) if v > 0]
    return out


def notes(got: Dict[str, Any]) -> List[str]:
    """The tables for a reader of the run (``facts["notes"]``)."""
    lines = [
        f"program_trace (chip {got['chip']}, {got['steps']} steps): fwd "
        f"{got['fwd_ms_per_step']:.3f} + bwd {got['bwd_ms_per_step']:.3f} + "
        f"optimizer {got['optimizer_ms_per_step']:.3f} + other "
        f"{got['other_direction_ms_per_step']:.3f} = "
        f"{got['busy_ms_per_step']:.3f} ms a step; recompute "
        f"{got['recompute_ms_per_step']:.3f}, layer-stack traffic "
        f"{got['layer_stack_traffic_ms_per_step']:.3f}, scope coverage "
        f"{got['scope_coverage']:.2f} %",
        "device time by scope, ms a step: " + ", ".join(
            f"{k} {v:.3f}" for k, v in got["device_rows"]),
        "every scope, ms a step (nested scopes overlap): " + ", ".join(
            f"{k} {v:.3f}" for k, v in got["scope_ms_per_step"].items()),
    ]
    if got["host_span_ms"]:
        lines.append("program spans in the traced window, ms (count): " + ", ".join(
            f"{k} {v:.3f} ({got['host_span_count'][k]})"
            for k, v in got["host_span_ms"].items()))
        lines.append("chip idle gaps by bench span > program span, ms: " + ", ".join(
            f"{k} {v:.3f}" for k, v in got["idle_gaps_ms"]))
    return lines


def for_facts(facts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """What ``reduce_tables`` gives for this run's trace, parsed once; ``None``
    where there is no trace, it cannot be read, or the program names nothing."""
    if "program_trace" not in facts:
        got = None
        trace = facts.get("trace") or {}
        path = trace.get("xplane")
        if path and os.path.exists(path):
            try:
                got = reduce_tables(load_tables(path))
            except Exception as e:  # noqa: BLE001 - a reader never raises
                facts.setdefault("notes", []).append(
                    f"program_trace: cannot read {path}: {e!r}")
        if got is not None and got.get("chip") is None:
            got = None
        if got is not None:
            got["host_steps"] = trace.get("host_steps") or got["steps"]
            facts.setdefault("notes", []).extend(notes(got))
        facts["program_trace"] = got
    return facts["program_trace"]


def device_metric(facts: Dict[str, Any], key: str) -> Optional[float]:
    """A device number of an instrumented program, under the flat key
    ``--check`` uses (``fwd_ms_per_step``, ``scope_ms_per_step.mlp``,
    ``kernel_ms_per_step.flash_attention_fwd``), else ``None``."""
    got = for_facts(facts)
    if not got or not got["instrumented"]:
        return None
    return _flatten(got).get(key)


def host_span_metric(facts: Dict[str, Any], span: str) -> Optional[float]:
    """Milliseconds a step of the traced window under one ``ray_tpu:*`` span:
    0 where its layer recorded other spans and not this one (no block
    boundary fell into the window), ``None`` where the program records no
    span of that layer at all."""
    got = for_facts(facts)
    layer = span.split("/")[0] + "/"
    if not got or not any(k.startswith(layer) for k in got["host_span_ms"]):
        return None
    return got["host_span_ms"].get(span, 0.0) / got["host_steps"]


# ------------------------------------------------------------------- script
def _flatten(got: Dict[str, Any]) -> Dict[str, Any]:
    flat = {k: v for k, v in got.items() if isinstance(v, (int, float, bool))}
    for group in ("scope_ms_per_step", "kernel_ms_per_step", "host_span_ms",
                  "host_span_count"):
        for k, v in got.get(group, {}).items():
            flat[f"{group}.{k}"] = v
    for k, v in got.get("idle_gaps_ms", []):
        flat[f"idle_gaps_ms.{k}"] = v
    return flat


def check() -> int:
    """Every ``*.scoped.*expected.json`` under testdata/ names a recorded
    trace (or a cut table of one) and the numbers it must reduce to, under
    ``program_numbers``, to 1e-9 relative."""
    testdata = os.path.join(ROOT, "benchmarks", "testdata")
    bad = n = 0
    for path in sorted(glob.glob(os.path.join(testdata, "*.scoped.*expected.json"))):
        with open(path) as f:
            expected = json.load(f)
        source = os.path.join(
            testdata, expected.get("program_source") or expected["source"])
        tables = (read_tables(source) if source.endswith(".json.gz")
                  else load_tables(source))
        got = _flatten(reduce_tables(tables))
        for key, want in expected["program_numbers"].items():
            have = got.get(key)
            ok = (have == want if not isinstance(want, float)
                  else have is not None
                  and abs(have - want) <= 1e-9 * max(1.0, abs(want)))
            if not ok:
                bad += 1
                print(f"{os.path.basename(path)}: {key} = {have!r}, "
                      f"expected {want!r}")
        n += 1
        print(f"{os.path.basename(path)}: {len(expected['program_numbers'])} "
              "numbers checked")
    for have, want in (
        (path_elements("jit(step)/transpose(jvp(ln_f))/while/body/mul:"),
         (["step", "ln_f", "while", "body", "mul"], ["jit", "transpose", "jvp"])),
        (_intersect([(0, 10)], [(2, 3), (5, 12)]), [(2, 3), (5, 10)]),
    ):
        if have != want:
            bad += 1
            print(f"program_trace: {have!r}, expected {want!r}")
    if not n:
        bad += 1
        print("program_trace --check: no recorded trace under testdata/")
    print("program_trace --check:", "FAILED" if bad else "ok")
    return 1 if bad else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--reduce", help="print what a trace reduces to")
    ap.add_argument("--flat", action="store_true",
                    help="with --reduce: the flat numbers --check compares")
    ap.add_argument("--cut", help="cut a trace to its first --steps steps")
    ap.add_argument("--steps", type=int, default=1)
    ap.add_argument("--out", help="with --cut: the .json.gz to write")
    args = ap.parse_args()
    if args.check:
        return check()
    if args.reduce:
        tables = (read_tables(args.reduce) if args.reduce.endswith(".json.gz")
                  else load_tables(args.reduce))
        got = reduce_tables(tables)
        print(json.dumps(_flatten(got) if args.flat else got, indent=1))
        for line in notes(got) if got.get("chip") is not None else ():
            print(line, file=sys.stderr)
    if args.cut:
        save_tables(cut(load_tables(args.cut), args.steps), args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
