"""From a ``jax.profiler`` trace (``*.xplane.pb``) to the tables the per-layer
metrics read. Kept with the benchmark so that every PR computes the same
number in the same way; checked against ``benchmarks/testdata/`` by

    JAX_PLATFORMS=cpu python benchmarks/harness/trace_reduce.py --check

and usable by hand:

    python benchmarks/harness/trace_reduce.py --dump <file.xplane.pb>
    python benchmarks/harness/trace_reduce.py --reduce <file.xplane.pb> [--devices N]

What the trace of a v5e looks like (jax 0.9.0 / libtpu 0.0.34, looked at by
hand in PR 22): one plane ``/device:TPU:<n>`` per chip with the lines
``XLA Modules`` (one event per run of a jitted program, named
``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one event per HLO instruction run,
NAMED BY ITS WHOLE HLO LINE, ``%name = type opcode(operands), attributes``; a
``while`` encloses the events of its body), ``Async XLA Ops`` (copies, slices
and collectives in flight, start to done; written for the first chip only)
and ``Steps``; one plane ``/host:CPU`` whose lines are threads and whose events
include the benchmark's ``bench:<span>`` annotations. Times are nanoseconds on
one clock. Event stats carry no category or kernel name.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import sys
from typing import Any, Dict, Iterable, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness.spans import PREFIX  # noqa: E402

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
# opcodes that only enclose other instructions: their time is their children's
WRAPPERS = ("while", "conditional", "call")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
MOSAIC_TARGET = "tpu_custom_call"
# gap labels, in the order a gap is tested against the host spans under it
# (under `sync` the host is waiting: the gap is launch or wake-up latency)
GAP_SPANS = ("data_wait", "dispatch", "report", "sync")

Interval = Tuple[int, int]
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_OPCODE = re.compile(r"\s*([\w\-]+)\(")


# ------------------------------------------------------------------ loading
def parse_instruction(text: str) -> Tuple[str, str, str]:
    """An ``XLA Ops`` event is named by its whole HLO line,
    ``%name = <result type> opcode(operands), attributes``. Returns
    ``(name, opcode, kind)``; kind is ``mosaic`` (a Pallas kernel: custom call
    to ``tpu_custom_call``), ``collective``, ``wrapper`` or ``op``."""
    if " = " not in text:
        return text.lstrip("%"), "", "op"
    name, rest = text.split(" = ", 1)
    if rest.startswith("("):                   # a tuple type: find its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest_after = rest[i + 1:]
    else:
        rest_after = rest[rest.find(" "):] if " " in rest else ""
    m = _OPCODE.match(rest_after)
    opcode = m.group(1) if m else ""
    target = _TARGET.search(rest)
    base = re.sub(r"-(start|done)$", "", opcode)
    if opcode == "custom-call" and target and target.group(1) == MOSAIC_TARGET:
        kind = "mosaic"
    elif base in COLLECTIVES:
        kind = "collective"
    elif opcode in WRAPPERS:
        kind = "wrapper"
    else:
        kind = "op"
    if opcode == "custom-call" and target:
        opcode = f"custom-call:{target.group(1)}"
    return name.lstrip("%"), opcode, kind


def load_tables(path: str) -> Dict[str, Any]:
    """The events the reduction needs, as plain lists, times in ns:
    ``{"devices": {id: {"ops": [...], "async": [...], "modules": [...]}},
    "host": [(span, start, dur)]}`` with ops and async events as
    ``(name, opcode, kind, start, dur)`` and modules as ``(name, start, dur)``.
    ``path`` may be gzipped."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    parsed: Dict[str, Tuple[str, str, str]] = {}
    devices: Dict[int, Dict[str, list]] = {}
    host: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(
                int(m.group(1)), {"ops": [], "async": [], "modules": []})
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE):
                    into = dev["ops" if line.name == OPS_LINE else "async"]
                    for ev in line.events:
                        if ev.name not in parsed:
                            parsed[ev.name] = parse_instruction(ev.name)
                        into.append(parsed[ev.name] + (
                            int(ev.start_ns), int(ev.duration_ns)))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        dev["modules"].append(
                            (ev.name, int(ev.start_ns), int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        host.append((ev.name[len(PREFIX):], int(ev.start_ns),
                                     int(ev.duration_ns)))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def save_tables(tables: Dict[str, Any], path: str) -> None:
    """Compact form of ``load_tables`` (testdata; a trace that is too large
    to commit)."""
    with gzip.open(path, "wt") as f:
        json.dump({"devices": {str(k): v for k, v in tables["devices"].items()},
                   "host": tables["host"]}, f)


def read_tables(path: str) -> Dict[str, Any]:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return {"devices": {int(k): {key: [tuple(e) for e in v[key]]
                                 for key in ("ops", "async", "modules")}
                        for k, v in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]]}


# ---------------------------------------------------------------- intervals
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of the (disjoint, sorted) intervals ``a`` not under ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def overlap(a: Interval, spans: List[Interval]) -> int:
    return sum(max(0, min(a[1], hi) - max(a[0], lo)) for lo, hi in spans)


# ---------------------------------------------------------------- reduction
def step_module(modules: List[Tuple[str, int, int]]) -> Optional[str]:
    """The jitted step's module: the one with most device time."""
    by_name: Dict[str, int] = {}
    for name, _, dur in modules:
        by_name[name] = by_name.get(name, 0) + dur
    return max(by_name, key=by_name.get) if by_name else None


def reduce_tables(tables: Dict[str, Any], n_devices: Optional[int] = None) -> Dict[str, Any]:
    """Per-device numbers averaged over the devices used. The traced window is
    first device event -> last device event, over all devices."""
    devices = tables["devices"]
    ids = sorted(devices)[:n_devices] if n_devices else sorted(devices)
    ids = [i for i in ids if devices[i]["ops"]]
    if not ids:
        return {"devices": 0}
    w0 = min(e[3] for i in ids for e in devices[i]["ops"])
    w1 = max(e[3] + e[4] for i in ids for e in devices[i]["ops"])
    host_spans: Dict[str, List[Interval]] = {}
    for name, start, dur in tables["host"]:
        host_spans.setdefault(name, []).append((start, start + dur))

    n = len(ids)
    # this profiler writes the async line (collectives in flight) for the
    # first chip only: total collective time is averaged over the chips that
    # have one, exposed time (from the ops line) over all
    with_async = [i for i in ids if devices[i]["async"]] or ids
    busy = steps = step_ns = mosaic_ns = mosaic_calls = coll_ns = exposed_ns = 0.0
    op_ns: Dict[str, float] = {}
    gaps: List[Interval] = []
    module = None
    for i in ids:
        ops = [e for e in devices[i]["ops"] if e[2] != "wrapper"]
        busy_iv = union((s, s + d) for _, _, _, s, d in ops)
        busy += total(busy_iv)
        module = module or step_module(devices[i]["modules"])
        runs = [m for m in devices[i]["modules"] if m[0] == module]
        steps += len(runs)
        step_ns += sum(m[2] for m in runs)
        coll_iv, other_iv = [], []
        for name, opcode, kind, s, d in ops:
            label = f"{name} ({opcode})"
            op_ns[label] = op_ns.get(label, 0.0) + d
            if kind == "mosaic":
                mosaic_ns += d
                mosaic_calls += 1
            (coll_iv if kind == "collective" else other_iv).append((s, s + d))
        # a collective in flight (start -> done) is on the async line
        in_flight = [(s, s + d) for _, _, kind, s, d in devices[i]["async"]
                     if kind == "collective"]
        if i in with_async:
            coll_ns += total(union(coll_iv + in_flight))
        # exposed: the core sits in a collective instruction (a synchronous
        # one, or a -done waiting for its data) and runs nothing else
        exposed_ns += total(subtract(union(coll_iv), union(other_iv)))
        if i == ids[0]:
            gaps = subtract([(w0, w1)], busy_iv)
    labelled: Dict[str, float] = {}
    for gap in gaps:
        rest = gap[1] - gap[0]
        for name in GAP_SPANS:
            under = min(rest, overlap(gap, host_spans.get(name, [])))
            labelled[name] = labelled.get(name, 0.0) + under
            rest -= under
        labelled["other"] = labelled.get("other", 0.0) + rest
    per_step, s = 1e-6 / max(steps, 1), 1e-9
    return {
        "devices": n,
        "window_s": (w1 - w0) * s,
        "busy_s": busy / n * s,
        "step_module": module,
        "steps": steps / n,
        "step_device_ms": step_ns * per_step,
        "mosaic_ms_per_step": mosaic_ns * per_step,
        "mosaic_calls_per_step": mosaic_calls / max(steps, 1),
        "collective_ms_per_step": coll_ns * per_step * n / len(with_async),
        "collective_exposed_ms_per_step": exposed_ns * per_step,
        "device_ops": [[k, v / n * s] for k, v in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v * s] for k, v in
                      sorted(labelled.items(), key=lambda kv: -kv[1]) if v > 0],
    }


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def reduce_dir(trace_dir: str, n_devices: Optional[int] = None) -> Dict[str, Any]:
    path = find_xplane(trace_dir)
    if path is None:
        return {"devices": 0}
    out = reduce_tables(load_tables(path), n_devices)
    out["xplane"] = path
    return out


# ------------------------------------------------------------------- script
def dump(path: str) -> None:
    """What is in a trace, for a look by hand before writing code against it."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            by_name: Dict[str, List[float]] = {}
            for ev in events:
                got = by_name.setdefault(ev.name, [0, 0.0])
                got[0] += 1
                got[1] += ev.duration_ns
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{len(by_name)} names")
            for name, (cnt, ns) in sorted(by_name.items(),
                                          key=lambda kv: -kv[1][1])[:12]:
                print(f"    {ns / 1e6:10.3f} ms  x{cnt:<6} {name[:110]}")
            if events and DEVICE_PLANE.match(plane.name):
                ev = events[len(events) // 2]
                print("    stats of one event:",
                      {k: str(v)[:60] for k, v in ev.stats})


def check() -> int:
    """Every recorded trace under testdata/ must reduce to the numbers beside
    it (``<name>.expected.json``), to 1e-9 relative."""
    testdata = os.path.join(ROOT, "benchmarks", "testdata")
    bad = 0
    for expected_path in sorted(glob.glob(os.path.join(testdata, "*.expected.json"))):
        with open(expected_path) as f:
            expected = json.load(f)
        source = os.path.join(testdata, expected["source"])
        tables = (read_tables(source) if source.endswith(".tables.json.gz")
                  else load_tables(source))
        got = reduce_tables(tables, expected.get("devices"))
        for key, want in expected["numbers"].items():
            have = got.get(key)
            ok = (have == want if not isinstance(want, float)
                  else abs(have - want) <= 1e-9 * max(1.0, abs(want)))
            if not ok:
                bad += 1
                print(f"{os.path.basename(expected_path)}: {key} = {have!r}, "
                      f"expected {want!r}")
        print(f"{os.path.basename(expected_path)}: "
              f"{len(expected['numbers'])} numbers checked")
    # interval arithmetic, on numbers small enough to check by eye
    for have, want in (
        (union([(5, 7), (0, 2), (1, 3)]), [(0, 3), (5, 7)]),
        (subtract([(0, 10)], [(2, 3), (5, 12)]), [(0, 2), (3, 5)]),
        (total(subtract([(0, 4), (6, 9)], [(1, 7)])), 1 + 2),
    ):
        if have != want:
            bad += 1
            print(f"interval arithmetic: {have!r}, expected {want!r}")
    print("trace_reduce --check:", "FAILED" if bad else "ok")
    return 1 if bad else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--dump")
    ap.add_argument("--reduce")
    ap.add_argument("--save-tables", help="with --reduce: also write the "
                    "compact tables to this .json.gz")
    ap.add_argument("--devices", type=int)
    args = ap.parse_args()
    if args.check:
        return check()
    if args.dump:
        dump(args.dump)
    if args.reduce:
        tables = load_tables(args.reduce)
        if args.save_tables:
            save_tables(tables, args.save_tables)
        print(json.dumps(reduce_tables(tables, args.devices), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
