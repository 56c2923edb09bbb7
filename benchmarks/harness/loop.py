"""The benchmark's ``train_loop_per_worker``: everything measured on the device
happens here, in the one process that holds the chips, and travels back as
``train.report`` rows (the last row is the summary).

One general loop serves every mix; what a mix is comes from its file
(``loop``, ``sync_every``, ``report_every``, ``warmup_steps``, ``dataset``).
It judges nothing: ``harness/checks.py`` does, in the driver, from the rows.
"""

from __future__ import annotations

import importlib
import itertools
import json
import math
import os
import time
from typing import Any, Dict, Iterator, List, Optional

from benchmarks.harness import traffic as traffic_lib
from benchmarks.harness.spans import Spans

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Window:
    """What one run of the loop body saw."""

    def __init__(self) -> None:
        self.steps = 0
        self.seconds = 0.0
        self.completions: List[float] = []      # perf_counter at each sync
        self.losses: List[float] = []
        self.spans = Spans()
        self.t0 = 0.0


def _run(state, step_fn, source: Iterator, *, seconds: float, max_steps: int,
         sync_every: int, report_every: int, phase: str, step0: int):
    """The loop body, the same for warm-up, the measured window and the traced
    window: next batch -> step_fn -> (every ``sync_every`` steps) wait for the
    loss, fetch it, (every ``report_every``) ``train.report``. Opens and
    closes on a ``block_until_ready``. Ends at the first sync point past
    ``seconds``, or after ``max_steps`` steps."""
    import jax

    from ray_tpu import train

    w = _Window()
    span = w.spans.span
    jax.block_until_ready(state)
    w.t0 = time.perf_counter()
    metrics = None
    while w.steps < max_steps:
        with span("data_wait"):
            batch = next(source)
        with span("dispatch"):
            state, metrics = step_fn(state, batch)
        w.steps += 1
        if sync_every and w.steps % sync_every == 0:
            with span("sync"):
                metrics["loss"].block_until_ready()
            now = time.perf_counter()
            w.completions.append(now)
            with span("report"):
                loss = float(metrics["loss"])
                if report_every and w.steps % report_every == 0:
                    train.report({"phase": phase, "step": step0 + w.steps,
                                  "loss": loss})
            w.losses.append(loss)
            if now - w.t0 >= seconds:
                break
    with span("drain"):
        jax.block_until_ready(state)
    w.seconds = time.perf_counter() - w.t0
    if not w.losses or (sync_every and w.steps % sync_every):
        w.losses.append(float(metrics["loss"]))   # outside the window
    return state, w


def _dataset_source(shard, global_batch: int, sharding, epochs: List[int]):
    """Batches from this rank's Dataset shard through the Data iterator, as a
    user's loop asks for them; wraps to a new epoch when the shard runs out
    (counted: a cell's ``n_blocks`` is sized so that it does not)."""
    while True:
        epochs[0] += 1
        yield from shard.iter_batches(
            batch_size=global_batch, drop_last=True, sharding=sharding)


def _intervals_ms(w: _Window, sync_every: int = 1) -> List[float]:
    """Milliseconds a step between consecutive sync points."""
    ts = [w.t0] + w.completions
    return [(b - a) * 1e3 / sync_every for a, b in zip(ts, ts[1:])]


def train_loop(cfg: Dict[str, Any]) -> None:
    t_enter_wall = time.time()
    import jax
    import numpy as np

    from ray_tpu import train
    from ray_tpu.parallel import mesh as mesh_lib

    cell, config, mix = cfg["cell"], cfg["config"], cfg["traffic"]
    seed, chips = cfg["seed"], cell["chips"]

    compiles: List[Any] = []          # (fun_name, seconds)
    cache_events: Dict[str, int] = {}

    def on_duration(event: str, seconds: float, **kw):
        if event == BACKEND_COMPILE_EVENT:
            compiles.append((kw.get("fun_name", "?"), round(seconds, 3)))

    def on_event(event: str, **_):
        if event.startswith("/jax/compilation_cache/"):
            key = event.rsplit("/", 1)[1]
            cache_events[key] = cache_events.get(key, 0) + 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    # every program of a cell, however quick to compile, comes out of the
    # persistent cache from the second run on (JAX's defaults keep only those
    # that took over a second)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    t0 = time.perf_counter()
    all_devices = jax.devices()
    backend_init_s = time.perf_counter() - t0
    if len(all_devices) < chips:
        raise RuntimeError(f"the cell needs {chips} device(s), the worker sees "
                           f"{len(all_devices)}")
    devices = all_devices[:chips]
    platform = devices[0].platform
    if platform != "tpu" and not cfg["rehearse_cpu"]:
        raise RuntimeError(f"worker devices are on {platform!r}, not tpu")
    train.report({"phase": "enter", "t_enter_wall": t_enter_wall})

    family = importlib.import_module(f"benchmarks.families.{config['family']}")
    shapes = family.shapes(config, cell)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(**cell["mesh"]), devices)
    t0 = time.perf_counter()
    bundle = family.build(config, cell, mesh, seed)
    jax.block_until_ready(bundle.state)
    state_init_s = time.perf_counter() - t0
    state, step_fn = bundle.state, bundle.step_fn
    global_batch = cell["per_chip_batch"] * chips
    seq_len, alphabet = shapes["seq_len"], mix["alphabet"]

    # correctness against the plain reference, before the state is stepped
    t0 = time.perf_counter()
    first_rows = traffic_lib.host_batch(global_batch, seed, seq_len, alphabet)
    reference = family.reference_check(bundle, first_rows, config, cell)
    reference_s = time.perf_counter() - t0

    epochs = [0]
    if mix["loop"] == "iterator":
        source = _dataset_source(train.get_dataset_shard("train"), global_batch,
                                 bundle.data_sharding, epochs)
    elif mix["loop"] == "resident":
        source = itertools.repeat(
            jax.device_put(first_rows, bundle.data_sharding))
    else:
        raise ValueError(f"traffic loop {mix['loop']!r} is not one the "
                         "harness has (iterator, resident)")
    sync_every, report_every = mix["sync_every"], mix["report_every"]

    # warm-up: the first step compiles (or loads) the one step program, the
    # next ones time it; the first batch must be the rows the seed gives
    t0 = time.perf_counter()
    first = next(source)
    data_ok = bool(np.array_equal(
        np.asarray(first["tokens"]), first_rows["tokens"]) and np.array_equal(
        np.asarray(first["targets"]), first_rows["targets"]))
    state, warm0 = _run(state, step_fn, iter([first]), seconds=math.inf,
                        max_steps=1, sync_every=1, report_every=0,
                        phase="warmup", step0=0)
    first_step_s = time.perf_counter() - t0
    state, warm = _run(state, step_fn, source, seconds=math.inf,
                       max_steps=mix["warmup_steps"], sync_every=1,
                       report_every=0, phase="warmup", step0=1)
    warm_step_s = float(np.median(_intervals_ms(warm))) / 1e3
    steps_done = 1 + warm.steps
    # a mix with no sync point cannot look at the clock: its step count is
    # fixed here, from the warm-up's step time
    max_steps = (10 ** 9 if sync_every
                 else max(1, math.ceil(cfg["seconds"] / warm_step_s)))

    n_compiles_before = len(compiles)
    t_window_wall = time.time()
    state, win = _run(state, step_fn, source, seconds=cfg["seconds"],
                      max_steps=max_steps, sync_every=sync_every,
                      report_every=report_every, phase="window",
                      step0=steps_done)
    t_end_wall = time.time()
    compiles_in_window = len(compiles) - n_compiles_before
    steps_done += win.steps
    epochs_at_window_end = epochs[0]

    traced: Optional[Dict[str, Any]] = None
    if cfg["trace"]:
        from benchmarks.harness import trace_reduce

        trace_dir = os.path.join(cfg["out_dir"], "trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # host spans come from bench:*
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            state, tw = _run(state, step_fn, source, seconds=math.inf,
                             max_steps=cell["trace_steps"],
                             sync_every=sync_every, report_every=report_every,
                             phase="traced", step0=steps_done)
        finally:
            jax.profiler.stop_trace()
        steps_done += tw.steps
        traced = trace_reduce.reduce_dir(trace_dir, n_devices=chips)
        traced["host_window_s"] = tw.seconds
        traced["host_steps"] = tw.steps

    stats = [d.memory_stats() or {} for d in devices]
    step_counter = int(state["step"])
    # what the step program needs on a device, as the chip's compiler built
    # it: the allocator's peak_bytes_in_use does not count a program's
    # temporaries on this backend (1.25 GiB read beside a 9.86 GiB step)
    t0 = time.perf_counter()
    mem = step_fn.lower(state, next(source)).compile().memory_analysis()
    step_program_bytes = int(
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    memory_analysis_s = time.perf_counter() - t0
    intervals = _intervals_ms(win, sync_every or 1)
    span_ms = win.spans.total_ms()
    with open(os.path.join(cfg["out_dir"], "intervals_ms.json"), "w") as f:
        json.dump(intervals, f)     # for whoever wants another statistic
    jax.monitoring.unregister_event_listener(on_event)
    jax.monitoring.unregister_event_duration_listener(on_duration)
    train.report({"phase": "summary", "summary": {
        "platforms": sorted({d.platform for d in devices}),
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "visible_devices": len(all_devices),
        "jax": jax.__version__,
        "mesh": {a: n for a, n in mesh.shape.items() if n > 1},
        "attention": family.attention_resolved(bundle),
        "shapes": shapes,
        "global_batch": global_batch,
        "t_enter_wall": t_enter_wall,
        "t_window_wall": t_window_wall,
        "t_end_wall": t_end_wall,
        "setup_parts_s": {
            "backend_init": backend_init_s,
            "state_init": state_init_s,
            "reference_check": reference_s,
            "first_step": first_step_s,
            "warmup_steps": warm.seconds,
            "loop_enter_to_window": t_window_wall - t_enter_wall,
        },
        "compile_events": compiles,
        "cache_dir": jax.config.jax_compilation_cache_dir,
        "cache_events": cache_events,
        "reference": reference,
        "data_ok": data_ok,
        "warm_step_s": warm_step_s,
        "window": {
            "steps": win.steps,
            "seconds": win.seconds,
            "asked_seconds": cfg["seconds"],
            "tokens": win.steps * global_batch * seq_len,
            "first_loss": warm0.losses[0],
            "losses_head": win.losses[:3],
            "losses_tail": win.losses[-3:],
            "nonfinite_losses": sum(
                not math.isfinite(x)
                for x in warm0.losses + warm.losses + win.losses),
            "intervals": len(intervals) if sync_every else 0,
            "step_ms_median": float(np.percentile(intervals, 50)) if sync_every else None,
            "step_ms_mean": float(np.mean(intervals)) if sync_every else None,
            "step_ms_p90": float(np.percentile(intervals, 90)) if sync_every else None,
            "step_ms_max": max(intervals) if sync_every else None,
            "span_ms": span_ms,
            "compiles_in_window": compiles_in_window,
        },
        "epochs": epochs_at_window_end,
        "steps_run": steps_done,
        "step_counter": step_counter,
        "allocator_peak_bytes": max(
            (s.get("peak_bytes_in_use", 0) for s in stats), default=0),
        "step_program_bytes": step_program_bytes,
        "peak_bytes_in_use": max([step_program_bytes] + [
            s.get("peak_bytes_in_use", 0) for s in stats]),
        "memory_stats": {k: v for k, v in stats[0].items()
                         if isinstance(v, (int, float))},
        "memory_analysis_s": memory_analysis_s,
        "t_done_wall": time.time(),
        "bytes_limit": max((s.get("bytes_limit", 0) for s in stats), default=0),
        "traced": traced,
    }})
