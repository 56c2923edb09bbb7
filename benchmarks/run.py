"""The benchmark's one command (BENCHMARK.json ``command``):

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is the ray_tpu DRIVER: ``ray_tpu.init()`` -> the mix's Dataset ->
``JaxTrainer(...).fit()`` with one worker leased every chip ->
``ray_tpu.shutdown()`` -> the contract's last line. It never initialises a JAX
backend: the chips belong to the train worker, and everything measured on the
device happens in ``harness/loop.py`` there and comes back through
``train.report``. With no TPU it exits non-zero in seconds and prints no
result. ``--rehearse-cpu`` runs the same path at a tiny size on the CPU and
prints ``platform=cpu`` and no number under a metric's name.

Everything that belongs to one cell, configuration, mix or per-layer metric is
found by its name in BENCHMARK.json (``benchmarks/README.md``).
"""

from __future__ import annotations

import time

_T_IMPORT = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, List  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _process_start() -> float:
    try:
        import psutil

        return psutil.Process().create_time()
    except Exception:  # noqa: BLE001 - any failure: the import-time clock
        return _T_IMPORT


class _TimedSplit:
    """Stands where the Dataset stands in ``JaxTrainer(datasets=...)`` and
    times ``split``: the part of ``fit()`` in which today's trainer
    materialises the whole dataset before a worker starts."""

    def __init__(self, dataset) -> None:
        self.dataset = dataset
        self.seconds = None

    def split(self, n: int):
        t0 = time.perf_counter()
        try:
            return self.dataset.split(n)
        finally:
            self.seconds = time.perf_counter() - t0


def _driver_backend_initialised() -> bool:
    jax = sys.modules.get("jax")
    return jax is not None and jax._src.xla_bridge.backends_are_initialized()


def _apply_rehearsal(cell: Dict[str, Any], config: Dict[str, Any]) -> None:
    """The tiny sizes of the CPU rehearsal, from testdata/rehearse-<family>.json."""
    path = os.path.join(ROOT, "benchmarks", "testdata",
                        f"rehearse-{config['family']}.json")
    with open(path) as f:
        tiny = json.load(f)
    config.update(tiny["config"])
    cell.update(tiny["cell"])


def _read_metrics(names: List[Dict[str, Any]], facts: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for entry in names:
        reader = importlib.import_module(
            f"benchmarks.layer_metrics.{entry['name']}")
        if reader.UNIT != entry["unit"]:
            raise SystemExit(f"layer_metrics/{entry['name']}.py reports "
                             f"{reader.UNIT!r}, BENCHMARK.json says "
                             f"{entry['unit']!r}")
        value = reader.read(facts)
        if value is not None:      # a reader that finds nothing returns nothing
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def main() -> int:
    t_start = _process_start()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU; prints no metric")
    args = ap.parse_args()

    from benchmarks.harness import checks, flops, loop, peaks, spec, traffic

    try:
        import ray_tpu
        from ray_tpu import train
    except ImportError as e:
        print(f"benchmarks/run.py: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 3

    cell, config, mix = spec.load_cell(args.workload)
    if args.rehearse_cpu:
        _apply_rehearsal(cell, config)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    family = importlib.import_module(f"benchmarks.families.{config['family']}")
    shapes = family.shapes(config, cell)
    global_batch = cell["per_chip_batch"] * cell["chips"]
    out_dir = os.path.join(ROOT, ".bench_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)

    t0 = time.perf_counter()
    ray_tpu.init()
    init_s = time.perf_counter() - t0
    driver: Dict[str, Any] = {"t_proc_start": t_start, "init_s": init_s}
    result = None
    try:
        node_tpus = int(ray_tpu.cluster_resources().get("TPU", 0))
        if not args.rehearse_cpu and node_tpus < cell["chips"]:
            print(f"benchmarks/run.py: cell {args.workload} needs "
                  f"{cell['chips']} TPU chip(s), this node has {node_tpus} "
                  f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', 'unset')}): "
                  "no chip, no number", file=sys.stderr)
            return 2
        datasets = {}
        if mix["dataset"] is not None:
            datasets["train"] = _TimedSplit(traffic.build_dataset(
                mix, cell, args.seed, shapes["seq_len"], global_batch))
        trainer = train.JaxTrainer(
            loop.train_loop,
            train_loop_config={
                "cell": cell, "config": config, "traffic": mix,
                "seed": args.seed, "seconds": args.seconds,
                "trace": bool(args.trace), "out_dir": out_dir,
                "rehearse_cpu": args.rehearse_cpu,
            },
            scaling_config=train.ScalingConfig(
                num_workers=1, use_tpu=not args.rehearse_cpu,
                # a chip-holding process sees every chip of its host: lease
                # them all, so that no second process is started on one
                tpus_per_worker=0 if args.rehearse_cpu else node_tpus,
            ),
            datasets=datasets,
        )
        driver["t_fit_called"] = time.time()
        result = trainer.fit()
        driver["t_fit_returned"] = time.time()
        if "train" in datasets:
            driver["dataset_materialize_s"] = datasets["train"].seconds
    finally:
        ray_tpu.shutdown()
        driver["t_shutdown_returned"] = time.time()
    if result.error is not None:
        raise result.error

    summary = result.metrics_dataframe[-1]["summary"]
    win = summary["window"]
    facts: Dict[str, Any] = {
        "cell": cell, "config": config, "traffic": mix, "summary": summary,
        "trace": summary["traced"], "driver": driver, "notes": [],
    }
    failures = checks.failures(summary, cell, args.rehearse_cpu)
    if _driver_backend_initialised():
        failures.append("the driver process initialised a JAX backend")
    on_tpu = summary["platforms"] == ["tpu"]
    if on_tpu:
        facts["peaks"] = peaks.peaks_for(summary["device_kind"])

    setup_s = summary["t_window_wall"] - t_start
    teardown_s = driver["t_shutdown_returned"] - summary["t_done_wall"]
    # the whole window's rate, and the one the metric is: where the loop sees
    # its steps complete, tokens a step over the median step interval, so that
    # a rare stall of the host does not decide a run (`step_ms_mean` holds it)
    window_rate = win["tokens"] / win["seconds"] / cell["chips"]
    if win["intervals"]:
        tokens_per_s_per_chip = (global_batch * shapes["seq_len"] * 1e3
                                 / win["step_ms_median"] / cell["chips"])
    else:
        tokens_per_s_per_chip = window_rate
    end_to_end = {
        "tokens_per_s_per_chip": tokens_per_s_per_chip,
        "step_ms_p90": win["step_ms_p90"],
        "teardown_s": teardown_s,
        "setup_s": setup_s,
    }

    # ---- earlier lines: what a reader of the run wants beside the metrics
    print(f"cell {args.workload}: config {cell['config']} / traffic "
          f"{cell['traffic']} / chips {cell['chips']}  seed {args.seed}  "
          f"seconds {args.seconds:g}  trace {args.trace}")
    print(f"device: platform={','.join(summary['platforms'])} "
          f"device_kind={summary['device_kind']!r} count="
          f"{summary['device_count']} (worker sees "
          f"{summary['visible_devices']}, node advertised TPU={node_tpus}); "
          f"jax {summary['jax']}; mesh {summary['mesh'] or 'one device'}; "
          f"attention (impl, interpret)={summary['attention']}")
    parts = summary["setup_parts_s"]
    print(f"set-up {setup_s:.2f} s = process start -> window: init() "
          f"{init_s:.2f}, fit() -> loop entered "
          f"{summary['t_enter_wall'] - driver['t_fit_called']:.2f}"
          + (f" (Dataset.split {driver['dataset_materialize_s']:.2f})"
             if driver.get("dataset_materialize_s") is not None else "")
          + "; in the worker: "
          + ", ".join(f"{k} {v:.2f}" for k, v in parts.items()))
    print(f"compile cache: {summary['cache_dir']} {summary['cache_events']}; "
          f"backend compiles or cache loads: "
          + ", ".join(f"{n} {s:g}s" for n, s in summary["compile_events"]
                      if s >= 0.5))
    ref = summary["reference"]
    print(f"reference check on {ref['rows']} rows: program loss "
          f"{ref['program']['loss']:.6f} vs float32 reference "
          f"{ref['reference']['loss']:.6f} (rtol {ref['loss_rtol']:g})"
          + (f"; grad-norm {ref['program']['grad_norm']:.5f} vs "
             f"{ref['reference']['grad_norm']:.5f} (rtol "
             f"{ref['grad_norm_rtol']:g})" if ref["with_grad"] else ""))
    print(f"window: {win['steps']} steps of {global_batch}x"
          f"{shapes['seq_len']} tokens in {win['seconds']:.3f} s (asked "
          f"{win['asked_seconds']:g}); loss {win['first_loss']:.3f} (first "
          f"step) -> {win['losses_tail'][-1]:.3f}; epochs started "
          f"{summary['epochs']}; compiles in window "
          f"{win['compiles_in_window']}; warm-up step "
          f"{summary['warm_step_s'] * 1e3:.2f} ms")
    if win["intervals"]:
        print(f"step interval over {win['intervals']} samples: median "
              f"{win['step_ms_median']:.3f} ms, p90 {win['step_ms_p90']:.3f}, "
              f"max {win['step_ms_max']:.3f}, mean {win['step_ms_mean']:.3f}; "
              f"by the median {tokens_per_s_per_chip:.1f} tokens/s/chip, by "
              f"the whole window, stalls and all, {window_rate:.1f}")
    print("host spans, ms a step: " + ", ".join(
        f"{k} {v / win['steps']:.3f}" for k, v in win["span_ms"].items()))
    print(f"teardown {teardown_s:.2f} s = the loop's last row posted -> "
          f"shutdown() returned (fit() returned after "
          f"{driver['t_fit_returned'] - summary['t_done_wall']:.2f}); window "
          f"closed -> last row "
          f"{summary['t_done_wall'] - summary['t_end_wall']:.2f}")
    print(f"peak HBM {summary['peak_bytes_in_use'] / 2 ** 30:.2f} GiB of "
          f"{summary['bytes_limit'] / 2 ** 30:.2f}: the step program needs "
          f"{summary['step_program_bytes'] / 2 ** 30:.2f} (compiler), the "
          f"allocator's peak reads "
          f"{summary['allocator_peak_bytes'] / 2 ** 30:.2f}; "
          f"memory_stats {summary['memory_stats']}")
    if on_tpu:
        mfu = (flops.train_flops_per_token(shapes) * tokens_per_s_per_chip
               / facts["peaks"]["bf16_flops_per_s"])
        print(f"end-to-end MFU {100 * mfu:.2f} % "
              f"({flops.train_flops_per_token(shapes) / 1e9:.3f} GFLOP/token, "
              f"no recompute counted)")

    group = "per_layer" if args.trace else "end_to_end"
    if args.trace:
        metrics = _read_metrics(spec.metrics_for(args.workload, group), facts)
    else:
        metrics = {
            m["name"]: {"value": float(end_to_end[m["name"]]), "unit": m["unit"]}
            for m in spec.metrics_for(args.workload, group)
            if end_to_end.get(m["name"]) is not None
        }
    for note in facts["notes"]:
        print(note)
    for f in failures:
        print(f"benchmarks/run.py: NOT CORRECT: {f}", file=sys.stderr)

    device = {
        "platform": summary["platforms"][0],
        "kind": summary["device_kind"],
        "count": summary["device_count"],
        "memory_peak_bytes": summary["peak_bytes_in_use"],
    }
    line: Dict[str, Any] = {
        "correct": not failures,
        "attempted": win["steps"],
        "failed": win["nonfinite_losses"],
        "metrics": metrics,
        "device": device,
    }
    trace = summary["traced"]
    if args.trace and trace and trace.get("devices"):
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    if not on_tpu:
        # a rehearsal: nothing from a CPU run stands under a metric's name
        line["rehearsal_values"] = line.pop("metrics")
        line["metrics"] = {}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
