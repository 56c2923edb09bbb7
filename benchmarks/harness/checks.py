"""The comparison that decides ``correct``. Driver side, from the worker's
summary row; returns the failures as sentences."""

from __future__ import annotations

from typing import Any, Dict, List


def failures(summary: Dict[str, Any], cell: Dict[str, Any],
             rehearse_cpu: bool) -> List[str]:
    bad: List[str] = []
    ref, win = summary["reference"], summary["window"]
    prog, plain = ref["program"], ref["reference"]
    checks = [("loss", ref["loss_rtol"])]
    if ref["with_grad"]:
        checks.append(("grad_norm", ref["grad_norm_rtol"]))
    for name, rtol in checks:
        if not abs(prog[name] - plain[name]) <= rtol * abs(plain[name]):
            bad.append(f"{name} of the program on {ref['rows']} rows, "
                       f"{prog[name]!r}, is not within rtol {rtol:g} of the "
                       f"float32 reference's {plain[name]!r}")
    if not summary["data_ok"]:
        bad.append("the first batch out of the loop's source is not the rows "
                   "the seed gives")
    if win["nonfinite_losses"]:
        bad.append(f"{win['nonfinite_losses']} loss(es) not finite")
    last = sum(win["losses_tail"]) / len(win["losses_tail"])
    if not last < win["first_loss"]:
        bad.append(f"loss did not fall: {win['first_loss']:.4f} at the first "
                   f"step, {last:.4f} at the window's end")
    if summary["step_counter"] != summary["steps_run"]:
        bad.append(f"the state's step counter reads {summary['step_counter']} "
                   f"after {summary['steps_run']} steps")
    if win["compiles_in_window"]:
        bad.append(f"{win['compiles_in_window']} compilation(s) inside the "
                   "measured window")
    if summary["device_count"] != cell["chips"]:
        bad.append(f"ran on {summary['device_count']} device(s), the cell "
                   f"asks for {cell['chips']}")
    if not rehearse_cpu:
        if summary["platforms"] != ["tpu"]:
            bad.append(f"worker devices are on {summary['platforms']}, not tpu")
        if summary["attention"] != ["pallas", False]:
            bad.append("attention resolved to (impl, interpret)="
                       f"{summary['attention']}, not compiled Pallas")
    return bad

