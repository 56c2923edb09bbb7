"""`flash_window_tile_share`: Of the (q tile, kv tile) pairs the causal walk
alone would visit, the share the WINDOWED flash calls visit — from the
program's `ops/flash_tiling` events (ray_tpu/ops/attention.py: one a distinct
traced call; since PR 66 each carries `window`, `tiles_visited` and
`tiles_causal`), summed over the windowed calls of the run, forward and
backward. Lower is better: (band + its edge tiles) ÷ triangle; 100 would be
a window that skips nothing. Nothing from a program whose events carry no
window, or that traced no windowed call."""

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_counter"

EVENT = ("ops", "flash_tiling")


def read(facts):
    from benchmarks.harness import session_timeline

    try:
        events = session_timeline.load_record() or ()
    except Exception as e:  # noqa: BLE001 - a reader never raises
        facts.setdefault("notes", []).append(
            f"flash_window_tile_share: cannot read the session's record: {e!r}")
        return None
    calls = {tuple(sorted(a.items())): a for a in (
        e.get("args") or {} for e in events
        if (e.get("cat"), e.get("name")) == EVENT) if a.get("window")}
    causal = sum(a["tiles_causal"] for a in calls.values())
    if not causal:
        return None
    return 100.0 * sum(a["tiles_visited"] for a in calls.values()) / causal
