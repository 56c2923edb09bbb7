"""`moe_passes_per_step`: Passes over the held experts' row buffer a step of
the TIMED window, Σ over the expert layers, mean over the window's steps —
from the program's `train/step_counters` events (`passes`: the trip count of
`ops/moe._run_passes`, a value of the step that leaves it as an output).
6.0 / 4.0 where every layer of the Nemotron / LFM2 cell ran one pass in every
step; a second pass costs ~10 ms a layer and step. Nothing from a program
that hands no counters out of its step."""

LAYER = "Kernels"
UNIT = "count"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_counter"


def read(facts):
    from benchmarks.harness import step_counters

    return step_counters.passes_per_step(facts)
