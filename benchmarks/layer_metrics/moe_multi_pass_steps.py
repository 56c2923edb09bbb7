"""`moe_multi_pass_steps`: Share of the TIMED window's steps in which any
expert layer ran more than one pass over its row buffer — from the program's
`train/step_counters` events (`passes` a layer). Where it is neither 0 nor
100 the window's median step interval falls on one side of a second pass or
the other by seed. Nothing from a program that hands no counters out of its
step."""

LAYER = "Kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_counter"


def read(facts):
    from benchmarks.harness import step_counters

    return step_counters.multi_pass_steps(facts)
