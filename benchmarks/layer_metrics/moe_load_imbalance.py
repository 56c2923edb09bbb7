"""`moe_load_imbalance`: How far the fullest held expert of the worst expert
layer stands over its layer's mean, `max_per_expert · held ÷ pairs − 1`, mean
over the TIMED window's steps — from the program's `train/step_counters`
events. What the selection bias is there to hold near 0; a router that drifts
off set-up's balance shows here before it fills a second pass. Nothing from a
program that hands no counters out of its step."""

LAYER = "Model"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_counter"


def read(facts):
    from benchmarks.harness import step_counters

    return step_counters.load_imbalance(facts)
