"""`step_ms_mean`: Mean host interval between consecutive step completions over
the whole window: what `tokens_per_s_per_chip`, made from the median interval,
leaves out (rare host stalls, the Dataset's block boundaries) shows as the gap
to `step_ms_median`."""

LAYER = "Step"
UNIT = "ms"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(facts):
    return facts['summary']['window']['step_ms_mean']
