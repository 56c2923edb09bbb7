"""`launch_s`: `fit()` called (driver clock) -> the worker's loop entered (the
worker's wall clock, first report row). Holds the Dataset's materialisation
in cells that have one: `Dataset.split` runs inside `fit()` before the
worker is handed the loop."""

LAYER = "Launch"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(facts):
    return facts['summary']['t_enter_wall'] - facts['driver']['t_fit_called']
