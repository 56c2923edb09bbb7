"""`dataset_materialize_s`: Driver clock around `Dataset.split` inside `fit()`:
today it materialises the whole dataset (every map task) before a worker
starts."""

LAYER = "Data"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "host_clock"


def read(facts):
    return facts['driver'].get('dataset_materialize_s')
