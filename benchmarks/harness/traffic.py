"""The one general traffic generator. A mix is a file of parameters
(``traffic/<mix>.json``); nothing here knows a mix or a configuration by name.

Rows are made from (``--seed``, row id), so the same seed gives the same
tokens whichever block, task or process makes them.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def token_rows(ids, seed: int, seq_len: int, alphabet: int) -> np.ndarray:
    return np.stack([
        np.random.default_rng([seed, int(i)]).integers(
            0, alphabet, size=seq_len, dtype=np.int32)
        for i in ids
    ])


def make_tokens(block, seed: int, seq_len: int, alphabet: int):
    """Data map task: a block of row ids -> a block of token rows."""
    return {"tokens": token_rows(block["id"], seed, seq_len, alphabet)}


def with_targets(block):
    """Data map task: next-token targets for a block of token rows."""
    tokens = block["tokens"]
    targets = np.roll(tokens, -1, axis=1)
    targets[:, -1] = -1
    return {"tokens": tokens, "targets": targets}


def host_batch(n_rows: int, seed: int, seq_len: int, alphabet: int) -> Dict[str, Any]:
    """Rows 0..n_rows-1 as the dataset would deliver them (the resident mix's
    batch; the rows the reference check uses)."""
    return with_targets(make_tokens(
        {"id": np.arange(n_rows)}, seed, seq_len, alphabet))


def build_dataset(traffic: Dict[str, Any], cell: Dict[str, Any], seed: int,
                  seq_len: int, global_batch: int):
    """The mix's Dataset, lazy: ``n_blocks`` blocks of ``batches_per_block``
    global batches of rows each (both in the cell's file)."""
    from ray_tpu import data

    n_blocks = cell["n_blocks"]
    rows = n_blocks * cell["batches_per_block"] * global_batch
    return (
        data.range(rows, parallelism=n_blocks)
        .map_batches(make_tokens, fn_kwargs={
            "seed": seed, "seq_len": seq_len, "alphabet": traffic["alphabet"]})
        .map_batches(with_targets)
    )

