"""Batch iteration with double-buffered device transfer.

Parity: data/iterator.py:234 (`iter_torch_batches`) — the accelerator-feeding
edge of the Data layer. TPU-native shape: batches are assembled on host
(zero-copy out of the shm store where possible), then `jax.device_put` with
an optional NamedSharding; a one-batch prefetch pipeline keeps the transfer
of batch N+1 overlapped with compute on batch N (double buffering — the
device_put is async, so issuing it early is all the overlap XLA needs).
"""

from __future__ import annotations

import collections
import itertools
from typing import Any, Dict, Iterator, Optional

import numpy as np

from ray_tpu.data.block import (
    Block, block_concat, block_num_rows, block_size_bytes, block_slice,
)
from ray_tpu.tracing import PROFILE_MIN_DUR_S, profile_span
from ray_tpu.tracing import names as span_names


def _span(path: str, batch: int) -> profile_span:
    """A span of the Data layer (``ray_tpu:data/<name>``), tagged with the
    iterator's own count of the batch it works towards. Opened and closed
    where the work happens, never across a ``yield``: a consumer's pause is
    not the iterator's time."""
    component, name = path.split("/")
    return profile_span(name, {"batch": batch}, component=component,
                        min_dur_s=PROFILE_MIN_DUR_S)


def _host_batches(
    block_refs: Iterator[Any], batch_size: int, drop_last: bool
) -> Iterator[Block]:
    """Assemble exact-size host batches from a stream of block refs."""
    import ray_tpu

    buf = []
    buffered = 0
    batch = 0      # batches handed out so far: the id of the one in the making
    for ref in block_refs:
        with _span(span_names.DATA_GET_BLOCK, batch) as span:
            block = ray_tpu.get(ref)
            span.args.update(rows=block_num_rows(block),
                             bytes=block_size_bytes(block))
        if block_num_rows(block) == 0:
            continue
        buf.append(block)
        buffered += block_num_rows(block)
        while buffered >= batch_size:
            with _span(span_names.DATA_ASSEMBLE, batch):
                merged = block_concat(buf)
                out = block_slice(merged, 0, batch_size)
            yield out
            with _span(span_names.DATA_ASSEMBLE, batch):
                rest = block_slice(merged, batch_size, buffered)
            buf = [rest] if block_num_rows(rest) else []
            buffered -= batch_size
            batch += 1
    if buffered and not drop_last:
        with _span(span_names.DATA_ASSEMBLE, batch):
            out = block_concat(buf)
        yield out


def _device_put(target: Any):
    """``jax.device_put`` onto ``target`` under a span, counting batches."""
    import jax

    count = itertools.count()

    def put(batch: Block):
        with _span(span_names.DATA_DEVICE_PUT, next(count)):
            return jax.device_put(batch, target)

    return put


def _prefetched(items: Iterator[Any], put, depth: int) -> Iterator[Any]:
    """Double-buffering window: issue `put` (an async device transfer) for
    item N+1..N+depth while item N is being consumed."""
    window: collections.deque = collections.deque()
    for item in items:
        window.append(put(item))
        if len(window) >= depth:
            yield window.popleft()
    while window:
        yield window.popleft()


def iter_batches(
    block_refs: Iterator[Any],
    *,
    batch_size: int = 256,
    prefetch_batches: int = 1,
    drop_last: bool = False,
    device: Any = None,
    sharding: Any = None,
) -> Iterator[Dict[str, Any]]:
    """Yield dict-of-array batches. With `device`/`sharding` set, batches are
    jax arrays already resident (or in flight) on the accelerator; the
    prefetch window issues transfers ahead of consumption."""
    host_iter = _host_batches(block_refs, batch_size, drop_last)
    if device is None and sharding is None:
        yield from host_iter
        return

    put = _device_put(sharding if sharding is not None else device)
    yield from _prefetched(host_iter, put, max(1, prefetch_batches + 1))
