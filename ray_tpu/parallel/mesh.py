"""Device mesh management — the TPU-native answer to process groups.

The reference builds distributed training on NCCL/GLOO process groups
(python/ray/util/collective/collective.py, train/torch/config.py:69). On TPU the
idiomatic unit is a *named mesh* over which XLA lays out collectives on ICI; we
standardize six axes (any of which may be size 1):

  dp    pure data parallelism (params replicated)
  fsdp  data parallelism with params sharded (ZeRO-3 style, all-gather on use)
  pp    pipeline stages
  tp    tensor (megatron-style) parallelism
  cp    context/sequence parallelism (ring attention)
  ep    expert parallelism (MoE all-to-all)

Axis order matters for ICI locality: innermost axes get nearest-neighbor links,
so tp (latency-bound, per-layer collectives) is placed innermost and dp
(bandwidth-bound, once-per-step grad reduce) outermost — the layout recipe of
the public scaling literature.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Outermost → innermost.
AXIS_ORDER: Tuple[str, ...] = ("dp", "pp", "fsdp", "ep", "cp", "tp")

# Axes over which a global batch is split.
BATCH_AXES: Tuple[str, ...] = ("dp", "fsdp")


@dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape. Unspecified axes default to size 1."""

    dp: int = 1
    pp: int = 1
    fsdp: int = 1
    ep: int = 1
    cp: int = 1
    tp: int = 1

    @property
    def sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    @property
    def num_devices(self) -> int:
        n = 1
        for v in self.sizes.values():
            n *= v
        return n

    def batch_size_divisor(self) -> int:
        return self.dp * self.fsdp

    @staticmethod
    def for_devices(n: int, *, tp: int = 1, pp: int = 1, cp: int = 1, ep: int = 1,
                    fsdp: Optional[int] = None) -> "MeshSpec":
        """Fill the data axes with whatever devices remain after model axes."""
        model = tp * pp * cp * ep
        if n % model != 0:
            raise ValueError(f"{n} devices not divisible by tp*pp*cp*ep={model}")
        rest = n // model
        if fsdp is None:
            fsdp, dp = rest, 1
        else:
            if rest % fsdp:
                raise ValueError(f"residual {rest} not divisible by fsdp={fsdp}")
            dp = rest // fsdp
        return MeshSpec(dp=dp, pp=pp, fsdp=fsdp, ep=ep, cp=cp, tp=tp)


def make_mesh(
    spec: MeshSpec, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    if devices is None:
        devices = jax.devices()
    n = spec.num_devices
    if len(devices) < n:
        raise ValueError(f"MeshSpec needs {n} devices, have {len(devices)}")
    shape = tuple(spec.sizes[a] for a in AXIS_ORDER)
    arr = np.array(devices[:n]).reshape(shape)
    return Mesh(arr, AXIS_ORDER)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    devices = [device] if device is not None else jax.devices()[:1]
    return make_mesh(MeshSpec(), devices)


def data_sharding(mesh: Mesh, extra_dims: int = 1) -> NamedSharding:
    """Sharding for a [global_batch, ...] input batch: batch split over dp+fsdp,
    sequence split over cp when present, remaining dims replicated."""
    cp = mesh.shape.get("cp", 1)
    seq_axis = "cp" if cp > 1 else None
    dims = [BATCH_AXES] + [seq_axis] + [None] * max(0, extra_dims - 1)
    return NamedSharding(mesh, P(*dims))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# Trace-time context: what the step factory knows of the chips its step will
# run on. Model code reads it while the step traces; train_step enters both
# inside its jitted body so they are active whenever the step traces.
class _Observed(threading.local):
    mesh: Optional[Mesh] = None
    memory: Tuple[Optional[int], int] = (None, 0)   # outside chip_memory


_local = _Observed()


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """The mesh, for model code that needs it (GPT-2's ring attention wraps a
    shard_map over it)."""
    prev = _local.mesh
    _local.mesh = mesh
    try:
        yield mesh
    finally:
        _local.mesh = prev


def current_mesh() -> Optional[Mesh]:
    return _local.mesh


@contextlib.contextmanager
def chip_memory(bytes_limit: Optional[int], resident_bytes: int):
    """What the step factory observed of one chip of the mesh:
    ``bytes_limit`` the device's ``memory_stats()["bytes_limit"]`` (None where
    it states none — the CPU backend), ``resident_bytes`` what a chip holds
    through the whole step besides activations: the placed state, and the
    gradients (the parameters' bytes again). Outside it a remat rule knows no
    limit (GPT-2's ``remat=True`` then keeps only each block's input)."""
    prev = _local.memory
    _local.memory = (bytes_limit, resident_bytes)
    try:
        yield
    finally:
        _local.memory = prev


def current_chip_memory() -> Tuple[Optional[int], int]:
    return _local.memory
