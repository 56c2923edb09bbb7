"""Resource model: CPUs, memory, TPU chips, and ICI-slice topology labels.

TPU-first design (the reference's gap): ``_private/resource_spec.py:279`` only
autodetects GPUs; accelerator constants live in ``util/accelerators/accelerators.py``
with no TPU topology awareness. Here TPUs are first-class:

- every node reports ``TPU`` (chip count) plus a ``TPU-<gen>`` generation resource
  (e.g. ``TPU-v5litepod``), mirroring how the reference exposes
  ``accelerator_type:<T4>`` style resources;
- nodes in the same ICI slice share a ``tpu-slice:<name>`` label so placement groups
  with PACK affinity land on one slice (ICI > DCN bandwidth);
- autodetection counts the chip device files this host exposes and never touches
  JAX: a chip belongs to one process, and the processes that count chips (driver,
  raylet) are long-lived parents of the worker that must open them.
"""

from __future__ import annotations

import glob
import importlib.util
import os
from typing import Dict, List, Optional

# Fractional resources use fixed-point arithmetic to avoid float drift, mirroring
# the reference's FixedPoint (src/ray/raylet/scheduling/fixed_point.h).
RESOURCE_UNIT = 10_000


def to_fixed(v: float) -> int:
    return int(round(v * RESOURCE_UNIT))


def from_fixed(v: int) -> float:
    return v / RESOURCE_UNIT


class ResourceSet:
    """A bag of named resource quantities with fixed-point internal storage."""

    __slots__ = ("_amounts",)

    def __init__(self, amounts: Optional[Dict[str, float]] = None, _fixed=None):
        if _fixed is not None:
            self._amounts = dict(_fixed)
        else:
            self._amounts = {
                k: to_fixed(v) for k, v in (amounts or {}).items() if v != 0
            }

    @staticmethod
    def from_fixed_dict(d: Dict[str, int]) -> "ResourceSet":
        return ResourceSet(_fixed={k: v for k, v in d.items() if v != 0})

    def to_dict(self) -> Dict[str, float]:
        return {k: from_fixed(v) for k, v in self._amounts.items()}

    def fixed(self) -> Dict[str, int]:
        return dict(self._amounts)

    def get(self, name: str) -> float:
        return from_fixed(self._amounts.get(name, 0))

    def is_empty(self) -> bool:
        return not self._amounts

    def fits(self, other: "ResourceSet") -> bool:
        """True if `other` (a demand) fits within self (availability)."""
        return all(self._amounts.get(k, 0) >= v for k, v in other._amounts.items())

    def subtract(self, other: "ResourceSet") -> "ResourceSet":
        out = dict(self._amounts)
        for k, v in other._amounts.items():
            out[k] = out.get(k, 0) - v
        return ResourceSet.from_fixed_dict(out)

    def add(self, other: "ResourceSet") -> "ResourceSet":
        out = dict(self._amounts)
        for k, v in other._amounts.items():
            out[k] = out.get(k, 0) + v
        return ResourceSet.from_fixed_dict(out)

    def utilization(self, total: "ResourceSet") -> float:
        """Max fractional utilization across resources present in `total`."""
        utils = []
        for k, tot in total._amounts.items():
            if tot <= 0:
                continue
            avail = self._amounts.get(k, 0)
            utils.append(1.0 - avail / tot)
        return max(utils) if utils else 0.0

    def __eq__(self, other):
        return isinstance(other, ResourceSet) and other._amounts == self._amounts

    def __repr__(self):
        return f"ResourceSet({self.to_dict()})"


def tpu_device_files() -> List[str]:
    """One device file per chip: ``/dev/accel<N>`` (v2–v4) or the numbered
    VFIO groups ``/dev/vfio/<N>`` (v5e and newer) — what libtpu enumerates."""
    return sorted(
        glob.glob("/dev/accel[0-9]*") + glob.glob("/dev/vfio/[0-9]*")
    )


def detect_tpu_resources() -> Dict[str, float]:
    """Count this host's TPU chips WITHOUT initialising JAX.

    Returns {} when there is no TPU here: ``JAX_PLATFORMS`` names platforms
    and ``tpu`` is not one of them (this process tree was told to stay off
    the chip), no chip device file exists, or libtpu is not installed (nothing
    could drive one). Raises when chips are present but cannot be opened —
    that is a broken host, not a TPU-less one.

    The count comes from the device files, not from ``TPU_ACCELERATOR_TYPE``:
    that variable names the slice the host belongs to, and a host can be
    handed fewer chips than the slice has (seen: ``v5litepod-4`` with one
    chip visible). It only supplies the generation label."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.lower().split(","):
        return {}
    chips = tpu_device_files()
    if not chips or importlib.util.find_spec("libtpu") is None:
        return {}
    locked = [p for p in chips if not os.access(p, os.R_OK | os.W_OK)]
    if locked:
        raise RuntimeError(
            f"TPU device files {locked} exist but this user cannot open them "
            "read-write; fix the permissions or pass num_tpus=0 explicitly"
        )
    out = {"TPU": float(len(chips))}
    acc_type = os.environ.get("TPU_ACCELERATOR_TYPE")  # e.g. "v5litepod-8"
    if acc_type:
        out[f"TPU-{acc_type.split('-')[0]}"] = float(len(chips))
    return out


def node_resources(
    num_cpus: Optional[int] = None,
    num_tpus: Optional[int] = None,
    memory_mb: Optional[int] = None,
    custom: Optional[Dict[str, float]] = None,
    detect_tpus: bool = True,
) -> Dict[str, float]:
    """Build the resource dict a node advertises on registration."""
    res: Dict[str, float] = {}
    res["CPU"] = float(num_cpus if num_cpus is not None else os.cpu_count() or 1)
    if num_tpus is not None:
        res["TPU"] = float(num_tpus)
    elif detect_tpus:
        res.update(detect_tpu_resources())
    if memory_mb is None:
        try:
            import psutil

            memory_mb = int(psutil.virtual_memory().total / (1024 * 1024) * 0.7)
        except ImportError:  # pragma: no cover
            memory_mb = 4096
    res["memory"] = float(memory_mb)
    if custom:
        res.update(custom)
    return res
