"""Rehearsal 3: compile a cell's step at its real size for a v5e that is
described, not attached (no chip time; `on-chip-measurement` guide, section 2).

    JAX_PLATFORMS=cpu python benchmarks/harness/rehearse_compile.py \
        --cell gpt2-xl.fsdp4-dataset [--per-chip-batch 8] [--remat 1]

Prints the bytes the compiled step needs on each device
(``compiled.memory_analysis()``), the collectives the compiler put in and the
Mosaic calls it kept. Nothing runs: no time, rate or share comes from here.
"""

from __future__ import annotations

import argparse
import collections
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def main() -> int:
    from benchmarks.harness import spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--per-chip-batch", type=int)
    ap.add_argument("--remat", type=int, choices=(0, 1))
    args = ap.parse_args()

    import importlib

    import jax
    import numpy as np
    from jax.experimental import topologies

    from ray_tpu.parallel import mesh as mesh_lib

    cell, config, _ = spec.load_cell(args.cell)
    if args.per_chip_batch:
        cell["per_chip_batch"] = args.per_chip_batch
    if args.remat is not None:
        cell["remat"] = bool(args.remat)
    family = importlib.import_module(f"benchmarks.families.{config['family']}")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = mesh_lib.make_mesh(
        mesh_lib.MeshSpec(**cell["mesh"]), list(topo.devices)[:cell["chips"]])
    fn, abstract_args = family.abstract_step(config, cell, mesh)
    compiled = fn.lower(*abstract_args).compile()
    mem = compiled.memory_analysis()
    gib = 2.0 ** 30
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    hlo = compiled.as_text()
    ops = collections.Counter()
    for line in hlo.splitlines():
        m = re.search(r"= \S+ (%s)(-start)?\(" % "|".join(COLLECTIVES), line)
        if m:
            ops[m.group(1)] += 1
    print(f"cell {args.cell}: per_chip_batch={cell['per_chip_batch']} "
          f"remat={cell['remat']} mesh={cell['mesh']} on a described v5e:2x2 "
          "(compiled, not run)")
    print(f"  per device: arguments {mem.argument_size_in_bytes / gib:.2f} GiB"
          f", outputs {mem.output_size_in_bytes / gib:.2f}, temporaries "
          f"{mem.temp_size_in_bytes / gib:.2f}, aliased "
          f"{mem.alias_size_in_bytes / gib:.2f} -> {total / gib:.2f} GiB")
    print(f"  collectives: {dict(ops) or 'none'}; Mosaic calls: "
          f"{hlo.count('custom_call_target=' + chr(34) + 'tpu_custom_call')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
