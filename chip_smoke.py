"""Does the Train main path still start on the chip?

    python chip_smoke.py

drives, once, what a user of ray_tpu.train drives: ``ray_tpu.init()`` (a
subprocess cluster that counts this host's chips) → ``JaxTrainer`` with one
worker leased every chip → in that worker a mesh over ``jax.devices()``,
``make_gpt2_train_step(gpt2_124m())`` at full width and depth, batches from
``get_dataset_shard("train").iter_batches(sharding=…)``, ``step_fn`` for a few
tens of steps and ``train.report`` per step. Weights and tokens are random,
from a seed. Then one step of a small EvaByte (``models/llama.py`` with the
EVA mixer, ``remat=True``) through the same factory, for the ``ops/eva_tiling``
and ``model/remat_policy`` decisions it is traced with and the
``model/head_loss`` event of its eight heads' chunked loss (each chunk's
gradient made in the forward, PR 39), and one step of a small
Nemotron-H hybrid (``models/nemotron_h.py``: Mamba-2, LatentMoE and attention
layers and the MTP module) for its ``model/layer_pattern`` and
``model/expert_load`` events and the ``ops/ssd_tiling`` decisions of its
state-space scan's kernel pair (PR 41), one step of a small MiniCPM-SALA
(``models/minicpm_sala.py``: three lightning layers on that scan's kernels at
one head a group and one block-sparse layer past its ``dense_len``, PR 47) for
its ``LLLS`` pattern, its ``model/sparse_selection`` event and the
``ops/sparse_tiling`` decisions of its three attention kernels, one step of a
small LFM2-MoE (``models/lfm2_moe.py``: a short-convolution + dense layer, an
attention layer at hd 64 and three short-convolution layers over gated
experts, PR 50) for its ``DACCC`` pattern, the ``model/remat_policy`` decision
over its three kinds and its ``model/expert_load`` events, one step of a small
DeepSeek-V2 (``models/deepseek_v2.py``: latent attention at q·k 192 / v 128
over a dense layer and two layers of shared + routed experts under a balance
loss, PR 55) for its ``DEE`` pattern, the flash kernels' ``ops/flash_tiling``
at the two widths and the balance loss its step says beside its load, one
step of a small Xing4.0 (the same ``models/deepseek_v2.py`` with query
compression, the biased-sigmoid router, an MTP module and four
manifold-constrained hyper-connection streams around every sublayer, PR 57)
for its ``model/hyper_connection`` event, the ``ops/mhc_tiling`` decisions
of its hyper-connection kernels (PR 58: one of each of the four, or it
fails) and its expert layers' loads, the MTP module's among them, one step of
a small Qwen3-Next (``models/qwen3_next.py``: three Gated DeltaNet layers on
the delta rule's three kernels, ``ops/gated_delta.py``, and one output-gated
attention layer at hd 256, each over gated experts beside a gated shared one,
PR 61) for its ``LLLF`` pattern, its ``model/remat_policy`` decision and the
``ops/delta_tiling`` decisions of the solve, the forward and the backward
kernel and of the four kernels of the mixer's elementwise work around them
(``ops/delta_pointwise.py``, PR 63; or it fails), one step of a small Ouro
(``models/llama.py`` with ``ut_steps`` 2 over two layers, sandwich norms and
the exit gate, PR 64) for its ``model/loop`` event (or it fails), the remat
rule's passes and applications and the mean exit distribution the step says
of itself, one step of a small Trinity-class model (``models/afmoe.py``:
window and full gated attention layers in one pattern, experts beside a
shared one, PR 66) for its ``DWFWW`` pattern and the ``ops/flash_tiling``
decisions of its windowed calls beside its full ones — ``window`` and how
many of the triangle's tile pairs each visits (or it fails) —, and —
what the expert layer's chosen-set mask
rests on — that this backend's ``lax.top_k`` lists equal elements in index
order (``chosen_rows_off``). It then checks what came back (see
check_training/check_device) and, after ``shutdown()``, prints from the
session's record (``ray_tpu.timeline()``, PR 35) the phases of the ``fit()``
trace, the worker processes started and reaped and the ``train/compile``
events of the run — failing if ``train/fit`` or ``train/loop_entered`` is not
in it (``session_story``) — and, beside each toy expert run's set-up
``model/expert_load`` lines, what its step said of itself at run time: the
``train/step_counters`` event the record holds for it (PR 52; fails where a
step with an expert layer left none: ``step_load_line``).

This process never initialises a JAX backend: a chip belongs to one process
and that process is the train worker, so every device fact below travelled
through ``train.report``. With no chip it exits non-zero within seconds and
prints no result; on a TPU the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

It reports set-up (compile) seconds and no throughput: speed is the
benchmark's business. Sizes are arguments of ``run``; ``main`` always asks for
GPT-2-124M on the chip (tests/test_chip_smoke.py passes gpt2_tiny and CPU).
"""

from __future__ import annotations

import json
import math
import re
import sys
from typing import Any, Dict, List, Tuple

STEPS = 24
PER_CHIP_BATCH = 8           # 124M at 8×1,024 tokens fits a 16 GB chip with room
DATASET_BATCHES = 4          # global batches in the dataset: 24 steps = 6 epochs
ALPHABET = 64                # tokens come from the first 64 ids: learning that
                             # much alone is worth ln(vocab/64) nats of loss
LR, WARMUP = 6e-4, 4
MIN_LOSS_DROP = 0.25         # of those nats, first step → mean of the last four
# First-step agreement of the model's attention (compiled Pallas on the chip)
# with attention_impl="xla", and of the model with itself under remat=True,
# on the same batch, same chip. Both run bf16 matmuls with f32 accumulation
# and differ in the order of the softmax/accumulate roundings; bf16's unit
# roundoff is 2^-9. The loss is a mean over thousands of tokens, the gradient
# norm a root of a sum over every parameter, so a few roundoffs bound each.
LOSS_RTOL = 2.0 ** -10
GRAD_NORM_RTOL = 2.0 ** -8
# Nemotron-3-Super's routing as the cell runs it: 8 rows of 4,096 tokens over
# 512 experts, 22 chosen
ROUTER_SHAPE = (32768, 512)
ROUTER_TOP_K = 22


def with_targets(block):
    """Data map task: next-token targets for a block of token rows."""
    import numpy as np

    tokens = block["tokens"]
    targets = np.roll(tokens, -1, axis=1)
    targets[:, -1] = -1
    return {"tokens": tokens, "targets": targets}


_SHAPE3 = re.compile(r"\b(?:bf16|f32)\[(\d+),(\d+),(\d+)\]")


def chosen_rows_off(seed: int) -> int:
    """Rows in which ``ops/moe._chosen`` (the chosen set as a mask) differs
    from the ids ``lax.top_k`` lists, over scores at the published routing
    shape whose every row has ties across the k-th place. 0 wherever the
    backend's top_k lists equal elements in index order, which the mask
    rests on (tier-1 holds it on the CPU; this holds it on the chip)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    rows, top_k = ROUTER_SHAPE[0], ROUTER_TOP_K
    tied = jnp.round(
        16 * jax.random.uniform(jax.random.PRNGKey(seed), ROUTER_SHAPE)) / 16
    _, ids = jax.lax.top_k(tied, top_k)
    listed = jnp.zeros(ROUTER_SHAPE, bool).at[
        jnp.arange(rows)[:, None], ids].set(True)
    return int(jnp.sum(jnp.any(moe._chosen(tied, top_k) != listed, axis=-1)))


# the held experts' grouped products of the DeepSeek-V2-Lite and Xing4.0
# cells, as ops/grouped_matmul.grouped_dot is given them: (cell, rows of the
# buffer, held experts, pairs a balanced batch lands, K, N of W1 / W3; W2's is
# [N, K])
GROUPED_SHAPES = (("deepseek-v2-lite", 61440, 16, 49152, 2048, 1408),
                  ("xing4.0", 5120, 8, 4096, 3584, 1024))
# two roundings of one float32 sum to bfloat16 differ by one unit in the last
# place of the larger (2 ** -8); the sums differ by their order
GROUPED_OFF_LIMIT = 2 ** -6


def grouped_products_off(seed: int, shapes) -> Dict[str, Any]:
    """``ops/grouped_matmul.grouped_dot`` against ``lax.ragged_dot`` on this
    backend at ``shapes`` (as GROUPED_SHAPES; tests/test_chip_smoke.py gives
    a toy's), both of an expert's matrix shapes: the largest
    difference of the product and of its gradients to the input and to the
    weights, each as a share of the compiler's largest value (bf16 products
    of float32 sums: the two differ by the order of a sum, a few 1e-3), over
    the rows that are pairs; and the rule's decisions for those shapes
    (``ops/grouped_tiling``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import grouped_matmul

    worst = {}
    for cell, rows, held, pairs, K, N in shapes:
        rng = np.random.default_rng(seed)
        sizes = rng.multinomial(pairs, np.full(held, 1.0 / held))
        valid = (jnp.arange(rows) < pairs)[:, None]
        for k, n in ((K, N), (N, K)):
            kx, kw, kd = jax.random.split(jax.random.PRNGKey(seed % 2 ** 31), 3)
            x = jnp.where(valid, jax.random.normal(kx, (rows, k), jnp.bfloat16), 0)
            d = jnp.where(valid, jax.random.normal(kd, (rows, n), jnp.bfloat16), 0)
            w = 0.03 * jax.random.normal(kw, (held, k, n), jnp.bfloat16)
            group_sizes = jnp.asarray(sizes, jnp.int32)

            def results(product):
                o, vjp = jax.vjp(lambda x, w: product(x, w, group_sizes), x, w)
                d_x, d_w = vjp(d)
                return [jnp.where(valid, o, 0), jnp.where(valid, d_x, 0), d_w]

            ours = jax.jit(lambda: results(grouped_matmul.grouped_dot))()
            theirs = jax.jit(lambda: results(
                lambda x, w, s: jax.lax.ragged_dot(
                    x, w, s, preferred_element_type=x.dtype)))()
            for what, a, b in zip(("product", "d_input", "d_weights"),
                                  ours, theirs):
                a, b = a.astype(jnp.float32), b.astype(jnp.float32)
                worst[f"{cell} [{k}->{n}] {what}"] = float(
                    jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
    return {"off": worst, "shapes": [(s[1], s[4], s[5]) for s in shapes],
            "tiling": grouped_matmul.grouped_tiling_decisions()}


def attention_call_shapes(hlo_text: str, head_dim: int) -> Tuple[int, List[List[int]]]:
    """(number of Mosaic custom calls, the distinct [B·H, S, hd] — or, from
    the S-minor kernel pair, [B·H, hd, S] — shapes on their lines) in a
    compiled step's HLO: the operands and results of the flash kernels as
    each device runs them, batch and head merged into the one dim of rows
    the kernels walk."""
    calls, shapes = 0, set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        calls += 1
        for m in _SHAPE3.finditer(line):
            dims = tuple(int(d) for d in m.groups())
            if head_dim in dims[1:]:
                shapes.add(dims)
    return calls, [list(s) for s in sorted(shapes)]


def train_loop(config: Dict[str, Any]) -> None:
    """The per-worker loop. Reports one row per step and a final summary row;
    judges nothing — the driver does, from the rows."""
    import importlib.metadata
    import time
    from collections import Counter
    from dataclasses import replace

    import jax
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models.blocks import (
        compiler_rematerialized, remat_policy_decisions)
    from ray_tpu.ops.attention import flash_tiling_decisions, resolve_attention
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.train.train_step import (
        default_optimizer, make_gpt2_train_step, make_train_step)

    cfg, steps = config["model"], config["steps"]
    cache_events: Counter = Counter()

    def on_event(event: str, **_):
        if event.startswith("/jax/compilation_cache/"):
            cache_events[event.rsplit("/", 1)[1]] += 1

    jax.monitoring.register_event_listener(on_event)
    t_start = time.perf_counter()
    devices = jax.devices()
    backend_seconds = time.perf_counter() - t_start
    mesh = mesh_lib.make_mesh(
        mesh_lib.MeshSpec.for_devices(len(devices)), devices
    )

    def build(model_cfg):
        return make_gpt2_train_step(
            model_cfg, mesh=mesh,
            optimizer=default_optimizer(lr=LR, warmup=WARMUP, total_steps=steps),
            rng=jax.random.PRNGKey(config["seed"]),
        )

    bundle = build(cfg)
    state = bundle.state
    global_batch = config["per_chip_batch"] * len(devices)
    shard = train.get_dataset_shard("train")

    first_batch, hlo, compile_seconds, setup_seconds = None, "", 0.0, 0.0
    step, epochs = 0, 0
    while step < steps:
        for batch in shard.iter_batches(
            batch_size=global_batch, drop_last=True,
            sharding=bundle.data_sharding,
        ):
            if first_batch is None:
                first_batch = batch
                t0 = time.perf_counter()
                hlo = bundle.step_fn.lower(state, batch).compile().as_text()
                compile_seconds = time.perf_counter() - t0
            t0 = time.perf_counter()
            state, metrics = bundle.step_fn(state, batch)
            row = {
                "step": int(state["step"]),
                "loss": float(metrics["loss"]),
                "grad_norm": float(metrics["grad_norm"]),
                "epoch": epochs,
                "seconds": time.perf_counter() - t0,
            }
            if step == 0:
                setup_seconds = time.perf_counter() - t_start
            train.report(row)
            step += 1
            if step == steps:
                break
        epochs += 1

    # First-step parity with the XLA einsum attention: fresh state from the
    # same seed, one batch at full width. The batch is one chip's worth of
    # rows spread over all devices, not the global batch: under fsdp > 1
    # GSPMD leaves the XLA variant's S×S residuals unsharded over the batch
    # (PERF.md, open questions) and the global batch does not fit a chip.
    data_sharding = bundle.data_sharding
    del state, bundle
    n_dev = len(devices)
    parity_rows = n_dev * max(1, config["per_chip_batch"] // n_dev)
    parity_batch = jax.device_put(
        {k: np.asarray(v[:parity_rows]) for k, v in first_batch.items()},
        data_sharding,
    )
    # ... and with the same model under remat=True, which also leaves the
    # model/remat_policy decision for this chip in the summary.
    parity = {}
    for key, model_cfg in ((cfg.attention_impl, cfg),
                           ("xla", replace(cfg, attention_impl="xla")),
                           ("remat", replace(cfg, remat=True))):
        variant = build(model_cfg)
        _, m = variant.step_fn(variant.state, parity_batch)
        parity[key] = {"loss": float(m["loss"]),
                       "grad_norm": float(m["grad_norm"])}
        del variant
    # One step of an EVA model through the same factory: its kernels' tiling
    # decisions and the remat rule's on this block's shapes.
    eva = None
    if config.get("eva_model") is not None:
        from ray_tpu.models import llama
        from ray_tpu.ops.cross_entropy import head_loss_decisions
        from ray_tpu.ops.eva_attention import eva_tiling_decisions

        eva_cfg = config["eva_model"]
        variant = make_train_step(
            llama, eva_cfg, mesh=mesh, rng=jax.random.PRNGKey(config["seed"]),
            optimizer=default_optimizer(lr=LR, warmup=WARMUP, total_steps=steps))
        tokens = np.random.default_rng(config["seed"]).integers(
            0, ALPHABET, size=(n_dev, eva_cfg.seq_len), dtype=np.int32)
        eva_batch = jax.device_put(
            with_targets({"tokens": tokens}), data_sharding)
        # one compile, read and run: what the compiler rematerialized by
        # itself is recompute the remat rule did not choose
        compiled = variant.step_fn.lower(variant.state, eva_batch).compile()
        _, m = compiled(variant.state, eva_batch)
        eva = {"loss": float(m["loss"]), "grad_norm": float(m["grad_norm"]),
               "seq_len": eva_cfg.seq_len,
               "compiler_rematerialized": len(
                   compiler_rematerialized(compiled.as_text())),
               "attention": list(resolve_attention(eva_cfg.attention_impl, mesh)),
               "tiling": eva_tiling_decisions(),
               # its heads' loss goes in chunks (more heads than one); no
               # step before it had a chunked head
               "head_loss": head_loss_decisions()}
        del variant
    # One step of a hybrid (Mamba-2 / LatentMoE / attention / MTP) through
    # the same factory: the pattern it is traced with, and what its first
    # batch sends the experts held here once their selection bias is
    # balanced on it.
    hybrid = None
    if config.get("hybrid_model") is not None:
        from ray_tpu.models import nemotron_h
        from ray_tpu.models.blocks import layer_pattern_decisions
        from ray_tpu.ops.mamba2 import ssd_tiling_decisions

        hybrid_cfg = config["hybrid_model"]
        variant = make_train_step(
            nemotron_h, hybrid_cfg, mesh=mesh,
            rng=jax.random.PRNGKey(config["seed"]),
            optimizer=default_optimizer(lr=LR, warmup=WARMUP, total_steps=steps,
                                        decay_mask=nemotron_h.decays))
        tokens = np.random.default_rng(config["seed"]).integers(
            0, ALPHABET, size=(n_dev, hybrid_cfg.seq_len), dtype=np.int32)
        hybrid_batch = jax.device_put(
            with_targets({"tokens": tokens}), data_sharding)
        with mesh_lib.use_mesh(mesh):
            params, load = nemotron_h.balance_router_bias(
                variant.state["params"], hybrid_batch["tokens"],
                hybrid_batch["targets"], hybrid_cfg)
        _, m = variant.step_fn({**variant.state, "params": params},
                               hybrid_batch)
        hybrid = {"loss": float(m["loss"]), "grad_norm": float(m["grad_norm"]),
                  "seq_len": hybrid_cfg.seq_len,
                  "layer_pattern": layer_pattern_decisions(),
                  "ssd_tiling": ssd_tiling_decisions(),
                  "expert_load": load,
                  "step_load": np.asarray(m["counters"]).tolist(),
                  "chosen_rows_off": chosen_rows_off(config["seed"]),
                  "grouped": grouped_products_off(
                      config["seed"], config["grouped_shapes"])}
        del variant
    # One step of a linear / block-sparse attention hybrid through the same
    # factory: its pattern, the scan's tiling at one head a group, what its
    # sparse layer's selection is and how the three attention kernels tile.
    sala = None
    if config.get("sala_model") is not None:
        from ray_tpu.models import minicpm_sala
        from ray_tpu.models.blocks import layer_pattern_decisions
        from ray_tpu.ops.mamba2 import ssd_tiling_decisions
        from ray_tpu.ops.sparse_attention import sparse_tiling_decisions

        sala_cfg = config["sala_model"]
        variant = make_train_step(
            minicpm_sala, sala_cfg, mesh=mesh,
            rng=jax.random.PRNGKey(config["seed"]),
            optimizer=default_optimizer(lr=LR, warmup=WARMUP, total_steps=steps))
        tokens = np.random.default_rng(config["seed"]).integers(
            0, ALPHABET, size=(n_dev, sala_cfg.seq_len), dtype=np.int32)
        _, m = variant.step_fn(variant.state, jax.device_put(
            with_targets({"tokens": tokens}), data_sharding))
        sala = {"loss": float(m["loss"]), "grad_norm": float(m["grad_norm"]),
                "seq_len": sala_cfg.seq_len,
                "layer_pattern": [d for d in layer_pattern_decisions()
                                  if d["pattern"] == sala_cfg.pattern],
                "ssd_tiling": [d for d in ssd_tiling_decisions()
                               if d["group_heads"] == 1],
                "sparse_selection": minicpm_sala.sparse_selection_decisions(),
                "sparse_tiling": sparse_tiling_decisions()}
        del variant
    # One step of a short-convolution / attention hybrid with gated experts
    # through the same factory: its pattern of pairs, what the rule over its
    # three kinds keeps, what its first batch sends the held experts.
    lfm2 = None
    if config.get("lfm2_model") is not None:
        from ray_tpu.models import lfm2_moe
        from ray_tpu.models.blocks import layer_pattern_decisions

        lfm2_cfg = config["lfm2_model"]
        variant = make_train_step(
            lfm2_moe, lfm2_cfg, mesh=mesh,
            rng=jax.random.PRNGKey(config["seed"]),
            optimizer=default_optimizer(lr=LR, warmup=WARMUP, total_steps=steps,
                                        decay_mask=lfm2_moe.decays))
        tokens = np.random.default_rng(config["seed"]).integers(
            0, ALPHABET, size=(n_dev, lfm2_cfg.seq_len), dtype=np.int32)
        lfm2_batch = jax.device_put(
            with_targets({"tokens": tokens}), data_sharding)
        with mesh_lib.use_mesh(mesh):
            params, load = lfm2_moe.balance_router_bias(
                variant.state["params"], lfm2_batch["tokens"], lfm2_cfg)
        _, m = variant.step_fn({**variant.state, "params": params}, lfm2_batch)
        lfm2 = {"loss": float(m["loss"]), "grad_norm": float(m["grad_norm"]),
                "seq_len": lfm2_cfg.seq_len,
                "layer_pattern": [d for d in layer_pattern_decisions()
                                  if d["pattern"] == lfm2_cfg.pattern],
                "remat_policy": [d for d in remat_policy_decisions()
                                 if d["n_layer"] == lfm2_cfg.n_layer
                                 and d["seq"] == lfm2_cfg.seq_len],
                "expert_load": load,
                "step_load": np.asarray(m["counters"]).tolist()}
        del variant
    # One step of a latent-attention model with shared + routed experts
    # under a balance loss, through the same factory: the flash kernels at
    # unequal q·k and v widths, the loss term out of the layer scan.
    dsv2 = None
    if config.get("dsv2_model") is not None:
        from ray_tpu.models import deepseek_v2
        from ray_tpu.models.blocks import layer_pattern_decisions

        dsv2_cfg = config["dsv2_model"]
        variant = make_train_step(
            deepseek_v2, dsv2_cfg, mesh=mesh,
            rng=jax.random.PRNGKey(config["seed"]),
            optimizer=default_optimizer(lr=LR, warmup=WARMUP,
                                        total_steps=steps))
        tokens = np.random.default_rng(config["seed"]).integers(
            0, ALPHABET, size=(n_dev, dsv2_cfg.seq_len), dtype=np.int32)
        dsv2_batch = jax.device_put(
            with_targets({"tokens": tokens}), data_sharding)
        with mesh_lib.use_mesh(mesh):
            params, load = deepseek_v2.balance_routers(
                variant.state["params"], dsv2_batch["tokens"], dsv2_cfg)
        _, m = variant.step_fn({**variant.state, "params": params}, dsv2_batch)
        counters = np.asarray(m["counters"])
        n_load = len(deepseek_v2.step_fields(dsv2_cfg)) - 1
        dsv2 = {"loss": float(m["loss"]), "grad_norm": float(m["grad_norm"]),
                "seq_len": dsv2_cfg.seq_len,
                "layer_pattern": [d for d in layer_pattern_decisions()
                                  if d["pattern"] == dsv2_cfg.pattern],
                "remat_policy": [d for d in remat_policy_decisions()
                                 if d["n_layer"] == dsv2_cfg.n_layer
                                 and d["seq"] == dsv2_cfg.seq_len],
                "flash_tiling": [d for d in flash_tiling_decisions()
                                 if (d["hd"], d["hd_v"]) == (
                                     dsv2_cfg.qk_dim, dsv2_cfg.v_head_dim)],
                "expert_load": load,
                "step_load": counters[:, :n_load].tolist(),
                # the last column holds the float32's bits
                "balance_loss": np.ascontiguousarray(
                    counters[:, n_load]).view(np.float32).tolist()}
        del variant
    # One step of the same family as Xing4.0 sets it: query compression, the
    # biased-sigmoid router (its biases balanced at set-up), an MTP module
    # and a four-stream hyper-connection around every sublayer.
    xing4 = None
    if config.get("xing4_model") is not None:
        from ray_tpu.models import deepseek_v2, hyper_connections
        from ray_tpu.models.blocks import layer_pattern_decisions
        from ray_tpu.ops.hyper_connections import mhc_tiling_decisions

        xing4_cfg = config["xing4_model"]
        variant = make_train_step(
            deepseek_v2, xing4_cfg, mesh=mesh,
            rng=jax.random.PRNGKey(config["seed"]),
            optimizer=default_optimizer(lr=LR, warmup=WARMUP,
                                        total_steps=steps,
                                        decay_mask=deepseek_v2.decays))
        tokens = np.random.default_rng(config["seed"]).integers(
            0, ALPHABET, size=(n_dev, xing4_cfg.seq_len), dtype=np.int32)
        xing4_batch = jax.device_put(
            with_targets({"tokens": tokens}), data_sharding)
        with mesh_lib.use_mesh(mesh):
            params, load = deepseek_v2.balance_router_bias(
                variant.state["params"], xing4_batch, xing4_cfg)
        _, m = variant.step_fn({**variant.state, "params": params},
                               xing4_batch)
        xing4 = {"loss": float(m["loss"]), "grad_norm": float(m["grad_norm"]),
                 "seq_len": xing4_cfg.seq_len,
                 "layer_pattern": [d for d in layer_pattern_decisions()
                                   if d["pattern"] in (xing4_cfg.pattern,
                                                       xing4_cfg.mtp_pattern)],
                 "hyper_connection": [
                     d for d in hyper_connections.decisions()
                     if d["streams"] == xing4_cfg.hc_mult],
                 "mhc_tiling": [d for d in mhc_tiling_decisions()
                                if d["C"] == xing4_cfg.d_model],
                 "expert_load": load,
                 "step_load": np.asarray(m["counters"]).tolist()}
        del variant
    # One step of a Gated DeltaNet / gated attention hybrid over experts
    # beside a gated shared one: the delta rule's solve, forward and backward
    # kernels at two value heads a key head, the flash pair at hd 256.
    qwen3 = None
    if config.get("qwen3_model") is not None:
        from ray_tpu.models import qwen3_next
        from ray_tpu.models.blocks import layer_pattern_decisions
        from ray_tpu.ops.delta_pointwise import pointwise_tiling_decisions
        from ray_tpu.ops.gated_delta import delta_tiling_decisions

        qwen3_cfg = config["qwen3_model"]
        variant = make_train_step(
            qwen3_next, qwen3_cfg, mesh=mesh,
            rng=jax.random.PRNGKey(config["seed"]),
            optimizer=default_optimizer(lr=LR, warmup=WARMUP,
                                        total_steps=steps,
                                        decay_mask=qwen3_next.decays))
        tokens = np.random.default_rng(config["seed"]).integers(
            0, ALPHABET, size=(n_dev, qwen3_cfg.seq_len), dtype=np.int32)
        qwen3_batch = jax.device_put(
            with_targets({"tokens": tokens}), data_sharding)
        with mesh_lib.use_mesh(mesh):
            params, load = qwen3_next.balance_routers(
                variant.state["params"], qwen3_batch["tokens"], qwen3_cfg)
        _, m = variant.step_fn({**variant.state, "params": params},
                               qwen3_batch)
        counters = np.asarray(m["counters"])
        n_load = len(qwen3_next.step_fields(qwen3_cfg)) - 1
        qwen3 = {"loss": float(m["loss"]), "grad_norm": float(m["grad_norm"]),
                 "seq_len": qwen3_cfg.seq_len,
                 "layer_pattern": [d for d in layer_pattern_decisions()
                                   if d["pattern"] == qwen3_cfg.pattern],
                 "remat_policy": [d for d in remat_policy_decisions()
                                  if d["n_layer"] == qwen3_cfg.n_layer
                                  and d["seq"] == qwen3_cfg.seq_len],
                 "delta_tiling": [d for d in delta_tiling_decisions()
                                  if d["S"] == qwen3_cfg.seq_len],
                 "pointwise_tiling": [d for d in pointwise_tiling_decisions()
                                      if d["S"] == qwen3_cfg.seq_len],
                 "flash_tiling": [d for d in flash_tiling_decisions()
                                  if d["hd"] == qwen3_cfg.head_dim],
                 "expert_load": load,
                 "step_load": counters[:, :n_load].tolist(),
                 "balance_loss": np.ascontiguousarray(
                     counters[:, n_load]).view(np.float32).tolist()}
        del variant
    # One step of a looped stack: two layers run twice on one set of weights,
    # a head and an exit gate after each pass (models/llama.py, PR 64).
    ouro = None
    if config.get("ouro_model") is not None:
        from ray_tpu.models import llama
        from ray_tpu.models.blocks import loop_decisions

        ouro_cfg = config["ouro_model"]
        variant = make_train_step(
            llama, ouro_cfg, mesh=mesh,
            rng=jax.random.PRNGKey(config["seed"]),
            optimizer=default_optimizer(lr=LR, warmup=WARMUP,
                                        total_steps=steps))
        tokens = np.random.default_rng(config["seed"]).integers(
            0, ALPHABET, size=(n_dev, ouro_cfg.seq_len), dtype=np.int32)
        _, m = variant.step_fn(variant.state, jax.device_put(
            with_targets({"tokens": tokens}), data_sharding))
        ouro = {"loss": float(m["loss"]), "grad_norm": float(m["grad_norm"]),
                "seq_len": ouro_cfg.seq_len,
                "loop": [d for d in loop_decisions()
                         if d["passes"] == ouro_cfg.ut_steps
                         and d["layers"] == ouro_cfg.n_layer],
                "remat_policy": [d for d in remat_policy_decisions()
                                 if d.get("passes") == ouro_cfg.ut_steps
                                 and d["seq"] == ouro_cfg.seq_len],
                "exit_distribution": np.asarray(m["counters"]).view(
                    np.float32)[0].tolist()}
        del variant
    # One step of a pattern whose kinds differ in attention: window layers
    # under RoPE whose flash calls walk the band alone, a full layer with no
    # position, gated experts beside a shared one (models/afmoe.py, PR 66).
    afmoe_step = None
    if config.get("afmoe_model") is not None:
        from ray_tpu.models import afmoe
        from ray_tpu.models.blocks import layer_pattern_decisions

        afmoe_cfg = config["afmoe_model"]
        variant = make_train_step(
            afmoe, afmoe_cfg, mesh=mesh,
            rng=jax.random.PRNGKey(config["seed"]),
            optimizer=default_optimizer(lr=LR, warmup=WARMUP,
                                        total_steps=steps,
                                        decay_mask=afmoe.decays))
        tokens = np.random.default_rng(config["seed"]).integers(
            0, ALPHABET, size=(n_dev, afmoe_cfg.seq_len), dtype=np.int32)
        afmoe_batch = jax.device_put(
            with_targets({"tokens": tokens}), data_sharding)
        with mesh_lib.use_mesh(mesh):
            params, load = afmoe.balance_router_bias(
                variant.state["params"], [afmoe_batch["tokens"]], afmoe_cfg)
        _, m = variant.step_fn({**variant.state, "params": params},
                               afmoe_batch)
        afmoe_step = {
            "loss": float(m["loss"]), "grad_norm": float(m["grad_norm"]),
            "seq_len": afmoe_cfg.seq_len, "expert_load": load,
            "layer_pattern": [d for d in layer_pattern_decisions()
                              if d["pattern"] == afmoe_cfg.pattern],
            "remat_policy": [d for d in remat_policy_decisions()
                             if (d["n_layer"], d["seq"]) == (
                                 afmoe_cfg.n_layer, afmoe_cfg.seq_len)],
            "flash_tiling": [d for d in flash_tiling_decisions()
                             if (d["Sq"], d["hd"]) == (
                                 afmoe_cfg.seq_len, afmoe_cfg.head_dim)]}
        del variant
    jax.monitoring.unregister_event_listener(on_event)

    tpu_calls, attn_shapes = attention_call_shapes(hlo, cfg.head_dim)
    train.report({"summary": {
        "platforms": sorted({d.platform for d in devices}),
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax": jax.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "mesh": {a: n for a, n in mesh.shape.items() if n > 1},
        "attention": list(resolve_attention(cfg.attention_impl, mesh)),
        "tpu_custom_calls": tpu_calls,
        "attention_call_shapes": attn_shapes,
        "flash_tiling": flash_tiling_decisions(),
        "remat_policy": remat_policy_decisions(),
        "global_batch": global_batch,
        "epochs": epochs,
        "backend_seconds": backend_seconds,
        "step_compile_seconds": compile_seconds,
        "setup_seconds": setup_seconds,
        "cache_dir": jax.config.jax_compilation_cache_dir,
        "cache_events": dict(cache_events),
        "parity_rows": parity_rows,
        "parity": parity,
        "eva": eva,
        "hybrid": hybrid,
        "sala": sala,
        "lfm2": lfm2,
        "dsv2": dsv2,
        "xing4": xing4,
        "qwen3": qwen3,
        "ouro": ouro,
        "afmoe": afmoe_step,
    }})


def run(model_cfg, *, steps: int, per_chip_batch: int, num_devices: int,
        use_tpu: bool, seed: int = 0, eva_model=None,
        hybrid_model=None, sala_model=None,
        lfm2_model=None, dsv2_model=None,
        xing4_model=None, qwen3_model=None, ouro_model=None,
        afmoe_model=None, grouped_shapes=GROUPED_SHAPES
        ) -> List[Dict[str, Any]]:
    """Driver side: a small token dataset through Data, then
    JaxTrainer(train_loop) with one worker driving `num_devices` devices.
    Returns the reported rows (steps, then the summary); raises the worker's
    error. Needs ray_tpu.init() done; touches no JAX backend."""
    import numpy as np

    from ray_tpu import data, train

    rows = DATASET_BATCHES * per_chip_batch * num_devices
    tokens = np.random.default_rng(seed).integers(
        0, ALPHABET, size=(rows, model_cfg.seq_len), dtype=np.int32
    )
    ds = data.from_numpy(
        np.array_split(tokens, DATASET_BATCHES), column="tokens"
    ).map_batches(with_targets)
    result = train.JaxTrainer(
        train_loop,
        train_loop_config={
            "model": model_cfg, "steps": steps,
            "per_chip_batch": per_chip_batch, "seed": seed,
            "eva_model": eva_model, "hybrid_model": hybrid_model,
            "sala_model": sala_model, "lfm2_model": lfm2_model,
            "dsv2_model": dsv2_model, "xing4_model": xing4_model,
            "qwen3_model": qwen3_model, "ouro_model": ouro_model,
            "afmoe_model": afmoe_model,
            "grouped_shapes": grouped_shapes,
        },
        scaling_config=train.ScalingConfig(
            num_workers=1, use_tpu=use_tpu,
            tpus_per_worker=num_devices if use_tpu else 0,
        ),
        datasets={"train": ds},
    ).fit()
    if result.error is not None:
        raise result.error
    return result.metrics_dataframe


def check_training(rows: List[Dict[str, Any]], model_cfg, steps: int) -> List[str]:
    """What must hold on any device. Returns the failures."""
    step_rows, summary = rows[:-1], rows[-1]["summary"]
    bad = []
    if [r["step"] for r in step_rows] != list(range(1, steps + 1)):
        bad.append(f"step counter {[r['step'] for r in step_rows]} is not "
                   f"1..{steps}")
    for r in step_rows:
        if not (math.isfinite(r["loss"]) and math.isfinite(r["grad_norm"])):
            bad.append(f"step {r['step']}: loss {r['loss']} grad_norm "
                       f"{r['grad_norm']} not finite")
    if summary["epochs"] < 2:
        bad.append(f"the iterator did not cycle: {summary['epochs']} epoch(s)")
    first = step_rows[0]["loss"]
    last = sum(r["loss"] for r in step_rows[-4:]) / 4
    need = MIN_LOSS_DROP * math.log(model_cfg.padded_vocab / ALPHABET)
    if not last < first - need:
        bad.append(f"loss did not fall: {first:.4f} -> {last:.4f} "
                   f"(needs {need:.2f} nats)")
    model = summary["parity"][model_cfg.attention_impl]
    for other, what in (("xla", "attention_impl='xla'"), ("remat", "remat=True")):
        for name, rtol in (("loss", LOSS_RTOL), ("grad_norm", GRAD_NORM_RTOL)):
            got = summary["parity"][other][name]
            if not abs(model[name] - got) <= rtol * abs(got):
                bad.append(f"first-step {name} {model[name]!r} disagrees with "
                           f"{what} {got!r} beyond rtol {rtol:g}")
    # the model/remat_policy decision the remat=True step was traced with
    if not summary["remat_policy"]:
        bad.append("the remat=True step recorded no remat policy decision")
    eva = summary["eva"]
    if eva is not None:
        if not (math.isfinite(eva["loss"]) and math.isfinite(eva["grad_norm"])):
            bad.append(f"the EVA step's loss {eva['loss']} or grad_norm "
                       f"{eva['grad_norm']} is not finite")
        if not any(d["seq"] == eva["seq_len"] for d in summary["remat_policy"]):
            bad.append("the EVA step recorded no remat policy decision")
        # where the kernels run (compiled or interpreted) each is traced with
        # a tiling decision; the XLA formulation has none to make
        kernels = {d["kernel"] for d in eva["tiling"]}
        if eva["attention"][0] == "pallas" and kernels != {"fwd", "bwd"}:
            bad.append(f"the EVA step recorded tiling decisions for "
                       f"{sorted(kernels)}, not for fwd and bwd")
        # its head goes in chunks: each makes its gradient beside its loss
        if not any(d["grad_in_forward"] for d in eva["head_loss"]):
            bad.append("the EVA step's chunked head recorded no "
                       "model/head_loss event with its gradient in the forward")
    hybrid = summary.get("hybrid")
    if hybrid is not None:
        if not (math.isfinite(hybrid["loss"])
                and math.isfinite(hybrid["grad_norm"])):
            bad.append(f"the hybrid step's loss {hybrid['loss']} or grad_norm "
                       f"{hybrid['grad_norm']} is not finite")
        # both of its events: the pattern it was traced with, and what the
        # batch sent the held experts of every expert layer
        if not hybrid["layer_pattern"]:
            bad.append("the hybrid step recorded no model/layer_pattern event")
        if not hybrid["expert_load"]:
            bad.append("the hybrid step recorded no model/expert_load event")
        kernels = {d["kernel"] for d in hybrid["ssd_tiling"]}
        if kernels != {"fwd", "bwd"}:
            bad.append("the hybrid step recorded ops/ssd_tiling decisions for "
                       f"{sorted(kernels)}, not for both scan kernels")
        dropped = sum(e["pairs_dropped"] for e in hybrid["expert_load"])
        if dropped:
            bad.append(f"the hybrid step's expert layers dropped {dropped} "
                       "(token, choice) pairs")
        if hybrid["chosen_rows_off"]:
            bad.append(f"ops/moe._chosen differs from lax.top_k's own list in "
                       f"{hybrid['chosen_rows_off']} of {ROUTER_SHAPE[0]} "
                       "rows with ties: this backend's top_k does not list "
                       "equal elements in index order")
        grouped = hybrid["grouped"]
        for what, off in grouped["off"].items():
            if not off < GROUPED_OFF_LIMIT:
                bad.append(f"ops/grouped_matmul.grouped_dot differs from "
                           f"lax.ragged_dot at {what} by {off} of the largest "
                           f"value (limit {GROUPED_OFF_LIMIT})")
        took = {(d["rows"], d["K"], d["N"], d["form"])
                for d in grouped["tiling"]}
        for rows, K, N in grouped["shapes"]:
            for k, n in ((K, N), (N, K)):
                for form in ("gmm", "gmm_t", "tgmm"):
                    if (rows, k, n, form) not in took:
                        bad.append("no ops/grouped_tiling decision for "
                                   f"{form} at rows={rows} K={k} N={n}")
    sala = summary.get("sala")
    if sala is not None:
        if not (math.isfinite(sala["loss"]) and math.isfinite(sala["grad_norm"])):
            bad.append(f"the MiniCPM-SALA step's loss {sala['loss']} or "
                       f"grad_norm {sala['grad_norm']} is not finite")
        if not sala["layer_pattern"]:
            bad.append("the MiniCPM-SALA step recorded no model/layer_pattern "
                       "event for its pattern")
        if {d["kernel"] for d in sala["ssd_tiling"]} != {"fwd", "bwd"}:
            bad.append("the MiniCPM-SALA step recorded no ops/ssd_tiling "
                       "decision at one head a group for both scan kernels")
        if not any(d["mode"] == "sparse" for d in sala["sparse_selection"]):
            bad.append("the MiniCPM-SALA step recorded no model/"
                       "sparse_selection event on the sparse branch")
        kernels = {d["kernel"] for d in sala["sparse_tiling"]}
        if kernels != {"fwd", "bwd_dq", "bwd_dkv"}:
            bad.append("the MiniCPM-SALA step recorded ops/sparse_tiling "
                       f"decisions for {sorted(kernels)}, not for its three "
                       "kernels")
    lfm2 = summary.get("lfm2")
    if lfm2 is not None:
        if not (math.isfinite(lfm2["loss"]) and math.isfinite(lfm2["grad_norm"])):
            bad.append(f"the LFM2-MoE step's loss {lfm2['loss']} or "
                       f"grad_norm {lfm2['grad_norm']} is not finite")
        if not lfm2["layer_pattern"] or not lfm2["remat_policy"]:
            bad.append("the LFM2-MoE step recorded no model/layer_pattern or "
                       "no model/remat_policy event for its five layers")
        if not lfm2["expert_load"]:
            bad.append("the LFM2-MoE step recorded no model/expert_load event")
        dropped = sum(e["pairs_dropped"] for e in lfm2["expert_load"])
        if dropped:
            bad.append(f"the LFM2-MoE step's expert layers dropped {dropped} "
                       "(token, choice) pairs")
    dsv2 = summary.get("dsv2")
    if dsv2 is not None:
        if not (math.isfinite(dsv2["loss"]) and math.isfinite(dsv2["grad_norm"])):
            bad.append(f"the DeepSeek-V2 step's loss {dsv2['loss']} or "
                       f"grad_norm {dsv2['grad_norm']} is not finite")
        if not dsv2["layer_pattern"] or not dsv2["remat_policy"]:
            bad.append("the DeepSeek-V2 step recorded no model/layer_pattern "
                       "or no model/remat_policy event for its layers")
        if not dsv2["expert_load"]:
            bad.append("the DeepSeek-V2 step recorded no model/expert_load "
                       "event")
        if {d["kernel"] for d in dsv2["flash_tiling"]} != {"fwd", "bwd"}:
            bad.append("the DeepSeek-V2 step recorded no ops/flash_tiling "
                       "decision of both kernels at its two widths")
        # ~1 a layer where the router is near balance, never 0 or below
        if not all(0.5 < b < 8.0 for b in dsv2["balance_loss"]):
            bad.append("the DeepSeek-V2 step said balance losses "
                       f"{dsv2['balance_loss']} of its expert layers")
    qwen3 = summary.get("qwen3")
    if qwen3 is not None:
        if not (math.isfinite(qwen3["loss"])
                and math.isfinite(qwen3["grad_norm"])):
            bad.append(f"the Qwen3-Next step's loss {qwen3['loss']} or "
                       f"grad_norm {qwen3['grad_norm']} is not finite")
        if not qwen3["layer_pattern"] or not qwen3["remat_policy"]:
            bad.append("the Qwen3-Next step recorded no model/layer_pattern "
                       "or no model/remat_policy event for its layers")
        if {d["kernel"] for d in qwen3["delta_tiling"]} != {"solve", "fwd",
                                                            "bwd"}:
            bad.append("the Qwen3-Next step recorded no ops/delta_tiling "
                       "decision of each of the solve, the forward and the "
                       "backward kernel: the delta rule ran on no kernel of "
                       "the program's, or solved inside the other two")
        if {d["kernel"] for d in qwen3["pointwise_tiling"]} != {
                "conv_norm_fwd", "conv_norm_bwd", "gate_norm_fwd",
                "gate_norm_bwd"}:
            bad.append("the Qwen3-Next step recorded no ops/delta_tiling "
                       "decision of each of the four kernels around the "
                       "scan: the mixer's conv, norms and gate ran as XLA's "
                       "elementwise code")
        if {d["kernel"] for d in qwen3["flash_tiling"]} != {"fwd", "bwd"}:
            bad.append("the Qwen3-Next step recorded no ops/flash_tiling "
                       "decision of both kernels at its head width")
        dropped = sum(e["pairs_dropped"] for e in qwen3["expert_load"])
        if not qwen3["expert_load"] or dropped:
            bad.append(f"the Qwen3-Next step's expert halves recorded no "
                       f"model/expert_load event or dropped {dropped} pairs")
        if not all(0.5 < b < 8.0 for b in qwen3["balance_loss"]):
            bad.append("the Qwen3-Next step said balance losses "
                       f"{qwen3['balance_loss']} of its layers")
    ouro = summary.get("ouro")
    if ouro is not None:
        if not (math.isfinite(ouro["loss"])
                and math.isfinite(ouro["grad_norm"])):
            bad.append(f"the Ouro step's loss {ouro['loss']} or grad_norm "
                       f"{ouro['grad_norm']} is not finite")
        if not ouro["loop"]:
            bad.append("the Ouro step recorded no model/loop event: its "
                       "layers did not go through blocks.run_repeated")
        *p, entropy = ouro["exit_distribution"]
        if not (abs(sum(p) - 1.0) < 1e-3 and all(0.0 < q < 1.0 for q in p)
                and 0.0 < entropy < math.log(len(p)) + 1e-3):
            bad.append("the Ouro step said a mean exit distribution "
                       f"{p} of entropy {entropy}: not one over its passes")
    afmoe_step = summary.get("afmoe")
    if afmoe_step is not None:
        if not (math.isfinite(afmoe_step["loss"])
                and math.isfinite(afmoe_step["grad_norm"])):
            bad.append(f"the AFMoE step's loss {afmoe_step['loss']} or "
                       f"grad_norm {afmoe_step['grad_norm']} is not finite")
        if not afmoe_step["layer_pattern"]:
            bad.append("the AFMoE step recorded no model/layer_pattern event")
        calls = {(d["kernel"], bool(d["window"]))
                 for d in afmoe_step["flash_tiling"]}
        if calls != {(k, w) for k in ("fwd", "bwd") for w in (False, True)}:
            bad.append("the AFMoE step recorded ops/flash_tiling decisions "
                       f"{sorted(calls)}: not a windowed and a full call of "
                       "each kernel")
        for d in afmoe_step["flash_tiling"]:
            skipped = d["tiles_visited"] < d["tiles_causal"]
            if d["window"] and d["window"] + d["block_k"] < d["Skv"] \
                    and not skipped:
                bad.append(f"the AFMoE step's {d['kernel']} call under "
                           f"window={d['window']} visits every tile pair of "
                           "the triangle: the band is not walked alone")
        if any(load["pairs_dropped"] for load in afmoe_step["expert_load"]):
            bad.append("the AFMoE step's set-up dropped pairs: "
                       f"{afmoe_step['expert_load']}")
    xing4 = summary.get("xing4")
    if xing4 is not None:
        if not (math.isfinite(xing4["loss"])
                and math.isfinite(xing4["grad_norm"])):
            bad.append(f"the Xing4.0 step's loss {xing4['loss']} or "
                       f"grad_norm {xing4['grad_norm']} is not finite")
        if not xing4["layer_pattern"] or not xing4["hyper_connection"]:
            bad.append("the Xing4.0 step recorded no model/layer_pattern or "
                       "no model/hyper_connection event")
        took = sorted(d["kernel"] for d in xing4["mhc_tiling"])
        if took != ["mix_bwd", "mix_fwd", "write_bwd", "write_fwd"]:
            bad.append("the Xing4.0 step recorded no ops/mhc_tiling decision "
                       f"for each of its four hyper-connection kernels: {took}")
        if len(xing4["expert_load"]) != len(xing4["step_load"]):
            bad.append("the Xing4.0 step recorded no model/expert_load event "
                       "for each expert layer its step reports, the MTP "
                       "module's among them")
        dropped = sum(e["pairs_dropped"] for e in xing4["expert_load"])
        if dropped:
            bad.append(f"the Xing4.0 step's expert layers dropped {dropped} "
                       "(token, choice) pairs")
    return bad


def check_device(summary: Dict[str, Any], model_cfg, per_chip_batch: int,
                 advertised_tpus: int) -> List[str]:
    """What must hold on the chip. Returns the failures."""
    bad = []
    if summary["platforms"] != ["tpu"]:
        bad.append(f"worker devices are on {summary['platforms']}, not tpu")
    if summary["device_count"] != advertised_tpus:
        bad.append(f"the node advertised TPU={advertised_tpus} and the worker "
                   f"leased them all sees {summary['device_count']}")
    if summary["attention"] != ["pallas", False]:
        bad.append(f"attention resolved to (impl, interpret)="
                   f"{summary['attention']}, not compiled Pallas")
    # fwd + bwd kernels, each on ONE device's shard of the batch: GSPMD
    # cannot partition a Mosaic call, so anything but the per-chip batch here
    # means every chip is computing the gathered global batch
    from ray_tpu.ops.attention import S_MINOR, kernel_layout

    pair = kernel_layout(model_cfg.head_dim)
    tile = [model_cfg.seq_len, model_cfg.head_dim]
    want = [[per_chip_batch * model_cfg.n_head]
            + (tile[::-1] if pair == S_MINOR else tile)]
    if summary["tpu_custom_calls"] < 2 or summary["attention_call_shapes"] != want:
        bad.append(f"compiled step has {summary['tpu_custom_calls']} Mosaic "
                   f"call(s) over {summary['attention_call_shapes']}; wanted "
                   f">= 2 over the per-device shard {want}")
    # the ops/flash_tiling decisions the worker traced its kernels with
    kernels = {d["kernel"] for d in summary["flash_tiling"]}
    if kernels != {"fwd", "bwd"}:
        bad.append(f"flash tiling decisions recorded for {sorted(kernels)}, "
                   "not for fwd and bwd")
    # ... each for the kernel pair its head width takes (the worker's record
    # holds the other steps' attention layers too)
    for d in summary["flash_tiling"]:
        if d["layout"] != kernel_layout(d["hd"], d["hd_v"]):
            bad.append(f"flash {d['kernel']} kernel traced for {d['layout']} "
                       f"operands at hd={d['hd']} / {d['hd_v']}, where the "
                       f"rule says {kernel_layout(d['hd'], d['hd_v'])}")
    return bad


def session_story(trace: List[Dict[str, Any]]) -> Tuple[List[str], List[str]]:
    """(lines, failures) from the finished session's record — what
    ``ray_tpu.timeline()`` returns after ``shutdown()``: the phases of the
    ``fit()`` attempt's trace in time order, the worker processes the raylet
    started and reaped, the driver's init and shutdown, and the train
    worker's compiles of half a second or more (PR 35); the actor class's
    load and the backend's bring-up among the phases, what each kill did, and
    the record's account of itself, which must be there and read nothing
    lost (PR 68). Seconds on the host's clock: set-up and teardown, not
    speed."""
    from ray_tpu.tracing import names

    spans = [e for e in trace
             if e.get("cat") in ("train", "data", "raylet", "driver",
                                 "worker", "gcs")
             and e.get("ph") in ("X", "i")]
    by_name: Dict[str, List[Dict[str, Any]]] = {}
    for e in spans:
        by_name.setdefault(f"{e['cat']}/{e['name']}", []).append(e)
    failures = [f"the session's record holds no {name}"
                for name in ("train/fit", "train/loop_entered")
                if name not in by_name]
    if failures:
        return [], failures
    fit = by_name["train/fit"][-1]
    t0, trace_id = fit["ts"], fit["args"].get("trace_id")
    lines = [f"fit() trace {trace_id}: attempt {fit['args'].get('attempt')}, "
             f"{fit.get('dur', 0.0) / 1e6:.2f} s; phases (start after fit() "
             "was called, seconds):"]
    compiles, task_loads = [], []
    for e in spans:
        name = f"{e['cat']}/{e['name']}"
        if name == "train/compile":
            compiles.append(e)
        elif name == "worker/load_class" and e["args"].get("kind") == "task":
            task_loads.append(e.get("dur", 0.0) / 1e6)
        elif (name in names.SETUP_SPANS and e is not fit
              and e["args"].get("trace_id") == trace_id):
            said = ""
            if name == "worker/load_class":
                said = (f"  {e['args']['name']}: {e['args']['modules_imported']}"
                        " modules imported")
            elif name == "train/backend_init":
                said = (f"  rank {e['args']['rank']}: {e['args']['devices']} x "
                        f"{e['args']['platform']}")
            elif name == "train/group_shutdown":
                said = (f"  gone at return {e['args']['gone_at_return']} / "
                        f"{e['args']['killed']} killed"
                        + (f", errors {e['args']['kill_errors']}"
                           if e["args"]["kill_errors"] else ""))
            lines.append(f"  {name:28s} +{(e['ts'] - t0) / 1e6:7.2f}  "
                         f"{e.get('dur', 0.0) / 1e6:7.2f}{said}")
    if task_loads:
        lines.append(f"  {'worker/load_class':28s} {len(task_loads)} x task: "
                     f"{sum(task_loads):.2f} s in all")
    for e in by_name.get("gcs/kill_actor", ()):
        a = e["args"]
        lines.append(f"  {'gcs/kill_actor':28s} +{(e['ts'] - t0) / 1e6:7.2f}  "
                     f"{e.get('dur', 0.0) / 1e6:7.2f}  {a['class_name']}: "
                     f"{a['outcome']}"
                     + ("" if a["forwarded"] else
                        f" (actor {a['state']}, node alive "
                        f"{a['node_alive']}, address {a['had_address']})")
                     + (f" {a['error']}" if a["error"] else ""))
    for name in ("raylet/worker_start", "raylet/worker_reap"):
        groups: Dict[str, List[float]] = {}
        for e in by_name.get(name, ()):
            what = f"{e['args'].get('kind', '')} {e['args']['platform']}"
            groups.setdefault(what.strip(), []).append(e.get("dur", 0.0) / 1e6)
        for what, durs in sorted(groups.items()):
            lines.append(f"  {name:28s} {len(durs)} x {what}: "
                         f"{min(durs):.2f} to {max(durs):.2f} s")
    for e in by_name.get("driver/wait_process", ()):
        lines.append(f"  {'driver/wait_process':28s} {e['args']['name']} (pid "
                     f"{e['args']['pid']}): {e.get('dur', 0.0) / 1e6:.2f} s")
    for name in ("driver/init", "driver/shutdown"):
        for e in by_name.get(name, ()):
            lines.append(f"  {name:28s} {e.get('dur', 0.0) / 1e6:.2f} s")
    summaries = by_name.get("driver/record_summary", ())
    if not summaries:
        failures.append("the session's record holds no driver/record_summary: "
                        "it cannot say what it lost")
    for e in summaries:
        a = e["args"]
        lost = sum(r["lost"] for r in a["sources"]) + a["setup_evicted"]
        lines.append(
            f"  {'driver/record_summary':28s} lost {lost}; in flight at "
            f"shutdown() {a['in_flight']}, unflushed set-up spans "
            f"{a['unflushed_setup']}, last flush "
            f"{a['flush_age_s'] or 0.0:.2f} s before, loop gone "
            f"{a['window_s'] or 0.0:.2f} s after; evicted tasks "
            f"{a['evicted_tasks']}, truncated {a['truncated_events']}, "
            f"set-up spans evicted {a['setup_evicted']}")
        for r in a["sources"]:
            lines.append(f"    {r['source']:26s} recorded {r['recorded']}, "
                         f"delivered {r['delivered']}, recovered "
                         f"{r['recovered']}, dropped {r['dropped']}, lost "
                         f"{r['lost']}")
        if lost:
            failures.append(f"the session's record lost {lost} events "
                            "(driver/record_summary)")
    total = sum(e["args"]["seconds"] for e in compiles)
    slow = [e for e in compiles if e["args"]["seconds"] >= 0.5]
    lines.append(f"train/compile: {len(compiles)} backend compiles or cache "
                 f"loads, {total:.1f} s; those of 0.5 s or more: "
                 + ", ".join(f"{e['args']['fun_name']} "
                             f"{e['args']['seconds']:.1f}s "
                             f"({e['args'].get('cache') or 'not cached'})"
                             for e in slow))
    return lines, failures


def step_load_line(trace: List[Dict[str, Any]], step: Dict[str, Any],
                   what: str) -> Tuple[List[str], List[str]]:
    """(lines, failures): the ``train/step_counters`` event in the session's
    record that holds what ``step`` — a toy run with expert layers — said of
    itself (its ``metrics["counters"]``, rows of names.STEP_EXPERT_LOAD_ARGS):
    the step's own load, to read beside set-up's ``model/expert_load``."""
    from ray_tpu.tracing import names

    for e in trace:
        args = e.get("args") or {}
        if (f"{e.get('cat')}/{e.get('name')}" == names.TRAIN_STEP_COUNTERS
                and [list(row) for row in zip(*(
                    args[f] for f in names.STEP_EXPERT_LOAD_ARGS))]
                == step["step_load"]):
            return [f"{what} step {args['step']} said of itself "
                    f"({args['kind']}, recorded "
                    f"{e['ts'] / 1e6 - args['t_dispatch']:.2f} s after its "
                    f"dispatch): layers {args['layers']} ran "
                    f"{args['passes']} pass(es) over a buffer of "
                    f"{args['buffer_rows']} rows for {args['pairs']} pairs "
                    f"on the {args['held']} held experts (fullest "
                    f"{args['max_per_expert']})"], []
    return [], [f"the {what} step ran {len(step['step_load'])} expert layers "
                "and the session's record holds no train/step_counters "
                "event with its load"]


def print_pattern_and_scan(step: Dict[str, Any]) -> None:
    """A pattern step's `model/layer_pattern` and `ops/ssd_tiling` events."""
    for d in step["layer_pattern"]:
        print(f"layer pattern: {d['pattern']} -> {d['applications']} as "
              f"{d['groups']}")
    for d in step["ssd_tiling"]:
        print(f"ssd tiling: {d['kernel']} rows={d['rows']} S={d['S']} "
              f"Q={d['Q']} heads a group={d['group_heads']} P={d['P']} "
              f"N={d['N']} -> {d['head_tile']} heads a grid step, "
              f"vmem_estimate={d['vmem_estimate']}")


def _driver_backend_initialised() -> bool:
    jax = sys.modules.get("jax")
    return jax is not None and jax._src.xla_bridge.backends_are_initialized()


def main() -> int:
    import os

    import ray_tpu
    from ray_tpu.core.resources import tpu_device_files
    from ray_tpu.models import (afmoe, deepseek_v2, gpt2, lfm2_moe, llama,
                                qwen3_next, minicpm_sala, nemotron_h)
    from ray_tpu.ops.sparse_attention import SparseSizes

    model_cfg = gpt2.gpt2_124m()
    # Nemotron-H's three kinds of layer at a quarter of the width: one short
    # period and the MTP module, 8 of 64 experts held, heads of 128 / 64
    hybrid_cfg = nemotron_h.NemotronHConfig(
        vocab_size=4096, seq_len=2048, pattern="MEME*E", n_layer_published=6,
        d_model=1024, n_head=4, n_kv_head=1, mamba_heads=16, mamba_groups=1,
        n_experts=64, top_k=6, held_first=8, held_count=8, latent=256,
        d_expert=640, d_shared=1280, remat=True)
    # EvaByte's block at an eighth of its width: heads of 128, two windows of
    # 2,048 bytes, so the second window's queries see 128 summaries
    eva_cfg = llama.evabyte_6p5b(n_layer=2, n_head=8, n_kv_head=8, d_model=1024,
                                 d_ff=2816, seq_len=4096, remat=True)
    # MiniCPM-SALA's period at a quarter of the width: heads of 128, rows of
    # 4,096 tokens past a dense_len of 2,048, 16 of 64 blocks a query
    sala_cfg = minicpm_sala.MiniCPMSALAConfig(
        vocab_size=4096, seq_len=4096, pattern="LLLS", d_model=1024, d_ff=4096,
        lightning_heads=4, lightning_heads_published=8, n_head=4, n_kv_head=1,
        sparse=SparseSizes(top_k=16, window=512, dense_len=2048), remat=True)
    # LFM2-MoE's layers 1-5 at half the width: heads of 64 (the S-minor flash
    # pair under RoPE and QK-norm, 4 query heads a key-value head), 16 of 64
    # experts held, top-4
    lfm2_cfg = lfm2_moe.LFM2MoEConfig(
        vocab_size=4096, seq_len=2048, pattern="DACCC", first_layer=1,
        d_model=1024, n_head=16, n_kv_head=4, d_ff=2816, held_count=16,
        d_expert=768, remat=True)
    # DeepSeek-V2-Lite's layers 0-2 at half the width: latent attention at
    # the published head widths (q·k 192, v 128: the S-minor flash pair at
    # unequal widths) under YaRN, 16 of 64 experts held, top-6, two shared
    dsv2_cfg = deepseek_v2.DeepseekV2Config(
        vocab_size=4096, seq_len=2048, n_layer=3, d_model=1024, n_head=8,
        d_ff=2816, held_count=16, d_expert=704, remat=True)
    # Xing4.0's layers 1-2 and its MTP module at two sevenths of the width:
    # the same latent attention with query compression under YaRN x 64, four
    # hyper-connection streams of 1,024, 8 of 64 experts held, top-4 by a
    # biased sigmoid beside one shared expert, no balance loss
    xing4_cfg = deepseek_v2.DeepseekV2Config(
        vocab_size=4096, seq_len=2048, n_layer=2, first_layer=1,
        first_k_dense=2, n_layer_published=40, d_model=1024, n_head=8,
        q_lora_rank=256, rope_factor=64.0, rope_mscale=1.0,
        rope_mscale_all_dim=1.0, d_ff=2816, top_k=4, held_count=8,
        d_expert=512, n_shared=1, routed_scaling=2.0, scoring="sigmoid",
        norm_topk_prob=True, selection_bias=True, aux_loss_alpha=0.0,
        hc_mult=4, mtp_layers=1, remat=True)
    # Qwen3-Next's layers 0-3 at half the width: the delta rule at the
    # published head widths (8 key heads serving 16 value heads at 128, chunk
    # 64: two value heads a grid step), gated attention at hd 256 (8 query
    # heads on 2 key-value heads, rotary on 64), 16 of 128 experts held,
    # top-10 by a normalised softmax beside a gated shared expert
    qwen3_cfg = qwen3_next.Qwen3NextConfig(
        vocab_size=4096, seq_len=2048, n_layer=4, d_model=1024, n_head=8,
        linear_key_heads=8, linear_value_heads=16, n_experts=128,
        held_count=16, d_expert=512, d_shared=512, remat=True)
    # Ouro's block at half the width: two layers run twice on one set of
    # weights, sandwich norms, a head and an exit gate after each pass
    ouro_cfg = llama.ouro_2p6b(
        n_layer=2, ut_steps=2, d_model=1024, n_head=8, n_kv_head=8, d_ff=2816,
        vocab_size=8192, seq_len=2048, remat=True)
    # Trinity-Mini's layers at half the width: a dense window layer, a window
    # and a full expert layer and two more window layers, rows of four windows
    afmoe_cfg = afmoe.trinity_mini(
        pattern="DWFWW", first_layer=1, d_model=1024, n_head=8, n_kv_head=2,
        sliding_window=1024, d_ff=2816, n_experts=32, held_count=8,
        d_expert=512, vocab_size=8192, seq_len=4096, remat=True)
    ray_tpu.init()
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if chips == 0:
            # what core/resources.detect_tpu_resources had to go on
            print("chip_smoke: no TPU on this node (JAX_PLATFORMS="
                  f"{os.environ.get('JAX_PLATFORMS', 'unset')}; chip device "
                  f"files: {tpu_device_files() or 'none'})", file=sys.stderr)
            return 2
        rows = run(model_cfg, steps=STEPS, per_chip_batch=PER_CHIP_BATCH,
                   num_devices=chips, use_tpu=True, eva_model=eva_cfg,
                   hybrid_model=hybrid_cfg, sala_model=sala_cfg,
                   lfm2_model=lfm2_cfg, dsv2_model=dsv2_cfg,
                   xing4_model=xing4_cfg, qwen3_model=qwen3_cfg,
                   ouro_model=ouro_cfg, afmoe_model=afmoe_cfg)
    finally:
        ray_tpu.shutdown()

    summary = rows[-1]["summary"]
    failures = check_training(rows, model_cfg, STEPS) + check_device(
        summary, model_cfg, PER_CHIP_BATCH, chips
    )
    if _driver_backend_initialised():
        failures.append("the driver process initialised a JAX backend")
    # the record the session left behind: asked after shutdown(), so it
    # starts nothing and holds the teardown too
    record = ray_tpu.timeline()
    story, missing = session_story(record)
    failures += missing
    step_loads = {}
    for what, key in (("hybrid", "hybrid"), ("LFM2-MoE", "lfm2"),
                      ("DeepSeek-V2", "dsv2"), ("Xing4.0", "xing4"),
                      ("Qwen3-Next", "qwen3")):
        step_loads[key], missing = step_load_line(record, summary[key], what)
        failures += missing

    print(f"device: platform={','.join(summary['platforms'])} "
          f"device_kind={summary['device_kind']!r} "
          f"count={summary['device_count']} (node advertised TPU={chips})")
    print(f"versions: jax={summary['jax']} libtpu={summary['libtpu']}")
    print(f"mesh: {summary['mesh'] or 'one device'}  global_batch="
          f"{summary['global_batch']}x{model_cfg.seq_len}")
    print(f"attention: impl={summary['attention'][0]} interpret="
          f"{summary['attention'][1]}; {summary['tpu_custom_calls']} Mosaic "
          f"calls per device over {summary['attention_call_shapes']}")
    for d in summary["flash_tiling"]:
        print(f"flash tiling: {d['kernel']} rows={d['rows']} Sq={d['Sq']} "
              f"Skv={d['Skv']} hd={d['hd']} -> {d['layout']} block_q="
              f"{d['block_q']} block_k={d['block_k']} vmem_estimate="
              f"{d['vmem_estimate'] / 2 ** 20:.2f} MiB")
    gib = 2.0 ** 30
    for d in summary["remat_policy"]:
        print(f"remat policy: n_layer={d['n_layer']} batch={d['batch']} "
              f"seq={d['seq']} -> saved {d['saved'] or 'block inputs only'}, "
              f"{d['saved_bytes'] / gib:.2f} GiB of a budget of "
              f"{d['budget_bytes'] / gib:.2f} (bytes_limit "
              f"{d['bytes_limit'] / gib:.2f} GiB) left by the backward's "
              f"phase {d['phase']!r} ({d['phase_bytes'] / gib:.2f} GiB); MLP "
              f"{d['mlp_rows']} rows at a time, head {d['head_rows']}")
    eva = summary["eva"]
    for d in eva["tiling"]:
        print(f"eva tiling: {d['kernel']} rows={d['rows']} S={d['Sq']} "
              f"hd={d['hd']} window={d['window']} chunk={d['chunk']} -> "
              f"block_q={d['block_q']} block_k={d['block_k']} vmem_estimate="
              f"{d['vmem_estimate'] / 2 ** 20:.2f} MiB")
    for d in eva["head_loss"]:
        print(f"head loss: {d['chunks']} chunk(s) of {d['batch']} x "
              f"{d['rows']} positions x {d['columns']} columns "
              f"({d['heads']} heads), gradient in the forward: "
              f"{d['grad_in_forward']}, kept for the backward "
              f"{d['residual_bytes'] / 2 ** 20:.1f} MiB, the float32 "
              f"d lm_head carry moved {d['carry_bytes_a_step'] / 2 ** 20:.1f}"
              " MiB a step")
    print(f"eva step ({eva_cfg.n_layer} layers of {eva_cfg.d_model}, "
          f"{summary['device_count']}x{eva['seq_len']} bytes, remat): "
          f"attention {eva['attention']}, loss {eva['loss']:.4f} "
          f"grad_norm {eva['grad_norm']:.4f}; instructions the compiler "
          f"rematerialized by itself: {eva['compiler_rematerialized']}")
    hybrid = summary["hybrid"]
    print_pattern_and_scan(hybrid)
    for e in hybrid["expert_load"]:
        print(f"expert load: layer {e['layer']}: {e['pairs']} pairs of "
              f"{e['tokens']} tokens on the held experts (max "
              f"{e['max_per_expert']}, mean {e['mean_per_expert']:.1f} an "
              f"expert; {e['tokens_without_held_expert']} tokens with none), "
              f"{e['buffer_passes']} pass(es) over a buffer of "
              f"{e['buffer_rows']} rows ({e['buffer_fill']:.3f} full), "
              f"dropped {e['pairs_dropped']}")
    print("\n".join(step_loads["hybrid"]))
    for d in hybrid["grouped"]["tiling"]:
        print(f"grouped tiling: {d['form']} rows={d['rows']} "
              f"held={d['held']} K={d['K']} N={d['N']} "
              f"({d['dtype_bytes']}-byte) -> {d['impl']}, row tile "
              f"{d['row_tile']}, vmem estimate {d['vmem_estimate']}")
    for what, off in hybrid["grouped"]["off"].items():
        print(f"grouped products against lax.ragged_dot: {what}: off by "
              f"{off:.2e} of the largest value")
    print(f"chosen set as a mask against lax.top_k's list, "
          f"{ROUTER_SHAPE[0]}x{ROUTER_SHAPE[1]} scores with ties, top "
          f"{ROUTER_TOP_K}: {hybrid['chosen_rows_off']} rows differ")
    print(f"hybrid step ({hybrid_cfg.pattern} + MTP {hybrid_cfg.mtp_pattern} "
          f"of {hybrid_cfg.d_model}, {summary['device_count']}x"
          f"{hybrid['seq_len']} tokens, remat): loss {hybrid['loss']:.4f} "
          f"grad_norm {hybrid['grad_norm']:.4f}")
    sala = summary["sala"]
    print_pattern_and_scan(sala)
    for d in sala["sparse_selection"]:
        print(f"sparse selection: rows={d['rows']} S={d['S']}: {d['mode']}, "
              f"{d['top_k']} of {d['blocks']} blocks a query "
              f"({d['window_blocks']} the window's, {d['init_blocks']} "
              f"initial), dense up to {d['dense_len']}; "
              f"{100 * d['kept_share']:.1f} % of the visible keys kept")
    for d in sala["sparse_tiling"]:
        print(f"sparse tiling: {d['kernel']} rows={d['rows']} S={d['S']} "
              f"heads a group={d['group_heads']} hd={d['hd']} block="
              f"{d['block']} x {d['blocks_per_query']} a query -> "
              f"{d['block_q']} tokens x {d['block_k']} keys a tile, "
              f"vmem_estimate={d['vmem_estimate']}")
    print(f"MiniCPM-SALA step ({sala_cfg.pattern} of {sala_cfg.d_model}, "
          f"{summary['device_count']}x{sala['seq_len']} tokens, remat): loss "
          f"{sala['loss']:.4f} grad_norm {sala['grad_norm']:.4f}")
    lfm2 = summary["lfm2"]
    for d in lfm2["layer_pattern"]:
        print(f"layer pattern: {d['pattern']} -> {d['applications']} as "
              f"{d['groups']}")
    for d in lfm2["remat_policy"]:
        print(f"LFM2-MoE remat policy: {d['n_layer']} layers of three kinds, "
              f"batch={d['batch']} seq={d['seq']}: saved={d['saved']} "
              f"({d['saved_bytes'] / gib:.2f} GiB of {d['budget_bytes'] / gib:.2f}"
              f" left by the backward's phase {d['phase']!r})")
    for e in lfm2["expert_load"]:
        print(f"LFM2-MoE expert load: published layer {e['layer']}: "
              f"{e['pairs']} pairs of {e['tokens']} tokens on the held "
              f"experts (max {e['max_per_expert']}, mean "
              f"{e['mean_per_expert']:.1f} an expert), {e['buffer_passes']} "
              f"pass(es) over a buffer of {e['buffer_rows']} rows, dropped "
              f"{e['pairs_dropped']}")
    print("\n".join(step_loads["lfm2"]))
    print(f"LFM2-MoE step ({lfm2_cfg.pattern} of {lfm2_cfg.d_model}, "
          f"{summary['device_count']}x{lfm2['seq_len']} tokens, remat): loss "
          f"{lfm2['loss']:.4f} grad_norm {lfm2['grad_norm']:.4f}")
    dsv2 = summary["dsv2"]
    for d in dsv2["layer_pattern"]:
        print(f"layer pattern: {d['pattern']} -> {d['applications']} as "
              f"{d['groups']}")
    for d in dsv2["flash_tiling"]:
        print(f"flash tiling: {d['kernel']} rows={d['rows']} Sq={d['Sq']} "
              f"Skv={d['Skv']} hd={d['hd']} hd_v={d['hd_v']} -> block_q="
              f"{d['block_q']} block_k={d['block_k']} vmem_estimate="
              f"{d['vmem_estimate']} layout={d['layout']}")
    for d in dsv2["remat_policy"]:
        print(f"DeepSeek-V2 remat policy: {d['n_layer']} layers of two kinds, "
              f"batch={d['batch']} seq={d['seq']}: saved={d['saved']} "
              f"({d['saved_bytes'] / gib:.2f} GiB of {d['budget_bytes'] / gib:.2f}"
              f" left by the backward's phase {d['phase']!r})")
    for e in dsv2["expert_load"]:
        print(f"DeepSeek-V2 expert load: published layer {e['layer']}: "
              f"{e['pairs']} pairs of {e['tokens']} tokens on the held "
              f"experts (max {e['max_per_expert']}, mean "
              f"{e['mean_per_expert']:.1f} an expert), {e['buffer_passes']} "
              f"pass(es) over a buffer of {e['buffer_rows']} rows, dropped "
              f"{e['pairs_dropped']}")
    print("\n".join(step_loads["dsv2"]))
    print(f"DeepSeek-V2 step ({dsv2_cfg.pattern} of {dsv2_cfg.d_model}, "
          f"{summary['device_count']}x{dsv2['seq_len']} tokens, remat): loss "
          f"{dsv2['loss']:.4f} grad_norm {dsv2['grad_norm']:.4f}, balance "
          f"loss a layer {[round(b, 4) for b in dsv2['balance_loss']]}")
    xing4 = summary["xing4"]
    for d in xing4["layer_pattern"]:
        print(f"layer pattern: {d['pattern']} -> {d['applications']} as "
              f"{d['groups']}")
    for d in xing4["hyper_connection"]:
        print(f"hyper-connection: {d['streams']} streams, {d['rounds']} "
              f"Sinkhorn rounds, the stream in {d['stream_dtype']}, "
              f"{d['carry_bytes_per_token']} B a token")
    for d in xing4["mhc_tiling"]:
        print(f"mhc tiling: {d['kernel']}: "
              f"{d['token_tile']} of {d['tokens']} tokens a tile at "
              f"{d['n']} x {d['C']}, VMEM estimate {d['vmem_estimate']} B")
    for e in xing4["expert_load"]:
        print(f"Xing4.0 expert load: published layer {e['layer']}: "
              f"{e['pairs']} pairs of {e['tokens']} tokens on the held "
              f"experts (max {e['max_per_expert']}, mean "
              f"{e['mean_per_expert']:.1f} an expert), {e['buffer_passes']} "
              f"pass(es) over a buffer of {e['buffer_rows']} rows, dropped "
              f"{e['pairs_dropped']}")
    print("\n".join(step_loads["xing4"]))
    print(f"Xing4.0 step ({xing4_cfg.pattern} + MTP {xing4_cfg.mtp_pattern} "
          f"of {xing4_cfg.hc_mult} x {xing4_cfg.d_model}, "
          f"{summary['device_count']}x{xing4['seq_len']} tokens, remat): loss "
          f"{xing4['loss']:.4f} grad_norm {xing4['grad_norm']:.4f}")
    qwen3 = summary["qwen3"]
    for d in qwen3["layer_pattern"]:
        print(f"layer pattern: {d['pattern']} -> {d['applications']} as "
              f"{d['groups']}")
    for d in qwen3["delta_tiling"]:
        print(f"delta tiling: {d['kernel']} rows={d['rows']} S={d['S']} "
              f"chunk={d['C']}, {d['key_heads']} key heads x "
              f"{d['value_heads_per_key']} value heads at {d['dk']} / "
              f"{d['dv']} -> {d['head_tile']} value head(s) of "
              f"{d['key_tile']} key head(s) a grid step, VMEM "
              f"estimate {d['vmem_estimate'] / 2 ** 20:.2f} MiB")
    for d in qwen3["pointwise_tiling"]:
        print(f"delta tiling: {d['kernel']} rows={d['rows']} S={d['S']} "
              f"channels={d['channels']}, the norm over {d['heads']} head(s) "
              f"-> {d['token_tile']} tokens a grid step, "
              f"{d['channel_tile']} lanes at a time, VMEM estimate "
              f"{d['vmem_estimate'] / 2 ** 20:.2f} MiB")
    for d in qwen3["flash_tiling"]:
        print(f"flash tiling: {d['kernel']} rows={d['rows']} Sq={d['Sq']} "
              f"hd={d['hd']} -> block_q={d['block_q']} block_k={d['block_k']} "
              f"layout={d['layout']}")
    for d in qwen3["remat_policy"]:
        print(f"Qwen3-Next remat policy: {d['n_layer']} layers of two kinds, "
              f"batch={d['batch']} seq={d['seq']}: saved={d['saved']} "
              f"({d['saved_bytes'] / gib:.2f} GiB of {d['budget_bytes'] / gib:.2f}"
              f" left by the backward's phase {d['phase']!r})")
    for e in qwen3["expert_load"]:
        print(f"Qwen3-Next expert load: published layer {e['layer']}: "
              f"{e['pairs']} pairs of {e['tokens']} tokens on the held "
              f"experts (max {e['max_per_expert']}, mean "
              f"{e['mean_per_expert']:.1f} an expert), {e['buffer_passes']} "
              f"pass(es) over a buffer of {e['buffer_rows']} rows, dropped "
              f"{e['pairs_dropped']}")
    print("\n".join(step_loads["qwen3"]))
    print(f"Qwen3-Next step ({qwen3_cfg.pattern} of {qwen3_cfg.d_model}, "
          f"{summary['device_count']}x{qwen3['seq_len']} tokens, remat): loss "
          f"{qwen3['loss']:.4f} grad_norm {qwen3['grad_norm']:.4f}, balance "
          f"loss a layer {[round(b, 4) for b in qwen3['balance_loss']]}")
    ouro = summary["ouro"]
    for d in ouro["loop"]:
        print(f"loop: {d['layers']} layers x {d['passes']} passes = "
              f"{d['applications']} applications on one set of weights; a "
              f"stack of their float32 gradients "
              f"{d['grad_stack_bytes'] / 2 ** 20:.1f} MiB; heads: {d['heads']}")
    for d in ouro["remat_policy"]:
        print(f"Ouro remat policy: {d['n_layer']} layers, {d['passes']} "
              f"passes, {d['applications']} applications, batch={d['batch']} "
              f"seq={d['seq']}: saved={d['saved']} "
              f"({d['saved_bytes'] / gib:.2f} GiB of {d['budget_bytes'] / gib:.2f}"
              f" left by the backward's phase {d['phase']!r})")
    print(f"Ouro step ({ouro_cfg.n_layer} layers x {ouro_cfg.ut_steps} passes "
          f"of {ouro_cfg.d_model}, {summary['device_count']}x"
          f"{ouro['seq_len']} tokens, remat): loss {ouro['loss']:.4f} "
          f"grad_norm {ouro['grad_norm']:.4f}; it said of itself a mean exit "
          f"distribution {[round(q, 4) for q in ouro['exit_distribution'][:-1]]}"
          f", entropy {ouro['exit_distribution'][-1]:.4f}")
    afmoe_step = summary["afmoe"]
    for d in afmoe_step["layer_pattern"]:
        print(f"AFMoE layer pattern {d['pattern']}: applications "
              f"{d['applications']}, runs {d['groups']}")
    for d in afmoe_step["flash_tiling"]:
        print(f"AFMoE flash {d['kernel']}: rows={d['rows']} S={d['Sq']} "
              f"hd={d['hd']} window={d['window'] or 'none'} -> block_q="
              f"{d['block_q']} block_k={d['block_k']}, visits "
              f"{d['tiles_visited']} of the triangle's {d['tiles_causal']} "
              "tile pairs")
    for d in afmoe_step["remat_policy"]:
        print(f"AFMoE remat policy: {d['n_layer']} layers batch={d['batch']} "
              f"seq={d['seq']}: saved={d['saved']} "
              f"({d['saved_bytes'] / gib:.2f} GiB of {d['budget_bytes'] / gib:.2f}"
              f" left by the backward's phase {d['phase']!r})")
    print(f"AFMoE step ({afmoe_cfg.pattern} of {afmoe_cfg.d_model}, window "
          f"{afmoe_cfg.sliding_window}, {summary['device_count']}x"
          f"{afmoe_step['seq_len']} tokens, remat): loss "
          f"{afmoe_step['loss']:.4f} grad_norm {afmoe_step['grad_norm']:.4f}")
    print(f"set-up seconds (not speed): backend {summary['backend_seconds']:.1f}"
          f", step compile {summary['step_compile_seconds']:.1f}, start to "
          f"end of first step {summary['setup_seconds']:.1f}")
    print(f"compile cache: {summary['cache_dir']} {summary['cache_events']}")
    print("loss: " + " ".join(f"{r['loss']:.3f}" for r in rows[:-1]))
    print("step wall seconds (host-synchronised every step; not a rate): "
          + " ".join(f"{r['seconds']:.2f}" for r in rows[:-1]))
    print(f"first-step parity on {summary['parity_rows']} rows: "
          + "; ".join(("remat=True" if key == "remat"
                       else f"attention_impl={key!r}")
                      + f" loss={m['loss']:.5f} grad_norm={m['grad_norm']:.5f}"
                      for key, m in summary["parity"].items())
          + f" (rtol {LOSS_RTOL:g} / {GRAD_NORM_RTOL:g})")
    print(f"epochs over the {DATASET_BATCHES}-batch dataset: {summary['epochs']}")
    print("\n".join(story))
    if failures:
        for f in failures:
            print(f"chip_smoke: FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": summary["platforms"][0],
        "kind": summary["device_kind"],
        "count": summary["device_count"],
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
