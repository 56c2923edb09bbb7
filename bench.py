"""Headline benchmark: GPT-2-124M pretraining throughput, tokens/sec/chip.

Runs the full jitted train step (fwd + bwd + AdamW, bf16 compute, donated
buffers) on the local accelerator and prints ONE JSON line:

    {"metric": "gpt2_124m_train_tokens_per_sec_per_chip", "value": N,
     "unit": "tokens/s/chip", "vs_baseline": N}

Baseline: the reference publishes no GPT-2 numbers (BASELINE.md — `published`
is empty); the north-star target from BASELINE.json is ≥90% of published
GPU-node throughput. We anchor on the well-known A100 GPT-2-124M data point
(~150k tokens/s/GPU for a tuned torch impl); 90% of a T4-class reference node
is far below that. vs_baseline = value / 135_000 (i.e. ≥1.0 beats the target).
"""

from __future__ import annotations

import json
import sys
import time

BASELINE_TOKENS_PER_SEC_PER_CHIP = 135_000.0


def find_batch(step_fn, state, cfg, candidates=(16, 8, 4)):
    """Largest per-chip batch that fits in HBM."""
    from ray_tpu.train.train_step import synthetic_batch

    for b in candidates:
        try:
            batch = synthetic_batch(cfg, global_batch=b)
            state2, m = step_fn(state, batch)
            float(m["loss"])
            return b, state2
        except Exception as e:  # noqa: BLE001 - OOM probing
            if "RESOURCE_EXHAUSTED" in str(e) or "Out of memory" in str(e):
                continue
            raise
    raise RuntimeError("no batch size fits")


def validate_ring_kernels_on_tpu():
    """Compile + run the ring-attention building blocks NON-interpret on the
    chip (the CPU dryrun exercises them only in interpret mode). Small shapes,
    a few seconds of compile. A failure raises: the run exits non-zero."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import (
        flash_attention_with_lse,
        mha_backward_chunk,
    )
    from ray_tpu.ops.ring_attention import ring_attention_sharded
    from ray_tpu.parallel import mesh as mesh_lib

    B, H, S, hd = 2, 4, 512, 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.bfloat16)
    o, lse = flash_attention_with_lse(q, k, v, S, 0, interpret=False)
    dq, _, _ = mha_backward_chunk(
        q, k, v, o, lse, jnp.ones_like(o), S, 0, interpret=False
    )
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(cp=1), jax.devices()[:1])
    l = jax.jit(
        lambda q, k, v: jnp.sum(
            ring_attention_sharded(
                q, k, v, mesh, axis_name="cp", causal=True
            ).astype(jnp.float32) ** 2
        )
    )(q, k, v)
    print(
        f"ring kernels compiled on "
        f"{jax.devices()[0].device_kind}: ok (loss={float(l):.1f}, "
        f"|dq|={float(jnp.abs(dq).mean()):.4f})",
        file=sys.stderr,
    )


# bf16 peak FLOP/s per chip by device_kind (public TPU specs). A device that
# is not in the table is an error, not mfu=None.
PEAK_BF16_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v4 lite": 138e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e / Trillium
    "TPU v6e": 918e12,
    "TPU7x": 2307e12,        # Ironwood bf16
}


def main():
    import jax

    from ray_tpu.models import gpt2
    from ray_tpu.train.train_step import (
        default_optimizer,
        make_gpt2_train_step,
        synthetic_batch,
    )

    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    n_chips = len(devices)
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        sys.exit(
            f"bench.py measures the chip and found platform={platform!r} "
            f"({kind}, {n_chips} device(s)): no TPU, no number"
        )
    if kind not in PEAK_BF16_FLOPS:
        sys.exit(f"device_kind {kind!r} has no entry in PEAK_BF16_FLOPS")
    # Config from the round-3/4 measured sweeps + device profiles on v5e:
    # - scan_layers=False: the layer scan spent ~15% of each step in
    #   dynamic-update-slice fusions moving stacked params/grads; unrolling
    #   removes them and shrinks live memory enough that remat=False fits.
    # - remat=False: with the flash kernel there are no S×S residuals.
    # - fused CE (ops/cross_entropy.py): the f32 [B,S,V] log-softmax
    #   residual was 17 ms/step of pure HBM traffic (r4 profile).
    cfg = gpt2.gpt2_124m(remat=False, scan_layers=False)
    # fsdp over all local chips (== single-device mesh on one chip) so the
    # per-chip division below is honest on multi-chip hosts.
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec.for_devices(n_chips), devices)
    bundle = make_gpt2_train_step(
        cfg,
        mesh=mesh,
        optimizer=default_optimizer(total_steps=1000),
        rng=jax.random.PRNGKey(0),
    )
    state = bundle.state

    per_chip = (24, 16, 8, 4)
    global_batch, state = find_batch(
        bundle.step_fn, state, cfg, candidates=tuple(b * n_chips for b in per_chip)
    )
    # Device-resident pre-staged batches, as the Train data path delivers
    # them (the iterator device_puts prefetched batches; see
    # data/iterator.py), stepped with the bundle's device-side train loop
    # (multi_step_fn: lax.scan over the step axis — one dispatch for all N
    # steps, the way MaxText-style TPU trainers run).
    import numpy as np

    steps = 50
    stacked_sh = bundle.stacked_data_sharding
    stacked = {
        k: jax.device_put(
            np.stack([
                np.asarray(
                    synthetic_batch(cfg, global_batch=global_batch,
                                    seed=100 + i)[k]
                )
                for i in range(steps)
            ]),
            stacked_sh,
        )
        for k in ("tokens", "targets")
    }

    # warmup (compiles the scan; time only executions past the first two)
    state, ms = bundle.multi_step_fn(state, stacked)
    float(ms["loss"][-1])
    state, ms = bundle.multi_step_fn(state, stacked)
    float(ms["loss"][-1])

    t0 = time.perf_counter()
    state, ms = bundle.multi_step_fn(state, stacked)
    m = {"loss": ms["loss"][-1]}
    # host fetch waits for the whole scanned sequence
    float(m["loss"])
    dt = time.perf_counter() - t0

    # Honest labels (ADVICE r5): the headline number is the SCANNED device
    # loop (multi_step_fn: lax.scan over pre-staged batches — one dispatch
    # for all N steps, the delivery data/iterator.iter_stacked_batches
    # feeds). Per-step dispatch (one jitted call per optimizer step, what a
    # host-driven JaxTrainer loop pays) is measured separately below.
    ps_steps = 10
    ps_batch = jax.device_put(
        synthetic_batch(cfg, global_batch=global_batch, seed=7),
        bundle.data_sharding,
    )
    state, pm = bundle.step_fn(state, ps_batch)  # warm per-step dispatch
    float(pm["loss"])
    t0 = time.perf_counter()
    for _ in range(ps_steps):
        state, pm = bundle.step_fn(state, ps_batch)
    float(pm["loss"])
    dt_ps = time.perf_counter() - t0
    tps_chip_per_step = (
        ps_steps * global_batch * cfg.seq_len / dt_ps / max(n_chips, 1)
    )

    tokens = steps * global_batch * cfg.seq_len
    tps_chip = tokens / dt / max(n_chips, 1)
    mfu = gpt2.flops_per_token(cfg) * tps_chip / PEAK_BF16_FLOPS[kind]

    result = {
        "metric": "gpt2_124m_train_tokens_per_sec_per_chip",
        "value": round(tps_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(tps_chip / BASELINE_TOKENS_PER_SEC_PER_CHIP, 3),
        # the headline is the scanned device loop; the per-step dispatch
        # path is reported under its own label, not blended in
        "schedule": "scanned_multi_step",
        "per_step_dispatch_tokens_per_sec_per_chip": round(tps_chip_per_step, 1),
        "scan_vs_per_step": round(tps_chip / max(tps_chip_per_step, 1e-9), 3),
    }
    # extra context on stderr (driver reads stdout's single JSON line)
    print(
        f"platform={platform} device_kind={kind!r} count={n_chips} "
        f"batch={global_batch} steps={steps} dt={dt:.2f}s "
        f"loss={float(m['loss']):.3f} mfu={mfu:.3f} "
        f"| scanned={tps_chip:,.0f} tok/s/chip vs per-step dispatch="
        f"{tps_chip_per_step:,.0f} tok/s/chip",
        file=sys.stderr,
    )
    # before the result line: a run whose ring kernels fail prints no number
    validate_ring_kernels_on_tpu()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
