"""Family ``nemotron_h``: from a configuration file to the program's train step.

Nemotron-H is the program's hybrid model (``ray_tpu/models/nemotron_h.py``):
a pattern of Mamba-2, LatentMoE and attention layers and one multi-token-
prediction module. As for the other families the benchmark hands the program
the published sizes, the chip's share of the deployment and what the cell's
file states (per-chip batch, row length, ``remat``, mesh) and NOTHING else:
how the pattern is scanned, the row buffer of the held experts, what remat
keeps, tiles and head chunks stay at the program's defaults. ``build`` also
has the program balance its selection biases on the first batch, once.

Everything ``benchmarks/README.md`` asks of a family is here — ``build``,
``shapes``, ``attention_resolved``, ``reference_check``, ``abstract_step`` —
and, as family ``evabyte`` brought before it (``harness/flops.py`` is GPT-2's
arithmetic and no file that is there may be edited):

- ``train_flops_per_token(shapes)``: this family's own count
  (``nemotron_mfu_device`` reads it). ``run.py``'s human line "end-to-end MFU"
  is GPT-2's 6·params + 12·L·S·d: it counts every held expert for every token
  and is wrong here;
- ``ssd_scan_call(shapes)``: least operations and HBM bytes of the state-space
  scans ONE step makes (``ssd_scan_roofline`` reads it).

The grouped expert products are the TPU compiler's own kernel
(``lax.ragged_dot``), not a Pallas kernel of the program: no roofline reader.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.families import nemotron_h_reference

# AdamW as the program's default_optimizer builds it, on the schedule a
# pre-training run with this router starts with: a linear warm-up from 0 to
# 2.2e-4 over 2,000 steps (DeepSeek-V3 technical report, arXiv:2412.19437,
# section 4.2 — the published schedule of the model whose sigmoid router,
# selection bias and bias update this family's expert layer takes; Nemotron's
# own is not in its config). A 20 s window is that run's first ~18 steps, at
# rates up to 2e-6; it does not depend on --seconds. The other families' 6e-4
# from the second step on is not what such a run sees: Adam moves each of
# 838 M weights by about the rate whatever its gradient, the stream the
# routers read changes under a bias that no step updates (assumed (f)), and
# the held experts' load — and with it the step's time — drifts by seed
# (PERF.md section 6, PR 33, has the readings at both).
LR, WARMUP, TOTAL_STEPS = 2.2e-4, 2_000, 100_000

# memory_stats()["bytes_limit"] of a v5e chip (PERF.md §6): what the remat
# rule is told when the step is compiled for a chip that is described
V5E_BYTES_LIMIT = 16_909_334_528

# Program (bf16 stream and matmul operands; f32 accumulation, router, Δ,
# decays, state, softmax, residual add and logits; the compiled flash and
# grouped kernels) against the float32 reference on the same weights and the
# cell's own first row of 4,096 tokens: the loss (trunk + 0.1 · MTP) and its
# gradient, tensor by tensor (grad_error). harness/checks.py compares two
# numbers under the name ``grad_norm`` by one rtol; this family gives it the
# reference's summed tensor norms S and, for the program, S · (1 +
# grad_error), so GRAD_NORM_RTOL is the limit of grad_error. Each limit
# stands between readings on the chip (PERF.md §6, PR 33): over a dozen
# seeds the program was 1.1e-5 .. 7.7e-5 off on the loss and, by grad_error,
# 5.7e-4 .. 8.0e-4 (median tensor 5.0e-4 .. 7.0e-4 + summed norms 1.8e-5 ..
# 1.5e-4). The reference with its forward matmuls' operands in float8_e4m3
# (one scale a tensor; the precision below the bf16 the configuration
# states) reads 2.4e-4 .. 7.6e-4 on the loss and 3.7e-3, 5.5e-3 by grad_error
# (the median tensor 3.1e-3, 4.3e-3): it fails the gradient's limit in both
# seeds and the loss's too. With the routed experts left out 3.0e-5, 3.3e-5 /
# 7.9e-3, 8.2e-3 (the summed norms 7.1e-3, 7.2e-3): the gradient's. With the
# MTP loss left out 9.0e-2 on the loss.
LOSS_RTOL = 1.7e-4        # 2.2x the worst seen, 1.4x under float8's lowest
GRAD_NORM_RTOL = 1.75e-3  # 2.2x the worst seen, 2.1x under float8's lowest


def grad_error(norms, reference_norms) -> Dict[str, float]:
    """How far a gradient is from the reference's, from each parameter
    tensor's norm: ``median`` — the median over the tensors (those the
    reference gives a gradient at all: not the selection biases) of the
    relative error of the tensor's norm, which a fault in ANY kind of layer
    moves, a Mamba or router tensor as much as the embedding — plus ``sum`` —
    the relative error of the summed norms, where a routed expert's 0.06
    counts beside the embedding's 63.5 (a sum of squares would lose it) and
    which a kind left out moves most. ``total`` is their sum."""
    import numpy as np

    norms = np.asarray(norms, np.float64)
    ref = np.asarray(reference_norms, np.float64)
    live = ref > 0
    median = float(np.median(np.abs(norms[live] - ref[live]) / ref[live]))
    summed = float(abs(norms.sum() - ref.sum()) / ref.sum())
    return {"median": median, "sum": summed, "total": median + summed}


def _require_program() -> None:
    """A checkout whose program has no Nemotron-H model (the parent of PR 33)
    cannot run this family: say so before a cluster is started."""
    from ray_tpu.tracing import names

    if not hasattr(names, "SSD_SCAN"):
        raise SystemExit(
            "benchmarks/families/nemotron_h.py: this checkout cannot run a "
            "cell of family nemotron_h: its program has no Mamba-2 mixer "
            "(ray_tpu/ops/mamba2.py), no expert layer that knows its share "
            "(ray_tpu/ops/moe.latent_moe) and no pattern of layer kinds "
            "(ray_tpu/models/nemotron_h.py)")


def program_config(config: Dict[str, Any], cell: Dict[str, Any]):
    """The program's NemotronHConfig for this configuration file and cell."""
    from ray_tpu.models import nemotron_h

    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"]:
        raise SystemExit(f"hybrid_override_pattern {pattern!r} has not "
                         f"num_hidden_layers={config['num_hidden_layers']} layers")
    return nemotron_h.NemotronHConfig(
        vocab_size=config["vocab_size"],
        seq_len=cell["seq_len"],
        pattern=pattern,
        mtp_pattern=(config["mtp_hybrid_override_pattern"]
                     if config["num_nextn_predict_layers"] else ""),
        n_layer_published=config["published"]["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        mamba_groups=config["n_groups"],
        ssm_state=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"],
        chunk=config["chunk_size"],
        n_experts=config["published"]["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        held_first=config["held_first_expert"],
        held_count=config["n_routed_experts"],
        latent=config["moe_latent_size"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_shared_expert_intermediate_size"],
        routed_scaling=float(config["routed_scaling_factor"]),
        rms_eps=config["layer_norm_epsilon"],
        mtp_loss_weight=config["mtp_loss_scaling_factor"],
        remat=cell["remat"],
    )


_expert_load: list = []     # build's model/expert_load events, for the summary


def _optimizer():
    from ray_tpu.models import nemotron_h
    from ray_tpu.train.train_step import default_optimizer

    return default_optimizer(lr=LR, warmup=WARMUP, total_steps=TOTAL_STEPS,
                             decay_mask=nemotron_h.decays)


def build(config: Dict[str, Any], cell: Dict[str, Any], mesh, seed: int):
    """The program's TrainStepBundle, through its one step factory (the
    weights drawn with the device's own bit generator, ``impl="rbg"``: 68
    tensors through threefry compile for 40 s on the chip), its expert
    layers' selection biases balanced on the first batch the seed
    gives (``nemotron_h.balance_router_bias``): the bias's between-step
    update is not part of the step (assumed (f)), so the run starts where a
    deployment's update would have brought it and the held experts see the
    mean load, not what the seed's 64 symbols happen to draw."""
    import dataclasses

    import jax

    from benchmarks.harness import spec, traffic
    from ray_tpu.models import nemotron_h
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.train.train_step import make_train_step

    bundle = make_train_step(
        nemotron_h, program_config(config, cell), mesh=mesh,
        optimizer=_optimizer(), rng=jax.random.key(seed, impl="rbg"))
    alphabet = spec.load_cell(cell["name"])[2]["alphabet"]
    first = jax.device_put(traffic.host_batch(
        cell["per_chip_batch"] * cell["chips"], seed, cell["seq_len"],
        alphabet), bundle.data_sharding)
    with mesh_lib.use_mesh(mesh):
        params, _expert_load[:] = nemotron_h.balance_router_bias(
            bundle.state["params"], first["tokens"], first["targets"],
            bundle.cfg)
    return dataclasses.replace(bundle, state={**bundle.state, "params": params})


def shapes(config: Dict[str, Any], cell: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the arithmetic needs: harness/flops.py's keys (run.py reads
    them for every cell) and this family's own. From the files alone: the
    driver calls this and must not touch JAX."""
    _require_program()
    d, vocab = config["hidden_size"], config["vocab_size"]
    heads, kv, hd = (config["num_attention_heads"],
                     config["num_key_value_heads"], config["head_dim"])
    mh, mp = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, state = config["n_groups"], config["ssm_state_size"]
    inner, conv_dim = mh * mp, mh * mp + 2 * groups * state
    latent, held = config["moe_latent_size"], config["n_routed_experts"]
    experts = config["published"]["n_routed_experts"]
    d_expert = config["moe_intermediate_size"]
    d_shared = config["moe_shared_expert_intermediate_size"]
    pattern = config["hybrid_override_pattern"]
    mtp = (config["mtp_hybrid_override_pattern"]
           if config["num_nextn_predict_layers"] else "")
    # a layer's parameters that sit in a matmul a token meets, and the rest
    # (conv, A_log, D, dt_bias, the gated norm; the selection bias; every
    # layer's pre-norm)
    matmul = {"M": d * (inner + conv_dim + mh) + inner * d,
              "E": d * experts + 2 * d * latent + 2 * d * d_shared,
              "*": 2 * d * (heads + kv) * hd}
    other = {"M": (config["conv_kernel"] + 1) * conv_dim + 3 * mh + inner + d,
             "E": d + experts, "*": d}
    routed = 2 * latent * d_expert                      # one routed expert
    layers = pattern + mtp
    params = (sum(matmul[k] + other[k] for k in layers)
              + layers.count("E") * held * routed + 2 * vocab * d + d
              + (2 * d * d + 2 * d if mtp else 0))
    return {
        "params": params,
        "matmul_params_per_kind": matmul,
        "routed_expert_params": routed,
        "expected_pairs_per_token": (config["num_experts_per_tok"] * held
                                     / experts),
        "vocab": vocab,
        "n_layer": len(pattern),
        "pattern": pattern,
        "mtp_pattern": mtp,
        "d_model": d,
        "n_head": heads,
        "head_dim": hd,
        "seq_len": cell["seq_len"],
        "mamba_heads": mh,
        "mamba_head_dim": mp,
        "mamba_groups": groups,
        "ssm_state": state,
        "chunk": config["chunk_size"],
        "per_chip_batch": cell["per_chip_batch"],
        "chips": cell["chips"],
        "remat": cell["remat"],
        "attention_dtype_bytes": 2,                    # bf16 q, k, v, o, do
    }


def _scan_macs_per_token(shapes: Dict[str, Any]) -> float:
    """Multiply-adds a token of ONE Mamba-2 layer's state-space scan, forward:
    Q/2·(G·N + H·P) inside its chunk (C·Bᵀ and the masked product with Δx,
    the causal half) and 2·H·P·N with the state (B ⊗ Δx into it, C out)."""
    h, p = shapes["mamba_heads"], shapes["mamba_head_dim"]
    g, n = shapes["mamba_groups"], shapes["ssm_state"]
    q = min(shapes["chunk"], shapes["seq_len"])
    return q / 2.0 * (g * n + h * p) + 2.0 * h * p * n


def train_flops_per_token(shapes: Dict[str, Any]) -> float:
    """Forward + backward operations one trained token REQUIRES here: 6 per
    matmul parameter the token meets — the routed experts by the pairs a
    token is expected to land on held ones (top_k · held / n_experts a
    layer), the head once for the trunk and once for the MTP module, the
    embedding a gather — and by shape three times the forward's attention
    (q·k and p·v over the causal half) and state-space scan. Recomputed
    operations do not count. ``nemotron_h.flops_per_token`` is the program's
    count of the same (a tier-1 test holds the two together)."""
    d, s = shapes["d_model"], shapes["seq_len"]
    layers = shapes["pattern"] + shapes["mtp_pattern"]
    matmul = sum(shapes["matmul_params_per_kind"][k] for k in layers)
    matmul += (layers.count("E") * shapes["expected_pairs_per_token"]
               * shapes["routed_expert_params"])
    matmul += d * shapes["vocab"]
    if shapes["mtp_pattern"]:
        matmul += 2 * d * d + d * shapes["vocab"]
    attention = 2.0 * shapes["n_head"] * shapes["head_dim"] * (s + 1) / 2.0
    shaped = (layers.count("*") * attention
              + layers.count("M") * _scan_macs_per_token(shapes))
    return 6.0 * (matmul + shaped)


def ssd_scan_call(shapes: Dict[str, Any]) -> Dict[str, float]:
    """Least operations and HBM bytes of the state-space scans ONE step makes
    on one device, forward and backward (twice the forward), no recompute. A
    scan reads x, B, C (bf16) and Δ (f32) and writes y (f32); the backward
    reads those and y's gradient and writes theirs."""
    tokens = shapes["per_chip_batch"] * shapes["seq_len"]
    h, p = shapes["mamba_heads"], shapes["mamba_head_dim"]
    g, n = shapes["mamba_groups"], shapes["ssm_state"]
    layers = (shapes["pattern"] + shapes["mtp_pattern"]).count("M")
    a = shapes["attention_dtype_bytes"]
    moved = (h * p + 2 * g * n) * a + h * 4 + h * p * 4
    return {"flops": 3.0 * 2.0 * _scan_macs_per_token(shapes) * tokens * layers,
            "bytes": 3.0 * moved * tokens * layers}


def attention_resolved(bundle) -> list:
    """What the program's one attention rule chose on this mesh."""
    from ray_tpu.ops.attention import resolve_attention

    return list(resolve_attention(bundle.cfg.attention_impl, bundle.mesh))


def reference_sizes(cfg, **switches) -> Dict[str, Any]:
    """The share description the reference takes, from the program's config."""
    return {"eps": cfg.rms_eps, "pattern": cfg.pattern,
            "mtp_pattern": cfg.mtp_pattern, "mamba_groups": cfg.mamba_groups,
            "top_k": cfg.top_k, "scaling": cfg.routed_scaling,
            "held_first": cfg.held_first,
            "mtp_weight": cfg.mtp_loss_weight, **switches}


def readings(bundle, batch_host: Dict[str, Any], cell: Dict[str, Any],
             which=("program", "reference"), **switches) -> Dict[str, Any]:
    """Loss and (``reference_grad``) each parameter tensor's gradient norm of
    the program and of the reference, with ``switches``
    (nemotron_h_reference's) for the readings a limit must catch, on the
    state's parameters as set-up left them and the cell's own first
    ``reference_rows`` rows, whole. One compiled program a side."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import nemotron_h
    from ray_tpu.parallel import mesh as mesh_lib

    mesh, cfg = bundle.mesh, bundle.cfg
    rows = cell["reference_rows"]
    with_grad = bool(cell["reference_grad"])
    params = bundle.state["params"]
    param_sh = jax.tree.map(lambda p: p.sharding, params)
    scalar = NamedSharding(mesh, P())
    sizes = reference_sizes(cfg, **switches)

    def program(p, tokens, targets):
        with mesh_lib.use_mesh(mesh):
            return nemotron_h.loss_fn(p, tokens, targets, cfg)

    def reference(p, tokens, targets):
        with jax.default_matmul_precision("highest"):
            return nemotron_h_reference.loss(p, tokens, targets, sizes)

    def loss_and_grad_norms(loss_of):
        def fn(p, tokens, targets):
            if not with_grad:
                return loss_of(p, tokens, targets), jnp.zeros((0,))
            loss, grads = jax.value_and_grad(loss_of)(p, tokens, targets)
            return loss, jnp.stack([optax.global_norm(g)
                                    for g in jax.tree.leaves(grads)])

        fn.__name__ = loss_of.__name__ + "_loss_and_grad_norms"
        return jax.jit(
            fn, in_shardings=(param_sh, bundle.data_sharding, bundle.data_sharding),
            out_shardings=(scalar, scalar))

    sub = jax.device_put(
        {k: np.asarray(v[:rows]).copy() for k, v in batch_host.items()},
        bundle.data_sharding)
    out = {}
    for loss_of in (program, reference):
        if loss_of.__name__ in which:
            loss, norms = loss_and_grad_norms(loss_of)(
                params, sub["tokens"], sub["targets"])
            out[loss_of.__name__] = {
                "loss": float(loss),
                "grad_norm_by_tensor": np.asarray(norms, np.float64).tolist()}
    out.update(rows=rows, with_grad=with_grad, loss_rtol=LOSS_RTOL,
               grad_norm_rtol=GRAD_NORM_RTOL)
    return out


def reference_check(bundle, batch_host: Dict[str, Any], config, cell) -> Dict[str, Any]:
    """Program against the plain reference (``readings``; ``grad_norm`` as
    GRAD_NORM_RTOL's comment says), and what the first batch, whole, sends
    the experts held here under the bias ``build`` balanced on it (the
    program's ``model/expert_load`` events: a batch that passed the row
    buffer would show ``pairs_dropped``). Returns the numbers; judges
    nothing."""
    out = readings(bundle, batch_host, cell)
    total = float(sum(out["reference"]["grad_norm_by_tensor"]))
    error = (grad_error(out["program"]["grad_norm_by_tensor"],
                        out["reference"]["grad_norm_by_tensor"])
             if out["with_grad"] else {"total": 0.0})
    out["reference"]["grad_norm"] = total
    out["program"].update(grad_norm=total * (1.0 + error["total"]),
                          grad_error=error)
    out["expert_load"] = _expert_load
    return out


def abstract_step(config: Dict[str, Any], cell: Dict[str, Any], mesh):
    """(jitted step, abstract arguments) for a compile with no device to hold
    an array (harness/rehearse_compile.py). The step IS the program's:
    ``train_step._compose_step`` composes it, told the described chip's
    bytes_limit and the bytes its state and gradients take (as family
    ``evabyte`` does, and why)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import nemotron_h
    from ray_tpu.train.train_step import _compose_step, _resident_bytes

    cfg = program_config(config, cell)
    optimizer = _optimizer()
    step_given, state_sh, batch_sh = _compose_step(
        nemotron_h, cfg, mesh, optimizer, None)
    params = jax.eval_shape(lambda: nemotron_h.init(cfg, jax.random.PRNGKey(0)))
    shapes_of = {"params": params,
                 "opt_state": jax.eval_shape(optimizer.init, params),
                 "step": jax.ShapeDtypeStruct((), jnp.int32)}
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        shapes_of, state_sh)
    global_batch = cell["per_chip_batch"] * cell["chips"]
    tok = jax.ShapeDtypeStruct((global_batch, cfg.seq_len), jnp.int32,
                               sharding=batch_sh["tokens"])
    fn = jax.jit(
        step_given((V5E_BYTES_LIMIT, _resident_bytes(state))),
        in_shardings=(state_sh, batch_sh), out_shardings=(state_sh, None),
        donate_argnums=(0,))
    return fn, (state, {"tokens": tok, "targets": tok})
