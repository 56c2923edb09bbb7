"""Family ``qwen3_next``: from a configuration file to the program's train step.

Qwen3-Next is the program's hybrid of Gated DeltaNet and gated attention over
a mixture of experts (``ray_tpu/models/qwen3_next.py``): three layers in four
the gated delta rule (a fused q, k, v, z projection, a causal conv of 4 taps,
L2-normed q and k, 16 key heads serving 32 value heads, the recurrence on the
program's own chunked kernel pair, a gated per-head norm), the fourth
grouped-query attention at head width 256 with a zero-centred QK-norm, RoPE
on a quarter of the head and a per-head sigmoid output gate; every layer then
512 gated experts top-10 by a normalised softmax beside ONE shared expert
under a per-token sigmoid gate, with the sequence-wise balance loss in the
step's objective. As for the other families the benchmark hands the program
the published sizes, the chip's share of the deployment and what the cell's
file states (per-chip batch, row length, ``remat``, mesh) and NOTHING else:
the scan's chunk and tiling, how the pattern is scanned, the held experts'
row buffer, what remat keeps and the rows the head takes at a time stay at
the program's defaults.

Everything ``benchmarks/README.md`` asks of a family is here — ``build``,
``shapes``, ``attention_resolved``, ``reference_check``, ``abstract_step`` —
and, as the families before it brought:

- ``train_flops_per_token(shapes)``: this family's own count
  (``qwen3_next_mfu_device`` reads it);
- ``gated_delta_call(shapes)``: least operations and HBM bytes of the three
  DeltaNet layers' scan calls ONE step makes, counted from the chunk form at
  the program's chunk, whatever implements it (``gated_delta_roofline``);
- ``flash_attn_call(shapes)``: the same of the one attention layer's flash
  calls at head width 256 (``qwen3_next_flash_attn_roofline``);
- ``experts_call(shapes)``: the same of the held experts' grouped products.

No name of ``ray_tpu`` is imported at module level: a checkout whose program
lacks this family (the parent of PR 61) imports this file, is told so by
``shapes`` — which the driver calls before it starts a cluster — and exits.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict

from benchmarks.families import qwen3_next_reference

# AdamW as the program's default_optimizer builds it (beta 0.9 / 0.95, weight
# decay 0.1 on the matrices alone — ``qwen3_next.decays`` —, clipping 1.0,
# bf16 moments), a linear warm-up to 3e-4 STRETCHED tenfold, to 20,000 steps:
# a 20 s window is that run's first ~30 steps at rates up to 5e-7, so that a
# window cannot move the routers out of set-up's balance (the configuration's
# ``assumed`` (h), (i); the LFM2 and DeepSeek families' choice, for their
# reason). It does not depend on --seconds.
LR, WARMUP, TOTAL_STEPS = 3e-4, 20_000, 100_000

# memory_stats()["bytes_limit"] of a v5e chip (PERF.md §6): what the remat
# rule is told when the step is compiled for a chip that is described
V5E_BYTES_LIMIT = 16_909_334_528

# Program (bf16 stream and matmul operands; f32 accumulation, router logits
# and probabilities, norms' statistics, the conv, the gates g and β, the
# delta rule's solve, decays and state, attention's softmax and output gate,
# residual add, logits and the loss; the compiled scan, flash and grouped
# kernels) against the float32 reference — the delta rule TOKEN BY TOKEN — on
# the same weights and the window's own first batch, whole (4 rows of 8,192):
# the loss — balance loss included —, and the gradient tensor by tensor
# (``grad_error``, as family nemotron_h compares it: harness/checks.py
# compares two numbers under the name ``grad_norm`` by one rtol; this family
# gives it the reference's summed tensor norms S and, for the program, S ·
# (1 + grad_error), so GRAD_NORM_RTOL is the limit of grad_error). The
# reference is GIVEN the sets the program's routers chose (its file says why)
# and reports how far below its own last chosen probability a
# given-but-not-own expert lies at worst, relative to it: past ROUTE_MARGIN
# the program's choice is not the reference's rule, and 1.0 is added to
# grad_error, which no rtol passes.
#
# The readings on the chip (PERF.md §6, PR 61; loss / grad_error / margin):
# the program over sixteen runs and five readings in one process 9.3e-7 ..
# 2.9e-5 / 2.0e-4 .. 8.3e-4 / 0.123 .. 0.153 — 12 % of the tokens choose
# another set than the float32 reference in layer 0 and 31 .. 33 % in layer 3
# (512 experts' tenth and eleventh probabilities lie close, and the bf16
# stream's error at a router's logit grows with depth); GIVEN those sets the
# gradients agree to 8e-4. The controls (``controls()``, one seed): the
# reference with its forward matmuls' operands in float8_e4m3 (one scale a
# tensor; the precision below the bf16 the configuration states for
# operands), routing by its own scores: 3.0e-5 .. 7.2e-5 / 2.85e-3 .. 4.18e-3
# / 0.80 — it fails the gradient's limit and the margin's, each alone (the
# two limits THIS cell brings); attention's output gate in float8: 6.2e-5 /
# 6.76e-3 / 0.16 — the gradient's; the recurrence's q, k, v in float8: 3.4e-5
# / 1.11e-3 / 0.50 — the margin's. So: the gradient's limit stands 1.9x over
# the worst seen and 1.8x under float8's lowest; the margin's 2.0x over and
# 1.7x under the lowest control's. The
# loss's is the accepted expert cells' 1.7e-4 (5.9x over the first reading):
# float8's 3.0e-5 is INSIDE the program's range — at the initial weights the
# loss carries no precision signal here either.
#
# What no limit the program passes can refuse, each NEARER the float32
# reference than the bf16 program or inside its own range, because the
# program's error is its bf16 STREAM's: the output gate in bf16 (1.9e-7 /
# 2.7e-5 / 1.8e-3) and the PROGRAM with its solve's float32 products in one
# bf16 pass (1.0e-5 / 3.6e-4 / 0.131). The solve's precision is held where it
# shows: tests/test_qwen3_next.py reads the kernels 3.2e-6 from the
# recurrence in float32 and 1.0e-3 with one bf16 pass.
LOSS_RTOL = 1.7e-4
GRAD_NORM_RTOL = 1.6e-3
ROUTE_MARGIN = 0.30


def _require_program() -> None:
    """A checkout whose program has no Qwen3-Next model (the parent of
    PR 61) cannot run this family: say so before a cluster is started."""
    import importlib.util

    if importlib.util.find_spec("ray_tpu.models.qwen3_next") is None:
        raise SystemExit(
            "benchmarks/families/qwen3_next.py: this checkout cannot run a "
            "cell of family qwen3_next: its program has no Gated DeltaNet "
            "model (ray_tpu/models/qwen3_next.py, ray_tpu/ops/gated_delta.py, "
            "a gated shared expert in ray_tpu/ops/moe.gated_moe)")


def program_config(config: Dict[str, Any], cell: Dict[str, Any]):
    """The program's Qwen3NextConfig for this configuration file and cell."""
    from ray_tpu.models import qwen3_next

    for key, only in (("hidden_act", "silu"), ("norm_topk_prob", True),
                      ("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("rope_scaling", None), ("tie_word_embeddings", False),
                      ("use_sliding_window", False)):
        if config[key] != only:
            raise SystemExit(f"{key} = {config[key]!r}: the program's "
                             f"Qwen3-Next layer is {only!r}")
    return qwen3_next.Qwen3NextConfig(
        vocab_size=config["vocab_size"],
        seq_len=cell["seq_len"],
        n_layer=config["num_hidden_layers"],
        first_layer=config["first_layer"],
        full_attention_interval=config["full_attention_interval"],
        d_model=config["hidden_size"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        partial_rotary_factor=config["partial_rotary_factor"],
        rope_theta=float(config["rope_theta"]),
        linear_key_heads=config["linear_num_key_heads"],
        linear_value_heads=config["linear_num_value_heads"],
        linear_key_dim=config["linear_key_head_dim"],
        linear_value_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        n_experts=config["published"]["num_experts"],
        top_k=config["num_experts_per_tok"],
        held_first=config["held_first_expert"],
        held_count=config["num_experts"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["shared_expert_intermediate_size"],
        aux_loss_coef=config["router_aux_loss_coef"],
        init_std=config["initializer_range"],
        rms_eps=config["rms_norm_eps"],
        remat=cell["remat"],
    )


_expert_load: list = []     # build's model/expert_load events, for the summary

# the chunk the program's scan works in (ops/gated_delta.CHUNK, the published
# fallback): what ``gated_delta_call`` counts the chunk form at; a tier-1 test
# holds the two together
DELTA_CHUNK = 64


def _optimizer(cell: Dict[str, Any]):
    """(The CPU rehearsal's tiny sizes state a warm-up of their own.)"""
    from ray_tpu.models import qwen3_next
    from ray_tpu.train.train_step import default_optimizer

    return default_optimizer(lr=LR, warmup=cell.get("lr_warmup", WARMUP),
                             total_steps=TOTAL_STEPS,
                             decay_mask=qwen3_next.decays)


def build(config: Dict[str, Any], cell: Dict[str, Any], mesh, seed: int):
    """The program's TrainStepBundle, through its one step factory (the
    weights drawn with the device's own bit generator, ``impl="rbg"``, as
    family nemotron_h draws its), every layer's router balanced by
    ``qwen3_next.balance_routers`` (rounds of gradient descent on each
    layer's own balance loss, the other weights held), each round on a batch
    of its own: the mix's rows from 0 on as the seed gives them — the
    window's and as many again past its end (the DeepSeek family's set-up,
    and why: the configuration's ``assumed`` (h))."""
    import dataclasses

    import jax

    from benchmarks.harness import spec, traffic
    from ray_tpu.models import qwen3_next
    from ray_tpu.ops import moe
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.train.train_step import make_train_step

    bundle = make_train_step(
        qwen3_next, program_config(config, cell), mesh=mesh,
        optimizer=_optimizer(cell), rng=jax.random.key(seed, impl="rbg"))
    alphabet = spec.load_cell(cell["name"])[2]["alphabet"]
    batch = cell["per_chip_batch"] * cell["chips"]
    rows = traffic.host_batch(batch * moe.BALANCE_ROUNDS, seed,
                              cell["seq_len"], alphabet)["tokens"]
    batches = [jax.device_put(rows[i:i + batch], bundle.data_sharding)
               for i in range(0, len(rows), batch)]
    with mesh_lib.use_mesh(mesh):
        params, _expert_load[:] = qwen3_next.balance_routers(
            bundle.state["params"], batches, bundle.cfg)
    return dataclasses.replace(bundle, state={**bundle.state, "params": params})


def shapes(config: Dict[str, Any], cell: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the arithmetic needs: harness/flops.py's keys (run.py reads
    them for every cell) and this family's own. From the files alone: the
    driver calls this and must not touch JAX."""
    _require_program()
    d, vocab = config["hidden_size"], config["vocab_size"]
    heads, kv_heads, hd = (config["num_attention_heads"],
                           config["num_key_value_heads"], config["head_dim"])
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    fe, fs = (config["moe_intermediate_size"],
              config["shared_expert_intermediate_size"])
    held, experts = config["num_experts"], config["published"]["num_experts"]
    layers, every = config["num_hidden_layers"], config["full_attention_interval"]
    attention_layers = sum((config["first_layer"] + i + 1) % every == 0
                           for i in range(layers))
    delta_layers = layers - attention_layers
    # a mixer's parameters that sit in a matmul a token meets, and the rest
    # (the conv's taps, A_log, dt_bias, the gated norm's gain; the QK-norms'
    # gains); a layer's two pre-norms; the expert half outside its routed
    # experts (router, shared expert, its gate's column)
    delta = d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d
    delta_other = (config["linear_conv_kernel_dim"] * (2 * hk * dk + hv * dv)
                   + 2 * hv + dv)
    attention = d * heads * 2 * hd + 2 * d * kv_heads * hd + heads * hd * d
    half = d * experts + 3 * d * fs + d
    routed = 3 * d * fe                                 # one routed expert
    params = (delta_layers * (delta + delta_other)
              + attention_layers * (attention + 2 * hd)
              + layers * (half + 2 * d + held * routed) + 2 * vocab * d + d)
    return {
        "params": params,
        "matmul_params_per_kind": {"L": delta + half, "F": attention + half},
        "routed_expert_params": routed,
        "expected_pairs_per_token": (config["num_experts_per_tok"] * held
                                     / experts),
        "expert_layers": layers,
        "delta_layers": delta_layers,
        "attention_layers": attention_layers,
        "held_experts": held,
        "d_expert": fe,
        "vocab": vocab,
        "n_layer": layers,
        "d_model": d,
        "n_head": heads,
        "head_dim": hd,
        "delta_key_heads": hk,
        "delta_value_heads": hv,
        "delta_key_dim": dk,
        "delta_value_dim": dv,
        "delta_chunk": DELTA_CHUNK,
        "seq_len": cell["seq_len"],
        "per_chip_batch": cell["per_chip_batch"],
        "chips": cell["chips"],
        "remat": cell["remat"],
        "attention_dtype_bytes": 2,          # bf16 q, k, v, o, do
    }


def _delta_macs_per_token(shapes: Dict[str, Any]) -> float:
    """Multiply-adds one token's forward requires of ONE DeltaNet layer's
    scan, from the chunk form at chunk C (the configuration's layer
    equations), a masked product at the half its mask leaves: a key head's
    K·Kᵀ and Q·Kᵀ, once for the value heads it serves (2 · C·d_k / 2); a
    value head's solve applied to [βγK | βV] by substitution (C · (d_k + d_v)
    / 2), W·S, Q·S and the state's Kᵀ·D (3 · d_k · d_v), the masked P·D
    (C · d_v / 2)."""
    c, dk, dv = (shapes["delta_chunk"], shapes["delta_key_dim"],
                 shapes["delta_value_dim"])
    return (shapes["delta_key_heads"] * c * dk
            + shapes["delta_value_heads"]
            * (c * (dk + dv) / 2 + c * dv / 2 + 3 * dk * dv))


def train_flops_per_token(shapes: Dict[str, Any]) -> float:
    """Forward + backward operations one trained token REQUIRES here: 6 per
    matmul parameter the token meets — the mixers' projections, the router,
    the shared expert with its gate, the routed experts by the pairs a token
    is expected to land on held ones (top_k · held / n_experts a layer), the
    untied head whole, the embedding a gather — and by shape three times the
    forward's attention (two products at 256 over the causal half, one layer
    in four) and scan (``_delta_macs_per_token``). Recomputed operations do
    not count. ``qwen3_next.flops_per_token`` is the program's count of the
    same (a tier-1 test holds the two together)."""
    d, s = shapes["d_model"], shapes["seq_len"]
    per_kind = shapes["matmul_params_per_kind"]
    matmul = (shapes["delta_layers"] * per_kind["L"]
              + shapes["attention_layers"] * per_kind["F"])
    matmul += (shapes["expert_layers"] * shapes["expected_pairs_per_token"]
               * shapes["routed_expert_params"])
    matmul += d * shapes["vocab"]
    attention = 2 * shapes["n_head"] * shapes["head_dim"] * (s + 1) / 2.0
    return 6.0 * (matmul + shapes["attention_layers"] * attention
                  + shapes["delta_layers"] * _delta_macs_per_token(shapes))


def gated_delta_call(shapes: Dict[str, Any]) -> Dict[str, float]:
    """Least operations and HBM bytes of the DeltaNet layers' scan calls ONE
    step makes on one device, no recompute: a forward and a backward call a
    layer. Operations: the chunk form's (``_delta_macs_per_token``: 2 a
    multiply-add), the backward twice the forward's (each product's two
    gradients). Bytes: forward reads q, k (a KEY head's, once) and v in bf16
    and g, β in float32 and writes o; backward reads those, o's cotangent
    and the float32 state each chunk starts from ([d_k, d_v] a value head and
    chunk — the forward writes it, the backward reads it) and writes the five
    gradients."""
    tokens = float(shapes["per_chip_batch"] * shapes["seq_len"])
    hk, hv = shapes["delta_key_heads"], shapes["delta_value_heads"]
    dk, dv, c = (shapes["delta_key_dim"], shapes["delta_value_dim"],
                 shapes["delta_chunk"])
    a = shapes["attention_dtype_bytes"]
    qkv = tokens * a * (2 * hk * dk + hv * dv)
    o = tokens * a * hv * dv
    gates = tokens * 4 * 2 * hv
    states = tokens / c * hv * dk * dv * 4
    fwd = {"flops": 2.0 * tokens * _delta_macs_per_token(shapes),
           "bytes": qkv + gates + o + states}
    bwd = {"flops": 2.0 * fwd["flops"],
           "bytes": 2 * qkv + 2 * gates + o + states}
    return {k: shapes["delta_layers"] * (fwd[k] + bwd[k]) for k in fwd}


def experts_call(shapes: Dict[str, Any]) -> Dict[str, float]:
    """Least operations and HBM bytes of the held experts' grouped products
    ONE step makes on one device, no recompute: family deepseek_v2's count
    (three products forward, six backward, a product reading its rows and
    the held experts' weights and writing its rows, in bf16) on this
    family's shapes."""
    from benchmarks.families import deepseek_v2

    return deepseek_v2.experts_call(shapes)


def flash_attn_call(shapes: Dict[str, Any]) -> Dict[str, float]:
    """Least operations and HBM bytes of the attention layers' flash calls
    ONE step makes on one device, no recompute (harness/flops.attention_call:
    a forward and a backward call an ATTENTION layer, one layer in four, over
    the causal half at head width 256; k and v counted at the 16 query heads'
    width, as the kernel is handed them)."""
    from benchmarks.harness import flops

    fwd = flops.attention_call(shapes, backward=False)
    bwd = flops.attention_call(shapes, backward=True)
    return {k: shapes["attention_layers"] * (fwd[k] + bwd[k]) for k in fwd}


def attention_resolved(bundle) -> list:
    """What the program's one attention rule chose on this mesh (the scan's
    kernels follow the same rule)."""
    from ray_tpu.ops.attention import resolve_attention

    return list(resolve_attention(bundle.cfg.attention_impl, bundle.mesh))


def reference_sizes(cfg, **switches) -> Dict[str, Any]:
    """The model and share description the reference takes, from the
    program's config. ``order`` flat: the program's fused projections hold
    their columns as q, k, v, z and b, a (the configuration's ``assumed``
    (b))."""
    return {"eps": cfg.rms_eps, "pattern": cfg.pattern,
            "Hk": cfg.linear_key_heads, "Hv": cfg.linear_value_heads,
            "dk": cfg.linear_key_dim, "dv": cfg.linear_value_dim,
            "rotary": cfg.rotary_dim, "theta": cfg.rope_theta,
            "top_k": cfg.top_k, "alpha": cfg.aux_loss_coef,
            "held_first": cfg.held_first, "order": "flat", **switches}


# The readings a limit must refuse (PERF.md §6, PR 61), by name: the
# reference switched (qwen3_next_reference's switches) or, for the solve —
# which the token-by-token reference does not have — the PROGRAM with the
# solve's float32 products (ops/gated_delta._mm32) in ONE bf16 pass.
def controls() -> Dict[str, Dict[str, Any]]:
    import jax.numpy as jnp

    f8 = jnp.float8_e4m3fn
    return {"float8": {"operand_dtype": f8},
            "scan_float8": {"scan_dtype": f8},
            "gate_float8": {"gate_dtype": f8},
            "gate_bf16": {"gate_dtype": jnp.bfloat16},
            "solve_bf16": {"program_solve": "bf16"}}


@contextlib.contextmanager
def _program_solve(precision):
    """The program's scan with its solve's products at ``precision`` (None:
    as it is; "bf16": one pass of bf16 operands), for the control alone —
    the test's hand on the program, not an option of it."""
    if precision is None:
        yield
        return
    from ray_tpu.ops import gated_delta

    kept = gated_delta._mm32
    # (an operand arrives as its two bf16 terms: the first alone)
    gated_delta._mm32 = lambda a, b: gated_delta._nn(a[0], b[0])
    gated_delta._chunks_call.clear_cache()
    try:
        yield
    finally:
        gated_delta._mm32 = kept
        gated_delta._chunks_call.clear_cache()


def readings(bundle, batch_host: Dict[str, Any], cell: Dict[str, Any],
             **control) -> Dict[str, Any]:
    """Loss and (``reference_grad``) each parameter tensor's gradient norm of
    the program and of the reference on the state's parameters as set-up
    left them and the cell's own first ``reference_rows`` rows, whole. The
    reference is given the sets the program's routers chose and reports on
    them (``routing``, the layers in their order). With ``control`` (one of
    ``controls()``): the reference so switched, routing by its own scores,
    stands where the program stands — or, with ``program_solve``, the
    program itself with its solve so switched. One compiled program a side."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.families.minicpm_sala import grad_passes
    from ray_tpu.models import qwen3_next
    from ray_tpu.parallel import mesh as mesh_lib

    mesh, cfg = bundle.mesh, bundle.cfg
    rows = cell["reference_rows"]
    with_grad = bool(cell["reference_grad"])
    params = bundle.state["params"]
    param_sh = jax.tree.map(lambda p: p.sharding, params)
    data_sh, scalar = bundle.data_sharding, NamedSharding(mesh, P())
    sub = jax.device_put(
        {k: np.asarray(v[:rows]).copy() for k, v in batch_host.items()},
        data_sh)
    shape = (rows, cfg.seq_len, cfg.n_experts)
    solve = control.pop("program_solve", None)

    def program(p, tokens, targets, _):
        with mesh_lib.use_mesh(mesh):
            return qwen3_next.loss_fn(p, tokens, targets, cfg), ()

    def program_sets(p, tokens):
        """What the program's routers chose, a forward of its own."""
        with mesh_lib.use_mesh(mesh):
            return [s.reshape(shape)
                    for s in qwen3_next.chosen_experts(p, tokens, cfg)]

    def reference_with(given, **switches):
        sizes = reference_sizes(cfg, **switches)

        def reference(p, tokens, targets, sets):
            with jax.default_matmul_precision("highest"):
                loss, reports, _ = qwen3_next_reference.loss_and_routing(
                    p, tokens, targets, sizes, sets if given else None)
            return loss, reports

        return reference

    def side(loss_of, sets, passes=1):
        """(loss, each tensor's gradient norm, what ``loss_of`` gives beside
        its loss) of one side. With ``passes`` > 1 the gradient is made a
        part of the parameter tensors at a time (``grad_passes``: parts of
        about equal bytes), the others held: the float32 reference's whole
        (the running sum over the rows and a row's, 2 x 2.5 GB, beside a
        row's float32 activations) does not fit beside the step's state."""
        def fn(p, tokens, targets, sets):
            if not with_grad or passes > 1:
                loss, aux = loss_of(p, tokens, targets, sets)
                return loss, jnp.zeros((0,)), aux
            (loss, aux), grads = jax.value_and_grad(loss_of, has_aux=True)(
                p, tokens, targets, sets)
            return loss, jnp.stack([optax.global_norm(g)
                                    for g in jax.tree.leaves(grads)]), aux

        def part_norms(which):
            def fn(p, tokens, targets, sets):
                leaves, treedef = jax.tree.flatten(p)

                def of(part):
                    full = list(leaves)
                    for i, leaf in zip(which, part):
                        full[i] = leaf
                    return loss_of(treedef.unflatten(full), tokens, targets,
                                   sets)[0]

                grads = jax.grad(of)([leaves[i] for i in which])
                return jnp.stack([optax.global_norm(g) for g in grads])

            fn.__name__ = loss_of.__name__ + "_grad_norms"
            return fn

        fn.__name__ = loss_of.__name__ + "_loss_and_grad_norms"
        loss, norms, aux = jax.jit(fn, out_shardings=(scalar, scalar, None))(
            params, sub["tokens"], sub["targets"], sets)
        norms = np.asarray(norms, np.float64)
        if with_grad and passes > 1:
            norms = np.zeros(len(jax.tree.leaves(params)))
            for part in grad_passes(params, passes):
                norms[part] = np.asarray(jax.jit(
                    part_norms(tuple(part)), out_shardings=scalar)(
                    params, sub["tokens"], sub["targets"], sets), np.float64)
        return {"loss": float(loss), "grad_norm_by_tensor": norms.tolist()}, aux

    ref_passes = cell.get("reference_grad_passes", 1)
    if control:
        # the switched reference routes by its own scores: its sets are its
        # reports' ``own``
        prog, reports = side(reference_with(False, **control), None,
                             ref_passes)
        sets = [r["own"] for r in reports]
    else:
        with _program_solve(solve):
            prog, _ = side(program, None)
            sets = jax.jit(program_sets, in_shardings=(param_sh, data_sh))(
                params, sub["tokens"])
    ref, reports = side(reference_with(True), sets, ref_passes)
    tokens = rows * cfg.seq_len
    ref["routing"] = [{"differ_share": float(r["differ"]) / tokens,
                       "worst_margin": float(r["worst_margin"])}
                      for r in reports]
    return {"program": prog, "reference": ref, "rows": rows,
            "with_grad": with_grad, "loss_rtol": LOSS_RTOL,
            # (the CPU rehearsal's tiny sizes state their own two)
            "grad_norm_rtol": cell.get("grad_norm_rtol", GRAD_NORM_RTOL),
            "route_margin": cell.get("route_margin", ROUTE_MARGIN)}


def reference_check(bundle, batch_host: Dict[str, Any], config, cell,
                    **control) -> Dict[str, Any]:
    """Program against the plain reference (``readings``; ``grad_norm`` and
    the routing's margin as the limits' comment says), and what the first
    batch sends the experts held here (the program's ``model/expert_load``
    events: a batch that passed the row buffer would show
    ``pairs_dropped``). With ``control`` (one of ``controls()``) the reading
    a limit must refuse stands where the program stands. Returns the
    numbers; judges nothing."""
    from benchmarks.families.nemotron_h import grad_error

    out = readings(bundle, batch_host, cell, **control)
    prog, ref = out["program"], out["reference"]
    worst = max((r["worst_margin"] for r in ref["routing"]), default=0.0)
    off = 0.0 if worst <= out["route_margin"] else 1.0
    total = float(sum(ref["grad_norm_by_tensor"]))
    error = (grad_error(prog["grad_norm_by_tensor"], ref["grad_norm_by_tensor"])
             if out["with_grad"] else {"total": 0.0})
    ref["grad_norm"] = total
    prog.update(grad_norm=total * (1.0 + error["total"] + off),
                grad_error=error, routing_worst_margin=worst)
    if off and not out["with_grad"]:
        prog["loss"] *= 2.0
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

    from ray_tpu.models import qwen3_next
    from ray_tpu.train.train_step import _compose_step, _resident_bytes

    cfg = program_config(config, cell)
    optimizer = _optimizer(cell)
    step_given, state_sh, batch_sh = _compose_step(
        qwen3_next, cfg, mesh, optimizer, None)
    params = jax.eval_shape(
        lambda: qwen3_next.init(cfg, jax.random.PRNGKey(0)))
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
