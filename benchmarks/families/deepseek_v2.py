"""Family ``deepseek_v2``: from a configuration file to the program's train step.

DeepSeek-V2 is the program's latent-attention model with shared + routed
gated experts (``ray_tpu/models/deepseek_v2.py``): every layer multi-head
latent attention (q·k at nope + rope = 192, v at 128, YaRN on the rope
channels, the flash kernels reading each at its own width), then a dense
SwiGLU MLP or a mixture of SiLU-gated experts beside a shared one, routed
top-k by a softmax whose chosen probabilities gate as they are, with the
sequence-wise balance loss in the step's objective. As for the other families
the benchmark hands the program the published sizes, the chip's share of the
deployment and what the cell's file states (per-chip batch, row length,
``remat``, mesh) and NOTHING else: how the pattern is scanned, the held
experts' row buffer, what remat keeps, tiles, the rows the MLP and the head
take at a time stay at the program's defaults.

Everything ``benchmarks/README.md`` asks of a family is here — ``build``,
``shapes``, ``attention_resolved``, ``reference_check``, ``abstract_step`` —
and, as the families before it brought:

- ``train_flops_per_token(shapes)``: this family's own count
  (``dsv2_mfu_device`` reads it);
- ``experts_call(shapes)``: least operations and HBM bytes of the held
  experts' grouped products ONE step makes (``dsv2_experts_roofline``);
- ``flash_attn_call(shapes)``: the same of the five layers' flash calls, q·k
  at 192 and p·v at 128 (``mla_flash_attn_roofline``).

No name of ``ray_tpu`` is imported at module level: a checkout whose program
lacks this family (the parent of PR 55) imports this file, is told so by
``shapes`` — which the driver calls before it starts a cluster — and exits.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.families import deepseek_v2_reference

# AdamW as the program's default_optimizer builds it, on the published
# schedule's start (the configuration's ``assumed`` (e)) with the warm-up
# STRETCHED tenfold, to 20,000 steps to 4.2e-4: a 20 s window is that run's
# first ~20 steps at rates up to 4e-7. Why (``assumed`` (j)): at the
# published 2,000 steps the window's twenty steps take the loss from 9.87 to
# 7.59 — every weight under the routers moves — and the held experts' load
# leaves set-up's balance inside the window: ``moe_multi_pass_steps`` 10.5 %,
# ``moe_load_imbalance`` 176 %, a second pass over the row buffer in two
# steps of nineteen (my chip run, PR 55, PERF.md section 6); a run's balance
# loss holds that balance over thousands of steps, which a window cannot
# show. The LFM2 family's choice, for its reason; the step's program is the
# same. It does not depend on --seconds.
LR, WARMUP, TOTAL_STEPS = 4.2e-4, 20_000, 100_000

# memory_stats()["bytes_limit"] of a v5e chip (PERF.md §6): what the remat
# rule is told when the step is compiled for a chip that is described
V5E_BYTES_LIMIT = 16_909_334_528

# Program (bf16 stream and matmul operands; f32 accumulation, router logits
# and probabilities, norms' statistics, attention's softmax, residual add,
# logits and the loss; the compiled flash and grouped kernels) against the
# float32 reference on the same weights and the window's own first batch,
# whole (4 rows of 8,192): the loss — balance loss included —, and the
# gradient tensor by tensor (``grad_error``, as family nemotron_h compares
# it: harness/checks.py compares two numbers under the name ``grad_norm`` by
# one rtol; this family gives it the reference's summed tensor norms S and,
# for the program, S · (1 + grad_error), so GRAD_NORM_RTOL is the limit of
# grad_error). The reference is GIVEN the sets the program's routers chose
# (its file says why) and reports how far below its own last chosen
# probability a given-but-not-own expert lies at worst, relative to it: past
# ROUTE_MARGIN the program's choice is not the reference's rule, and 1.0 is
# added to grad_error, which no rtol passes.
#
# The readings on the chip (PERF.md §6, PR 55; loss / grad_error / margin):
# the program, three readings on two seeds, 1.4e-5 .. 3.7e-5 (over
# fourteen more runs' printed lines 2.6e-6 .. 1.30e-4, and their gradients
# 3.1e-4 .. 9.9e-4) / 4.5e-4 .. 1.53e-3 / 5.8e-2 .. 6.5e-2 — 8 .. 10 % of the tokens choose another set than the float32
# reference, more the deeper the layer (the bf16 stream's error at a router's
# logit reaches 6e-2 by layer 4), and GIVEN those sets the gradients agree to
# 1.5e-3. The reference with its forward matmuls' operands in float8_e4m3
# (one scale a tensor; the precision below the bf16 the configuration states
# for operands), routing by its own scores, two seeds: 1.1e-4, 1.45e-3 /
# 6.2e-3, 5.7e-3 / 0.53, 0.51 (70 .. 77 % of the tokens choose another set):
# it fails the gradient's limit and the margin's, each alone (the two limits
# THIS cell brings: they decide ``correct``'s precision), and the loss's on
# one seed of two. So: the gradient's limit stands 2.0x over the worst seen
# and 1.9x under float8's lowest; the margin's 2.8x over and 2.8x under. The
# loss's is the accepted expert cells' 1.7e-4, which every run of this cell
# was judged by: 4.6x over the first readings (3.7e-5), 1.3x over the worst
# of seventeen (1.30e-4; their root mean square 4.8e-5). It lies between no
# two readings — float8's lower, 1.1e-4, is INSIDE the program's range: at
# the initial weights the loss carries no precision signal here — so it is
# kept as the harness's other cells have it, not set by this one.
#
# What ISSUE 55 asked for beside it — bf16 where the configuration says
# float32 (``stats_dtype``: the router's logits and probabilities,
# attention's logits and softmax, the head's logits and log-softmax, by
# ``lax.reduce_precision``), everything else float32 — NO limit the program
# passes can refuse: it reads 1.7e-6, 1.1e-5 / 3.7e-6, 7.4e-6 / 1.3e-2,
# 1.5e-2 (2.4 .. 2.9 % of the tokens), nearer the float32 reference than the
# bf16 program on every count, because the program's error is its bf16
# STREAM's, which those statistics do not touch. What would refuse it is a
# reference that emulates the bf16 stream (every tensor between two matmuls
# rounded to bf16, statistics float32), against which the program's own
# readings would fall under the control's: a follow-up (PERF.md §6, §7).
LOSS_RTOL = 1.7e-4
GRAD_NORM_RTOL = 3e-3
ROUTE_MARGIN = 0.18


def _require_program() -> None:
    """A checkout whose program has no DeepSeek-V2 model (the parent of
    PR 55) cannot run this family: say so before a cluster is started."""
    import importlib.util

    if importlib.util.find_spec("ray_tpu.models.deepseek_v2") is None:
        raise SystemExit(
            "benchmarks/families/deepseek_v2.py: this checkout cannot run a "
            "cell of family deepseek_v2: its program has no latent-attention "
            "model (ray_tpu/models/deepseek_v2.py, flash attention at unequal "
            "q.k and v widths, ray_tpu/ops/moe.balance_loss)")


def program_config(config: Dict[str, Any], cell: Dict[str, Any]):
    """The program's DeepseekV2Config for this configuration file and cell."""
    from ray_tpu.models import deepseek_v2

    if config["q_lora_rank"] is not None:
        raise SystemExit("q_lora_rank: the program's latent attention has no "
                         "query compression (the Lite model's null)")
    for key, only in (("scoring_func", "softmax"), ("topk_method", "greedy"),
                      ("norm_topk_prob", False), ("seq_aux", True),
                      ("moe_layer_freq", 1), ("hidden_act", "silu")):
        if config[key] != only:
            raise SystemExit(f"{key} = {config[key]!r}: the program's "
                             f"DeepSeek-V2 layer is {only!r}")
    yarn = config["rope_scaling"]
    return deepseek_v2.DeepseekV2Config(
        vocab_size=config["vocab_size"],
        seq_len=cell["seq_len"],
        n_layer=config["num_hidden_layers"],
        first_layer=config["first_layer"],
        first_k_dense=config["first_k_dense_replace"],
        d_model=config["hidden_size"],
        n_head=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        rope_factor=float(yarn["factor"]),
        rope_original_len=yarn["original_max_position_embeddings"],
        rope_beta_fast=float(yarn["beta_fast"]),
        rope_beta_slow=float(yarn["beta_slow"]),
        rope_mscale=yarn["mscale"],
        rope_mscale_all_dim=yarn["mscale_all_dim"],
        d_ff=config["intermediate_size"],
        n_experts=config["published"]["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        held_first=config["held_first_expert"],
        held_count=config["n_routed_experts"],
        d_expert=config["moe_intermediate_size"],
        n_shared=config["n_shared_experts"],
        routed_scaling=float(config["routed_scaling_factor"]),
        aux_loss_alpha=config["aux_loss_alpha"],
        init_std=config["initializer_range"],
        rms_eps=config["rms_norm_eps"],
        remat=cell["remat"],
    )


_expert_load: list = []     # build's model/expert_load events, for the summary


def _optimizer(cell: Dict[str, Any]):
    """(The CPU rehearsal's tiny sizes state a warm-up of their own.)"""
    from ray_tpu.train.train_step import default_optimizer

    return default_optimizer(lr=LR, warmup=cell.get("lr_warmup", WARMUP),
                             total_steps=TOTAL_STEPS)


def build(config: Dict[str, Any], cell: Dict[str, Any], mesh, seed: int):
    """The program's TrainStepBundle, through its one step factory (the
    weights drawn with the device's own bit generator, ``impl="rbg"``, as
    family nemotron_h draws its), its expert layers' routers balanced by
    ``deepseek_v2.balance_routers`` (rounds of gradient descent on each
    layer's own balance loss, the other weights held), each round on a batch
    of its own: the mix's rows from 0 on as the seed gives them — the
    window's and as many again past its end. This family has no selection
    bias — its balance loss is what balances a router, over a run's many
    steps —, and a router as drawn sends the fullest held expert several
    times the mean on the mix's 64 symbols (the configuration's ``assumed``
    (i)), so the run starts where training under that loss would have
    brought it. Every round on ONE batch fits that batch: the held experts
    then get 25 % of its pairs and, on the window's other batches, a seed's
    own 24.3 .. 25.3 % — the routed experts' time with it, and six seeds'
    rates spread 0.70 % (PERF.md section 6, PR 55, second round)."""
    import dataclasses

    import jax

    from benchmarks.harness import spec, traffic
    from ray_tpu.models import deepseek_v2
    from ray_tpu.ops import moe
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.train.train_step import make_train_step

    bundle = make_train_step(
        deepseek_v2, program_config(config, cell), mesh=mesh,
        optimizer=_optimizer(cell), rng=jax.random.key(seed, impl="rbg"))
    alphabet = spec.load_cell(cell["name"])[2]["alphabet"]
    batch = cell["per_chip_batch"] * cell["chips"]
    rows = traffic.host_batch(batch * moe.BALANCE_ROUNDS, seed,
                              cell["seq_len"], alphabet)["tokens"]
    batches = [jax.device_put(rows[i:i + batch], bundle.data_sharding)
               for i in range(0, len(rows), batch)]
    with mesh_lib.use_mesh(mesh):
        params, _expert_load[:] = deepseek_v2.balance_routers(
            bundle.state["params"], batches, bundle.cfg)
    return dataclasses.replace(bundle, state={**bundle.state, "params": params})


def shapes(config: Dict[str, Any], cell: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the arithmetic needs: harness/flops.py's keys (run.py reads
    them for every cell) and this family's own. From the files alone: the
    driver calls this and must not touch JAX."""
    _require_program()
    d, vocab, heads = (config["hidden_size"], config["vocab_size"],
                       config["num_attention_heads"])
    nope, rope, hv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    rank = config["kv_lora_rank"]
    f, fe = config["intermediate_size"], config["moe_intermediate_size"]
    held, experts = (config["n_routed_experts"],
                     config["published"]["n_routed_experts"])
    layers = config["num_hidden_layers"]
    dense_layers = sum(config["first_layer"] + i
                       < config["first_k_dense_replace"] for i in range(layers))
    expert_layers = layers - dense_layers
    # latent attention's parameters that sit in a matmul a token meets (W_q,
    # W_kva, W_kvb, W_o) and the rest (the latent's gain; a layer's two
    # pre-norms)
    attention = (d * heads * (nope + rope) + d * (rank + rope)
                 + rank * heads * (nope + hv) + heads * hv * d)
    shared = 3 * d * fe * config["n_shared_experts"]
    matmul = {"D": attention + 3 * d * f,
              "E": attention + d * experts + shared}
    other = rank + 2 * d
    routed = 3 * d * fe                                 # one routed expert
    params = (dense_layers * (matmul["D"] + other)
              + expert_layers * (matmul["E"] + other + held * routed)
              + 2 * vocab * d + d)
    return {
        "params": params,
        "matmul_params_per_kind": matmul,
        "routed_expert_params": routed,
        "expected_pairs_per_token": (config["num_experts_per_tok"] * held
                                     / experts),
        "expert_layers": expert_layers,
        "dense_layers": dense_layers,
        "attention_layers": layers,
        "held_experts": held,
        "d_expert": fe,
        "vocab": vocab,
        "n_layer": layers,
        "d_model": d,
        "n_head": heads,
        "head_dim": nope + rope,             # q's and k's
        "v_head_dim": hv,                    # v's and o's
        "seq_len": cell["seq_len"],
        "per_chip_batch": cell["per_chip_batch"],
        "chips": cell["chips"],
        "remat": cell["remat"],
        "attention_dtype_bytes": 2,          # bf16 q, k, v, o, do
    }


def train_flops_per_token(shapes: Dict[str, Any]) -> float:
    """Forward + backward operations one trained token REQUIRES here: 6 per
    matmul parameter the token meets — the routed experts by the pairs a
    token is expected to land on held ones (top_k · held / n_experts a
    layer), the shared expert and the untied head whole, the embedding a
    gather — and by shape three times the forward's attention (q·k at 192
    and p·v at 128 over the causal half). Recomputed operations do not count.
    ``deepseek_v2.flops_per_token`` is the program's count of the same (a
    tier-1 test holds the two together)."""
    d, s = shapes["d_model"], shapes["seq_len"]
    per_kind = shapes["matmul_params_per_kind"]
    matmul = (shapes["dense_layers"] * per_kind["D"]
              + shapes["expert_layers"] * per_kind["E"])
    matmul += (shapes["expert_layers"] * shapes["expected_pairs_per_token"]
               * shapes["routed_expert_params"])
    matmul += d * shapes["vocab"]
    attention = (shapes["n_head"] * (shapes["head_dim"] + shapes["v_head_dim"])
                 * (s + 1) / 2.0)
    return 6.0 * (matmul + shapes["attention_layers"] * attention)


def experts_call(shapes: Dict[str, Any]) -> Dict[str, float]:
    """Least operations and HBM bytes of the held experts' grouped products
    ONE step makes on one device, no recompute: a balanced layer lands
    tokens · top_k · held / n_experts pairs on the held experts, each through
    three products forward (x·W1, x·W3, a·W2) and six backward (each one's
    gradient to its input and to its weights). A product reads its rows and
    the held experts' weights and writes its rows, in bf16. The shared
    expert is a dense MLP: no grouped product, not counted here."""
    tokens = shapes["per_chip_batch"] * shapes["seq_len"]
    pairs = tokens * shapes["expected_pairs_per_token"]
    d, fe, held = shapes["d_model"], shapes["d_expert"], shapes["held_experts"]
    a = shapes["attention_dtype_bytes"]
    product = {"flops": 2.0 * pairs * d * fe,
               "bytes": a * (pairs * (d + fe) + held * d * fe)}
    return {k: 9.0 * shapes["expert_layers"] * v for k, v in product.items()}


def flash_attn_call(shapes: Dict[str, Any]) -> Dict[str, float]:
    """Least operations and HBM bytes of the layers' flash calls ONE step
    makes on one device, no recompute: a forward and a backward call a layer
    over the causal half, each product at ITS width — forward q·kᵀ at
    ``head_dim`` (192) and p·v at ``v_head_dim`` (128); backward q·kᵀ again,
    dq and dk at 192, dp = do·vᵀ and dv at 128 — S² multiply-adds a width
    and head each, halved by the mask. Bytes: forward reads q, k (192) and v
    (128), writes o (128) and the f32 log-sum-exp; backward reads q, k, v, o,
    do and lse and writes dq, dk, dv, each at its own width."""
    b, h, s = shapes["per_chip_batch"], shapes["n_head"], shapes["seq_len"]
    hd, hv, w = (shapes["head_dim"], shapes["v_head_dim"],
                 shapes["attention_dtype_bytes"])
    bhs = float(b * h * s)
    fwd = {"flops": bhs * s * (hd + hv),
           "bytes": bhs * w * (2 * hd + 2 * hv) + 4.0 * bhs}
    bwd = {"flops": bhs * s * (3 * hd + 2 * hv),
           "bytes": bhs * w * (4 * hd + 4 * hv) + 4.0 * bhs}
    return {k: shapes["attention_layers"] * (fwd[k] + bwd[k]) for k in fwd}


def attention_resolved(bundle) -> list:
    """What the program's one attention rule chose on this mesh."""
    from ray_tpu.ops.attention import resolve_attention

    return list(resolve_attention(bundle.cfg.attention_impl, bundle.mesh))


def reference_sizes(cfg, **switches) -> Dict[str, Any]:
    """The model and share description the reference takes, from the
    program's config. ``rope_pairing`` half: the program's rotary columns
    are stored de-interleaved (the configuration's ``assumed`` (b))."""
    return {"eps": cfg.rms_eps, "pattern": cfg.pattern,
            "nope": cfg.qk_nope_dim, "rope": cfg.qk_rope_dim,
            "rank": cfg.kv_lora_rank, "theta": cfg.rope_theta,
            "rope_factor": cfg.rope_factor,
            "rope_original_len": cfg.rope_original_len,
            "rope_beta_fast": cfg.rope_beta_fast,
            "rope_beta_slow": cfg.rope_beta_slow,
            "rope_mscale": cfg.rope_mscale,
            "rope_mscale_all_dim": cfg.rope_mscale_all_dim,
            "top_k": cfg.top_k, "scaling": cfg.routed_scaling,
            "alpha": cfg.aux_loss_alpha, "held_first": cfg.held_first,
            "rope_pairing": "half", **switches}


def readings(bundle, batch_host: Dict[str, Any], cell: Dict[str, Any],
             **control) -> Dict[str, Any]:
    """Loss and (``reference_grad``) each parameter tensor's gradient norm of
    the program and of the reference on the state's parameters as set-up
    left them and the cell's own first ``reference_rows`` rows, whole. The
    reference is given the sets the program's routers chose and reports on
    them (``routing``, the expert layers in their order). With ``control``
    (deepseek_v2_reference's switches) the reference so switched, routing by
    its own scores, stands where the program stands. One compiled program a
    side."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.families.minicpm_sala import grad_passes
    from ray_tpu.models import deepseek_v2
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

    def program(p, tokens, targets, _):
        with mesh_lib.use_mesh(mesh):
            return deepseek_v2.loss_fn(p, tokens, targets, cfg), ()

    def program_sets(p, tokens):
        """What the program's routers chose, a forward of its own."""
        with mesh_lib.use_mesh(mesh):
            return [s.reshape(shape)
                    for s in deepseek_v2.chosen_experts(p, tokens, cfg)]

    def reference_with(given, **switches):
        sizes = reference_sizes(cfg, **switches)

        def reference(p, tokens, targets, sets):
            with jax.default_matmul_precision("highest"):
                loss, reports, _ = deepseek_v2_reference.loss_and_routing(
                    p, tokens, targets, sizes, sets if given else None)
            return loss, reports

        return reference

    def side(loss_of, sets, passes=1):
        """(loss, each tensor's gradient norm, what ``loss_of`` gives beside
        its loss) of one side. With ``passes`` > 1 the gradient is made a
        part of the parameter tensors at a time (``grad_passes``: parts of
        about equal bytes), the others held: a part's gradient is made, and
        stands on the chip, a pass — the float32 reference's whole (two
        copies of 3.25 GB inside the loop over the rows beside a row's
        float32 activations) does not fit beside the step's state."""
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

    if control:
        # the switched reference routes by its own scores: its sets are its
        # reports' ``own``
        prog, reports = side(reference_with(False, **control), None,
                             cell.get("reference_grad_passes", 1))
        sets = [r["own"] for r in reports]
    else:
        prog, _ = side(program, None)
        sets = jax.jit(program_sets, in_shardings=(param_sh, data_sh))(
            params, sub["tokens"])
    ref, reports = side(reference_with(True), sets,
                        cell.get("reference_grad_passes", 1))
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
    ``pairs_dropped``). With ``control`` (deepseek_v2_reference's switches:
    ``stats_dtype`` for bf16 where the configuration says float32) the
    reference so switched stands where the program stands — the reading a
    limit must refuse. Returns the numbers; judges nothing."""
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

    from ray_tpu.models import deepseek_v2
    from ray_tpu.train.train_step import _compose_step, _resident_bytes

    cfg = program_config(config, cell)
    optimizer = _optimizer(cell)
    step_given, state_sh, batch_sh = _compose_step(
        deepseek_v2, cfg, mesh, optimizer, None)
    params = jax.eval_shape(
        lambda: deepseek_v2.init(cfg, jax.random.PRNGKey(0)))
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
