"""Family ``xing4``: from a configuration file to the program's train step.

Xing4.0 is the program's latent-attention model (``ray_tpu/models/
deepseek_v2.py``: the DeepSeek-V2 family's layer, its second caller) with what
its config sets beside DeepSeek-V2-Lite's: query compression (``q_lora_rank``),
a biased-sigmoid router whose chosen scores are normalised and scaled
(``noaux_tc``: ``ops/moe.Rule()`` as the Nemotron and LFM2 routers have it),
one shared expert, no balance loss, one multi-token-prediction module, and
every sublayer inside a four-stream manifold-constrained hyper-connection
(``ray_tpu/models/hyper_connections.py``). As for the other families the
benchmark hands the program the published sizes, the chip's share of the
deployment and what the cell's file states (per-chip batch, row length,
``remat``, mesh) and NOTHING else: how the pattern is scanned, the held
experts' row buffer, what remat keeps, tiles, the stream's layout and dtype
stay at the program's defaults. ``build`` also has the program balance its
selection biases on the seed's first rows, once.

Everything ``benchmarks/README.md`` asks of a family is here — ``build``,
``shapes``, ``attention_resolved``, ``reference_check``, ``abstract_step`` —
and, as the families before it brought:

- ``train_flops_per_token(shapes)``: this family's own count
  (``dsv2_mfu_device`` reads it);
- ``experts_call(shapes)``: least operations and HBM bytes of the held
  experts' grouped products ONE step makes (``dsv2_experts_roofline``);
- ``flash_attn_call(shapes)``: the same of the six layers' flash calls, q·k
  at 192 and p·v at 128 (``mla_flash_attn_roofline``);
- ``mhc_call(shapes)``: the same of the hyper-connections' stream traffic
  (``mhc_stream_roofline``).

No name of ``ray_tpu`` is imported at module level: a checkout whose program
lacks hyper-connections (the parent of PR 57) imports this file, is told so
by ``shapes`` — which the driver calls before it starts a cluster — and exits.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.families import xing4_reference

# AdamW as the program's default_optimizer builds it, on the schedule the
# Nemotron and LFM2 families run their biased-sigmoid expert layers under (a
# linear warm-up from 0 to 2.2e-4: DeepSeek-V3 technical report,
# arXiv:2412.19437, section 4.2, the published schedule of the model this
# config's shape comes from) with the warm-up STRETCHED tenfold, to 20,000
# steps: a 20 s window is that run's first ~40 steps at rates up to 4e-7. Why
# (the configuration's ``assumed`` (i), the LFM2 configuration's (h)): the
# selection bias's between-step update is not part of the step, and on one
# chip of an EP group a router sees the gradient of the experts held HERE
# alone, so it learns to prefer them; a deployment's bias update holds the
# balance the stretched warm-up merely does not disturb. The step's program
# is the same. It does not depend on --seconds.
LR, WARMUP, TOTAL_STEPS = 2.2e-4, 20_000, 100_000

# memory_stats()["bytes_limit"] of a v5e chip (PERF.md §6): what the remat
# rule is told when the step is compiled for a chip that is described
V5E_BYTES_LIMIT = 16_909_334_528

# Program (bf16 stream — all four — and matmul operands; f32 accumulation,
# the maps' statistic, sigmoids and Sinkhorn rounds, both mixes' sums, router,
# norms' statistics, attention's softmax, logits and the loss; the compiled
# flash and grouped kernels) against the float32 reference on the same
# weights and the window's own first batch, whole (1 row of 8,192): the loss
# (CE_trunk + 0.1 · CE_mtp), and the gradient tensor by tensor
# (``grad_error``, as family nemotron_h compares it: harness/checks.py
# compares two numbers under the name ``grad_norm`` by one rtol; this family
# gives it the reference's summed tensor norms S and, for the program, S · (1
# + grad_error), so GRAD_NORM_RTOL is the limit of grad_error). The reference
# is GIVEN the sets the program's routers chose (its file says why) and
# reports how far below its own last chosen biased score a given-but-not-own
# expert lies at worst: past ROUTE_MARGIN the program's choice is not the
# reference's rule, and 1.0 is added to grad_error, which no rtol passes.
#
# The readings on the chip (PERF.md §6, PR 57; loss / grad_error / margin):
# the program, twelve seeds, 7e-6 .. 1.48e-4 (mean 7.3e-5) / 9.1e-4 ..
# 2.25e-3 (mean 1.7e-3, standard deviation 4e-4; its median tensor 8e-4) /
# 1.12e-2 on the one seed that printed it — 4.9 .. 7.8 % of the tokens choose
# another set than the float32 reference, more the deeper the layer, and
# GIVEN those sets the gradients agree to 2e-3. The reference with its
# forward matmuls' operands in float8_e4m3 (one scale a tensor; the precision
# below the bf16 the configuration states for operands), routing by its own
# scores, one seed: 6.6e-4 / 4.14e-3 / 0.172 (47 .. 63 % of the tokens choose
# another set): refused by each of the three limits alone. So: the loss's
# limit stands 2.0x over the worst of twelve and 2.2x under float8's (the
# accepted expert cells' 1.7e-4, which every run of this PR was also judged
# by and passed, would stand 1.15x over that worst: too little for the
# driver's fresh seeds; here the loss DOES see the operands' precision, so
# the limit is this cell's own, between its two readings); the gradient's
# 1.42x over the worst seen (+3.7 standard deviations) and 1.29x under
# float8's; the margin's (the LFM2 cell's) 3.6x over and 4.3x under.
#
# What ISSUE 57 asked for beside it — the maps and the Sinkhorn rounds in
# bf16 (``maps_dtype``: the Phi product's result, the sigmoids, every round,
# by ``lax.reduce_precision``), everything else float32 — NO limit the
# program passes can refuse: it reads 4.2e-6 / 1.17e-3 / 2.4e-3, nearer the
# float32 reference than the bf16 program, because the program's error is
# its bf16 STREAM's, which the maps' precision does not touch (the DeepSeek
# cell's statistics control, for its reason; PERF.md §7).
LOSS_RTOL = 3e-4
GRAD_NORM_RTOL = 3.2e-3
ROUTE_MARGIN = 4e-2


def _require_program() -> None:
    """A checkout whose program has no hyper-connection (the parent of
    PR 57) cannot run this family: say so before a cluster is started."""
    import importlib.util

    if importlib.util.find_spec("ray_tpu.models.hyper_connections") is None:
        raise SystemExit(
            "benchmarks/families/xing4.py: this checkout cannot run a cell "
            "of family xing4: its program has no hyper-connected residual "
            "path (ray_tpu/models/hyper_connections.py; query compression, "
            "a configured router rule and an MTP module in "
            "ray_tpu/models/deepseek_v2.py)")


def program_config(config: Dict[str, Any], cell: Dict[str, Any]):
    """The program's DeepseekV2Config for this configuration file and cell."""
    from ray_tpu.models import deepseek_v2

    for key, only in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("norm_topk_prob", True), ("n_group", 1),
                      ("topk_group", 1), ("moe_layer_freq", 1),
                      ("hidden_act", "silu"), ("attention_bias", False),
                      ("tie_word_embeddings", False)):
        if config[key] != only:
            raise SystemExit(f"{key} = {config[key]!r}: the program's "
                             f"Xing4.0 layer is {only!r}")
    if -config["mhc_h_res_clamp_min"] != config["mhc_h_res_clamp_max"]:
        raise SystemExit("the program clips H_res symmetrically")
    yarn = config["rope_scaling"]
    return deepseek_v2.DeepseekV2Config(
        vocab_size=config["vocab_size"],
        seq_len=cell["seq_len"],
        n_layer=config["num_hidden_layers"],
        first_layer=config["first_layer"],
        first_k_dense=config["first_k_dense_replace"],
        n_layer_published=config["published"]["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_head=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        rope_factor=float(yarn["factor"]),
        rope_original_len=yarn["original_max_position_embeddings"],
        rope_beta_fast=float(yarn["beta_fast"]),
        rope_beta_slow=float(yarn["beta_slow"]),
        rope_mscale=float(yarn["mscale"]),
        rope_mscale_all_dim=float(yarn["mscale_all_dim"]),
        d_ff=config["intermediate_size"],
        n_experts=config["published"]["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        held_first=config["held_first_expert"],
        held_count=config["n_routed_experts"],
        d_expert=config["moe_intermediate_size"],
        n_shared=config["n_shared_experts"],
        routed_scaling=float(config["routed_scaling_factor"]),
        scoring=config["scoring_func"],
        norm_topk_prob=config["norm_topk_prob"],
        selection_bias=True,
        aux_loss_alpha=0.0,
        hc_mult=config["hc_mult"],
        hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"],
        hc_res_clamp=float(config["mhc_h_res_clamp_max"]),
        mtp_layers=config["num_nextn_predict_layers"],
        mtp_loss_weight=config["mtp_loss_weight"],
        init_std=config["initializer_range"],
        rms_eps=config["rms_norm_eps"],
        remat=cell["remat"],
    )


_expert_load: list = []     # build's model/expert_load events, for the summary


def _optimizer(cell: Dict[str, Any]):
    """(The CPU rehearsal's tiny sizes state a warm-up of their own.)"""
    from ray_tpu.models import deepseek_v2
    from ray_tpu.train.train_step import default_optimizer

    return default_optimizer(lr=LR, warmup=cell.get("lr_warmup", WARMUP),
                             total_steps=TOTAL_STEPS,
                             decay_mask=deepseek_v2.decays)


def build(config: Dict[str, Any], cell: Dict[str, Any], mesh, seed: int):
    """The program's TrainStepBundle, through its one step factory (the
    weights drawn with the device's own bit generator, ``impl="rbg"``, as
    family nemotron_h draws its), its expert layers' selection biases — the
    trunk's four and the MTP module's — balanced by
    ``deepseek_v2.balance_router_bias``, each of its 64 rounds on a batch of
    its own: the mix's rows from 0 on as the seed gives them — the window's
    and as many again past its end. The bias's between-step update is not
    part of the step (the configuration's ``assumed`` (h)), so the run starts
    where a deployment's update would have brought it. Every round on ONE
    batch (4 rows; my first chip runs, PR 57) fits that batch's near-ties: a
    layer's routing over the mix's 64 symbols moves an expert's load by a
    symbol's ~128 tokens at a time, the held eight then stood up to a
    quarter over the mean on every other batch, and 18 % of a window's steps
    took a second pass (PERF.md section 6)."""
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
                              cell["seq_len"], alphabet)
    batches = [jax.device_put({k: v[i:i + batch] for k, v in rows.items()},
                              bundle.data_sharding)
               for i in range(0, batch * moe.BALANCE_ROUNDS, batch)]
    with mesh_lib.use_mesh(mesh):
        params, _expert_load[:] = deepseek_v2.balance_router_bias(
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
    rank, q_rank = config["kv_lora_rank"], config["q_lora_rank"]
    f, fe = config["intermediate_size"], config["moe_intermediate_size"]
    held, experts = (config["n_routed_experts"],
                     config["published"]["n_routed_experts"])
    layers, mtp_layers = (config["num_hidden_layers"],
                          config["num_nextn_predict_layers"])
    n = config["hc_mult"]
    maps = n * n + 2 * n
    dense_layers = sum(config["first_layer"] + i
                       < config["first_k_dense_replace"] for i in range(layers))
    expert_layers = layers - dense_layers + mtp_layers
    # latent attention's parameters that sit in a matmul a token meets (W_qa,
    # W_qb, W_kva, W_kvb, W_o), the two hyper-connections' Φ a layer, and the
    # rest (the two latents' gains, a layer's two pre-norms, each
    # hyper-connection's three α and its biases; the selection bias is a
    # buffer and no parameter)
    attention = (q_rank * (d + heads * (nope + rope)) + d * (rank + rope)
                 + rank * heads * (nope + hv) + heads * hv * d)
    phis = 2 * n * d * maps
    shared = 3 * d * fe * config["n_shared_experts"]
    matmul = {"D": attention + phis + 3 * d * f,
              "E": attention + phis + d * experts + shared}
    other = q_rank + rank + 2 * d + 2 * (3 + maps)
    routed = 3 * d * fe                                 # one routed expert
    params = (dense_layers * (matmul["D"] + other)
              + expert_layers * (matmul["E"] + other + held * routed)
              + 2 * vocab * d + d
              + (2 * d * d + 3 * d if mtp_layers else 0))
    return {
        "params": params,
        "matmul_params_per_kind": matmul,
        "routed_expert_params": routed,
        "expected_pairs_per_token": (config["num_experts_per_tok"] * held
                                     / experts),
        "expert_layers": expert_layers,
        "dense_layers": dense_layers,
        "attention_layers": layers + mtp_layers,
        "mtp_layers": mtp_layers,
        "held_experts": held,
        "d_expert": fe,
        "vocab": vocab,
        "n_layer": layers + mtp_layers,
        "d_model": d,
        "hc_mult": n,
        "n_head": heads,
        "head_dim": nope + rope,             # q's and k's
        "v_head_dim": hv,                    # v's and o's
        "seq_len": cell["seq_len"],
        "per_chip_batch": cell["per_chip_batch"],
        "chips": cell["chips"],
        "remat": cell["remat"],
        "attention_dtype_bytes": 2,          # bf16 q, k, v, o, do; the stream
    }


def train_flops_per_token(shapes: Dict[str, Any]) -> float:
    """Forward + backward operations one trained token REQUIRES here: 6 per
    matmul parameter the token meets — the routed experts by the pairs a
    token is expected to land on held ones (top_k · held / n_experts a
    layer), the shared expert, the hyper-connections' two Φ a layer (6 x 0.69
    M), the MTP module's layer, its join (2·d·d) and its own pass through the
    untied head, the embedding a gather — and by shape three times the
    forward's attention (q·k at 192 and p·v at 128 over the causal half). The
    hyper-connections' mixes and Sinkhorn rounds are elementwise: nothing.
    Recomputed operations do not count. ``deepseek_v2.flops_per_token`` is
    the program's count of the same (a tier-1 test holds the two together)."""
    d, s = shapes["d_model"], shapes["seq_len"]
    per_kind = shapes["matmul_params_per_kind"]
    matmul = (shapes["dense_layers"] * per_kind["D"]
              + shapes["expert_layers"] * per_kind["E"])
    matmul += (shapes["expert_layers"] * shapes["expected_pairs_per_token"]
               * shapes["routed_expert_params"])
    matmul += d * shapes["vocab"]
    if shapes["mtp_layers"]:
        matmul += 2 * d * d + d * shapes["vocab"]
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
    (the MTP module's too) over the causal half, each product at ITS width —
    forward q·kᵀ at ``head_dim`` (192) and p·v at ``v_head_dim`` (128);
    backward q·kᵀ again, dq and dk at 192, dp = do·vᵀ and dv at 128 — S²
    multiply-adds a width and head each, halved by the mask. Bytes: forward
    reads q, k (192) and v (128), writes o (128) and the f32 log-sum-exp;
    backward reads q, k, v, o, do and lse and writes dq, dk, dv, each at its
    own width."""
    b, h, s = shapes["per_chip_batch"], shapes["n_head"], shapes["seq_len"]
    hd, hv, w = (shapes["head_dim"], shapes["v_head_dim"],
                 shapes["attention_dtype_bytes"])
    bhs = float(b * h * s)
    fwd = {"flops": bhs * s * (hd + hv),
           "bytes": bhs * w * (2 * hd + 2 * hv) + 4.0 * bhs}
    bwd = {"flops": bhs * s * (3 * hd + 2 * hv),
           "bytes": bhs * w * (4 * hd + 4 * hv) + 4.0 * bhs}
    return {k: shapes["attention_layers"] * (fwd[k] + bwd[k]) for k in fwd}


def mhc_call(shapes: Dict[str, Any]) -> Dict[str, float]:
    """Least operations and HBM bytes of the hyper-connections' stream
    traffic ONE step makes on one device, no recompute: two sublayers a layer
    (the MTP module's too), and a sublayer passes over its tokens' n-stream
    carry (n · d numbers a token, in the stream's bf16) EIGHT times — forward:
    the maps and the pre-mix read x once (a token's streams stay on the chip
    between the Φ product and the mix); the write-back reads x and writes x';
    backward: the sublayer's output's cotangent ``Σ H_post[i] · dx'[i]`` and
    the maps' cotangents read dx' and x before the sublayer's own backward
    can run; after it ``dx = H_resᵀ · dx' + H_pre ⊗ du + Φ's part`` reads dx'
    and x again and writes dx — and moves the sublayer's own d-wide tensors
    once each (u and du in bf16, y and dy in float32: 12 bytes a channel).
    The maps themselves (24 + 16 numbers a token) are not counted. Operations:
    the Φ product forward and its two backward products, 2 · n·d · (n² + 2n)
    each; the mixes' multiply-adds are counted (2·n·d the pre-mix, 2·n·(n +
    1)·d the write-back, each again twice backward) though no matrix unit
    runs them."""
    tokens = shapes["per_chip_batch"] * shapes["seq_len"]
    n, d = shapes["hc_mult"], shapes["d_model"]
    a = shapes["attention_dtype_bytes"]
    sublayers = 2 * shapes["attention_layers"]
    per_token = {
        "bytes": 8.0 * n * d * a + 12.0 * d,
        "flops": 3.0 * (2.0 * n * d * (n * n + 2 * n)
                        + 2.0 * n * d + 2.0 * n * (n + 1) * d)}
    return {k: sublayers * tokens * v for k, v in per_token.items()}


def attention_resolved(bundle) -> list:
    """What the program's one attention rule chose on this mesh."""
    from ray_tpu.ops.attention import resolve_attention

    return list(resolve_attention(bundle.cfg.attention_impl, bundle.mesh))


def reference_sizes(cfg, **switches) -> Dict[str, Any]:
    """The model and share description the reference takes, from the
    program's config."""
    return {"eps": cfg.rms_eps, "pattern": cfg.pattern,
            "mtp_pattern": cfg.mtp_pattern, "mtp_weight": cfg.mtp_loss_weight,
            "nope": cfg.qk_nope_dim, "rope": cfg.qk_rope_dim,
            "rank": cfg.kv_lora_rank, "theta": cfg.rope_theta,
            "rope_factor": cfg.rope_factor,
            "rope_original_len": cfg.rope_original_len,
            "rope_beta_fast": cfg.rope_beta_fast,
            "rope_beta_slow": cfg.rope_beta_slow,
            "rope_mscale": cfg.rope_mscale,
            "rope_mscale_all_dim": cfg.rope_mscale_all_dim,
            "top_k": cfg.top_k, "scaling": cfg.routed_scaling,
            "held_first": cfg.held_first, "hc_mult": cfg.hc_mult,
            "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters, "hc_eps": cfg.hc_eps,
            "hc_clamp_min": -cfg.hc_res_clamp, "hc_clamp_max": cfg.hc_res_clamp,
            **switches}


def readings(bundle, batch_host: Dict[str, Any], cell: Dict[str, Any],
             **control) -> Dict[str, Any]:
    """Loss and (``reference_grad``) each parameter tensor's gradient norm of
    the program and of the reference on the state's parameters as set-up
    left them and the cell's own first ``reference_rows`` rows, whole. The
    reference is given the sets the program's routers chose and reports on
    them (``routing``, the expert layers in their order, the MTP module's
    last). With ``control`` (xing4_reference's switches) the reference so
    switched, routing by its own scores, stands where the program stands.
    One compiled program a side; the reference's gradient a part of the
    parameter tensors at a time (``reference_grad_passes``)."""
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

    def program_sets(p, tokens, targets):
        """What the program's routers chose, a forward of its own."""
        with mesh_lib.use_mesh(mesh):
            return [s.reshape(shape) for s in
                    deepseek_v2.chosen_experts(p, tokens, cfg, targets)]

    def reference_with(given, **switches):
        sizes = reference_sizes(cfg, **switches)

        def reference(p, tokens, targets, sets):
            with jax.default_matmul_precision("highest"):
                loss, reports, _ = xing4_reference.loss_and_routing(
                    p, tokens, targets, sizes, sets if given else None)
            return loss, reports

        return reference

    def side(loss_of, sets, passes=1):
        """(loss, each tensor's gradient norm, what ``loss_of`` gives beside
        its loss) of one side; with ``passes`` > 1 the gradient is made a
        part of the parameter tensors at a time, the others held."""
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

    passes = cell.get("reference_grad_passes", 1)
    if control:
        # the switched reference routes by its own scores: its sets are its
        # reports' ``own``
        prog, reports = side(reference_with(False, **control), None, passes)
        sets = [r["own"] for r in reports]
    else:
        prog, _ = side(program, None)
        sets = jax.jit(program_sets, in_shardings=(param_sh, data_sh, data_sh))(
            params, sub["tokens"], sub["targets"])
    ref, reports = side(reference_with(True), sets, passes)
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
    the routing's margin as the limits' comment says), and what set-up's rows
    send the experts held here (the program's ``model/expert_load`` events).
    With ``control`` (xing4_reference's switches: ``operand_dtype``,
    ``maps_dtype``) the reference so switched stands where the program
    stands — the reading a limit must refuse. Returns the numbers; judges
    nothing. The selection biases are buffers: no gradient reaches one on
    either side, and ``grad_error`` leaves such tensors out."""
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
