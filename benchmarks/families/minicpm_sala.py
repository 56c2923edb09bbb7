"""Family ``minicpm_sala``: from a configuration file to the program's train step.

MiniCPM-SALA is the program's linear / block-sparse attention hybrid
(``ray_tpu/models/minicpm_sala.py``): a pattern of lightning (decayed linear
attention, on the state-space scan's kernels) and minicpm4 (block top-k
sparse attention) layers, each with a SwiGLU MLP, under MiniCPM's µP
scalings. As for the other families the benchmark hands the program the
published sizes, the chip's share of the deployment and what the cell's file
states (per-chip batch, row length, ``remat``, mesh) and NOTHING else: tiles,
the scan's chunk, what remat keeps, the rows the MLP and the head take at a
time stay at the program's defaults.

Everything ``benchmarks/README.md`` asks of a family is here — ``build``,
``shapes``, ``attention_resolved``, ``reference_check``, ``abstract_step`` —
and, as the families before it brought (``harness/flops.py`` is GPT-2's
arithmetic and no file that is there may be edited):

- ``train_flops_per_token(shapes)``: this family's own count
  (``sala_mfu_device`` reads it). ``run.py``'s human line "end-to-end MFU" is
  GPT-2's 6·params + 12·L·S·d: it counts attention over every key in every
  layer and is wrong here;
- ``ssd_scan_call(shapes)`` / ``sparse_attn_call(shapes)``: least
  operations and HBM bytes of the lightning layers' scans — the state-space
  scan's kernels, under its scope: the accepted ``ssd_scan_roofline`` reads
  them by the name family nemotron_h gave — and of the sparse layers'
  attention kernels ONE step makes (``sparse_attn_roofline``).

No name of ``ray_tpu`` is imported at module level: a checkout whose program
lacks this family (the parent of PR 47) imports this file, is told so by
``shapes`` — which the driver calls before it starts a cluster — and exits.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.families import minicpm_sala_reference

# AdamW as the program's default_optimizer builds it, with a schedule that
# does not depend on --seconds (family gpt2's and evabyte's, so the optimizer
# is the same code at the same settings).
LR, WARMUP, TOTAL_STEPS = 6e-4, 4, 10_000

# memory_stats()["bytes_limit"] of a v5e chip (PERF.md §6): what the remat
# rule is told when the step is compiled for a chip that is described
V5E_BYTES_LIMIT = 16_909_334_528

# Program (bf16 stream and matmul operands; f32 accumulation, QK-norm
# statistics, decays, state, selection scores, softmax, residual add and
# logits; the compiled scan and sparse-attention kernels) against the float32
# reference on the same weights and the cell's own first row: the loss on all
# 16,384 tokens, the gradient tensor by tensor (``grad_error``, as family
# nemotron_h compares it: harness/checks.py compares two numbers under the
# name ``grad_norm`` by one rtol; this family gives it the reference's summed
# tensor norms S and, for the program, S · (1 + grad_error), so
# GRAD_NORM_RTOL is the limit of grad_error) on the first
# ``reference_grad_tokens``. The reference attends over the blocks the
# PROGRAM chose (its file says why) and reports how far below its own last
# chosen score a block lies that the program chose and it did not, as a share
# of that score: past SELECT_MARGIN the program's selection is not the
# reference's rule, and 1.0 is added to grad_error (to the loss's error
# where no gradient is compared), which no rtol passes.
#
# The gradient's loss leaves out the row's first ``reference_grad_skip``
# targets (64). At a row's FIRST position a lightning head's output is one
# term, (q0·k0/√128) v0, and the per-head output norm keeps that term's
# direction alone: q0 and k0 get a gradient through the norm's eps only — 0
# for almost every head, ~1/√eps = 1,000 × a cotangent for a head whose
# |q0·k0|/√128 is under 1e-3, a width no bf16 product resolves (its error
# there is 1.6e-3). With that position in, 3 of 62 seeds on the chip read the
# lightning layers' wq / wk norms −10 %, −1.3 % and +34 % off (grad_error
# 2.2e-3, 4.1e-3, 1.41e-2 where the other 59 read 4.07e-3 .. 4.42e-3) — in
# layer 0, in the loss of positions 0–63, 31 × the reference there and clean
# everywhere else (PERF.md §6; on the CPU at 2,048 tokens +115 %, found at
# position 0 by bisection, gone with that one target masked; tier-1 holds
# the structure). 64 positions are four decay lengths of the slowest held
# head: what position 0 leaves in the next positions' states is gone by then.
#
# The readings on the chip (PERF.md §6, PR 47; loss / grad_error / margin):
# the program, 30 seeds with the 64 targets out — the three seeds above among
# them — 0 .. 2.0e-6 / 5.30e-3 .. 5.65e-3 / 1.7e-3 .. 2.5e-3 (99.7 % of its
# choices the reference's own). The reference with its forward matmuls'
# operands in float8_e4m3 (one scale a tensor; the precision below the bf16
# the configuration states), two seeds: 1.5e-6, 1.3e-5 / 1.6e-3, 1.9e-3 /
# **1.07e-2, 1.24e-2**: it fails the margin's limit, and that limit alone —
# its grad_error is BELOW the program's, because 2 x 2.6e-3 of the program's
# is one coherent rounding and no noise: at the initial weights every
# target's d logit is the same number, −(1 − 1/9,216)/12,223, which the
# chunked head's two gradient products (ops/cross_entropy.chunked_head_xent:
# float32 d logits into a bf16 product) round to bf16 the same way for every
# token, 171.55 units in the last place to 172: +0.26 % on every tensor
# (+0.20 % at 12,287 targets). Measured: with 1/count applied AFTER the two
# products every tensor's +0.19 .. +0.23 % falls to 0.00 .. +0.04 % and the
# program reads 6.4e-4 where it read 4.41e-3 (seed 2718281901, all 12,287
# targets). That op is the EvaByte and Nemotron cells' too and is not this
# PR's to change (PERF.md §7). With the selection rule wrong (a block's score
# its first compressed key's, not the max) the margin reads 0.17, 0.20. So:
# the loss's limit is the Nemotron cell's (85x the worst seen), the
# gradient's stands 1.15x over the worst seen — 5.2e-3 of it the head's
# rounding, the rest spread over 3.5e-4 on 30 seeds — and sees no precision
# until that rounding is repaired; the margin's stands 2.2x over the worst
# seen and 1.9x under float8's lowest, and is the one limit that refuses a
# lower precision (tier-1 sends the float8 control through reference_check
# and harness/checks.failures).
LOSS_RTOL = 1.7e-4
GRAD_NORM_RTOL = 6.5e-3
SELECT_MARGIN = 5.5e-3


def _require_program() -> None:
    """A checkout whose program has no MiniCPM-SALA model (the parent of
    PR 47) cannot run this family: say so before a cluster is started."""
    import importlib.util

    if importlib.util.find_spec("ray_tpu.models.minicpm_sala") is None:
        raise SystemExit(
            "benchmarks/families/minicpm_sala.py: this checkout cannot run a "
            "cell of family minicpm_sala: its program has no lightning / "
            "block-sparse attention model (ray_tpu/models/minicpm_sala.py, "
            "ray_tpu/ops/sparse_attention.py)")


def _pattern(config: Dict[str, Any]) -> str:
    kinds = {"lightning-attn": "L", "minicpm4": "S"}
    mixers = config["mixer_types"]
    if len(mixers) != config["num_hidden_layers"]:
        raise SystemExit(f"mixer_types has {len(mixers)} entries, not "
                         f"num_hidden_layers={config['num_hidden_layers']}")
    return "".join(kinds[m] for m in mixers)


def _sparse_sizes(config: Dict[str, Any]) -> Dict[str, int]:
    z = config["sparse_config"]
    return {"block": z["block_size"], "kernel": z["kernel_size"],
            "stride": z["kernel_stride"], "top_k": z["topk"],
            "init_blocks": z["init_blocks"], "window": z["window_size"],
            "dense_len": z["dense_len"]}


def program_config(config: Dict[str, Any], cell: Dict[str, Any]):
    """The program's MiniCPMSALAConfig for this configuration file and cell."""
    from ray_tpu.models import minicpm_sala
    from ray_tpu.ops.sparse_attention import SparseSizes

    if config["lightning_nkv"] != config["lightning_nh"]:
        raise SystemExit("the lightning mixer has no grouped heads: "
                         "lightning_nkv must equal lightning_nh")
    return minicpm_sala.MiniCPMSALAConfig(
        vocab_size=config["vocab_size"],
        seq_len=cell["seq_len"],
        pattern=_pattern(config),
        n_layer_published=config["published"]["num_hidden_layers"],
        d_model=config["hidden_size"],
        d_ff=config["intermediate_size"],
        head_dim=config["head_dim"],
        lightning_heads=config["lightning_nh"],
        lightning_head_first=config["lightning_head_first"],
        lightning_heads_published=config["published"]["lightning_nh"],
        rope_theta=float(config["rope_theta"]),
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        sparse=SparseSizes(**_sparse_sizes(config)),
        scale_emb=float(config["scale_emb"]),
        scale_depth=float(config["scale_depth"]),
        dim_model_base=config["dim_model_base"],
        rms_eps=config["rms_norm_eps"],
        remat=cell["remat"],
    )


def _optimizer():
    from ray_tpu.train.train_step import default_optimizer

    return default_optimizer(lr=LR, warmup=WARMUP, total_steps=TOTAL_STEPS)


def build(config: Dict[str, Any], cell: Dict[str, Any], mesh, seed: int):
    """The program's TrainStepBundle, through its one step factory (the
    weights drawn with the device's own bit generator, ``impl="rbg"``, as
    family nemotron_h draws its: threefry over a billion numbers compiles and
    runs for tens of seconds on the chip)."""
    import jax

    from ray_tpu.models import minicpm_sala
    from ray_tpu.train.train_step import make_train_step

    return make_train_step(
        minicpm_sala, program_config(config, cell), mesh=mesh,
        optimizer=_optimizer(), rng=jax.random.key(seed, impl="rbg"))


def _padded(vocab: int) -> int:
    return -(-vocab // 128) * 128


def shapes(config: Dict[str, Any], cell: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the arithmetic needs: harness/flops.py's keys (run.py reads
    them for every cell) and this family's own. From the files alone: the
    driver calls this and must not touch JAX."""
    _require_program()
    d, f, hd = config["hidden_size"], config["intermediate_size"], config["head_dim"]
    lh = config["lightning_nh"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    vocab = _padded(config["vocab_size"])
    pattern = _pattern(config)
    # a layer's parameters that sit in a matmul a token meets, and the rest
    # (the two pre-norms, the QK-norm gains, the lightning output norm's)
    matmul = {"L": 5 * d * lh * hd + 3 * d * f,
              "S": d * hd * (3 * heads + 2 * kv) + 3 * d * f}
    other = {"L": 2 * d + 2 * hd + lh * hd, "S": 2 * d + 2 * hd}
    params = (sum(matmul[k] + other[k] for k in pattern) + 2 * vocab * d + d)
    return {
        "params": params,
        "matmul_params_per_kind": matmul,
        "vocab": vocab,
        "n_layer": len(pattern),
        "pattern": pattern,
        "d_model": d,
        "n_head": heads,
        "n_kv_head": kv,
        "head_dim": hd,
        "lightning_heads": lh,
        "chunk": 128,                 # the program's default scan chunk
        "sparse": _sparse_sizes(config),
        "seq_len": cell["seq_len"],
        "per_chip_batch": cell["per_chip_batch"],
        "chips": cell["chips"],
        "remat": cell["remat"],
        "attention_dtype_bytes": 2,                    # bf16 q, k, v, o, do
    }


def _scan_macs_per_token(shapes: Dict[str, Any]) -> float:
    """Multiply-adds a token of ONE lightning layer's scan, forward: one head
    a group, P = N = hd — Q/2·2·H·hd inside its chunk (q·kᵀ and the decayed
    product with v, the causal half) and 2·H·hd² with the state."""
    h, hd = shapes["lightning_heads"], shapes["head_dim"]
    q = min(shapes["chunk"], shapes["seq_len"])
    return q / 2.0 * 2 * h * hd + 2.0 * h * hd * hd


def _given_pairs(shapes: Dict[str, Any]) -> float:
    """(token, key) pairs of one row a query head attends over: token t sees
    t + 1 keys and is GIVEN at most top_k blocks of them (all of them on the
    dense branch)."""
    s, z = shapes["seq_len"], shapes["sparse"]
    given = min(z["top_k"] * z["block"], s) if s > z["dense_len"] else s
    return given * (given + 1) / 2.0 + (s - given) * given


def train_flops_per_token(shapes: Dict[str, Any]) -> float:
    """Forward + backward operations one trained token REQUIRES here: 6 per
    matmul parameter the token meets (the embedding is a gather) and by shape
    three times the forward's mixers — the lightning scan, the attention's
    two products over the keys a token is GIVEN — and, forward only, the
    sparse layer's compressed-key scores over the causal half. Recomputed
    operations do not count. ``minicpm_sala.flops_per_token`` is the
    program's count of the same (a tier-1 test holds the two together)."""
    d, s, z = shapes["d_model"], shapes["seq_len"], shapes["sparse"]
    heads, hd = shapes["n_head"], shapes["head_dim"]
    pattern = shapes["pattern"]
    matmul = (sum(shapes["matmul_params_per_kind"][k] for k in pattern)
              + d * shapes["vocab"])
    attention = 2.0 * heads * hd * _given_pairs(shapes) / s
    scores = (((s - z["kernel"]) // z["stride"] + 1) / 2.0 * heads * hd
              if s > z["dense_len"] else 0.0)
    shaped = (pattern.count("L") * _scan_macs_per_token(shapes)
              + pattern.count("S") * attention)
    return 6.0 * (matmul + shaped) + 2.0 * pattern.count("S") * scores


def ssd_scan_call(shapes: Dict[str, Any]) -> Dict[str, float]:
    """Least operations and HBM bytes of the lightning layers' scans ONE step
    makes on one device, forward and backward (twice the forward), no
    recompute. A scan reads v, k, q (bf16) and Δ (f32) and writes o (f32);
    the backward reads those and o's gradient and writes theirs."""
    tokens = shapes["per_chip_batch"] * shapes["seq_len"]
    h, hd = shapes["lightning_heads"], shapes["head_dim"]
    layers = shapes["pattern"].count("L")
    a = shapes["attention_dtype_bytes"]
    moved = 3 * h * hd * a + h * 4 + h * hd * 4
    return {"flops": 3.0 * 2.0 * _scan_macs_per_token(shapes) * tokens * layers,
            "bytes": 3.0 * moved * tokens * layers}


def sparse_attn_call(shapes: Dict[str, Any]) -> Dict[str, float]:
    """Least operations and HBM bytes of the sparse layers' attention ONE
    step makes on one device, whatever implements it: over the keys a query
    is GIVEN (min(visible, top_k · block)), forward two products a pair and
    head, backward five, no recompute. The forward reads q, k, v and writes o
    and the f32 log-sum-exp; the backward reads q, k, v, o, do and lse and
    writes dq, dk, dv (k, v and theirs on the key-value heads)."""
    b, s = shapes["per_chip_batch"], shapes["seq_len"]
    heads, kv, hd = shapes["n_head"], shapes["n_kv_head"], shapes["head_dim"]
    layers, a = shapes["pattern"].count("S"), shapes["attention_dtype_bytes"]
    pairs = b * heads * _given_pairs(shapes)
    q_row, kv_row = float(b * heads * s * hd * a), float(b * kv * s * hd * a)
    return {"flops": 14.0 * hd * pairs * layers,
            "bytes": layers * (6 * q_row + 8 * kv_row + 8.0 * b * heads * s)}


def attention_resolved(bundle) -> list:
    """What the program's one attention rule chose on this mesh."""
    from ray_tpu.ops.attention import resolve_attention

    return list(resolve_attention(bundle.cfg.attention_impl, bundle.mesh))


def reference_sizes(cfg, **switches) -> Dict[str, Any]:
    """The share description the reference takes, from the program's config."""
    return {"eps": cfg.rms_eps, "theta": cfg.rope_theta,
            "pattern": cfg.pattern,
            "lightning_head_first": cfg.lightning_head_first,
            "lightning_heads_published": cfg.lightning_heads_published,
            "sparse": cfg.sparse._asdict(), "depth_scale": cfg.depth_scale,
            "scale_emb": cfg.scale_emb,
            "head_scale": cfg.dim_model_base / cfg.d_model, **switches}


def grad_passes(params, n: int) -> list:
    """The parameter tensors (indices into the tree's leaves) in ``n`` parts
    of about equal bytes: the largest first, each to the lightest part."""
    import jax

    sizes = [x.size * x.dtype.itemsize for x in jax.tree.leaves(params)]
    parts, weight = [[] for _ in range(n)], [0] * n
    for i in sorted(range(len(sizes)), key=lambda i: -sizes[i]):
        j = weight.index(min(weight))
        parts[j].append(i)
        weight[j] += sizes[i]
    return [sorted(part) for part in parts]


def readings(bundle, batch_host: Dict[str, Any], cell: Dict[str, Any],
             which=("program", "reference"), **switches) -> Dict[str, Any]:
    """Loss (the cell's first ``reference_rows`` rows, whole) and, with
    ``reference_grad``, each parameter tensor's gradient norm (their first
    ``reference_grad_tokens`` tokens, the loss without the first
    ``reference_grad_skip`` targets) of the program and of the reference —
    with ``switches`` (minicpm_sala_reference's) for the readings a limit
    must catch — on the state's parameters as set-up left them. The reference
    is told the blocks the program chose at each length and reports on them
    (``selection``, the sparse layers in their order)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import minicpm_sala
    from ray_tpu.parallel import mesh as mesh_lib

    mesh = bundle.mesh
    rows = cell["reference_rows"]
    with_grad = bool(cell["reference_grad"])
    params = bundle.state["params"]
    param_sh = jax.tree.map(lambda p: p.sharding, params)
    data_sh, scalar = bundle.data_sharding, NamedSharding(mesh, P())

    def rows_of(length, skip):
        sub = {k: np.asarray(v[:rows, :length]).copy()
               for k, v in batch_host.items()}
        sub["targets"][:, -1] = -1             # a prefix ends where it ends
        sub["targets"][:, :skip] = -1          # (the limits' comment: why)
        return jax.device_put(sub, data_sh)

    def at(length, skip=0):
        """(the row's first ``length`` tokens — the first ``skip`` of them
        no targets —, the program's config and the reference's sizes at that
        length, the blocks the program chose)."""
        cfg = dataclasses.replace(bundle.cfg, seq_len=length)
        sub = rows_of(length, skip)

        def chosen(p, tokens):
            with mesh_lib.use_mesh(mesh):
                return minicpm_sala.chosen_blocks(p, tokens, cfg)

        ids = jax.jit(chosen, in_shardings=(param_sh, data_sh))(
            params, sub["tokens"])
        return sub, cfg, reference_sizes(cfg, **switches), ids

    def program_loss(cfg):
        def program(p, tokens, targets, ids):
            with mesh_lib.use_mesh(mesh):
                return minicpm_sala.loss_fn(p, tokens, targets, cfg), []
        return program

    def reference_loss(sizes):
        def reference(p, tokens, targets, ids):
            with jax.default_matmul_precision("highest"):
                return minicpm_sala_reference.loss_and_selection(
                    p, tokens, targets, sizes, ids)
        return reference

    def norms(loss_of, which):
        """The gradient norms of the parameter tensors ``which`` (indices
        into the tree's leaves), the others held: the gradient of a part is
        made, and stands on the chip, a pass."""
        def fn(p, tokens, targets, ids):
            leaves, treedef = jax.tree.flatten(p)

            def of(part):
                full = list(leaves)
                for i, leaf in zip(which, part):
                    full[i] = leaf
                return loss_of(treedef.unflatten(full), tokens, targets, ids)[0]

            grads = jax.grad(of)([leaves[i] for i in which])
            return jnp.stack([optax.global_norm(g) for g in grads])

        fn.__name__ = loss_of.__name__ + "_grad_norms"
        return fn

    whole, cfg, sizes, ids = at(bundle.cfg.seq_len)
    sides = {"program": program_loss(cfg), "reference": reference_loss(sizes)}
    out: Dict[str, Any] = {}
    for name in which:
        loss, selection = jax.jit(sides[name])(
            params, whole["tokens"], whole["targets"], ids)
        out[name] = {"loss": float(loss), "grad_norm_by_tensor": [],
                     "selection": [{k: float(v) for k, v in r.items()}
                                   for r in selection]}
    if with_grad:
        prefix, cfg, sizes, ids = at(cell["reference_grad_tokens"],
                                     cell["reference_grad_skip"])
        sides = {"program": program_loss(cfg),
                 "reference": reference_loss(sizes)}
        passes = grad_passes(params, cell.get("reference_grad_passes", 1))
        for name in which:
            by_tensor = np.zeros(len(jax.tree.leaves(params)))
            # the program's gradient fits in one pass; the float32
            # reference's does not (the cell's file says how far it does)
            for part in (passes if name == "reference" else [sum(passes, [])]):
                by_tensor[part] = np.asarray(jax.jit(
                    norms(sides[name], tuple(part)), out_shardings=scalar)(
                    params, prefix["tokens"], prefix["targets"], ids),
                    np.float64)
            out[name]["grad_norm_by_tensor"] = by_tensor.tolist()
    out.update(rows=rows, with_grad=with_grad, loss_rtol=LOSS_RTOL,
               grad_norm_rtol=GRAD_NORM_RTOL,
               # (the CPU rehearsal's tiny sizes state their own: a score
               # there is a sum over 2 heads of 16, not 16 of 128)
               select_margin=cell.get("select_margin", SELECT_MARGIN))
    return out


def reference_check(bundle, batch_host: Dict[str, Any], config, cell,
                    **control) -> Dict[str, Any]:
    """Program against the plain reference (``readings``; ``grad_norm`` and
    the selection's margin as the limits' comment says). With ``control``
    (minicpm_sala_reference's switches: ``operand_dtype`` for a precision
    below the configuration's) the reference so switched stands where the
    program stands — the reading a limit must refuse. Returns the numbers;
    judges nothing."""
    from benchmarks.families.nemotron_h import grad_error

    out = readings(bundle, batch_host, cell,
                   which=("reference",) if control else ("program", "reference"))
    if control:
        out["program"] = readings(bundle, batch_host, cell,
                                  which=("reference",), **control)["reference"]
    prog, ref = out["program"], out["reference"]
    # the report on the program's chosen blocks is the reference's; a
    # control's own scores report on the same blocks
    judged = prog if control else ref
    worst = max((r["worst_margin"] for r in judged["selection"]), default=0.0)
    off = 0.0 if worst <= out["select_margin"] else 1.0
    total = float(sum(ref["grad_norm_by_tensor"]))
    error = (grad_error(prog["grad_norm_by_tensor"], ref["grad_norm_by_tensor"])
             if out["with_grad"] else {"total": 0.0})
    ref["grad_norm"] = total
    prog.update(grad_norm=total * (1.0 + error["total"] + off),
                grad_error=error, selection_worst_margin=worst)
    if off and not out["with_grad"]:
        prog["loss"] *= 2.0
    return out


def abstract_step(config: Dict[str, Any], cell: Dict[str, Any], mesh):
    """(jitted step, abstract arguments) for a compile with no device to hold
    an array (harness/rehearse_compile.py). The step IS the program's:
    ``train_step._compose_step`` composes it, told the described chip's
    bytes_limit and the bytes its state and gradients take (as family
    ``evabyte`` does, and why)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import minicpm_sala
    from ray_tpu.train.train_step import _compose_step, _resident_bytes

    cfg = program_config(config, cell)
    optimizer = _optimizer()
    step_given, state_sh, batch_sh = _compose_step(
        minicpm_sala, cfg, mesh, optimizer, None)
    params = jax.eval_shape(lambda: minicpm_sala.init(cfg, jax.random.PRNGKey(0)))
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
