"""Family ``evabyte``: from a configuration file to the program's train step.

EvaByte is the program's llama-family model (``ray_tpu/models/llama.py``) with
the EVA mixer, eight prediction heads and a unit-offset norm. As for family
``gpt2`` the benchmark hands the program the published sizes and what the
cell's file states (per-chip batch, ``remat``, mesh) and NOTHING else: tiles,
``attention_impl``, what remat keeps and how the head chunks stay at the
program's defaults.

Everything ``benchmarks/README.md`` asks of a family is here — ``build``,
``shapes``, ``attention_resolved``, ``reference_check``, ``abstract_step`` —
and two things the README did not foresee, which a second family had to bring
because ``harness/flops.py`` is GPT-2's arithmetic (6N + 12·L·S·d a token;
every Mosaic call a full-causal S² flash call) and no file that is there may
be edited:

- ``train_flops_per_token(shapes)``: this family's own count, attention as
  the EVA mask cuts it (``eva_mfu_device`` reads it);
- ``eva_call(shapes, kernel)``: operations and HBM bytes of ONE call of each
  new kernel, from shapes (``eva_agg_roofline`` reads it).

The next ``benchmark`` issue may fold both into the README's list (a family
states its FLOPs a token and its kernels' work; ``mfu_device`` and the
roofline readers then ask the family, not ``harness/flops.py``). Until then
``run.py``'s human line "end-to-end MFU" is computed with GPT-2's 12·L·S·d and
overstates this family's attention 16-fold: ``eva_mfu_device`` is the number.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.families import evabyte_reference

# AdamW as the program's default_optimizer builds it, with a schedule that
# does not depend on --seconds (family gpt2's, so the optimizer is the same
# code at the same settings in every cell).
LR, WARMUP, TOTAL_STEPS = 6e-4, 4, 10_000

# memory_stats()["bytes_limit"] of a v5e chip (PERF.md §6): what the remat
# rule is told when the step is compiled for a chip that is described
V5E_BYTES_LIMIT = 16_909_334_528

# Program (bf16 operands; f32 accumulation, softmax, residual add and logits;
# compiled EVA kernels) against the float32 reference on the same weights and
# the cell's own first row: the loss on all 32,768 bytes, the gradient norm on
# the first 12,288. Each limit stands between two readings on the chip
# (PERF.md §6, PR 31). Over nine seeds the program's relative error was
# 0.9e-5 .. 8.3e-5 on the loss and 0.4e-4 .. 6.6e-4 on the gradient norm. The
# reference with the summaries dropped reads 6.6e-4 and 0.29 off: it fails
# both. With its forward matmuls' operands in 8 bits (float8_e4m3, one scale
# a tensor; the precision below the bf16 the configuration states) it reads
# 2.3e-4 and 7.7e-3 off: it fails the gradient norm. The limits are 2.9x and
# 3.0x the worst seen.
LOSS_RTOL = 2.0 ** -12
GRAD_NORM_RTOL = 2.0 ** -9


def _require_program() -> None:
    """A checkout whose program has no EVA attention (the parent of PR 31)
    cannot run this family: say so before a cluster is started."""
    from ray_tpu.tracing import names

    if not hasattr(names, "EVA_AGG_FWD_KERNEL"):
        raise SystemExit(
            "benchmarks/families/evabyte.py: this checkout's program has no "
            "EVA attention (ray_tpu/ops/eva_attention.py): it cannot run a "
            "cell of family evabyte")


def program_config(config: Dict[str, Any], cell: Dict[str, Any]):
    """The program's LlamaConfig for this configuration file and cell."""
    from ray_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=config["vocab_size"],
        seq_len=config["max_position_embeddings"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        d_model=config["hidden_size"],
        d_ff=config["intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_eps=config["rms_norm_eps"],
        mixer=config["attention_class"],
        window=config["window_size"],
        chunk=config["chunk_size"],
        n_pred_heads=config["num_pred_heads"],
        norm_unit_offset=config["norm_add_unit_offset"],
        init_std=config["init_std"],
        remat=cell["remat"],
    )


def _optimizer():
    from ray_tpu.train.train_step import default_optimizer

    return default_optimizer(lr=LR, warmup=WARMUP, total_steps=TOTAL_STEPS)


def build(config: Dict[str, Any], cell: Dict[str, Any], mesh, seed: int):
    """The program's TrainStepBundle, through its one step factory."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.train.train_step import make_train_step

    return make_train_step(
        llama, program_config(config, cell), mesh=mesh,
        optimizer=_optimizer(), rng=jax.random.PRNGKey(seed))


def shapes(config: Dict[str, Any], cell: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the arithmetic needs: harness/flops.py's keys (run.py reads
    them for every cell) and this family's own."""
    _require_program()
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    heads, ff = config["num_attention_heads"], config["intermediate_size"]
    vocab, pred = config["vocab_size"], config["num_pred_heads"]
    hd = d // heads
    padded_vocab = -(-vocab // 128) * 128              # the embedding's rows
    # q, k, v, o; gate, up, down; two norms; EVA's two vectors a head
    per_layer = 4 * d * d + 3 * d * ff + 2 * d + 2 * heads * hd
    matmul = layers * (4 * d * d + 3 * d * ff) + d * pred * vocab
    return {
        "params": layers * per_layer + padded_vocab * d + d * pred * vocab + d,
        "matmul_params": matmul,
        "n_layer": layers,
        "d_model": d,
        "n_head": heads,
        "head_dim": hd,
        "seq_len": config["max_position_embeddings"],
        "window": config["window_size"],
        "chunk": config["chunk_size"],
        "n_pred_heads": pred,
        "per_chip_batch": cell["per_chip_batch"],
        "chips": cell["chips"],
        "remat": cell["remat"],
        "attention_dtype_bytes": 2,                    # bf16 q, k, v, o, do
    }


def _pairs(shapes: Dict[str, Any]) -> float:
    """(query, key) pairs one head of one row attends over: each query its
    window's keys up to itself, and one summary a chunk of every earlier
    window."""
    s, w, c = shapes["seq_len"], shapes["window"], shapes["chunk"]
    n_win = s // w
    local = s * (w + 1) / 2.0
    remote = w * (w // c) * n_win * (n_win - 1) / 2.0
    return local + remote


def train_flops_per_token(shapes: Dict[str, Any]) -> float:
    """Forward + backward operations one trained token REQUIRES: 6 per matmul
    parameter (the embedding is a gather) plus, a layer, three times the
    forward's two attention matmuls (q·k and p·v, 2·hd multiply-adds a pair
    and head each) over the pairs the EVA mask leaves. Recomputed operations
    do not count; the summary pass (a few operations a key) is left out."""
    pairs_per_token = _pairs(shapes) / shapes["seq_len"]
    attention = 3.0 * shapes["n_layer"] * 4.0 * shapes["d_model"] * pairs_per_token
    return 6.0 * shapes["matmul_params"] + attention


def eva_call(shapes: Dict[str, Any], kernel: str) -> Dict[str, float]:
    """Least operations and HBM bytes of ONE call of an EVA kernel on one
    device's shard ``[B, H, S, hd]``. ``eva_agg_fwd``: two matmuls a pair
    (k·q and p·v); reads q, k, v and the two summary rows, writes o and the
    f32 log-normaliser. ``eva_agg_bwd``: five (k·q again, dv, dp, dk, dq);
    reads q, k, v, do, the summaries, lse and delta, writes dq, dk, dv and the
    summaries' two gradients."""
    b, h, hd = shapes["per_chip_batch"], shapes["n_head"], shapes["head_dim"]
    s, a = shapes["seq_len"], shapes["attention_dtype_bytes"]
    row, summaries = float(b * h * s * hd * a), float(b * h * s // shapes["chunk"] * hd * a)
    pairs = b * h * _pairs(shapes)
    if kernel == "eva_agg_fwd":
        return {"flops": 4.0 * hd * pairs,
                "bytes": 4 * row + 2 * summaries + 4.0 * b * h * s}
    if kernel == "eva_agg_bwd":
        return {"flops": 10.0 * hd * pairs,
                "bytes": 7 * row + 4 * summaries + 8.0 * b * h * s}
    raise KeyError(kernel)


def attention_resolved(bundle) -> list:
    """What the program's one attention rule chose on this mesh."""
    from ray_tpu.ops.attention import resolve_attention

    return list(resolve_attention(bundle.cfg.attention_impl, bundle.mesh))


def _sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    return {"eps": config["rms_norm_eps"], "theta": float(config["rope_theta"]),
            "window": config["window_size"], "chunk": config["chunk_size"],
            "n_pred_heads": config["num_pred_heads"]}


def reference_check(bundle, batch_host: Dict[str, Any], config, cell) -> Dict[str, Any]:
    """Program against the plain reference on the step state's INITIAL
    parameters: the loss on the cell's own first row(s), whole; the gradient
    norm (``reference_grad``) on the first ``reference_grad_tokens`` bytes of
    them — the float32 reference's backward at the whole row does not fit
    beside the state (the cell's file says how far it does). Returns the
    numbers; judges nothing."""
    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import llama
    from ray_tpu.parallel import mesh as mesh_lib

    mesh, cfg = bundle.mesh, bundle.cfg
    rows = cell["reference_rows"]
    with_grad = bool(cell["reference_grad"])
    params = bundle.state["params"]
    param_sh = jax.tree.map(lambda p: p.sharding, params)
    scalar = NamedSharding(mesh, P())
    sizes = _sizes(config)

    def program(p, tokens, targets):
        with mesh_lib.use_mesh(mesh):
            return llama.loss_fn(p, tokens, targets, cfg)

    def reference(p, tokens, targets):
        with jax.default_matmul_precision("highest"):
            return evabyte_reference.loss(p, tokens, targets, sizes)

    def jitted(fn):
        return jax.jit(
            fn, in_shardings=(param_sh, bundle.data_sharding, bundle.data_sharding),
            out_shardings=scalar)

    def grad_norm(loss_of):
        def fn(p, tokens, targets):
            return optax.global_norm(jax.grad(loss_of)(p, tokens, targets))

        fn.__name__ = loss_of.__name__ + "_grad_norm"
        return fn

    def rows_of(length):
        sub = {k: np.asarray(v[:rows, :length]).copy() for k, v in batch_host.items()}
        sub["targets"][:, -1] = -1             # a prefix ends where it ends
        return jax.device_put(sub, bundle.data_sharding)

    whole = rows_of(cfg.seq_len)
    prefix = rows_of(cell["reference_grad_tokens"]) if with_grad else None
    out = {}
    for loss_of in (program, reference):
        got = {"loss": float(jitted(loss_of)(
            params, whole["tokens"], whole["targets"])), "grad_norm": 0.0}
        if with_grad:
            got["grad_norm"] = float(jitted(grad_norm(loss_of))(
                params, prefix["tokens"], prefix["targets"]))
        out[loss_of.__name__] = got
    out.update(rows=rows, with_grad=with_grad, loss_rtol=LOSS_RTOL,
               grad_norm_rtol=GRAD_NORM_RTOL)
    return out


def abstract_step(config: Dict[str, Any], cell: Dict[str, Any], mesh):
    """(jitted step, abstract arguments) for a compile with no device to hold
    an array (harness/rehearse_compile.py). The step IS the program's:
    ``train_step._compose_step`` composes it, told the described chip's
    bytes_limit and the bytes its state and gradients take — the factory
    itself places real arrays, so it cannot be called on a described
    topology, and a copy of the step would not ask the remat rule (D20)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.train.train_step import _compose_step, _resident_bytes

    cfg = program_config(config, cell)
    optimizer = _optimizer()
    step_given, state_sh, batch_sh = _compose_step(
        llama, cfg, mesh, optimizer, None)
    params = jax.eval_shape(lambda: llama.init(cfg, jax.random.PRNGKey(0)))
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
