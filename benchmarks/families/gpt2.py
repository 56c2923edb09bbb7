"""Family ``gpt2``: from a configuration file to the program's train step.

The benchmark hands the program the published sizes and what the cell's file
states (per-chip batch, ``remat``, mesh) and NOTHING else: tiles,
``scan_layers``, ``attention_impl`` and ``loss_chunk`` stay at the program's
defaults, so a PR that makes the program choose better shows a gain here and
one that retunes a script shows nothing.

Everything a family must provide (``benchmarks/README.md``):
``build``, ``shapes``, ``reference_check`` and ``abstract_step``.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.families import gpt2_reference

# AdamW as the program's default_optimizer builds it, with a schedule that
# does not depend on --seconds.
LR, WARMUP, TOTAL_STEPS = 6e-4, 4, 10_000

# Program (bf16 matmuls with f32 accumulation, bf16 activations, compiled
# flash kernels) against the float32 reference on the same weights and rows.
# bf16's unit roundoff is 2^-9; the loss is a mean over >= 2,048 tokens of
# log-probabilities near 11 nats and the gradient norm a root of a sum over
# every parameter, so roundoffs average down. The chip runs of this PR read a
# relative error of 0.2e-5 .. 8.3e-5 on the loss (45 runs: 124M on 2 rows, XL
# on 4) and 0.7e-3 .. 1.9e-3 on the gradient norm (30 runs, 124M; the
# program's was the lower in all 30) (PERF.md, Findings PR 22); the tolerances
# are 6x and 4x the worst seen. An 8-bit matmul (unit roundoff 2^-4, 32x
# bf16's) would fail both.
LOSS_RTOL = 2.0 ** -11
GRAD_NORM_RTOL = 2.0 ** -7


def program_config(config: Dict[str, Any], cell: Dict[str, Any]):
    """The program's GPT2Config for this configuration file and cell."""
    from ray_tpu.models import gpt2

    return gpt2.GPT2Config(
        vocab_size=config["vocab_size"],
        seq_len=config["n_positions"],
        n_layer=config["n_layer"],
        n_head=config["n_head"],
        d_model=config["n_embd"],
        remat=cell["remat"],
    )


def _optimizer():
    from ray_tpu.train.train_step import default_optimizer

    return default_optimizer(lr=LR, warmup=WARMUP, total_steps=TOTAL_STEPS)


def build(config: Dict[str, Any], cell: Dict[str, Any], mesh, seed: int):
    """The program's TrainStepBundle: weights born on the device, sharded,
    from ``seed`` by the program's own init."""
    import jax

    from ray_tpu.train.train_step import make_gpt2_train_step

    return make_gpt2_train_step(
        program_config(config, cell), mesh=mesh, optimizer=_optimizer(),
        rng=jax.random.PRNGKey(seed),
    )


def shapes(config: Dict[str, Any], cell: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the yardstick's arithmetic needs (harness/flops.py)."""
    d, layers = config["n_embd"], config["n_layer"]
    vocab = -(-config["vocab_size"] // 128) * 128      # as the program pads
    seq = config["n_positions"]
    per_layer = 12 * d * d + 13 * d                    # weights + biases + 2 LN
    params = layers * per_layer + vocab * d + seq * d + 2 * d
    return {
        "params": params,
        "n_layer": layers,
        "d_model": d,
        "n_head": config["n_head"],
        "head_dim": d // config["n_head"],
        "seq_len": seq,
        "padded_vocab": vocab,
        "per_chip_batch": cell["per_chip_batch"],
        "chips": cell["chips"],
        "remat": cell["remat"],
        "attention_dtype_bytes": 2,                    # bf16 q, k, v, o, do
    }


def attention_resolved(bundle) -> list:
    """What the program's one attention rule chose on this mesh."""
    from ray_tpu.ops.attention import resolve_attention

    return list(resolve_attention(bundle.cfg.attention_impl, bundle.mesh))


def reference_check(bundle, batch_host: Dict[str, Any], config, cell) -> Dict[str, Any]:
    """Program against the plain reference on the first rows of the first
    batch, forward (and, where the reference fits, the gradient norm), on the
    step state's INITIAL parameters. Returns the numbers; judges nothing."""
    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import gpt2
    from ray_tpu.parallel import mesh as mesh_lib

    mesh, cfg = bundle.mesh, bundle.cfg
    rows = cell["reference_rows"]
    with_grad = bool(cell["reference_grad"])
    sub = jax.device_put(
        {k: np.asarray(v[:rows]) for k, v in batch_host.items()},
        bundle.data_sharding,
    )
    params = bundle.state["params"]
    param_sh = jax.tree.map(lambda p: p.sharding, params)
    scalar = NamedSharding(mesh, P())

    def program(p, tokens, targets):
        with mesh_lib.use_mesh(mesh):
            return gpt2.loss_fn(p, tokens, targets, cfg)

    def reference(p, tokens, targets):
        with jax.default_matmul_precision("highest"):
            return gpt2_reference.loss(p, tokens, targets, config["n_head"],
                                       config["layer_norm_epsilon"])

    def evaluated(loss_of):
        def fn(p, tokens, targets):
            if with_grad:
                loss, g = jax.value_and_grad(loss_of)(p, tokens, targets)
                return loss, optax.global_norm(g)
            return loss_of(p, tokens, targets), 0.0

        fn.__name__ = loss_of.__name__
        return jax.jit(
            fn, in_shardings=(param_sh, bundle.data_sharding, bundle.data_sharding),
            out_shardings=(scalar, scalar))

    out = {}
    for loss_of in (program, reference):
        loss, gnorm = evaluated(loss_of)(params, sub["tokens"], sub["targets"])
        out[loss_of.__name__] = {"loss": float(loss), "grad_norm": float(gnorm)}
    out.update(rows=rows, with_grad=with_grad, loss_rtol=LOSS_RTOL,
               grad_norm_rtol=GRAD_NORM_RTOL)
    return out


def abstract_step(config: Dict[str, Any], cell: Dict[str, Any], mesh):
    """(jitted step, abstract arguments) for a compile with no device to hold
    an array (the v5e rehearsal, harness/rehearse_compile.py). The step is
    composed as train_step.make_gpt2_train_step composes it — that factory
    places real arrays, so it cannot be called on a described topology."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import gpt2
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.parallel import sharding as sharding_lib
    from ray_tpu.train.train_step import _opt_state_shardings

    cfg = program_config(config, cell)
    optimizer = _optimizer()
    param_sh = sharding_lib.tree_shardings(mesh, gpt2.logical_axes(cfg), None)
    params = jax.eval_shape(lambda: gpt2.init(cfg, jax.random.PRNGKey(0)))
    opt_sh = _opt_state_shardings(optimizer, params, param_sh, mesh)
    opt_state = jax.eval_shape(optimizer.init, params)
    data_sh = mesh_lib.data_sharding(mesh, extra_dims=1)
    state_sh = {"params": param_sh, "opt_state": opt_sh,
                "step": NamedSharding(mesh, P())}

    def step(state, batch):
        with mesh_lib.use_mesh(mesh):
            loss, grads = jax.value_and_grad(gpt2.loss_fn)(
                state["params"], batch["tokens"], batch["targets"], cfg)
        updates, new_opt = optimizer.update(
            grads, state["opt_state"], state["params"])
        return {
            "params": optax.apply_updates(state["params"], updates),
            "opt_state": new_opt, "step": state["step"] + 1,
        }, {"loss": loss, "grad_norm": optax.global_norm(grads)}

    def abstract(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings)

    global_batch = cell["per_chip_batch"] * cell["chips"]
    tok = jax.ShapeDtypeStruct((global_batch, cfg.seq_len), jnp.int32,
                               sharding=data_sh)
    state = {
        "params": abstract(params, param_sh),
        "opt_state": abstract(opt_state, opt_sh),
        "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=state_sh["step"]),
    }
    fn = jax.jit(
        step, in_shardings=(state_sh, {"tokens": data_sh, "targets": data_sh}),
        out_shardings=(state_sh, None), donate_argnums=(0,),
    )
    return fn, (state, {"tokens": tok, "targets": tok})
