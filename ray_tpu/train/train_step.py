"""Sharded training step factory: params/optimizer sharding + jitted SGD step.

This is the compute core the Train layer (JaxTrainer) drives. The reference's
equivalent is torch DDP prepare_model + the user's train loop
(train/torch/train_loop_utils.py:75); here the whole step — forward, backward,
grad allreduce (implicit via GSPMD), optimizer update — is ONE jitted function
over a named mesh, with buffers donated so params update in place in HBM.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import gpt2
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.parallel import sharding as sharding_lib
from ray_tpu.tracing import (PROFILE_MIN_DUR_S, names as scopes, profile_span,
                             step_counters)


@dataclass
class TrainStepBundle:
    """Everything a training loop needs: initialized sharded state + step fn."""

    state: Dict[str, Any]          # {"params", "opt_state", "step"}
    step_fn: Callable              # (state, batch) -> (state, metrics)
    mesh: Mesh
    data_sharding: NamedSharding
    cfg: Any


def _scale_by_adam_lowmem(b1: float, b2: float, eps: float,
                          moment_dtype) -> optax.GradientTransformation:
    """scale_by_adam with BOTH moments stored in `moment_dtype` (bf16).

    The optimizer pass is HBM-bandwidth floor (~4.3 ms/step at GPT-2-124M
    on v5e); storing m and v in bf16 halves their read+write traffic
    (~1.2 ms/step). All update arithmetic runs in f32 — only the stored
    moments are rounded, a ~0.4% relative perturbation of the per-param
    step size (far finer than 8-bit Adam variants in production use).
    """

    def init(params):
        z = lambda p: jnp.zeros_like(p, dtype=moment_dtype)
        return optax.ScaleByAdamState(
            count=jnp.zeros((), jnp.int32),
            mu=jax.tree.map(z, params),
            nu=jax.tree.map(z, params),
        )

    def update(updates, state, params=None):
        del params
        count = state.count + 1
        def upd(g, m, v):
            g32 = g.astype(jnp.float32)
            m32 = m.astype(jnp.float32) * b1 + g32 * (1 - b1)
            v32 = v.astype(jnp.float32) * b2 + (g32 * g32) * (1 - b2)
            mhat = m32 / (1 - b1 ** count.astype(jnp.float32))
            vhat = v32 / (1 - b2 ** count.astype(jnp.float32))
            step = mhat / (jnp.sqrt(vhat) + eps)
            return step.astype(g.dtype), m32.astype(moment_dtype), v32.astype(moment_dtype)
        out = jax.tree.map(upd, updates, state.mu, state.nu)
        steps = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
        mu = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
        nu = jax.tree.map(lambda t: t[2], out, is_leaf=lambda x: isinstance(x, tuple))
        return steps, optax.ScaleByAdamState(count=count, mu=mu, nu=nu)

    return optax.GradientTransformation(init, update)


def default_optimizer(
    lr: float = 3e-4, weight_decay: float = 0.1, warmup: int = 100,
    total_steps: int = 10_000, b1: float = 0.9, b2: float = 0.95,
    grad_clip: float = 1.0, eps: float = 1e-8, decay_mask=None,
) -> optax.GradientTransformation:
    """AdamW with warmup-cosine LR, global-norm clipping, and bf16-stored
    moments (see _scale_by_adam_lowmem). ``decay_mask`` (optax's ``mask``: a
    tree of bools, or a function of the parameters that gives one) says which
    leaves the weight decay touches — a model with buffers among its
    parameters gives its own (``nemotron_h.decays``); None: all of them."""
    sched = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup, max(total_steps, warmup + 1), end_value=lr * 0.1
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        _scale_by_adam_lowmem(b1, b2, eps, jnp.bfloat16),
        optax.add_decayed_weights(weight_decay, mask=decay_mask),
        optax.scale_by_learning_rate(sched),
    )


def make_train_step(
    model,
    cfg,
    mesh: Optional[Mesh] = None,
    optimizer: Optional[optax.GradientTransformation] = None,
    rng: Optional[jax.Array] = None,
    rules: Optional[Dict] = None,
) -> TrainStepBundle:
    """Build sharded state and a jitted train step for ``cfg`` on ``mesh``.

    ``model`` is a model module of ``ray_tpu.models``. All the factory asks
    of it: ``init(cfg, rng)``, ``logical_axes(cfg)``,
    ``loss_fn(params, tokens, targets, cfg)`` and ``mesh_rules(cfg, mesh)``
    — the sharding rules this config adds on this mesh, or the refusal of a
    mesh it cannot run on."""
    if mesh is None:
        mesh = mesh_lib.single_device_mesh()
    if optimizer is None:
        optimizer = default_optimizer()
    if rng is None:
        rng = jax.random.PRNGKey(0)

    step_given, state_shardings, batch_shardings = _compose_step(
        model, cfg, mesh, optimizer, rules)

    # Shard-aware init: run init jitted with output shardings so large models
    # are *born sharded* and never materialize on one device.
    params = jax.jit(
        lambda r: model.init(cfg, r), out_shardings=state_shardings["params"]
    )(rng)
    opt_state = jax.jit(
        optimizer.init, out_shardings=state_shardings["opt_state"]
    )(params)
    state = {
        "params": params,
        "opt_state": opt_state,
        "step": _step_counter(mesh),
    }
    step_fn = _Step(jax.jit(
        step_given(_chip_memory(mesh, state)),
        in_shardings=(state_shardings, batch_shardings),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    ), _offered_counters(model, cfg))
    return TrainStepBundle(
        state=state, step_fn=step_fn, mesh=mesh,
        data_sharding=batch_shardings["tokens"], cfg=cfg,
    )


def make_gpt2_train_step(
    cfg: gpt2.GPT2Config,
    mesh: Optional[Mesh] = None,
    optimizer: Optional[optax.GradientTransformation] = None,
    rng: Optional[jax.Array] = None,
    rules: Optional[Dict] = None,
) -> TrainStepBundle:
    """make_train_step for GPT-2 (models/gpt2.py)."""
    return make_train_step(gpt2, cfg, mesh, optimizer, rng, rules)


def _offered_counters(model, cfg):
    """What ``model``'s step says of itself every step (a
    ``models.blocks.StepCounters``), or None: the module states it once, as
    ``step_counters(cfg)``; most have nothing to say and no such function."""
    offer = getattr(model, "step_counters", None)
    return offer(cfg) if offer is not None else None


class _Step:
    """The step a factory returns: the jitted ``step(state, batch)`` under
    the program's own span, its counters on their way to the record.

    A call (a) opens ``ray_tpu:train/step`` (args ``step`` = this object's
    count of calls) around the jitted call — the enqueue, or the wait on a
    full queue: on the profiler's clock under a profiler session, in the
    task-event buffer when it lasted ``PROFILE_MIN_DUR_S`` —; (b) where the
    model offers counters (a layer's loss term among them, as float32 bits:
    ``StepCounters.float_fields``), hands ``metrics["counters"]``, still
    being made,
    to ``tracing.step_counters`` with the same ``step`` and the wall time of
    the call, and lets it record what earlier steps' arrays are ready by now:
    one small fetch, never a wait. Everything else is the jitted object's:
    ``.lower``, ``.trace``, ``.eval_shape``, ``_cache_size`` pass through."""

    def __init__(self, jitted, counters=None):
        self._jitted = jitted
        self._counters = counters
        self._calls = 0
        self._decoders: Dict[int, Callable] = {}     # by the batch's tokens

    def __call__(self, state, batch):
        self._calls = n = self._calls + 1
        t_dispatch = time.time()
        with profile_span("step", {"step": n}, component="train",
                          min_dur_s=PROFILE_MIN_DUR_S):
            out = self._jitted(state, batch)
        if self._counters is not None:
            tokens = batch["tokens"].size
            decode = (self._decoders.get(tokens)
                      or self._decoders.setdefault(tokens, self._decode(tokens)))
            step_counters.watch(n, t_dispatch, out[1]["counters"], decode)
            step_counters.drain()
        return out

    def _decode(self, tokens: int):
        """Host array [layers, fields] → the event's args, for a batch of
        ``tokens`` tokens."""
        spec = self._counters
        static = spec.static(tokens)

        def decode(rows):
            def column(i, f):       # a float field's int32s are its bits
                c = np.ascontiguousarray(rows[:, i])
                return c.view(np.float32) if f in spec.float_fields else c

            return {"kind": spec.kind, "layers": list(spec.layers),
                    **{f: column(i, f).tolist()
                       for i, f in enumerate(spec.fields)}, **static}

        return decode

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "_jitted"), name)


def _compose_step(model, cfg, mesh: Mesh, optimizer, rules: Optional[Dict]):
    """What of a train step needs no array: ``(step_given, state_shardings,
    batch_shardings)``. ``step_given(memory)`` is the whole step — forward,
    backward, optimizer — as ``step(state, batch)``, to jit over those
    shardings with the state donated. ``memory`` is what _chip_memory
    measures of the placed state, so it can only come after the shardings."""
    rules = {**model.mesh_rules(cfg, mesh), **(rules or {})}
    param_shardings = sharding_lib.tree_shardings(
        mesh, model.logical_axes(cfg), rules)
    params = jax.eval_shape(lambda: model.init(cfg, jax.random.PRNGKey(0)))
    state_shardings = {
        "params": param_shardings,
        "opt_state": _opt_state_shardings(
            optimizer, params, param_shardings, mesh),
        "step": mesh_lib.replicated(mesh),
    }
    data_sh = mesh_lib.data_sharding(mesh, extra_dims=1)
    # a model that offers counters hands them out beside its loss, and they
    # leave the step as ONE output, `metrics["counters"]`; a model that
    # offers none is asked exactly what it was always asked
    with_counters = _offered_counters(model, cfg) is not None
    loss_fn = (partial(model.loss_fn, counters=True) if with_counters
               else model.loss_fn)

    def step_given(memory: Tuple[Optional[int], int]):
        def step(state, batch):
            tokens, targets = batch["tokens"], batch["targets"]
            # active while the step traces: use_mesh so the model can reach
            # the mesh (ring attention wraps a shard_map over it), chip_memory
            # so its remat rule knows what the chip has free.
            with mesh_lib.use_mesh(mesh), mesh_lib.chip_memory(*memory):
                out, grads = jax.value_and_grad(
                    loss_fn, has_aux=with_counters)(
                    state["params"], tokens, targets, cfg
                )
            new_params, new_opt, gnorm = _apply_optimizer(
                optimizer, grads, state)
            new_state = {
                "params": new_params,
                "opt_state": new_opt,
                "step": state["step"] + 1,
            }
            if not with_counters:
                return new_state, {"loss": out, "grad_norm": gnorm}
            loss, counters = out
            return new_state, {"loss": loss, "grad_norm": gnorm,
                               "counters": counters}

        return step

    return step_given, state_shardings, {"tokens": data_sh, "targets": data_sh}


@jax.named_scope(scopes.OPTIMIZER)
def _apply_optimizer(optimizer, grads, state):
    """The step's update: (new params, new optimizer state, the gradients'
    global norm), under one scope on the device."""
    updates, new_opt = optimizer.update(
        grads, state["opt_state"], state["params"]
    )
    new_params = optax.apply_updates(state["params"], updates)
    return new_params, new_opt, optax.global_norm(grads)


def _resident_bytes(state) -> int:
    """What stays on a chip through a whole step over ``state`` (placed, or
    abstract with shardings): its shard of every state leaf, and of the
    gradients, which are the parameters' bytes again."""
    def on_chip(tree):
        return sum(
            math.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
            for x in jax.tree.leaves(tree))

    return on_chip(state) + on_chip(state["params"])


def _chip_memory(mesh: Mesh, state) -> Tuple[Optional[int], int]:
    """(bytes_limit, resident bytes) of one chip for a step over the placed
    ``state``: the smallest limit this process's devices of the mesh report
    (None when one reports none: the CPU backend), and _resident_bytes."""
    stats = [d.memory_stats() for d in mesh.local_devices]
    limits = [s.get("bytes_limit") if s else None for s in stats]
    limit = None if None in limits else min(limits)
    return limit, _resident_bytes(state)


def _step_counter(mesh: Mesh) -> jax.Array:
    """The state's step counter, placed the way step_fn returns it. Left as
    an uncommitted jnp.zeros the first call's arguments differ from every
    later call's and the whole step compiles twice (10.8 s of the second
    call at GPT-2-124M on a v5e, PR 21)."""
    return jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P()))


def _opt_state_shardings(optimizer, params, param_shardings, mesh):
    """Shardings for the optimizer state: every copy of the parameter tree in
    it (Adam's mu and nu) takes the parameters' shardings leaf for leaf;
    everything else (counts) replicates. ``params`` may be abstract."""
    return optax.tree_map_params(
        optimizer, lambda _, sharding: sharding,
        jax.eval_shape(optimizer.init, params), param_shardings,
        transform_non_params=lambda _: mesh_lib.replicated(mesh),
    )


def synthetic_batch(cfg: gpt2.GPT2Config, global_batch: int, seed: int = 0):
    """Deterministic fake LM batch (benchmarks + tests)."""
    rng = np.random.default_rng(seed)
    tokens = rng.integers(
        0, cfg.vocab_size, size=(global_batch, cfg.seq_len), dtype=np.int32
    )
    targets = np.roll(tokens, -1, axis=1)
    targets[:, -1] = -1
    return {"tokens": tokens, "targets": targets}
