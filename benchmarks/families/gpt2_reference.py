"""GPT-2, forward and loss, in straight ``jax.numpy`` and float32.

The benchmark's plain reference (Radford et al. 2019, "Language Models are
Unsupervised Multitask Learners"; the block is the pre-LayerNorm transformer
decoder of the released code): no kernel, no cache, no sharding rule, no
mixed precision, nothing imported from ``ray_tpu``. The caller sets
``jax.default_matmul_precision("highest")`` — on a TPU a float32 matmul runs in
lower precision otherwise.

It reads the program's parameter tree as the program lays it out (layers
stacked on a leading axis; ``qkv_w`` as ``[L, D, 3, H, hd]``; ``proj_w`` as
``[L, H, hd, D]``), because the comparison is on the program's own seeded
weights. Departures from the published model, all taken from the program so
that the comparison measures precision and nothing else:

- the softmax runs over the embedding's padded rows (50,304, not 50,257): the
  pad rows are ordinary random rows and are never a target. The published
  model's loss is lower by at most ln(50304/50257) = 9.3e-4 nats;
- dropout is absent (pretraining);
- targets < 0 are ignored and the loss is the mean over the others.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def logits(params, tokens, n_head: int, eps: float = 1e-5):
    """tokens [B, S] int32 -> logits [B, S, rows of wte], float32."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    seq = tokens.shape[1]
    wte = f32(params["wte"])
    x = wte[tokens] + f32(params["wpe"])[:seq]
    head_dim = x.shape[-1] // n_head
    causal = jnp.tril(jnp.ones((seq, seq), bool))

    def block(x, p):
        p = {k: f32(v) for k, v in p.items()}
        h = _layernorm(x, p["ln1_scale"], p["ln1_bias"], eps)
        q, k, v = (
            jnp.einsum("bsd,dhk->bhsk", h, p["qkv_w"][:, j])
            + p["qkv_b"][j][None, :, None, :]
            for j in range(3)
        )
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(head_dim)
        scores = jnp.where(causal, scores, -jnp.inf)
        attn = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
        x = x + jnp.einsum("bhsk,hkd->bsd", attn, p["proj_w"]) + p["proj_b"]
        h = _layernorm(x, p["ln2_scale"], p["ln2_bias"], eps)
        h = _gelu_new(h @ p["fc_w"] + p["fc_b"])
        return x + h @ p["out_w"] + p["out_b"], None

    # one block, written once and run over the stacked layers in order (the
    # weights arrive stacked). Unrolled in Python the float32 program took
    # 6.3-6.7 s to load from the compile cache in EVERY run (PR 22).
    x, _ = jax.lax.scan(block, x, params["blocks"])
    x = _layernorm(x, f32(params["lnf_scale"]), f32(params["lnf_bias"]), eps)
    return x @ wte.T


def loss(params, tokens, targets, n_head: int, eps: float = 1e-5):
    """Mean next-token cross-entropy over targets >= 0."""
    logp = jax.nn.log_softmax(logits(params, tokens, n_head, eps), axis=-1)
    valid = targets >= 0
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, targets, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.maximum(
        jnp.sum(valid), 1)
