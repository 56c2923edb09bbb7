"""Masked softmax cross-entropy over an LM head's logits: two ops, each with a
custom VJP, for the two ways a model takes its sequence through the head.

**The whole sequence at once — ``softmax_xent``: the bf16 logits are kept.**
The naive `log_softmax` + `take_along_axis` loss keeps the full-vocabulary
f32 log-probability tensor as an autodiff residual. At GPT-2-124M bench shape
([24, 1024, 50304]) that is a 4.9 GB HBM write plus re-reads — the device
profile showed ~17 ms/step (8%) in those loop fusions alone. This op's VJP
saves only the bf16 logits (which the LM-head matmul already produced) plus a
[B, S] logsumexp:

- forward: two streaming passes over the logits (row max, then exp-sum fused
  with the one-hot pick) — no full-size f32 tensor is ever written;
- backward: d_logits = (softmax - onehot) · g is a pure elementwise chain off
  the saved logits, which XLA fuses straight into the two consuming backward
  matmuls (dx and d_wte) instead of materializing it.

**The sequence in chunks — ``chunked_head_xent``: the gradient is made in the
forward.** Where the float32 logits of the whole sequence are too large to be
one tensor (parts.lm_head_loss: EvaByte's eight heads over 32,768 bytes,
Nemotron's 16,384 columns over 8 x 4,096 tokens) the head and the loss are
ONE op over chunks of the sequence. A cross-entropy's gradient with respect to
its logits needs nothing but the logits and a weight known from the targets
alone, so under differentiation the chunk that has its logits in hand also
makes ``d x`` and its share of ``d lm_head`` — for a unit cotangent; the
backward multiplies both by the scalar that arrives. The logits of a chunk
are multiplied out once a step, and nothing of their size is kept or made
again (a ``checkpoint`` a chunk made them twice: PERF.md §6, PR 39). Called
without differentiation the op does no gradient work.

What a chunk costs beside its three products is the running ``d lm_head``:
the scan's carry, float32 [D, heads · columns], which EVERY chunk reads and
writes whole — chunks x 2 x D x columns x 4 bytes a step
(``model/head_loss``'s ``carry_bytes_a_step``). The chunk's ``d lm_head``
product does tokens / 4 operations a byte of it, so a chunk of few tokens
waits for the carry, and one of ~1,000 or more hides it under the product
(on a v5e). Against that stands where a chunk's logits live: up to 64 MiB
the TPU compiler keeps them in the chip's fast memory, past it every pass
over them goes to HBM. The caller weighs the two (parts.head_chunk_rows);
the op itself takes any ``rows`` that divide the sequence: the sums are the
same sums, only the addends a partial one holds change.

Numerics of both are the reference formulation's (f32 max-subtracted softmax;
tests assert equality vs jax.nn.log_softmax). Ignore index: any target < 0
contributes 0 loss and 0 gradient.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.tracing import names


def _nll_and_lse(logits, targets):
    lf = logits.astype(jnp.float32)
    m = jnp.max(lf, axis=-1)
    # one-hot pick via compare+select on the same pass as the exp-sum (a
    # take_along_axis gather on the minor dim would defeat the fusion)
    V = logits.shape[-1]
    cols = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    onehot = cols == targets[..., None]
    shifted = lf - m[..., None]
    sumexp = jnp.sum(jnp.exp(shifted), axis=-1)
    picked = jnp.sum(jnp.where(onehot, shifted, 0.0), axis=-1)
    lse = m + jnp.log(sumexp)
    valid = targets >= 0
    nll = jnp.where(valid, jnp.log(sumexp) - picked, 0.0)
    return nll, lse


@jax.custom_vjp
def softmax_xent(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """logits [..., V] (any float dtype), targets [...] int32 (< 0 = ignore)
    → per-position negative log-likelihood [...] f32 (0 at ignored positions).
    """
    nll, _ = _nll_and_lse(logits, targets)
    return nll


def _xent_fwd(logits, targets):
    nll, lse = _nll_and_lse(logits, targets)
    return nll, (logits, lse, targets)


def _xent_bwd(res, g):
    logits, lse, targets = res
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    cols = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    onehot = (cols == targets[..., None]).astype(jnp.float32)
    gm = jnp.where(targets >= 0, g, 0.0)[..., None]
    dlogits = ((p - onehot) * gm).astype(logits.dtype)
    return dlogits, None


softmax_xent.defvjp(_xent_fwd, _xent_bwd)


# --------------------------------------------------------------------------- #
# The head and the loss in chunks of the sequence, gradient in the forward
# --------------------------------------------------------------------------- #

_decisions: Dict[tuple, Dict[str, Any]] = {}


def head_loss_decisions() -> List[Dict[str, Any]]:
    """Every distinct way this process has traced the chunked head, as the
    ``model/head_loss`` events carry them."""
    return list(_decisions.values())


def _record(x, targets, lm_head, rows: int, grad_in_forward: bool) -> None:
    """What a chunked head was traced as (``model/head_loss``): the chunks,
    whether this trace makes the gradient beside the loss, what it then
    keeps for the backward — ``d x`` where the chunked ``x`` stood, and the
    running ``d lm_head`` in float32 — and the bytes of that running sum the
    step moves: every chunk reads and writes it whole."""
    from ray_tpu.ops.attention import record_decision

    B, S = x.shape[:2]
    chunks = S // rows
    kept = carry = 0
    if grad_in_forward:
        carry = lm_head.size * 4
        kept = x.size * x.dtype.itemsize + carry
    record_decision(_decisions, names.HEAD_LOSS, dict(zip(
        names.HEAD_LOSS_ARGS,
        (B, rows, chunks, lm_head.shape[1], targets.shape[-1],
         grad_in_forward, kept, chunks * 2 * carry))))


def _chunks(a, rows: int):
    """[B, S, ...] -> [S / rows, B, rows, ...]: what the scan walks."""
    B, S = a.shape[:2]
    return a.reshape((B, S // rows, rows) + a.shape[2:]).swapaxes(0, 1)


def _chunk_logits(x_c, lm_head, heads: int):
    """[B, c, D] hidden -> [B, c, heads, columns a head] logits, float32 out
    of the matmul (``fp32_logits``)."""
    logits = jnp.einsum("bsd,dv->bsv", x_c, lm_head,
                        preferred_element_type=jnp.float32)
    return logits.reshape(logits.shape[:2] + (heads, -1))


def _mean_over_heads(total, count):
    return jnp.mean(total / jnp.maximum(count, 1))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def chunked_head_xent(x: jax.Array, targets: jax.Array, lm_head: jax.Array,
                      rows: int, weights=None) -> jax.Array:
    """Head(s) + cross-entropy over hidden states ``x`` [B, S, D], ``rows``
    positions of the sequence at a time: ``lm_head`` [D, heads · columns] in
    x's dtype, head p in columns p·columns … (p+1)·columns, ``targets``
    [B, S, heads] int32 (< 0 = ignore) → the mean over the heads of each
    head's mean negative log-likelihood over its valid targets, float32.
    ``rows`` divides S; a chunk's logits [B, rows, heads · columns] are
    float32 and the largest tensor there is — beside the float32 ``d
    lm_head`` that every chunk of the differentiated op reads and writes
    (the module's docstring: why a chunk should not be too short).

    ``weights`` [B, S, heads] float32 (None: every target weighs 1, and the
    op lowers as it did before it took any): Σ weight · nll over a head's
    valid targets ÷ their NUMBER — the weights are differentiated, their
    cotangent a target's own nll ÷ that number (and ÷ heads)."""
    _record(x, targets, lm_head, rows, grad_in_forward=False)
    heads = targets.shape[-1]

    def chunk(total, xs):
        x_c, t_c, *w_c = xs
        nll, _ = _nll_and_lse(_chunk_logits(x_c, lm_head, heads), t_c)
        for w in w_c:
            nll = nll * w
        return total + jnp.sum(nll, axis=(0, 1)), None

    total, _ = lax.scan(chunk, jnp.zeros((heads,), jnp.float32),
                        _scanned(x, targets, weights, rows))
    return _mean_over_heads(total, jnp.sum(targets >= 0, axis=(0, 1)))


def _scanned(x, targets, weights, rows: int):
    """What the chunk scan walks: x, the targets and, where there are any,
    the weights."""
    given = (x, targets) if weights is None else (x, targets, weights)
    return tuple(_chunks(a, rows) for a in given)


def _chunked_fwd(x, targets, lm_head, rows, weights=None):
    """The loss, and for a unit cotangent ``d x`` (a chunk: stacked by the
    scan, in x's dtype) and ``d lm_head`` (the scan's carry, float32): each
    chunk forms (softmax − onehot) · weight from the logits it has and
    multiplies it into both. The weight of a valid target of head p is
    1 / (heads · count_p), from the targets alone — times the target's own of
    ``weights`` where those are given, whose cotangent, nll / (heads ·
    count_p), the chunk then hands out too (stacked by the scan). The two
    products take what AD's transposes of the logits' einsum took: float32
    ``d logits``, the other operand in the compute dtype, float32 out."""
    _record(x, targets, lm_head, rows, grad_in_forward=True)
    heads = targets.shape[-1]
    count = jnp.sum(targets >= 0, axis=(0, 1))
    weight = 1.0 / (heads * jnp.maximum(count, 1).astype(jnp.float32))

    def chunk(carry, xs):
        total, d_head = carry
        x_c, t_c, *w_c = xs
        logits = _chunk_logits(x_c, lm_head, heads)
        nll, lse = _nll_and_lse(logits, t_c)
        of_target = jnp.broadcast_to(weight, t_c.shape)
        for w in w_c:
            of_target = of_target * w
        d_logits, _ = _xent_bwd((logits, lse, t_c), of_target)
        d_logits = d_logits.reshape(x_c.shape[:2] + (-1,))
        d_x = lax.dot_general(d_logits, lm_head, (((2,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        d_head = d_head + lax.dot_general(
            x_c, d_logits, (((0, 1), (0, 1)), ((), ())),
            preferred_element_type=jnp.float32)
        if not w_c:
            return ((total + jnp.sum(nll, axis=(0, 1)), d_head),
                    d_x.astype(x.dtype))
        return ((total + jnp.sum(nll * w_c[0], axis=(0, 1)), d_head),
                (d_x.astype(x.dtype), nll * weight))

    (total, d_head), d_x = lax.scan(
        chunk, (jnp.zeros((heads,), jnp.float32),
                jnp.zeros(lm_head.shape, jnp.float32)),
        _scanned(x, targets, weights, rows))
    d_w = None
    if weights is not None:
        d_x, d_w = d_x
    return _mean_over_heads(total, count), (d_x, d_head, d_w)


def _unchunked(a, g):
    """The scan's stack [S / rows, B, rows, ...] times the cotangent ``g``,
    as [B, S, ...] in the stack's dtype."""
    n, B, c = a.shape[:3]
    return (g * a).astype(a.dtype).swapaxes(0, 1).reshape(
        (B, n * c) + a.shape[3:])


def _chunked_bwd(rows, res, g):
    d_x, d_head, d_w = res                  # [S / rows, B, rows, D], [D, V]
    d_x = _unchunked(d_x, g)
    return (d_x, None, (g * d_head).astype(d_x.dtype),
            None if d_w is None else _unchunked(d_w, g))


chunked_head_xent.defvjp(_chunked_fwd, _chunked_bwd)
