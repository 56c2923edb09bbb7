"""The parts more than one model family is built from, taking arguments, not
a config: RMSNorm, rotary embedding, the float32 residual add, the weight
cast that stays inside the layer loop, causal attention (grouped heads, the
flash kernel or XLA's softmax), the rows an MLP and a head take at a time, the
untied head's loss — and, beside the parts it describes, the half of the
remat rule that is about an attention + MLP BLOCK: its shapes on one chip
(BlockShard), what it may keep (remat_candidates) and what its backward
holds (block_working_set). The half about the step is blocks.py's; a model
file imports both and no other model.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.blocks import (KindShard, RematCandidate, RematPolicy,
                                   StepCounters, checkpoint_kinds,
                                   choose_remat_policy_kinds, model_working_set)
from ray_tpu.ops import moe
from ray_tpu.tracing import names as scopes


# one head whose float32 logits of the whole sequence stay under this takes
# them whole (head_rows): [B, S, V] is 4.2 GB at llama_7b's 8 x 4,096 x 32,000.
# Past it the head goes in chunks whose logits stay under it wherever that
# leaves a chunk tokens enough (head_chunk_rows): 64 MiB is the largest
# buffer the TPU compiler gives the chip's fast memory — S(1) on the chunk's
# logits in the compiled step — and every pass over a chunk's logits, the two
# gradient products' operands among them, then reads them from there
HEAD_CHUNK_BYTES = 2 ** 26
# the least tokens a chunk of the chunked head holds, whatever its logits then
# take: the chunk's d lm_head product is 2 · tokens · D · V operations over a
# carry of 8 · D · V bytes, tokens / 4 operations a byte whatever D and V, and
# a v5e does 240 in the time it moves one (197 TFLOP/s, 819 GB/s): under ~960
# tokens the product waits for the carry (PERF.md §6, PR 65)
HEAD_CHUNK_TOKENS = 1024
# an MLP whose hidden tensor of the whole sequence passes this takes the
# sequence in chunks (mlp_rows): a SwiGLU's backward holds five of them —
# 3.6 GB at 32,768 x 11,008, which one chip does not have beside EvaByte's
# state
MLP_CHUNK_BYTES = 2 ** 28
MXU = 128       # a matmul dim below this still costs a full pass


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def rmsnorm(x, g, eps: float, unit_offset: bool = False):
    """x / rms(x) · g, or · (1 + g) with ``unit_offset``; float32 statistics."""
    xf = x.astype(jnp.float32)
    rms = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    scale = 1.0 + g.astype(jnp.float32) if unit_offset else g
    return (xf * rms).astype(x.dtype) * scale.astype(x.dtype)


def head_rmsnorm(x, g, eps: float, axis: int):
    """RMSNorm over ``axis`` (the head's width, wherever the layout has it),
    float32 statistics, one gain vector for every head: QK-norm."""
    xf = x.astype(jnp.float32)
    rms = lax.rsqrt(jnp.mean(xf * xf, axis=axis, keepdims=True) + eps)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    return (xf * rms).astype(x.dtype) * g.astype(x.dtype).reshape(shape)


def yarn_inv_freq(dim: int, theta: float, factor: float, original_len: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's ``dim`` / 2 rotary frequencies (arXiv:2309.00071, as
    modeling_deepseek.py's DeepseekV2YarnRotaryEmbedding makes them): channel
    pair i keeps ``theta^(-2i/dim)`` where it turns more than ``beta_fast``
    times over the ``original_len`` positions the model was trained at, takes
    it divided by ``factor`` where it turns fewer than ``beta_slow`` times,
    and a linear blend of the two between (the correction range, floored and
    ceiled: 10 and 23 of 32 at the published 64 / 10,000 / 40 / 4,096 / 32 /
    1). Host arithmetic on sizes: a constant of the step."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)

    def turns_at(beta):     # the (fractional) pair that turns `beta` times
        return (dim * math.log(original_len / (2 * math.pi * beta))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim // 2 - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         s_minor: bool = False, *, inv_freq=None,
         span: Optional[Tuple[int, int]] = None) -> jax.Array:
    """Rotary embedding, HF-llama convention: x [..., S, hd] with the head
    dim split as [first half, second half] (rotate_half), NOT interleaved.
    With ``s_minor`` x is [..., hd, S] (head_layout's order for a narrow
    head) and so is the result.

    What a caller may state beside ``theta``: ``inv_freq``, the frequencies
    themselves, one a channel pair (YaRN's blend: yarn_inv_freq; ``theta`` is
    then not read); ``span`` = (first, past-the-last) channel of the head that
    rotates, the others passing as they are (latent attention rotates 64 of
    its 192: cos is 1, sin 0 and the permutation empty outside the span, so
    no tensor is sliced or put together)."""
    hd = x.shape[-2] if s_minor else x.shape[-1]
    lo, hi = span or (0, hd)
    half = (hi - lo) // 2
    if inv_freq is None:
        freqs = 1.0 / theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [S, half]
    if span is not None:
        return _rope_span(x, angles, s_minor, lo, hi)
    # x·cos + rotate_half(x)·sin, rotate_half(x) = [-x2, x1] = x @ R with R a
    # signed permutation (exact in any dtype): a [hd, hd] matmul a head, 0.5 %
    # of a block's operations, where slicing the head dim in two makes
    # tensors of half a head — 64 of 128 lanes, each taking what a whole one
    # does; four stood in HBM in the 32,768-token backward
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)             # [S, hd]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    eye = jnp.eye(half, dtype=x.dtype)
    zero = jnp.zeros_like(eye)
    rot = jnp.block([[zero, eye], [-eye, zero]])                      # x @ rot
    if s_minor:
        cos, sin = cos.T, sin.T                                       # [hd, S]
        rotated = jnp.einsum("...ds,de->...es", x, rot)
    else:
        rotated = jnp.einsum("...d,de->...e", x, rot)
    return (x.astype(jnp.float32) * cos
            + rotated.astype(jnp.float32) * sin).astype(x.dtype)


def _rope_span(x, angles, s_minor: bool, lo: int, hi: int):
    """rope's x·cos + (x @ R)·sin over channels [lo, hi) of the head:
    outside the span cos is 1, sin 0 and R empty."""
    hd = x.shape[-2] if s_minor else x.shape[-1]
    half = (hi - lo) // 2
    rot = np.zeros((hd, hd), np.float32)              # x @ rot
    i = np.arange(half)
    first, second = lo + i, lo + half + i
    rot[first, second], rot[second, first] = 1.0, -1.0
    cos, sin = (jnp.concatenate([fn(angles)] * 2, axis=-1)
                for fn in (jnp.cos, jnp.sin))
    edges = ((0, 0), (lo, hd - hi))
    cos = jnp.pad(cos, edges, constant_values=1.0)    # [S, hd]
    sin = jnp.pad(sin, edges)
    rot = jnp.asarray(rot, x.dtype)
    if s_minor:
        cos, sin = cos.T, sin.T                       # [hd, S]
        rotated = jnp.einsum("...ds,de->...es", x, rot)
    else:
        rotated = jnp.einsum("...d,de->...e", x, rot)
    return (x.astype(jnp.float32) * cos
            + rotated.astype(jnp.float32) * sin).astype(x.dtype)


def made_once(x):
    """x, written to HBM once where it stands — and, since AD carries the
    barrier to the cotangent, its cotangent likewise. For the seam between
    elementwise work (a norm, a gate) and the products or the kernel that
    read its result: left alone, XLA's TPU fusion makes such a value INSIDE
    every product that reads it, as that product's operand, and a
    [16384, 2048] x [2048, 4096] product with a norm's float32 arithmetic
    over several inputs in front of it takes 2.3-2.5 ms where the bare one
    takes 1.45-1.7 (PERF.md section 6, PR 49).

    The rule a new model file follows, with its two measurements (PR 49 in
    the MiniCPM-SALA cell, PR 67 in the Trinity cell; PERF.md section 6):

    - a projection whose output goes through a norm or a rotation takes
      ``cotangent_made_once``: the norm's backward is one pass in front of
      the projection's two backward products (PR 49: d W 2.34 -> 1.71 ms)
      — where XLA stores the product's output in the order the norm reads
      it; where it re-orders on the way (PR 67, hd 128 in front of the
      hd-minor flash pair) the seam gave nothing (+1.8 ms of 926);
    - the weights ``cast_in_the_loop`` hands a layer are ``made_once`` before
      the products that read them: in a layer that stands outside a scan the
      cast, and a transposing copy of the float32 weight with it, is else an
      operand fusion of the first forward's product (PR 67: a [32768, 2048]
      x [2048, 4096] product 7.7 ms with it, 2.9 bare, 2.8 its arithmetic;
      the step 953.3 -> 925.8 ms by that seam alone);
    - a norm read by SEVERAL products is ``made_once`` where the trace says
      its passes cost less than they take out of the products — they did in
      PR 49's seams (one reader each), and did NOT for a [32768, 2048] ln1
      in front of four readers (PR 67: the norm's own passes +5.7 ms a step
      for 2.7 off the products): measure, do not assume;
    - a value that XLA makes as a product's OUTPUT fusion in an order its
      next reader does not take (a gate on the output of a kernel) gains
      nothing from a barrier — the re-ordering copies stay, and cost more
      alone (PR 67: 13.1 -> 29.4 ms): that pass is a kernel's
      (ops/attention_pointwise.py)."""
    return lax.optimization_barrier(x)


@jax.custom_vjp
def cotangent_made_once(x):
    """x untouched, so that what makes it and what reads it fuse as they
    did; its cotangent ``made_once``. For a projection whose output goes
    through a norm: the norm's backward is then one pass in front of the
    projection's two backward products, not a part of each."""
    return x


cotangent_made_once.defvjp(lambda x: (x, None),
                           lambda _, g: (made_once(g),))


def residual_add(x, y):
    """x + y in float32, the stream stored in x's dtype (the released
    EvaByte's ``fp32_skip_add``; y is a matmul's float32 accumulator)."""
    return (x.astype(jnp.float32) + y.astype(jnp.float32)).astype(x.dtype)


def attention_on_mesh(attention_impl: str):
    """(impl, interpret, mesh): "pallas" or "xla" on the mesh in use. Ring
    attention is models/gpt2.py's own path, taken before it comes here."""
    from ray_tpu.ops.attention import resolve_attention
    from ray_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.current_mesh()
    impl, interpret = resolve_attention(attention_impl, mesh)
    if impl == "ring":
        raise NotImplementedError("only models/gpt2.py has a ring-attention "
                                  "path; use a mesh without a cp axis")
    return impl, interpret, mesh


def head_layout(head_dim: int, v_dim: Optional[int] = None) -> str:
    """The axis order a block projects its heads in (a flash_attention
    layout over b, h, s, d), from the head's widths alone (q's and k's, and
    v's where it is another), whatever attention then runs: the order the
    flash kernels take at those widths with no transpose at their edge
    (ops/attention.kernel_layout: the one rule). A head narrower than a lane
    tile (GPT-2's 64) goes S-minor with the heads leading, "hbds" = [H, B,
    hd, S] — how XLA stores such a projection's output and the layer scan's
    saved stack of it whatever the einsum says; one of whole tiles "bhsd"."""
    from ray_tpu.ops.attention import S_MINOR, kernel_layout

    return "hbds" if kernel_layout(head_dim, v_dim) == S_MINOR else "bhsd"


def causal_attention(q, k, v, attention_impl: str, *, layout: str = "bhsd",
                     scale: Optional[float] = None,
                     window: Optional[int] = None,
                     grouped_kv: bool = False):
    """q [B,H,S,hd], k/v [B,KH,S,hd] → [B,H,S,hd], causal (head-major layout —
    the hd-minor flash kernels' own, so the hot path has no boundary
    transposes); KH heads of k and v serve H / KH heads of q each. Another
    head-major ``layout`` (head_layout's) says where the four dims of the
    three and of the result are. Which kernel pair runs is the head widths'
    either way; a block that hands the other pair's order pays the
    transposes at the kernel's edge. v, and with it the result, may be of
    another width than q and k; ``scale`` multiplies the logits (1/√hd of
    q's width where none is given). Under ``window`` query i sees the keys
    j <= i with i − j < window: the flash pair walks that band alone
    (ops/attention.flash_attention), the XLA path masks it. With
    ``grouped_kv`` the flash pair is handed k and v at their own KH heads and
    reads each where it stands (S8: nothing repeated in HBM; PERF.md §6, PR 66
    says what the repeat cost); without it — every caller before PR 66, whose
    lowered steps stay as they were — they are repeated to q's heads first."""
    from ray_tpu.ops.attention import flash_attention_sharded

    impl, interpret, mesh = attention_on_mesh(attention_impl)
    heads = layout.index("h")
    groups = q.shape[heads] // k.shape[heads]
    if groups > 1 and not (grouped_kv and impl == "pallas"):
        k = jnp.repeat(k, groups, axis=heads)
        v = jnp.repeat(v, groups, axis=heads)
    if impl == "pallas":
        return flash_attention_sharded(
            q, k, v, mesh, layout=layout, causal=True, interpret=interpret,
            scale=scale, **({} if window is None else {"window": window}))
    # XLA path: einsum + mask; XLA fuses the softmax chain.
    S = q.shape[layout.index("s")]
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[layout.index("d")])
    at_q, at_k = layout.replace("s", "q"), layout.replace("s", "k")
    rows = layout[:2]                       # the logits keep the rows' order
    logits = jnp.einsum(f"{at_q},{at_k}->{rows}qk", q, k) * scale
    mask = jnp.tril(jnp.ones((S, S), dtype=bool))
    if window is not None:
        mask &= ~jnp.tril(jnp.ones((S, S), dtype=bool), -window)
    logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum(f"{rows}qk,{at_k}->{at_q}", probs, v)


def is_flash(attention_impl: str, mesh) -> bool:
    """Attention on this mesh is a Pallas kernel: its o and lse exist."""
    from ray_tpu.ops.attention import resolve_attention

    return resolve_attention(attention_impl, mesh)[0] == "pallas"


def cast_in_the_loop(p, x, dt, keys):
    """The layer's matmul weights ``keys`` in the compute dtype, cast inside
    the layer loop. A plain ``astype`` of a layer sliced out of the stack the
    TPU compiler turns into one cast of the WHOLE stack before the loop
    (through an ``optimization_barrier`` too) and keeps the copy for the
    length of the step: 1.5 GB beside EvaByte's four layers, which the chip
    does not have.
    A factor of one that depends on the loop's carry keeps the cast where it
    is written; it costs a read of the layer's f32 weights a use, 0.6 % of
    the 32,768-token step."""
    one = lax.stop_gradient(1.0 + 0.0 * x[0, 0, 0].astype(jnp.float32))
    return {k: (p[k] * one).astype(dt) for k in keys}


def all_but(tree, *names: str):
    """``tree``'s structure with True at every leaf but those whose key is
    one of ``names``. An optimizer's weight-decay ``mask`` (optax's) for a
    model whose expert layers hold selection biases: buffers — no gradient
    reaches them, and a decay must not."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) not in names, tree)


def param_count(init, *buffers: str) -> int:
    """The elements of the tree ``init()`` makes (its shapes alone: nothing
    is made), all but the leaves named ``buffers``: the parameters a step
    moves."""
    shapes = jax.eval_shape(init)
    return sum(math.prod(p.shape) for p, counted in zip(
        jax.tree.leaves(shapes), jax.tree.leaves(all_but(shapes, *buffers)))
        if counted)


def expert_step_counters(layers, n_experts: int, top_k: int, held: moe.Held,
                         fields: Tuple[str, ...] = scopes.STEP_EXPERT_LOAD_ARGS,
                         float_fields: Tuple[str, ...] = ()
                         ) -> Optional[StepCounters]:
    """What a model with expert layers (ops/moe.routed_experts' load a layer,
    handed out of its loss as blocks.packed_aux packs it) offers a step
    factory as its ``step_counters(cfg)``: a row an expert layer under the
    ids ``layers`` — ``model/expert_load``'s ``layer`` —, read against the
    row buffer a batch's tokens give (moe.step_load_static); None for a
    pattern without an expert layer."""
    layers = tuple(layers)
    if not layers:
        return None
    return StepCounters(
        scopes.EXPERT_LOAD_KIND, fields, layers,
        partial(moe.step_load_static, n_experts=n_experts, top_k=top_k,
                held=held), float_fields)


def expert_layer_ids(pattern: str, first_layer: int,
                     experts: Dict[str, bool]) -> Tuple[int, ...]:
    """The published index of every expert layer of ``pattern`` (one
    character a layer, ``experts[kind]`` says which kinds hold experts), in
    the order they come: ``model/expert_load``'s ``layer``."""
    return tuple(first_layer + i for i, kind in enumerate(pattern)
                 if experts[kind])


def untied_logits(x, lm_head, dtype):
    """The head's input x [B, S, D] → logits [B, S, vocab] through an untied
    head [D, vocab] in the compute dtype: a family's public ``forward``."""
    return jnp.einsum("bsd,dv->bsv", x, lm_head.astype(dtype))


def rows_under(seq: int, bytes_a_row: int, limit: int) -> int:
    """The largest power-of-two fraction of ``seq`` whose rows stay under
    ``limit`` bytes (``seq`` itself where they do)."""
    rows = seq
    while rows % 2 == 0 and rows * bytes_a_row > limit:
        rows //= 2
    return rows


def mlp_rows(batch: int, seq: int, d_model: int, d_ff: int,
             itemsize: int, hidden_tensors: int = 5) -> int:
    """Rows of the sequence the MLP takes at a time: all of them where a
    hidden tensor of the whole sequence stays under MLP_CHUNK_BYTES. A longer
    sequence goes in chunks whose hidden tensors (a SwiGLU's backward holds
    five, an MLP with one hidden tensor three) together take what two
    of the block's [B, S, D] activations do — a fifth more beside the eight
    of that size that wait in the chunk's backward for the attention's
    (rematted_working_set). On the chip the 32,768-byte EvaByte step's
    MLP backward takes 403.6 ms at the 4,096 rows this gives, 405.1 at 2,048
    and 420.7 at 8,192, and its compiled step needs 0.7 GB less than at 8,192
    (PERF.md §6, PR 32)."""
    if batch * seq * d_ff * itemsize <= MLP_CHUNK_BYTES:
        return seq
    return rows_under(seq, hidden_tensors * batch * d_ff * itemsize,
                      2 * batch * seq * d_model * itemsize)


def swiglu(h, w_gate, w_up, w_down):
    """down(silu(gate(h)) · up(h)) of a normed h [B, rows, D], float32 (the
    down-projection's accumulator): the gated MLP's three products under
    scope ``mlp``, its two hidden tensors named. The norm before it and the
    residual after it are the caller's."""
    with jax.named_scope(scopes.MLP):
        gate = checkpoint_name(jnp.einsum("bsd,df->bsf", h, w_gate),
                               scopes.RES_MLP_GATE)
        up = checkpoint_name(jnp.einsum("bsd,df->bsf", h, w_up),
                             scopes.RES_MLP_UP)
        return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, w_down,
                          preferred_element_type=jnp.float32)


def in_row_chunks(fn, x, rows: int):
    """``fn(x)`` for a ``fn`` that works each row of x [B, S, D] alone (a
    feed-forward half, norm and residual and all), the sequence taken
    ``rows`` at a time (mlp_rows) where that is fewer than S, each chunk its
    own ``checkpoint``: a chunk's hidden tensors are made again in its
    backward and never exist for the whole sequence (models/llama.py's _mlp
    says what that is worth)."""
    B, S, D = x.shape
    if rows == S:
        return fn(x)
    chunks = x.reshape(B, S // rows, rows, D).swapaxes(0, 1)
    out = lax.map(jax.checkpoint(fn), chunks)
    return out.swapaxes(0, 1).reshape(B, S, D)


def head_chunk_rows(batch: int, seq: int, columns: int) -> int:
    """Rows of the sequence a chunk of the chunked head holds
    (ops/cross_entropy.chunked_head_xent): as many as keep its float32
    logits [batch, rows, columns] under HEAD_CHUNK_BYTES, but never fewer
    than HEAD_CHUNK_TOKENS tokens a chunk — the float32 ``d lm_head``, the
    chunk scan's carry, is read and written whole by EVERY chunk, and a
    shorter chunk's ``d lm_head`` product waits for it."""
    return rows_under(seq, batch * columns * 4,
                      max(HEAD_CHUNK_BYTES, HEAD_CHUNK_TOKENS * columns * 4))


def head_rows(batch: int, seq: int, columns: int, heads: int) -> int:
    """Rows of the sequence the head takes at a time where it goes in chunks
    (head_chunk_rows) — more heads than one always do — and 0 where one head
    takes the sequence whole (softmax_xent): where its float32 logits stay
    under HEAD_CHUNK_BYTES."""
    if heads == 1 and rows_under(seq, batch * columns * 4,
                                 HEAD_CHUNK_BYTES) == seq:
        return 0
    return head_chunk_rows(batch, seq, columns)


def head_targets(targets: jax.Array, n_heads: int) -> jax.Array:
    """targets [B, S] (the next token, -1 = ignore) → [B, S, n_heads]: head
    p's target at t is targets[t + p], -1 past the row's end."""
    S = targets.shape[1]
    padded = jnp.pad(targets, ((0, 0), (0, n_heads - 1)), constant_values=-1)
    return jnp.stack([padded[:, p:p + S] for p in range(n_heads)], axis=-1)


@jax.named_scope(scopes.LM_HEAD_LOSS)
def lm_head_loss(x, targets, lm_head, dtype, n_pred_heads: int = 1,
                 weights=None):
    """Untied head(s) + cross-entropy over final hidden states [B, S, D]: the
    mean over the heads of each head's mean over its valid targets. Where the
    head goes in chunks (head_rows) the logits and their gradient
    are never one tensor, and a chunk's logits are multiplied out once a
    step: the chunk that makes its loss makes its gradient
    (ops/cross_entropy.chunked_head_xent). With ``weights`` [B, S] float32
    (one head): Σ weight · nll over the valid targets ÷ their number, always
    through the chunked head, whose gradient reaches the weights too."""
    from ray_tpu.ops import cross_entropy

    B, S = targets.shape
    lm_head = lm_head.astype(dtype)
    if weights is not None:
        return cross_entropy.chunked_head_xent(
            x, head_targets(targets, 1), lm_head,
            head_chunk_rows(B, S, lm_head.shape[1]), weights[..., None])
    rows = head_rows(B, S, lm_head.shape[1], n_pred_heads)
    if not rows:
        # fused CE (ops/cross_entropy.py): no [B, S, V] float32 residual
        nll = cross_entropy.softmax_xent(
            jnp.einsum("bsd,dv->bsv", x, lm_head), targets)
        return jnp.sum(nll) / jnp.maximum(jnp.sum(targets >= 0), 1)
    return cross_entropy.chunked_head_xent(
        x, head_targets(targets, n_pred_heads), lm_head, rows)


def mtp_join(x, targets, wte, enorm, hnorm, eh_proj, eps: float):
    """A multi-token-prediction module's input and targets (DeepSeek-V3,
    arXiv:2412.19437 §2.2): position t joins the trunk's x_t [B, S, D]
    (before the final norm) with the embedding of token t+1 (= targets[t];
    ``wte`` in the compute dtype) — each under a norm of its own, the
    embedding's half first, through ``eh_proj`` [2·D, D] — and predicts token
    t+2 (= targets[t+1]); no target where either is past the row's end."""
    has_next = targets >= 0
    later = jnp.pad(targets[:, 1:], ((0, 0), (0, 1)), constant_values=-1)
    with jax.named_scope(scopes.EMBED):
        e = wte[jnp.where(has_next, targets, 0)]
    both = jnp.concatenate([rmsnorm(e, enorm, eps), rmsnorm(x, hnorm, eps)],
                           axis=-1)
    h = jnp.einsum("bse,ed->bsd", both, eh_proj.astype(x.dtype))
    return h, jnp.where(has_next, later, -1)


class BlockShard(NamedTuple):
    """One chip's share of a step, in elements: global shapes ÷ the mesh axes
    that split them. Everything the remat rule computes, it computes from
    this and n_layer. The model states its block's shapes (gpt2.block_shard,
    llama.block_shard); the defaults are GPT-2's block."""
    batch: int            # rows of the batch on this chip
    seq: int
    d_model: int
    heads: int            # attention heads on this chip
    head_dim: int
    d_ff: int             # MLP hidden width on this chip
    vocab: int            # LM-head columns on this chip
    dtype_bytes: int      # of an activation
    flash: bool           # attention is a Pallas kernel: its o and lse exist
    dense_mlp: bool       # the MLP is the dense one: its hidden tensors exist
    kv_heads: int = 0     # heads of k and v where q has more (0: as many)
    # the dense MLP's named hidden tensors, each d_ff wide: one before a
    # gelu, two (gate, up) in a SwiGLU
    mlp_hidden: Tuple[str, ...] = (scopes.RES_MLP_HIDDEN,)
    window: int = 0       # > 0: the EVA mixer (ops/eva_attention.py) — a query
    chunk: int = 0        # sees its window and one summary a chunk before it
    # rows of the sequence the LM head and the MLP take at a time (0: all of
    # them). An MLP that takes fewer makes its hidden tensors again in each
    # chunk's backward: they are no candidates
    head_rows: int = 0
    mlp_rows: int = 0
    # the block casts its layer's matmul weights inside the layer loop
    # (cast_in_the_loop): one layer's stand in the block's backward
    cast_in_loop: bool = False
    # channels of the layers' carry where it is wider than d_model (a
    # hyper-connected model's n streams: models/hyper_connections.py); 0: d_model
    carry_width: int = 0
    # how often the model runs its layers on one set of weights
    # (blocks.run_repeated): the block inputs that wait, and the head's rows,
    # are this many times a pass's
    passes: int = 1
    # each sublayer's output goes through a norm of its own before the
    # residual add (a sandwich norm): the float32 output and the normed one
    # wait in the block's backward, and so do their cotangents
    out_norms: bool = False
    # > 0: the flash pair is given this causal window (a query sees that many
    # keys at most; ops/attention.py walks the band alone), so its o and lse
    # cost the band's pairs to make again, not the triangle's. NOT ``window``
    # above, which selects the EVA mixer's arithmetic
    flash_window: int = 0


def shard_block(whole: BlockShard, mesh) -> BlockShard:
    """A block stated in global shapes, on one chip of ``mesh``: batch over
    the data axes that divide it, heads / MLP width / vocab over tp, the
    sequence over cp."""
    from ray_tpu.ops.attention import batch_head_axes

    if mesh is None:
        return whole
    batch, heads, kv_heads = whole.batch, whole.heads, whole.kv_heads
    d_ff, vocab, seq = whole.d_ff, whole.vocab, whole.seq
    batch_axes, head_ax = batch_head_axes(mesh, batch, heads)
    for ax in batch_axes or ():
        batch //= mesh.shape[ax]
    tp, cp = mesh.shape.get("tp", 1), mesh.shape.get("cp", 1)
    if head_ax:
        heads //= tp
        if kv_heads % tp == 0:
            kv_heads //= tp
    if d_ff % tp == 0:
        d_ff //= tp
    if vocab % tp == 0:
        vocab //= tp
    if seq % cp == 0:
        seq //= cp
    return whole._replace(batch=batch, heads=heads, kv_heads=kv_heads,
                          d_ff=d_ff, vocab=vocab, seq=seq)


def remat_candidates(s: BlockShard) -> List[RematCandidate]:
    """The block's named residuals as (names kept together, bytes a layer,
    FLOPs a layer to recompute them, bytes keeping them frees), most FLOPs
    per byte first; of equal ones the one that frees more, then the block's
    own order. A matmul output of width N contracted over
    K costs 2·K·N a row and holds N elements, so the qkv, proj and fc outputs
    all come to K FLOPs per element; the flash kernel's o comes to about
    2·S per element (causal: half of two S×S matmuls, whose head_dim side
    fills the MXU only from 128 up), so it leads at long sequences and
    trails at short ones. lse goes with o: neither is of use alone.

    A block with the EVA mixer has that kernel's o and lse in their place: a
    query's keys are half its window and, on average, the summaries of half
    the sequence — (w + S/c − w/c) per element where causal attention has S.
    Its summaries (1/chunk the size of k and v) come from a pass over k and v
    that is a few operations an element: they trail everything. That pass
    reads k in float32, and a k that is made again stands in both precisions
    from the block's second forward to the pass's backward — across the whole
    MLP backward (_eva_k_f32). A kept k is read from its stack when the pass
    needs it: keeping k frees those bytes, so k leads q."""
    tokens = s.batch * s.seq
    a = s.dtype_bytes
    attn_width = s.heads * s.head_dim
    kv_width = (s.kv_heads or s.heads) * s.head_dim
    out = [RematCandidate((name,), tokens * width * a,
                          2 * tokens * s.d_model * width, frees)
           for name, width, frees in ((scopes.RES_Q, attn_width, 0),
                                      (scopes.RES_K, kv_width, _eva_k_f32(s)),
                                      (scopes.RES_V, kv_width, 0))]
    if s.flash and s.window:
        keys = s.window + (s.seq - s.window) // s.chunk      # twice the mean
        out.append(RematCandidate(
            (scopes.RES_EVA_O, scopes.RES_EVA_LSE),
            tokens * s.heads * (s.head_dim * a + 4),
            2 * s.batch * s.heads * s.seq * keys * max(s.head_dim, MXU),
        ))
        out.append(RematCandidate(
            (scopes.RES_EVA_KT, scopes.RES_EVA_VT),
            2 * tokens // s.chunk * attn_width * a,
            6 * tokens * attn_width,
        ))
    elif s.flash:
        out.append(RematCandidate(
            (scopes.RES_FLASH_O, scopes.RES_FLASH_LSE),
            tokens * s.heads * (s.head_dim * a + 4),
            2 * s.batch * s.heads * twice_causal_pairs(s.seq, s.flash_window)
            * max(s.head_dim, MXU),
        ))
    out.append(RematCandidate((scopes.RES_MID,), tokens * s.d_model * a,
                              2 * tokens * attn_width * s.d_model))
    if s.dense_mlp and s.mlp_rows in (0, s.seq):
        out += [RematCandidate((name,), tokens * s.d_ff * a,
                               2 * tokens * s.d_model * s.d_ff)
                for name in s.mlp_hidden]
    return sorted(out, key=lambda c: (-c.flops / c.nbytes, -c.frees))


def twice_causal_pairs(seq: int, window: int = 0) -> int:
    """Twice the (query, key) pairs of a causal row of ``seq`` tokens under a
    window of ``window`` keys: S · S as the rule has always priced the
    triangle where there is none (0) or it hides nothing, else the band's
    w(w+1) + 2(S − w)·w."""
    if not 0 < window < seq:
        return seq * seq
    return window * (window + 1) + 2 * (seq - window) * window


def _eva_k_f32(s: BlockShard) -> int:
    """Bytes of the float32 k the EVA summary pass reads (0 with no window):
    the compiler writes it beside k out of the rotation."""
    if not s.window:
        return 0
    return s.batch * s.seq * (s.kv_heads or s.heads) * s.head_dim * 4


def rematted_working_set(s: BlockShard, n_layer: int) -> int:
    """Bytes of activations a chip needs for a step whose blocks keep only
    their inputs, as the rule counts them: the stack of block inputs; the LM
    head's logits, their gradient and one float32 copy inside the softmax;
    one block's whole residual set, live while its backward runs; the
    largest parameter (the embedding) gathered in the compute dtype beside
    its unreduced float32 gradient. The block's set peaks in the MLP's
    backward, where everything the attention's backward will read is already
    made again and waits: four tensors of the stream's width and q, k, v, o,
    beside each of the MLP's hidden tensors and its gradient (and a gated
    MLP's product) for the rows it takes at a time. A block that states more
    holds more there (PERF.md §6, PR 32: the 32,768-byte EvaByte step
    compiled for a v5e). With the EVA mixer the summary pass's float32 k
    waits too (_eva_k_f32), unless k is kept — remat_candidates says what
    keeping it frees. Where the block casts its layer's weights inside the
    loop they stand twice in the compute dtype: the cast, and the copy the
    compiler moves ahead of the MLP's loop. An estimate from shapes — XLA's
    schedule decides the real figure (PR 28: from 0.08 GiB under at the
    GPT-2 cells' shapes to 8 over; PR 32: 0.13 GB over at the EvaByte cell's)
    — which is what the reserve is for."""
    return model_working_set(s, n_layer) + block_working_set(s)


def block_working_set(s: BlockShard) -> int:
    """rematted_working_set's part that is one block's: its whole residual
    set, live while its backward runs. Of a model whose layers are of more
    than one kind each run's largest counts in its phase
    (blocks.backward_phases)."""
    tokens = s.batch * s.seq
    a = s.dtype_bytes
    attn_width = s.heads * s.head_dim
    kv_width = (s.kv_heads or s.heads) * s.head_dim
    hidden = 2 * len(s.mlp_hidden) + (len(s.mlp_hidden) - 1)
    block = a * (tokens * (4 * s.d_model + 4 * attn_width)
                 + s.batch * (s.mlp_rows or s.seq) * hidden * s.d_ff)
    weights = 2 * a * s.d_model * (
        2 * attn_width + 2 * kv_width + (len(s.mlp_hidden) + 1) * s.d_ff
    ) if s.cast_in_loop else 0
    out_norms = 4 * tokens * s.d_model * 4 if s.out_norms else 0
    return block + _eva_k_f32(s) + weights + out_norms


def swiglu_price(batch: int, seq: int, rows: int, d_model: int, d_ff: int,
                 itemsize: int, names: Tuple[str, str]
                 ) -> Tuple[Tuple[RematCandidate, ...], int]:
    """A SwiGLU half of hidden width ``d_ff`` that takes ``rows`` of the
    sequence at a time (0: all), for the remat rule: (what it may keep — its
    two hidden tensors, ``names``, where it is not chunked: a chunk makes
    them again —, the bytes its backward holds: a chunk's five hidden
    tensors, the weights cast twice)."""
    tokens = batch * seq
    kept = tuple(
        RematCandidate((name,), tokens * d_ff * itemsize,
                       2 * tokens * d_model * d_ff)
        for name in names) if rows in (0, seq) else ()
    return kept, itemsize * (batch * (rows or seq) * 5 * d_ff
                             + 2 * 3 * d_model * d_ff)


def routing_candidates(tokens: int, d_model: int, n_experts: int, top_k: int,
                       held_count: int) -> Tuple[RematCandidate, ...]:
    """What an expert layer's routing decided (ops/moe.py tags it), for the
    remat rule: the scores at three bf16 passes of the router's float32
    product; the ``top_k``'s last value and index at a full sort of each
    row's n_experts with an index operand (what the TPU lowers it to) and
    the pairs' sorted keys with their gates at theirs (moe.sort_ops): a MB or
    two that spare a sort rank first."""
    rows = moe.row_buffer(tokens, n_experts, top_k, held_count)
    passes = moe.buffer_passes(tokens, n_experts, top_k, held_count)
    return (
        RematCandidate((scopes.RES_MOE_SCORES,), tokens * n_experts * 4,
                       3 * 2 * tokens * d_model * n_experts),
        RematCandidate((scopes.RES_MOE_KTH, scopes.RES_MOE_LAST), tokens * 8,
                       tokens * moe.sort_ops(n_experts, operands=2)),
        RematCandidate((scopes.RES_MOE_PAIR_KEY, scopes.RES_MOE_PAIR_GATE),
                       passes * rows * 8,
                       moe.sort_ops(tokens * held_count, operands=2)))


def gated_experts_working_set(tokens: int, d_model: int, n_experts: int,
                              top_k: int, held_count: int, d_expert: int,
                              itemsize: int) -> Tuple[int, int]:
    """What the backward of moe.gated_moe's routed part holds, as two terms a
    family combines by what it measured: (the half's stream — the experts'
    input and float32 sum with their cotangents — and the routing's three
    [tokens, n_experts] tensors; the routed passes' — one pass's rows (input,
    two hidden tensors, their product, the gradients of each), the held
    experts' weights cast and the float32 sums their gradients are made
    in)."""
    rows = moe.row_buffer(tokens, n_experts, top_k, held_count)
    weights = 3 * held_count * d_model * d_expert
    return (tokens * d_model * (2 * itemsize + 8) + tokens * n_experts * 12,
            itemsize * rows * (2 * d_model + 6 * d_expert)
            + (itemsize + 4) * weights)


def choose_remat_policy(shard: BlockShard, n_layer: int,
                        bytes_limit: Optional[int],
                        resident_bytes: int) -> RematPolicy:
    """choose_remat_policy_kinds for ``n_layer`` blocks of one kind."""
    return choose_remat_policy_kinds(
        [KindShard(n_layer, tuple(remat_candidates(shard)),
                   block_working_set(shard))],
        rematted_working_set(shard, n_layer), bytes_limit, resident_bytes)


def checkpoint_block(block_fn, remat: bool, shard: BlockShard,
                     n_layer: int, grad_bytes: int = 0):
    """``block_fn(x, layer_params)`` as the layer scan calls it, for any model
    whose block carries the names of tracing/names.RESIDUALS; n_layer is how
    many of them one chip runs (a pipeline stage's share under pp): a
    policy-``checkpoint`` that keeps the block's input and, of its named
    residuals, those the chip has room for with ``remat`` and all of them
    without. Left to its own AD the scan stacks every elementwise
    intermediate too (the gelu alone: five ``[n_layer, B, S, d_ff]`` tensors
    beside its input), and copying those in and out of the stacks cost the
    gpt2-124m step 9.2 of its 74.0 ms and 4.2 of its 9.25 GiB; recomputing
    them costs 0.5 ms (PERF.md §6, PR 30). Without remat that holds only
    where the names cover every output that is dear to make again — a Pallas
    attention kernel's and the dense MLP's; XLA and ring attention and the
    experts tag none of theirs, so those blocks stay as AD leaves them. The
    one-kind case of checkpoint_kinds. Where the shard states ``passes``
    (blocks.run_repeated) the block is applied n_layer x passes times and
    ``grad_bytes``, one LAYER's weight gradients on a chip, says what one
    pass's stack of them takes beside the running sum."""
    if not remat and not (shard.flash and shard.dense_mlp):
        return block_fn
    kind = KindShard(n_layer * shard.passes, tuple(remat_candidates(shard)),
                     block_working_set(shard), grad_bytes)
    return checkpoint_kinds({"block": block_fn}, remat, shard,
                            {"block": kind}, [(("block",), n_layer)])["block"]
