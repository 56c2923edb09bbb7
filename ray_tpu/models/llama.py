"""LLaMA-family decoder in pure JAX, designed for the MXU and GSPMD.

Second flagship model family beside GPT-2 (models/gpt2.py): the modern
decoder recipe — RMSNorm (pre-norm, no biases), SwiGLU MLP, rotary position
embeddings, grouped-query attention, untied LM head. It runs on GPT-2's
machinery, not a copy of it: the same layer scan and policy-``checkpoint``
(``gpt2._run_blocks`` / ``_checkpointed``), the same remat rule on this
block's own shapes, the same scopes and residual names (tracing/names.py);
logical axis names on every parameter so any dp/fsdp/tp mesh works through
parallel/sharding.py rules, bf16 compute over f32 params, the Pallas kernels
in head-major layout.

The config selects the mixer — ``causal`` (flash attention) or ``eva``
(ops/eva_attention.py: exact softmax inside a window, one learned summary a
chunk of every earlier window) — the number of prediction heads (head p
predicts token t + 1 + p) and the norm's unit offset: with ``eva``, eight
heads and the offset this is EvaByte (``evabyte_6p5b``).

Numerics anchor: tests/test_llama_model.py checks logits against
HuggingFace transformers' LlamaForCausalLM on a tiny config — RoPE layout,
GQA repetition, and norm conventions all match the reference architecture
(the framework reference has no LLaMA model; this is new work, SURVEY §2.10
scope: "every model family").
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import gpt2
from ray_tpu.models.gpt2 import _round_up
from ray_tpu.tracing import names as scopes

# the head's float32 logits of one sequence chunk stay under this
# (_head_rows): [B, S, V] whole is 4.2 GB at llama_7b's 8 x 4,096 x 32,000
_HEAD_CHUNK_BYTES = 2 ** 26
# an MLP whose hidden tensor of the whole sequence passes this takes the
# sequence in chunks (_mlp_rows): a SwiGLU's backward holds five of them —
# 3.6 GB at 32,768 x 11,008, which one chip does not have beside EvaByte's
# state
_MLP_CHUNK_BYTES = 2 ** 28


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    seq_len: int = 2048
    n_layer: int = 22
    n_head: int = 32
    n_kv_head: int = 8            # grouped-query attention
    d_model: int = 2048
    d_ff: int = 5632              # SwiGLU hidden
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False           # as GPT2Config.remat: True recomputes what
                                  # does not fit (gpt2.choose_remat_policy)
    attention_impl: str = "auto"  # auto | xla | pallas
    mixer: str = "causal"         # causal | eva (window, chunk)
    window: int = 0
    chunk: int = 0
    n_pred_heads: int = 1         # head p predicts token t + 1 + p
    norm_unit_offset: bool = False   # norm scales by (1 + g), g born 0
    init_std: float = 0.02

    def __post_init__(self):
        if self.mixer not in ("causal", "eva"):
            raise ValueError(f"unknown mixer {self.mixer!r}")
        if self.mixer == "eva":
            if self.n_kv_head != self.n_head:
                raise ValueError("the eva mixer has no grouped heads: "
                                 "n_kv_head must equal n_head")
            if (self.chunk <= 0 or self.window % self.chunk
                    or self.seq_len % self.window):
                raise ValueError(
                    f"the eva mixer needs chunk | window | seq_len; got "
                    f"chunk={self.chunk} window={self.window} "
                    f"seq_len={self.seq_len}")
        if not isinstance(self.remat, bool):
            raise ValueError(f"remat must be True or False; got {self.remat!r}")
        if self.n_head % self.n_kv_head:
            raise ValueError(
                f"n_head={self.n_head} must be divisible by "
                f"n_kv_head={self.n_kv_head}"
            )
        if self.d_model % self.n_head:
            raise ValueError("d_model must be divisible by n_head")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, 128)

    @property
    def head_vocab(self) -> int:
        """Columns of one prediction head: the vocabulary padded so that the
        heads' one matmul is a whole number of 128-lane tiles wide."""
        return _round_up(self.vocab_size, 128 // math.gcd(self.n_pred_heads, 128))


def llama_tiny(**overrides) -> LlamaConfig:
    """Test-size config (CPU mesh friendly; HF-parity test uses it)."""
    return replace(
        LlamaConfig(vocab_size=256, seq_len=128, n_layer=2, n_head=4,
                    n_kv_head=2, d_model=64, d_ff=176),
        **overrides,
    )


def llama_1b(**overrides) -> LlamaConfig:
    """TinyLlama-1.1B shape."""
    return replace(LlamaConfig(), **overrides)


def llama_7b(**overrides) -> LlamaConfig:
    return replace(
        LlamaConfig(n_layer=32, n_head=32, n_kv_head=32, d_model=4096,
                    d_ff=11008, seq_len=4096),
        **overrides,
    )


def evabyte_6p5b(**overrides) -> LlamaConfig:
    """EvaByte 6.5B as published (huggingface.co/EvaByte/EvaByte): llama_7b's
    widths over bytes, EVA attention, eight heads."""
    return replace(
        llama_7b(vocab_size=320, seq_len=32768, rope_theta=100000.0,
                 mixer="eva", window=2048, chunk=16, n_pred_heads=8,
                 norm_unit_offset=True, init_std=0.01275),
        **overrides,
    )


def evabyte_tiny(**overrides) -> LlamaConfig:
    """Test-size EvaByte: 4 windows of 64, chunks of 8, 4 heads."""
    return replace(
        LlamaConfig(vocab_size=320, seq_len=256, n_layer=2, n_head=4,
                    n_kv_head=4, d_model=128, d_ff=352, rope_theta=100000.0,
                    mixer="eva", window=64, chunk=8, n_pred_heads=4,
                    norm_unit_offset=True, init_std=0.01275),
        **overrides,
    )


# --------------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------------- #

def logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    blocks = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads", "kv"),
        "wk": ("layers", "embed", "heads", "kv"),
        "wv": ("layers", "embed", "heads", "kv"),
        "wo": ("layers", "heads", "kv", "embed"),
        "mlp_norm": ("layers", "embed"),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    }
    if cfg.mixer == "eva":
        blocks["eva_phi"] = blocks["eva_mu"] = ("layers", "heads", "kv")
    return {
        "wte": ("vocab", "embed"),
        "blocks": blocks,
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def mesh_rules(cfg: LlamaConfig, mesh) -> Dict[str, str]:
    """What this config needs of this mesh (as gpt2.mesh_rules): no rule
    beyond the defaults, and no pipeline — the layer loop below has no stage
    schedule, so a pp axis would only repeat the whole model on every stage."""
    if mesh.shape.get("pp", 1) > 1:
        raise NotImplementedError(
            "pipeline parallelism is not implemented for the LLaMA family; "
            "use a pp=1 mesh"
        )
    return {}


def init(cfg: LlamaConfig, rng: jax.Array) -> Dict[str, Any]:
    D, H, KH, hd = cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    F, L, V = cfg.d_ff, cfg.n_layer, cfg.padded_vocab
    pd = cfg.param_dtype
    keys = iter(jax.random.split(rng, 11))
    std = cfg.init_std
    # a norm's scale is 1 at birth: g = 1, or g = 0 under (1 + g)
    norm_init = jnp.zeros if cfg.norm_unit_offset else jnp.ones

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape) * s).astype(pd)

    def eva_vector(key):
        # clip(N(0, 1), -1, 1) · hd^-1/2
        return (jnp.clip(jax.random.normal(key, (L, H, hd)), -1.0, 1.0)
                / math.sqrt(hd)).astype(pd)

    blocks = {
        "attn_norm": norm_init((L, D), pd),
        "wq": normal(next(keys), (L, D, H, hd)),
        "wk": normal(next(keys), (L, D, KH, hd)),
        "wv": normal(next(keys), (L, D, KH, hd)),
        "wo": normal(next(keys), (L, H, hd, D), std / math.sqrt(2 * L)),
        "mlp_norm": norm_init((L, D), pd),
        "w_gate": normal(next(keys), (L, D, F)),
        "w_up": normal(next(keys), (L, D, F)),
        "w_down": normal(next(keys), (L, F, D), std / math.sqrt(2 * L)),
    }
    wte, lm_head = normal(next(keys), (V, D)), normal(
        next(keys), (D, cfg.n_pred_heads * cfg.head_vocab))
    if cfg.mixer == "eva":
        blocks["eva_phi"] = eva_vector(next(keys))
        blocks["eva_mu"] = eva_vector(next(keys))
    return {
        "wte": wte,
        "blocks": blocks,
        "final_norm": norm_init((D,), pd),
        "lm_head": lm_head,
    }


def param_count(cfg: LlamaConfig) -> int:
    import numpy as np

    return sum(
        int(np.prod(p.shape))
        for p in jax.tree.leaves(
            jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
        )
    )


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #

def _rmsnorm(x, g, cfg: LlamaConfig):
    xf = x.astype(jnp.float32)
    rms = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + cfg.rms_eps)
    scale = 1.0 + g.astype(jnp.float32) if cfg.norm_unit_offset else g
    return (xf * rms).astype(x.dtype) * scale.astype(x.dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding, HF-llama convention: x [..., S, hd] with the head
    dim split as [first half, second half] (rotate_half), NOT interleaved."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [S, half]
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
    rotated = jnp.einsum("...d,de->...e", x, rot)
    return (x.astype(jnp.float32) * cos
            + rotated.astype(jnp.float32) * sin).astype(x.dtype)


def _residual_add(x, y):
    """x + y in float32, the stream stored in x's dtype (the released
    EvaByte's ``fp32_skip_add``; y is a matmul's float32 accumulator)."""
    return (x.astype(jnp.float32) + y.astype(jnp.float32)).astype(x.dtype)


def _attention(q, k, v, p, cfg: LlamaConfig):
    """q [B,H,S,hd], k/v [B,KH,S,hd] → [B,H,S,hd]: the config's mixer."""
    from ray_tpu.ops.attention import flash_attention_sharded, resolve_attention
    from ray_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.current_mesh()
    impl, interpret = resolve_attention(cfg.attention_impl, mesh)
    if impl == "ring":
        raise NotImplementedError(
            "models/llama.py has no ring-attention path; use a mesh without "
            "a cp axis"
        )
    if cfg.mixer == "eva":
        from ray_tpu.ops import eva_attention as eva

        phi, mu = p["eva_phi"], p["eva_mu"]
        if impl == "pallas":
            return eva.eva_attention_sharded(
                q, k, v, phi, mu, mesh, window=cfg.window, chunk=cfg.chunk)
        return eva.eva_attention_xla(
            q, k, v, phi, mu, window=cfg.window, chunk=cfg.chunk)
    groups = cfg.n_head // cfg.n_kv_head
    if groups > 1:
        k = jnp.repeat(k, groups, axis=1)
        v = jnp.repeat(v, groups, axis=1)
    if impl == "pallas":
        return flash_attention_sharded(
            q, k, v, mesh, causal=True, interpret=interpret
        )
    S = q.shape[2]
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    mask = jnp.tril(jnp.ones((S, S), dtype=bool))
    logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


_MATMUL_WEIGHTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _cast_in_the_loop(p, x, dt, keys=_MATMUL_WEIGHTS):
    """The layer's matmul weights in the compute dtype, cast inside the layer
    loop. A plain ``astype`` of a layer sliced out of the stack the TPU
    compiler turns into one cast of the WHOLE stack before the loop (through
    an ``optimization_barrier`` too) and keeps the copy for the length of the
    step: 1.5 GB beside EvaByte's four layers, which the chip does not have.
    A factor of one that depends on the loop's carry keeps the cast where it
    is written; it costs a read of the layer's f32 weights a use, 0.6 % of
    the 32,768-token step."""
    one = lax.stop_gradient(1.0 + 0.0 * x[0, 0, 0].astype(jnp.float32))
    return {k: (p[k] * one).astype(dt) for k in keys}


@jax.named_scope(scopes.BLOCK)
def _block(x, p, cfg: LlamaConfig):
    """One block, x [B, S, D], under GPT-2's scopes and residual names."""
    positions = jnp.arange(x.shape[1])
    p = {**p, **_cast_in_the_loop(p, x, cfg.dtype)}
    with jax.named_scope(scopes.LN1):
        h = _rmsnorm(x, p["attn_norm"], cfg)
    with jax.named_scope(scopes.QKV):
        # named after the rotation: a kept q or k is not rotated again
        q = checkpoint_name(_rope(
            jnp.einsum("bsd,dhk->bhsk", h, p["wq"]),
            positions, cfg.rope_theta), scopes.RES_Q)
        k = checkpoint_name(_rope(
            jnp.einsum("bsd,dhk->bhsk", h, p["wk"]),
            positions, cfg.rope_theta), scopes.RES_K)
        v = checkpoint_name(
            jnp.einsum("bsd,dhk->bhsk", h, p["wv"]), scopes.RES_V)
    with jax.named_scope(scopes.ATTN):
        attn = _attention(q, k, v, p, cfg)
    with jax.named_scope(scopes.PROJ):
        x = checkpoint_name(_residual_add(x, jnp.einsum(
            "bhsk,hkd->bsd", attn, p["wo"],
            preferred_element_type=jnp.float32)), scopes.RES_MID)
    return _mlp(x, p, cfg)


def _swiglu(x, p, cfg: LlamaConfig):
    """x + down(silu(gate(h)) · up(h)), h = norm(x), on [B, rows, D]."""
    with jax.named_scope(scopes.LN2):
        h = _rmsnorm(x, p["mlp_norm"], cfg)
    with jax.named_scope(scopes.MLP):
        gate = checkpoint_name(jnp.einsum("bsd,df->bsf", h, p["w_gate"]),
                               scopes.RES_MLP_GATE)
        up = checkpoint_name(jnp.einsum("bsd,df->bsf", h, p["w_up"]),
                             scopes.RES_MLP_UP)
        return _residual_add(x, jnp.einsum(
            "bsf,fd->bsd", jax.nn.silu(gate) * up, p["w_down"],
            preferred_element_type=jnp.float32))


def _mlp(x, p, cfg: LlamaConfig):
    """The block's second half, norm and all. Where one hidden tensor of the
    whole sequence would pass _MLP_CHUNK_BYTES the sequence goes through in
    chunks (_mlp_rows), each its own ``checkpoint``: a chunk's hidden tensors
    are made again in its backward and never exist for the whole sequence
    (nor can a remat policy keep them: llama.block_shard tells the rule so).
    The norm is the chunk's too — a row's norm needs the row alone — so the
    loop's one input is the stream itself: the normed stream and its gradient
    never stand whole beside it. That is 0.5 GB of the 32,768-byte EvaByte
    step's peak, which falls in this loop's backward; with it, and k kept
    where q was, the step fits without the compiler making k and v a second
    time in every layer (PERF.md §6, PR 32)."""
    B, S, D = x.shape
    rows = _mlp_rows(B, S, D, cfg.d_ff, x.dtype.itemsize)
    if rows == S:
        return _swiglu(x, p, cfg)
    chunks = x.reshape(B, S // rows, rows, D).swapaxes(0, 1)
    out = lax.map(jax.checkpoint(partial(_swiglu, p=p, cfg=cfg)), chunks)
    return out.swapaxes(0, 1).reshape(B, S, D)


def _rows_under(seq: int, bytes_a_row: int, limit: int) -> int:
    """The largest power-of-two fraction of ``seq`` whose rows stay under
    ``limit`` bytes (``seq`` itself where they do)."""
    rows = seq
    while rows % 2 == 0 and rows * bytes_a_row > limit:
        rows //= 2
    return rows


def _mlp_rows(batch: int, seq: int, d_model: int, d_ff: int,
              itemsize: int) -> int:
    """Rows of the sequence the MLP takes at a time: all of them where a
    hidden tensor of the whole sequence stays under _MLP_CHUNK_BYTES. A longer
    sequence goes in chunks whose five hidden tensors together take what two
    of the block's [B, S, D] activations do — a fifth more beside the eight
    of that size that wait in the chunk's backward for the attention's
    (gpt2.rematted_working_set). On the chip the 32,768-byte EvaByte step's
    MLP backward takes 403.6 ms at the 4,096 rows this gives, 405.1 at 2,048
    and 420.7 at 8,192, and its compiled step needs 0.7 GB less than at 8,192
    (PERF.md §6, PR 32)."""
    if batch * seq * d_ff * itemsize <= _MLP_CHUNK_BYTES:
        return seq
    return _rows_under(seq, 5 * batch * d_ff * itemsize,
                       2 * batch * seq * d_model * itemsize)


def _head_rows(batch: int, seq: int, columns: int, heads: int) -> int:
    """Rows of the sequence the head takes at a time where it goes in chunks
    — their float32 logits stay under _HEAD_CHUNK_BYTES; more heads than one
    always do — and 0 where one head takes the sequence whole (softmax_xent)."""
    rows = _rows_under(seq, batch * columns * 4, _HEAD_CHUNK_BYTES)
    return rows if heads > 1 or rows < seq else 0


def block_shard(cfg: LlamaConfig, global_batch: int, seq: int,
                mesh) -> gpt2.BlockShard:
    """This config's block on one chip of ``mesh``, for the remat rule: the
    shapes of ITS residuals (two hidden tensors of d_ff, k and v of n_kv_head
    heads, with eva the window and the chunk the summaries come from)."""
    from ray_tpu.ops.attention import resolve_attention

    columns = cfg.n_pred_heads * cfg.head_vocab
    return gpt2.shard_block(gpt2.BlockShard(
        batch=global_batch, seq=seq, d_model=cfg.d_model, heads=cfg.n_head,
        head_dim=cfg.head_dim, d_ff=cfg.d_ff, vocab=columns,
        dtype_bytes=jnp.dtype(cfg.dtype).itemsize,
        flash=resolve_attention(cfg.attention_impl, mesh)[0] == "pallas",
        dense_mlp=True, kv_heads=cfg.n_kv_head,
        mlp_hidden=(scopes.RES_MLP_GATE, scopes.RES_MLP_UP),
        window=cfg.window if cfg.mixer == "eva" else 0, chunk=cfg.chunk,
        head_rows=_head_rows(global_batch, seq, columns, cfg.n_pred_heads),
        mlp_rows=_mlp_rows(global_batch, seq, cfg.d_model, cfg.d_ff,
                           jnp.dtype(cfg.dtype).itemsize),
        cast_in_loop=True,
    ), mesh)


def _trunk(params, tokens, cfg: LlamaConfig):
    """tokens [B, S] int32 → final hidden states [B, S, D]."""
    from ray_tpu.parallel import mesh as mesh_lib

    B, S = tokens.shape
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    block_fn = gpt2._checkpointed(
        partial(_block, cfg=cfg), cfg.remat,
        block_shard(cfg, B, S, mesh_lib.current_mesh()), cfg.n_layer)
    x = gpt2._run_blocks(block_fn, x, params["blocks"])
    with jax.named_scope(scopes.LN_F):
        return _rmsnorm(x, params["final_norm"], cfg)


def forward(params, tokens, cfg: LlamaConfig) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, n_pred_heads · head_vocab], head p
    in columns p·head_vocab … (p+1)·head_vocab."""
    x = _trunk(params, tokens, cfg)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype))


def head_targets(targets: jax.Array, n_heads: int) -> jax.Array:
    """targets [B, S] (the next token, -1 = ignore) → [B, S, n_heads]: head
    p's target at t is targets[t + p], -1 past the row's end."""
    S = targets.shape[1]
    padded = jnp.pad(targets, ((0, 0), (0, n_heads - 1)), constant_values=-1)
    return jnp.stack([padded[:, p:p + S] for p in range(n_heads)], axis=-1)


@jax.named_scope(scopes.LM_HEAD_LOSS)
def _lm_head_loss(x, targets, lm_head, cfg: LlamaConfig) -> jax.Array:
    """Untied head(s) + cross-entropy over final hidden states [B, S, D]: the
    mean over the heads of each head's mean over its valid targets. Where the
    head goes in chunks (_head_rows) the logits and their gradient
    are never one tensor, and a chunk's logits are multiplied out once a
    step: the chunk that makes its loss makes its gradient
    (ops/cross_entropy.chunked_head_xent)."""
    from ray_tpu.ops import cross_entropy

    B, S = targets.shape
    P = cfg.n_pred_heads
    lm_head = lm_head.astype(cfg.dtype)
    rows = _head_rows(B, S, lm_head.shape[1], P)
    if not rows:
        # fused CE (ops/cross_entropy.py): no [B, S, V] float32 residual
        nll = cross_entropy.softmax_xent(
            jnp.einsum("bsd,dv->bsv", x, lm_head), targets)
        return jnp.sum(nll) / jnp.maximum(jnp.sum(targets >= 0), 1)
    return cross_entropy.chunked_head_xent(x, head_targets(targets, P),
                                           lm_head, rows)


def loss_fn(params, tokens, targets, cfg: LlamaConfig) -> jax.Array:
    """Mean cross-entropy over targets >= 0 ([B, S] int32, the next token):
    with n_pred_heads > 1 head p is scored on targets[t + p]."""
    x = _trunk(params, tokens, cfg)
    return _lm_head_loss(x, targets, params["lm_head"], cfg)


def flops_per_token(cfg: LlamaConfig) -> float:
    n = param_count(cfg)
    attn = 12 * cfg.n_layer * cfg.d_model * cfg.seq_len
    return 6.0 * n + attn


# --------------------------------------------------------------------------- #
# HF interop (parity testing / loading released checkpoints)
# --------------------------------------------------------------------------- #

def params_from_hf(hf_model, cfg: LlamaConfig) -> Dict[str, Any]:
    """Map a transformers LlamaForCausalLM state dict into our pytree."""
    import numpy as np

    sd = {k: np.asarray(v.detach().float().numpy())
          for k, v in hf_model.state_dict().items()}
    D, H, KH, hd = cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    L, V = cfg.n_layer, cfg.padded_vocab

    def pad_vocab(w):  # [v, D] → [V, D]
        out = np.zeros((V, w.shape[1]), w.dtype)
        out[: w.shape[0]] = w
        return out

    blocks: Dict[str, list] = {k: [] for k in (
        "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
        "w_gate", "w_up", "w_down",
    )}
    for i in range(L):
        pre = f"model.layers.{i}."
        blocks["attn_norm"].append(sd[pre + "input_layernorm.weight"])
        # HF stores [out, in]; ours contract d→(h, hd) so transpose + reshape
        blocks["wq"].append(
            sd[pre + "self_attn.q_proj.weight"].T.reshape(D, H, hd)
        )
        blocks["wk"].append(
            sd[pre + "self_attn.k_proj.weight"].T.reshape(D, KH, hd)
        )
        blocks["wv"].append(
            sd[pre + "self_attn.v_proj.weight"].T.reshape(D, KH, hd)
        )
        blocks["wo"].append(
            sd[pre + "self_attn.o_proj.weight"].T.reshape(H, hd, D)
        )
        blocks["mlp_norm"].append(sd[pre + "post_attention_layernorm.weight"])
        blocks["w_gate"].append(sd[pre + "mlp.gate_proj.weight"].T)
        blocks["w_up"].append(sd[pre + "mlp.up_proj.weight"].T)
        blocks["w_down"].append(sd[pre + "mlp.down_proj.weight"].T)

    pd = cfg.param_dtype
    return {
        "wte": jnp.asarray(pad_vocab(sd["model.embed_tokens.weight"]), pd),
        "blocks": {
            k: jnp.asarray(np.stack(v), pd) for k, v in blocks.items()
        },
        "final_norm": jnp.asarray(sd["model.norm.weight"], pd),
        "lm_head": jnp.asarray(pad_vocab(sd["lm_head.weight"]).T, pd),
    }
