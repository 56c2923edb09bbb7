"""GPT-2 in pure JAX, designed for the MXU and GSPMD sharding.

Flagship model for the Train benchmarks (BASELINE.md config 3: GPT-2-124M
data-parallel pretraining, tokens/sec/chip). TPU-first choices:

- layers are *stacked* and iterated with ``lax.scan`` → compile time and the
  compiled program's size independent of depth; each block is a
  policy-``checkpoint`` so the scan stacks only the block's named residuals;
- weights carry logical axis names so any (dp, fsdp, tp, cp) mesh works via
  parallel/sharding.py rules — no model changes for a new parallelism plan;
- bf16 activations + matmuls (MXU native), f32 params/optimizer master copy;
- vocab padded to a multiple of 128 (lane width) so the LM-head matmul tiles;
- attention dispatches to the Pallas flash kernel on TPU (ops/attention.py) with
  an XLA einsum fallback elsewhere, and to ring attention when the mesh has a
  cp axis.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, replace
from functools import partial
from typing import (Any, Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.tracing import get_buffer, names as scopes


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    seq_len: int = 1024
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    dropout: float = 0.0          # pretraining default; nonzero not yet implemented
    dtype: Any = jnp.bfloat16     # activation/compute dtype
    param_dtype: Any = jnp.float32
    # What each block keeps for its backward besides its input:
    #   False — every named residual (tracing/names.RESIDUALS: the outputs of
    #           its matmuls and of the flash kernel), whatever the memory;
    #           only elementwise work (layer norms, gelu, bias and residual
    #           adds) runs again in the backward (fastest, most HBM). Where
    #           the names do not cover the block (_make_block_fn), all that
    #           AD saves
    #   True  — recompute what does not fit: whichever of those names
    #           choose_remat_policy finds room for on this chip (none where
    #           the device states no memory limit: one extra forward, least
    #           HBM)
    remat: bool = False
    attention_impl: str = "auto"  # auto | xla | pallas | ring
    use_bias: bool = True
    # mixture-of-experts MLP (ops/moe.py): 0 = dense. When > 0 every block's
    # MLP becomes E experts with top-k routing; expert params shard over the
    # mesh's ep axis. aux (load-balance) loss joins the training loss.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_coeff: float = 0.01
    # Pipeline parallelism (parallel/pipeline.py): number of GPipe
    # microbatches when the active mesh has a pp axis > 1. 0 = auto (one
    # microbatch per stage — minimum that keeps every stage busy; raise it
    # to shrink the (pp-1)/(M+pp-1) bubble at the cost of more live
    # activations). Ignored on pp=1 meshes.
    pipeline_microbatches: int = 0
    # When > 0, cross-entropy is computed in sequence chunks of this size
    # (scan + rematerialized chunk logits): the full [B, S, V] f32 logits
    # tensor (3.3 GB at GPT-2-124M batch 16) never exists in HBM. Off by
    # default: on v5e it costs ~6% step time (the backward recompute of the
    # vocab matmul outweighs the saved bandwidth at 124M scale); enable for
    # larger models / longer sequences where logits dominate memory.
    loss_chunk: Optional[int] = 0

    def __post_init__(self):
        if self.dropout:
            raise NotImplementedError(
                "dropout is not implemented yet (needs rng threading through "
                "the scan); pretraining runs use dropout=0"
            )
        if self.attention_impl not in ("auto", "xla", "pallas", "ring"):
            raise ValueError(f"unknown attention_impl {self.attention_impl!r}")
        if not isinstance(self.remat, bool):
            raise ValueError(f"remat must be True or False; got {self.remat!r}")
        if self.moe_experts < 0:
            raise ValueError("moe_experts must be >= 0")
        if self.moe_experts > 0:
            if not (1 <= self.moe_top_k <= self.moe_experts):
                raise ValueError(
                    f"moe_top_k={self.moe_top_k} must be in "
                    f"[1, moe_experts={self.moe_experts}]"
                )
            if self.moe_capacity_factor <= 0:
                raise ValueError("moe_capacity_factor must be > 0")
        if self.loss_chunk and self.seq_len % self.loss_chunk:
            raise ValueError(
                f"loss_chunk={self.loss_chunk} must divide seq_len="
                f"{self.seq_len} (or be 0 to disable chunked cross-entropy)"
            )

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    @property
    def d_ff(self) -> int:
        return 4 * self.d_model

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, 128)


def gpt2_124m(**overrides) -> GPT2Config:
    return replace(GPT2Config(), **overrides)


def gpt2_350m(**overrides) -> GPT2Config:
    return replace(
        GPT2Config(n_layer=24, n_head=16, d_model=1024), **overrides
    )


def gpt2_tiny(**overrides) -> GPT2Config:
    """Test-size config (CPU mesh friendly)."""
    return replace(
        GPT2Config(vocab_size=512, seq_len=128, n_layer=2, n_head=4, d_model=128),
        **overrides,
    )


# --------------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------------- #

def logical_axes(cfg: GPT2Config) -> Dict[str, Any]:
    """Pytree (matching init() output) of logical axis names per parameter."""
    blocks = {
        "ln1_scale": ("layers", "embed"),
        "ln1_bias": ("layers", "embed"),
        "qkv_w": ("layers", "embed", None, "heads", "kv"),
        "qkv_b": ("layers", None, "heads", "kv"),
        "proj_w": ("layers", "heads", "kv", "embed"),
        "proj_b": ("layers", "embed"),
        "ln2_scale": ("layers", "embed"),
        "ln2_bias": ("layers", "embed"),
        "fc_w": ("layers", "embed", "mlp"),
        "fc_b": ("layers", "mlp"),
        "out_w": ("layers", "mlp", "embed"),
        "out_b": ("layers", "embed"),
    }
    if cfg.moe_experts > 0:
        from ray_tpu.ops.moe import moe_logical_axes

        for key in ("fc_w", "fc_b", "out_w", "out_b"):
            del blocks[key]
        blocks["moe"] = moe_logical_axes()
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": blocks,
        "lnf_scale": ("embed",),
        "lnf_bias": ("embed",),
    }


def mesh_rules(cfg: GPT2Config, mesh) -> Dict[str, str]:
    """What this config needs of this mesh: the sharding rules to lay over
    parallel/sharding's defaults, or the refusal of a mesh it cannot run on."""
    pp = mesh.shape.get("pp", 1)
    if pp == 1:
        return {}
    if cfg.moe_experts > 0:
        raise NotImplementedError(
            "pipeline parallelism with MoE blocks is not supported yet "
            "(the aux-loss carry needs threading through the schedule); "
            "use a pp=1 mesh for MoE configs"
        )
    if cfg.n_layer % pp:
        raise ValueError(f"n_layer={cfg.n_layer} not divisible by pp={pp}")
    # pipelined plan: shard the stacked layer dim over pp so each stage
    # group holds only its own layers (parallel/pipeline.py reshapes
    # [L, ...] → [pp, L/pp, ...], which preserves this sharding).
    return {"layers": "pp"}


def init(cfg: GPT2Config, rng: jax.Array) -> Dict[str, Any]:
    """GPT-2 initialization: N(0, 0.02), residual projections scaled 1/sqrt(2L)."""
    D, H, hd, F, L = cfg.d_model, cfg.n_head, cfg.head_dim, cfg.d_ff, cfg.n_layer
    V, S = cfg.padded_vocab, cfg.seq_len
    pd = cfg.param_dtype
    k = iter(jax.random.split(rng, 8))
    std = 0.02
    resid_std = std / math.sqrt(2 * L)

    def normal(key, shape, s):
        return (jax.random.normal(key, shape) * s).astype(pd)

    blocks = {
        "ln1_scale": jnp.ones((L, D), pd),
        "ln1_bias": jnp.zeros((L, D), pd),
        "qkv_w": normal(next(k), (L, D, 3, H, hd), std),
        "qkv_b": jnp.zeros((L, 3, H, hd), pd),
        "proj_w": normal(next(k), (L, H, hd, D), resid_std),
        "proj_b": jnp.zeros((L, D), pd),
        "ln2_scale": jnp.ones((L, D), pd),
        "ln2_bias": jnp.zeros((L, D), pd),
        "fc_w": normal(next(k), (L, D, F), std),
        "fc_b": jnp.zeros((L, F), pd),
        "out_w": normal(next(k), (L, F, D), resid_std),
        "out_b": jnp.zeros((L, D), pd),
    }
    if cfg.moe_experts > 0:
        from ray_tpu.ops.moe import moe_init

        # the dense MLP is replaced wholesale: drop its params so optimizer
        # state, sharding, and param_count stay honest
        for key in ("fc_w", "fc_b", "out_w", "out_b"):
            del blocks[key]
        blocks["moe"] = moe_init(
            next(k), L, D, F, cfg.moe_experts, param_dtype=pd,
            resid_std=resid_std,
        )
    return {
        "wte": normal(next(k), (V, D), std),
        "wpe": normal(next(k), (S, D), 0.01),
        "blocks": blocks,
        "lnf_scale": jnp.ones((D,), pd),
        "lnf_bias": jnp.zeros((D,), pd),
    }


def param_count(cfg: GPT2Config) -> int:
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

def _layernorm(x, scale, bias, eps=1e-5):
    # f32 statistics for stability, cast back to compute dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _attention(q, k, v, cfg: GPT2Config):
    """q,k,v: [B, H, S, hd] → [B, H, S, hd], causal (head-major layout — the
    flash kernels' native one, so the hot path has no boundary transposes)."""
    from ray_tpu.ops.attention import flash_attention_sharded, resolve_attention
    from ray_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.current_mesh()
    impl, interpret = resolve_attention(cfg.attention_impl, mesh)
    if impl == "pallas":
        return flash_attention_sharded(
            q, k, v, mesh, causal=True, interpret=interpret
        )
    if impl == "ring":
        from ray_tpu.ops.ring_attention import ring_attention_sharded

        if mesh is None:
            raise ValueError(
                "attention_impl='ring' needs a mesh with a cp axis; call the "
                "model inside parallel.mesh.use_mesh(mesh) (train_step does)"
            )
        o = ring_attention_sharded(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), mesh, axis_name="cp", causal=True,
        )
        return jnp.swapaxes(o, 1, 2)
    # XLA path: einsum + mask; XLA fuses the softmax chain.
    S = q.shape[2]
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    mask = jnp.tril(jnp.ones((S, S), dtype=bool))
    logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@jax.named_scope(scopes.BLOCK)
def _block(x, layer_params, cfg: GPT2Config):
    """One transformer block. x: [B, S, D] (or (x, aux) when MoE is on —
    the load-balance loss accumulates through the layer carry)."""
    aux_in = None
    if isinstance(x, tuple):
        x, aux_in = x
    p = layer_params
    dt = cfg.dtype
    with jax.named_scope(scopes.LN1):
        h = _layernorm(x, p["ln1_scale"], p["ln1_bias"])
    # head-major projection, one einsum per q/k/v: each matmul writes its
    # output directly in the flash kernels' [B, H, S, hd] layout (XLA emits
    # transposed-output dots with NO separate formatting op — measured 0.04
    # ms/step). A packed single [D, 3·H·hd] dot was tried (round 5): it
    # saved 7 ms of matmul but XLA materialized 12.5 ms/step of layout
    # glue for the rank-5 transposed output — net loss.
    with jax.named_scope(scopes.QKV):
        w, b = p["qkv_w"].astype(dt), p["qkv_b"].astype(dt)
        q, k, v = (
            checkpoint_name(
                jnp.einsum("bsd,dhk->bhsk", h, w[:, i]) + b[i][None, :, None, :],
                name)
            for i, name in enumerate((scopes.RES_Q, scopes.RES_K, scopes.RES_V))
        )
    with jax.named_scope(scopes.ATTN):
        attn = _attention(q, k, v, cfg)
    with jax.named_scope(scopes.PROJ):
        x = x + jnp.einsum("bhsk,hkd->bsd", attn, p["proj_w"].astype(dt)) + p["proj_b"].astype(dt)
        x = checkpoint_name(x, scopes.RES_MID)
    with jax.named_scope(scopes.LN2):
        h = _layernorm(x, p["ln2_scale"], p["ln2_bias"])
    if cfg.moe_experts > 0:
        from ray_tpu.ops.moe import moe_mlp

        with jax.named_scope(scopes.MOE):
            y, aux = moe_mlp(
                h, p["moe"], top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor, dtype=dt,
            )
            x = x + y
        return (x, (aux_in if aux_in is not None else 0.0) + aux)
    with jax.named_scope(scopes.MLP):
        h = jnp.einsum("bsd,df->bsf", h, p["fc_w"].astype(dt)) + p["fc_b"].astype(dt)
        h = jax.nn.gelu(checkpoint_name(h, scopes.RES_MLP_HIDDEN), approximate=True)
        x = x + jnp.einsum("bsf,fd->bsd", h, p["out_w"].astype(dt)) + p["out_b"].astype(dt)
    return x if aux_in is None else (x, aux_in)


# --------------------------------------------------------------------------- #
# Remat: what a block keeps for its backward
# --------------------------------------------------------------------------- #

class BlockShard(NamedTuple):
    """One chip's share of a step, in elements: global shapes ÷ the mesh axes
    that split them. Everything the remat rule computes, it computes from
    this and n_layer. The model states its block's shapes (block_shard here,
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
    # (llama._cast_in_the_loop): one layer's stand in the block's backward
    cast_in_loop: bool = False


class RematCandidate(NamedTuple):
    names: Tuple[str, ...]   # residuals kept together
    nbytes: int              # what they take, a layer
    flops: int               # what making them again costs, a layer
    frees: int = 0           # bytes of rematted_working_set that are there
                             # only while these are made again, not kept


class RematPolicy(NamedTuple):
    saved: Tuple[str, ...]   # names.RESIDUALS a block keeps, in the order taken
    saved_bytes: int         # what they take on a chip, over n_layer layers
    budget_bytes: int        # what was free for them, with what keeping them
                             # freed (0: no limit is known)
    bytes_limit: int         # the chip's own figure the budget came from, or 0


# what the rule leaves free: the benchmark's fit rule keeps the same
# (benchmarks/README.md), and the working-set arithmetic below is an estimate
REMAT_RESERVE_BYTES = 2 ** 30
_MXU = 128                   # a matmul dim below this still costs a full pass

_decisions: Dict[tuple, Dict[str, Any]] = {}
_patterns: Dict[str, Dict[str, Any]] = {}


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


def block_shard(cfg: GPT2Config, global_batch: int, seq: int, mesh,
                flash: bool) -> BlockShard:
    """cfg's block on one chip of ``mesh``."""
    return shard_block(BlockShard(
        batch=global_batch, seq=seq, d_model=cfg.d_model, heads=cfg.n_head,
        head_dim=cfg.head_dim, d_ff=cfg.d_ff, vocab=cfg.padded_vocab,
        dtype_bytes=jnp.dtype(cfg.dtype).itemsize, flash=flash,
        dense_mlp=cfg.moe_experts == 0,
    ), mesh)


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
            2 * s.batch * s.heads * s.seq * keys * max(s.head_dim, _MXU),
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
            2 * s.batch * s.heads * s.seq * s.seq * max(s.head_dim, _MXU),
        ))
    out.append(RematCandidate((scopes.RES_MID,), tokens * s.d_model * a,
                              2 * tokens * attn_width * s.d_model))
    if s.dense_mlp and s.mlp_rows in (0, s.seq):
        out += [RematCandidate((name,), tokens * s.d_ff * a,
                               2 * tokens * s.d_model * s.d_ff)
                for name in s.mlp_hidden]
    return sorted(out, key=lambda c: (-c.flops / c.nbytes, -c.frees))


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


def model_working_set(s: BlockShard, n_layer: int) -> int:
    """rematted_working_set's part that no block decides: the stack of
    ``n_layer`` block inputs, the LM head's logits, the gathered embedding."""
    return n_layer * _block_input(s) + _head_terms(s) + _gathered(s)


def _block_input(s: BlockShard) -> int:
    return s.batch * s.seq * s.d_model * s.dtype_bytes


def _head_terms(s: BlockShard) -> int:
    a = s.dtype_bytes
    head = s.batch * (s.head_rows or s.seq) * s.vocab * (2 * a + 4)
    if s.head_rows:
        # a head in chunks makes its gradient in the forward and keeps it
        # (ops/cross_entropy.chunked_head_xent): d x stands where the chunked
        # x stood, the running float32 d lm_head is new
        head += s.d_model * s.vocab * 4
    return head


def _gathered(s: BlockShard) -> int:
    return s.vocab * s.d_model * (s.dtype_bytes + 4)


def block_working_set(s: BlockShard) -> int:
    """rematted_working_set's part that is one block's: its whole residual
    set, live while its backward runs. Of a model whose layers are of more
    than one kind each run's largest counts in its phase (backward_phases)."""
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
    return block + _eva_k_f32(s) + weights


class KindShard(NamedTuple):
    """What the remat rule needs of one kind of layer on one chip: how often
    the kind is applied, what a block of it may keep (remat_candidates, or
    the kind's own arithmetic), what its whole residual set takes while
    its backward runs (block_working_set), and the bytes of one application's
    weight gradients (backward_phases takes those not made yet off a run's
    phase; a model of one run of layers has none to take off and states 0)."""
    applications: int
    candidates: Tuple[RematCandidate, ...]
    block_bytes: int
    grad_bytes: int = 0


# a run of the layers as pattern_groups names it: the kinds of its repeated
# sub-pattern (keys of the model's KindShards) and the repeats
Run = Tuple[Sequence[str], int]


class Phase(NamedTuple):
    name: str                # "head", or the run as model/layer_pattern has it
    nbytes: int              # the fully rematted step's working set, in it


def run_name(run: Run) -> str:
    sub, reps = run
    return f"{reps} x scan({''.join(sub)})" if reps > 1 else "".join(sub)


def backward_phases(s: BlockShard, kinds: Dict[str, KindShard],
                    runs: Sequence[Run]) -> List[Phase]:
    """rematted_working_set by the phases of the step's backward, in the
    order it meets them: the head's, then every run of the layers from the
    last to the first. The step needs the LARGEST of them, not their sum. In a
    run's phase stand the block inputs that still wait (its own and those of
    the runs before it), the largest block of the run's kinds, the gathered
    embedding, and — in the last run, whose backward starts on them — the
    head's terms; what does NOT stand there yet are the weight gradients of
    the runs before it, which the step's resident bytes count from the start.
    A run's own gradients all count: a scan writes its stacked gradients from
    the moment its backward starts. A model of one kind in one scan has one
    such phase, and it is rematted_working_set to the byte (its head phase
    is that less the block). The MEMEMEMEM*E + *E hybrid's head, MTP module
    and last five layers are dead by the time the scan of eight writes 1.7 GiB
    of gradients: summed, the estimate stood 2.5 GiB over the compiled step
    (PERF.md §6, PR 42)."""
    layers = [reps * len(sub) for sub, reps in runs]
    grads = [reps * sum(kinds[k].grad_bytes for k in sub) for sub, reps in runs]
    phases = [Phase("head", model_working_set(s, sum(layers)) - sum(grads))]
    for i in reversed(range(len(runs))):
        live = (sum(layers[:i + 1]) * _block_input(s) + _gathered(s)
                + max(kinds[k].block_bytes for k in runs[i][0])
                - sum(grads[:i]))
        if i == len(runs) - 1:
            live += _head_terms(s)
        phases.append(Phase(run_name(runs[i]), live))
    return phases


def choose_remat_policy_kinds(kinds: Sequence[KindShard], working_set: int,
                              bytes_limit: Optional[int],
                              resident_bytes: int) -> RematPolicy:
    """THE rule for what ``remat=True`` keeps besides each block's input, for
    layers of any number of kinds: walk every kind's candidates (most
    recompute FLOPs per byte first) and take each whose copies — one an
    application of its kind — still fit what the chip has free: its
    bytes_limit less the reserve, what is resident (state and gradients) and
    the fully rematted step's ``working_set`` (the largest of
    backward_phases), plus what keeping it frees of that set. With no limit
    stated, nothing."""
    if bytes_limit is None:
        return RematPolicy((), 0, 0, 0)
    budget = bytes_limit - REMAT_RESERVE_BYTES - resident_bytes - working_set
    ranked = sorted(((c, k.applications) for k in kinds for c in k.candidates),
                    key=lambda cn: (-cn[0].flops / cn[0].nbytes, -cn[0].frees))
    saved, used = [], 0
    for c, n in ranked:
        if used + n * c.nbytes <= budget + c.frees:
            saved.extend(c.names)
            used += n * c.nbytes
            budget += c.frees
    return RematPolicy(tuple(saved), used, max(0, budget), bytes_limit)


def choose_remat_policy(shard: BlockShard, n_layer: int,
                        bytes_limit: Optional[int],
                        resident_bytes: int) -> RematPolicy:
    """choose_remat_policy_kinds for ``n_layer`` blocks of one kind."""
    return choose_remat_policy_kinds(
        [KindShard(n_layer, tuple(remat_candidates(shard)),
                   block_working_set(shard))],
        rematted_working_set(shard, n_layer), bytes_limit, resident_bytes)


def remat_policy_decisions() -> List[Dict[str, Any]]:
    """Every distinct remat decision this process has traced a model with, as
    the ``model/remat_policy`` events carry them."""
    return list(_decisions.values())


def compiler_rematerialized(hlo: str) -> List[str]:
    """The instructions of a compiled step (``compiled.as_text()``) that
    XLA's own rematerialization pass made: it clones what it frees early and
    marks the clone's name ``.remat``. Each is recompute the rule did not
    choose — the budget it spent was not there (PERF.md §6, PR 32)."""
    return re.findall(r"^\s*(?:ROOT )?%?(\S*\.remat\S*) = ", hlo, re.M)


def _flash(cfg: GPT2Config, mesh) -> bool:
    """Attention on this mesh is the flash kernel: its o and lse exist."""
    from ray_tpu.ops.attention import resolve_attention

    return resolve_attention(cfg.attention_impl, mesh)[0] == "pallas"


def _remat_policy(shard: BlockShard, kinds: Dict[str, KindShard],
                  runs: Sequence[Run]) -> RematPolicy:
    """choose_remat_policy_kinds for the step being traced, recorded. A static
    choice has no hit rate; its counter is the choice: each distinct one goes
    once, as an instant event, to the task-event buffer
    (→ ``ray_tpu.timeline()``), with the phase of the backward that set the
    working set and its bytes. ``shard`` is the model's: the stream, the
    head and the rows the head and the MLP take at a time."""
    from ray_tpu.parallel import mesh as mesh_lib

    n_layer = sum(k.applications for k in kinds.values())
    phase = max(backward_phases(shard, kinds, runs), key=lambda p: p.nbytes)
    policy = choose_remat_policy_kinds(
        tuple(kinds.values()), phase.nbytes, *mesh_lib.current_chip_memory())
    args = dict(zip(scopes.REMAT_POLICY_ARGS,
                    (n_layer, shard.batch, shard.seq, list(policy.saved))
                    + policy[1:] + (shard.mlp_rows or shard.seq,
                                    shard.head_rows or shard.seq) + phase))
    key = (shard, tuple(kinds.items()), tuple(runs)) + policy
    if key not in _decisions:
        _decisions[key] = args
        component, name = scopes.REMAT_POLICY.split("/")
        get_buffer().record_profile(name, component=component, args=args)
    return policy


def checkpoint_kinds(block_fns: Dict[str, Callable], remat: bool,
                     shard: BlockShard, kinds: Dict[str, KindShard],
                     runs: Sequence[Run]) -> Dict[str, Callable]:
    """Each kind's ``block_fn(x, layer_params)`` as run_pattern calls it: a
    policy-``checkpoint`` that keeps the block's input and, of the named
    residuals (tracing/names.RESIDUALS), those the ONE rule gave room —
    over all the kinds' applications together, in the largest phase of the
    backward over ``runs`` (every run of the layers the step applies, in the
    forward's order) — with ``remat`` and all of them without."""
    saved = (_remat_policy(shard, kinds, runs).saved if remat
             else scopes.RESIDUALS)
    policy = jax.checkpoint_policies.save_only_these_names(*saved)
    return {kind: jax.checkpoint(fn, policy=policy)
            for kind, fn in block_fns.items()}


def _checkpointed(block_fn, remat: bool, shard: BlockShard, n_layer: int):
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
    one-kind case of checkpoint_kinds."""
    if not remat and not (shard.flash and shard.dense_mlp):
        return block_fn
    kind = KindShard(n_layer, tuple(remat_candidates(shard)),
                     block_working_set(shard))
    return checkpoint_kinds({"block": block_fn}, remat, shard,
                            {"block": kind}, [(("block",), n_layer)])["block"]


def _make_block_fn(cfg: GPT2Config, global_batch: int, seq: int, mesh,
                   n_layer: int):
    """GPT-2's block, checkpointed for this step's shard (_checkpointed)."""
    return _checkpointed(
        partial(_block, cfg=cfg), cfg.remat,
        block_shard(cfg, global_batch, seq, mesh, _flash(cfg, mesh)), n_layer)


def pattern_groups(pattern: str) -> List[Tuple[str, int]]:
    """A pattern of layer kinds, one character a layer, as runs of a repeated
    sub-pattern: ``"MEMEMEMEM*E"`` → ``[("ME", 4), ("M", 1), ("*", 1),
    ("E", 1)]``, twelve layers of one kind → ``[("B", 12)]``. Greedy from the
    left: the repeat that covers most layers, of equal ones the shortest
    sub-pattern."""
    groups, i = [], 0
    while i < len(pattern):
        best = (pattern[i], 1)
        for width in range(1, (len(pattern) - i) // 2 + 1):
            sub, reps = pattern[i:i + width], 1
            while pattern.startswith(sub, i + reps * width):
                reps += 1
            if reps > 1 and reps * width > best[1] * len(best[0]):
                best = (sub, reps)
        groups.append(best)
        i += best[1] * len(best[0])
    return groups


def run_pattern(block_fns: Dict[str, Callable], pattern: str, x,
                stacks: Sequence[Dict[str, Any]], with_aux: bool = False):
    """x through ``pattern``'s layers, one character a layer: kind ``c`` is
    ``block_fns[c](x, layer_params)``. ``stacks`` holds the parameters, one
    entry a run of pattern_groups(pattern): ``stacks[g][c]`` stacks the
    run's layers of kind ``c`` in the order they come. A run of a repeated
    sub-pattern is ONE ``lax.scan`` over its own stacks, whose body holds the
    sub-pattern's layers — compile time and program size follow the number of
    distinct runs, not the depth, and no stack is sliced or copied; a layer
    outside any repeat is applied where it stands.

    ``with_aux``: every block function returns ``(x, aux)`` and the result is
    ``(x, auxes)``, ``auxes[g][i]`` the aux of the i-th layer of run g's
    sub-pattern (stacked over the repeats where the run is a scan)."""
    auxes = []
    for (sub, reps), group in zip(pattern_groups(pattern), stacks, strict=True):
        per_rep = {kind: sub.count(kind) for kind in dict.fromkeys(sub)}
        xs = {kind: group[kind] if n == 1 else jax.tree.map(
            lambda a, n=n: a.reshape((reps, n) + a.shape[1:]), group[kind])
            for kind, n in per_rep.items()}

        def body(x, layer_params, sub=sub, per_rep=per_rep):
            seen = {kind: 0 for kind in per_rep}
            aux = []
            for kind in sub:
                p = layer_params[kind]
                if per_rep[kind] > 1:
                    p = jax.tree.map(lambda a: a[seen[kind]], p)
                seen[kind] += 1
                x = block_fns[kind](x, p)
                if with_aux:
                    x, a = x
                    aux.append(a)
            return x, (aux if with_aux else None)

        if reps > 1:
            x, aux = lax.scan(body, x, xs)
        else:
            x, aux = body(x, jax.tree.map(lambda a: a[0], xs))
        auxes.append(aux)
    return (x, auxes) if with_aux else x


def record_layer_pattern(pattern: str) -> None:
    """The ``model/layer_pattern`` event of a model whose layers are of more
    than one kind: the pattern, how often each kind is applied and which
    runs are one scan; once per distinct pattern, at trace time."""
    if pattern in _patterns:
        return
    groups = pattern_groups(pattern)
    _patterns[pattern] = dict(zip(scopes.LAYER_PATTERN_ARGS, (
        pattern, {kind: pattern.count(kind) for kind in dict.fromkeys(pattern)},
        [run_name(run) for run in groups])))
    component, name = scopes.LAYER_PATTERN.split("/")
    get_buffer().record_profile(name, component=component,
                                args=_patterns[pattern])


def layer_pattern_decisions() -> List[Dict[str, Any]]:
    """Every distinct pattern this process has traced a model with, as the
    ``model/layer_pattern`` events carry them."""
    return list(_patterns.values())


def _run_blocks(block_fn, x, layers):
    """x through the blocks whose parameters are stacked in ``layers``: the
    one-kind case of run_pattern."""
    n_layer = jax.tree.leaves(layers)[0].shape[0]
    return run_pattern({"B": block_fn}, "B" * n_layer, x, [{"B": layers}])


def _blocks_pipelined(blocks, x, cfg: GPT2Config, mesh, pp: int):
    """Run the layer stack as a pp-stage GPipe pipeline (parallel/pipeline)."""
    from ray_tpu.parallel.pipeline import pipeline_apply, stages_from_layers

    mesh_rules(cfg, mesh)     # refuses what cannot be pipelined
    M = cfg.pipeline_microbatches or pp
    lpp = cfg.n_layer // pp
    # every microbatch of the batch is in flight at once: lpp layers of the
    # whole batch is what a stage's chips keep
    block_fn = _make_block_fn(cfg, x.shape[0], x.shape[1], mesh, lpp)
    stage_params = stages_from_layers(blocks, pp)

    def stage_fn(layers, h):
        return _run_blocks(block_fn, h, layers)

    return pipeline_apply(
        stage_fn, stage_params, x,
        num_stages=pp, num_microbatches=M, mesh=mesh,
    )


def _trunk(params: Dict[str, Any], tokens: jax.Array, cfg: GPT2Config) -> jax.Array:
    """tokens [B, S] int32 → final hidden states [B, S, D] (compute dtype)."""
    from ray_tpu.parallel import mesh as mesh_lib

    B, S = tokens.shape
    dt = cfg.dtype
    with jax.named_scope(scopes.EMBED):
        wte = params["wte"].astype(dt)
        x = wte[tokens] + params["wpe"][:S].astype(dt)

    mesh = mesh_lib.current_mesh()
    pp = mesh.shape.get("pp", 1) if mesh is not None else 1
    if pp > 1:
        x = _blocks_pipelined(params["blocks"], x, cfg, mesh, pp)
        return _ln_f(x, params), jnp.zeros((), jnp.float32)

    block_fn = _make_block_fn(cfg, B, S, mesh, cfg.n_layer)
    if cfg.moe_experts > 0:
        x = (x, jnp.zeros((), jnp.float32))  # thread the aux loss
    x = _run_blocks(block_fn, x, params["blocks"])
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe_experts > 0:
        x, aux = x
    return _ln_f(x, params), aux


@jax.named_scope(scopes.LN_F)
def _ln_f(x, params):
    return _layernorm(x, params["lnf_scale"], params["lnf_bias"])


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: GPT2Config) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, padded_vocab] (compute dtype)."""
    x, _ = _trunk(params, tokens, cfg)
    # tied LM head
    return jnp.einsum("bsd,vd->bsv", x, params["wte"].astype(cfg.dtype))


def _chunk_nll(x_c, targets_c, wte):
    """[B, c, D] hidden + [B, c] targets → (sum nll, count) for the chunk."""
    logits = jnp.einsum("bsd,vd->bsv", x_c, wte).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    mask = targets_c >= 0
    safe = jnp.where(mask, targets_c, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask), jnp.sum(mask)


def loss_fn(
    params: Dict[str, Any],
    tokens: jax.Array,
    targets: jax.Array,
    cfg: GPT2Config,
) -> jax.Array:
    """Mean next-token cross-entropy. targets [B, S] int32 (-1 = ignore).

    Computed blockwise over the sequence (lax.scan + jax.checkpoint): each
    chunk's [B, c, V] logits are built, reduced to a scalar, and recomputed in
    the backward pass — the LM-head output for the full sequence is never
    materialized. Same math, f32 softmax, identical numerics to the monolithic
    path (tests/test_gpt2_model.py asserts equality).
    """
    x, moe_aux = _trunk(params, tokens, cfg)
    return _lm_head_loss(x, targets, params["wte"], cfg) + cfg.moe_aux_coeff * moe_aux


@jax.named_scope(scopes.LM_HEAD_LOSS)
def _lm_head_loss(x, targets, wte, cfg: GPT2Config) -> jax.Array:
    """Tied LM head + mean cross-entropy over final hidden states [B, S, D]."""
    B, S = targets.shape
    wte = wte.astype(cfg.dtype)
    chunk = cfg.loss_chunk or 0
    # chunk is validated against cfg.seq_len at config time; S % chunk can
    # only be nonzero for ad-hoc shorter sequences, where logits are small
    # enough that the monolithic path is the right call anyway.
    if chunk <= 0 or S % chunk or S == chunk:
        from ray_tpu.ops.cross_entropy import softmax_xent

        # fused CE (ops/cross_entropy.py): saves bf16 logits + [B,S] lse as
        # the only residuals — the f32 [B,S,V] log-softmax tensor the naive
        # formulation materializes (4.9 GB at bench shape) never exists.
        logits = jnp.einsum("bsd,vd->bsv", x, wte)
        nll = softmax_xent(logits, targets)
        count = jnp.sum(targets >= 0)
        return jnp.sum(nll) / jnp.maximum(count, 1)

    xc = x.reshape(B, S // chunk, chunk, -1).swapaxes(0, 1)       # [n, B, c, D]
    tc = targets.reshape(B, S // chunk, chunk).swapaxes(0, 1)     # [n, B, c]
    chunk_fn = jax.checkpoint(partial(_chunk_nll, wte=wte))

    def scan_body(carry, xs):
        total, count = carry
        s, c = chunk_fn(*xs)
        return (total + s, count + c), None

    (total, count), _ = lax.scan(
        scan_body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (xc, tc),
    )
    return total / jnp.maximum(count, 1)


def flops_per_token(cfg: GPT2Config) -> float:
    """Approximate training FLOPs per token (fwd+bwd ≈ 6N + attention term)."""
    n = param_count(cfg)
    attn = 12 * cfg.n_layer * cfg.d_model * cfg.seq_len  # 2*2*3 per token
    return 6.0 * n + attn
