"""GPT-2 in pure JAX, designed for the MXU and GSPMD sharding.

Flagship model for the Train benchmarks (BASELINE.md config 3: GPT-2-124M
data-parallel pretraining, tokens/sec/chip). TPU-first choices:

- layers are *stacked* and run by ONE ``lax.scan`` (models/blocks.py) → compile
  time and program size independent of depth; each block is a
  ``checkpoint`` under the remat rule's policy (models/parts.py), so the scan
  stacks only the block's named residuals;
- weights carry logical axis names so any (dp, fsdp, tp, cp) mesh works via
  parallel/sharding.py rules — no model changes for a new parallelism plan;
- bf16 activations + matmuls (MXU native), f32 params/optimizer master copy;
- vocab padded to a multiple of 128 (lane width) so the LM-head matmul tiles;
- attention (parts.causal_attention) dispatches to the Pallas flash kernel on
  TPU (ops/attention.py) with an XLA einsum fallback elsewhere, and to ring
  attention here when the mesh has a cp axis.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import parts
from ray_tpu.models.blocks import run_blocks
from ray_tpu.tracing import names as scopes


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
        return parts.round_up(self.vocab_size, 128)


def gpt2_124m(**overrides) -> GPT2Config:
    return replace(GPT2Config(), **overrides)


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
    return parts.param_count(lambda: init(cfg, jax.random.PRNGKey(0)))


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
    """q,k,v → o, causal, all four in parts.head_layout's order for this head
    width: the one the flash kernels take with no transpose at their edge."""
    from ray_tpu.ops.attention import resolve_attention
    from ray_tpu.parallel import mesh as mesh_lib

    layout = parts.head_layout(cfg.head_dim)
    mesh = mesh_lib.current_mesh()
    if resolve_attention(cfg.attention_impl, mesh)[0] == "ring":
        from ray_tpu.ops.ring_attention import ring_attention_sharded

        if mesh is None:
            raise ValueError(
                "attention_impl='ring' needs a mesh with a cp axis; call the "
                "model inside parallel.mesh.use_mesh(mesh) (train_step does)"
            )
        # the ring's chunk kernels take [B, S, H, hd]
        to_ring = tuple(layout.index(c) for c in "bshd")
        o = ring_attention_sharded(
            *(jnp.transpose(x, to_ring) for x in (q, k, v)),
            mesh, axis_name="cp", causal=True,
        )
        return jnp.transpose(o, tuple("bshd".index(c) for c in layout))
    return parts.causal_attention(q, k, v, cfg.attention_impl, layout=layout)


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
    # head-major projection, one einsum per q/k/v, each written in the order
    # the flash kernels read at this head width (parts.head_layout): at
    # GPT-2's 64 that is [H, B, hd, S] — how XLA stores such a head whatever
    # the einsum says (an hd-minor [.., S, 64] fills half of every lane
    # tile), the projections' outputs and the layer scan's saved stacks
    # alike, so neither pass has a transposing copy between them and a
    # kernel (PR 48; ten a layer stood there, and
    # tests/test_flash_attention_tpu_compile.py holds the count at none). A
    # packed single [D, 3·H·hd] dot was tried (round 5): it saved 7 ms of
    # matmul but XLA materialized 12.5 ms/step of layout glue for the rank-5
    # transposed output — net loss.
    heads = parts.head_layout(cfg.head_dim).replace("d", "k")  # einsum's names
    with jax.named_scope(scopes.QKV):
        w, b = p["qkv_w"].astype(dt), p["qkv_b"].astype(dt)
        q, k, v = (
            checkpoint_name(
                jnp.einsum(f"bsd,dhk->{heads}", h, w[:, i])
                + jnp.expand_dims(b[i], (heads.index("b"), heads.index("s"))),
                name)
            for i, name in enumerate((scopes.RES_Q, scopes.RES_K, scopes.RES_V))
        )
    with jax.named_scope(scopes.ATTN):
        attn = _attention(q, k, v, cfg)
    with jax.named_scope(scopes.PROJ):
        x = x + jnp.einsum(f"{heads},hkd->bsd", attn, p["proj_w"].astype(dt)) + p["proj_b"].astype(dt)
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


def block_shard(cfg: GPT2Config, global_batch: int, seq: int, mesh,
                flash: bool) -> parts.BlockShard:
    """cfg's block on one chip of ``mesh``, for the remat rule."""
    return parts.shard_block(parts.BlockShard(
        batch=global_batch, seq=seq, d_model=cfg.d_model, heads=cfg.n_head,
        head_dim=cfg.head_dim, d_ff=cfg.d_ff, vocab=cfg.padded_vocab,
        dtype_bytes=jnp.dtype(cfg.dtype).itemsize, flash=flash,
        dense_mlp=cfg.moe_experts == 0,
    ), mesh)


def _make_block_fn(cfg: GPT2Config, global_batch: int, seq: int, mesh,
                   n_layer: int):
    """GPT-2's block, checkpointed for this step's shard."""
    return parts.checkpoint_block(
        partial(_block, cfg=cfg), cfg.remat,
        block_shard(cfg, global_batch, seq, mesh,
                    parts.is_flash(cfg.attention_impl, mesh)), n_layer)


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

    return pipeline_apply(
        lambda layers, h: run_blocks(block_fn, h, layers), stage_params, x,
        num_stages=pp, num_microbatches=M, mesh=mesh)


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
    x = run_blocks(block_fn, x, params["blocks"])
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


def loss_fn(params: Dict[str, Any], tokens: jax.Array, targets: jax.Array,
            cfg: GPT2Config) -> jax.Array:
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
