"""LLaMA-family decoder in pure JAX, designed for the MXU and GSPMD.

Second flagship model family beside GPT-2 (models/gpt2.py): the modern
decoder recipe — RMSNorm (pre-norm, no biases), SwiGLU MLP, rotary position
embeddings, grouped-query attention, untied LM head. It runs on what every
model here runs on, not a copy of it: the layer scan (``blocks.run_blocks``),
the policy-``checkpoint`` and the remat rule on this block's own shapes and
the shared parts (models/parts.py), tracing/names.py's scopes and residuals;
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
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import parts
from ray_tpu.models.blocks import run_blocks
from ray_tpu.tracing import names as scopes


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
                                  # does not fit (parts.choose_remat_policy)
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
        return parts.round_up(self.vocab_size, 128)

    @property
    def head_vocab(self) -> int:
        """Columns of one prediction head: the vocabulary padded so that the
        heads' one matmul is a whole number of 128-lane tiles wide."""
        return parts.round_up(self.vocab_size,
                              128 // math.gcd(self.n_pred_heads, 128))


def llama_tiny(**overrides) -> LlamaConfig:
    """Test-size config (CPU mesh friendly; HF-parity test uses it)."""
    return replace(
        LlamaConfig(vocab_size=256, seq_len=128, n_layer=2, n_head=4,
                    n_kv_head=2, d_model=64, d_ff=176),
        **overrides,
    )


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
    return parts.param_count(lambda: init(cfg, jax.random.PRNGKey(0)))


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #

def _norm(x, g, cfg: LlamaConfig):
    return parts.rmsnorm(x, g, cfg.rms_eps, cfg.norm_unit_offset)


def _head_layout(cfg: LlamaConfig) -> str:
    """The order the block projects its heads in: parts.head_layout's for
    the flash kernels; the EVA kernels take [B, H, S, hd] at any width."""
    return "bhsd" if cfg.mixer == "eva" else parts.head_layout(cfg.head_dim)


def _attention(q, k, v, p, cfg: LlamaConfig):
    """q, k/v (H and KH heads) → o, in _head_layout's order ([B,H,S,hd] at
    hd 128): the config's mixer."""
    if cfg.mixer != "eva":
        return parts.causal_attention(q, k, v, cfg.attention_impl,
                                      layout=_head_layout(cfg))
    from ray_tpu.ops import eva_attention as eva

    impl, _, mesh = parts.attention_on_mesh(cfg.attention_impl)
    phi, mu = p["eva_phi"], p["eva_mu"]
    if impl == "pallas":
        return eva.eva_attention_sharded(
            q, k, v, phi, mu, mesh, window=cfg.window, chunk=cfg.chunk)
    return eva.eva_attention_xla(
        q, k, v, phi, mu, window=cfg.window, chunk=cfg.chunk)


_MATMUL_WEIGHTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


@jax.named_scope(scopes.BLOCK)
def _block(x, p, cfg: LlamaConfig):
    """One block, x [B, S, D], under GPT-2's scopes and residual names."""
    positions = jnp.arange(x.shape[1])
    p = {**p, **parts.cast_in_the_loop(p, x, cfg.dtype, _MATMUL_WEIGHTS)}
    heads = _head_layout(cfg).replace("d", "k")         # the einsums' names
    s_minor = heads[-1] == "s"
    with jax.named_scope(scopes.LN1):
        h = _norm(x, p["attn_norm"], cfg)
    with jax.named_scope(scopes.QKV):
        # named after the rotation: a kept q or k is not rotated again
        q = checkpoint_name(parts.rope(
            jnp.einsum(f"bsd,dhk->{heads}", h, p["wq"]),
            positions, cfg.rope_theta, s_minor), scopes.RES_Q)
        k = checkpoint_name(parts.rope(
            jnp.einsum(f"bsd,dhk->{heads}", h, p["wk"]),
            positions, cfg.rope_theta, s_minor), scopes.RES_K)
        v = checkpoint_name(
            jnp.einsum(f"bsd,dhk->{heads}", h, p["wv"]), scopes.RES_V)
    with jax.named_scope(scopes.ATTN):
        attn = _attention(q, k, v, p, cfg)
    with jax.named_scope(scopes.PROJ):
        x = checkpoint_name(parts.residual_add(x, jnp.einsum(
            f"{heads},hkd->bsd", attn, p["wo"],
            preferred_element_type=jnp.float32)), scopes.RES_MID)
    return _mlp(x, p, cfg)


def _swiglu(x, p, cfg: LlamaConfig):
    """x + down(silu(gate(h)) · up(h)), h = norm(x), on [B, rows, D]."""
    with jax.named_scope(scopes.LN2):
        h = _norm(x, p["mlp_norm"], cfg)
    y = parts.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    with jax.named_scope(scopes.MLP):
        return parts.residual_add(x, y)


def _mlp(x, p, cfg: LlamaConfig):
    """The block's second half, norm and all. Where one hidden tensor of the
    whole sequence would pass parts.MLP_CHUNK_BYTES the sequence goes in
    chunks (parts.mlp_rows, parts.in_row_chunks), each its own ``checkpoint``:
    a chunk's hidden tensors are made again in its backward, never exist for
    the whole sequence (nor can a remat policy keep them: llama.block_shard
    tells the rule so).
    The norm is the chunk's too — a row's norm needs the row alone — so the
    loop's one input is the stream itself: the normed stream and its gradient
    never stand whole beside it. That is 0.5 GB of the 32,768-byte EvaByte
    step's peak, which falls in this loop's backward; with it, and k kept
    where q was, the step fits without the compiler making k and v a second
    time in every layer (PERF.md §6, PR 32)."""
    return parts.in_row_chunks(
        partial(_swiglu, p=p, cfg=cfg), x,
        parts.mlp_rows(*x.shape, cfg.d_ff, x.dtype.itemsize))


def block_shard(cfg: LlamaConfig, global_batch: int, seq: int,
                mesh) -> parts.BlockShard:
    """This config's block on one chip of ``mesh``, for the remat rule: the
    shapes of ITS residuals (two hidden tensors of d_ff, k and v of n_kv_head
    heads, with eva the window and the chunk the summaries come from)."""
    columns = cfg.n_pred_heads * cfg.head_vocab
    return parts.shard_block(parts.BlockShard(
        batch=global_batch, seq=seq, d_model=cfg.d_model, heads=cfg.n_head,
        head_dim=cfg.head_dim, d_ff=cfg.d_ff, vocab=columns,
        dtype_bytes=jnp.dtype(cfg.dtype).itemsize,
        flash=parts.is_flash(cfg.attention_impl, mesh),
        dense_mlp=True, kv_heads=cfg.n_kv_head,
        mlp_hidden=(scopes.RES_MLP_GATE, scopes.RES_MLP_UP),
        window=cfg.window if cfg.mixer == "eva" else 0, chunk=cfg.chunk,
        head_rows=parts.head_rows(global_batch, seq, columns,
                                  cfg.n_pred_heads),
        mlp_rows=parts.mlp_rows(global_batch, seq, cfg.d_model, cfg.d_ff,
                                jnp.dtype(cfg.dtype).itemsize),
        cast_in_loop=True,
    ), mesh)


def _trunk(params, tokens, cfg: LlamaConfig):
    """tokens [B, S] int32 → final hidden states [B, S, D]."""
    from ray_tpu.parallel import mesh as mesh_lib

    B, S = tokens.shape
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    block_fn = parts.checkpoint_block(
        partial(_block, cfg=cfg), cfg.remat,
        block_shard(cfg, B, S, mesh_lib.current_mesh()), cfg.n_layer)
    x = run_blocks(block_fn, x, params["blocks"])
    with jax.named_scope(scopes.LN_F):
        return _norm(x, params["final_norm"], cfg)


def forward(params, tokens, cfg: LlamaConfig) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, n_pred_heads · head_vocab], head p
    in columns p·head_vocab … (p+1)·head_vocab."""
    x = _trunk(params, tokens, cfg)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype))


def loss_fn(params, tokens, targets, cfg: LlamaConfig) -> jax.Array:
    """Mean cross-entropy over targets >= 0 ([B, S] int32, the next token):
    with n_pred_heads > 1 head p is scored on targets[t + p]."""
    x = _trunk(params, tokens, cfg)
    return parts.lm_head_loss(x, targets, params["lm_head"], cfg.dtype,
                              cfg.n_pred_heads)


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
