"""LLaMA-family decoder in pure JAX, designed for the MXU and GSPMD.

Second flagship model family beside GPT-2 (models/gpt2.py): the modern
decoder recipe — RMSNorm (pre-norm, no biases), SwiGLU MLP, rotary position
embeddings, grouped-query attention, untied LM head. Same TPU-first
structure as GPT-2: stacked layers under `lax.scan` (or unrolled), logical
axis names on every parameter so any dp/fsdp/tp/cp mesh works through
parallel/sharding.py rules, bf16 compute over f32 params, the Pallas flash
kernel in head-major layout, and the fused softmax cross-entropy
(ops/cross_entropy.py).

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
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


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
    remat: bool = False           # True: recompute each block in the backward
    attention_impl: str = "auto"  # auto | xla | pallas
    scan_layers: bool = True

    def __post_init__(self):
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
    keys = iter(jax.random.split(rng, 9))
    std = 0.02

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape) * s).astype(pd)

    blocks = {
        "attn_norm": jnp.ones((L, D), pd),
        "wq": normal(next(keys), (L, D, H, hd)),
        "wk": normal(next(keys), (L, D, KH, hd)),
        "wv": normal(next(keys), (L, D, KH, hd)),
        "wo": normal(next(keys), (L, H, hd, D), std / math.sqrt(2 * L)),
        "mlp_norm": jnp.ones((L, D), pd),
        "w_gate": normal(next(keys), (L, D, F)),
        "w_up": normal(next(keys), (L, D, F)),
        "w_down": normal(next(keys), (L, F, D), std / math.sqrt(2 * L)),
    }
    return {
        "wte": normal(next(keys), (V, D)),
        "blocks": blocks,
        "final_norm": jnp.ones((D,), pd),
        "lm_head": normal(next(keys), (D, V)),
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

def _rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    rms = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
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
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def _attention(q, k, v, cfg: LlamaConfig):
    """q [B,H,S,hd], k/v [B,KH,S,hd] → [B,H,S,hd], causal, GQA."""
    groups = cfg.n_head // cfg.n_kv_head
    if groups > 1:
        k = jnp.repeat(k, groups, axis=1)
        v = jnp.repeat(v, groups, axis=1)
    from ray_tpu.ops.attention import flash_attention_sharded, resolve_attention
    from ray_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.current_mesh()
    impl, interpret = resolve_attention(cfg.attention_impl, mesh)
    if impl == "ring":
        raise NotImplementedError(
            "models/llama.py has no ring-attention path; use a mesh without "
            "a cp axis"
        )
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


def _block(x, p, positions, cfg: LlamaConfig):
    dt = cfg.dtype
    h = _rmsnorm(x, p["attn_norm"], cfg.rms_eps)
    q = jnp.einsum("bsd,dhk->bhsk", h, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bhsk", h, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bhsk", h, p["wv"].astype(dt))
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    attn = _attention(q, k, v, cfg)
    x = x + jnp.einsum("bhsk,hkd->bsd", attn, p["wo"].astype(dt))
    h = _rmsnorm(x, p["mlp_norm"], cfg.rms_eps)
    gate = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, p["w_gate"].astype(dt)))
    up = jnp.einsum("bsd,df->bsf", h, p["w_up"].astype(dt))
    return x + jnp.einsum("bsf,fd->bsd", gate * up, p["w_down"].astype(dt))


def _trunk(params, tokens, cfg: LlamaConfig):
    B, S = tokens.shape
    dt = cfg.dtype
    x = params["wte"].astype(dt)[tokens]
    positions = jnp.arange(S)

    block_fn = partial(_block, positions=positions, cfg=cfg)
    if cfg.remat:
        block_fn = jax.checkpoint(block_fn)

    if cfg.scan_layers:
        def body(x, layer):
            return block_fn(x, layer), None

        x, _ = lax.scan(body, x, params["blocks"])
    else:
        for i in range(cfg.n_layer):
            layer = jax.tree_util.tree_map(lambda p: p[i], params["blocks"])
            x = block_fn(x, layer)
    return _rmsnorm(x, params["final_norm"], cfg.rms_eps)


def forward(params, tokens, cfg: LlamaConfig) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, padded_vocab]."""
    x = _trunk(params, tokens, cfg)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype))


def loss_fn(params, tokens, targets, cfg: LlamaConfig) -> jax.Array:
    """Mean next-token CE over targets >= 0 (fused CE, no [B,S,V] residual)."""
    from ray_tpu.ops.cross_entropy import softmax_xent

    logits = forward(params, tokens, cfg)
    nll = softmax_xent(logits, targets)
    count = jnp.sum(targets >= 0)
    return jnp.sum(nll) / jnp.maximum(count, 1)


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
