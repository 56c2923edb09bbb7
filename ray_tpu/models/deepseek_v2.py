"""DeepSeek-V2-family decoder in pure JAX: latent attention over a dense or a
shared + routed-experts feed-forward half, on one residual stream or on a
hyper-connection's n.

Sixth model family beside GPT-2, LLaMA, Nemotron-H, MiniCPM-SALA and
LFM2-MoE, with two callers: DeepSeek-V2-Lite (PR 55) and Xing4.0 (PR 57),
which differ in what the CONFIG states, never in a second copy of the layer.
Every layer is a pre-normed pair of sublayers, latent attention and then a
feed-forward half, of one of two kinds (one character a layer of
``cfg.pattern``, from the published ``first_k_dense_replace``):

- ``D`` — latent attention + a dense SwiGLU MLP (the leading layers);
- ``E`` — latent attention + a mixture of experts.

**The residual path** (``hc_mult``). 1: ``h = x + MLA(RMSNorm(x))``, ``x' = h
+ FF(RMSNorm(h))`` on a ``[B, S, d_model]`` carry. n > 1: a
manifold-constrained hyper-connection of n streams around every sublayer
(models/hyper_connections.py: the carry is ``[B, S, n · d_model]``; a
sublayer reads ``RMSNorm(Σ H_pre[i] · x[i])`` and its float32 output y goes
back as ``x'[i] = Σ H_res[i, j] · x[j] + H_post[i] · y``, the three maps made
from the token's own streams, H_res by ``hc_sinkhorn_iters`` Sinkhorn rounds);
the streams start as n copies of the embedding and end summed. With 1 there
is no map, no parameter and the lowered step is what it was before the path
existed (tests/test_xing4.py holds it to the recorded text).

**Multi-head latent attention** (arXiv:2405.04434 §2.1): ``q = u·W_q`` →
``[q_nope | q_pe]`` a head — or, with ``q_lora_rank``, the compressed query
``q = RMSNorm(u·W_qa)·W_qb`` (null in the Lite model) —; ``[c | k_pe] =
u·W_kva``, ``c ← RMSNorm(c)``; ``[k_nope | v] = c·W_kvb`` a head; ``k_pe`` is
ONE head for all of them. RoPE turns ``q_pe`` and ``k_pe`` only — the last
``qk_rope_dim`` channels of the q·k width — with YaRN's frequencies
(parts.yarn_inv_freq); ``k = [k_nope | k_pe]``; causal softmax of ``q·kᵀ ·
s``, ``s = (nope + rope)^-½ · m²`` with YaRN's ``m = 0.1 · mscale_all_dim ·
ln(factor) + 1``; ``· v`` at ``v_head_dim``; ``· W_o``. q and k are ``nope +
rope`` wide (192) and v and o ``v_head_dim`` (128): the flash kernels read
each at its own width (ops/attention.py, the S-minor pair — kernel_layout
says why), in parts.head_layout's order with no transpose at their edge. The
rotary channels of ``W_q`` (``W_qb``) and ``W_kva`` are stored de-interleaved
(pairs (i, i + rope/2)): the published checkpoint's (2i, 2i + 1) order is a
permutation of those columns, the same for q and k, which q·k does not see.

The feed-forward halves: the dense ``(silu(u·W₁) ⊙ u·W₃)·W₂``, and the
expert layer (ops/moe.gated_moe) under the router's RULE, which is the
configuration's (``cfg.rule``, ``selection_bias``, ``aux_loss_alpha``), not
a constant of this module: scores over all ``n_experts`` in float32 — one
``softmax`` (DeepSeek-V2) or each expert's ``sigmoid`` (Xing4.0's
``noaux_tc``) —, the ``top_k`` largest of score (+ a selection bias, a
buffer no gradient or decay reaches, where the config has one) chosen, gates
the chosen scores as they are or divided by their sum (``norm_topk_prob``),
times ``routed_scaling``; experts of the dense MLP's form at ``d_expert``,
beside ONE shared expert of the same form at ``n_shared · d_expert`` that
every token takes. With ``aux_loss_alpha`` > 0 training adds, an expert
layer, that × the sequence-wise balance loss (ops/moe.balance_loss): it
leaves the layer loop as a float32 a layer beside the layer's counters and
is added to the loss inside ``loss_fn``, so the step's ``jax.grad`` sees it.
A benchmark's run on freshly drawn weights has its routers balanced once at
set-up — by that loss where there is no bias (balance_routers), by the
bias's own rule where there is one (balance_router_bias) — set-up's alone:
no training path calls either. The head is untied, after an RMSNorm.

**Multi-token prediction** (``mtp_layers``; DeepSeek-V3, arXiv:2412.19437
§2.2): after the trunk, a module of that many expert layers on
``parts.mtp_join`` of the trunk's stream (before the final norm) and the
next token's embedding, with its own hyper-connections and final norm and
the SHARED embedding and head, targets shifted one further; ``loss =
CE_trunk + mtp_loss_weight · CE_mtp``.

It runs on the shared machinery: ``blocks.run_pattern`` /
``blocks.checkpoint_kinds`` (ONE remat rule over the two kinds, the trunk's
runs and the MTP module's), parts' RMSNorm, RoPE, residual add, weight cast
inside the loop, causal attention, the MTP join, the rows an MLP and a head
take at a time and the chunked head + loss; ops/moe.py's dispatch, shared
with the Nemotron-H and LFM2 families' expert layers; tracing/names.py's
scopes and residuals.

The config states the chip's SHARE of a deployment beside the published
sizes, as LFM2MoEConfig does: which routed experts and how many vocabulary
rows are held here, and which published layer the pattern starts at.
Routing is over all ``n_experts`` at the published top-k; what absent
experts would have added is left out: the shares' routed parts and
everything a chip computes whole, counted once, add up to the uncut layer
(tests/test_deepseek_v2.py, tests/test_xing4.py).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import blocks, hyper_connections as hyper, parts
from ray_tpu.ops import moe
from ray_tpu.tracing import names as scopes

KINDS = "DE"
EXPERTS = {"D": False, "E": True}
# the prefixes of a hyper-connected layer's two sublayers' maps
HC_ATTN, HC_FFN = "hc_attn_", "hc_ffn_"


@dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102400          # rows of the embedding / head held here
    seq_len: int = 4096
    n_layer: int = 27                 # layers run here
    first_layer: int = 0              # the published index of the first
    first_k_dense: int = 1            # published layers below this are dense
    d_model: int = 2048
    n_head: int = 16
    # query compression: q = RMSNorm(u·W_qa)·W_qb through this rank; None
    # (the Lite model's null): q = u·W_q
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10_000.0
    # YaRN (rope_scaling): factor 1 is plain RoPE
    rope_factor: float = 40.0
    rope_original_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    d_ff: int = 10944                 # the dense layers' SwiGLU hidden
    # the expert layers: the router is n_experts wide; ids held_first … +
    # held_count − 1 are computed here
    n_experts: int = 64
    top_k: int = 6
    held_first: int = 0
    held_count: int = 64
    d_expert: int = 1408
    n_shared: int = 2                 # one shared SwiGLU of n_shared · d_expert
    routed_scaling: float = 1.0
    # the router's rule: how logits become scores, whether the chosen scores
    # are divided by their sum, whether a selection bias (a buffer) chooses
    # beside the scores; and the balance loss's coefficient (0: no such loss)
    scoring: str = "softmax"
    norm_topk_prob: bool = False
    selection_bias: bool = False
    aux_loss_alpha: float = 0.001
    # the residual path: 1 is the plain ``x + F(norm(x))``; n > 1 a
    # manifold-constrained hyper-connection of n streams around every
    # sublayer (models/hyper_connections.py)
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    # a multi-token-prediction module of this many expert layers after the
    # trunk (0: none), its loss's weight, and the published depth (the
    # module's layers are published layers n_layer_published + k)
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.1
    n_layer_published: Optional[int] = None
    init_std: float = 0.02            # initializer_range, every matrix
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "auto"

    def __post_init__(self):
        if not isinstance(self.remat, bool):
            raise ValueError(f"remat must be True or False; got {self.remat!r}")
        if self.n_layer < 1:
            raise ValueError("n_layer must be at least 1")
        if not 0 <= self.held_first <= self.n_experts - self.held_count:
            raise ValueError(
                f"held experts {self.held_first}…+{self.held_count} are not "
                f"among {self.n_experts}")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError("top_k must be in [1, n_experts]")
        if self.qk_rope_dim % 2:
            raise ValueError("qk_rope_dim must be even")
        if self.scoring not in moe.SCORING:
            raise ValueError(f"scoring must be one of {sorted(moe.SCORING)}")
        if self.hc_mult < 1 or self.mtp_layers < 0:
            raise ValueError("hc_mult must be at least 1, mtp_layers at "
                             "least 0")
        if self.hc_mult > 1 and self.d_model % 128:
            raise ValueError(
                "under a hyper-connection (hc_mult > 1) a stream is whole "
                f"lane tiles: d_model {self.d_model} is not a multiple of 128")
        if self.vocab_size % 128:
            raise ValueError("vocab_size (the rows held here) must be a "
                             "multiple of 128")
        if self.rope_mscale != self.rope_mscale_all_dim:
            raise ValueError(
                "mscale != mscale_all_dim scales cos and sin by their ratio, "
                "which no published config of the family does and "
                "parts.rope does not")

    @property
    def pattern(self) -> str:
        return "".join("D" if self.first_layer + i < self.first_k_dense
                       else "E" for i in range(self.n_layer))

    @property
    def mtp_pattern(self) -> str:
        return "E" * self.mtp_layers

    @property
    def rule(self) -> moe.Rule:
        return moe.Rule(scoring=self.scoring, normalise=self.norm_topk_prob)

    @property
    def hc(self) -> Optional[hyper.HyperConnection]:
        """The residual path's hyper-connection, or None for the plain one."""
        if self.hc_mult == 1:
            return None
        return hyper.HyperConnection(self.hc_mult, self.hc_sinkhorn_iters,
                                     self.hc_eps, self.hc_res_clamp,
                                     self.rms_eps)

    @property
    def carry_width(self) -> int:
        """Channels of the layers' carry: hc_mult streams of d_model."""
        return self.hc_mult * self.d_model

    @property
    def qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def held(self) -> moe.Held:
        return moe.Held(self.held_first, self.held_count)

    @property
    def softmax_scale(self) -> float:
        """``qk_dim^-½ · m²``: YaRN's attention factor on q AND k."""
        m = (0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0
             if self.rope_factor > 1 else 1.0)
        return m * m / math.sqrt(self.qk_dim)


def xing4_tiny(**overrides) -> DeepseekV2Config:
    """Test-size config of the Xing4.0 kind: the second of two leading dense
    layers and expert layers, query compression, four streams, a biased
    sigmoid router whose chosen scores are normalised and scaled, one shared
    expert, no balance loss, an MTP module."""
    return replace(DeepseekV2Config(
        vocab_size=256, seq_len=64, n_layer=3, first_layer=1, first_k_dense=2,
        d_model=128, n_head=4, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, rope_factor=64.0,
        rope_original_len=16, rope_mscale=1.0, rope_mscale_all_dim=1.0,
        d_ff=160, n_experts=16, top_k=4, held_first=4, held_count=8,
        d_expert=48, n_shared=1, routed_scaling=2.0, scoring="sigmoid",
        norm_topk_prob=True, selection_bias=True, aux_loss_alpha=0.0,
        hc_mult=4, mtp_layers=1, n_layer_published=6), **overrides)


def deepseek_v2_tiny(**overrides) -> DeepseekV2Config:
    """Test-size config: the leading dense layer and a run of expert layers,
    q·k and v of unequal widths."""
    return replace(DeepseekV2Config(
        vocab_size=256, seq_len=64, n_layer=4, d_model=64, n_head=4,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        rope_original_len=16, d_ff=160, n_experts=16, top_k=4, held_first=4,
        held_count=8, d_expert=48, n_shared=2), **overrides)


# --------------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------------- #

_ATTN_WEIGHTS = ("wq", "wkv_a", "wkv_b", "wo")
_COMPRESSED_Q = ("wq_a", "wq_b")         # in wq's place under q_lora_rank
_DENSE_WEIGHTS = ("w_gate", "w_up", "w_down")


def _matmul_weights(kind: str, cfg: DeepseekV2Config) -> Tuple[str, ...]:
    """What a layer of ``kind`` takes in the compute dtype (the router, the
    norms' gains and the hyper-connection's α and biases stay as they are
    stored)."""
    attn = _ATTN_WEIGHTS if cfg.q_lora_rank is None else (
        _COMPRESSED_Q + _ATTN_WEIGHTS[1:])
    maps = (HC_ATTN + hyper.PHI, HC_FFN + hyper.PHI) if cfg.hc else ()
    return attn + maps + (moe.GATED_EXPERT + moe.GATED_SHARED_EXPERT
                          if EXPERTS[kind] else _DENSE_WEIGHTS)


def _runs(cfg: DeepseekV2Config) -> List[Tuple[str, int]]:
    """Every run of layers a step applies, in the forward's order: the
    trunk's, then the MTP module's."""
    return (blocks.pattern_groups(cfg.pattern)
            + blocks.pattern_groups(cfg.mtp_pattern))


def _layer_init(rng, n: int, kind: str, cfg: DeepseekV2Config):
    """``n`` stacked layers of ``kind``: the two pre-norms, latent
    attention's tensors and the feed-forward half's."""
    D, H, pd, std = cfg.d_model, cfg.n_head, cfg.param_dtype, cfg.init_std
    k_ff, *k = jax.random.split(rng, 8)
    k = iter(k)

    def normal(shape):
        return (jax.random.normal(next(k), shape) * std).astype(pd)

    p = {"attn_norm": jnp.ones((n, D), pd), "ffn_norm": jnp.ones((n, D), pd),
         "wq": normal((n, D, H, cfg.qk_dim)),
         # the published kv_a_proj_with_mqa: the latent, then the one k_pe
         "wkv_a": normal((n, D, cfg.kv_lora_rank + cfg.qk_rope_dim)),
         "kv_norm": jnp.ones((n, cfg.kv_lora_rank), pd),
         # the published kv_b_proj: a head's k_nope, then its v
         "wkv_b": normal((n, cfg.kv_lora_rank, H,
                          cfg.qk_nope_dim + cfg.v_head_dim)),
         "wo": normal((n, H, cfg.v_head_dim, D))}
    # (keys of their own: the tensors above are drawn as they always were)
    k_q, k_norm, k_hc_a, k_hc_f = jax.random.split(jax.random.fold_in(rng, 1), 4)
    if cfg.q_lora_rank is not None:
        r = cfg.q_lora_rank
        del p["wq"]
        p.update(
            wq_a=(jax.random.normal(k_q, (n, D, r)) * std).astype(pd),
            q_norm=jnp.ones((n, r), pd),
            wq_b=(jax.random.normal(k_norm, (n, r, H, cfg.qk_dim)) * std
                  ).astype(pd))
    if cfg.hc:
        p.update(hyper.init(k_hc_a, n, cfg.hc, D, std, pd, HC_ATTN))
        p.update(hyper.init(k_hc_f, n, cfg.hc, D, std, pd, HC_FFN))
    if EXPERTS[kind]:
        p.update(moe.gated_moe_init(
            k_ff, n, D, cfg.n_experts, cfg.held_count, cfg.d_expert, std, std,
            pd, selection_bias=cfg.selection_bias,
            d_shared=cfg.n_shared * cfg.d_expert))
    else:
        p.update(w_gate=normal((n, D, cfg.d_ff)), w_up=normal((n, D, cfg.d_ff)),
                 w_down=normal((n, cfg.d_ff, D)))
    return p


def _stack_init(rng, pattern: str, cfg: DeepseekV2Config):
    return blocks.init_pattern(rng, pattern, KINDS,
                               partial(_layer_init, cfg=cfg))


_LAYER_AXES = {
    "attn_norm": ("layers", "embed"), "ffn_norm": ("layers", "embed"),
    "wq": ("layers", "embed", "heads", "kv"),
    "wq_a": ("layers", "embed", None), "q_norm": ("layers", None),
    "wq_b": ("layers", None, "heads", "kv"),
    **hyper.logical_axes(HC_ATTN), **hyper.logical_axes(HC_FFN),
    "wkv_a": ("layers", "embed", None), "kv_norm": ("layers", None),
    "wkv_b": ("layers", None, "heads", "kv"),
    "wo": ("layers", "heads", "kv", "embed"),
    "w_gate": ("layers", "embed", "mlp"), "w_up": ("layers", "embed", "mlp"),
    "w_down": ("layers", "mlp", "embed"),
    **moe.gated_moe_logical_axes(),
}


def _stack_axes(pattern: str, cfg: DeepseekV2Config):
    layers = jax.eval_shape(
        lambda: _stack_init(jax.random.PRNGKey(0), pattern, cfg))
    return [{kind: {name: _LAYER_AXES[name] for name in stack}
             for kind, stack in group.items()} for group in layers]


def logical_axes(cfg: DeepseekV2Config) -> Dict[str, Any]:
    out = {"wte": ("vocab", "embed"),
           "blocks": _stack_axes(cfg.pattern, cfg),
           "final_norm": ("embed",),
           "lm_head": ("embed", "vocab")}
    if cfg.mtp_layers:
        out["mtp"] = {"blocks": _stack_axes(cfg.mtp_pattern, cfg),
                      "enorm": ("embed",), "hnorm": ("embed",),
                      "eh_proj": (None, "embed"), "final_norm": ("embed",)}
    return out


def mesh_rules(cfg: DeepseekV2Config, mesh) -> Dict[str, str]:
    """What this config needs of this mesh: no rule beyond the defaults, and
    the refusal of the axes no code here runs over."""
    for axis, why in (
            ("ep", "the expert layer computes the experts the config says it "
                   "holds and no all-to-all exchanges tokens"),
            ("tp", "the latent, the one shared k_pe and the held experts' "
                   "hidden width are not divided here"),
            ("pp", "a pattern of kinds under a stage schedule"),
            ("cp", "the flash kernels hold a row's keys whole")):
        if mesh.shape.get(axis, 1) > 1:
            raise NotImplementedError(
                f"{axis} > 1 is not implemented for the DeepSeek-V2 family "
                f"({why}); use a {axis}=1 mesh")
    return {}


def init(cfg: DeepseekV2Config, rng: jax.Array) -> Dict[str, Any]:
    k = jax.random.split(rng, 3)
    D, pd = cfg.d_model, cfg.param_dtype

    def normal(key, shape):
        return (jax.random.normal(key, shape) * cfg.init_std).astype(pd)

    out = {"wte": normal(k[0], (cfg.vocab_size, D)),
           "blocks": _stack_init(k[1], cfg.pattern, cfg),
           "final_norm": jnp.ones((D,), pd),
           "lm_head": normal(k[2], (D, cfg.vocab_size))}
    if cfg.mtp_layers:
        # (keys of their own: the tensors above are drawn as they always were)
        k_stack, k_proj = jax.random.split(jax.random.fold_in(rng, 1))
        out["mtp"] = {"blocks": _stack_init(k_stack, cfg.mtp_pattern, cfg),
                      "enorm": jnp.ones((D,), pd), "hnorm": jnp.ones((D,), pd),
                      "eh_proj": normal(k_proj, (2 * D, D)),
                      "final_norm": jnp.ones((D,), pd)}
    return out


def param_count(cfg: DeepseekV2Config) -> int:
    """The parameters a step moves: every leaf but the expert layers'
    selection biases, which are buffers (a router without one has none)."""
    return parts.param_count(lambda: init(cfg, jax.random.PRNGKey(0)),
                             "router_bias")


def decays(params):
    """Which leaves an optimizer's weight decay may touch (optax's ``mask``):
    all but the selection biases — no gradient reaches them, and a decay must
    not."""
    return parts.all_but(params, "router_bias")


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #

def rope_inv_freq(cfg: DeepseekV2Config) -> np.ndarray:
    """The rotary channels' ``qk_rope_dim`` / 2 frequencies: YaRN's blend, or
    plain ``theta^(-2i/dim)`` at factor 1."""
    if cfg.rope_factor > 1:
        return parts.yarn_inv_freq(
            cfg.qk_rope_dim, cfg.rope_theta, cfg.rope_factor,
            cfg.rope_original_len, cfg.rope_beta_fast, cfg.rope_beta_slow)
    half = cfg.qk_rope_dim // 2
    return (cfg.rope_theta ** (-np.arange(half) / half)).astype(np.float32)


def mla_operator(u, p, cfg: DeepseekV2Config):
    """u [B, S, D] (normed) → latent attention's output [B, S, D] float32."""
    nope, rope_dim, hv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    rank = cfg.kv_lora_rank
    layout = parts.head_layout(cfg.qk_dim, hv)
    heads = layout.replace("d", "k")                    # the einsums' names
    s_minor, width = heads[-1] == "s", heads.index("k")
    one_head = "bks" if s_minor else "bsk"              # k_pe: no head dim
    rotate = partial(parts.rope, positions=jnp.arange(u.shape[1]),
                     theta=cfg.rope_theta, s_minor=s_minor,
                     inv_freq=rope_inv_freq(cfg))

    with jax.named_scope(scopes.MLA_LATENT):
        # the two halves of each joint projection are slices of the WEIGHT:
        # no activation is cut in two
        if cfg.q_lora_rank is None:
            q_in, wq, q_spec = u, p["wq"], f"bsd,dhk->{heads}"
        else:       # the compressed query: a latent, its norm, then the heads
            q_in, wq, q_spec = parts.rmsnorm(
                jnp.einsum("bsd,dr->bsr", u, p["wq_a"]), p["q_norm"],
                cfg.rms_eps), p["wq_b"], f"bsr,rhk->{heads}"
        q = checkpoint_name(
            rotate(jnp.einsum(q_spec, q_in, wq),
                   span=(nope, nope + rope_dim)), scopes.RES_Q)
        c = checkpoint_name(parts.rmsnorm(
            jnp.einsum("bsd,dc->bsc", u, p["wkv_a"][:, :rank]),
            p["kv_norm"], cfg.rms_eps), scopes.RES_MLA_C)
        k_pe = checkpoint_name(
            rotate(jnp.einsum(f"bsd,dk->{one_head}", u, p["wkv_a"][:, rank:])),
            scopes.RES_MLA_KPE)
        k_nope = jnp.einsum(f"bsc,chk->{heads}", c, p["wkv_b"][..., :nope])
        v = checkpoint_name(
            jnp.einsum(f"bsc,chk->{heads}", c, p["wkv_b"][..., nope:]),
            scopes.RES_V)
        # the one k_pe serves every head: beside each head's k_nope
        shared = jnp.expand_dims(k_pe, heads.index("h"))
        k = checkpoint_name(jnp.concatenate(
            [k_nope, jnp.broadcast_to(
                shared, k_nope.shape[:width] + (rope_dim,)
                + k_nope.shape[width + 1:])], axis=width), scopes.RES_K)
    with jax.named_scope(scopes.ATTN):
        o = parts.causal_attention(q, k, v, cfg.attention_impl, layout=layout,
                                   scale=cfg.softmax_scale)
    with jax.named_scope(scopes.PROJ):
        return jnp.einsum(f"{heads},hkd->bsd", o, p["wo"],
                          preferred_element_type=jnp.float32)


def _swiglu(x, p, cfg: DeepseekV2Config, add: bool = True):
    """x + down(silu(gate(h)) · up(h)), h = norm(x), on [B, rows, D]; without
    ``add`` the float32 product alone (a hyper-connected layer writes it back
    itself)."""
    with jax.named_scope(scopes.LN2):
        h = parts.rmsnorm(x, p["ffn_norm"], cfg.rms_eps)
    y = parts.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    if not add:
        return y
    with jax.named_scope(scopes.MLP):
        return parts.residual_add(x, y)


def _dense(x, p, cfg: DeepseekV2Config, add: bool = True):
    """The dense feed-forward half, norm and all, in chunks of the sequence
    where parts.mlp_rows says so — as the llama block's, and why
    (models/llama.py)."""
    return parts.in_row_chunks(
        partial(_swiglu, p=p, cfg=cfg, add=add), x,
        parts.mlp_rows(*x.shape, cfg.d_ff, x.dtype.itemsize))


def _routing(cfg: DeepseekV2Config) -> Dict[str, Any]:
    # (the published code's 1e-20 under the chosen scores' sum, where the
    # rule divides by it)
    return dict(top_k=cfg.top_k, held=cfg.held, scaling=cfg.routed_scaling,
                rule=cfg.rule, **({"eps": 1e-20} if cfg.norm_topk_prob else {}))


def _experts(x, p, cfg: DeepseekV2Config, aux: Optional[str], rate=None,
             add: bool = True):
    """The expert feed-forward half → (x — without ``add`` the half's float32
    output alone —, what ``aux`` asks of it)."""
    B, S, D = x.shape
    with jax.named_scope(scopes.LN2):
        h = parts.rmsnorm(x, p["ffn_norm"], cfg.rms_eps)
    ht, out = h.reshape(-1, D), None
    if aux == "balance" and cfg.selection_bias:
        bias = moe.balance_bias_round(ht, p["router_w"], p["router_bias"],
                                      cfg.top_k, rate)
        p = {**p, "router_bias": bias}
        out = {"router_bias": bias, **moe.held_load(ht, p, **_routing(cfg))}
    elif aux == "balance":
        router_w = moe.balance_router(ht, p["router_w"], cfg.top_k, S, rate,
                                      cfg.rule)
        p = {**p, "router_w": router_w}
        out = {"router_w": router_w, **moe.held_load(ht, p, **_routing(cfg))}
    elif aux == "chosen":
        out = moe.chosen_experts(ht, p, cfg.top_k, cfg.rule)
    with jax.named_scope(scopes.MOE):
        f, load = moe.gated_moe(
            h, p, **_routing(cfg),
            balance=aux == "load" and cfg.aux_loss_alpha > 0,
            shared_rows=parts.mlp_rows(B, S, D, cfg.n_shared * cfg.d_expert,
                                       x.dtype.itemsize))
    return (parts.residual_add(x, f) if add else f,
            load if aux == "load" else out)


def _mixed(x, p, prefix: str, cfg: DeepseekV2Config):
    """(The carry for ``_joined`` to read, what a sublayer reads of it, the
    maps it will write back by): x, x itself and None on the plain residual
    path; under a hyper-connection the carry handed through the mix, the
    pre-mix of the streams and the token's maps (hyper.mixed)."""
    if cfg.hc is None:
        return x, x, None
    with jax.named_scope(scopes.MHC):
        return hyper.mixed(x, p, prefix, cfg.hc)


def _joined(x, y, h):
    """The carry after a sublayer's float32 output y: ``x + y``, or the
    hyper-connection's write-back by the maps ``h``."""
    if h is None:
        return parts.residual_add(x, y)
    with jax.named_scope(scopes.MHC):
        return hyper.joined(x, y, h)


@jax.named_scope(scopes.BLOCK)
def _layer(x, p, cfg: DeepseekV2Config, kind: str, aux: Optional[str] = None,
           rate=None):
    """One layer of ``kind``, x [B, S, carry_width]: latent attention's
    residual, then the feed-forward half's — each ``x + F(norm(x))``, or under
    a hyper-connection F(norm(the streams' pre-mix)) written back to every
    stream (_mixed, _joined). With ``aux`` the result is (x, aux's value),
    None for a dense layer: ``"load"`` — the training forward's: what the
    batch sends the held experts, as the dispatch that runs the passes has
    it, and the layer's balance loss (moe.routed_experts) —; in a forward of
    its own, no backward, ``"balance"`` — an expert layer first takes one
    round of balancing its router on this input, at ``rate``
    (moe.balance_router); the router and what the input then sends the held
    experts (moe.held_load) —, ``"chosen"`` — the set each token chose,
    [T, n_experts] bool."""
    p = {**p, **parts.cast_in_the_loop(p, x, cfg.dtype,
                                       _matmul_weights(kind, cfg))}
    plain = cfg.hc is None
    x, mixed, h = _mixed(x, p, HC_ATTN, cfg)
    with jax.named_scope(scopes.LN1):
        u = parts.rmsnorm(mixed, p["attn_norm"], cfg.rms_eps)
    x = checkpoint_name(_joined(x, mla_operator(u, p, cfg), h),
                        scopes.RES_MID)
    # (the plain path's halves add their own residual, where they always did:
    # a chunked half inside each chunk)
    x, mixed, h = _mixed(x, p, HC_FFN, cfg)
    if EXPERTS[kind]:
        y, out = _experts(mixed, p, cfg, aux, rate, add=plain)
    else:
        y, out = _dense(mixed, p, cfg, add=plain), None
    x = y if plain else _joined(x, y, h)
    return (x, out) if aux else x


def kind_shards(cfg: DeepseekV2Config, global_batch: int, seq: int, mesh
                ) -> Tuple[parts.BlockShard, Dict[str, blocks.KindShard]]:
    """This config's layers on one chip of ``mesh``, for the remat rule: the
    model's shard (stream, head, rows at a time) and, a kind, how often it is
    applied, what a layer of it may keep, its weight gradients and what its
    backward holds at once: the LARGEST of the backward's moments, each
    summed from what is live in it — never their sum, since no two of them
    overlap. Through every moment waits the cotangent of the block's output.

    - The feed-forward half's backward. Of latent attention there waits
      what its own backward will read — the block's input, ``u``, q, k, v,
      o (+ lse), the weights' cast — and not yet a gradient of those. The
      expert half holds its own stream (its input, the normed input, the
      float32 sum and its cotangent) and the routing's three [tokens,
      n_experts] tensors beside ONE of two: the routed passes' rows with
      the held experts' weights cast and their float32 gradients, or the
      shared expert's hidden tensors and weights. The dense half holds a
      chunk's hidden tensors and its weights.
    - Latent attention's own backward: its whole set, gradients and all,
      and nothing of the feed-forward half, which is done.

    At the DeepSeek-V2-Lite cell's shapes (4 x 8,192 tokens, 16 held experts
    1,408 wide, a 61,440-row buffer) the routed passes' moment is the largest
    by far, 4.30 GB: a row buffer of 1.25 x the pairs at 2 · 2,048 + 6 ·
    1,408 numbers a row (1.54 GB) and 4 + 2 bytes a held weight (0.83)
    outweigh the shared expert's un-chunked five [32,768, 2,816] tensors
    (0.99), and attention's waiting operands (0.97) with the half's stream
    (0.83) stand beside either; attention's own backward holds 2.12. Summed,
    the three stood at 6.18 GB, 1.9 GB over what the compiled step takes, and
    the rule kept nothing beside a chip with 2 GiB free (PERF.md §6, PR 56).

    Under a hyper-connection (``hc_mult`` n > 1) the carry is n · d_model
    wide and the shard says so (``carry_width``: the stack of block inputs
    and the carried cotangent are priced at it). A sublayer still reads and
    writes ``d_model`` — the pre-mix goes in, the float32 y comes out — so
    each moment holds what it held, with the n-stream tensors that wait in
    it added: through the feed-forward half's backward the block's input and
    the carry after attention's write-back, that carry's cotangent (the
    write-back's ``H_resᵀ`` part, which the mix's backward takes in), and a
    sublayer's maps (the mix's 24 float32 planes and 1 / rms, and two copies
    a Sinkhorn round of its n² planes, 3 KB a token beside the carry's 28);
    attention's own backward holds the block's input and its cotangent in
    the carry after attention's place. The cotangent is priced at float32,
    as XLA summed it before PR 58; the kernels (ops/hyper_connections.py) sum
    it in VMEM and it crosses HBM in the stream's dtype, n · d · (4 − a)
    bytes a token less, 235 MB at the Xing4.0 cell's shapes: the estimate is
    an upper bound by that much, and the compiled step says so (PERF.md §7).
    """
    a = jnp.dtype(cfg.dtype).itemsize
    D, F, Fe, H = cfg.d_model, cfg.d_ff, cfg.d_expert, cfg.n_head
    W = cfg.carry_width
    Fs = cfg.n_shared * cfg.d_expert
    hd, hv, rank = cfg.qk_dim, cfg.v_head_dim, cfg.kv_lora_rank
    flash = parts.is_flash(cfg.attention_impl, mesh)
    base = parts.shard_block(parts.BlockShard(
        batch=global_batch, seq=seq, d_model=D, heads=H, head_dim=hd, d_ff=F,
        vocab=cfg.vocab_size, dtype_bytes=a, flash=flash, dense_mlp=False,
        mlp_hidden=(scopes.RES_MLP_GATE, scopes.RES_MLP_UP),
        head_rows=parts.head_rows(global_batch, seq, cfg.vocab_size, 1),
        mlp_rows=parts.mlp_rows(global_batch, seq, D, F, a),
        cast_in_loop=True, carry_width=0 if cfg.hc is None else W), mesh)
    tokens = base.batch * base.seq
    C = blocks.RematCandidate
    if cfg.hc is None:
        waiting_streams = own_streams = 0
    else:
        n = cfg.hc_mult
        maps = tokens * 4 * (cfg.hc.outputs + 2 * cfg.hc.rounds * n * n)
        # beside the d_model-wide tensors the plain arithmetic counts: the
        # carry after attention and its float32 cotangent, the maps
        waiting_streams = tokens * (W - D) * a + tokens * W * (a + 4) + maps
        own_streams = tokens * (W - D) * a + tokens * W * 4 + maps

    # latent attention. The latent c and the one k_pe are 576 numbers a
    # token that stand for H · (hd + hv) of k and v: kept, k and v are one
    # up-projection from c away (priced so); q costs its projection; the
    # kernel's o and lse its two products over the causal half at their own
    # widths; the stream after the residual the out-projection
    latent = rank + cfg.qk_rope_dim
    q_params = (D * H * hd if cfg.q_lora_rank is None
                else cfg.q_lora_rank * (D + H * hd))
    attn_kept = [
        C((scopes.RES_MLA_C, scopes.RES_MLA_KPE), tokens * latent * a,
          2 * tokens * D * latent),
        C((scopes.RES_Q,), tokens * H * hd * a, 2 * tokens * q_params),
        C((scopes.RES_K,), tokens * H * hd * a,
          2 * tokens * rank * H * cfg.qk_nope_dim),
        C((scopes.RES_V,), tokens * H * hv * a, 2 * tokens * rank * H * hv),
        C((scopes.RES_MID,), tokens * W * a, 2 * tokens * H * hv * D)]
    if flash:
        attn_kept.append(C(
            (scopes.RES_FLASH_O, scopes.RES_FLASH_LSE),
            tokens * H * (hv * a + 4),
            base.batch * H * base.seq * base.seq
            * (max(hd, parts.MXU) + max(hv, parts.MXU))))
    attn_params = q_params + D * latent + rank * H * (cfg.qk_nope_dim + hv) \
        + H * hv * D
    # its backward holds four tensors of the stream's width, q, k and their
    # gradients, v, o and theirs, and the weights cast twice; until then what
    # that backward will read waits: the block's input and u, q, k, v, o (and
    # the kernel's lse), the weights cast once
    attn_set = a * (tokens * (4 * D + 4 * H * hd + 4 * H * hv)
                    + 2 * 2 * attn_params)
    attn_waits = (a * (tokens * (2 * D + 2 * H * hd + 2 * H * hv)
                       + attn_params) + (tokens * H * 4 if flash else 0))
    carried = tokens * W * a    # the cotangent of the block's output

    # the feed-forward halves: the dense one and the shared expert are
    # SwiGLUs (parts.swiglu_price); what the routing decided
    # (parts.routing_candidates); in the expert half's backward its stream
    # beside the LARGER of the routed passes' set and the shared expert's
    dense_kept, dense_set = parts.swiglu_price(
        base.batch, base.seq, base.mlp_rows, D, F, a, base.mlp_hidden)
    shared_kept, shared_set = parts.swiglu_price(
        base.batch, base.seq, parts.mlp_rows(base.batch, base.seq, D, Fs, a),
        D, Fs, a, (scopes.RES_MOE_SHARED_GATE, scopes.RES_MOE_SHARED_UP))
    experts_kept = parts.routing_candidates(
        tokens, D, cfg.n_experts, cfg.top_k, cfg.held_count) + shared_kept
    stream, routed_set = parts.gated_experts_working_set(
        tokens, D, cfg.n_experts, cfg.top_k, cfg.held_count, Fe, a)
    experts_set = stream + max(routed_set, shared_set)

    kinds = {}
    layers = cfg.pattern + cfg.mtp_pattern
    for kind in dict.fromkeys(layers):
        ff_kept, ff_set = ((experts_kept, experts_set) if EXPERTS[kind]
                           else (dense_kept, dense_set))
        kinds[kind] = blocks.KindShard(
            layers.count(kind), tuple(attn_kept) + ff_kept,
            carried + max(attn_waits + ff_set + waiting_streams,
                          attn_set + own_streams))
    return base, blocks.with_grad_bytes(
        blocks.one_candidate_a_name(kinds), partial(_layer_init, cfg=cfg), mesh)


def _block_fns(cfg: DeepseekV2Config, batch: int, seq: int,
               aux: Optional[str] = None):
    from ray_tpu.parallel import mesh as mesh_lib

    base, kinds = kind_shards(cfg, batch, seq, mesh_lib.current_mesh())
    for pattern in filter(None, (cfg.pattern, cfg.mtp_pattern)):
        blocks.record_layer_pattern(pattern)
    if cfg.hc:
        hyper.record_decision(cfg.hc, cfg.d_model, cfg.dtype)
    return blocks.checkpoint_kinds(
        {kind: partial(_layer, cfg=cfg, kind=kind, aux=aux) for kind in kinds},
        cfg.remat, base, kinds, _runs(cfg))


def _hidden(params, tokens, targets, cfg: DeepseekV2Config,
            aux: Optional[str] = None, rate=None):
    """tokens [B, S] int32 → (the trunk's stream [B, S, D] before the final
    norm — a hyper-connection's streams summed —, the MTP module's or None,
    the MTP targets, and with ``aux`` blocks.run_pattern's auxes — each
    layer's, _layer says what —: the trunk's runs, then the MTP module's).
    ``targets`` (the next token) is read by an MTP module only."""
    B, S = tokens.shape
    with jax.named_scope(scopes.EMBED):
        wte = params["wte"].astype(cfg.dtype)
        x = wte[tokens]
    if aux in (None, "load"):       # checkpointed: a backward may follow
        fns = _block_fns(cfg, B, S, aux)
    else:               # a forward of its own: no backward, no checkpoint
        fns = {kind: partial(_layer, cfg=cfg, kind=kind, aux=aux, rate=rate)
               for kind in KINDS}

    def run(pattern, x, stacks):
        """x [B, S, D] through ``pattern``'s layers → [B, S, D]: under a
        hyper-connection the streams start as copies of x and end summed."""
        if cfg.hc:
            with jax.named_scope(scopes.MHC):
                x = hyper.expand(x, cfg.hc_mult)
        out = blocks.run_pattern(fns, pattern, x, stacks, with_aux=bool(aux))
        x, auxes = out if aux else (out, [])
        if cfg.hc:
            with jax.named_scope(scopes.MHC):
                x = hyper.collapse(x, cfg.hc_mult)
        return x, auxes

    x, auxes = run(cfg.pattern, x, params["blocks"])
    if not cfg.mtp_layers:
        return x, None, None, auxes
    mtp = params["mtp"]
    with jax.named_scope(scopes.MTP):
        h, mtp_targets = parts.mtp_join(x, targets, wte, mtp["enorm"],
                                        mtp["hnorm"], mtp["eh_proj"],
                                        cfg.rms_eps)
        h, mtp_auxes = run(cfg.mtp_pattern, h, mtp["blocks"])
    return x, h, mtp_targets, auxes + mtp_auxes


def _final_norm(x, g, cfg: DeepseekV2Config):
    with jax.named_scope(scopes.LN_F):
        return parts.rmsnorm(x, g, cfg.rms_eps)


def forward(params, tokens, cfg: DeepseekV2Config) -> jax.Array:
    """tokens [B, S] int32 → the trunk's logits [B, S, vocab_size]."""
    x, *_ = _hidden(params, tokens, None, replace(cfg, mtp_layers=0))
    return jnp.einsum("bsd,dv->bsv", _final_norm(x, params["final_norm"], cfg),
                      params["lm_head"].astype(cfg.dtype))


def _losses(params, tokens, targets, cfg: DeepseekV2Config,
            aux: Optional[str] = None):
    """(the trunk's mean cross-entropy, the MTP module's or None, _hidden's
    auxes of ``aux``). The MTP module has a final norm of its own and shares
    the embedding and the head."""
    x, h, mtp_targets, auxes = _hidden(params, tokens, targets, cfg, aux)
    trunk = parts.lm_head_loss(_final_norm(x, params["final_norm"], cfg),
                               targets, params["lm_head"], cfg.dtype)
    if h is None:
        return trunk, None, auxes
    with jax.named_scope(scopes.MTP):
        return trunk, parts.lm_head_loss(
            _final_norm(h, params["mtp"]["final_norm"], cfg), mtp_targets,
            params["lm_head"], cfg.dtype), auxes


def losses(params, tokens, targets, cfg: DeepseekV2Config):
    """(the trunk's mean cross-entropy, the MTP module's or 0.0)."""
    trunk, mtp, _ = _losses(params, tokens, targets, cfg)
    return trunk, jnp.zeros((), jnp.float32) if mtp is None else mtp


def step_fields(cfg: DeepseekV2Config) -> Tuple[str, ...]:
    """What loss_fn hands out of a step a layer: the dispatch's counters and,
    under a balance loss, its value (float32 bits in the int32 array)."""
    return scopes.STEP_EXPERT_LOAD_ARGS + (
        (scopes.STEP_BALANCE_LOSS,) if cfg.aux_loss_alpha > 0 else ())


def loss_fn(params, tokens, targets, cfg: DeepseekV2Config,
            counters: bool = False):
    """Mean cross-entropy over targets >= 0 ([B, S] int32, the next token),
    plus ``mtp_loss_weight`` × an MTP module's, plus ``aux_loss_alpha`` × the
    sum over the expert layers of the balance loss: the objective, whole,
    inside what the step differentiates. With ``counters`` (what
    step_counters offers a step factory: the aux of its ``value_and_grad``)
    the result is (the loss, what each expert layer said of the batch: int32
    [expert layers, step_fields], the trunk's layers and then the MTP
    module's)."""
    loss, mtp, auxes = _losses(params, tokens, targets, cfg, "load")
    if mtp is not None:
        loss = loss + cfg.mtp_loss_weight * mtp
    balanced = cfg.aux_loss_alpha > 0
    if balanced and any(EXPERTS[k] for k in cfg.pattern + cfg.mtp_pattern):
        with jax.named_scope(scopes.MOE_AUX):
            loss = loss + cfg.aux_loss_alpha * jnp.sum(
                blocks.aux_column(auxes, scopes.STEP_BALANCE_LOSS))
    if not counters:
        return loss
    return loss, blocks.packed_aux(
        auxes, step_fields(cfg),
        (scopes.STEP_BALANCE_LOSS,) if balanced else ())


def _expert_layer_ids(cfg: DeepseekV2Config) -> Tuple[int, ...]:
    """The published index of every expert layer, the trunk's and then the
    MTP module's (published layers ``n_layer_published`` + k, DeepSeek-V3's
    numbering; past the layers run here where the config does not say)."""
    past = (cfg.n_layer_published if cfg.n_layer_published is not None
            else cfg.first_layer + cfg.n_layer)
    return (tuple(cfg.first_layer + i for i in _expert_layers(cfg.pattern))
            + tuple(past + i for i in _expert_layers(cfg.mtp_pattern)))


def step_counters(cfg: DeepseekV2Config) -> Optional[blocks.StepCounters]:
    """What ``loss_fn(..., counters=True)`` hands out of a step, or None for
    a pattern without an expert layer. A layer's id is ``model/expert_load``'s
    ``layer``: the published index."""
    return parts.expert_step_counters(
        _expert_layer_ids(cfg), cfg.n_experts, cfg.top_k, cfg.held,
        step_fields(cfg),
        (scopes.STEP_BALANCE_LOSS,) if cfg.aux_loss_alpha > 0 else ())


def flops_per_token(cfg: DeepseekV2Config) -> float:
    """Forward + backward operations one trained token REQUIRES here: 6 per
    matmul parameter the token meets (latent attention's projections — the
    query's two under compression —, the router, the shared expert, the
    routed experts by the pairs a token is expected to land on held ones,
    top_k · held / n_experts a layer, a hyper-connection's two Φ a layer; an
    MTP module's layers, its join and its pass through the head; the
    embedding is a gather, the head a matmul) and by shape three times the
    forward's attention (q·k at qk_dim and p·v at v_head_dim over the causal
    half). A hyper-connection's mixes and rounds are elementwise: not
    counted."""
    D, S, H = cfg.d_model, cfg.seq_len, cfg.n_head
    q = (D * H * cfg.qk_dim if cfg.q_lora_rank is None
         else cfg.q_lora_rank * (D + H * cfg.qk_dim))
    attn = (q + D * (cfg.kv_lora_rank + cfg.qk_rope_dim)
            + cfg.kv_lora_rank * H * (cfg.qk_nope_dim + cfg.v_head_dim)
            + H * cfg.v_head_dim * D)
    if cfg.hc:
        attn += 2 * cfg.carry_width * cfg.hc.outputs
    ff = {True: D * cfg.n_experts + 3 * D * cfg.d_expert * (
              cfg.n_shared + cfg.top_k * cfg.held_count / cfg.n_experts),
          False: 3 * D * cfg.d_ff}
    layers = cfg.pattern + cfg.mtp_pattern
    matmul = (sum(attn + ff[EXPERTS[k]] for k in layers)
              + D * cfg.vocab_size)
    if cfg.mtp_layers:
        matmul += 2 * D * D + D * cfg.vocab_size
    shaped = len(layers) * H * (cfg.qk_dim + cfg.v_head_dim) * (S + 1) / 2
    return 6.0 * (matmul + shaped)


# --------------------------------------------------------------------------- #
# What each token chose; what a batch sends the held experts
# --------------------------------------------------------------------------- #

def _expert_layers(pattern: str) -> List[int]:
    """The expert layers' indices in the pattern, in order."""
    return [i for i, kind in enumerate(pattern) if EXPERTS[kind]]


def chosen_experts(params, tokens, cfg: DeepseekV2Config, targets=None
                   ) -> List[jax.Array]:
    """The set each token of ``tokens`` [B, S] chose in each expert layer, in
    the layers' order (the MTP module's last: it reads ``targets``): [B·S,
    n_experts] bool a layer. What a reference is told, so that a near-tie
    rounding flipped is not read as a wrong model."""
    return blocks.aux_by_layer(
        _runs(cfg), _hidden(params, tokens, targets, cfg, "chosen")[3])


def _with_expert_leaf(params, cfg: DeepseekV2Config, name: str, rows):
    """``params`` with leaf ``name`` of every expert layer replaced by its
    entry of ``rows`` (one a layer, in order: the trunk's runs, then the MTP
    module's; a run's stack of ``E`` holds its layers in that order), in the
    old leaf's dtype."""
    rows = iter(rows)
    out = {**params, "blocks": blocks.with_leaf(
        cfg.pattern, params["blocks"], name, rows)}
    if cfg.mtp_layers:
        out["mtp"] = {**params["mtp"], "blocks": blocks.with_leaf(
            cfg.mtp_pattern, params["mtp"]["blocks"], name, rows)}
    return out


def _balanced(params, batches, cfg: DeepseekV2Config, leaf: str, rates):
    """(``params`` after ``len(rates)`` forwards of their own, round r on
    batch r mod N of ``batches`` — dicts of ``tokens`` and ``targets``
    [B, S], or one —, in which every expert layer's ``leaf`` takes one round
    of its balancing rule (_experts' ``"balance"``) at ``rates[r]`` on what
    the layers before it — as balanced so far — hand it, every other tensor
    held; what the last round's batch then sends the experts held here: the
    ``model/expert_load`` events, recorded here)."""
    batches = [batches] if isinstance(batches, dict) else list(batches)

    @jax.jit
    def one_round(p, batch, rate):
        auxes = blocks.aux_by_layer(_runs(cfg), _hidden(
            p, batch["tokens"], batch["targets"], cfg, "balance", rate)[3])
        return [aux.pop(leaf) for aux in auxes], auxes

    balanced = params
    for r, rate in enumerate(rates):
        rows, loads = one_round(balanced, batches[r % len(batches)], rate)
        balanced = _with_expert_leaf(balanced, cfg, leaf, rows)
    return balanced, moe.record_expert_loads(_expert_layer_ids(cfg),
                                             jax.device_get(loads))


def _falling(first_rate: float) -> List[float]:
    """moe.BALANCE_ROUNDS rates falling linearly from ``first_rate`` to 0."""
    rounds = moe.BALANCE_ROUNDS
    return [first_rate * (1.0 - r / rounds) for r in range(rounds)]


def balance_router_bias(params, batches, cfg: DeepseekV2Config):
    """(``params`` with every expert layer's selection bias balanced on
    ``batches`` — N dicts of ``tokens`` and ``targets`` [B, S], or one —,
    what the last round's batch then sends the experts held here). For a
    config whose router has a selection bias: the bias's between-step update
    is not part of the step, so a run starts from a bias that something
    balanced: moe.BALANCE_ROUNDS rounds of the auxiliary-loss-free rule
    (moe.balance_bias_round), round r on batch r mod N, the rate falling from
    moe.BALANCE_RATE to 0, layer by layer (a layer's input is what the layers
    before it, as balanced so far, give), the MTP module's layers after the
    trunk's, the weights held. Give it as many batches as rounds: rounds on
    one batch fit that batch's near-ties (moe.balance_bias_round says what
    that cost). For set-up, as nemotron_h.balance_router_bias and
    lfm2_moe.balance_router_bias are: no training path calls it."""
    return _balanced(params, batches, cfg, "router_bias",
                     _falling(moe.BALANCE_RATE))


def balance_routers(params, batches, cfg: DeepseekV2Config):
    """(``params`` with every expert layer's router balanced on ``batches``
    — N token arrays [B, S], or one —, what the last round's batch then
    sends the experts held here). For a config whose routers have no selection
    bias: what balances them is the balance loss, over a run's many steps, so a
    run on freshly drawn weights starts from routers that something
    balanced: moe.BALANCE_ROUNDS forwards of their own, round r on batch
    r mod N, in which every expert layer's router takes one round of
    moe.balance_router on what the layers before it — as balanced so far —
    hand it, the rate falling from moe.BALANCE_ROUTER_RATE to 0, every other
    weight held. The loads are the ``model/expert_load`` events
    (tracing/names.EXPERT_LOAD_ARGS; ``layer`` is the published index),
    recorded here. For set-up, as nemotron_h.balance_router_bias and
    lfm2_moe.balance_router_bias are: NO TRAINING PATH CALLS IT (nor
    ``_layer``'s ``"balance"``) — a benchmark's build and chip_smoke.py do,
    once before the first step; a run that starts from a checkpoint, or
    trains for long, needs neither."""
    if cfg.mtp_layers or cfg.selection_bias:
        raise ValueError("balance_routers is for routers balanced by a loss "
                         "(no selection bias, no MTP module's targets): "
                         "balance_router_bias balances a bias")
    batches = [batches] if hasattr(batches, "ndim") else batches
    return _balanced(params, [{"tokens": b, "targets": None} for b in batches],
                     cfg, "router_w", _falling(moe.BALANCE_ROUTER_RATE))
