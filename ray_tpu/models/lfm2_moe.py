"""LFM2-MoE-family decoder in pure JAX: a PATTERN of layers that are PAIRS.

Fifth model family beside GPT-2, LLaMA, Nemotron-H and MiniCPM-SALA, and the
first whose layer kind is a pair — an operator AND a feed-forward half, each
a pre-normed residual, ``h = x + Op(RMSNorm(x))``, ``x' = h + FF(RMSNorm(h))``
— read off a pattern string, one character a layer (from the published
``layer_types`` and ``num_dense_layers``: pattern_from):

- ``D`` — a gated short convolution + a dense SwiGLU MLP (the leading layers);
- ``A`` — grouped-query attention + a mixture of gated experts;
- ``C`` — a gated short convolution + a mixture of gated experts.

The operators: the **short convolution** ``[B̃ | C̃ | x̃] = u·W_in``,
``y = C̃ ⊙ conv_K(B̃ ⊙ x̃)`` (depthwise, causal, no bias, no activation:
ops/short_conv.py), ``· W_out``; **attention** with a per-head RMSNorm of q
and k (one gain vector of head_dim each) BEFORE RoPE (rotate-half), causal
softmax at 1/√hd, each key-value head serving n_head / n_kv_head query heads
(parts.causal_attention: the flash kernels, in parts.head_layout's order — at
the published hd = 64 the S-minor pair, RoPE applied in that order).

The feed-forward halves: the dense ``(silu(u·W₁) ⊙ u·W₃)·W₂``, and the
expert layer (ops/moe.gated_moe): sigmoid scores in float32 over all
``n_experts``, the ``top_k`` largest of score + bias chosen (the bias —
``router_bias``, the published ``expert_bias`` — chooses only and is a
buffer), gates ``scaling · s / (Σ_chosen s + route_eps)``, experts of the
dense MLP's form at ``d_expert``, read and written at the model's width,
nothing beside them. The head is the embedding's transpose (tied), after an
RMSNorm.

It runs on the shared machinery: ``blocks.run_pattern`` /
``blocks.checkpoint_kinds`` (ONE remat rule over the three kinds'
applications), parts' RMSNorm, RoPE, residual add, weight cast inside the
loop, causal attention, the rows an MLP and a head take at a time and the
chunked head + loss; ops/moe.py's dispatch, shared with the Nemotron-H
family's expert layer; tracing/names.py's scopes and residuals.

The config states the chip's SHARE of a deployment beside the published
sizes: which routed experts and how many vocabulary rows are held here, and
which published layer the pattern starts at. Routing is over all
``n_experts`` at the published top-k; what absent experts would have added
is left out (no code stands in for absent chips or their exchange): the
shares' expert layers add up to the whole layer's (tests/test_lfm2_moe.py).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import blocks, parts
from ray_tpu.ops import moe, short_conv
from ray_tpu.tracing import names as scopes

KINDS = "DAC"
# a kind's operator and feed-forward half
CONV_OPERATOR = {"D": True, "A": False, "C": True}
EXPERTS = {"D": False, "A": True, "C": True}
INIT_STD = 0.02      # every matrix; the out-projections rescaled (init)


@dataclass(frozen=True)
class LFM2MoEConfig:
    vocab_size: int = 65536           # rows of the embedding (= head) held here
    seq_len: int = 4096
    pattern: str = "DD" + "ACCC" * 9 + "AC"     # one character a layer
    first_layer: int = 0              # the published index of pattern[0]
    n_layer_published: int = 40       # the out-projections' init scale
    d_model: int = 2048
    n_head: int = 32
    n_kv_head: int = 8
    head_dim: int = 64
    rope_theta: float = 1_000_000.0
    conv_kernel: int = 3              # conv_L_cache
    d_ff: int = 11776                 # the dense layers' SwiGLU hidden
    # the expert layers: the router is n_experts wide; ids held_first … +
    # held_count − 1 are computed here
    n_experts: int = 64
    top_k: int = 4
    held_first: int = 0
    held_count: int = 64
    d_expert: int = 1536
    routed_scaling: float = 1.0
    route_eps: float = 1e-6           # in the gates' normalising sum
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "auto"

    def __post_init__(self):
        odd = set(self.pattern) - set(KINDS)
        if odd or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: a layer is one of "
                             f"{sorted(KINDS)}")
        if not isinstance(self.remat, bool):
            raise ValueError(f"remat must be True or False; got {self.remat!r}")
        if self.n_head % self.n_kv_head:
            raise ValueError(f"n_head={self.n_head} must be divisible by "
                             f"n_kv_head={self.n_kv_head}")
        if not 0 <= self.held_first <= self.n_experts - self.held_count:
            raise ValueError(
                f"held experts {self.held_first}…+{self.held_count} are not "
                f"among {self.n_experts}")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError("top_k must be in [1, n_experts]")
        if self.vocab_size % 128:
            raise ValueError("vocab_size (the rows held here) must be a "
                             "multiple of 128")

    @property
    def n_layer(self) -> int:
        return len(self.pattern)

    @property
    def held(self) -> moe.Held:
        return moe.Held(self.held_first, self.held_count)


def pattern_from(layer_types: Sequence[str], num_dense_layers: int) -> str:
    """The published ``layer_types`` (``conv`` / ``full_attention``), the
    first ``num_dense_layers`` of them with the dense MLP, as a pattern."""
    out = []
    for i, op in enumerate(layer_types):
        if op not in ("conv", "full_attention"):
            raise ValueError(f"layer_types[{i}] = {op!r}: conv or "
                             "full_attention")
        dense = i < num_dense_layers
        if dense and op != "conv":
            raise ValueError(f"layer {i}: attention + dense MLP is a pair "
                             "no published config has and no kind here is")
        out.append("D" if dense else "C" if op == "conv" else "A")
    return "".join(out)


def lfm2_moe_tiny(**overrides) -> LFM2MoEConfig:
    """Test-size config: a leading dense layer and one period, every kind."""
    return replace(LFM2MoEConfig(
        vocab_size=256, seq_len=64, pattern="DACCC", first_layer=1,
        n_layer_published=5, d_model=64, n_head=4, n_kv_head=2, head_dim=16,
        d_ff=160, n_experts=16, top_k=4, held_first=4, held_count=8,
        d_expert=48), **overrides)


# --------------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------------- #

_CONV_WEIGHTS = ("w_in", "w_out")
_ATTN_WEIGHTS = ("wq", "wk", "wv", "wo")
_DENSE_WEIGHTS = ("w_gate", "w_up", "w_down")


def _matmul_weights(kind: str) -> Tuple[str, ...]:
    """What a layer of ``kind`` takes in the compute dtype (the router, the
    conv's taps and the norms' gains stay as they are stored)."""
    return ((_CONV_WEIGHTS if CONV_OPERATOR[kind] else _ATTN_WEIGHTS)
            + (moe.GATED_EXPERT if EXPERTS[kind] else _DENSE_WEIGHTS))


def _layer_init(rng, n: int, kind: str, cfg: LFM2MoEConfig):
    """``n`` stacked layers of ``kind``: the two pre-norms, the operator's
    tensors and the feed-forward half's."""
    D, pd = cfg.d_model, cfg.param_dtype
    H, KH, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    # rescale_prenorm_residual: the out-projections by 1/sqrt(2·layers)
    out_std = INIT_STD / math.sqrt(2 * cfg.n_layer_published)
    k_ff, *k = jax.random.split(rng, 8)
    k = iter(k)

    def normal(shape, s=INIT_STD):
        return (jax.random.normal(next(k), shape) * s).astype(pd)

    p = {"op_norm": jnp.ones((n, D), pd), "ffn_norm": jnp.ones((n, D), pd)}
    if CONV_OPERATOR[kind]:
        p.update(w_in=normal((n, D, 3 * D)),
                 conv_w=normal((n, cfg.conv_kernel, D),
                               1.0 / math.sqrt(cfg.conv_kernel)),
                 w_out=normal((n, D, D), out_std))
    else:
        p.update(wq=normal((n, D, H, hd)), wk=normal((n, D, KH, hd)),
                 wv=normal((n, D, KH, hd)), wo=normal((n, H, hd, D), out_std),
                 q_norm=jnp.ones((n, hd), pd), k_norm=jnp.ones((n, hd), pd))
    if EXPERTS[kind]:
        p.update(moe.gated_moe_init(
            k_ff, n, D, cfg.n_experts, cfg.held_count, cfg.d_expert,
            INIT_STD, out_std, pd))
    else:
        p.update(w_gate=normal((n, D, cfg.d_ff)), w_up=normal((n, D, cfg.d_ff)),
                 w_down=normal((n, cfg.d_ff, D), out_std))
    return p


def _stack_init(rng, pattern: str, cfg: LFM2MoEConfig):
    return blocks.init_pattern(rng, pattern, KINDS,
                               partial(_layer_init, cfg=cfg))


_HEAD_AXES = ("layers", "embed", "heads", "kv")
_LAYER_AXES = {
    "op_norm": ("layers", "embed"), "ffn_norm": ("layers", "embed"),
    "w_in": ("layers", "embed", "mlp"), "conv_w": ("layers", None, None),
    "w_out": ("layers", "mlp", "embed"),
    "wq": _HEAD_AXES, "wk": _HEAD_AXES, "wv": _HEAD_AXES,
    "wo": ("layers", "heads", "kv", "embed"),
    "q_norm": ("layers", None), "k_norm": ("layers", None),
    "w_gate": ("layers", "embed", "mlp"), "w_up": ("layers", "embed", "mlp"),
    "w_down": ("layers", "mlp", "embed"),
    **moe.gated_moe_logical_axes(),
}


def logical_axes(cfg: LFM2MoEConfig) -> Dict[str, Any]:
    layers = jax.eval_shape(
        lambda: _stack_init(jax.random.PRNGKey(0), cfg.pattern, cfg))
    return {"wte": ("vocab", "embed"),
            "blocks": [{kind: {name: _LAYER_AXES[name] for name in stack}
                        for kind, stack in group.items()} for group in layers],
            "final_norm": ("embed",)}


def mesh_rules(cfg: LFM2MoEConfig, mesh) -> Dict[str, str]:
    """What this config needs of this mesh: no rule beyond the defaults, and
    the refusal of the axes no code here runs over."""
    for axis, why in (
            ("ep", "the expert layer computes the experts the config says it "
                   "holds and no all-to-all exchanges tokens"),
            ("tp", "the short convolution's channels, the grouped heads and "
                   "the held experts' hidden width are not divided here"),
            ("pp", "a pattern of kinds under a stage schedule"),
            ("cp", "the short convolution reads the tokens before it along "
                   "the whole row")):
        if mesh.shape.get(axis, 1) > 1:
            raise NotImplementedError(
                f"{axis} > 1 is not implemented for the LFM2-MoE family "
                f"({why}); use a {axis}=1 mesh")
    return {}


def init(cfg: LFM2MoEConfig, rng: jax.Array) -> Dict[str, Any]:
    k = jax.random.split(rng, 2)
    wte = jax.random.normal(k[0], (cfg.vocab_size, cfg.d_model)) * INIT_STD
    return {"wte": wte.astype(cfg.param_dtype),
            "blocks": _stack_init(k[1], cfg.pattern, cfg),
            "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype)}


def param_count(cfg: LFM2MoEConfig) -> int:
    """The parameters a step moves: every leaf but the expert layers'
    selection biases, which are buffers (the tied embedding once)."""
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

@jax.named_scope(scopes.SHORT_CONV)
def conv_operator(u, p):
    """u [B, S, D] (normed) → the operator's output [B, S, D] float32."""
    bcx = checkpoint_name(jnp.einsum("bsd,de->bse", u, p["w_in"]),
                          scopes.RES_CONV_BCX)
    # elementwise work on one side, the product on the other: the gated
    # output and its gradient cross as they are (parts.made_once)
    y = parts.made_once(short_conv.gated_short_conv(bcx, p["conv_w"]))
    return jnp.einsum("bsd,de->bse", y, p["w_out"],
                      preferred_element_type=jnp.float32)


def attention_operator(u, p, cfg: LFM2MoEConfig):
    """u [B, S, D] (normed) → the operator's output [B, S, D] float32."""
    layout = parts.head_layout(cfg.head_dim)
    heads = layout.replace("d", "k")                    # the einsums' names
    s_minor, width = heads[-1] == "s", heads.index("k")
    positions = jnp.arange(u.shape[1])

    def normed_rotated(w, g):
        x = parts.head_rmsnorm(jnp.einsum(f"bsd,dhk->{heads}", u, w), g, cfg.rms_eps,
                       width)
        return parts.rope(x, positions, cfg.rope_theta, s_minor)

    with jax.named_scope(scopes.QKV):
        # named after the norm and the rotation: a kept q or k has both
        q = checkpoint_name(normed_rotated(p["wq"], p["q_norm"]), scopes.RES_Q)
        k = checkpoint_name(normed_rotated(p["wk"], p["k_norm"]), scopes.RES_K)
        v = checkpoint_name(jnp.einsum(f"bsd,dhk->{heads}", u, p["wv"]),
                            scopes.RES_V)
    with jax.named_scope(scopes.ATTN):
        o = parts.causal_attention(q, k, v, cfg.attention_impl, layout=layout)
    with jax.named_scope(scopes.PROJ):
        return jnp.einsum(f"{heads},hkd->bsd", o, p["wo"],
                          preferred_element_type=jnp.float32)


def _swiglu(x, p, cfg: LFM2MoEConfig):
    """x + down(silu(gate(h)) · up(h)), h = norm(x), on [B, rows, D]."""
    with jax.named_scope(scopes.LN2):
        h = parts.rmsnorm(x, p["ffn_norm"], cfg.rms_eps)
    y = parts.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    with jax.named_scope(scopes.MLP):
        return parts.residual_add(x, y)


def _dense(x, p, cfg: LFM2MoEConfig):
    """The dense feed-forward half, norm and all, in chunks of the sequence
    where parts.mlp_rows says so — as the llama block's, and why
    (models/llama.py)."""
    return parts.in_row_chunks(
        partial(_swiglu, p=p, cfg=cfg), x,
        parts.mlp_rows(*x.shape, cfg.d_ff, x.dtype.itemsize))


def _routing(cfg: LFM2MoEConfig) -> Dict[str, Any]:
    return dict(top_k=cfg.top_k, held=cfg.held, scaling=cfg.routed_scaling,
                eps=cfg.route_eps)


def _experts(x, p, cfg: LFM2MoEConfig, aux: Optional[str]):
    """The expert feed-forward half → (x, what ``aux`` asks of it)."""
    with jax.named_scope(scopes.LN2):
        h = parts.rmsnorm(x, p["ffn_norm"], cfg.rms_eps)
    ht, out = h.reshape(-1, h.shape[-1]), None
    if aux == "balance":
        bias = moe.balance_bias(ht, p["router_w"], p["router_bias"], cfg.top_k)
        p = {**p, "router_bias": bias}
        out = {"router_bias": bias, **moe.held_load(ht, p, **_routing(cfg))}
    elif aux == "chosen":
        out = moe.chosen_experts(ht, p, cfg.top_k)
    with jax.named_scope(scopes.MOE):
        f, load = moe.gated_moe(h, p, **_routing(cfg))
    return parts.residual_add(x, f), load if aux == "load" else out


@jax.named_scope(scopes.BLOCK)
def _layer(x, p, cfg: LFM2MoEConfig, kind: str, aux: Optional[str] = None):
    """One layer of ``kind``, x [B, S, D]: the operator's residual, then the
    feed-forward half's. With ``aux`` the result is (x, aux's value), None
    for a dense layer: ``"load"`` — what the batch sends the held experts,
    as the dispatch that runs the passes has it (moe.routed_experts; the
    training forward's) —; in a forward of its own, no backward,
    ``"balance"`` — an expert layer first balances its selection bias on
    this input; the bias and what the input then sends the held experts
    (moe.held_load) —, ``"chosen"`` — the set each token chose, [T,
    n_experts] bool."""
    p = {**p, **parts.cast_in_the_loop(p, x, cfg.dtype, _matmul_weights(kind))}
    with jax.named_scope(scopes.LN1):
        u = parts.rmsnorm(x, p["op_norm"], cfg.rms_eps)
    if CONV_OPERATOR[kind]:
        y = conv_operator(u, p)
    else:
        y = attention_operator(u, p, cfg)
    x = checkpoint_name(parts.residual_add(x, y), scopes.RES_MID)
    if EXPERTS[kind]:
        x, out = _experts(x, p, cfg, aux)
    else:
        x, out = _dense(x, p, cfg), None
    return (x, out) if aux else x


def kind_shards(cfg: LFM2MoEConfig, global_batch: int, seq: int, mesh
                ) -> Tuple[parts.BlockShard, Dict[str, blocks.KindShard]]:
    """This config's layers on one chip of ``mesh``, for the remat rule: the
    model's shard (stream, head, rows at a time) and, a kind, how often it is
    applied, what a layer of it may keep and what its backward holds at once
    — the operator's residual set, which waits while the feed-forward half's
    backward runs, beside that half's own — and its weight gradients."""
    a = jnp.dtype(cfg.dtype).itemsize
    D, F, Fe = cfg.d_model, cfg.d_ff, cfg.d_expert
    width = cfg.n_head * cfg.head_dim
    base = parts.shard_block(parts.BlockShard(
        batch=global_batch, seq=seq, d_model=D, heads=cfg.n_head,
        head_dim=cfg.head_dim, d_ff=F, vocab=cfg.vocab_size, dtype_bytes=a,
        flash=parts.is_flash(cfg.attention_impl, mesh), dense_mlp=False,
        kv_heads=cfg.n_kv_head,
        mlp_hidden=(scopes.RES_MLP_GATE, scopes.RES_MLP_UP),
        head_rows=parts.head_rows(global_batch, seq, cfg.vocab_size, 1),
        mlp_rows=parts.mlp_rows(global_batch, seq, D, F, a),
        cast_in_loop=True), mesh)
    tokens = base.batch * base.seq
    C = blocks.RematCandidate

    # the operators. Conv: the in-projection's output; its backward holds
    # that, its gradient, the gated output and its gradient. Attention: q, k,
    # v and the flash kernel's outputs (parts.remat_candidates). Both: the
    # stream after the operator's residual
    mid = C((scopes.RES_MID,), tokens * D * a, 2 * tokens * D * D)
    conv_kept = (C((scopes.RES_CONV_BCX,), tokens * 3 * D * a,
                   2 * tokens * D * 3 * D), mid)
    conv_set = a * (tokens * (4 * D + 2 * 3 * D + 2 * D) + 2 * 4 * D * D)
    attn_kept = tuple(parts.remat_candidates(base))
    kv_width = cfg.n_kv_head * cfg.head_dim
    attn_set = a * (tokens * (4 * D + 4 * width)
                    + 2 * 2 * D * (width + kv_width))

    # the feed-forward halves. Dense: parts.swiglu_price. Experts: what the
    # routing decided (parts.routing_candidates), and in their backward the
    # half's stream and the routed passes' set (parts.gated_experts_working_set)
    # together
    dense_kept, dense_set = parts.swiglu_price(
        base.batch, base.seq, base.mlp_rows, D, F, a, base.mlp_hidden)
    experts_kept = parts.routing_candidates(
        tokens, D, cfg.n_experts, cfg.top_k, cfg.held_count)
    experts_set = sum(parts.gated_experts_working_set(
        tokens, D, cfg.n_experts, cfg.top_k, cfg.held_count, Fe, a))

    kinds = {}
    for kind in dict.fromkeys(cfg.pattern):
        op_kept, op_set = ((conv_kept, conv_set) if CONV_OPERATOR[kind]
                           else (attn_kept, attn_set))
        ff_kept, ff_set = ((experts_kept, experts_set) if EXPERTS[kind]
                           else (dense_kept, dense_set))
        kinds[kind] = blocks.KindShard(
            cfg.pattern.count(kind), op_kept + ff_kept, op_set + ff_set)
    return base, blocks.with_grad_bytes(
        blocks.one_candidate_a_name(kinds), partial(_layer_init, cfg=cfg), mesh)


def _block_fns(cfg: LFM2MoEConfig, batch: int, seq: int,
               aux: Optional[str] = None):
    from ray_tpu.parallel import mesh as mesh_lib

    base, kinds = kind_shards(cfg, batch, seq, mesh_lib.current_mesh())
    blocks.record_layer_pattern(cfg.pattern)
    return blocks.checkpoint_kinds(
        {kind: partial(_layer, cfg=cfg, kind=kind, aux=aux) for kind in kinds},
        cfg.remat, base, kinds, blocks.pattern_groups(cfg.pattern))


def _trunk(params, tokens, cfg: LFM2MoEConfig, aux: Optional[str] = None):
    """tokens [B, S] int32 → the head's input [B, S, D] (and, with ``aux``,
    blocks.run_pattern's: each layer's, _layer says what)."""
    B, S = tokens.shape
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    if aux in (None, "load"):       # the training forward, checkpointed
        fns = _block_fns(cfg, B, S, aux)
    else:               # a forward of its own: no backward, no checkpoint
        fns = {kind: partial(_layer, cfg=cfg, kind=kind, aux=aux)
               for kind in KINDS}
    out = blocks.run_pattern(fns, cfg.pattern, x, params["blocks"],
                             with_aux=bool(aux))
    x, auxes = out if aux else (out, None)
    with jax.named_scope(scopes.LN_F):
        x = parts.rmsnorm(x, params["final_norm"], cfg.rms_eps)
    return (x, auxes) if aux else x


def forward(params, tokens, cfg: LFM2MoEConfig) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, vocab_size] (the tied head)."""
    x = _trunk(params, tokens, cfg)
    return jnp.einsum("bsd,vd->bsv", x, params["wte"].astype(cfg.dtype))


def loss_fn(params, tokens, targets, cfg: LFM2MoEConfig,
            counters: bool = False):
    """Mean cross-entropy over targets >= 0 ([B, S] int32, the next token).
    With ``counters`` (what step_counters offers a step factory: the aux of
    its ``value_and_grad``) the result is (the loss, what the batch sent each
    expert layer's held experts: int32 [expert layers, fields], in the
    layers' order)."""
    x = _trunk(params, tokens, cfg, "load" if counters else None)
    if counters:
        x, auxes = x
    loss = parts.lm_head_loss(x, targets, params["wte"].T, cfg.dtype)
    if not counters:
        return loss
    return loss, blocks.packed_aux(auxes, scopes.STEP_EXPERT_LOAD_ARGS)


def step_counters(cfg: LFM2MoEConfig) -> Optional[blocks.StepCounters]:
    """What ``loss_fn(..., counters=True)`` hands out of a step, or None for
    a pattern without an expert layer. A layer's id is ``model/expert_load``'s
    ``layer``: the published index."""
    return parts.expert_step_counters(_expert_layer_ids(cfg), cfg.n_experts,
                                      cfg.top_k, cfg.held)


def flops_per_token(cfg: LFM2MoEConfig) -> float:
    """Forward + backward operations one trained token REQUIRES here: 6 per
    matmul parameter the token meets (the routed experts by the pairs a token
    is expected to land on held ones, top_k · held / n_experts a layer; the
    embedding is a gather, the tied head a matmul) and by shape three times
    the forward's attention (two products over the causal half). The short
    convolution's elementwise work (a few operations a channel) is not
    counted."""
    D, S = cfg.d_model, cfg.seq_len
    width = cfg.n_head * cfg.head_dim
    operator = {True: 4 * D * D,
                False: 2 * D * (cfg.n_head + cfg.n_kv_head) * cfg.head_dim}
    ff = {True: D * cfg.n_experts + cfg.top_k * cfg.held_count / cfg.n_experts
          * 3 * D * cfg.d_expert,
          False: 3 * D * cfg.d_ff}
    matmul = sum(operator[CONV_OPERATOR[k]] + ff[EXPERTS[k]]
                 for k in cfg.pattern) + D * cfg.vocab_size
    shaped = sum(2 * width * (S + 1) / 2 for k in cfg.pattern
                 if not CONV_OPERATOR[k])
    return 6.0 * (matmul + shaped)


# --------------------------------------------------------------------------- #
# The selection bias, balanced at set-up; what each token chose
# --------------------------------------------------------------------------- #

def _expert_layer_ids(cfg: LFM2MoEConfig) -> Tuple[int, ...]:
    return parts.expert_layer_ids(cfg.pattern, cfg.first_layer, EXPERTS)


def chosen_experts(params, tokens, cfg: LFM2MoEConfig) -> List[jax.Array]:
    """The set each token of ``tokens`` [B, S] chose in each expert layer, in
    the layers' order: [B·S, n_experts] bool a layer. What a reference is
    told, so that a near-tie rounding flipped is not read as a wrong model."""
    return blocks.aux_by_layer(blocks.pattern_groups(cfg.pattern),
                               _trunk(params, tokens, cfg, "chosen")[1])


def balance_router_bias(params, tokens, cfg: LFM2MoEConfig):
    """(``params`` with every expert layer's selection bias balanced on this
    batch, what the batch then sends the experts held here). The bias's
    between-step update is not part of the step, so a run starts from a bias
    that something balanced: moe.balance_bias, layer by layer in one forward
    of its own (a layer's input is what the balanced layers before it give),
    the weights held. The loads are the ``model/expert_load`` events
    (tracing/names.EXPERT_LOAD_ARGS; ``layer`` is the published index),
    recorded here. For set-up, on the first batch."""
    auxes = jax.device_get(jax.jit(
        lambda p, tok: _trunk(p, tok, cfg, "balance")[1])(params, tokens))
    loads = blocks.aux_by_layer(blocks.pattern_groups(cfg.pattern), auxes)
    biases = iter([load.pop("router_bias") for load in loads])
    return ({**params, "blocks": blocks.with_leaf(
        cfg.pattern, params["blocks"], "router_bias", biases)},
        moe.record_expert_loads(_expert_layer_ids(cfg), loads))
