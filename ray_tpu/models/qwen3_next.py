"""Qwen3-Next-family decoder in pure JAX: a pattern of two layer kinds, each a
MIXER and the same mixture of experts, each a pre-normed residual —
``h = x + Mixer(norm(x))``, ``x' = h + Experts(norm(h))``. Published layer i
is full attention where ``(i + 1) % full_attention_interval == 0``, else
Gated DeltaNet (``Qwen3NextConfig.pattern``, one character a layer):

- ``L`` — the **Gated DeltaNet** mixer (arXiv:2412.06464): ``[q, k, v, z] =
  u·W_qkvz``, ``[b, a] = u·W_ba``; a causal depthwise conv of
  ``conv_kernel`` taps over q‖k‖v, no bias, then SiLU; q and k L2-normalised
  over the head (q times d_k^-½); ``β = sigmoid(b)``, ``g = −exp(A_log) ·
  softplus(a + dt_bias)`` in float32; the gated delta rule over the row
  (ops/gated_delta.py: ``linear_key_heads`` key heads each serving
  ``linear_value_heads / linear_key_heads`` value heads); a per-head RMSNorm
  of the result (ONE gain vector of the head's width, drawn at 1 — the only
  norm here that is not zero-centred) times ``silu(z)`` in float32;
  ``· W_out``.
- ``F`` — **gated attention**: ``[q, gate] = u·W_q`` (a head's q then its
  gate), ``k, v = u·W_k, u·W_v``; a zero-centred RMSNorm over the head on q
  and on k; RoPE (rotate-half) on the first ``partial_rotary_factor`` of the
  head's channels; causal softmax at head_dim^-½, ``n_kv_head`` heads
  serving ``n_head`` (parts.causal_attention: the hd-minor flash kernels at
  the published 256); ``o ⊙ sigmoid(gate)``; ``· W_o``.

Every other norm is zero-centred: ``x / rms(x) · (1 + w)``, w drawn at 0
(parts.rmsnorm's ``unit_offset``). The expert half (ops/moe.gated_moe): a
softmax over all ``n_experts`` in float32, the ``top_k`` largest chosen, gates
the chosen probabilities over their sum; SiLU-gated experts at ``d_expert``;
beside them ONE shared expert of the same form at ``d_shared``, scaled by a
per-token ``sigmoid(u · w_g)``. The step's objective holds ``aux_loss_coef``
× the sum over the layers of moe.balance_loss. The head is untied.

The tensors' column orders are the program's own, fixed: ``W_qkvz``'s output
is q (key heads × d_k), k, v (value heads × d_v), z — the published
checkpoint groups them by key head (``fix_query_key_value_ordering``: a key
head's q, k, its value heads' v, z), a fixed permutation of columns that no
product sees; likewise ``W_ba`` is b then a (tests/test_qwen3_next.py maps one
tree onto the other).

It runs on the shared machinery, as its siblings do: ``blocks.run_pattern`` /
``blocks.checkpoint_kinds`` (ONE remat rule over both kinds' applications),
parts' norms, RoPE's ``span``, residual add, weight cast inside the loop,
causal attention and the chunked head + loss; ops/moe.py's dispatch;
ops/mamba2.causal_conv; tracing/names.py's scopes and residuals.

The config states the chip's SHARE of a deployment beside the published
sizes: which routed experts and how many vocabulary rows are held here, and
which published layer the pattern starts at. Routing is over all
``n_experts`` at the published top-k; what absent experts would have added is
left out (no code stands in for absent chips or their exchange): the shares'
routed parts and the gated shared expert, counted once, add up to the whole
layer's (tests/test_qwen3_next.py).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import blocks, parts
from ray_tpu.ops import delta_pointwise, gated_delta, mamba2, moe
from ray_tpu.tracing import names as scopes

KINDS = "LF"        # Gated DeltaNet, full (gated) attention


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936          # rows of the embedding / head held here
    seq_len: int = 8192
    n_layer: int = 48                 # layers run here
    first_layer: int = 0              # the published index of the first
    full_attention_interval: int = 4
    d_model: int = 2048
    # gated attention
    n_head: int = 16
    n_kv_head: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10_000_000.0
    # Gated DeltaNet
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_kernel: int = 4
    delta_chunk: int = gated_delta.CHUNK
    # the expert half: the router is n_experts wide; ids held_first … +
    # held_count − 1 are computed here
    n_experts: int = 512
    top_k: int = 10
    held_first: int = 0
    held_count: int = 512
    d_expert: int = 512
    d_shared: int = 512               # the one shared expert's hidden width
    aux_loss_coef: float = 0.001      # the balance loss's (0: no such loss)
    init_std: float = 0.02            # initializer_range, every matrix
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "auto"

    def __post_init__(self):
        if not isinstance(self.remat, bool):
            raise ValueError(f"remat must be True or False; got {self.remat!r}")
        if self.n_layer < 1 or self.full_attention_interval < 1:
            raise ValueError("n_layer and full_attention_interval must be at "
                             "least 1")
        if self.n_head % self.n_kv_head:
            raise ValueError(f"n_head={self.n_head} must be divisible by "
                             f"n_kv_head={self.n_kv_head}")
        if self.linear_value_heads % self.linear_key_heads:
            raise ValueError(
                f"linear_value_heads={self.linear_value_heads} must be a "
                f"multiple of linear_key_heads={self.linear_key_heads}")
        if self.rotary_dim % 2:
            raise ValueError("head_dim · partial_rotary_factor must be even")
        if not 0 <= self.held_first <= self.n_experts - self.held_count:
            raise ValueError(
                f"held experts {self.held_first}…+{self.held_count} are not "
                f"among {self.n_experts}")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError("top_k must be in [1, n_experts]")

    @property
    def pattern(self) -> str:
        return "".join(
            "F" if (self.first_layer + i + 1) % self.full_attention_interval
            == 0 else "L" for i in range(self.n_layer))

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_width(self) -> int:
        return self.linear_key_heads * self.linear_key_dim

    @property
    def value_width(self) -> int:
        return self.linear_value_heads * self.linear_value_dim

    @property
    def held(self) -> moe.Held:
        return moe.Held(self.held_first, self.held_count)


RULE = moe.Rule(scoring="softmax", normalise=True)


def qwen3_next_tiny(**overrides) -> Qwen3NextConfig:
    """Test-size config: one period, both kinds, two value heads a key head,
    a share of the experts."""
    return replace(Qwen3NextConfig(
        vocab_size=250, seq_len=48, n_layer=4, d_model=64, n_head=4,
        n_kv_head=2, head_dim=16, partial_rotary_factor=0.5,
        linear_key_heads=2, linear_value_heads=4, linear_key_dim=16,
        linear_value_dim=16, delta_chunk=16, n_experts=16, top_k=4,
        held_first=4, held_count=8, d_expert=48, d_shared=32), **overrides)


# --------------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------------- #

_DELTA_WEIGHTS = ("w_qkvz", "w_ba", "w_out")
_ATTN_WEIGHTS = ("wq", "wk", "wv", "wo")
_EXPERT_WEIGHTS = moe.GATED_EXPERT + moe.GATED_SHARED_EXPERT + ("shared_gate",)
# what no weight decay touches: every gain, the conv's taps, the gates' two
# vectors a value head
_NO_DECAY = ("op_norm", "ffn_norm", "q_norm", "k_norm", "delta_norm",
             "final_norm", "conv_w", "A_log", "dt_bias")


def _matmul_weights(kind: str) -> Tuple[str, ...]:
    """What a layer of ``kind`` takes in the compute dtype (the router, the
    conv's taps, the gates' vectors and the gains stay as they are stored)."""
    return (_DELTA_WEIGHTS if kind == "L" else _ATTN_WEIGHTS) + _EXPERT_WEIGHTS


def _layer_init(rng, n: int, kind: str, cfg: Qwen3NextConfig):
    """``n`` stacked layers of ``kind``, as the published code draws them:
    every matrix (the conv's taps among them) normal ``init_std``, the
    zero-centred gains 0, the gated norm's gain 1, ``A_log = log U(0, 16)``
    and ``dt_bias`` 1 a value head — some heads forget within a few tokens,
    others hold a chunk and more."""
    D, pd, std = cfg.d_model, cfg.param_dtype, cfg.init_std
    H, KH, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    Hv = cfg.linear_value_heads
    k_ff, *k = jax.random.split(rng, 8)
    k = iter(k)

    def normal(shape):
        return (jax.random.normal(next(k), shape) * std).astype(pd)

    p = {"op_norm": jnp.zeros((n, D), pd), "ffn_norm": jnp.zeros((n, D), pd)}
    if kind == "L":
        conv_dim = 2 * cfg.key_width + cfg.value_width
        p.update(
            w_qkvz=normal((n, D, conv_dim + cfg.value_width)),
            w_ba=normal((n, D, 2 * Hv)),
            conv_w=normal((n, cfg.conv_kernel, conv_dim)),
            A_log=jnp.log(jax.random.uniform(
                next(k), (n, Hv), minval=1e-6, maxval=16.0)).astype(pd),
            dt_bias=jnp.ones((n, Hv), pd),
            delta_norm=jnp.ones((n, cfg.linear_value_dim), pd),
            w_out=normal((n, cfg.value_width, D)))
    else:
        p.update(wq=normal((n, D, H, 2 * hd)), wk=normal((n, D, KH, hd)),
                 wv=normal((n, D, KH, hd)), wo=normal((n, H, hd, D)),
                 q_norm=jnp.zeros((n, hd), pd), k_norm=jnp.zeros((n, hd), pd))
    p.update(moe.gated_moe_init(
        k_ff, n, D, cfg.n_experts, cfg.held_count, cfg.d_expert, std, std, pd,
        selection_bias=False, d_shared=cfg.d_shared, shared_gate=True))
    return p


def _stack_init(rng, pattern: str, cfg: Qwen3NextConfig):
    return blocks.init_pattern(rng, pattern, KINDS,
                               partial(_layer_init, cfg=cfg))


_HEAD_AXES = ("layers", "embed", "heads", "kv")
_LAYER_AXES = {
    "op_norm": ("layers", "embed"), "ffn_norm": ("layers", "embed"),
    "w_qkvz": ("layers", "embed", "mlp"), "w_ba": ("layers", "embed", None),
    "conv_w": ("layers", None, None), "A_log": ("layers", None),
    "dt_bias": ("layers", None), "delta_norm": ("layers", None),
    "w_out": ("layers", "mlp", "embed"),
    "wq": _HEAD_AXES, "wk": _HEAD_AXES, "wv": _HEAD_AXES,
    "wo": ("layers", "heads", "kv", "embed"),
    "q_norm": ("layers", None), "k_norm": ("layers", None),
    **moe.gated_moe_logical_axes(),
}


def logical_axes(cfg: Qwen3NextConfig) -> Dict[str, Any]:
    layers = jax.eval_shape(
        lambda: _stack_init(jax.random.PRNGKey(0), cfg.pattern, cfg))
    return {"wte": ("vocab", "embed"),
            "blocks": [{kind: {name: _LAYER_AXES[name] for name in stack}
                        for kind, stack in group.items()} for group in layers],
            "final_norm": ("embed",), "lm_head": ("embed", "vocab")}


def mesh_rules(cfg: Qwen3NextConfig, mesh) -> Dict[str, str]:
    """What this config needs of this mesh: no rule beyond the defaults, and
    the refusal of the axes no code here runs over."""
    for axis, why in (
            ("ep", "the expert half computes the experts the config says it "
                   "holds and no all-to-all exchanges tokens"),
            ("tp", "the delta rule's key and value heads, the grouped "
                   "attention heads and the held experts' hidden width are "
                   "not divided here"),
            ("pp", "a pattern of kinds under a stage schedule"),
            ("cp", "the delta rule's state and the conv read the tokens "
                   "before them along the whole row")):
        if mesh.shape.get(axis, 1) > 1:
            raise NotImplementedError(
                f"{axis} > 1 is not implemented for the Qwen3-Next family "
                f"({why}); use a {axis}=1 mesh")
    return {}


def init(cfg: Qwen3NextConfig, rng: jax.Array) -> Dict[str, Any]:
    k = jax.random.split(rng, 3)
    pd = cfg.param_dtype

    def normal(key, shape):
        return (jax.random.normal(key, shape) * cfg.init_std).astype(pd)

    return {"wte": normal(k[0], (cfg.vocab_size, cfg.d_model)),
            "blocks": _stack_init(k[1], cfg.pattern, cfg),
            "final_norm": jnp.zeros((cfg.d_model,), pd),
            "lm_head": normal(k[2], (cfg.d_model, cfg.vocab_size))}


def param_count(cfg: Qwen3NextConfig) -> int:
    """The parameters a step moves: every leaf (no buffer here)."""
    return parts.param_count(lambda: init(cfg, jax.random.PRNGKey(0)))


def decays(params):
    """Which leaves an optimizer's weight decay may touch (optax's ``mask``):
    the matrices — not the gains, the conv's taps, ``A_log`` or ``dt_bias``."""
    return parts.all_but(params, *_NO_DECAY)


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #

def _norm(x, g, cfg: Qwen3NextConfig):
    """The zero-centred RMSNorm over the last axis."""
    return parts.rmsnorm(x, g, cfg.rms_eps, unit_offset=True)


def _head_sums(x, heads: int):
    """x [B, S, heads · d] float32 → (each head's Σ x² over its d channels [B,
    S, heads], the function that spreads a head's number back over its
    channels): two products with the heads' 0/1 indicator at the highest
    precision (exact placement, float32 sums), so that the tensor stays as
    the projections lay it out — tokens on the sublanes, a head's channels
    on the lanes. Taken apart into [.., heads, d] a per-head reduction makes
    XLA relay the whole tensor out, heads on the sublanes, and back: a copy
    each way of every [tokens, width] float32 it touches (PERF.md §6,
    PR 61)."""
    width = x.shape[-1]
    indicator = jnp.repeat(jnp.eye(heads, dtype=jnp.float32), width // heads,
                           axis=0)                              # [width, H]
    sums = jnp.einsum("bsc,ch->bsh", x * x, indicator,
                      precision=lax.Precision.HIGHEST)

    def spread(per_head):
        return jnp.einsum("bsh,ch->bsc", per_head, indicator,
                          precision=lax.Precision.HIGHEST)

    return sums, spread


def _l2norm(x, heads: int, eps: float = 1e-6):
    """x / ‖x‖ over each head's channels of x [B, S, heads · d], float32
    (the published ``l2norm``)."""
    xf = x.astype(jnp.float32)
    sums, spread = _head_sums(xf, heads)
    return xf * spread(lax.rsqrt(sums + eps))


def _conv_silu(x, taps, dtype):
    """silu(causal depthwise conv of x [B, S, C] by taps [K, C]), no bias, in
    the compute dtype (the conv and the SiLU in float32 inside)."""
    return jax.nn.silu(mamba2.causal_conv(
        x, taps, jnp.zeros((x.shape[-1],), taps.dtype))).astype(dtype)


def _gated_rmsnorm(o, z, gain, eps: float):
    """o / rms(o) over each head's channels of o [B, S, heads · d] · gain [d]
    · silu(z), the product in float32, in o's dtype; written once where it
    stands: elementwise work on one side, the out-projection on the other
    (parts.made_once)."""
    heads = o.shape[-1] // gain.shape[0]
    of = o.astype(jnp.float32)
    sums, spread = _head_sums(of, heads)
    return parts.made_once(
        (of * spread(lax.rsqrt(sums / gain.shape[0] + eps))
         * jnp.tile(gain.astype(jnp.float32), heads)
         * jax.nn.silu(z.astype(jnp.float32))).astype(o.dtype))


@jax.named_scope(scopes.DELTA_MIXER)
def delta_mixer(u, p, cfg: Qwen3NextConfig):
    """u [B, S, D] (normed) → the mixer's output [B, S, D] float32. The
    fused projection is ONE tensor, as published; its four parts (q, k, v,
    z) are four products on slices of the WEIGHT, so that no [tokens, 12,288]
    activation is made to be cut apart again, and the conv — depthwise —
    takes each part with its own taps. The elementwise work on either side
    of the scan is ops/delta_pointwise.py's two kernel pairs where the scan
    is its kernels (attention.resolve_attention's rule on
    ``attention_impl``), and the plain forms above anywhere else."""
    B, S, _ = u.shape
    Hk, Hv = cfg.linear_key_heads, cfg.linear_value_heads
    dk, dv, kw, vw = (cfg.linear_key_dim, cfg.linear_value_dim, cfg.key_width,
                      cfg.value_width)
    edges = (0, kw, 2 * kw, 2 * kw + vw, 2 * kw + 2 * vw)
    w, taps = p["w_qkvz"], p["conv_w"]
    q, k, v, z = (checkpoint_name(
        jnp.einsum("bsd,de->bse", u, w[:, lo:hi]), name)
        for lo, hi, name in zip(edges, edges[1:], scopes.RES_DELTA_PARTS))
    ba = checkpoint_name(jnp.einsum("bsd,de->bse", u, p["w_ba"],
                                    preferred_element_type=jnp.float32),
                         scopes.RES_DELTA_BA)
    impl, interpret, _ = parts.attention_on_mesh(cfg.attention_impl)
    if impl == "pallas":
        q, k, v = (delta_pointwise.conv_silu_norm(
            x, taps[:, lo:hi], heads, scale, interpret=interpret)
            for x, lo, hi, heads, scale in zip(
                (q, k, v), edges, edges[1:], (Hk, Hk, 0), (dk ** -0.5, 1, 1)))
    else:
        q, k, v = (_conv_silu(x, taps[:, lo:hi], u.dtype)
                   for x, lo, hi in zip((q, k, v), edges, edges[1:]))
        q = (_l2norm(q, Hk) * dk ** -0.5).astype(u.dtype)
        k = _l2norm(k, Hk).astype(u.dtype)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., Hv:] + p["dt_bias"].astype(jnp.float32))
    o = gated_delta.gated_delta_scan(
        q.reshape(B, S, Hk, dk), k.reshape(B, S, Hk, dk),
        v.reshape(B, S, Hv, dv), g, beta, cfg.delta_chunk, cfg.attention_impl)
    o = checkpoint_name(o.reshape(B, S, vw), scopes.RES_DELTA_O)
    if impl == "pallas":
        y = delta_pointwise.gated_rmsnorm(o, z, p["delta_norm"], cfg.rms_eps,
                                          interpret=interpret)
    else:
        y = _gated_rmsnorm(o, z, p["delta_norm"], cfg.rms_eps)
    return jnp.einsum("bse,ed->bsd", y, p["w_out"],
                      preferred_element_type=jnp.float32)


def attention_mixer(u, p, cfg: Qwen3NextConfig):
    """u [B, S, D] (normed) → the mixer's output [B, S, D] float32."""
    hd = cfg.head_dim
    positions = jnp.arange(u.shape[1])
    span = (0, cfg.rotary_dim)

    def normed_rotated(x, g):
        return parts.rope(_norm(x, g, cfg), positions, cfg.rope_theta,
                          span=span)

    with jax.named_scope(scopes.QKV):
        qg = jnp.einsum("bsd,dhk->bhsk", u, p["wq"])
        # named after the norm and the rotation: a kept q or k has both
        q = checkpoint_name(normed_rotated(qg[..., :hd], p["q_norm"]),
                            scopes.RES_Q)
        gate = checkpoint_name(qg[..., hd:], scopes.RES_ATTN_GATE)
        k = checkpoint_name(normed_rotated(
            jnp.einsum("bsd,dhk->bhsk", u, p["wk"]), p["k_norm"]), scopes.RES_K)
        v = checkpoint_name(jnp.einsum("bsd,dhk->bhsk", u, p["wv"]),
                            scopes.RES_V)
    with jax.named_scope(scopes.ATTN):
        o = parts.causal_attention(q, k, v, cfg.attention_impl)
        with jax.named_scope(scopes.GATED_ATTN_GATE):
            o = parts.made_once(
                (o.astype(jnp.float32)
                 * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(u.dtype))
    with jax.named_scope(scopes.PROJ):
        return jnp.einsum("bhsk,hkd->bsd", o, p["wo"],
                          preferred_element_type=jnp.float32)


def _routing(cfg: Qwen3NextConfig) -> Dict[str, Any]:
    return dict(top_k=cfg.top_k, held=cfg.held, scaling=1.0, rule=RULE)


def _experts(x, p, cfg: Qwen3NextConfig, aux: Optional[str], rate=None):
    """The expert half → (x, what ``aux`` asks of it)."""
    B, S, D = x.shape
    with jax.named_scope(scopes.LN2):
        h = _norm(x, p["ffn_norm"], cfg)
    ht, out = h.reshape(-1, D), None
    if aux == "balance":
        router_w = moe.balance_router(ht, p["router_w"], cfg.top_k, S, rate,
                                      RULE)
        p = {**p, "router_w": router_w}
        out = {"router_w": router_w, **moe.held_load(ht, p, **_routing(cfg))}
    elif aux == "chosen":
        out = moe.chosen_experts(ht, p, cfg.top_k, RULE)
    with jax.named_scope(scopes.MOE):
        f, load = moe.gated_moe(
            h, p, **_routing(cfg),
            balance=aux == "load" and cfg.aux_loss_coef > 0,
            shared_rows=parts.mlp_rows(B, S, D, cfg.d_shared,
                                       x.dtype.itemsize))
    return parts.residual_add(x, f), load if aux == "load" else out


@jax.named_scope(scopes.BLOCK)
def _layer(x, p, cfg: Qwen3NextConfig, kind: str, aux: Optional[str] = None,
           rate=None):
    """One layer of ``kind``, x [B, S, D]: the mixer's residual, then the
    expert half's. With ``aux`` the result is (x, aux's value): ``"load"`` —
    the training forward's: what the batch sends the held experts, as the
    dispatch that runs the passes has it, and the layer's balance loss
    (moe.routed_experts) —; in a forward of its own, no backward,
    ``"balance"`` — the layer's router first takes one round of balancing on
    this input, at ``rate`` (moe.balance_router); the router and what the
    input then sends the held experts (moe.held_load) —, ``"chosen"`` — the
    set each token chose, [T, n_experts] bool."""
    p = {**p, **parts.cast_in_the_loop(p, x, cfg.dtype, _matmul_weights(kind))}
    with jax.named_scope(scopes.LN1):
        u = _norm(x, p["op_norm"], cfg)
    y = delta_mixer(u, p, cfg) if kind == "L" else attention_mixer(u, p, cfg)
    x = checkpoint_name(parts.residual_add(x, y), scopes.RES_MID)
    x, out = _experts(x, p, cfg, aux, rate)
    return (x, out) if aux else x


def delta_scan_macs(cfg: Qwen3NextConfig) -> float:
    """Multiply-adds one token's forward REQUIRES of one Gated DeltaNet
    layer's scan, by the chunk form at ``delta_chunk`` C (ops/gated_delta.py),
    a masked product at the half its mask leaves: K Kᵀ and Q Kᵀ (a key head's,
    once for its value heads), the solve applied to [βγK | βV] by
    substitution, W·S, Q·S, the masked P·D and the state's Kᵀ·D."""
    C, dk, dv = cfg.delta_chunk, cfg.linear_key_dim, cfg.linear_value_dim
    a_key_head = 2 * C * dk / 2
    a_value_head = C * (dk + dv) / 2 + C * dv / 2 + 3 * dk * dv
    return (cfg.linear_key_heads * a_key_head
            + cfg.linear_value_heads * a_value_head)


def kind_shards(cfg: Qwen3NextConfig, global_batch: int, seq: int, mesh
                ) -> Tuple[parts.BlockShard, Dict[str, blocks.KindShard]]:
    """This config's layers on one chip of ``mesh``, for the remat rule: the
    model's shard (stream, head, rows at a time) and, a kind, how often it is
    applied, what a layer of it may keep, its weight gradients and what its
    backward holds at once — ``carried + max(waits + experts_set, own) −
    absent``: the LARGER of two moments (no two overlap), beside the block's
    carried cotangent (``carried``), less what the step's own terms count
    there and the compiled step does not hold. Every term is a buffer of the
    cell's step compiled for a v5e, read off the buffer assignment's live
    ranges (``chiprun_out/pr69/``: ``parent.live.txt`` is XLA's own list at its
    peak — moment 1 —, ``parent.moments.txt`` the bytes in use by program
    point and the lists at both moments; PERF.md §6, PR 69).

    1. The expert half's backward. Of the DeltaNet mixer's second forward the
       in-projections and the conv kernels have run by then and their outputs
       wait (``delta_waits``): the block's input, q‖k‖v‖z as projected, q, k,
       v out of ``conv_silu_norm`` (ONE tensor each: the kernel convolves,
       gates and normalises in one pass), the solve's X, the float32 [b, a]
       and the weights' cast. The scan has NOT run: no state, no o, no y
       waits. Of gated attention what its backward will read waits
       (``attn_waits``): the block's input and u, q with its gate, k, v (each
       once more by the group, as the kernel is handed them), o and lse, the
       weights' cast. The expert half holds its stream and the routing's
       tensors beside the LARGER of the routed passes' set and the shared
       expert's (``experts_set``: parts.gated_experts_working_set,
       parts.swiglu_price — the DeepSeek family's terms, 0.3 GiB over this
       step's 1.54).
    2. The mixer's own backward. The DeltaNet mixer's is largest while the
       gate/norm kernel's backward runs, the compiled step's peak
       (``delta_own``): the whole second forward stands — moment 1's set, the
       states a chunk starts from (float32 [d_k, d_v] a value head and chunk:
       8 × v's bytes at the published sizes), o and the gated y — beside the
       three cotangents born so far, d y, d o, d z. A tensor's cotangent is
       born when its consumer's backward runs and the residual dies there:
       never a gradient for every tensor at once. Attention's
       (``attn_own``): its set and the gradients of the stream, of q with its
       gate, of k, v at the query heads and of o.

    ``absent``: through a layer run's backward the step's terms
    (blocks.backward_phases, the resident bytes) count, and the compiled
    step does not hold, the embedding gathered beside an unreduced float32
    gradient (on one chip nothing is gathered: _trunk indexes the cast
    table), the embedding's gradient (made when the layers are done: 4 bytes
    a number), and in float32 the gradients made BEFORE the run's that wait
    for the optimizer in the compute dtype, 4 − a bytes a number over: the
    head's and those of the layers that stand alone after the kind's runs
    (a scan stacks its layers' in float32: nothing off for those). 0.68 GiB
    for the cell's ``L``; what else those terms hold too long — a later
    run's kept residuals, dead by then — is left on (PERF.md §7)."""
    a = jnp.dtype(cfg.dtype).itemsize
    D, H, hd = cfg.d_model, cfg.n_head, cfg.head_dim
    Hv, dk, dv = cfg.linear_value_heads, cfg.linear_key_dim, cfg.linear_value_dim
    kw, vw = cfg.key_width, cfg.value_width
    flash = parts.is_flash(cfg.attention_impl, mesh)
    base = parts.shard_block(parts.BlockShard(
        batch=global_batch, seq=seq, d_model=D, heads=H, head_dim=hd, d_ff=0,
        vocab=cfg.vocab_size, dtype_bytes=a, flash=flash, dense_mlp=False,
        kv_heads=cfg.n_kv_head,
        head_rows=parts.head_rows(global_batch, seq, cfg.vocab_size, 1),
        cast_in_loop=True), mesh)
    tokens = base.batch * base.seq
    C = blocks.RematCandidate
    carried = tokens * D * a
    mid = C((scopes.RES_MID,), tokens * D * a, 2 * tokens * vw * D)

    # Gated DeltaNet: the projections' outputs; the solve's X, at what its
    # kernel spends (the one residual of the scan that spares a call alone:
    # the recompute's solve); the scan's states and o together (either alone
    # spares no call), at what the forward call is left with
    fused = 2 * kw + 2 * vw
    chunk, r = cfg.delta_chunk, Hv // cfg.linear_key_heads
    chunks = -(-base.seq // chunk) * base.batch
    states = chunks * Hv * dk * dv * 4
    solved = chunks * Hv * chunk * chunk * a
    ba = tokens * 2 * Hv * 4
    delta_kept = (
        C(scopes.RES_DELTA_PARTS, tokens * fused * a, 2 * tokens * D * fused),
        C((scopes.RES_DELTA_BA,), ba, 2 * tokens * D * 2 * Hv),
        C((scopes.RES_DELTA_X,), solved, chunks * cfg.linear_key_heads
          * gated_delta.solve_flops(chunk, r, dk)),
        C((scopes.RES_DELTA_STATES, scopes.RES_DELTA_O),
          states + tokens * vw * a,
          int(2 * tokens * (delta_scan_macs(cfg) - kw * chunk / 2))),
        mid)
    delta_params = D * fused + D * 2 * Hv + vw * D
    delta_waits = (a * (tokens * (D + fused + (2 * kw + vw)) + delta_params)
                   + solved + ba)
    delta_own = delta_waits + states + a * tokens * 5 * vw

    # gated attention: q, k, v, the gate and the kernel's two
    # (parts.remat_candidates prices q, k, v and o + lse)
    width, kv_width = H * hd, cfg.n_kv_head * hd
    attn_kept = tuple(c for c in parts.remat_candidates(base)
                      if c.names != (scopes.RES_MID,)) + (
        C((scopes.RES_ATTN_GATE,), tokens * width * a,
          2 * tokens * D * width), mid)
    attn_params = D * 2 * width + 2 * D * kv_width + width * D
    attn_waits = (a * (tokens * (2 * D + 3 * width + 2 * kv_width + 2 * width)
                       + attn_params) + (tokens * H * 4 if flash else 0))
    attn_own = attn_waits + a * (tokens * (2 * D + 5 * width) + attn_params)

    # the expert half, as the DeepSeek family prices it
    shared_kept, shared_set = parts.swiglu_price(
        base.batch, base.seq,
        parts.mlp_rows(base.batch, base.seq, D, cfg.d_shared, a), D,
        cfg.d_shared, a, (scopes.RES_MOE_SHARED_GATE, scopes.RES_MOE_SHARED_UP))
    experts_kept = parts.routing_candidates(
        tokens, D, cfg.n_experts, cfg.top_k, cfg.held_count) + shared_kept
    stream, routed_set = parts.gated_experts_working_set(
        tokens, D, cfg.n_experts, cfg.top_k, cfg.held_count, cfg.d_expert, a)
    experts_set = stream + max(routed_set, shared_set)

    kinds = {}
    for kind in dict.fromkeys(cfg.pattern):
        kept, waits, own = ((delta_kept, delta_waits, delta_own) if kind == "L"
                            else (attn_kept, attn_waits, attn_own))
        kinds[kind] = blocks.KindShard(
            cfg.pattern.count(kind), kept + experts_kept,
            carried + max(waits + experts_set, own))
    kinds = blocks.with_grad_bytes(
        blocks.one_candidate_a_name(kinds), partial(_layer_init, cfg=cfg), mesh)
    one_chip = mesh is None or mesh.devices.size == 1
    table = base.vocab * D
    # d lm_head in the compute dtype, no d wte yet, nothing gathered (what
    # backward_phases adds: the table in the compute dtype and in float32)
    absent = table * ((4 - a) + 4 + (a + 4 if one_chip else 0))
    for kind, lone in _lone_after(cfg.pattern, kinds).items():
        kinds[kind] = kinds[kind]._replace(block_bytes=max(
            0, kinds[kind].block_bytes - absent - lone * (4 - a) // 4))
    return base, kinds


def _lone_after(pattern: str, kinds: Dict[str, blocks.KindShard]
                ) -> Dict[str, int]:
    """A kind of ``pattern`` → the float32 bytes of the weight gradients of
    the layers that stand alone (in no scan) after the kind's runs — after
    EVERY run that holds it: the least over them, so that what kind_shards
    takes off is off in each."""
    runs = blocks.pattern_groups(pattern)
    after = [sum(kinds[k].grad_bytes for sub, reps in runs[i + 1:] if reps == 1
                 for k in sub) for i in range(len(runs))]
    return {kind: min(after[i] for i, (sub, _) in enumerate(runs)
                      if kind in sub) for kind in kinds}


def _trunk(params, tokens, cfg: Qwen3NextConfig, aux: Optional[str] = None,
           rate=None):
    """tokens [B, S] int32 → the head's input [B, S, D] (and, with ``aux``,
    blocks.run_pattern's: each layer's, _layer says what)."""
    B, S = tokens.shape
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    fns = {kind: partial(_layer, cfg=cfg, kind=kind, aux=aux, rate=rate)
           for kind in KINDS}
    if aux in (None, "load"):       # checkpointed: a backward may follow
        from ray_tpu.parallel import mesh as mesh_lib

        base, kinds = kind_shards(cfg, B, S, mesh_lib.current_mesh())
        blocks.record_layer_pattern(cfg.pattern)
        fns = blocks.checkpoint_kinds(
            {kind: fns[kind] for kind in kinds}, cfg.remat, base, kinds,
            blocks.pattern_groups(cfg.pattern))
    out = blocks.run_pattern(fns, cfg.pattern, x, params["blocks"],
                             with_aux=bool(aux))
    x, auxes = out if aux else (out, None)
    with jax.named_scope(scopes.LN_F):
        x = _norm(x, params["final_norm"], cfg)
    return (x, auxes) if aux else x


def forward(params, tokens, cfg: Qwen3NextConfig) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, vocab_size]."""
    return jnp.einsum("bsd,dv->bsv", _trunk(params, tokens, cfg),
                      params["lm_head"].astype(cfg.dtype))


def step_fields(cfg: Qwen3NextConfig) -> Tuple[str, ...]:
    """What loss_fn hands out of a step a layer: the dispatch's counters and,
    under a balance loss, its value (float32 bits in the int32 array)."""
    return scopes.STEP_EXPERT_LOAD_ARGS + (
        (scopes.STEP_BALANCE_LOSS,) if cfg.aux_loss_coef > 0 else ())


def loss_fn(params, tokens, targets, cfg: Qwen3NextConfig,
            counters: bool = False):
    """Mean cross-entropy over targets >= 0 ([B, S] int32, the next token),
    plus ``aux_loss_coef`` × the sum over the layers of the balance loss:
    the objective, whole, inside what the step differentiates. With
    ``counters`` (what step_counters offers a step factory: the aux of its
    ``value_and_grad``) the result is (the loss, what each layer's expert
    half said of the batch: int32 [layers, step_fields])."""
    x, auxes = _trunk(params, tokens, cfg, "load")
    loss = parts.lm_head_loss(x, targets, params["lm_head"], cfg.dtype)
    balanced = cfg.aux_loss_coef > 0
    if balanced:
        with jax.named_scope(scopes.MOE_AUX):
            loss = loss + cfg.aux_loss_coef * jnp.sum(
                blocks.aux_column(auxes, scopes.STEP_BALANCE_LOSS))
    if not counters:
        return loss
    return loss, blocks.packed_aux(
        auxes, step_fields(cfg),
        (scopes.STEP_BALANCE_LOSS,) if balanced else ())


def _layer_ids(cfg: Qwen3NextConfig) -> Tuple[int, ...]:
    """The published index of every layer run here (each has experts)."""
    return tuple(cfg.first_layer + i for i in range(cfg.n_layer))


def step_counters(cfg: Qwen3NextConfig) -> Optional[blocks.StepCounters]:
    """What ``loss_fn(..., counters=True)`` hands out of a step. A layer's
    id is ``model/expert_load``'s ``layer``: the published index."""
    return parts.expert_step_counters(
        _layer_ids(cfg), cfg.n_experts, cfg.top_k, cfg.held, step_fields(cfg),
        (scopes.STEP_BALANCE_LOSS,) if cfg.aux_loss_coef > 0 else ())


def flops_per_token(cfg: Qwen3NextConfig) -> float:
    """Forward + backward operations one trained token REQUIRES here: 6 per
    matmul parameter the token meets (a mixer's projections, the router, the
    shared expert with its gate, the routed experts by the pairs a token is
    expected to land on held ones, top_k · held / n_experts a layer; the
    embedding is a gather, the head a matmul) and by shape three times the
    forward's attention (two products at head_dim over the causal half) and
    scan (delta_scan_macs). The conv, the norms and the gates are
    elementwise: not counted."""
    D, S = cfg.d_model, cfg.seq_len
    width, kv_width = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
    mixer = {"L": (D * (2 * cfg.key_width + 2 * cfg.value_width)
                   + D * 2 * cfg.linear_value_heads + cfg.value_width * D
                   + delta_scan_macs(cfg)),
             "F": (D * 2 * width + 2 * D * kv_width + width * D
                   + 2 * width * (S + 1) / 2)}
    experts = (D * cfg.n_experts + 3 * D * cfg.d_shared + D
               + cfg.top_k * cfg.held_count / cfg.n_experts
               * 3 * D * cfg.d_expert)
    return 6.0 * (sum(mixer[k] + experts for k in cfg.pattern)
                  + D * cfg.vocab_size)


# --------------------------------------------------------------------------- #
# What each token chose; the routers, balanced at set-up
# --------------------------------------------------------------------------- #

def chosen_experts(params, tokens, cfg: Qwen3NextConfig) -> List[jax.Array]:
    """The set each token of ``tokens`` [B, S] chose in each layer, in the
    layers' order: [B·S, n_experts] bool a layer. What a reference is told,
    so that a near-tie rounding flipped is not read as a wrong model."""
    return blocks.aux_by_layer(blocks.pattern_groups(cfg.pattern),
                               _trunk(params, tokens, cfg, "chosen")[1])


def balance_routers(params, batches, cfg: Qwen3NextConfig):
    """(``params`` with every layer's router balanced on ``batches`` — N token
    arrays [B, S], or one —, what the last round's batch then sends the
    experts held here). A softmax router has no selection bias: what balances
    it is the balance loss, over a run's many steps, so a run on freshly
    drawn weights starts from routers that something balanced —
    moe.BALANCE_ROUNDS forwards of their own, round r on batch r mod N, in
    which every layer's router takes one round of moe.balance_router on what
    the layers before it — as balanced so far — hand it, the rate falling
    from moe.BALANCE_ROUTER_RATE to 0, every other weight held; exactly
    deepseek_v2.balance_routers. The loads are the ``model/expert_load``
    events, recorded here. For set-up: NO TRAINING PATH CALLS IT (nor
    ``_layer``'s ``"balance"``) — a benchmark's build and chip_smoke.py do,
    once before the first step."""
    batches = [batches] if hasattr(batches, "ndim") else list(batches)
    runs = blocks.pattern_groups(cfg.pattern)

    @jax.jit
    def one_round(p, tokens, rate):
        auxes = blocks.aux_by_layer(
            runs, _trunk(p, tokens, cfg, "balance", rate)[1])
        return [aux.pop("router_w") for aux in auxes], auxes

    rounds = moe.BALANCE_ROUNDS
    for r in range(rounds):
        rows, loads = one_round(params, batches[r % len(batches)],
                                moe.BALANCE_ROUTER_RATE * (1.0 - r / rounds))
        params = {**params, "blocks": blocks.with_leaf(
            cfg.pattern, params["blocks"], "router_w", iter(rows))}
    return params, moe.record_expert_loads(_layer_ids(cfg),
                                           jax.device_get(loads))
