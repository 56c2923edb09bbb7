"""AFMoE-family decoder (Arcee Trinity) in pure JAX: WINDOW and FULL
attention layers mixed in one pattern, every sublayer between two norms.

Tenth model family. A layer is a pair of residuals, each with a norm on its
input AND on its output (a sandwich norm), ``h = x + RMSNorm(Attn(RMSNorm(x)))``,
``x' = h + RMSNorm(FF(RMSNorm(h)))``, read off a pattern string, one
character a layer (from the published ``layer_types`` and
``num_dense_layers``: pattern_from). The kinds differ in attention, in the
feed-forward half, or both:

- ``D`` — window attention + a dense SwiGLU MLP (the leading layers);
- ``W`` — window attention + a mixture of gated experts;
- ``F`` — full attention + a mixture of gated experts.

**Attention** is grouped-query (``n_head`` query heads on ``n_kv_head``
key-value heads) with a per-head RMSNorm of q and k (one gain vector of
head_dim each) and a sigmoid OUTPUT GATE from a projection of its own
(``γ = σ(u·W_g)``, one gate a query channel, on the kernel's output before
the out-projection). A WINDOW layer rotates q and k (RoPE, rotate-half, after
the norm) and a query sees the ``sliding_window`` keys up to its own; a FULL
layer has no positional signal at all (NoPE) and a query sees every key
before it. Both run on the one flash pair (parts.causal_attention; a window
layer hands it ``window``: ops/attention.py walks the band alone; k and v go
in at their own heads and the kernels read each for its group), under the
scopes ``attn_window`` / ``attn_full`` inside ``attn`` — both kinds run
kernels of one name, and a trace tells them apart by the scope.

**The feed-forward halves**: the dense ``(silu(u·W₁) ⊙ u·W₃)·W₂``, and the
expert layer (ops/moe.gated_moe): sigmoid scores in float32 over all
``n_experts``, the ``top_k`` largest of score + bias chosen (the bias —
``router_bias`` — chooses only and is a buffer), gates ``route_scale · s /
(Σ_chosen s + 1e-20)``, experts of the dense MLP's form at ``d_expert``
beside ONE shared expert of ``n_shared · d_expert``. The embedding's output
is scaled by √d_model (``mup_enabled``); the head is untied, after an RMSNorm.

It runs on the shared machinery: ``blocks.run_pattern`` /
``blocks.checkpoint_kinds`` (ONE remat rule over the three kinds'
applications, a window layer's attention priced by its band:
parts.BlockShard.flash_window), parts' RMSNorm, RoPE, residual add, weight
cast inside the loop, causal attention, the rows an MLP and a head take at a
time and the chunked head + loss; ops/moe.py's dispatch, shared with five
other families' expert layers; tracing/names.py's scopes and residuals.

The config states the chip's SHARE of a deployment beside the published
sizes: which routed experts and how many vocabulary rows are held here, and
which published layer the pattern starts at. Routing is over all
``n_experts`` at the published top-k; what absent experts would have added
is left out (no code stands in for absent chips or their exchange): the
shares' expert layers add up to the whole layer's (tests/test_afmoe.py).
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
from ray_tpu.ops import attention_pointwise, moe
from ray_tpu.tracing import names as scopes

KINDS = "DWF"
# a kind's attention (a window, or every key before the query) and its
# feed-forward half
WINDOWED = {"D": True, "W": True, "F": False}
EXPERTS = {"D": False, "W": True, "F": True}
INIT_STD = 0.02      # every matrix (initializer_range); every gain 1


@dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192          # rows of the embedding / head held here
    seq_len: int = 4096
    pattern: str = "DD" + "WFWW" * 7 + "WF"     # one character a layer
    first_layer: int = 0              # the published index of pattern[0]
    d_model: int = 2048
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    sliding_window: int = 2048        # keys a window layer's query sees
    rope_theta: float = 10_000.0      # window layers only; full layers: NoPE
    d_ff: int = 6144                  # the dense layers' SwiGLU hidden
    # the expert layers: the router is n_experts wide; ids held_first … +
    # held_count − 1 are computed here
    n_experts: int = 128
    top_k: int = 8
    held_first: int = 0
    held_count: int = 128
    d_expert: int = 1024
    n_shared: int = 1                 # one shared SwiGLU of n_shared · d_expert
    route_scale: float = 2.826
    mup_enabled: bool = True          # the embedding's output · √d_model
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
        if self.sliding_window < 1:
            raise ValueError("sliding_window must be at least 1")
        if not 0 <= self.held_first <= self.n_experts - self.held_count:
            raise ValueError(
                f"held experts {self.held_first}…+{self.held_count} are not "
                f"among {self.n_experts}")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError("top_k must be in [1, n_experts]")
        if self.vocab_size % 64:
            # (an eighth of the published 200,192 is 25,024 = 391 · 64: the
            # head's columns are then whole sublane tiles, not lane tiles)
            raise ValueError("vocab_size (the rows held here) must be a "
                             "multiple of 64")

    @property
    def n_layer(self) -> int:
        return len(self.pattern)

    @property
    def held(self) -> moe.Held:
        return moe.Held(self.held_first, self.held_count)

    @property
    def d_shared(self) -> int:
        return self.n_shared * self.d_expert

    def window(self, kind: str) -> Optional[int]:
        """The causal window a layer of ``kind`` hands the attention."""
        return self.sliding_window if WINDOWED[kind] else None


def pattern_from(layer_types: Sequence[str], num_dense_layers: int,
                 first_layer: int = 0, n_layer: Optional[int] = None) -> str:
    """The published ``layer_types`` (``sliding_attention`` /
    ``full_attention``), the first ``num_dense_layers`` of them with the
    dense MLP, as a pattern: layers ``first_layer`` … + ``n_layer`` − 1 (all
    from ``first_layer`` on where none is given)."""
    out = []
    for i, op in enumerate(layer_types):
        if op not in ("sliding_attention", "full_attention"):
            raise ValueError(f"layer_types[{i}] = {op!r}: sliding_attention "
                             "or full_attention")
        dense = i < num_dense_layers
        if dense and op != "sliding_attention":
            raise ValueError(f"layer {i}: full attention + dense MLP is a "
                             "pair no published config has and no kind here is")
        out.append("D" if dense else "W" if op == "sliding_attention" else "F")
    last = None if n_layer is None else first_layer + n_layer
    return "".join(out[first_layer:last])


def trinity_mini(**overrides) -> AfmoeConfig:
    """arcee-ai/Trinity-Mini (26B-A3B): 32 layers, two leading dense ones,
    three window layers then a full one eight times, every expert held."""
    return replace(AfmoeConfig(), **overrides)


def afmoe_tiny(**overrides) -> AfmoeConfig:
    """Test-size config: a leading dense layer and one period, every kind,
    rows of four windows."""
    return replace(AfmoeConfig(
        vocab_size=256, seq_len=64, pattern="DWFWW", first_layer=1,
        d_model=64, n_head=4, n_kv_head=2, head_dim=16, sliding_window=16,
        d_ff=160, n_experts=16, top_k=4, held_first=4, held_count=8,
        d_expert=48, route_scale=2.0), **overrides)


# --------------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------------- #

_ATTN_WEIGHTS = ("wq", "wk", "wv", "wg", "wo")
_DENSE_WEIGHTS = ("w_gate", "w_up", "w_down")
_NORMS = ("attn_norm", "attn_post_norm", "ffn_norm", "ffn_post_norm")


def _matmul_weights(kind: str) -> Tuple[str, ...]:
    """What a layer of ``kind`` takes in the compute dtype (the router and
    the norms' gains stay as they are stored)."""
    return _ATTN_WEIGHTS + (
        moe.GATED_EXPERT + moe.GATED_SHARED_EXPERT if EXPERTS[kind]
        else _DENSE_WEIGHTS)


def _layer_init(rng, n: int, kind: str, cfg: AfmoeConfig):
    """``n`` stacked layers of ``kind``: the four norms, attention's tensors
    (a window and a full layer hold the same) and the feed-forward half's."""
    D, pd = cfg.d_model, cfg.param_dtype
    H, KH, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    k_ff, *k = jax.random.split(rng, 9)
    k = iter(k)

    def normal(shape):
        return (jax.random.normal(next(k), shape) * INIT_STD).astype(pd)

    p = {name: jnp.ones((n, D), pd) for name in _NORMS}
    p.update(wq=normal((n, D, H, hd)), wk=normal((n, D, KH, hd)),
             wv=normal((n, D, KH, hd)), wg=normal((n, D, H, hd)),
             wo=normal((n, H, hd, D)),
             q_norm=jnp.ones((n, hd), pd), k_norm=jnp.ones((n, hd), pd))
    if EXPERTS[kind]:
        p.update(moe.gated_moe_init(
            k_ff, n, D, cfg.n_experts, cfg.held_count, cfg.d_expert,
            INIT_STD, INIT_STD, pd, d_shared=cfg.d_shared))
    else:
        p.update(w_gate=normal((n, D, cfg.d_ff)), w_up=normal((n, D, cfg.d_ff)),
                 w_down=normal((n, cfg.d_ff, D)))
    return p


def _stack_init(rng, pattern: str, cfg: AfmoeConfig):
    return blocks.init_pattern(rng, pattern, KINDS,
                               partial(_layer_init, cfg=cfg))


_HEAD_AXES = ("layers", "embed", "heads", "kv")
_LAYER_AXES = {
    **{name: ("layers", "embed") for name in _NORMS},
    "wq": _HEAD_AXES, "wk": _HEAD_AXES, "wv": _HEAD_AXES, "wg": _HEAD_AXES,
    "wo": ("layers", "heads", "kv", "embed"),
    "q_norm": ("layers", None), "k_norm": ("layers", None),
    "w_gate": ("layers", "embed", "mlp"), "w_up": ("layers", "embed", "mlp"),
    "w_down": ("layers", "mlp", "embed"),
    **moe.gated_moe_logical_axes(),
}


def logical_axes(cfg: AfmoeConfig) -> Dict[str, Any]:
    return blocks.pattern_logical_axes(
        lambda rng: _stack_init(rng, cfg.pattern, cfg), _LAYER_AXES)


def mesh_rules(cfg: AfmoeConfig, mesh) -> Dict[str, str]:
    """What this config needs of this mesh: no rule beyond the defaults, and
    the refusal of the axes no code here runs over."""
    for axis, why in (
            ("ep", "the expert layer computes the experts the config says it "
                   "holds and no all-to-all exchanges tokens"),
            ("tp", "the grouped heads, the output gate and the held experts' "
                   "hidden width are not divided here"),
            ("pp", "a pattern of kinds under a stage schedule"),
            ("cp", "a window layer's band is walked over whole rows: the "
                   "ring (ops/ring_attention.py) refuses a window")):
        if mesh.shape.get(axis, 1) > 1:
            raise NotImplementedError(
                f"{axis} > 1 is not implemented for the AFMoE family "
                f"({why}); use a {axis}=1 mesh")
    return {}


def init(cfg: AfmoeConfig, rng: jax.Array) -> Dict[str, Any]:
    k = jax.random.split(rng, 3)
    pd = cfg.param_dtype
    wte = jax.random.normal(k[0], (cfg.vocab_size, cfg.d_model)) * INIT_STD
    head = jax.random.normal(k[2], (cfg.d_model, cfg.vocab_size)) * INIT_STD
    return {"wte": wte.astype(pd),
            "blocks": _stack_init(k[1], cfg.pattern, cfg),
            "final_norm": jnp.ones((cfg.d_model,), pd),
            "lm_head": head.astype(pd)}


def param_count(cfg: AfmoeConfig) -> int:
    """The parameters a step moves: every leaf but the expert layers'
    selection biases, which are buffers."""
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

def attention_operator(u, p, cfg: AfmoeConfig, kind: str):
    """u [B, S, D] (normed) → the operator's output [B, S, D] float32, under
    its output norm: QK-norm, then RoPE on a window layer alone, the flash
    pair over the kind's window, the sigmoid gate, the out-projection.

    Every elementwise value between a projection and the kernel or product
    that reads it is made once, in one pass, and is no operand fusion of a
    product (PERF.md §6, PR 67). The five weights as cast are pinned
    (parts.made_once: in a layer outside a scan the cast and a copy of the
    float32 weight stood inside each first-forward product). Where the flash
    pair runs at a head of whole lane tiles, QK-norm + RoPE and the gate are
    the kernel pairs of ops/attention_pointwise.py, which read and write
    ``[B, S, H · hd]`` where a plain product writes and reads it and
    ``[B, H, S, hd]`` where the flash pair does: no tensor is re-ordered in
    HBM on the way. Anywhere else (a narrower head, the XLA path) the plain
    forms run as they did (barriers on u, on q's and k's cotangents and on
    the gate's product were measured at this cell's shapes and gave nothing
    or cost: a barrier pins a value, not the order XLA stores it in)."""
    layout = parts.head_layout(cfg.head_dim)
    heads = layout.replace("d", "k")                    # the einsums' names
    s_minor, width = heads[-1] == "s", heads.index("k")
    window = cfg.window(kind)
    theta = None if window is None else cfg.rope_theta  # a full layer: NoPE
    impl, interpret, _ = parts.attention_on_mesh(cfg.attention_impl)
    kernels = impl == "pallas" and layout == "bhsd"

    def by_head(name):     # (w: the five weights as cast, pinned below)
        return jnp.einsum(f"bsd,dhk->{heads}", u, w[name])

    def flat(name):     # [B, S, H · hd]: where a plain product writes it
        return jnp.einsum("bsd,dn->bsn", u, w[name].reshape(u.shape[2], -1))

    def normed(name, gain):
        if kernels:
            return attention_pointwise.head_norm_rope(
                flat(name), gain, w[name].shape[1], cfg.rms_eps, theta,
                interpret=interpret)
        x = parts.head_rmsnorm(by_head(name), gain, cfg.rms_eps, width)
        if theta is None:
            return x
        return parts.rope(x, jnp.arange(u.shape[1]), theta, s_minor)

    with jax.named_scope(scopes.QKV):
        w = dict(zip(_ATTN_WEIGHTS, parts.made_once(
            tuple(p[name] for name in _ATTN_WEIGHTS))))
        # named after the norm and the rotation: a kept q or k has both
        q = checkpoint_name(normed("wq", p["q_norm"]), scopes.RES_Q)
        k = checkpoint_name(normed("wk", p["k_norm"]), scopes.RES_K)
        v = checkpoint_name(by_head("wv"), scopes.RES_V)
        gate = checkpoint_name(flat("wg") if kernels else by_head("wg"),
                               scopes.RES_ATTN_GATE)
    with jax.named_scope(scopes.ATTN), jax.named_scope(
            scopes.ATTN_FULL if window is None else scopes.ATTN_WINDOW):
        o = parts.causal_attention(q, k, v, cfg.attention_impl, layout=layout,
                                   window=window, grouped_kv=True)
        with jax.named_scope(scopes.GATED_ATTN_GATE):
            if kernels:
                o = attention_pointwise.sigmoid_gated(o, gate,
                                                      interpret=interpret)
            else:
                o = parts.made_once(
                    (o.astype(jnp.float32)
                     * jax.nn.sigmoid(gate.astype(jnp.float32))
                     ).astype(u.dtype))
    with jax.named_scope(scopes.PROJ):
        if kernels:     # o [B, S, H · hd]: where a plain product reads it
            y = jnp.einsum("bsn,nd->bsd", o, w["wo"].reshape(o.shape[2], -1),
                           preferred_element_type=jnp.float32)
        else:
            y = jnp.einsum(f"{heads},hkd->bsd", o, w["wo"],
                           preferred_element_type=jnp.float32)
        with jax.named_scope(scopes.LN1_POST):
            return parts.rmsnorm(y, p["attn_post_norm"], cfg.rms_eps)


def _swiglu(x, p, cfg: AfmoeConfig):
    """x + norm(down(silu(gate(h)) · up(h))), h = norm(x), on [B, rows, D]:
    the output's norm is the rows' (float32 in, float32 out: no normed copy
    of the sequence stands whole)."""
    with jax.named_scope(scopes.LN2):
        h = parts.rmsnorm(x, p["ffn_norm"], cfg.rms_eps)
    y = parts.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    with jax.named_scope(scopes.LN2_POST):
        y = parts.rmsnorm(y, p["ffn_post_norm"], cfg.rms_eps)
    with jax.named_scope(scopes.MLP):
        return parts.residual_add(x, y)


def _dense(x, p, cfg: AfmoeConfig):
    """The dense feed-forward half, norms and all, in chunks of the sequence
    where parts.mlp_rows says so — as the llama block's, and why
    (models/llama.py)."""
    return parts.in_row_chunks(
        partial(_swiglu, p=p, cfg=cfg), x,
        parts.mlp_rows(*x.shape, cfg.d_ff, x.dtype.itemsize))


def _routing(cfg: AfmoeConfig) -> Dict[str, Any]:
    # (route_norm: the chosen scores over their sum; the published code's
    # 1e-20 under it; moe.route's default rule — sigmoid, normalised)
    return dict(top_k=cfg.top_k, held=cfg.held, scaling=cfg.route_scale,
                eps=1e-20)


def _experts(x, p, cfg: AfmoeConfig, aux: Optional[str], rate=None):
    """The expert feed-forward half → (x, what ``aux`` asks of it)."""
    B, S, D = x.shape
    with jax.named_scope(scopes.LN2):
        h = parts.rmsnorm(x, p["ffn_norm"], cfg.rms_eps)
    ht, out = h.reshape(-1, D), None
    if aux == "balance":
        bias = moe.balance_bias_round(ht, p["router_w"], p["router_bias"],
                                      cfg.top_k, rate)
        p = {**p, "router_bias": bias}
        out = {"router_bias": bias, **moe.held_load(ht, p, **_routing(cfg))}
    elif aux == "chosen":
        out = moe.chosen_experts(ht, p, cfg.top_k)
    with jax.named_scope(scopes.MOE):
        f, load = moe.gated_moe(
            h, p, **_routing(cfg),
            shared_rows=parts.mlp_rows(B, S, D, cfg.d_shared,
                                       x.dtype.itemsize))
    with jax.named_scope(scopes.LN2_POST):
        f = parts.rmsnorm(f, p["ffn_post_norm"], cfg.rms_eps)
    return parts.residual_add(x, f), load if aux == "load" else out


@jax.named_scope(scopes.BLOCK)
def _layer(x, p, cfg: AfmoeConfig, kind: str, aux: Optional[str] = None,
           rate=None):
    """One layer of ``kind``, x [B, S, D]: attention's residual, then the
    feed-forward half's, each between two norms. With ``aux`` the result is
    (x, aux's value), None for a dense layer: ``"load"`` — what the batch
    sends the held experts, as the dispatch that runs the passes has it
    (moe.routed_experts; the training forward's) —; in a forward of its own,
    no backward, ``"balance"`` — an expert layer first takes one round of
    balancing its selection bias on this input, at ``rate``
    (moe.balance_bias_round); the bias and what the input then sends the
    held experts (moe.held_load) —, ``"chosen"`` — the set each token chose,
    [T, n_experts] bool."""
    p = {**p, **parts.cast_in_the_loop(p, x, cfg.dtype, _matmul_weights(kind))}
    with jax.named_scope(scopes.LN1):
        u = parts.rmsnorm(x, p["attn_norm"], cfg.rms_eps)
    y = attention_operator(u, p, cfg, kind)
    x = checkpoint_name(parts.residual_add(x, y), scopes.RES_MID)
    if EXPERTS[kind]:
        x, out = _experts(x, p, cfg, aux, rate)
    else:
        x, out = _dense(x, p, cfg), None
    return (x, out) if aux else x


def kind_shards(cfg: AfmoeConfig, global_batch: int, seq: int, mesh
                ) -> Tuple[parts.BlockShard, Dict[str, blocks.KindShard]]:
    """This config's layers on one chip of ``mesh``, for the remat rule: the
    model's shard (stream, head, rows at a time) and, a kind, how often it is
    applied, what a layer of it may keep, its weight gradients and what its
    backward holds at once — the LARGER of two moments, as the DeepSeek and
    Qwen3-Next families' (no two overlap); through both waits the cotangent
    of the block's output.

    - The feed-forward half's backward. The whole block's forward has been
      made again by then, so attention's residual set waits: its input and
      ``u``, q and the gate, k and v (at their own heads: the kernels read
      grouped heads where they stand), o, the gated o and lse, the weights
      cast once.
      The expert half holds its stream and the routing's tensors beside the
      LARGER of the routed passes' set and the shared expert's; the dense
      half a chunk's hidden tensors and its weights; the expert half its
      output's normed float32 copy and that copy's cotangent too (the output
      norm; a dense chunk's are the chunk's).
    - Attention's own backward: its set and each tensor's gradient, and the
      out-projection's float32 output with its norm's.

    A window layer's flash_o + flash_lse cost the BAND's pairs to make
    again, a full layer's the triangle's (parts.BlockShard.flash_window):
    the kinds' candidates of one name are priced a kind and summed over the
    layers that have it (blocks.one_candidate_a_name)."""
    a = jnp.dtype(cfg.dtype).itemsize
    D, F, H, hd = cfg.d_model, cfg.d_ff, cfg.n_head, cfg.head_dim
    flash = parts.is_flash(cfg.attention_impl, mesh)
    base = parts.shard_block(parts.BlockShard(
        batch=global_batch, seq=seq, d_model=D, heads=H, head_dim=hd, d_ff=F,
        vocab=cfg.vocab_size, dtype_bytes=a, flash=flash, dense_mlp=False,
        kv_heads=cfg.n_kv_head,
        mlp_hidden=(scopes.RES_MLP_GATE, scopes.RES_MLP_UP),
        head_rows=parts.head_rows(global_batch, seq, cfg.vocab_size, 1),
        mlp_rows=parts.mlp_rows(global_batch, seq, D, F, a),
        cast_in_loop=True, out_norms=True), mesh)
    tokens = base.batch * base.seq
    C = blocks.RematCandidate
    carried = tokens * D * a
    width, kv_width = H * hd, cfg.n_kv_head * hd
    mid = C((scopes.RES_MID,), tokens * D * a, 2 * tokens * width * D)
    # what an output norm adds to a half's backward: the normed float32 copy
    # of the half's output and its cotangent (the output itself and ITS
    # cotangent are the half's own: the expert stream's float32 sum, the
    # out-projection's accumulator)
    out_norm = 2 * tokens * D * 4

    def attn_kept(kind):
        shard = base._replace(flash_window=cfg.window(kind) or 0)
        return tuple(c for c in parts.remat_candidates(shard)
                     if c.names != (scopes.RES_MID,)) + (
            C((scopes.RES_ATTN_GATE,), tokens * width * a,
              2 * tokens * D * width), mid)

    attn_params = D * 2 * width + 2 * D * kv_width + width * D
    attn_waits = (a * (tokens * (2 * D + 4 * width + 2 * kv_width)
                       + attn_params) + (tokens * H * 4 if flash else 0))
    attn_set = (attn_waits + a * (tokens * (2 * D + 5 * width) + attn_params)
                + out_norm)

    dense_kept, dense_set = parts.swiglu_price(
        base.batch, base.seq, base.mlp_rows, D, F, a, base.mlp_hidden)
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
        ff_kept, ff_set = ((experts_kept, experts_set + out_norm)
                           if EXPERTS[kind] else (dense_kept, dense_set))
        kinds[kind] = blocks.KindShard(
            cfg.pattern.count(kind), attn_kept(kind) + ff_kept,
            carried + max(attn_waits + ff_set, attn_set))
    return base, blocks.with_grad_bytes(
        blocks.one_candidate_a_name(kinds), partial(_layer_init, cfg=cfg), mesh)


def _trunk(params, tokens, cfg: AfmoeConfig, aux: Optional[str] = None,
           rate=None):
    """tokens [B, S] int32 → the head's input [B, S, D] (and, with ``aux``,
    blocks.run_pattern's: each layer's, _layer says what)."""
    B, S = tokens.shape
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
        if cfg.mup_enabled:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)
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
        x = parts.rmsnorm(x, params["final_norm"], cfg.rms_eps)
    return (x, auxes) if aux else x


def forward(params, tokens, cfg: AfmoeConfig) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, vocab_size] (the untied head)."""
    return parts.untied_logits(_trunk(params, tokens, cfg), params["lm_head"],
                               cfg.dtype)


def loss_fn(params, tokens, targets, cfg: AfmoeConfig,
            counters: bool = False):
    """Mean cross-entropy over targets >= 0 ([B, S] int32, the next token).
    With ``counters`` (what step_counters offers a step factory: the aux of
    its ``value_and_grad``) the result is (the loss, what the batch sent each
    expert layer's held experts: int32 [expert layers, fields], in the
    layers' order)."""
    x = _trunk(params, tokens, cfg, "load" if counters else None)
    if counters:
        x, auxes = x
    loss = parts.lm_head_loss(x, targets, params["lm_head"], cfg.dtype)
    if not counters:
        return loss
    return loss, blocks.packed_aux(auxes, scopes.STEP_EXPERT_LOAD_ARGS)


def _expert_layer_ids(cfg: AfmoeConfig) -> Tuple[int, ...]:
    return parts.expert_layer_ids(cfg.pattern, cfg.first_layer, EXPERTS)


def step_counters(cfg: AfmoeConfig) -> Optional[blocks.StepCounters]:
    """What ``loss_fn(..., counters=True)`` hands out of a step, or None for
    a pattern without an expert layer. A layer's id is ``model/expert_load``'s
    ``layer``: the published index."""
    return parts.expert_step_counters(_expert_layer_ids(cfg), cfg.n_experts,
                                      cfg.top_k, cfg.held)


def attended_pairs(seq: int, window: Optional[int]) -> int:
    """(query, key) pairs one head's causal attention over a row of ``seq``
    tokens needs: the triangle's S(S + 1)/2, or under a window of w < S keys
    the band's w(w + 1)/2 + (S − w)·w."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def flops_per_token(cfg: AfmoeConfig) -> float:
    """Forward + backward operations one trained token REQUIRES here: 6 per
    matmul parameter the token meets (attention's five projections, the
    router, the shared expert, the routed experts by the pairs a token is
    expected to land on held ones, top_k · held / n_experts a layer; the
    embedding is a gather, the head a matmul) and by shape three times the
    forward's attention — two products over the pairs each KIND sees: the
    causal half for ``F``, the band for ``D`` / ``W`` (attended_pairs). The
    norms, the rotation and the gates are elementwise: not counted."""
    D, S = cfg.d_model, cfg.seq_len
    width, kv_width = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
    attn = D * (3 * width + 2 * kv_width)
    ff = {True: D * cfg.n_experts + 3 * D * cfg.d_expert * (
              cfg.n_shared + cfg.top_k * cfg.held_count / cfg.n_experts),
          False: 3 * D * cfg.d_ff}
    matmul = (sum(attn + ff[EXPERTS[k]] for k in cfg.pattern)
              + D * cfg.vocab_size)
    shaped = sum(2 * width * attended_pairs(S, cfg.window(k)) / S
                 for k in cfg.pattern)
    return 6.0 * (matmul + shaped)


# --------------------------------------------------------------------------- #
# The selection bias, balanced at set-up; what each token chose
# --------------------------------------------------------------------------- #

def chosen_experts(params, tokens, cfg: AfmoeConfig) -> List[jax.Array]:
    """The set each token of ``tokens`` [B, S] chose in each expert layer, in
    the layers' order: [B·S, n_experts] bool a layer. What a reference is
    told, so that a near-tie rounding flipped is not read as a wrong model."""
    return blocks.aux_by_layer(blocks.pattern_groups(cfg.pattern),
                               _trunk(params, tokens, cfg, "chosen")[1])


def balance_router_bias(params, batches, cfg: AfmoeConfig):
    """(``params`` with every expert layer's selection bias balanced on
    ``batches`` — N token arrays [B, S], or one —, what the last round's
    batch then sends the experts held here). The bias's between-step update
    (the published ``load_balance_coeff``: its trainer's rate) is not part of
    the step, so a run starts from a bias that something balanced:
    moe.BALANCE_ROUNDS rounds of the auxiliary-loss-free rule
    (moe.balance_bias_round), round r on batch r mod N, the rate falling from
    moe.BALANCE_RATE to 0, layer by layer in a forward of its own a round (a
    layer's input is what the layers before it, as balanced so far, give),
    the weights held. Give it as many batches as rounds: rounds on one batch
    fit that batch's near-ties (models/deepseek_v2.balance_router_bias says
    what that cost). The loads are the ``model/expert_load`` events
    (tracing/names.EXPERT_LOAD_ARGS; ``layer`` is the published index),
    recorded here. For set-up: no training path calls it."""
    batches = [batches] if hasattr(batches, "ndim") else list(batches)
    runs = blocks.pattern_groups(cfg.pattern)

    @jax.jit
    def one_round(p, tokens, rate):
        auxes = blocks.aux_by_layer(
            runs, _trunk(p, tokens, cfg, "balance", rate)[1])
        return [aux.pop("router_bias") for aux in auxes], auxes

    rounds = moe.BALANCE_ROUNDS
    for r in range(rounds):
        biases, loads = one_round(params, batches[r % len(batches)],
                                  moe.BALANCE_RATE * (1.0 - r / rounds))
        params = {**params, "blocks": blocks.with_leaf(
            cfg.pattern, params["blocks"], "router_bias", iter(biases))}
    return params, moe.record_expert_loads(_expert_layer_ids(cfg),
                                           jax.device_get(loads))
