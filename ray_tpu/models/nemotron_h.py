"""Nemotron-H-family hybrid decoder in pure JAX: a PATTERN of layer kinds.

Third model family beside GPT-2 and LLaMA, and the first whose layers are not
alike: every layer is a mixer OR a feed-forward part alone,
``x ← x + f_kind(RMSNorm(x))``, the kind read off a pattern string, one
character a layer (the published ``hybrid_override_pattern``):

- ``M`` — a Mamba-2 mixer (ops/mamba2.py: chunked state-space scan);
- ``E`` — a LatentMoE layer (ops/moe.latent_moe: sigmoid router over all the
  experts with a selection bias, the held experts' grouped products inside a
  latent projection, a shared expert beside them);
- ``*`` — grouped-query causal attention, no positional encoding
  (parts.causal_attention: the flash kernels).

After the trunk, one multi-token-prediction module (``mtp_pattern``, a second
small trunk fed by the first and by the next token's embedding) predicts the
token after next through the SAME final norm, embedding and head;
``loss = CE_trunk + mtp_loss_weight · CE_mtp``.

It runs on the shared machinery: ``blocks.run_pattern`` (a run of a repeated
sub-pattern is one ``lax.scan`` over the kinds' stacked parameters),
``blocks.checkpoint_kinds`` (ONE remat rule over all the kinds' applications),
models/parts.py's RMSNorm, residual add, weight cast inside the loop, causal
attention and chunked head + loss; tracing/names.py's scopes and residuals.

The config states the chip's SHARE of a deployment beside the published
sizes: how many of the Mamba heads / groups, attention heads, routed experts
and vocabulary rows are held here. Routing is over all ``n_experts`` at the
published top-k; what absent experts and heads would have added is left out
(no code stands in for absent chips): the out-projections' partial sums go
on as they are.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import blocks, parts
from ray_tpu.ops import mamba2, moe
from ray_tpu.tracing import names as scopes

KINDS = "ME*"        # Mamba-2 mixer, LatentMoE layer, attention
INIT_STD = 0.02      # every matrix; the three out-projections rescaled (init)


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072          # rows of the embedding / head held here
    seq_len: int = 4096
    pattern: str = "MEMEMEMEM*E"      # one character a layer: M, E or *
    mtp_pattern: str = "*E"           # the MTP module's layers ("": none)
    n_layer_published: int = 88       # the out-projections' init scale
    d_model: int = 4096
    # attention: heads held here, of head_dim
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    # Mamba-2: heads and groups held here
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    mamba_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    # LatentMoE: the router is n_experts wide; ids held_first … + held_count
    # − 1 are computed here
    n_experts: int = 512
    top_k: int = 22
    held_first: int = 0
    held_count: int = 512
    latent: int = 1024
    d_expert: int = 2688
    d_shared: int = 5376
    routed_scaling: float = 5.0
    rms_eps: float = 1e-5
    mtp_loss_weight: float = 0.1
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "auto"

    def __post_init__(self):
        for name in ("pattern", "mtp_pattern"):
            odd = set(getattr(self, name)) - set(KINDS)
            if odd:
                raise ValueError(f"{name} {getattr(self, name)!r}: a layer is "
                                 f"one of {sorted(KINDS)}, not {sorted(odd)}")
        if not self.pattern:
            raise ValueError("pattern is empty")
        if not isinstance(self.remat, bool):
            raise ValueError(f"remat must be True or False; got {self.remat!r}")
        if self.n_head % self.n_kv_head:
            raise ValueError(f"n_head={self.n_head} must be divisible by "
                             f"n_kv_head={self.n_kv_head}")
        if self.mamba_heads % self.mamba_groups:
            raise ValueError("mamba_heads must be divisible by mamba_groups")
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
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def held(self) -> moe.Held:
        return moe.Held(self.held_first, self.held_count)


def nemotron_3_super_120b(**overrides) -> NemotronHConfig:
    """NVIDIA-Nemotron-3-Super-120B-A12B as published, whole (88 layers)."""
    return replace(NemotronHConfig(
        pattern="MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"), **overrides)


def nemotron_h_tiny(**overrides) -> NemotronHConfig:
    """Test-size config: one short period, every kind, an MTP module."""
    return replace(NemotronHConfig(
        vocab_size=256, seq_len=64, pattern="MEME*E", mtp_pattern="*E",
        n_layer_published=6, d_model=64, n_head=4, n_kv_head=2, head_dim=16,
        mamba_heads=4, mamba_head_dim=16, mamba_groups=2, ssm_state=16,
        chunk=16, n_experts=32, top_k=4, held_first=8, held_count=8,
        latent=32, d_expert=48, d_shared=96), **overrides)


# --------------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------------- #

def _attn_init(rng, n: int, cfg: NemotronHConfig, out_std: float):
    D, H, KH, hd = cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    k = jax.random.split(rng, 4)

    def normal(key, shape, s):
        return (jax.random.normal(key, shape) * s).astype(cfg.param_dtype)

    return {"wq": normal(k[0], (n, D, H, hd), INIT_STD),
            "wk": normal(k[1], (n, D, KH, hd), INIT_STD),
            "wv": normal(k[2], (n, D, KH, hd), INIT_STD),
            "wo": normal(k[3], (n, H, hd, D), out_std)}


_ATTN_AXES = {"wq": ("layers", "embed", "heads", "kv"),
              "wk": ("layers", "embed", "heads", "kv"),
              "wv": ("layers", "embed", "heads", "kv"),
              "wo": ("layers", "heads", "kv", "embed")}
_ATTN_WEIGHTS = ("wq", "wk", "wv", "wo")


def _layer_init(rng, n: int, kind: str, cfg: NemotronHConfig):
    """``n`` stacked layers of ``kind``: the kind's own tensors and its
    pre-norm ``norm``."""
    # rescale_prenorm_residual: the three out-projections by 1/sqrt(2·layers)
    out_std = INIT_STD / math.sqrt(2 * cfg.n_layer_published)
    if kind == "M":
        p = mamba2.mamba2_init(
            rng, n, cfg.d_model, cfg.mamba_heads, cfg.mamba_head_dim,
            cfg.mamba_groups, cfg.ssm_state, cfg.conv_kernel, INIT_STD,
            out_std, cfg.param_dtype)
    elif kind == "E":
        p = moe.latent_moe_init(
            rng, n, cfg.d_model, cfg.n_experts, cfg.held_count, cfg.latent,
            cfg.d_expert, cfg.d_shared, INIT_STD, out_std, cfg.param_dtype)
    else:
        p = _attn_init(rng, n, cfg, out_std)
    return {**p, "norm": jnp.ones((n, cfg.d_model), cfg.param_dtype)}


def _stack_init(rng, pattern: str, cfg: NemotronHConfig):
    return blocks.init_pattern(rng, pattern, KINDS,
                               partial(_layer_init, cfg=cfg))


def _stack_axes(pattern: str):
    axes = {"M": mamba2.mamba2_logical_axes(),
            "E": moe.latent_moe_logical_axes(), "*": _ATTN_AXES}
    return [{kind: {**axes[kind], "norm": ("layers", "embed")}
             for kind in counts} for counts in blocks.group_counts(pattern)]


def logical_axes(cfg: NemotronHConfig) -> Dict[str, Any]:
    out = {"wte": ("vocab", "embed"), "blocks": _stack_axes(cfg.pattern),
           "final_norm": ("embed",), "lm_head": ("embed", "vocab")}
    if cfg.mtp_pattern:
        out["mtp"] = {"blocks": _stack_axes(cfg.mtp_pattern),
                      "enorm": ("embed",), "hnorm": ("embed",),
                      "eh_proj": (None, "embed")}
    return out


def mesh_rules(cfg: NemotronHConfig, mesh) -> Dict[str, str]:
    """What this config needs of this mesh: no rule beyond the defaults, and
    the refusal of the axes no code here runs over."""
    if mesh.shape.get("pp", 1) > 1:
        raise NotImplementedError(
            "pipeline parallelism is not implemented for the Nemotron-H "
            "family (expert layers under a stage schedule); use a pp=1 mesh")
    if mesh.shape.get("ep", 1) > 1:
        raise NotImplementedError(
            "expert parallelism across chips (ep > 1) is not implemented: "
            "the expert layer computes the experts the config says it holds "
            "and no all-to-all exchanges tokens; use an ep=1 mesh")
    if mesh.shape.get("cp", 1) > 1:
        raise NotImplementedError(
            "context parallelism is not implemented for the Nemotron-H "
            "family (the state-space scan carries its state along the whole "
            "row); use a cp=1 mesh")
    return {}


def init(cfg: NemotronHConfig, rng: jax.Array) -> Dict[str, Any]:
    D, V, pd = cfg.d_model, cfg.vocab_size, cfg.param_dtype
    k = jax.random.split(rng, 5)

    def normal(key, shape):
        return (jax.random.normal(key, shape) * INIT_STD).astype(pd)

    out = {"wte": normal(k[0], (V, D)),
           "blocks": _stack_init(k[1], cfg.pattern, cfg),
           "final_norm": jnp.ones((D,), pd),
           "lm_head": normal(k[2], (D, V))}
    if cfg.mtp_pattern:
        out["mtp"] = {"blocks": _stack_init(k[3], cfg.mtp_pattern, cfg),
                      "enorm": jnp.ones((D,), pd), "hnorm": jnp.ones((D,), pd),
                      "eh_proj": normal(k[4], (2 * D, D))}
    return out


def param_count(cfg: NemotronHConfig) -> int:
    return parts.param_count(lambda: init(cfg, jax.random.PRNGKey(0)))


def decays(params):
    """Which leaves an optimizer's weight decay may touch (optax's ``mask``):
    all but the expert layers' selection biases, which are buffers — no
    gradient reaches them, and a decay must not."""
    return parts.all_but(params, "router_bias")


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #

def _shared_rows(cfg: NemotronHConfig, batch: int, seq: int) -> int:
    """Rows of the sequence the shared expert takes at a time: an MLP with
    one hidden tensor of d_shared."""
    return parts.mlp_rows(batch, seq, cfg.d_model, cfg.d_shared,
                          jnp.dtype(cfg.dtype).itemsize, hidden_tensors=3)


@jax.named_scope(scopes.BLOCK)
def _layer(x, p, cfg: NemotronHConfig, kind: str, aux: Optional[str] = None):
    """One layer of ``kind``: x + f_kind(RMSNorm(x)), x [B, S, D]. With
    ``aux`` the result is (x, aux's value), None for a layer that is no
    expert layer: ``"load"`` — what the batch sends the held experts, as the
    dispatch that runs the passes has it (moe.routed_experts; the training
    forward's) —, ``"balance"`` (set-up's forward, balance_router_bias) — an
    expert layer first balances its selection bias on this input; the bias
    and what the input then sends the held experts (moe.held_load)."""
    out = None
    weights = {"M": mamba2.MATMUL_WEIGHTS, "E": moe.LATENT_MOE_MATMUL_WEIGHTS,
               "*": _ATTN_WEIGHTS}[kind]
    p = {**p, **parts.cast_in_the_loop(p, x, cfg.dtype, weights)}
    with jax.named_scope(scopes.LN1):
        u = parts.rmsnorm(x, p["norm"], cfg.rms_eps)
    if kind == "M":
        y = mamba2.mamba2_mixer(
            u, p, heads=cfg.mamba_heads, head_dim=cfg.mamba_head_dim,
            groups=cfg.mamba_groups, state=cfg.ssm_state, chunk=cfg.chunk,
            eps=cfg.rms_eps)
    elif kind == "E":
        routing = dict(top_k=cfg.top_k, held=cfg.held,
                       scaling=cfg.routed_scaling)
        if aux == "balance":
            ut = u.reshape(-1, u.shape[-1])
            bias = moe.balance_bias(ut, p["router_w"], p["router_bias"],
                                    cfg.top_k)
            p = {**p, "router_bias": bias}
            out = {"router_bias": bias, **moe.held_load(ut, p, **routing)}
        with jax.named_scope(scopes.MOE):
            y, load = moe.latent_moe(
                u, p, **routing,
                shared_rows=_shared_rows(cfg, x.shape[0], x.shape[1]))
        if aux == "load":
            out = load
    else:
        with jax.named_scope(scopes.QKV):
            q = checkpoint_name(
                jnp.einsum("bsd,dhk->bhsk", u, p["wq"]), scopes.RES_Q)
            k = checkpoint_name(
                jnp.einsum("bsd,dhk->bhsk", u, p["wk"]), scopes.RES_K)
            v = checkpoint_name(
                jnp.einsum("bsd,dhk->bhsk", u, p["wv"]), scopes.RES_V)
        with jax.named_scope(scopes.ATTN):
            o = parts.causal_attention(q, k, v, cfg.attention_impl)
        with jax.named_scope(scopes.PROJ):
            y = jnp.einsum("bhsk,hkd->bsd", o, p["wo"],
                           preferred_element_type=jnp.float32)
    x = parts.residual_add(x, y)
    return (x, out) if aux else x


def kind_shards(cfg: NemotronHConfig, global_batch: int, seq: int, mesh
                ) -> Tuple[parts.BlockShard, Dict[str, blocks.KindShard]]:
    """This config's layers on one chip of ``mesh``, for the remat rule: the
    model's shard (stream, head, rows at a time) and, a kind, how often it is
    applied (trunk and MTP module together), what a layer of it may keep,
    what its backward holds at once and what its weight gradients take —
    each from the kind's own shapes."""
    a = jnp.dtype(cfg.dtype).itemsize
    D = cfg.d_model
    base = parts.shard_block(parts.BlockShard(
        batch=global_batch, seq=seq, d_model=D, heads=cfg.n_head,
        head_dim=cfg.head_dim, d_ff=cfg.d_shared, vocab=cfg.vocab_size,
        dtype_bytes=a,
        flash=parts.is_flash(cfg.attention_impl, mesh),
        dense_mlp=False, kv_heads=cfg.n_kv_head,
        head_rows=parts.head_rows(global_batch, seq, cfg.vocab_size, 1),
        mlp_rows=_shared_rows(cfg, global_batch, seq), cast_in_loop=True,
    ), mesh)
    tokens = base.batch * base.seq
    C = blocks.RematCandidate
    counts = {kind: cfg.pattern.count(kind) + cfg.mtp_pattern.count(kind)
              for kind in KINDS}

    # M: the three projections, the chunk states, the scan's output
    H, P, G, N = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups,
                  cfg.ssm_state)
    inner, conv_dim = H * P, H * P + 2 * G * N
    Q = min(cfg.chunk, seq)
    chunks = base.batch * -(-base.seq // Q)
    scan_flops = 2 * tokens * (Q * (G * N + H * P) + 2 * H * P * N)
    mamba = blocks.KindShard(counts["M"], (
        C((scopes.RES_MAMBA_Z,), tokens * inner * a, 2 * tokens * D * inner),
        C((scopes.RES_MAMBA_XBC,), tokens * conv_dim * a,
          2 * tokens * D * conv_dim),
        C((scopes.RES_MAMBA_DT,), tokens * H * 4, 2 * tokens * D * parts.MXU),
        C((scopes.RES_SSD_STATES,), chunks * H * P * N * 4,
          2 * tokens * H * P * N),
        C((scopes.RES_SSD_Y,), tokens * inner * a, scan_flops),
    ), a * tokens * (4 * D + 4 * inner + 2 * conv_dim)
        # the scan's backward kernel reads the chunk states and y's float32
        # gradient and the recompute wrote y in float32 beside them; a chunk's
        # [Q, Q] tiles are the kernels', in VMEM (ops/mamba2.py, PR 41)
        + chunks * H * P * N * 4 + 2 * tokens * inner * 4
        + 2 * a * D * (2 * inner + conv_dim))

    # E: the latent input; what the routing decided (parts.routing_candidates);
    # the shared expert's hidden where it is not chunked
    rows = moe.row_buffer(tokens, cfg.n_experts, cfg.top_k, cfg.held_count)
    experts_kept = [
        C((scopes.RES_MOE_LATENT,), tokens * cfg.latent * a,
          2 * tokens * D * cfg.latent),
        *parts.routing_candidates(tokens, D, cfg.n_experts, cfg.top_k,
                                  cfg.held_count)]
    if base.mlp_rows in (0, base.seq):
        experts_kept.append(C((scopes.RES_MOE_SHARED_HIDDEN,),
                              tokens * base.d_ff * a,
                              2 * tokens * D * base.d_ff))
    expert_params = (2 * D * cfg.latent + 2 * D * base.d_ff
                     + 2 * cfg.held_count * cfg.latent * cfg.d_expert)
    # the routed experts' backward (the latents, three [tokens, n_experts]
    # tensors of the routing, one pass's rows) and the shared expert's (its
    # rows' hidden tensors) are never live together: the larger counts. Summed
    # they stood 0.5 GiB over what the compiled step holds in an expert
    # layer's backward (PERF.md §6, PR 42)
    routed = (a * tokens * 3 * cfg.latent + tokens * cfg.n_experts * 12
              + a * rows * (2 * cfg.latent + 3 * cfg.d_expert))
    shared = a * base.batch * (base.mlp_rows or base.seq) * 3 * base.d_ff
    experts = blocks.KindShard(counts["E"], tuple(experts_kept), (
        a * tokens * 4 * D + max(routed, shared) + 2 * a * expert_params))

    # *: q, k, v and the flash kernel's outputs (no MLP half, no mid-stream)
    attn = blocks.KindShard(counts["*"], tuple(
        c for c in parts.remat_candidates(base) if c.names != (scopes.RES_MID,)
    ), a * tokens * (4 * D + 4 * base.heads * base.head_dim)
        + 2 * a * D * 2 * (base.heads + base.kv_heads) * base.head_dim)
    kinds = {"M": mamba, "E": experts, "*": attn}
    return base, blocks.with_grad_bytes(
        {k: v for k, v in kinds.items() if v.applications},
        partial(_layer_init, cfg=cfg), mesh)


def _block_fns(cfg: NemotronHConfig, batch: int, seq: int,
               aux: Optional[str] = None):
    from ray_tpu.parallel import mesh as mesh_lib

    base, kinds = kind_shards(cfg, batch, seq, mesh_lib.current_mesh())
    for pattern in filter(None, (cfg.pattern, cfg.mtp_pattern)):
        blocks.record_layer_pattern(pattern)
    return blocks.checkpoint_kinds(
        {kind: partial(_layer, cfg=cfg, kind=kind, aux=aux) for kind in kinds},
        cfg.remat, base, kinds,
        blocks.pattern_groups(cfg.pattern)
        + blocks.pattern_groups(cfg.mtp_pattern))


def _hidden(params, tokens, targets, cfg: NemotronHConfig,
            aux: Optional[str] = None):
    """tokens [B, S] → (the trunk's stream before the final norm, the MTP
    module's — None without one —, the MTP targets, and with ``aux`` the
    layers' (_layer says what; blocks.run_pattern's auxes): the trunk's,
    then the MTP module's)."""
    B, S = tokens.shape
    wte = params["wte"].astype(cfg.dtype)
    with jax.named_scope(scopes.EMBED):
        x = wte[tokens]
    if aux == "balance":     # set-up's forward: no backward, no checkpoint
        block_fns = {kind: partial(_layer, cfg=cfg, kind=kind, aux=aux)
                     for kind in KINDS}
    else:
        block_fns = _block_fns(cfg, B, S, aux)

    def run(pattern, x, stacks):
        out = blocks.run_pattern(block_fns, pattern, x, stacks,
                                 with_aux=bool(aux))
        return out if aux else (out, None)

    x, aux = run(cfg.pattern, x, params["blocks"])
    if not cfg.mtp_pattern:
        return x, None, None, (aux, None)
    with jax.named_scope(scopes.MTP):
        mtp = params["mtp"]
        h, mtp_targets = parts.mtp_join(x, targets, wte, mtp["enorm"],
                                        mtp["hnorm"], mtp["eh_proj"],
                                        cfg.rms_eps)
        h, mtp_aux = run(cfg.mtp_pattern, h, mtp["blocks"])
    return x, h, mtp_targets, (aux, mtp_aux)


def _final_norm(x, params, cfg):
    with jax.named_scope(scopes.LN_F):
        return parts.rmsnorm(x, params["final_norm"], cfg.rms_eps)


def forward(params, tokens, cfg: NemotronHConfig) -> jax.Array:
    """tokens [B, S] int32 → the trunk's logits [B, S, vocab_size]."""
    x, *_ = _hidden(params, tokens, None, replace(cfg, mtp_pattern=""))
    return jnp.einsum("bsd,dv->bsv", _final_norm(x, params, cfg),
                      params["lm_head"].astype(cfg.dtype))


def _losses(params, tokens, targets, cfg: NemotronHConfig,
            aux: Optional[str] = None):
    """losses, and _hidden's auxes of ``aux``."""
    x, h, mtp_targets, auxes = _hidden(params, tokens, targets, cfg, aux)
    trunk = parts.lm_head_loss(_final_norm(x, params, cfg), targets,
                               params["lm_head"], cfg.dtype)
    if h is None:
        return trunk, jnp.zeros((), jnp.float32), auxes
    with jax.named_scope(scopes.MTP):
        return trunk, parts.lm_head_loss(_final_norm(h, params, cfg),
                                         mtp_targets, params["lm_head"],
                                         cfg.dtype), auxes


def losses(params, tokens, targets, cfg: NemotronHConfig):
    """(the trunk's mean cross-entropy, the MTP module's or 0.0)."""
    return _losses(params, tokens, targets, cfg)[:2]


def loss_fn(params, tokens, targets, cfg: NemotronHConfig,
            counters: bool = False):
    """CE_trunk + mtp_loss_weight · CE_mtp over targets >= 0 ([B, S] int32,
    the next token). With ``counters`` (what step_counters offers a step
    factory: the aux of its ``value_and_grad``) the result is (the loss, what
    the batch sent each expert layer's held experts: int32 [expert layers,
    fields], the trunk's layers and then the MTP module's)."""
    trunk, mtp, (aux, mtp_aux) = _losses(
        params, tokens, targets, cfg, "load" if counters else None)
    loss = trunk + cfg.mtp_loss_weight * mtp
    if not counters:
        return loss
    return loss, blocks.packed_aux(aux + (mtp_aux or []),
                                   scopes.STEP_EXPERT_LOAD_ARGS)


def step_counters(cfg: NemotronHConfig) -> Optional[blocks.StepCounters]:
    """What ``loss_fn(..., counters=True)`` hands out of a step, or None for
    a pattern without an expert layer. A layer's id is ``model/expert_load``'s
    ``layer``: its place among the expert layers, the MTP module's after the
    trunk's."""
    return parts.expert_step_counters(
        range((cfg.pattern + cfg.mtp_pattern).count("E")), cfg.n_experts,
        cfg.top_k, cfg.held)


def flops_per_token(cfg: NemotronHConfig) -> float:
    """Forward + backward operations one trained token REQUIRES here: 6 per
    matmul parameter the token meets (the routed experts by the pairs a token
    is expected to land on held ones, top_k · held / n_experts a layer; the
    embedding is a gather), and by shape three times the forward's attention
    (two matmuls over the causal half) and state-space scan (the chunk's two
    quadratic products over its causal half, the state's two products)."""
    D, S = cfg.d_model, cfg.seq_len
    H, P, G, N = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups,
                  cfg.ssm_state)
    Q = min(cfg.chunk, S)
    inner = H * P
    per = {
        "M": (D * (2 * inner + 2 * G * N + H) + inner * D,
              Q / 2 * (G * N + inner) + 2 * inner * N),
        "E": (D * cfg.n_experts + 2 * D * cfg.latent + 2 * D * cfg.d_shared
              + cfg.top_k * cfg.held_count / cfg.n_experts
              * 2 * cfg.latent * cfg.d_expert, 0.0),
        "*": (2 * D * (cfg.n_head + cfg.n_kv_head) * cfg.head_dim,
              2 * cfg.n_head * cfg.head_dim * (S + 1) / 2),
    }
    layers = cfg.pattern + cfg.mtp_pattern
    matmul = sum(per[k][0] for k in layers) + D * cfg.vocab_size
    shaped = sum(per[k][1] for k in layers)
    if cfg.mtp_pattern:
        matmul += 2 * D * D + D * cfg.vocab_size
    return 6.0 * (matmul + shaped)


# --------------------------------------------------------------------------- #
# The selection bias, balanced at set-up; what a batch sends the held experts
# --------------------------------------------------------------------------- #

def balance_router_bias(params, tokens, targets, cfg: NemotronHConfig):
    """(``params`` with every expert layer's selection bias balanced on this
    batch, what the batch then sends the experts held here). The bias's
    between-step update is not part of the step, so a run starts from a bias
    that something balanced: moe.balance_bias, layer by layer in one forward
    of its own (a layer's input is what the balanced layers before it give),
    the weights held. The loads are the ``model/expert_load`` events
    (tracing/names.EXPERT_LOAD_ARGS), recorded here, the trunk's expert
    layers first, then the MTP module's. For set-up, on the first batch."""
    trunk, mtp = jax.device_get(jax.jit(
        lambda p, tok, tgt: _hidden(p, tok, tgt, cfg, "balance")[3])(
        params, tokens, targets))
    loads = blocks.aux_by_layer(
        blocks.pattern_groups(cfg.pattern)
        + blocks.pattern_groups(cfg.mtp_pattern), trunk + (mtp or []))
    biases = iter([load.pop("router_bias") for load in loads])
    params = {**params, "blocks": blocks.with_leaf(
        cfg.pattern, params["blocks"], "router_bias", biases)}
    if cfg.mtp_pattern:
        params["mtp"] = {**params["mtp"], "blocks": blocks.with_leaf(
            cfg.mtp_pattern, params["mtp"]["blocks"], "router_bias", biases)}
    return params, moe.record_expert_loads(range(len(loads)), loads)
