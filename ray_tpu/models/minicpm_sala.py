"""MiniCPM-SALA-family decoder in pure JAX: a PATTERN of two attention kinds.

Fourth model family beside GPT-2, LLaMA and Nemotron-H. Every layer is a
mixer AND a SwiGLU MLP, each a µP-scaled residual,
``x ← x + (scale_depth / √n_layer_published) · f(RMSNorm(x))``; the mixer's
kind is read off a pattern string, one character a layer (the published
``mixer_types``):

- ``L`` — ``lightning-attn``: decayed linear attention. q, k, v projections,
  per-head RMSNorm of q and k with learned gains, RoPE on both,
  ``s_t = λ_h s_{t−1} + k_tᵀ v_t``, ``o_t = (q_t / √hd) s_t`` with
  ``λ_h = exp(−2^{−8(h+1)/H})`` for the head's PUBLISHED index h of H, a
  per-head RMSNorm of o with a learned gain, a sigmoid output gate, the
  out-projection. The recurrence IS ops/mamba2.ssd_scan's
  (``x = v, Δ = 1, A = −slope_h, B = k, C = q / √hd``, one head a group,
  P = N = hd): the same Pallas kernel pair the Mamba-2 mixer runs, state and
  decays in float32.
- ``S`` — ``minicpm4``: grouped-query attention with QK-norm, NO RoPE, a
  sigmoid output gate. Rows of at most ``dense_len`` tokens take plain causal
  attention (parts.causal_attention: the flash kernels); longer rows take
  ops/sparse_attention.py — every token is given ``top_k`` blocks of keys (the
  first, its window's, and the highest-scoring others by compressed-key
  scores summed over its group's heads) and attends over those alone.

The embedding's output is scaled by ``scale_emb``; the head sees
``RMSNorm(x) ÷ (d_model / dim_model_base)``. It runs on the shared machinery:
``blocks.run_pattern`` / ``blocks.checkpoint_kinds`` (ONE remat rule over
both kinds' applications), parts' RMSNorm, RoPE, residual add, weight cast
inside the loop, the rows an MLP and a head take at a time and the chunked
head + loss; tracing/names.py's scopes and residuals.

The config states the chip's SHARE of a deployment beside the published
sizes: which of the lightning heads, how many query heads on how many
key-value heads, how many vocabulary rows are held here. What absent heads
would have added is left out (no code stands in for absent chips): QK-norm
and the output norm are per head, the selection sums over ONE key-value
head's group, the gates are elementwise and the out-projections linear, so
the shares' mixer outputs add up to the whole layer's
(tests/test_minicpm_sala.py).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import blocks, parts
from ray_tpu.ops import mamba2, sparse_attention
from ray_tpu.ops.attention import record_decision
from ray_tpu.ops.sparse_attention import SparseSizes
from ray_tpu.tracing import names as scopes

KINDS = "LS"         # lightning linear attention, block-sparse attention
INIT_STD = 0.02      # every matrix; the out-projections rescaled (init)


@dataclass(frozen=True)
class MiniCPMSALAConfig:
    vocab_size: int = 73448           # rows of the embedding / head held here
    seq_len: int = 16384
    pattern: str = "SLLLLLLLLSLLLLLLSSLLLLSLLLLLLSSS"   # one character a layer
    n_layer_published: int = 32       # the residuals' and the init's scale
    d_model: int = 4096
    d_ff: int = 16384                 # SwiGLU hidden
    head_dim: int = 128
    # lightning: heads held here, the first one's published index, and how
    # many the model has (the decay slopes go by the published index)
    lightning_heads: int = 32
    lightning_head_first: int = 0
    lightning_heads_published: int = 32
    chunk: int = 128                  # the scan's chunk
    rope_theta: float = 10000.0
    # sparse: query heads held here on n_kv_head key-value heads
    n_head: int = 32
    n_kv_head: int = 2
    sparse: SparseSizes = SparseSizes()
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rms_eps: float = 1e-6
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
        if not (0 <= self.lightning_head_first
                <= self.lightning_heads_published - self.lightning_heads):
            raise ValueError(
                f"lightning heads {self.lightning_head_first}…+"
                f"{self.lightning_heads} are not among "
                f"{self.lightning_heads_published}")

    @property
    def n_layer(self) -> int:
        return len(self.pattern)

    @property
    def padded_vocab(self) -> int:
        return parts.round_up(self.vocab_size, 128)

    @property
    def depth_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.n_layer_published)

    @property
    def is_sparse(self) -> bool:
        """A row of seq_len tokens takes the block-sparse branch."""
        return self.seq_len > self.sparse.dense_len


def minicpm_sala_tiny(**overrides) -> MiniCPMSALAConfig:
    """Test-size config: one period, both kinds, rows past a tiny dense_len."""
    return replace(MiniCPMSALAConfig(
        vocab_size=250, seq_len=128, pattern="LLLS", n_layer_published=4,
        d_model=64, d_ff=128, head_dim=16, lightning_heads=4,
        lightning_heads_published=4, chunk=32, n_head=4, n_kv_head=2,
        sparse=SparseSizes(block=16, kernel=8, stride=4, top_k=5,
                           init_blocks=1, window=24, dense_len=64)),
        **overrides)


# --------------------------------------------------------------------------- #
# Parameters
# --------------------------------------------------------------------------- #

_MATMUL_WEIGHTS = ("wq", "wk", "wv", "wg", "wo", "w_gate", "w_up", "w_down")


def _heads(cfg: MiniCPMSALAConfig, kind: str) -> Tuple[int, int]:
    """(query heads, key-value heads) a layer of ``kind`` holds here."""
    if kind == "L":
        return cfg.lightning_heads, cfg.lightning_heads
    return cfg.n_head, cfg.n_kv_head


def _layer_init(rng, n: int, kind: str, cfg: MiniCPMSALAConfig):
    D, F, hd, pd = cfg.d_model, cfg.d_ff, cfg.head_dim, cfg.param_dtype
    H, KH = _heads(cfg, kind)
    # rescale_prenorm_residual: the two out-projections by 1/sqrt(2·layers)
    out_std = INIT_STD / math.sqrt(2 * cfg.n_layer_published)
    k = iter(jax.random.split(rng, 8))

    def normal(shape, s=INIT_STD):
        return (jax.random.normal(next(k), shape) * s).astype(pd)

    p = {"norm": jnp.ones((n, D), pd),
         "wq": normal((n, D, H, hd)), "wk": normal((n, D, KH, hd)),
         "wv": normal((n, D, KH, hd)), "wg": normal((n, D, H, hd)),
         "wo": normal((n, H, hd, D), out_std),
         "q_norm": jnp.ones((n, hd), pd), "k_norm": jnp.ones((n, hd), pd),
         "mlp_norm": jnp.ones((n, D), pd),
         "w_gate": normal((n, D, F)), "w_up": normal((n, D, F)),
         "w_down": normal((n, F, D), out_std)}
    if kind == "L":
        p["o_norm"] = jnp.ones((n, H, hd), pd)
    return p


_HEAD_AXES = ("layers", "embed", "heads", "kv")
_LAYER_AXES = {
    "norm": ("layers", "embed"), "wq": _HEAD_AXES, "wk": _HEAD_AXES,
    "wv": _HEAD_AXES, "wg": _HEAD_AXES,
    "wo": ("layers", "heads", "kv", "embed"),
    "q_norm": ("layers", None), "k_norm": ("layers", None),
    "o_norm": ("layers", "heads", None), "mlp_norm": ("layers", "embed"),
    "w_gate": ("layers", "embed", "mlp"), "w_up": ("layers", "embed", "mlp"),
    "w_down": ("layers", "mlp", "embed"),
}


def logical_axes(cfg: MiniCPMSALAConfig) -> Dict[str, Any]:
    def axes(kind):
        return {k: v for k, v in _LAYER_AXES.items()
                if kind == "L" or k != "o_norm"}

    return {"wte": ("vocab", "embed"),
            "blocks": [{kind: axes(kind) for kind in counts}
                       for counts in blocks.group_counts(cfg.pattern)],
            "final_norm": ("embed",), "lm_head": ("embed", "vocab")}


def mesh_rules(cfg: MiniCPMSALAConfig, mesh) -> Dict[str, str]:
    """What this config needs of this mesh: no rule beyond the defaults, and
    the refusal of the axes no code here runs over."""
    for axis, why in (
            ("pp", "a pattern of kinds under a stage schedule"),
            ("cp", "the linear-attention state and the block selection run "
                   "along the whole row")):
        if mesh.shape.get(axis, 1) > 1:
            raise NotImplementedError(
                f"{axis} > 1 is not implemented for the MiniCPM-SALA family "
                f"({why}); use a {axis}=1 mesh")
    return {}


def init(cfg: MiniCPMSALAConfig, rng: jax.Array) -> Dict[str, Any]:
    D, V, pd = cfg.d_model, cfg.padded_vocab, cfg.param_dtype
    k = jax.random.split(rng, 3)

    def normal(key, shape):
        return (jax.random.normal(key, shape) * INIT_STD).astype(pd)

    return {"wte": normal(k[0], (V, D)),
            "blocks": blocks.init_pattern(k[1], cfg.pattern, KINDS,
                                          partial(_layer_init, cfg=cfg)),
            "final_norm": jnp.ones((D,), pd),
            "lm_head": normal(k[2], (D, V))}


def param_count(cfg: MiniCPMSALAConfig) -> int:
    return parts.param_count(lambda: init(cfg, jax.random.PRNGKey(0)))


# --------------------------------------------------------------------------- #
# Forward
# --------------------------------------------------------------------------- #

_selections: Dict[tuple, Dict[str, Any]] = {}


def sparse_selection_decisions() -> List[Dict[str, Any]]:
    """Every distinct selection this process has traced a sparse layer with,
    as the ``model/sparse_selection`` events carry them."""
    return list(_selections.values())


def lightning_slopes(cfg: MiniCPMSALAConfig) -> jax.Array:
    """The held heads' decay slopes, float32 [lightning_heads]: head h of H
    published ones forgets by ``exp(−2^{−8(h+1)/H})`` a token."""
    h = cfg.lightning_head_first + jnp.arange(cfg.lightning_heads,
                                              dtype=jnp.float32)
    return jnp.exp2(-8.0 * (h + 1.0) / cfg.lightning_heads_published)


def _head_norm(x, g, cfg: MiniCPMSALAConfig):
    """RMSNorm over the last (head) dim, gains ``g`` broadcast from the right."""
    return parts.rmsnorm(x, g, cfg.rms_eps)


def _residual(x, y, cfg: MiniCPMSALAConfig):
    """x + depth_scale · y in float32 (y a matmul's float32 accumulator)."""
    return parts.residual_add(x, cfg.depth_scale * y.astype(jnp.float32))


def _project(u, w, name: str):
    return checkpoint_name(jnp.einsum("bsd,dhk->bhsk", u, w), name)


@jax.named_scope(scopes.LIGHTNING_ATTN)
def _lightning(u, p, cfg: MiniCPMSALAConfig):
    """u [B, S, D] (normed) → the mixer's output [B, S, D] float32."""
    hd = cfg.head_dim
    positions = jnp.arange(u.shape[1])
    with jax.named_scope(scopes.QKV):
        # named after the norm and the rotation: a kept q or k has both. What
        # comes back through the norm is made once, in front of the
        # projection's two backward products (parts.made_once)
        q = checkpoint_name(parts.rope(_head_norm(parts.cotangent_made_once(
            jnp.einsum("bsd,dhk->bhsk", u, p["wq"])), p["q_norm"], cfg),
            positions, cfg.rope_theta), scopes.RES_Q)
        k = checkpoint_name(parts.rope(_head_norm(parts.cotangent_made_once(
            jnp.einsum("bsd,dhk->bhsk", u, p["wk"])), p["k_norm"], cfg),
            positions, cfg.rope_theta), scopes.RES_K)
        # v and the gate as the scan has them, [B, S, H, hd]
        v = checkpoint_name(jnp.einsum("bsd,dhk->bshk", u, p["wv"]),
                            scopes.RES_V)
        gate = checkpoint_name(jnp.einsum("bsd,dhk->bshk", u, p["wg"]),
                               scopes.RES_SALA_GATE)
    # one head a group, P = N = hd
    q, k = jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)
    scale = jnp.asarray(1.0 / math.sqrt(hd), q.dtype)
    y = mamba2.ssd_scan(v, jnp.ones(v.shape[:3], jnp.float32),
                        -lightning_slopes(cfg), k, q * scale, cfg.chunk)
    # the scan's kernels on one side, the output norm and gate on the other:
    # y and its gradient cross as they are; so does the gated output, into
    # the out-projection
    y = checkpoint_name(parts.made_once(y).astype(u.dtype),
                        scopes.RES_LIGHTNING_Y)
    o = _head_norm(y, p["o_norm"], cfg)                      # [B, S, H, hd]
    o = parts.made_once(
        o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype))
    with jax.named_scope(scopes.PROJ):
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"],
                          preferred_element_type=jnp.float32)


def _record_selection(cfg: MiniCPMSALAConfig, rows: int, S: int) -> None:
    z = cfg.sparse
    sparse = S > z.dense_len
    record_decision(_selections, scopes.SPARSE_SELECTION, dict(zip(
        scopes.SPARSE_SELECTION_ARGS,
        (rows, S, S // z.block, z.top_k, sparse_attention.window_blocks(z),
         z.init_blocks, z.dense_len, "sparse" if sparse else "dense",
         sparse_attention.kept_share(S, z) if sparse else 1.0))))


@jax.named_scope(scopes.SPARSE_ATTENTION)
def _sparse(u, p, cfg: MiniCPMSALAConfig):
    """u [B, S, D] (normed) → (the mixer's output [B, S, D] float32, the
    blocks each token was given — None on the dense branch)."""
    B, S, _ = u.shape
    with jax.named_scope(scopes.QKV):
        q = checkpoint_name(_head_norm(
            jnp.einsum("bsd,dhk->bhsk", u, p["wq"]), p["q_norm"], cfg),
            scopes.RES_Q)
        k = checkpoint_name(_head_norm(
            jnp.einsum("bsd,dhk->bhsk", u, p["wk"]), p["k_norm"], cfg),
            scopes.RES_K)
        v = _project(u, p["wv"], scopes.RES_V)
        gate = _project(u, p["wg"], scopes.RES_SALA_GATE)
    _record_selection(cfg, B * cfg.n_kv_head, S)
    ids = None
    if S > cfg.sparse.dense_len:
        o, ids = sparse_attention.sparse_attention(q, k, v, cfg.sparse)
    else:
        with jax.named_scope(scopes.ATTN):
            o = parts.causal_attention(q, k, v, cfg.attention_impl)
    o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
    with jax.named_scope(scopes.PROJ):
        y = jnp.einsum("bhsk,hkd->bsd", o, p["wo"],
                       preferred_element_type=jnp.float32)
    return y, ids


def mixer(u, p, cfg: MiniCPMSALAConfig, kind: str) -> jax.Array:
    """The mixer of ``kind`` alone on a normed input u [B, S, D] with one
    layer's tensors ``p`` (the matmul weights in u's dtype) → [B, S, D]
    float32: what a chip's share of heads adds to the layer's residual."""
    return _lightning(u, p, cfg) if kind == "L" else _sparse(u, p, cfg)[0]


def _swiglu(x, p, cfg: MiniCPMSALAConfig):
    """x + depth_scale · down(silu(gate(h)) · up(h)), h = norm(x), on
    [B, rows, D]."""
    with jax.named_scope(scopes.LN2):
        h = parts.rmsnorm(x, p["mlp_norm"], cfg.rms_eps)
    y = parts.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    with jax.named_scope(scopes.MLP):
        return _residual(x, y, cfg)


def _mlp(x, p, cfg: MiniCPMSALAConfig):
    """The layer's second half, norm and all, in chunks of the sequence
    where parts.mlp_rows says so — as the llama block's, and why
    (models/llama.py)."""
    return parts.in_row_chunks(
        partial(_swiglu, p=p, cfg=cfg), x,
        parts.mlp_rows(*x.shape, cfg.d_ff, x.dtype.itemsize))


@jax.named_scope(scopes.BLOCK)
def _layer(x, p, cfg: MiniCPMSALAConfig, kind: str, with_ids: bool = False):
    """One layer of ``kind``, x [B, S, D]: the mixer's residual, then the
    MLP's. With ``with_ids`` the result is (x, the sparse layer's chosen
    block ids or None)."""
    p = {**p, **parts.cast_in_the_loop(p, x, cfg.dtype, _MATMUL_WEIGHTS)}
    with jax.named_scope(scopes.LN1):
        u = parts.rmsnorm(x, p["norm"], cfg.rms_eps)
    if kind == "L":
        y, ids = _lightning(u, p, cfg), None
    else:
        y, ids = _sparse(u, p, cfg)
    x = checkpoint_name(_residual(x, y, cfg), scopes.RES_MID)
    x = _mlp(x, p, cfg)
    return (x, ids) if with_ids else x


def kind_shards(cfg: MiniCPMSALAConfig, global_batch: int, seq: int, mesh
                ) -> Tuple[parts.BlockShard, Dict[str, blocks.KindShard]]:
    """This config's layers on one chip of ``mesh``, for the remat rule: the
    model's shard (stream, head, rows at a time) and, a kind, how often it is
    applied, what a layer of it may keep, what its backward holds at once —
    the attention + MLP block's set (parts.block_working_set, on the kind's
    own heads) and what the kind adds to it — and its weight gradients."""
    a = jnp.dtype(cfg.dtype).itemsize
    D, hd, z = cfg.d_model, cfg.head_dim, cfg.sparse
    sparse = seq > z.dense_len

    def shard(kind):
        H, KH = _heads(cfg, kind)
        return parts.shard_block(parts.BlockShard(
            batch=global_batch, seq=seq, d_model=D, heads=H, head_dim=hd,
            d_ff=cfg.d_ff, vocab=cfg.padded_vocab, dtype_bytes=a,
            flash=kind == "S" and not sparse
            and parts.is_flash(cfg.attention_impl, mesh),
            dense_mlp=True, kv_heads=KH,
            mlp_hidden=(scopes.RES_MLP_GATE, scopes.RES_MLP_UP),
            head_rows=parts.head_rows(global_batch, seq, cfg.padded_vocab, 1),
            mlp_rows=parts.mlp_rows(global_batch, seq, D, cfg.d_ff, a),
            cast_in_loop=True), mesh)

    C = blocks.RematCandidate
    kinds = {}
    for kind in dict.fromkeys(cfg.pattern):
        s = shard(kind)
        tokens, width = s.batch * s.seq, s.heads * hd
        kept = parts.remat_candidates(s) + [
            C((scopes.RES_SALA_GATE,), tokens * width * a,
              2 * tokens * D * width)]
        # beside the block's set: the gate, the gated output and the weights
        # of the gate's projection, cast
        extra = a * (2 * tokens * width + 2 * D * width)
        if kind == "L":
            Q = min(cfg.chunk, seq)
            chunks = s.batch * -(-s.seq // Q)
            scan = 2 * tokens * s.heads * (Q * 2 * hd + 2 * hd * hd)
            kept += [C((scopes.RES_SSD_STATES,), chunks * s.heads * hd * hd * 4,
                       2 * tokens * s.heads * hd * hd),
                     C((scopes.RES_LIGHTNING_Y,), tokens * width * a, scan)]
            # the scan's backward reads the chunk states and y's float32
            # gradient beside the float32 y the recompute wrote
            extra += chunks * s.heads * hd * hd * 4 + 2 * tokens * width * 4
        elif sparse:
            top, NB = min(z.top_k, seq // z.block), seq // z.block
            n_c = (seq - z.kernel) // z.stride + 1
            given = min(z.top_k * z.block, seq)
            kept += [C((scopes.RES_SPARSE_IDS,),
                       tokens * s.kv_heads * top * 4,
                       6 * 2 * tokens * s.heads * n_c * hd),
                     C((scopes.RES_SPARSE_O, scopes.RES_SPARSE_LSE),
                       tokens * s.heads * (hd * a + 4),
                       2 * 2 * tokens * s.heads * given * hd)]
            # who-was-given-what as the kernels take it, the ids, the
            # kernel's statistics
            extra += tokens * s.kv_heads * (NB * a + top * 4) \
                + 2 * tokens * s.heads * 4
        kinds[kind] = blocks.KindShard(
            cfg.pattern.count(kind), tuple(kept),
            parts.block_working_set(s) + extra)
    return shard(cfg.pattern[0]), blocks.with_grad_bytes(
        kinds, partial(_layer_init, cfg=cfg), mesh)


def _block_fns(cfg: MiniCPMSALAConfig, batch: int, seq: int):
    from ray_tpu.parallel import mesh as mesh_lib

    base, kinds = kind_shards(cfg, batch, seq, mesh_lib.current_mesh())
    blocks.record_layer_pattern(cfg.pattern)
    return blocks.checkpoint_kinds(
        {kind: partial(_layer, cfg=cfg, kind=kind) for kind in kinds},
        cfg.remat, base, kinds, blocks.pattern_groups(cfg.pattern))


def _trunk(params, tokens, cfg: MiniCPMSALAConfig, with_ids: bool = False):
    """tokens [B, S] int32 → the head's input [B, S, D] (and, with
    ``with_ids``, blocks.run_pattern's aux: each layer's chosen ids or None)."""
    B, S = tokens.shape
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens] * jnp.asarray(
            cfg.scale_emb, cfg.dtype)
    if with_ids:        # a forward for a check: no backward, no checkpoint
        fns = {kind: partial(_layer, cfg=cfg, kind=kind, with_ids=True)
               for kind in KINDS}
    else:
        fns = _block_fns(cfg, B, S)
    out = blocks.run_pattern(fns, cfg.pattern, x, params["blocks"],
                             with_aux=with_ids)
    x, aux = out if with_ids else (out, None)
    with jax.named_scope(scopes.LN_F):
        x = parts.rmsnorm(x, params["final_norm"], cfg.rms_eps)
        x = x * jnp.asarray(cfg.dim_model_base / cfg.d_model, x.dtype)
    return (x, aux) if with_ids else x


def forward(params, tokens, cfg: MiniCPMSALAConfig) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, padded_vocab]."""
    x = _trunk(params, tokens, cfg)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype))


def loss_fn(params, tokens, targets, cfg: MiniCPMSALAConfig) -> jax.Array:
    """Mean cross-entropy over targets >= 0 ([B, S] int32, the next token)."""
    x = _trunk(params, tokens, cfg)
    return parts.lm_head_loss(x, targets, params["lm_head"], cfg.dtype)


def chosen_blocks(params, tokens, cfg: MiniCPMSALAConfig) -> List[jax.Array]:
    """The key blocks each token of ``tokens`` [B, S] is given in each sparse
    layer, in the layers' order: int32 [B, n_kv_head, S, top] a layer (none
    where the rows are short enough for plain attention). What a reference is
    told, so that a tie rounding flipped is not read as a wrong model."""
    return blocks.aux_by_layer(blocks.pattern_groups(cfg.pattern),
                               _trunk(params, tokens, cfg, with_ids=True)[1])


def flops_per_token(cfg: MiniCPMSALAConfig) -> float:
    """Forward + backward operations one trained token REQUIRES here: 6 per
    matmul parameter the token meets (the embedding is a gather) and by shape
    three times the forward's mixers: the lightning scan's two products
    inside its chunk over the causal half and its two with the state; the
    attention's two products over the keys a token is GIVEN — every visible
    one on the dense branch, at most top_k blocks on the sparse one, with the
    compressed-key scores (one product, no backward) beside them."""
    D, S, hd, z = cfg.d_model, cfg.seq_len, cfg.head_dim, cfg.sparse
    mlp = 3 * D * cfg.d_ff
    LH, Q = cfg.lightning_heads, min(cfg.chunk, S)
    given = min(z.top_k * z.block, S) if cfg.is_sparse else S
    mean_keys = (given * (given + 1) / 2 + (S - given) * given) / S
    scores = ((S - z.kernel) // z.stride + 1) / 2 * cfg.n_head * hd / 3 \
        if cfg.is_sparse else 0.0
    per = {
        "L": (5 * D * LH * hd + mlp, LH * (Q / 2 * 2 * hd + 2 * hd * hd)),
        "S": (D * hd * (3 * cfg.n_head + 2 * cfg.n_kv_head) + mlp,
              2 * cfg.n_head * hd * mean_keys + scores),
    }
    matmul = sum(per[k][0] for k in cfg.pattern) + D * cfg.padded_vocab
    shaped = sum(per[k][1] for k in cfg.pattern)
    return 6.0 * (matmul + shaped)
