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
heads and the offset this is EvaByte (``evabyte_6p5b``). It also says how
often the stack of layers runs (``ut_steps`` passes over ONE set of weights,
the final norm inside the loop: ``blocks.run_repeated``), whether each
sublayer's output goes through a second norm before its residual add
(``sandwich_norm``), and whether a gate after every pass weighs that pass's
loss (``exit_gate``: the exit distribution over the passes, its entropy in
the objective): with four passes and both this is Ouro (``ouro_2p6b``).

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
from ray_tpu.models.blocks import StepCounters, run_blocks, run_repeated
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
    ut_steps: int = 1             # passes of the layers over one set of weights
    sandwich_norm: bool = False   # a norm on each sublayer's output too
    exit_gate: bool = False       # a gate a pass weighs the passes' losses
    exit_beta: float = 0.0        # the exit distribution's entropy, in the loss

    def __post_init__(self):
        if self.ut_steps < 1:
            raise ValueError(f"ut_steps must be >= 1; got {self.ut_steps}")
        if self.exit_gate and self.n_pred_heads != 1:
            raise ValueError("the exit gate weighs ONE head's loss a pass: "
                             "n_pred_heads must be 1")
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


def ouro_2p6b(**overrides) -> LlamaConfig:
    """Ouro-2.6B as published (huggingface.co/ByteDance/Ouro-2.6B; "Scaling
    Latent Reasoning via Looped Language Models", arXiv:2510.25741): 48
    layers run four times on one set of weights, sandwich norms, the final
    norm inside the loop, a head and an exit gate after every pass; the
    Stage-I objective at beta 0.05."""
    return replace(
        LlamaConfig(vocab_size=49152, seq_len=65536, n_layer=48, n_head=16,
                    n_kv_head=16, d_model=2048, d_ff=5632,
                    rope_theta=1000000.0, rms_eps=1e-6, ut_steps=4,
                    sandwich_norm=True, exit_gate=True, exit_beta=0.05),
        **overrides,
    )


def ouro_tiny(**overrides) -> LlamaConfig:
    """Test-size Ouro: two layers run three times."""
    return replace(
        LlamaConfig(vocab_size=256, seq_len=64, n_layer=2, n_head=4,
                    n_kv_head=4, d_model=64, d_ff=176, rope_theta=1000000.0,
                    rms_eps=1e-6, ut_steps=3, sandwich_norm=True,
                    exit_gate=True, exit_beta=0.05),
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
    if cfg.sandwich_norm:
        blocks["attn_out_norm"] = blocks["mlp_out_norm"] = ("layers", "embed")
    axes = {
        "wte": ("vocab", "embed"),
        "blocks": blocks,
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }
    if cfg.exit_gate:
        axes["exit_w"], axes["exit_b"] = ("embed",), (None,)
    return axes


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
    if cfg.sandwich_norm:
        blocks["attn_out_norm"] = norm_init((L, D), pd)
        blocks["mlp_out_norm"] = norm_init((L, D), pd)
    params = {
        "wte": wte,
        "blocks": blocks,
        "final_norm": norm_init((D,), pd),
        "lm_head": lm_head,
    }
    if cfg.exit_gate:
        # drawn like any other weight (a key of its own: the eleven above
        # draw what they always drew), the bias 0: at birth the gate is
        # near 1/2 and differs by token
        params["exit_w"] = normal(jax.random.fold_in(rng, 11), (D,))
        params["exit_b"] = jnp.zeros((1,), pd)
    return params


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
        y = jnp.einsum(f"{heads},hkd->bsd", attn, p["wo"],
                       preferred_element_type=jnp.float32)
        if cfg.sandwich_norm:
            with jax.named_scope(scopes.LN1_POST):
                y = _norm(y, p["attn_out_norm"], cfg)
        x = checkpoint_name(parts.residual_add(x, y), scopes.RES_MID)
    return _mlp(x, p, cfg)


def _swiglu(x, p, cfg: LlamaConfig):
    """x + down(silu(gate(h)) · up(h)), h = norm(x), on [B, rows, D]; with
    ``sandwich_norm`` the MLP's output under a norm of its own, the rows'
    (float32 in, float32 out: no normed copy of the sequence stands whole)."""
    with jax.named_scope(scopes.LN2):
        h = _norm(x, p["mlp_norm"], cfg)
    y = parts.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
    if cfg.sandwich_norm:
        with jax.named_scope(scopes.LN2_POST):
            y = _norm(y, p["mlp_out_norm"], cfg)
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
        head_rows=_head_rows(cfg, global_batch, seq),
        mlp_rows=parts.mlp_rows(global_batch, seq, cfg.d_model, cfg.d_ff,
                                jnp.dtype(cfg.dtype).itemsize),
        cast_in_loop=True, passes=cfg.ut_steps, out_norms=cfg.sandwich_norm,
    ), mesh)


def _head_rows(cfg: LlamaConfig, batch: int, seq: int) -> int:
    """Rows of the sequence the head takes at a time (parts.head_rows). A
    weighted head (``exit_gate``) is ONE chunked call over every pass's rows:
    its batch is the passes' together, and it is never taken whole."""
    columns = cfg.n_pred_heads * cfg.head_vocab
    if not cfg.exit_gate:
        return parts.head_rows(batch, seq, columns, cfg.n_pred_heads)
    return parts.head_chunk_rows(cfg.ut_steps * batch, seq, columns)


def _trunk(params, tokens, cfg: LlamaConfig, every_pass: bool = False):
    """tokens [B, S] int32 → final hidden states [B, S, D]. Where the layers
    run ``ut_steps`` times over their one set of weights
    (blocks.run_repeated) the final norm stands INSIDE the loop: the normed
    state is what a pass hands out and what the next starts from, and the
    result is the last pass's — or, with ``every_pass``, all of them,
    [ut_steps, B, S, D]."""
    from ray_tpu.parallel import mesh as mesh_lib

    B, S = tokens.shape
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    mesh = mesh_lib.current_mesh()

    def loop_end(x):
        with jax.named_scope(scopes.LN_F):
            return _norm(x, params["final_norm"], cfg)

    if cfg.ut_steps == 1:
        block_fn = parts.checkpoint_block(
            partial(_block, cfg=cfg), cfg.remat,
            block_shard(cfg, B, S, mesh), cfg.n_layer)
        x = loop_end(run_blocks(block_fn, x, params["blocks"]))
        return x[None] if every_pass else x
    chips = mesh.devices.size if mesh is not None else 1
    layer_bytes = sum(math.prod(a.shape[1:]) * a.dtype.itemsize
                      for a in jax.tree.leaves(params["blocks"]))
    block_fn = parts.checkpoint_block(
        partial(_block, cfg=cfg), cfg.remat, block_shard(cfg, B, S, mesh),
        cfg.n_layer, grad_bytes=layer_bytes // chips)
    states = run_repeated(
        {"B": block_fn}, "B" * cfg.n_layer, x, [{"B": params["blocks"]}],
        cfg.ut_steps, loop_end,
        heads=(f"one chunked call over {cfg.ut_steps} x {B} rows"
               if cfg.exit_gate else "the last pass's"))
    return states if every_pass else states[-1]


def forward(params, tokens, cfg: LlamaConfig) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, n_pred_heads · head_vocab], head p
    in columns p·head_vocab … (p+1)·head_vocab."""
    x = _trunk(params, tokens, cfg)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype))


def loss_fn(params, tokens, targets, cfg: LlamaConfig,
            counters: bool = False):
    """Mean cross-entropy over targets >= 0 ([B, S] int32, the next token):
    with n_pred_heads > 1 head p is scored on targets[t + p]. With
    ``exit_gate`` the exit-weighted objective (_exit_loss), and with
    ``counters`` (what step_counters offers) ``(loss, counters)``: the
    step's mean exit distribution as blocks.StepCounters packs it."""
    if not cfg.exit_gate:
        x = _trunk(params, tokens, cfg)
        return parts.lm_head_loss(x, targets, params["lm_head"], cfg.dtype,
                                  cfg.n_pred_heads)
    loss, said = _exit_loss(
        params, _trunk(params, tokens, cfg, every_pass=True), targets, cfg)
    return (loss, said) if counters else loss


def _exit_loss(params, states, targets, cfg: LlamaConfig):
    """The exit-weighted objective over the passes' states [T, B, S, D]
    (Ouro's Stage I, a uniform prior over the exit step): a gate after every
    pass, λ_t = sigmoid(h_t · w + b) a token; the exit distribution p_t =
    λ_t · Π_{j<t} (1 − λ_j), the last pass taking what is left; and

        loss = 1/N · Σ_i [ Σ_t p_t(i) · nll_t(i) − β · H(p(i)) ]

    over the N valid targets, nll_t the ONE head's cross-entropy on pass t's
    state. The head is one chunked call over the passes' rows [B · T, S]
    with p as its weights (parts.lm_head_loss), so p's cotangent is the
    per-token nll and the float32 d lm_head is summed once a step. Gate,
    distribution and entropy are float32; log p comes from log λ and
    log(1 − λ) (``log_sigmoid`` of ± the logit), never the log of a product.
    Returns (loss, the step's counters: the mean of each p_t and of H over
    the valid tokens, float32 bits in ONE int32 row, constants to AD)."""
    T, B, S, D = states.shape
    valid = targets >= 0
    n_valid = jnp.maximum(jnp.sum(valid), 1).astype(jnp.float32)
    with jax.named_scope(scopes.EXIT_GATE):
        logit = (jnp.sum(states.astype(jnp.float32)
                         * params["exit_w"].astype(jnp.float32), axis=-1)
                 + params["exit_b"].astype(jnp.float32))        # [T, B, S]
        log_stay = jax.nn.log_sigmoid(-logit)                  # log(1 − λ)
        stayed = jnp.cumsum(log_stay, axis=0) - log_stay       # Σ_{j<t}
        last = (jnp.arange(T) == T - 1)[:, None, None]
        log_p = stayed + jnp.where(last, 0.0, jax.nn.log_sigmoid(logit))
        p = jnp.exp(log_p)
        entropy = -jnp.sum(p * log_p, axis=0)                   # [B, S]
        mean_entropy = jnp.sum(jnp.where(valid, entropy, 0.0)) / n_valid
    # rows [B · T, S], a batch row's passes together: the batch's sharding
    # stays what it is
    weighted = parts.lm_head_loss(
        states.swapaxes(0, 1).reshape(B * T, S, D),
        jnp.repeat(targets, T, axis=0), params["lm_head"], cfg.dtype,
        weights=p.swapaxes(0, 1).reshape(B * T, S))
    # (the head divides by ITS valid targets, each of the N counted T times)
    loss = T * weighted - cfg.exit_beta * mean_entropy
    with jax.named_scope(scopes.EXIT_GATE):
        mean_p = jnp.sum(jnp.where(valid, p, 0.0), axis=(1, 2)) / n_valid
        said = jax.lax.bitcast_convert_type(jax.lax.stop_gradient(
            jnp.concatenate([mean_p, mean_entropy[None]])), jnp.int32)[None]
    return loss, said


def step_counters(cfg: LlamaConfig):
    """What a step of this config says of itself (train_step asks every
    model): with the exit gate ONE row, the step's mean exit distribution
    over the passes and its mean entropy (names.EXIT_DISTRIBUTION_KIND);
    nothing without."""
    if not cfg.exit_gate:
        return None
    fields = tuple(f"{scopes.STEP_EXIT_PASS}{t + 1}"
                   for t in range(cfg.ut_steps)) + (scopes.STEP_EXIT_ENTROPY,)
    return StepCounters(
        scopes.EXIT_DISTRIBUTION_KIND, fields, (cfg.n_layer - 1,),
        lambda tokens: dict(zip(scopes.EXIT_DISTRIBUTION_STATIC_ARGS,
                                (cfg.ut_steps,))), fields)


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
