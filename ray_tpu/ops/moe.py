"""Mixture-of-experts MLP with capacity-based einsum dispatch.

TPU-native expert parallelism (SURVEY §2.10; the reference has no TPU MoE —
this is new work in the GShard/Switch style): the router's top-k choices are
turned into STATIC-shaped dispatch/combine tensors, so the whole layer is
three einsums + a batched expert matmul pair. No dynamic shapes, no
gather/scatter — XLA tiles everything onto the MXU, and the expert dimension
shards over the mesh's `ep` axis (each device holds E/ep experts; the
dispatch einsum becomes an all-to-all that XLA inserts from the shardings).

Shapes (T = B*S tokens, E experts, C capacity slots per expert):
    router_w   [D, E]
    fc_w       [E, D, F]    fc_b  [E, F]
    out_w      [E, F, D]    out_b [E, D]
    dispatch   [T, E, C]  one-hot: token t occupies slot c of expert e
    combine    [T, E, C]  dispatch * gate weight

Tokens over an expert's capacity are DROPPED (standard GShard semantics:
the residual connection carries them through unchanged); capacity_factor
sizes C = ceil(k * T / E) * capacity_factor.

Beside it, ``latent_moe`` (PR 33): an expert layer that is told which experts
it HOLDS — one chip's share of an expert-parallel deployment. It routes over
all ``n_experts`` at the published width and top-k (sigmoid scores, a
selection bias, gates normalised over the chosen and scaled) and keeps the
outcome in the terms of the experts it holds (PR 34): which tokens chose each
held expert, a [T, held] membership, and their gates, [T, held] — the chosen
set as a mask over the scores, so the normalising sum is a masked row-sum and
no score is gathered by chosen id. The membership's true entries, sorted by
expert (T · held keys that carry their own token, not T · top_k with an index
operand), are the rows of the two products, computed as grouped matmuls
(``ops/grouped_matmul.grouped_dot``: since PR 60 the program's own Pallas
kernels at a cell's shapes, ``lax.ragged_dot`` — the TPU compiler's grouped
kernel — at a toy's; the work of either follows the real group sizes),
around them a latent projection and beside them a
shared expert. No pair is dropped — a batch that lands more pairs here than
one row buffer holds takes further passes over it, and everything that costs
a row is done by the pass that holds it (PR 36; its gate comes out of the
pairs' sort beside its key, PR 51) — and memory is linear in T. A pass that
runs alone costs one pass (PR 51): the first stands outside any loop and
writes what it makes, and only a batch that fills more enters one.

``gated_moe`` (PR 50) is the same dispatch around the other kind of expert:
three matrices, SiLU-gated, read and written at the model's width, no latent
and no shared expert beside them. What a held expert IS — ``relu(x·W1)²·W2``
or ``(silu(x·W1) ⊙ x·W3)·W2`` — is the tuple of weights the passes are given
(_pass_rows); routing, the pairs, the row buffer, the passes and their
written-out backward, the load and the bias's balance are one code for both.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.layout import Layout, with_layout_constraint

from ray_tpu.ops.grouped_matmul import grouped_dot
from ray_tpu.tracing import get_buffer, names as scopes


def moe_capacity(num_tokens: int, num_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    return max(
        1, int(math.ceil(top_k * num_tokens / num_experts * capacity_factor))
    )


def moe_init(rng: jax.Array, num_layers: int, d_model: int, d_ff: int,
             num_experts: int, param_dtype=jnp.float32,
             resid_std: float = 0.02) -> Dict[str, Any]:
    """Per-layer stacked expert params ([L, E, ...], matching blocks)."""
    k1, k2, k3 = jax.random.split(rng, 3)
    std = 0.02

    def normal(key, shape, s):
        return (jax.random.normal(key, shape) * s).astype(param_dtype)

    L, D, F, E = num_layers, d_model, d_ff, num_experts
    return {
        "router_w": normal(k1, (L, D, E), std),
        "fc_w": normal(k2, (L, E, D, F), std),
        "fc_b": jnp.zeros((L, E, F), param_dtype),
        "out_w": normal(k3, (L, E, F, D), resid_std),
        "out_b": jnp.zeros((L, E, D), param_dtype),
    }


def moe_logical_axes() -> Dict[str, Any]:
    """Logical axes for one layer-stacked MoE param tree: the `expert` axis
    maps to the mesh's ep dimension (sharding rules in parallel/mesh)."""
    return {
        "router_w": ("layers", "embed", None),
        "fc_w": ("layers", "expert", "embed", "mlp"),
        "fc_b": ("layers", "expert", "mlp"),
        "out_w": ("layers", "expert", "mlp", "embed"),
        "out_b": ("layers", "expert", "embed"),
    }


def moe_mlp(x: jax.Array, params: Dict[str, Any], *, top_k: int,
            capacity_factor: float = 1.25, dtype=jnp.bfloat16):
    """x: [B, S, D] → ([B, S, D], aux_loss scalar).

    params hold ONE layer's tensors (no leading L): router_w [D,E],
    fc_w [E,D,F], fc_b [E,F], out_w [E,F,D], out_b [E,D].
    aux_loss is the standard load-balancing loss (mean fraction * mean
    router prob per expert, scaled by E) — add it to the model loss.
    """
    B, S, D = x.shape
    T = B * S
    E = params["router_w"].shape[-1]
    C = moe_capacity(T, E, top_k, capacity_factor)
    xt = x.reshape(T, D)

    # --- routing (f32 for a stable softmax)
    logits = jnp.einsum(
        "td,de->te", xt.astype(jnp.float32),
        params["router_w"].astype(jnp.float32),
    )
    probs = jax.nn.softmax(logits, axis=-1)                   # [T, E]
    gate_vals, gate_idx = lax.top_k(probs, top_k)             # [T, k]
    # renormalize the chosen gates so they sum to 1 per token
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # --- capacity assignment: position of each (token, choice) within its
    # expert, computed with a cumulative sum over the one-hot choice matrix
    # (static shapes end to end)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)   # [T, k, E]
    # GShard priority: ALL tokens' 1st choices outrank any 2nd choice —
    # cumsum in k-major order so capacity pressure degrades to top-1
    # routing instead of early tokens' spillover evicting later tokens
    flat = onehot.swapaxes(0, 1).reshape(top_k * T, E)        # k-major
    position = jnp.cumsum(flat, axis=0) - flat                # [k*T, E]
    pos_in_expert = jnp.sum(position * flat, axis=-1)         # [k*T]
    keep = (pos_in_expert < C).astype(jnp.float32)
    pos = pos_in_expert.reshape(top_k, T).swapaxes(0, 1)      # [T, k]
    keep = keep.reshape(top_k, T).swapaxes(0, 1)

    slot_onehot = jax.nn.one_hot(
        pos.astype(jnp.int32), C, dtype=jnp.float32
    )                                                         # [T, k, C]
    # dispatch[t,e,c] = 1 iff token t's kept choice routes to (e, c)
    dispatch = jnp.einsum(
        "tke,tkc->tec", onehot * keep[..., None], slot_onehot
    )
    combine = jnp.einsum(
        "tke,tkc->tec", onehot * (gate_vals * keep)[..., None], slot_onehot
    )

    # --- expert compute: batched over E (shardable on the ep mesh axis)
    xin = jnp.einsum("tec,td->ecd", dispatch.astype(dtype), xt.astype(dtype))
    h = jnp.einsum("ecd,edf->ecf", xin, params["fc_w"].astype(dtype))
    h = h + params["fc_b"].astype(dtype)[:, None, :]
    h = jax.nn.gelu(h, approximate=True)
    out = jnp.einsum("ecf,efd->ecd", h, params["out_w"].astype(dtype))
    out = out + params["out_b"].astype(dtype)[:, None, :]
    y = jnp.einsum("tec,ecd->td", combine.astype(dtype), out)

    # --- load-balancing aux loss (Switch Transformer eq. 4)
    frac_tokens = jnp.mean(onehot[:, 0, :], axis=0)           # top-1 share
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return y.reshape(B, S, D), aux.astype(jnp.float32)


# --------------------------------------------------------------------------- #
# An expert layer that knows its share (PR 33)
# --------------------------------------------------------------------------- #

# the row buffer ONE PASS of the held experts' pairs takes, as a multiple of
# the pairs a batch sends them on average (T · top_k · held / n_experts). A
# COST QUANTUM, not a capacity: a batch that lands more than that here — a
# router that has learnt to prefer the experts whose gradient it sees — takes
# further passes over the same buffer, as many as its pairs need and never
# more than the worst case (every token's choices on held experts) would. No
# pair is ever dropped; every cost of a row (gather, masks, gate, scatter-add)
# is paid for the rows of the passes that run, in steps of one buffer; memory
# stays one buffer's. The number weighs the rows a pass carries empty against
# a second pass's fixed cost: with a balanced selection bias a batch lands
# within 0.3 % of the mean (11,231–11,298 of 11,264 pairs at set-up, every
# layer and seed of the Nemotron cell), so 1.25 — a pass holds a batch up to 27 % over
# the mean at that shape, tiles rounded — leaves ninety times that spread
# and runs three empty rows in fourteen, where the 4.0 chosen before the
# balance existed ran three in four. On the chip 12,800, 14,336 and 16,896
# rows cost a layer the same to 0.7 ms, and in the Nemotron cell, whose
# routers drift off set-up's balance within a run, 1.25 was the best or the
# equal of 1.125 and 1.5 (PERF.md §6, PR 36). It is not a knob: a router out
# of balance costs more passes (a second one ~10 ms a layer and step there),
# never a wrong result.
ROW_BUFFER_MULTIPLE = 1.25
_ROW_TILE = 512              # whole row tiles of either grouped kernel
# the selection bias at initialisation: noise small beside the scores' spread
# (a sigmoid of logits of std ~1.3), large enough to decide near-ties
ROUTER_BIAS_STD = 0.01
# balance_bias: rounds of the balancing rule on one batch, and its first rate
# (the rate falls linearly to 0, so the last rounds settle what the first
# ones found; 64 rounds of at most 0.02 can carry a bias 0.65, the scores
# span 1)
BALANCE_ROUNDS, BALANCE_RATE = 64, 0.02


class Held(NamedTuple):
    """The routed experts one chip holds: ids first … first + count − 1."""
    first: int
    count: int


def latent_moe_init(rng: jax.Array, n_layers: int, d_model: int,
                    n_experts: int, held: int, latent: int, d_expert: int,
                    d_shared: int, std: float, out_std: float,
                    param_dtype=jnp.float32) -> Dict[str, Any]:
    """``n_layers`` stacked layers: the router over all ``n_experts`` and its
    selection bias (a buffer: no gradient reaches it, and an optimizer that
    decays weights must leave it out), the latent projections, ``held``
    routed experts and the shared expert."""
    k = iter(jax.random.split(rng, 8))
    L = n_layers

    def normal(key, shape, s):
        return (jax.random.normal(key, shape) * s).astype(param_dtype)

    return {
        "router_w": normal(next(k), (L, d_model, n_experts), std),
        "router_bias": normal(next(k), (L, n_experts), ROUTER_BIAS_STD),
        "w_down": normal(next(k), (L, d_model, latent), std),
        "w_up": normal(next(k), (L, latent, d_model), out_std),
        "w1": normal(next(k), (L, held, latent, d_expert), std),
        "w2": normal(next(k), (L, held, d_expert, latent), std),
        "shared_w1": normal(next(k), (L, d_model, d_shared), std),
        "shared_w2": normal(next(k), (L, d_shared, d_model), out_std),
    }


def latent_moe_logical_axes() -> Dict[str, Any]:
    return {
        "router_w": ("layers", "embed", None),
        "router_bias": ("layers", None),
        "w_down": ("layers", "embed", None),
        "w_up": ("layers", None, "embed"),
        "w1": ("layers", "expert", None, "mlp"),
        "w2": ("layers", "expert", "mlp", None),
        "shared_w1": ("layers", "embed", "mlp"),
        "shared_w2": ("layers", "mlp", "embed"),
    }


# what latent_moe takes in the compute dtype (the router stays float32)
LATENT_MOE_MATMUL_WEIGHTS = ("w_down", "w_up", "w1", "w2", "shared_w1",
                             "shared_w2")


def row_buffer(tokens: int, n_experts: int, top_k: int, held: int) -> int:
    """Rows of the held experts' pair buffer for a batch of ``tokens``:
    ROW_BUFFER_MULTIPLE times the mean, a whole number of row tiles, and never
    more than the worst case a batch can produce (every token's choices on
    held experts) — where that is the smaller, one pass takes any batch."""
    worst = tokens * min(top_k, held)
    mean = tokens * top_k * held / n_experts
    rows = -(-int(math.ceil(ROW_BUFFER_MULTIPLE * mean)) // _ROW_TILE) * _ROW_TILE
    return min(worst, rows)


def buffer_passes(tokens: int, n_experts: int, top_k: int, held: int) -> int:
    """Passes over the row buffer that the worst case a batch can produce
    would take: the static length of routed_experts' loop, of which a batch
    runs those its pairs fill."""
    return -(-tokens * min(top_k, held)
             // row_buffer(tokens, n_experts, top_k, held))


def sort_ops(n: int, operands: int) -> int:
    """Operations of a sorting network over ``n`` keys (bitonic: log2(n) ·
    (log2(n) + 1) / 2 stages of n / 2 compare-exchanges), each a comparison
    and two selects an operand that moves: what the TPU lowers _chosen's
    ``top_k`` (a row's n_experts with an index operand) and held_pairs' sort
    (tokens · held keys with their gates) to, for whoever prices keeping
    their outcome against making it again."""
    stages = math.log2(n) * (math.log2(n) + 1) / 2
    return int(n / 2 * stages * (1 + 2 * operands))


@jax.custom_vjp
def _sigmoid(x: jax.Array) -> jax.Array:
    """``jax.nn.sigmoid`` whose backward reads the NAMED scores: the
    primitive's own derivative rule reads its own output, so a block that
    keeps a ``checkpoint_name`` copy of it keeps the bytes and still makes the
    router's product again to feed the primitive. Same values forward, and
    backward ``g · s · (1 − s)`` as ``lax.logistic`` gives."""
    return jax.nn.sigmoid(x)


def _sigmoid_fwd(x):
    s = checkpoint_name(jax.nn.sigmoid(x), scopes.RES_MOE_SCORES)
    return s, s


def _sigmoid_bwd(s, g):
    return (g * (s * (1 - s)),)


_sigmoid.defvjp(_sigmoid_fwd, _sigmoid_bwd)


@jax.custom_vjp
def _softmax(x: jax.Array) -> jax.Array:
    """``jax.nn.softmax`` over the last axis whose backward reads the NAMED
    probabilities, as _sigmoid's reads its scores and for its reason: same
    values forward, and backward ``s · (g − Σ g · s)``."""
    return jax.nn.softmax(x, axis=-1)


def _softmax_fwd(x):
    s = checkpoint_name(jax.nn.softmax(x, axis=-1), scopes.RES_MOE_SCORES)
    return s, s


def _softmax_bwd(s, g):
    return (s * (g - jnp.sum(g * s, axis=-1, keepdims=True)),)


_softmax.defvjp(_softmax_fwd, _softmax_bwd)

# how a router turns its logits into scores: each expert's own sigmoid, or
# one softmax over all the experts
SCORING = {"sigmoid": _sigmoid, "softmax": _softmax}


def _scores(u: jax.Array, router_w: jax.Array,
            scoring: str = "sigmoid") -> jax.Array:
    """u [T, D] → every expert's score [T, n_experts], float32: its sigmoid,
    or with ``scoring`` "softmax" its probability among all the experts."""
    return SCORING[scoring](jnp.einsum(
        "td,de->te", u.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGH))


def _chosen(biased: jax.Array, top_k: int) -> jax.Array:
    """biased [T, E] → [T, E] bool, ``top_k`` true a row: the very set
    ``lax.top_k`` names, as a mask. It puts equal elements in index order, so
    with ``kth`` its last value and ``last`` that value's index the set is all
    above ``kth`` and, of those equal to it, the ids up to ``last`` — one
    elementwise pass, ties included. That order among equals is the backend's
    lowering, not ``lax.top_k``'s contract: tier-1 holds it on the CPU
    (tests/test_nemotron_h.py, the ties case), ``chip_smoke.chosen_rows_off``
    on the chip at the published routing shape. ``kth`` and ``last`` carry
    names: a block that keeps them (8 bytes a token) sorts once a step, not
    again in its backward's second forward."""
    vals, idx = lax.top_k(biased, top_k)
    kth = checkpoint_name(vals[:, -1:], scopes.RES_MOE_KTH)
    last = checkpoint_name(idx[:, -1:], scopes.RES_MOE_LAST)
    ids = lax.broadcasted_iota(idx.dtype, biased.shape, 1)
    return (biased > kth) | ((biased == kth) & (ids <= last))


class Rule(NamedTuple):
    """What a router is told beside its weights: how logits become scores
    (SCORING) and whether the chosen scores are divided by their sum. The
    default is the Nemotron and LFM2 routers' (sigmoid, normalised);
    DeepSeek-V2's is a softmax whose chosen probabilities gate AS THEY ARE
    (``norm_topk_prob`` false)."""
    scoring: str = "sigmoid"
    normalise: bool = True


def scored_choice(u: jax.Array, router_w: jax.Array,
                  bias: Optional[jax.Array], top_k: int,
                  scoring: str = "sigmoid") -> Tuple[jax.Array, jax.Array]:
    """u [T, D] → (every expert's score [T, n_experts] float32, the set each
    token chose [T, n_experts] bool): the k largest of score + ``bias`` (the
    bias — None: a router without one — chooses only). What route gates
    from and what a balance loss reads (balance_loss), made once."""
    scores = _scores(u, router_w, scoring)
    biased = scores if bias is None else scores + bias.astype(jnp.float32)
    return scores, _chosen(lax.stop_gradient(biased), top_k)


def route(u: jax.Array, router_w: jax.Array, bias: Optional[jax.Array],
          top_k: int, scaling: float, held: Held, eps: float = 0.0,
          rule: Rule = Rule(), with_choice: bool = False):
    """u [T, D] → (``here`` [T, held] bool: the token chose that held expert,
    the held experts' gates [T, held] float32, which mean something only where
    ``here``): scores in float32 (``rule.scoring``), the k largest of score +
    bias chosen (the bias chooses only), gates = scaling · score / (Σ over
    ALL the chosen + ``eps``), or scaling · score where the rule does not
    normalise. The sum is a masked row-sum and the held experts' scores a
    static slice:
    nothing is gathered by chosen id. The gates are not masked by ``here``:
    only a pair's row reads one, and ``HeldPairs.valid`` says which rows are
    pairs. ``with_choice`` adds scored_choice's pair as a third result."""
    scores, chosen = scored_choice(u, router_w, bias, top_k, rule.scoring)
    if rule.normalise:
        denom = jnp.sum(jnp.where(chosen, scores, 0.0), axis=-1, keepdims=True)
        if eps:
            denom = denom + eps
    span = slice(held.first, held.first + held.count)
    here, gates = chosen[:, span], scaling * scores[:, span]
    if rule.normalise:
        gates = gates / denom
    return (here, gates, (scores, chosen)) if with_choice else (here, gates)


@jax.named_scope(scopes.MOE_AUX)
def balance_loss(scores: jax.Array, chosen: jax.Array, top_k: int,
                 rows: int) -> jax.Array:
    """The sequence-wise balance loss of one expert layer, before its
    coefficient (DeepSeek-V2, arXiv:2405.04434 eq. 23–26; ``seq_aux``):
    scores / chosen [T, n_experts] (scored_choice's) of a batch whose rows
    are ``rows`` tokens long → the mean over the batch's rows of ``Σ_e f_e ·
    P_e``, with, within ONE row, ``f_e = count_e · n_experts / (top_k ·
    rows)`` (how many of the row's tokens chose e: a constant to AD) and
    ``P_e`` the row's mean score of e. 1.0 where every expert is chosen
    equally often and scored 1 / n_experts. Its gradient reaches the router
    through P alone — from every token, held choice or not: the router is
    whole on every chip of an EP group, and this term is not cut by the
    share."""
    n_experts = scores.shape[-1]
    count = jnp.sum(chosen.reshape(-1, rows, n_experts), axis=1,
                    dtype=jnp.float32)
    f = lax.stop_gradient(count) * (n_experts / (top_k * rows))
    mean_score = jnp.mean(scores.reshape(-1, rows, n_experts), axis=1)
    return jnp.mean(jnp.sum(f * mean_score, axis=-1))


def balance_bias(u: jax.Array, router_w: jax.Array, bias: jax.Array,
                 top_k: int) -> jax.Array:
    """The selection bias after BALANCE_ROUNDS of the auxiliary-loss-free
    balancing rule on ONE batch (u [T, D], the layer's normed input), the
    weights held: ``b_e ← b_e + γ · sign(mean load − load_e)`` over all the
    experts, γ falling from BALANCE_RATE to 0. What a run's many steps do to
    the bias between them, done at once; for set-up — no step calls it."""
    scores = _scores(u, router_w)
    mean = scores.shape[0] * top_k / scores.shape[1]

    def body(i, b):
        biased = scores + b
        kth = lax.top_k(biased, top_k)[0][:, -1:]
        load = jnp.sum(biased >= kth, axis=0, dtype=jnp.float32)
        rate = BALANCE_RATE * (1.0 - i / BALANCE_ROUNDS)
        return b + rate * jnp.sign(mean - load)

    return lax.fori_loop(0, BALANCE_ROUNDS, body, bias.astype(jnp.float32))


def balance_bias_round(u: jax.Array, router_w: jax.Array, bias: jax.Array,
                       top_k: int, rate) -> jax.Array:
    """ONE round of balance_bias's rule on one batch at ``rate``: ``b_e ← b_e
    + rate · sign(mean load − load_e)``. For a caller that gives every round
    a batch of its own (models/deepseek_v2.balance_router_bias): rounds on
    ONE batch fit that batch — where a layer's routing is a few dozen
    patterns (tokens drawn from few symbols) an expert's load moves by a
    pattern's whole block of tokens, the rule settles where a block's
    near-ties split evenly, and that split is the batch's own: on every other
    batch the held experts' share stood up to a quarter over the mean
    (PERF.md §6, PR 57). Set-up's, as balance_bias: no step calls it."""
    scores = _scores(u, router_w)
    mean = scores.shape[0] * top_k / scores.shape[1]
    biased = scores + bias.astype(jnp.float32)
    kth = lax.top_k(biased, top_k)[0][:, -1:]
    load = jnp.sum(biased >= kth, axis=0, dtype=jnp.float32)
    return bias.astype(jnp.float32) + rate * jnp.sign(mean - load)


# balance_router: a run's first rate, in units of a LOGIT (a round's step is
# the gradient's direction at the length that moves the batch's logits by
# its rate, root mean square; the caller lets it fall linearly to 0 over
# BALANCE_ROUNDS as balance_bias's does). A step measured on the weights
# instead diverges where the layer's input has a component every token
# shares — after a dense MLP it has —: along it a weight's change moves every
# token's logit at once (all tokens on six experts within sixteen rounds,
# PERF.md §6, PR 55)
BALANCE_ROUTER_RATE = 0.3


def balance_router(u: jax.Array, router_w: jax.Array, top_k: int, rows: int,
                   rate, rule: Rule = Rule()) -> jax.Array:
    """The router of a layer WITHOUT a selection bias after ONE round of
    gradient descent on its own balance_loss on one batch (u [T, D], the
    layer's normed input, in rows of ``rows`` tokens), everything else held:
    ``W ← W − rate · g / rms(u·g)`` — the round moves the batch's logits by
    ``rate``. BALANCE_ROUNDS of them, a fresh batch each and the rate falling
    from BALANCE_ROUTER_RATE to 0, are what a run's many steps under the
    balance loss do to a router, done at once, as balance_bias does for a
    router that is balanced by a bias. A fresh batch each because rounds on
    ONE batch fit that batch: a router's choices on tokens drawn from few
    symbols are a few dozen patterns, which a batch's own near-ties split —
    its held experts then get 25 % of the pairs on that batch and a seed's own
    24–26 % on every other (PERF.md §6, PR 55). NO TRAINING PATH CALLS IT —
    no step, no trainer, no model's loss_fn: models/deepseek_v2.balance_routers
    does, which a benchmark's build and chip_smoke.py call once before the
    first step, so that a run on freshly drawn weights starts balanced (the
    cell's configuration states the departure, ``assumed`` (i))."""
    def loss(w):
        return balance_loss(*scored_choice(u, w, None, top_k, rule.scoring),
                            top_k, rows)

    w = router_w.astype(jnp.float32)
    g = jax.grad(loss)(w)
    moved = jnp.einsum("td,de->te", u.astype(jnp.float32), g,
                       precision=lax.Precision.HIGH)
    return w - rate * g / (jnp.sqrt(jnp.mean(jnp.square(moved))) + 1e-30)


class HeldPairs(NamedTuple):
    """The (token, held expert) pairs a batch chose, sorted by expert and
    within an expert by token, as ``passes`` buffers of ``rows``: row r of
    pass i is the pair at place ``key[i, r]`` of the membership's transpose —
    expert · T + token, so token ``key % T`` — with gate ``gate_rows[i, r]``
    = ``gates[key[i, r]]``, while ``valid[i, r]``; ``group_sizes[i, e]`` of
    the pass's rows belong to held expert e. Every pair on a held expert is
    in some pass: passes · rows covers the worst case. A row that holds no
    pair has key 0 — token 0 — and whatever gate the sort left there, NOT
    zero: ``valid`` alone says which rows count, and ``_pass_rows`` masks by
    it. A row's token and its latent are looked up by the pass that runs it,
    not here; its gate came out of the sort beside its key (PR 51), a
    constant to AD — ``gates`` is where a gate's cotangent goes back to."""
    key: jax.Array            # [passes, rows] int32
    gates: jax.Array          # [held · T] float32: route's gates, expert-major
    gate_rows: jax.Array      # [passes, rows] float32: the same, row for row
    valid: jax.Array          # [passes, rows] bool
    group_sizes: jax.Array    # [passes, held] int32
    per_expert: jax.Array     # [held] int32: pairs on each held expert


def held_pairs(here: jax.Array, gates: jax.Array, rows: int,
               passes: int) -> HeldPairs:
    """Lay the true entries of ``here`` [T, held] (route's membership, with
    its ``gates``) over ``passes`` buffers of ``rows``, expert by expert and
    token-ascending within one. A pair's sort key is its place in ``here.T``
    — expert · T + token, so it carries both — and every other entry's key
    sorts last: one sort of T · held keys with the gates that lie there
    beside them, no index operand."""
    T, held = here.shape
    none = held * T                              # fits int32 with room
    per_expert = jnp.sum(here, axis=0, dtype=jnp.int32)
    place = jnp.arange(none, dtype=jnp.int32).reshape(held, T)
    gates = gates.T.reshape(none)
    # (the pairs' keys are distinct, so the sort need not be stable: a stable
    # one takes an index operand on the TPU — as AD's rule for a sort does,
    # with a gather by it: the gates ride along as numbers, and their way
    # back is _run_passes_bwd's scatter by key)
    key, gate = lax.sort(
        (jnp.where(here.T, place, none).reshape(none),
         lax.stop_gradient(gates)), num_keys=1, is_stable=False)
    # past the T · held keys there are no pairs
    total = passes * rows

    def first(x):
        return jnp.pad(x, (0, max(0, total - none)))[:total]

    # (named: a block that keeps the sorted keys and their gates does not
    # sort again and looks no gate up; valid and group_sizes are a row-sum
    # of `here` away)
    key = checkpoint_name(first(key), scopes.RES_MOE_PAIR_KEY)
    gate = checkpoint_name(first(gate), scopes.RES_MOE_PAIR_GATE)
    valid = jnp.arange(total) < jnp.sum(per_expert)
    # a pass's share of each expert's run of rows
    lo = (jnp.arange(passes) * rows)[:, None]
    ends = jnp.clip(jnp.cumsum(per_expert)[None, :], lo, lo + rows) - lo
    return HeldPairs(
        key=jnp.where(valid, key, 0).reshape(passes, rows),
        gates=gates,
        gate_rows=gate.reshape(passes, rows),
        valid=valid.reshape(passes, rows),
        group_sizes=jnp.diff(ends, axis=1, prepend=0).astype(jnp.int32),
        per_expert=per_expert)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


# the two kinds of held expert, each by the names of its weights in a layer's
# tensors: relu(x·W1)²·W2 (latent_moe's) and (silu(x·W1) ⊙ x·W3)·W2
# (gated_moe's). _pass_rows tells them apart by how many there are
RELU2_EXPERT = ("w1", "w2")
GATED_EXPERT = ("w1", "w3", "w2")


@contextlib.contextmanager
def _dispatch_scope():
    """``moe_routed`` > ``moe_dispatch``: a pass's own work around the grouped
    products. routed_experts is no one scope: what it runs enters
    ``moe_routed`` part by part, and the products' kernels do not."""
    with jax.named_scope(scopes.MOE_ROUTED), \
            jax.named_scope(scopes.MOE_DISPATCH):
        yield


def _pass_rows(x, ws, gate, valid, group_sizes):
    """One buffer of pairs through the held experts, row for row: x [rows,
    width] (each pair's token's input), its gate [rows] → gate · f_e(x)
    [rows, width] float32, 0 in a row without a pair. The experts' weights
    ``ws`` say what a held expert is: (W1, W2) — ``relu(x·W1_e)² · W2_e`` —
    or (W1, W3, W2) — ``(silu(x·W1_e) ⊙ x·W3_e) · W2_e``."""
    w1, w2 = ws[0], ws[-1]
    with _dispatch_scope():
        x = jnp.where(valid[:, None], x, 0)
    # rows past the last group are whatever the kernel left there (NaN as
    # likely as not), in the products and in their cotangents: each is masked
    # before anything multiplies it. The products themselves stand under
    # neither scope: a trace's reader finds them by the kernel's name
    # (ops/grouped_matmul.py) and adds them to the `moe_routed` scope's time
    h = grouped_dot(x, w1, group_sizes)
    if len(ws) == 3:
        up = grouped_dot(x, ws[1], group_sizes)
        with _dispatch_scope():
            a = (jax.nn.silu(jnp.where(valid[:, None], h, 0))
                 * jnp.where(valid[:, None], up, 0))
    else:
        with _dispatch_scope():
            a = _relu2(jnp.where(valid[:, None], h, 0))
    # out of the kernel in the compute dtype: a float32 output would make
    # the backward's two grouped products take float32 operands
    o = grouped_dot(a, w2, group_sizes)
    with _dispatch_scope():
        return (jnp.where(valid[:, None], o, 0).astype(jnp.float32)
                * gate[:, None])


@_dispatch_scope()
def _looked_up(ell, key):
    """What one pass looks up for its rows' ``key`` [rows]: each row's token
    and its latent out of ell [T, latent]."""
    token = key % ell.shape[0]
    return token, ell[token]


@jax.named_scope(scopes.MOE_FURTHER_PASSES)
def _further_passes(n, body, first):
    """``body`` over passes 1 … ``n`` − 1, from what the first pass made:
    a ``while`` that a batch whose pairs fit one pass — every batch of a
    balanced router — does not enter. A step that runs nothing under this
    scope took the one-pass path in every expert layer."""
    return lax.fori_loop(1, n, body, first)


class _Float32Sum(NamedTuple):
    """A float32 sum that starts AT an array of the compute dtype — the first
    pass's weight gradient, which is the whole sum where no further pass runs
    — stored as that array and the bits it lacks: a bfloat16 is the high half
    of its float32's word, so ``low`` holds the other 16 bits, zero to start
    with (for float32 there are none). Nothing is converted to start the sum
    and nothing to leave it; ``high`` + ``low`` take the bytes the float32
    sum took, and a further pass reads and writes what its add did."""
    high: jax.Array
    low: Optional[jax.Array]

    @classmethod
    def starting_at(cls, d):
        if d.dtype == jnp.float32:
            return cls(d, None)
        if d.dtype != jnp.bfloat16:
            raise TypeError(f"the experts' weights are {d.dtype}: the passes "
                            "sum their gradients for bfloat16 or float32")
        with _dispatch_scope():
            return cls(d, jnp.zeros(d.shape, jnp.uint16))

    def plus(self, d, last):
        """The sum with ``d`` added in float32; after the ``last`` pass
        ``high`` is the sum cast to the compute dtype, as a cast of the
        float32 sum gives it."""
        if self.low is None:
            return _Float32Sum(self.high + d, None)
        word = (lax.bitcast_convert_type(self.high, jnp.uint16)
                .astype(jnp.uint32) << 16 | self.low.astype(jnp.uint32))
        s = lax.bitcast_convert_type(word, jnp.float32) + d.astype(jnp.float32)
        word = lax.bitcast_convert_type(s, jnp.uint32)
        high = lax.bitcast_convert_type((word >> 16).astype(jnp.uint16),
                                        jnp.bfloat16)
        return _Float32Sum(jnp.where(last, s.astype(jnp.bfloat16), high),
                           word.astype(jnp.uint16))


@jax.custom_vjp
def _run_passes(ell, ws, gates, gate_rows, key, valid, group_sizes, n):
    """The first ``n`` passes' sum over ell [T, latent]: each pass looks up
    its own rows' tokens and latents (``key[i]`` into ell; its gates are
    ``gate_rows[i]`` — ``gates[key[i]]``, which is not looked up: the flat
    ``gates`` is here for its cotangent), runs them through _pass_rows
    (``ws``: the held experts' weights, and with them their form) and adds
    each row to its token's row of ONE [T, latent] float32 sum. ``n`` is a
    value of the step — the passes this batch's pairs fill. A pass that runs
    alone costs one pass: the first runs as it stands and writes what it
    makes — one without a pair is all zeros, so ``n`` = 0 needs no guard —
    and only a batch that fills more enters the ``while`` (_further_passes).
    The backward is written out below (one pass's vjp at a time), because AD
    through a loop of conditional passes keeps every pass's operands at once
    (the 8 x 4,096-token step then needs 18.7 GB of a v5e's 15.75)."""
    def add_pass(i, r):
        token, x = _looked_up(ell, key[i])
        o = _pass_rows(x, ws, gate_rows[i], valid[i], group_sizes[i])
        with _dispatch_scope():
            return r.at[token].add(o)

    with jax.named_scope(scopes.MOE_ROUTED):
        zeros = jnp.zeros(ell.shape, jnp.float32)
    return _further_passes(n, add_pass, add_pass(0, zeros))


def _run_passes_fwd(ell, ws, gates, gate_rows, key, valid, group_sizes, n):
    return (_run_passes(ell, ws, gates, gate_rows, key, valid, group_sizes, n),
            (ell, ws, gates, gate_rows, key, valid, group_sizes, n))


def _run_passes_bwd(res, d_r):
    """The transposes of a pass's lookups written out: the sum's cotangent
    gathered by token, the latents' scatter-added by token and the gates' by
    key — a pass's ``rows`` scalars into the [held · T] sum, not passes · rows
    of them kept for one scatter at the end. The first pass's weight
    gradients are the kernel's own outputs; further passes add theirs to them
    in float32 (_Float32Sum)."""
    ell, ws, gates, gate_rows, key, valid, group_sizes, n = res

    def pass_vjp(i, d_ell, d_gates):
        token, x = _looked_up(ell, key[i])
        _, vjp = jax.vjp(
            lambda x, ws, g: _pass_rows(x, ws, g, valid[i], group_sizes[i]),
            x, ws, gate_rows[i])
        with _dispatch_scope():
            d_o = d_r[token]
        d_x, d_w, d_gate = vjp(d_o)
        with _dispatch_scope():
            return (d_ell.at[token].add(d_x.astype(jnp.float32)), d_w,
                    d_gates.at[key[i]].add(d_gate))

    def body(i, sums):
        d_ell, d_w, d_gates = pass_vjp(i, sums[0], sums[2])
        with jax.named_scope(scopes.MOE_ROUTED):
            return (d_ell, tuple(s.plus(d, i == n - 1)
                                 for s, d in zip(sums[1], d_w)), d_gates)

    with jax.named_scope(scopes.MOE_ROUTED):
        zeros = jnp.zeros(ell.shape, jnp.float32), jnp.zeros_like(gates)
    d_ell, d_w, d_gates = pass_vjp(0, *zeros)
    d_ell, d_ws, d_gates = _further_passes(n, body, (
        d_ell, tuple(_Float32Sum.starting_at(d) for d in d_w), d_gates))
    with jax.named_scope(scopes.MOE_ROUTED):
        return (d_ell.astype(ell.dtype), tuple(s.high for s in d_ws), d_gates,
                None, None, None, None, None)


_run_passes.defvjp(_run_passes_fwd, _run_passes_bwd)


def _dispatch(u, p, top_k: int, held: Held, scaling: float, eps: float = 0.0,
              rule: Rule = Rule()):
    """Route u [T, D] and lay the pairs on held experts over the row buffer:
    (the membership [T, held], the HeldPairs, the layer's load — int32
    scalars under tracing/names.STEP_EXPERT_LOAD_ARGS: the passes the pairs
    fill, the pairs landed here, the fullest held expert's —, scored_choice's
    scores and choice of every token). A layer without a selection bias has
    no ``router_bias`` among its tensors."""
    T = u.shape[0]
    n_experts = p["router_w"].shape[-1]
    rows = row_buffer(T, n_experts, top_k, held.count)
    here, gates, choice = route(u, p["router_w"], p.get("router_bias"), top_k,
                                scaling, held, eps, rule, with_choice=True)
    pairs = held_pairs(here, gates, rows,
                       buffer_passes(T, n_experts, top_k, held.count))
    landed = jnp.sum(pairs.per_expert)
    return here, pairs, dict(zip(scopes.STEP_EXPERT_LOAD_ARGS, (
        -(-landed // rows), landed, jnp.max(pairs.per_expert)))), choice


def routed_experts(u: jax.Array, ell: jax.Array, p: Dict[str, Any], *,
                   top_k: int, held: Held, scaling: float, eps: float = 0.0,
                   form: Tuple[str, ...] = RELU2_EXPERT, rule: Rule = Rule(),
                   balance_rows: int = 0
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The held experts' part of the routed result, in the width the experts
    read and write: u [T, D] (what the router reads), ell [T, width] (what
    the experts read: a latent, or u itself) → (r [T, width] float32 = Σ over
    a token's chosen AND held experts of gate · f_e(ell), f_e by ``form``
    (the names of the experts' weights in ``p``: _pass_rows), and what the
    batch sent the held experts: _dispatch's load, three int32 scalars that
    were there to run the passes — for whoever hands them out of the step; a
    caller that drops them has paid nothing). One pass over the row buffer
    where the batch's pairs fit it (row_buffer); a batch with more runs the
    further passes it fills. ``rule`` says what kind of router this is
    (Rule); with ``balance_rows`` — the length of the batch's rows — the load
    holds the layer's balance_loss too (a float32 under
    names.STEP_BALANCE_LOSS), from the scores and the choice the dispatch
    made anyway: no second router product."""
    with _dispatch_scope():
        _, pairs, load, choice = _dispatch(u, p, top_k, held, scaling, eps,
                                           rule)
    if balance_rows:
        with jax.named_scope(scopes.MOE_ROUTED):
            load[scopes.STEP_BALANCE_LOSS] = balance_loss(*choice, top_k,
                                                          balance_rows)
    # (each weight row-major as it enters the passes: the backward's grouped
    # products read two of them transposed, and the first pass stands
    # outside any loop now, so without this the compiler lays the float32
    # parameters themselves — and their moments — out transposed, by copies
    # a step, to spare the transposing copy of a cast)
    with jax.named_scope(scopes.MOE_ROUTED):
        ws = tuple(with_layout_constraint(
            p[w], Layout(major_to_minor=tuple(range(p[w].ndim))))
            for w in form)
    # (no scope around the passes: each part of one enters `moe_routed`
    # itself, and the grouped products' kernels stand outside it — a trace's
    # reader adds the kernel's time to the scope's, once)
    return _run_passes(ell, ws, pairs.gates,
                       pairs.gate_rows, pairs.key, pairs.valid,
                       pairs.group_sizes, load["passes"]), load


def _beside_shared(routed: jax.Array, shared, u: jax.Array,
                   rows: int) -> jax.Array:
    """An expert layer's output [B, S, D] float32: ``routed`` (the held
    experts' part, of that size in any shape) + ``shared(u)``, the shared
    expert of every token of u [B, S, D]. ``shared`` works each row alone, so
    with ``rows`` < S (0: all of them) it takes the sequence that many rows
    at a time, each chunk its own ``checkpoint`` (as models/parts.py's
    in_row_chunks, which this module may not import, and for its reason)."""
    B, S, D = u.shape
    if rows in (0, S):
        return routed.reshape(B, S, D) + shared(u)
    chunks = u.reshape(B, S // rows, rows, D).swapaxes(0, 1)
    sh = lax.map(jax.checkpoint(shared), chunks)
    return routed.reshape(B, S, D) + sh.swapaxes(0, 1).reshape(B, S, D)


def latent_moe(u: jax.Array, p: Dict[str, Any], *, top_k: int, held: Held,
               scaling: float, shared_rows: int = 0
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """u [B, S, D] (normed, compute dtype) → (the layer's output [B, S, D] in
    float32: ``r·W_up`` for the held routed experts' r over ``u·W_down``,
    plus the shared expert ``relu(u·S1)²·S2``; routed_experts' load). ``p``
    holds one layer's tensors, LATENT_MOE_MATMUL_WEIGHTS in the compute
    dtype. With ``shared_rows`` < S the shared expert takes the sequence in
    chunks of that many rows, each its own ``checkpoint`` (as
    models/llama.py's MLP, and for its reason)."""
    B, S, D = u.shape
    ut = u.reshape(B * S, D)
    with jax.named_scope(scopes.MOE_LATENT):
        ell = checkpoint_name(jnp.einsum("td,dl->tl", ut, p["w_down"]),
                              scopes.RES_MOE_LATENT)
    r, load = routed_experts(ut, ell, p, top_k=top_k, held=held,
                             scaling=scaling)
    with jax.named_scope(scopes.MOE_LATENT):
        out = jnp.einsum("tl,ld->td", r.astype(u.dtype), p["w_up"],
                         preferred_element_type=jnp.float32)

    def shared(u_rows):
        # a dense MLP: under the block's `mlp` scope as any other
        with jax.named_scope(scopes.MLP), jax.named_scope(scopes.MOE_SHARED):
            h = checkpoint_name(
                jnp.einsum("bsd,df->bsf", u_rows, p["shared_w1"]),
                scopes.RES_MOE_SHARED_HIDDEN)
            return jnp.einsum("bsf,fd->bsd", _relu2(h), p["shared_w2"],
                              preferred_element_type=jnp.float32)

    return _beside_shared(out, shared, u, shared_rows), load


def gated_moe_init(rng: jax.Array, n_layers: int, d_model: int,
                   n_experts: int, held: int, d_expert: int, std: float,
                   out_std: float, param_dtype=jnp.float32, *,
                   selection_bias: bool = True, d_shared: int = 0,
                   shared_gate: bool = False) -> Dict[str, Any]:
    """``n_layers`` stacked layers of gated_moe: the router over all
    ``n_experts`` and its selection bias (a buffer, as latent_moe_init's;
    none without ``selection_bias``: a router balanced by a loss has none),
    ``held`` SiLU-gated experts at the model's width and, with ``d_shared``,
    one shared expert of the same form at that hidden width beside them —
    with ``shared_gate`` under a per-token gate, one column ``w_g``."""
    k = iter(jax.random.split(rng, 5))
    L = n_layers

    def normal(key, shape, s):
        return (jax.random.normal(key, shape) * s).astype(param_dtype)

    p = {
        "router_w": normal(next(k), (L, d_model, n_experts), std),
        "router_bias": normal(next(k), (L, n_experts), ROUTER_BIAS_STD),
        "w1": normal(next(k), (L, held, d_model, d_expert), std),
        "w3": normal(next(k), (L, held, d_model, d_expert), std),
        "w2": normal(next(k), (L, held, d_expert, d_model), out_std),
    }
    if not selection_bias:
        del p["router_bias"]
    if d_shared:
        # (keys of their own: the tensors above are drawn as they always were)
        ks = jax.random.split(jax.random.fold_in(rng, 1), 3)
        p.update(shared_w1=normal(ks[0], (L, d_model, d_shared), std),
                 shared_w3=normal(ks[1], (L, d_model, d_shared), std),
                 shared_w2=normal(ks[2], (L, d_shared, d_model), out_std))
    if shared_gate:
        p["shared_gate"] = normal(jax.random.fold_in(rng, 2),
                                  (L, d_model, 1), std)
    return p


def gated_moe_logical_axes() -> Dict[str, Any]:
    return {
        "router_w": ("layers", "embed", None),
        "router_bias": ("layers", None),
        "w1": ("layers", "expert", "embed", "mlp"),
        "w3": ("layers", "expert", "embed", "mlp"),
        "w2": ("layers", "expert", "mlp", "embed"),
        "shared_w1": ("layers", "embed", "mlp"),
        "shared_w3": ("layers", "embed", "mlp"),
        "shared_w2": ("layers", "mlp", "embed"),
        "shared_gate": ("layers", "embed", None),
    }


# the shared expert beside gated_moe's routed ones, by its weights' names
GATED_SHARED_EXPERT = ("shared_w1", "shared_w3", "shared_w2")


def gated_moe(u: jax.Array, p: Dict[str, Any], *, top_k: int, held: Held,
              scaling: float, eps: float = 0.0, rule: Rule = Rule(),
              balance: bool = False, shared_rows: int = 0
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """u [B, S, D] (normed, compute dtype) → (the held experts' part of the
    layer's output [B, S, D] in float32: Σ over a token's chosen AND held
    experts of gate · (silu(u·W1_e) ⊙ u·W3_e) · W2_e, at the model's width —
    no latent around the experts; routed_experts' load). ``p`` holds one
    layer's tensors, GATED_EXPERT's in the compute dtype. A layer whose
    tensors hold a shared expert (GATED_SHARED_EXPERT, compute dtype) adds
    ``(silu(u·S1) ⊙ u·S3)·S2`` of every token, whole on every chip — with
    ``shared_rows`` < S in chunks of that many rows, each its own
    ``checkpoint``, as latent_moe's —, times the token's ``sigmoid(u · w_g)``
    where the tensors hold ``shared_gate`` (w_g [D, 1], compute dtype; the
    gate in float32). ``balance``: the load holds the layer's balance_loss
    over rows of S tokens."""
    B, S, D = u.shape
    ut = u.reshape(B * S, D)
    # (a caller with the default router and no balance loss says what it
    # always said)
    more = {**({"rule": rule} if rule != Rule() else {}),
            **({"balance_rows": S} if balance else {})}
    r, load = routed_experts(ut, ut, p, top_k=top_k, held=held,
                             scaling=scaling, eps=eps, form=GATED_EXPERT,
                             **more)
    out = r.reshape(B, S, D)
    if "shared_w1" not in p:
        return out, load

    def shared(u_rows):
        # a dense MLP: under the block's `mlp` scope as any other
        with jax.named_scope(scopes.MLP), jax.named_scope(scopes.MOE_SHARED):
            gate = checkpoint_name(
                jnp.einsum("bsd,df->bsf", u_rows, p["shared_w1"]),
                scopes.RES_MOE_SHARED_GATE)
            up = checkpoint_name(
                jnp.einsum("bsd,df->bsf", u_rows, p["shared_w3"]),
                scopes.RES_MOE_SHARED_UP)
            y = jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                           p["shared_w2"], preferred_element_type=jnp.float32)
            if "shared_gate" not in p:
                return y
            return y * jax.nn.sigmoid(jnp.einsum(
                "bsd,do->bso", u_rows, p["shared_gate"],
                preferred_element_type=jnp.float32))

    return _beside_shared(out, shared, u, shared_rows), load


def chosen_experts(u: jax.Array, p: Dict[str, Any], top_k: int,
                   rule: Rule = Rule()) -> jax.Array:
    """u [T, D] → [T, n_experts] bool: the set route chooses for each token,
    for whoever compares it with another router's (a reference told the
    program's choice does not read a flipped near-tie as a wrong model)."""
    return scored_choice(u, p["router_w"], p.get("router_bias"), top_k,
                         rule.scoring)[1]


def step_load_static(tokens: int, n_experts: int, top_k: int,
                     held: Held) -> Dict[str, int]:
    """What every step's load of a layer is read against, for a batch of
    ``tokens`` (tracing/names.EXPERT_LOAD_STATIC_ARGS): the rows one pass
    over the buffer takes, the experts held."""
    return dict(zip(scopes.EXPERT_LOAD_STATIC_ARGS, (
        row_buffer(tokens, n_experts, top_k, held.count), held.count)))


def held_load(u: jax.Array, p: Dict[str, Any], *, top_k: int, held: Held,
              scaling: float, eps: float = 0.0, rule: Rule = Rule()
              ) -> Dict[str, jax.Array]:
    """What a batch sends the held experts of one layer (u [T, D], the
    layer's normed input): the numbers of the ``model/expert_load`` event."""
    here, pairs, load, _ = _dispatch(u, p, top_k, held, scaling, eps, rule)
    # (what a step hands out of itself, and from the same code)
    filled, landed, fullest = (load[k] for k in scopes.STEP_EXPERT_LOAD_ARGS)
    rows = pairs.key.shape[1]
    return {
        "tokens": jnp.asarray(u.shape[0], jnp.int32),
        "pairs": landed,
        "max_per_expert": fullest,
        "mean_per_expert": jnp.mean(pairs.per_expert.astype(jnp.float32)),
        "tokens_without_held_expert": jnp.sum(~jnp.any(here, axis=-1)),
        "buffer_rows": jnp.asarray(rows, jnp.int32),
        "buffer_passes": filled,
        # how full the passes that run are: pairs ÷ (passes · rows)
        "buffer_fill": landed / jnp.maximum(filled * rows, 1),
        "pairs_dropped": landed - jnp.sum(pairs.valid),
    }


def record_expert_loads(layers, loads) -> list:
    """The ``model/expert_load`` events (tracing/names.EXPERT_LOAD_ARGS) of
    ``loads`` — held_load's numbers, one an expert layer, on the host — under
    the ids ``layers`` gives them in that order: recorded, and returned."""
    component, name = scopes.EXPERT_LOAD.split("/")
    events = []
    for layer, load in zip(layers, loads, strict=True):
        # (numpy scalars off the host: a count an int, a mean or share a float)
        args = {"layer": layer, **{
            k: load[k].item() for k in scopes.EXPERT_LOAD_ARGS[1:]}}
        get_buffer().record_profile(name, component=component, args=args)
        events.append(args)
    return events
