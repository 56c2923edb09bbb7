"""``ops/moe``'s pairs and passes as they stood at PR 50 (commit 7408bd9),
frozen as the oracle of the tests that hold later forms of them bit-equal to
it: ``held_pairs`` with the flat table of gates, each pass's three lookups,
``_run_passes`` as one ``fori_loop(0, n)`` with every weight gradient summed
into float32 from zeros. Scopes and residual names left out: they change no
number."""

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import moe


class HeldPairs(NamedTuple):
    key: jax.Array            # [passes, rows] int32
    gates: jax.Array          # [held · T] float32: route's gates, expert-major
    valid: jax.Array          # [passes, rows] bool
    group_sizes: jax.Array    # [passes, held] int32
    per_expert: jax.Array     # [held] int32


def held_pairs(here, gates, rows, passes):
    T, held = here.shape
    none = held * T
    per_expert = jnp.sum(here, axis=0, dtype=jnp.int32)
    place = jnp.arange(none, dtype=jnp.int32).reshape(held, T)
    key = jnp.sort(jnp.where(here.T, place, none).reshape(none), stable=False)
    total = passes * rows
    key = jnp.pad(key, (0, max(0, total - none)))[:total]
    valid = jnp.arange(total) < jnp.sum(per_expert)
    lo = (jnp.arange(passes) * rows)[:, None]
    ends = jnp.clip(jnp.cumsum(per_expert)[None, :], lo, lo + rows) - lo
    return HeldPairs(
        key=jnp.where(valid, key, 0).reshape(passes, rows),
        gates=gates.T.reshape(none),
        valid=valid.reshape(passes, rows),
        group_sizes=jnp.diff(ends, axis=1, prepend=0).astype(jnp.int32),
        per_expert=per_expert)


def pass_rows(x, ws, gate, valid, group_sizes):
    w1, w2 = ws[0], ws[-1]
    x = jnp.where(valid[:, None], x, 0)
    h = lax.ragged_dot(x, w1, group_sizes, preferred_element_type=x.dtype)
    if len(ws) == 3:
        up = lax.ragged_dot(x, ws[1], group_sizes,
                            preferred_element_type=x.dtype)
        a = (jax.nn.silu(jnp.where(valid[:, None], h, 0))
             * jnp.where(valid[:, None], up, 0))
    else:
        a = jnp.square(jax.nn.relu(jnp.where(valid[:, None], h, 0)))
    o = lax.ragged_dot(a, w2, group_sizes, preferred_element_type=x.dtype)
    return jnp.where(valid[:, None], o, 0).astype(jnp.float32) * gate[:, None]


def looked_up(ell, gates, key):
    token = key % ell.shape[0]
    return token, ell[token], gates[key]


@jax.custom_vjp
def run_passes(ell, ws, gates, key, valid, group_sizes, n):
    def body(i, r):
        token, x, gate = looked_up(ell, gates, key[i])
        return r.at[token].add(
            pass_rows(x, ws, gate, valid[i], group_sizes[i]))

    return lax.fori_loop(0, n, body, jnp.zeros(ell.shape, jnp.float32))


def _run_passes_fwd(ell, ws, gates, key, valid, group_sizes, n):
    return (run_passes(ell, ws, gates, key, valid, group_sizes, n),
            (ell, ws, gates, key, valid, group_sizes, n))


def _run_passes_bwd(res, d_r):
    ell, ws, gates, key, valid, group_sizes, n = res

    def body(i, sums):
        d_ell, d_ws, d_gates = sums
        token, x, gate = looked_up(ell, gates, key[i])
        _, vjp = jax.vjp(
            lambda x, ws, g: pass_rows(x, ws, g, valid[i], group_sizes[i]),
            x, ws, gate)
        d_x, d_w, d_gate = vjp(d_r[token])
        d_ell = d_ell.at[token].add(d_x.astype(jnp.float32))
        d_gates = d_gates.at[key[i]].add(d_gate)
        return (d_ell, tuple(s + d.astype(jnp.float32)
                             for s, d in zip(d_ws, d_w)), d_gates)

    sums = lax.fori_loop(0, n, body, (
        jnp.zeros(ell.shape, jnp.float32),
        tuple(jnp.zeros(w.shape, jnp.float32) for w in ws),
        jnp.zeros_like(gates)))
    return (sums[0].astype(ell.dtype),
            tuple(s.astype(w.dtype) for s, w in zip(sums[1], ws)), sums[2],
            None, None, None, None)


run_passes.defvjp(_run_passes_fwd, _run_passes_bwd)


def dispatch(u, p, top_k, held, scaling, eps=0.0):
    """(the membership [T, held], the HeldPairs above, the passes they
    fill) by the routing and the row buffer that stand (``moe.route``,
    ``row_buffer``, ``buffer_passes``: no later PR has touched them)."""
    T = u.shape[0]
    n_experts = p["router_w"].shape[-1]
    rows = moe.row_buffer(T, n_experts, top_k, held.count)
    here, gates = moe.route(u, p["router_w"], p["router_bias"], top_k,
                            scaling, held, eps)
    pairs = held_pairs(here, gates, rows,
                       moe.buffer_passes(T, n_experts, top_k, held.count))
    return here, pairs, -(-jnp.sum(pairs.per_expert) // rows)


def routed_experts(u, ell, p, *, top_k, held, scaling, eps=0.0,
                   form=moe.RELU2_EXPERT):
    _, pairs, filled = dispatch(u, p, top_k, held, scaling, eps)
    return run_passes(ell, tuple(p[w] for w in form), pairs.gates, pairs.key,
                      pairs.valid, pairs.group_sizes, filled)
