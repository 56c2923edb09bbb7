"""The Qwen3-Next family (``ray_tpu/models/qwen3_next.py``, the gated delta
rule's kernel pair ``ops/gated_delta.py``, ``ops/moe``'s gated shared expert)
against its plain float32 reference
(``benchmarks/families/qwen3_next_reference.py``, the delta rule token by
token): the whole step's loss — balance loss included — and every gradient,
both layer kinds and the expert half; the scan's kernels against the XLA
chunk form against the recurrence, forward and all five gradients, at a row
that is not whole chunks with two value heads a key head; the shares of an
expert half tied to the uncut layer; the prefix property; the shared gate
leaving a layer without one as it was; the published column order mapped
onto the program's; the cell's parameter count and the family's arithmetic;
the meshes it refuses; the comparison that decides ``correct`` with its
controls — and the benchmark's new entries: each reader this PR adds names
the new cell alone, imports nothing of ``ray_tpu`` at module level and reads
nothing, without raising, from another cell's recorded trace."""

import ast
import collections
import dataclasses
import importlib
import json
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.ad_checkpoint import checkpoint_name

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import qwen3_next as family  # noqa: E402
from benchmarks.families import qwen3_next_reference as reference  # noqa: E402
from ray_tpu.models import blocks, parts, qwen3_next as qn  # noqa: E402
from ray_tpu.ops import delta_pointwise, gated_delta, moe  # noqa: E402
from ray_tpu.tracing import names  # noqa: E402

CELL = "qwen3-next-80b-a3b-l4.dataset"
CONFIG = "qwen3-next-80b-a3b-l4"
NEW_READERS = ("qwen3_next_mfu_device", "delta_mixer_ms_per_step",
               "gated_delta_ms_per_step", "gated_delta_roofline",
               "qwen3_next_flash_attn_roofline")
# accepted readers of a scope, a kernel or a counter this family's step has
SHARED_READERS = ("flash_fwd_ms_per_step", "flash_bwd_ms_per_step",
                  "moe_routed_ms_per_step", "moe_dispatch_ms_per_step",
                  "moe_shared_ms_per_step", "moe_further_passes_ms_per_step",
                  "moe_passes_per_step", "moe_multi_pass_steps",
                  "moe_load_imbalance", "moe_aux_ms_per_step")


def _batch(cfg, rows=2, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, 64, (rows, cfg.seq_len)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    targets[:, -1] = -1
    return tokens, targets


def _params(cfg, seed=0):
    """Seeded weights with every gain off its drawn value, so that a gain
    the program forgot would show."""
    params = qn.init(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def moved(path, x):
        if getattr(path[-1], "key", "").endswith("norm"):
            return x + 0.1 * jax.random.normal(next(keys), x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(moved, params)


def _sizes(cfg, **switches):
    return family.reference_sizes(cfg, **switches)


def _benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _rel(a, b):
    a, b = (np.asarray(t, np.float32) for t in (a, b))
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


# ------------------------------------------------- program against reference
@pytest.mark.parametrize("impl,remat", [("xla", False), ("pallas", True)],
                         ids=["xla-no-remat", "pallas-remat"])
def test_loss_and_every_gradient_equal_the_reference_in_float32(impl, remat):
    cfg = qn.qwen3_next_tiny(dtype=jnp.float32, attention_impl=impl,
                             remat=remat)
    assert cfg.pattern == "LLLF"
    params, (tokens, targets) = _params(cfg), _batch(cfg)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: qn.loss_fn(p, tokens, targets, cfg)))(params)
        sets = [s.reshape(2, cfg.seq_len, cfg.n_experts)
                for s in qn.chosen_experts(params, tokens, cfg)]
        (ref, reports), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_and_routing(
                p, tokens, targets, _sizes(cfg), sets)[:2], has_aux=True))(
            params)
    assert [int(r["differ"]) for r in reports] == [0] * 4
    assert float(loss) == pytest.approx(float(ref), rel=2e-6)
    flat, ref_flat = (jax.tree_util.tree_leaves_with_path(g)
                      for g in (grads, ref_grads))
    assert len(flat) == len(ref_flat) == len(jax.tree.leaves(params))
    for (path, g), (_, r) in zip(flat, ref_flat):
        # (the two vectors a value head get 1e-5-sized gradients: sums of
        # cancelling float32 terms)
        loose = getattr(path[-1], "key", "") in ("A_log", "dt_bias")
        assert float(jnp.linalg.norm(r)) > 0, path
        assert _rel(g, r) < (2e-3 if loose else 2e-5), (path, _rel(g, r))


def test_the_reference_in_blocks_is_the_reference_whole(monkeypatch):
    """The reference's blocks are memory, not meaning."""
    cfg = qn.qwen3_next_tiny(dtype=jnp.float32)
    params, (tokens, targets) = _params(cfg), _batch(cfg)
    with jax.default_matmul_precision("highest"):
        whole = reference.loss(params, tokens, targets, _sizes(cfg))
        monkeypatch.setattr(reference, "SCAN_BLOCK", 12)
        monkeypatch.setattr(reference, "TOKEN_BLOCK", 16)
        monkeypatch.setattr(reference, "QUERY_BLOCK", 8)
        cut = reference.loss(params, tokens, targets, _sizes(cfg))
    assert float(cut) == pytest.approx(float(whole), rel=1e-6)


def test_the_published_column_order_maps_onto_the_programs():
    """``fix_query_key_value_ordering`` groups the fused projections' outputs
    by key head; the program holds them q, k, v, z and b, a. Permuting the
    program's columns into the published order and telling the reference so
    changes no number."""
    cfg = qn.qwen3_next_tiny(dtype=jnp.float32)
    params, (tokens, targets) = _params(cfg), _batch(cfg)
    hk, hv = cfg.linear_key_heads, cfg.linear_value_heads
    dk, dv, r = cfg.linear_key_dim, cfg.linear_value_dim, hv // hk
    kw, vw = cfg.key_width, cfg.value_width
    cols = np.arange(2 * kw + 2 * vw)
    q, k = (cols[i * kw:(i + 1) * kw].reshape(hk, dk) for i in (0, 1))
    v, z = (cols[2 * kw + i * vw:2 * kw + (i + 1) * vw].reshape(hk, r * dv)
            for i in (0, 1))
    qkvz = np.concatenate([q, k, v, z], axis=1).reshape(-1)
    ba = np.concatenate([np.arange(hv).reshape(hk, r),
                         hv + np.arange(hv).reshape(hk, r)], axis=1).reshape(-1)
    published = jax.tree_util.tree_map_with_path(
        lambda path, x: (x[..., qkvz] if path[-1].key == "w_qkvz" else
                         x[..., ba] if path[-1].key == "w_ba" else x), params)
    with jax.default_matmul_precision("highest"):
        flat = reference.loss(params, tokens, targets, _sizes(cfg))
        grouped = reference.loss(published, tokens, targets,
                                 _sizes(cfg, order="published"))
        wrong = reference.loss(params, tokens, targets,
                               _sizes(cfg, order="published"))
    assert float(grouped) == pytest.approx(float(flat), rel=1e-6)
    assert abs(float(wrong) - float(flat)) > 1e-5 * float(flat)


# ------------------------------------------------------------ the delta rule
def _recurrence(q, k, v, g, beta):
    """The module docstring's recurrence, token by token, float32."""
    r = v.shape[2] // q.shape[2]
    qf, kf = (jnp.repeat(t.astype(jnp.float32), r, axis=2) for t in (q, k))

    def token(S, xs):
        qt, kt, vt, gt, bt = xs
        S = jnp.exp(gt)[..., None, None] * S
        d = bt[..., None] * (vt - jnp.sum(S * kt[..., None], axis=-2))
        S = S + kt[..., None] * d[..., None, :]
        return S, jnp.sum(S * qt[..., None], axis=-2)

    xs = tuple(jnp.moveaxis(t, 1, 0)
               for t in (qf, kf, v.astype(jnp.float32), g, beta))
    B, _, Hv, dv = v.shape
    _, o = lax.scan(token, jnp.zeros((B, Hv, q.shape[-1], dv)), xs)
    return jnp.moveaxis(o, 0, 1)


def _scan_inputs(B, S, Hk, Hv, dk, dv, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(ks[i], (B, S, Hk, dk)) for i in (0, 1))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, Hv, dv))
    # heads that forget within a token and heads that hold the row
    A = jax.random.uniform(ks[3], (Hv,), minval=0.01, maxval=16.0)
    g = -A * jax.nn.softplus(jax.random.normal(ks[4], (B, S, Hv)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, S, Hv)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_the_kernels_the_chunk_form_and_the_recurrence_agree(dtype, tol):
    """Forward and all five gradients, at a row of 50 tokens in chunks of 16
    (three whole and a part) with two value heads a key head."""
    args = _scan_inputs(2, 50, 2, 4, 16, 16, dtype)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    forms = {
        "recurrence": _recurrence,
        "chunked": lambda *a: gated_delta.gated_delta_chunked(*a, chunk=16),
        "kernels": lambda *a: gated_delta.gated_delta_scan(
            *a, chunk=16, impl="pallas")}
    got = {}
    for name, fn in forms.items():
        out, grads = jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight),
            argnums=(0, 1, 2, 3, 4))(*args)
        got[name] = (fn(*args),) + grads
    for name in ("chunked", "kernels"):
        for what, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"),
                              got[name], got["recurrence"]):
            assert a.shape == b.shape
            assert _rel(a, b) < tol, (name, what, _rel(a, b))
    tiled = {d["kernel"]: d for d in gated_delta.delta_tiling_decisions()
             if (d["S"], d["C"], d["dk"]) == (64, 16, 16)}
    # both value heads of a key head in one grid step, of all three kernels
    assert set(tiled) == {"solve", "fwd", "bwd"}
    assert all(d["head_tile"] == 2 and d["value_heads_per_key"] == 2
               for d in tiled.values())


def test_a_solve_in_one_bf16_pass_does_not_pass_for_float32(monkeypatch):
    """The control the comparison with the reference cannot be (PERF.md §6,
    PR 61: at the cell's sizes the bf16 stream's error hides it): with the
    solve's float32 products cut to ONE bf16 pass — the family's
    ``solve_bf16`` — the kernels leave the recurrence by a hundred times
    the tolerance they are held to."""
    q, k, v, g, beta = _scan_inputs(1, 64, 1, 2, 16, 16, jnp.float32, seed=3)
    # keys that look alike and slow gates: a chunk's tokens correct each
    # other's writes, so the solve is far from the identity
    k = k + 2.0 * k[:, :1]
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    args = (q, k, v, 0.05 * g, beta)
    want = _recurrence(*args)
    kernels = lambda: gated_delta.gated_delta_scan(*args, chunk=32,
                                                   impl="pallas")
    assert _rel(kernels(), want) < 1e-5
    monkeypatch.setattr(gated_delta, "_mm32",
                        lambda a, b: gated_delta._nn(a[0], b[0]))
    gated_delta._chunks_call.clear_cache()
    try:
        assert _rel(kernels(), want) > 3e-4
    finally:
        monkeypatch.undo()
        gated_delta._chunks_call.clear_cache()


def test_the_scan_repeats_no_key_head_and_names_its_kernels():
    args = _scan_inputs(1, 32, 2, 4, 16, 16, jnp.float32)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(gated_delta.gated_delta_scan(
            *a, chunk=16, impl="pallas"))))(*args))
    assert names.GATED_DELTA_FWD_KERNEL in text
    assert names.GATED_DELTA_BWD_KERNEL in text
    assert names.GATED_DELTA_FWD_KERNEL in names.KERNELS
    assert names.GATED_DELTA_BWD_KERNEL in names.KERNELS
    # q and k enter the kernels at their own 2 heads x 16 channels
    assert "f32[1,32,32]" in text and "repeat" not in text
    assert {names.DELTA_MIXER, names.GATED_DELTA,
            names.GATED_ATTN_GATE} <= set(names.SCOPES)


def _kernel_calls(jaxpr):
    """The pallas_call equations of a jaxpr, nested ones included, by the
    kernel's name."""
    found = collections.Counter()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found += _kernel_calls(inner)
    return found


@pytest.mark.parametrize("kept,solves,forwards", [
    ("delta_x", 1, 2), ("nothing", 2, 2), ("no_checkpoint", 1, 1),
    ("delta_x_states_o", 1, 1)])
def test_a_step_solves_once_where_the_policy_keeps_x(kept, solves, forwards):
    """The gradient's kernel calls: X is a value of the step with a name, so
    a checkpoint policy that holds it drops the recompute's solve (the
    forward kernel still runs twice: the backward needs the states); one
    that holds nothing solves twice; without a checkpoint, or with the
    states and o kept besides, every kernel runs once."""
    args = _scan_inputs(1, 32, 2, 4, 16, 16, jnp.float32)

    def loss(*a):
        o = gated_delta.gated_delta_scan(*a, chunk=16, impl="pallas")
        return jnp.sum(checkpoint_name(o, names.RES_DELTA_O))

    policies = jax.checkpoint_policies
    policy = {
        "delta_x": policies.save_only_these_names(names.RES_DELTA_X),
        "nothing": policies.nothing_saveable,
        "delta_x_states_o": policies.save_only_these_names(
            names.RES_DELTA_X, names.RES_DELTA_STATES, names.RES_DELTA_O),
    }.get(kept)
    fn = jax.checkpoint(loss, policy=policy) if policy else loss
    calls = _kernel_calls(jax.make_jaxpr(
        jax.grad(fn, argnums=(0, 1, 2, 3, 4)))(*args).jaxpr)
    assert calls == {names.GATED_DELTA_SOLVE_KERNEL: solves,
                     names.GATED_DELTA_FWD_KERNEL: forwards,
                     names.GATED_DELTA_BWD_KERNEL: 1}


@pytest.mark.parametrize("r,S,head_tile", [(1, 64, 1), (2, 64, 2), (4, 64, 4),
                                           (2, 40, 2)],
                         ids=["ht1", "ht2", "ht4", "padded-row"])
def test_the_solve_kernel_writes_the_chunk_forms_inverse(r, S, head_tile):
    """The solve kernel's X against ``_solve`` on the XLA chunk form's A —
    float32 K Kᵀ ⊙ Γ by β, a chunk and value head at a time — in bf16, the
    dtype the other two kernels read it in: equal to the rounding of the
    cast, at one, two and four value heads stacked a grid step and at a row
    padded to whole chunks; outside a head's own block nothing is stored."""
    C, Hk, dk = 16, 2, 16
    q, k, v, g, beta = _scan_inputs(2, S, Hk, r * Hk, dk, 16, jnp.bfloat16,
                                    seed=5)
    q, k, v, g, beta = gated_delta._padded(q, k, v, g, beta, C)
    cum = gated_delta._cumulative(g, C)
    B, Sp, Hv = cum.shape
    nc = Sp // C
    X = gated_delta._chunks_call(
        "solve", *gated_delta._merged(q, k, v), cum, beta, Hk, C, True)
    assert X.dtype == jnp.bfloat16
    assert X.shape == (B, Hv // head_tile, nc, C, head_tile * C)
    assert {d["head_tile"] for d in gated_delta.delta_tiling_decisions()
            if (d["kernel"], d["S"], d["C"], d["value_heads_per_key"]) == (
                "solve", Sp, C, r)} == {head_tile}
    # [B, T, nc, C, ht, C] → a value head's [C, C] a chunk
    got = X.reshape(B, Hv // head_tile, nc, C, head_tile, C).transpose(
        0, 2, 1, 4, 3, 5).reshape(B, nc, Hv, C, C).astype(jnp.float32)
    i = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    K = jnp.repeat(k.astype(jnp.float32), r, axis=2).reshape(
        B, nc, C, Hv, dk)
    c = cum.reshape(B, nc, C, Hv)
    b = beta.reshape(B, nc, C, Hv)

    def one(K, c, b):       # [C, dk], [C], [C] → [C, C]
        G = jnp.exp(jnp.where(i >= j, c[:, None] - c[None, :], 0.0))
        A = jnp.where(i > j, b[:, None] * (jnp.dot(
            K, K.T, precision=lax.Precision.HIGHEST) * G), 0.0)
        return gated_delta._solve(A, i, j, C)

    want = jax.vmap(jax.vmap(jax.vmap(one, in_axes=(1, 1, 1))))(K, c, b)
    assert float(jnp.max(jnp.abs(want))) > 1.0 - 1e-6
    # bf16 keeps 8 bits: half a unit in the last place of the largest entry
    assert float(jnp.max(jnp.abs(got - want))) <= 2.0 ** -8 * float(
        jnp.max(jnp.abs(want)))
    assert _rel(got, want) < 3e-3


def test_the_solves_time_stays_in_the_rooflines_denominator():
    """``gated_delta_roofline`` divides by ``kernel_ms_per_step`` of
    ``gated_delta_fwd`` + ``gated_delta_bwd``, and the trace's reader names
    an instruction's kernel by the FIRST of ``names.KERNELS`` inside its HLO
    name: the solve's name begins with the forward's and stands after it,
    so its time is the forward's there — a roofline that rose because the
    solve's time fell out of it would be a false reading."""
    from benchmarks.harness import program_trace

    assert names.GATED_DELTA_SOLVE_KERNEL.startswith(
        names.GATED_DELTA_FWD_KERNEL)
    order = names.KERNELS.index
    assert order(names.GATED_DELTA_FWD_KERNEL) < order(
        names.GATED_DELTA_SOLVE_KERNEL)
    path = ("jit(step)/jit(main)/while/body/checkpoint/block/delta_mixer/"
            "gated_delta/jit(_chunks_call)/pallas_call")
    for hlo, kernel in ((f"{names.GATED_DELTA_SOLVE_KERNEL}.14",
                         names.GATED_DELTA_FWD_KERNEL),
                        (f"{names.GATED_DELTA_FWD_KERNEL}.27",
                         names.GATED_DELTA_FWD_KERNEL),
                        (f"{names.GATED_DELTA_BWD_KERNEL}.12",
                         names.GATED_DELTA_BWD_KERNEL)):
        where = program_trace.classify(path, hlo, "mosaic")
        assert where["kernel"] == kernel
        assert names.GATED_DELTA in where["scopes"]


def test_the_tiling_rule_reads_the_shapes():
    cell = gated_delta.choose_delta_tiling("fwd", 4, 8192, 64, 16, 2, 128,
                                           128, 2)
    assert cell.head_tile == 2          # 2 x 64 rows: one MXU pass tall
    # the solve's grid step has no state and no [N, d_v] value: the same
    # stack and key heads in under half the forward's estimate
    solve = gated_delta.choose_delta_tiling("solve", 4, 8192, 64, 16, 2, 128,
                                            128, 2)
    assert (solve.head_tile, solve.key_tile) == (2, cell.key_tile) == (2, 4)
    assert solve.vmem_estimate < cell.vmem_estimate // 2
    assert {"fwd", "solve"} <= {
        d["kernel"] for d in gated_delta.delta_tiling_decisions()
        if (d["rows"], d["S"], d["C"], d["key_heads"]) == (4, 8192, 64, 16)}
    # one head_tile a scan (X is laid out by it): the backward's room decides
    assert {gated_delta.choose_delta_tiling(
        kernel, 4, 8192, 128, 16, 2, 128, 128, 2).head_tile
        for kernel in ("solve", "fwd", "bwd")} == {1}
    # what making X again costs: K Kᵀ and five levels of two three-pass
    # products at N = 128 — ~130 MFLOP, 7.9 kFLOP a byte of X
    assert gated_delta.solve_flops(64, 2, 128) == 2 * 31 * 128 ** 3
    assert gated_delta.solve_flops(64, 4, 128) == 2 * gated_delta.solve_flops(
        64, 2, 128)
    assert gated_delta.choose_delta_tiling(
        "fwd", 1, 64, 16, 2, 4, 16, 16, 4).head_tile == 4
    with pytest.raises(ValueError, match="does not fit VMEM"):
        gated_delta.choose_delta_tiling("bwd", 1, 8192, 2048, 1, 1, 128, 128, 4)
    with pytest.raises(ValueError, match="unknown delta-rule kernel 'both'"):
        gated_delta.choose_delta_tiling("both", 1, 64, 16, 2, 2, 16, 16, 4)
    event = gated_delta.delta_tiling_decisions()[-1]
    assert tuple(event) == names.DELTA_TILING_ARGS


def test_a_rows_first_outputs_do_not_depend_on_what_follows():
    """The conv, the scan and attention are causal: the logits of a row's
    first 29 tokens stay when the rest of the row changes."""
    cfg = qn.qwen3_next_tiny(dtype=jnp.float32, attention_impl="pallas")
    params, (tokens, _) = _params(cfg), _batch(cfg, rows=1)
    other = tokens.copy()
    other[:, 29:] = (other[:, 29:] + 7) % 64
    forward = jax.jit(lambda t: qn.forward(params, t, cfg))
    with jax.default_matmul_precision("highest"):
        a, b = forward(tokens), forward(other)
    assert float(jnp.max(jnp.abs(a[:, :29] - b[:, :29]))) < 1e-5
    assert float(jnp.max(jnp.abs(a[:, 29:] - b[:, 29:]))) > 1e-3


# ------------------------------- the elementwise work around the scan (PR 63)
def _plain_conv_norm(x, w, heads, scale):
    """The first pair's definition: models/qwen3_next.py's plain lines."""
    a = qn._conv_silu(x, w, x.dtype)
    return (qn._l2norm(a, heads) * scale).astype(x.dtype) if heads else a


def _close(got, want, dtype):
    """≤ 1e-6 of the tensor's largest value in float32 (the arithmetic's own
    error, where terms cancel); in bf16 one ulp OF EACH VALUE's binade (2⁻⁷
    of it) besides."""
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    tol = 1e-6 * max(1.0, float(np.max(np.abs(want))))
    if dtype == jnp.bfloat16:
        tol = tol + 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30)))
                            - 7)
    return bool(np.all(np.abs(got - want) <= tol))


def _with_grads(fn, *args, dy):
    y, vjp = jax.vjp(fn, *args)
    return (y,) + vjp(dy)


# S = 300 is 19 tiles of 16 tokens (the rule takes a divisor of the padded
# 304): every tile but the first reads the one before it, every one but the
# last the one after; S = 40 is one tile of 48 with 8 tokens of padding
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("B,S,heads,d", [(2, 300, 2, 128), (1, 40, 4, 16),
                                         (2, 50, 0, 192)],
                         ids=["2-heads-19-tiles", "4-narrow-heads", "no-norm"])
def test_the_conv_norm_pair_is_the_plain_conv_silu_and_l2norm(B, S, heads, d,
                                                              dtype):
    """``delta_conv_norm_fwd`` / ``_bwd`` (interpreted here) against
    ``_conv_silu`` + ``_l2norm`` and ``jax.grad`` of them: the output, the
    input's gradient and the taps'. K = 4."""
    C = max(heads, 1) * d
    k = jax.random.split(jax.random.PRNGKey(S), 3)
    x = jax.random.normal(k[0], (B, S, C), jnp.float32).astype(dtype)
    dy = jax.random.normal(k[1], (B, S, C), jnp.float32).astype(dtype)
    w = jax.random.normal(k[2], (4, C), jnp.float32) * 0.5
    scale = d ** -0.5 if heads else 1.0
    got = _with_grads(lambda x, w: delta_pointwise.conv_silu_norm(
        x, w, heads, scale, interpret=True), x, w, dy=dy)
    want = _with_grads(lambda x, w: _plain_conv_norm(x, w, heads, scale),
                       x, w, dy=dy)
    assert [t.dtype for t in got] == [dtype, dtype, jnp.float32]
    assert _close(got[0], want[0], dtype)
    # (the plain form's d x is a sum of K bf16-rounded terms: two ulps)
    assert _close(got[1], want[1], dtype) or dtype == jnp.bfloat16 and float(
        np.max(np.abs(np.asarray(got[1] - want[1], np.float32)))) <= 2.0 ** -6 * float(
            jnp.max(jnp.abs(want[1])))
    assert _rel(got[2], want[2]) < (1e-6 if dtype == jnp.float32 else 2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("B,S,heads,d", [(2, 300, 2, 128), (1, 40, 4, 16)],
                         ids=["2-heads-19-tiles", "4-narrow-heads"])
def test_the_gate_norm_pair_is_the_plain_gated_rmsnorm(B, S, heads, d, dtype):
    """``delta_gate_norm_fwd`` / ``_bwd`` against the mixer's plain lines
    after the scan and ``jax.grad`` of them: y, d o, d z and d gain."""
    k = jax.random.split(jax.random.PRNGKey(S + 1), 4)
    o, z, dy = (jax.random.normal(key, (B, S, heads * d), jnp.float32
                                  ).astype(dtype) for key in k[:3])
    gain = 1 + 0.1 * jax.random.normal(k[3], (d,), jnp.float32)
    got = _with_grads(lambda *a: delta_pointwise.gated_rmsnorm(
        *a, 1e-6, interpret=True), o, z, gain, dy=dy)
    want = _with_grads(lambda *a: qn._gated_rmsnorm(*a, 1e-6),
                       o, z, gain, dy=dy)
    assert [t.dtype for t in got] == [dtype] * 3 + [jnp.float32]
    assert all(_close(g, w, dtype) for g, w in zip(got[:3], want[:3]))
    assert _rel(got[3], want[3]) < (1e-6 if dtype == jnp.float32 else 2e-3)


def test_a_rows_first_tokens_see_zeros_and_a_tile_sees_its_neighbours(
        monkeypatch):
    """The conv by its definition, token by token in numpy: a row's first
    K − 1 tokens read zeros before the row — not the row before it in the
    batch —, a tile's first tokens the tile before (the halo), and the
    gradient of a tile's last tokens the tile after."""
    B, S, C, K = 2, 72, 128, 4
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    x, dy = (np.asarray(jax.random.normal(key, (B, S, C))) for key in k[:2])
    w = np.asarray(jax.random.normal(k[2], (K, C))) * 0.5
    padded = np.concatenate([np.zeros((B, K - 1, C), np.float32), x], 1)
    c = sum(padded[:, j:j + S] * w[j] for j in range(K))
    sig = 1 / (1 + np.exp(-c))
    dc = dy * sig * (1 + c * (1 - sig))
    after = np.concatenate([dc, np.zeros((B, K - 1, C), np.float32)], 1)
    dx = sum(after[:, K - 1 - j:K - 1 - j + S] * w[j] for j in range(K))
    dw = np.stack([(dc * padded[:, j:j + S]).sum((0, 1)) for j in range(K)])
    # 72 tokens are 80 with the padding: five tiles of 16 under this target
    # (the calls are jitted by shape: none traced under another target stays)
    monkeypatch.setattr(delta_pointwise, "_TARGET_TOKENS", 16)
    monkeypatch.setattr(delta_pointwise, "_decisions", {})
    delta_pointwise._conv_norm_call.clear_cache()
    got = _with_grads(lambda x, w: delta_pointwise.conv_silu_norm(
        x, w, interpret=True), jnp.asarray(x), jnp.asarray(w),
        dy=jnp.asarray(dy))
    delta_pointwise._conv_norm_call.clear_cache()
    assert [(d["kernel"], d["S"], d["token_tile"])
            for d in delta_pointwise.pointwise_tiling_decisions()] == [
        ("conv_norm_fwd", 80, 16), ("conv_norm_bwd", 80, 16)]
    for mine, theirs in zip(got, (c * sig, dx, dw)):
        assert _rel(mine, theirs) < 1e-6
    assert np.allclose(got[0][:, 0], (x[:, 0] * w[K - 1]) * sig[:, 0],
                       rtol=1e-5, atol=1e-7)


def test_the_pointwise_tiling_rule_reads_the_shapes():
    """At the cell's shapes: 256 tokens of q, k and v a grid step, 128 of o
    and z (the gate pair's backward holds five blocks of 4,096 channels),
    a head's lanes at a time, every estimate inside Mosaic's default limit."""
    from ray_tpu.ops.attention import VMEM_BUDGET_BYTES

    rule = delta_pointwise.choose_pointwise_tiling
    cell = {(kernel, C, heads): rule(kernel, 4, 8192, C, heads, 2)
            for kernel, C, heads in (
                ("conv_norm_fwd", 2048, 16), ("conv_norm_bwd", 2048, 16),
                ("conv_norm_fwd", 4096, 0), ("conv_norm_bwd", 4096, 0),
                ("gate_norm_fwd", 4096, 32), ("gate_norm_bwd", 4096, 32))}
    assert {key: t[:2] for key, t in cell.items()} == {
        ("conv_norm_fwd", 2048, 16): (256, 128),
        ("conv_norm_bwd", 2048, 16): (256, 128),
        ("conv_norm_fwd", 4096, 0): (256, 128),
        ("conv_norm_bwd", 4096, 0): (256, 128),
        ("gate_norm_fwd", 4096, 32): (128, 128),
        ("gate_norm_bwd", 4096, 32): (128, 128)}
    assert all(t.vmem_estimate <= VMEM_BUDGET_BYTES for t in cell.values())
    # a pair has one tile, the backward's; a forward's estimate is its own
    assert cell["conv_norm_fwd", 4096, 0].vmem_estimate < cell[
        "conv_norm_bwd", 4096, 0].vmem_estimate
    # with no norm a narrow tensor takes more lanes at a time
    assert rule("conv_norm_fwd", 4, 8192, 1024, 0, 2)[:2] == (256, 512)
    event = delta_pointwise.pointwise_tiling_decisions()[-1]
    assert tuple(event) == names.DELTA_POINTWISE_TILING_ARGS
    assert names.DELTA_TILING_ARGS != names.DELTA_POINTWISE_TILING_ARGS
    with pytest.raises(ValueError, match="do not fit VMEM"):
        rule("gate_norm_bwd", 1, 8192, 2 ** 17, 1024, 4)
    with pytest.raises(ValueError, match="unknown delta-mixer kernel 'fwd'"):
        rule("fwd", 1, 64, 128, 1, 4)


def test_the_mixers_gradient_runs_the_four_kernels_and_names_them():
    """The delta mixer's gradient under a checkpoint that keeps nothing:
    q, k and v each through the first pair's forward twice (the recompute)
    and its backward once, the scan's result and z through the second's. No
    new name holds an older kernel's, and no older one a new one's — a
    trace's reader finds a kernel by the first of ``names.KERNELS`` inside
    an instruction's name."""
    cfg = qn.qwen3_next_tiny(dtype=jnp.float32, attention_impl="pallas")
    p = {**_first_layer(_params(cfg)), }
    u = jax.random.normal(jax.random.PRNGKey(0), (1, cfg.seq_len, cfg.d_model))
    loss = jax.checkpoint(lambda u, p: jnp.sum(qn.delta_mixer(u, p, cfg)),
                          policy=jax.checkpoint_policies.nothing_saveable)
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(u, p)
    calls = _kernel_calls(jaxpr.jaxpr)
    new = (names.DELTA_CONV_NORM_FWD_KERNEL, names.DELTA_CONV_NORM_BWD_KERNEL,
           names.DELTA_GATE_NORM_FWD_KERNEL, names.DELTA_GATE_NORM_BWD_KERNEL)
    assert [calls[name] for name in new] == [6, 3, 2, 1]
    text = str(jaxpr)
    assert "bsc,ch->bsh" not in text and "bsh,ch->bsc" not in text
    first = names.KERNELS.index(new[0])
    old = names.KERNELS[:first]     # (later PRs' kernels stand after these)
    assert names.KERNELS[first:first + 4] == new
    assert not [(a, b) for a in new for b in old if a in b or b in a]
    assert not [(a, b) for a in new for b in new if a != b and a in b]
    # and off a TPU, left to the rule, the plain forms run
    plain = qn.qwen3_next_tiny(dtype=jnp.float32)
    assert not _kernel_calls(jax.make_jaxpr(
        lambda u, p: qn.delta_mixer(u, p, plain))(u, p).jaxpr)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_the_tiny_model_with_the_kernels_is_the_tiny_model_without(
        dtype, tol, monkeypatch):
    """Loss and every gradient of the tiny model on the four kernels
    against the same model — the same scan and flash kernels — with the plain
    forms in their place."""
    cfg = qn.qwen3_next_tiny(dtype=dtype, attention_impl="pallas", remat=True)
    params, (tokens, targets) = _params(cfg), _batch(cfg)

    def run():
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                lambda p: qn.loss_fn(p, tokens, targets, cfg)))(params)

    loss, grads = run()
    monkeypatch.setattr(
        delta_pointwise, "conv_silu_norm",
        lambda x, w, heads=0, scale=1.0, **_: _plain_conv_norm(
            x, w, heads, scale))
    monkeypatch.setattr(
        delta_pointwise, "gated_rmsnorm",
        lambda o, z, gain, eps, **_: qn._gated_rmsnorm(o, z, gain, eps))
    plain_loss, plain_grads = run()
    assert float(loss) == pytest.approx(float(plain_loss), rel=tol / 10)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(plain_grads)):
        loose = getattr(path[-1], "key", "") in ("A_log", "dt_bias")
        assert _rel(g, r) < tol * (100 if loose else 1), (path, _rel(g, r))


# --------------------------------------------------------- the expert half
def _first_layer(params):
    return jax.tree.map(lambda t: t[0], params["blocks"][0]["L"])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_shares_and_the_gated_shared_expert_add_up_to_the_uncut_layer(
        dtype):
    """Four chips of an EP 4 group each hold 4 of 16 experts: their routed
    parts, and the gated shared expert counted ONCE, are the reference's
    whole layer."""
    cfg = qn.qwen3_next_tiny(held_first=0, held_count=16)
    p = _first_layer(_params(cfg))
    u = jax.random.normal(jax.random.PRNGKey(3), (2, cfg.seq_len, cfg.d_model))
    u = u.astype(dtype).astype(jnp.float32)     # both route by the same input
    cast = {k: (v.astype(dtype) if k != "router_w" else v)
            for k, v in p.items()}
    shared_names = moe.GATED_SHARED_EXPERT + ("shared_gate",)
    total = 0.0

    @partial(jax.jit, static_argnames="first")
    def share(tensors, first):
        return moe.gated_moe(u.astype(dtype), tensors, top_k=cfg.top_k,
                             held=moe.Held(first, 4), scaling=1.0,
                             rule=qn.RULE)[0]

    with jax.default_matmul_precision("highest"):
        for first in range(0, 16, 4):
            held = {k: (v[first:first + 4] if k in moe.GATED_EXPERT else v)
                    for k, v in cast.items() if k not in shared_names}
            total = total + share(held, first).astype(jnp.float32)
        one = {k: (v[:4] if k in moe.GATED_EXPERT else v)
               for k, v in cast.items()}
        both = share(one, 0)
        only = share({k: v for k, v in one.items() if k not in shared_names},
                     0)
        total = total + (both - only).astype(jnp.float32)
        whole = jnp.stack([reference.experts(
            row, jax.tree.map(lambda t: t.astype(jnp.float32), p),
            _sizes(cfg))[0] for row in u])
    assert _rel(total, whole) < (1e-5 if dtype == jnp.float32 else 2e-2)
    # the gate is not a constant: without it the layer is another
    ungated = jnp.stack([reference._swiglu(
        row, p["shared_w1"], p["shared_w3"], p["shared_w2"], {}) for row in u])
    assert _rel((both - only).astype(jnp.float32), ungated) > 0.3


def test_a_layer_without_a_shared_gate_traces_as_it_did():
    """``shared_gate`` among a layer's tensors adds the gate's product, its
    sigmoid and one multiply inside the shared expert; a layer without the
    tensor (the DeepSeek and Xing cells') has none of them."""
    cfg = qn.qwen3_next_tiny()
    p = _first_layer(_params(cfg))
    u = jnp.ones((1, 16, cfg.d_model))

    def primitives(tensors):
        jaxpr = jax.make_jaxpr(lambda u, t: moe.gated_moe(
            u, t, top_k=cfg.top_k, held=cfg.held, scaling=1.0,
            rule=qn.RULE)[0])(u, tensors)
        return [e.primitive.name for e in jaxpr.eqns]

    without = primitives({k: v for k, v in p.items() if k != "shared_gate"})
    gated = primitives(p)
    extra = list(gated)
    for name in without:
        extra.remove(name)
    assert sorted(extra) == ["dot_general", "logistic", "mul"]
    assert "logistic" not in without
    # and what gated_moe_init draws for a layer that asks for none is what
    # it drew before the option existed
    args = (jax.random.PRNGKey(0), 1, 8, 4, 2, 6, 0.02, 0.02)
    plain = moe.gated_moe_init(*args, selection_bias=False, d_shared=6)
    with_gate = moe.gated_moe_init(*args, selection_bias=False, d_shared=6,
                                   shared_gate=True)
    assert set(with_gate) - set(plain) == {"shared_gate"}
    assert all(bool(jnp.array_equal(plain[k], with_gate[k])) for k in plain)


def test_set_up_balances_the_routers_and_changes_nothing_else():
    cfg = qn.qwen3_next_tiny()
    params = _params(cfg)
    batches = [_batch(cfg, rows=2, seed=s)[0] for s in range(4)]
    balanced, loads = qn.balance_routers(params, batches, cfg)
    assert [e["layer"] for e in loads] == [0, 1, 2, 3]
    assert all(e["pairs_dropped"] == 0 for e in loads)
    changed = [path[-1].key for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree.leaves(balanced)) if not bool(jnp.array_equal(a, b))]
    assert set(changed) == {"router_w"}

    chosen = jax.jit(lambda p: qn.chosen_experts(p, batches[0], cfg))

    def spread(p):
        sets = chosen(p)
        return max(float(jnp.std(jnp.sum(s, axis=0).astype(jnp.float32)))
                   for s in sets)

    assert spread(balanced) < spread(params)


def test_a_step_says_its_loads_and_balance_loss_among_its_counters():
    cfg = qn.qwen3_next_tiny()
    params, (tokens, targets) = _params(cfg), _batch(cfg)
    spec = qn.step_counters(cfg)
    assert spec.layers == (0, 1, 2, 3)
    assert spec.fields == names.STEP_EXPERT_LOAD_ARGS + (
        names.STEP_BALANCE_LOSS,)
    loss, counters = jax.jit(lambda p: qn.loss_fn(
        p, tokens, targets, cfg, counters=True))(params)
    assert counters.shape == (4, 4) and counters.dtype == jnp.int32
    balance = np.asarray(counters[:, 3]).view(np.float32)
    plain = jax.jit(lambda p: qn.loss_fn(
        p, tokens, targets, dataclasses.replace(cfg, aux_loss_coef=0.0)))(
        params)
    assert float(loss - plain) == pytest.approx(
        cfg.aux_loss_coef * float(balance.sum()), rel=1e-3)
    assert qn.step_counters(dataclasses.replace(
        cfg, aux_loss_coef=0.0)).fields == names.STEP_EXPERT_LOAD_ARGS


def test_weight_decay_leaves_the_gains_the_taps_and_the_gates_vectors():
    cfg = qn.qwen3_next_tiny()
    params = jax.eval_shape(lambda: qn.init(cfg, jax.random.PRNGKey(0)))
    mask = qn.decays(params)
    kept = {path[-1].key for path, m in
            jax.tree_util.tree_leaves_with_path(mask) if not m}
    assert kept == {"op_norm", "ffn_norm", "q_norm", "k_norm", "delta_norm",
                    "final_norm", "conv_w", "A_log", "dt_bias"}


# ------------------------------------------------- the pattern and the rule
def test_the_pattern_its_groups_and_what_the_rule_may_keep():
    cfg = qn.qwen3_next_tiny(attention_impl="pallas")
    assert blocks.pattern_groups(cfg.pattern) == [("L", 3), ("F", 1)]
    assert qn.Qwen3NextConfig(n_layer=8).pattern == "LLLFLLLF"
    assert qn.Qwen3NextConfig(first_layer=3, n_layer=2).pattern == "FL"
    assert qn.Qwen3NextConfig().rotary_dim == 64
    base, kinds = qn.kind_shards(cfg, 2, cfg.seq_len, None)
    assert set(kinds) == {"L", "F"}
    assert (kinds["L"].applications, kinds["F"].applications) == (3, 1)
    flat = [n for k in kinds.values() for c in k.candidates for n in c.names]
    assert len(flat) == len(set(flat)) and set(flat) <= set(names.RESIDUALS)
    assert {*names.RES_DELTA_PARTS, names.RES_DELTA_BA, names.RES_DELTA_STATES,
            names.RES_DELTA_O, names.RES_DELTA_X, names.RES_ATTN_GATE,
            names.RES_Q,
            names.RES_FLASH_O, names.RES_MID, names.RES_MOE_SCORES,
            names.RES_MOE_SHARED_GATE} <= set(flat)
    assert all(k.grad_bytes > 0 and k.block_bytes > 0 for k in kinds.values())
    # every name the rule may keep is on a value of the traced step
    params, (tokens, targets) = _params(cfg), _batch(cfg)
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: qn.loss_fn(p, tokens, targets, cfg)))(params))
    for name in flat:
        assert f"name={name}" in text, name


def _cells_rule():
    """The cell's layers on a chip that states a v5e's 15.75 GiB → (the
    config, its shard, its kinds, the phase that sets the working set, the
    resident bytes, the rule's choice)."""
    cell, config = _cell()
    cfg = dataclasses.replace(family.program_config(config, cell),
                              attention_impl="pallas")
    base, kinds = qn.kind_shards(cfg, cell["per_chip_batch"], cell["seq_len"],
                                 None)
    phase = max(blocks.backward_phases(
        base, kinds, blocks.pattern_groups(cfg.pattern)),
        key=lambda p: p.nbytes)
    # parameters, two moments and the gradients: 7.51 GB (the config's file)
    resident = 12 * qn.param_count(cfg)
    assert "7.51 GB" in config["deployment"] and round(resident / 1e7) == 751
    policy = blocks.choose_remat_policy_kinds(
        tuple(kinds.values()), phase.nbytes, family.V5E_BYTES_LIMIT, resident)
    return cfg, base, kinds, phase, resident, policy


def test_at_the_cells_shapes_the_rule_keeps_the_solves_result():
    """The cell's layers on a chip that states a v5e's 15.75 GiB: the rule
    takes ``delta_x`` (7.9 kFLOP a byte of it made again, where the
    projections' outputs stand at 2,048) before the projections', at 134 MB
    a DeltaNet layer — and, since the DeltaNet kind is charged what the
    compiled backward holds (PR 69), the four projections' outputs of all
    three layers after it, 2.25 GiB, with the routing's scores and
    attention's q: never the scan's states and o, 3.75 GiB."""
    _, _, kinds, phase, _, policy = _cells_rule()
    x = next(c for c in kinds["L"].candidates
             if c.names == (names.RES_DELTA_X,))
    assert x.nbytes == 4 * 128 * 32 * 64 * 64 * 2 == 134_217_728
    assert x.flops == 4 * 128 * 16 * gated_delta.solve_flops(64, 2, 128)
    assert 7_900 < x.flops / x.nbytes < 8_000
    ranked = sorted((c for k in kinds.values() for c in k.candidates),
                    key=lambda c: -c.flops / c.nbytes)
    parts_ = next(c for c in ranked if c.names == names.RES_DELTA_PARTS)
    assert ranked.index(x) < ranked.index(parts_)
    assert kinds["L"].applications * parts_.nbytes == 2_415_919_104
    assert phase.name == "3 x scan(L)"
    assert policy.saved_bytes <= policy.budget_bytes
    assert {names.RES_DELTA_X, names.RES_FLASH_O, names.RES_MID,
            names.RES_MOE_SCORES, names.RES_Q, *names.RES_DELTA_PARTS} <= set(
        policy.saved)
    assert not {names.RES_DELTA_STATES, names.RES_DELTA_O} & set(policy.saved)


@pytest.mark.parametrize("kind", ["L", "F"])
def test_a_kinds_backward_is_priced_by_the_larger_of_its_two_moments(kind):
    """Each term of ``kind_shards``' ``block_bytes`` at the cell's shapes, as
    its docstring states them: 32,768 tokens in bf16, a stream of 2,048, the
    fused projection 12,288 wide (q, k 2,048; v, z 4,096), 128 chunks a row."""
    cfg, base, kinds, _, _, _ = _cells_rule()
    MiB, T, a, D = 2 ** 20, 4 * 8192, 2, 2048
    carried = T * D * a
    stream, routed = parts.gated_experts_working_set(T, D, 512, 10, 32, 512, a)
    _, shared = parts.swiglu_price(4, 8192, 8192, D, 512, a, ("g", "u"))
    experts_set = stream + max(routed, shared)
    assert (stream, routed, shared) == (960 * MiB, 926 * MiB, 172 * MiB)
    table = 18_992 * D
    lone = {"L": kinds["F"].grad_bytes, "F": 0}[kind]
    absent = lone * (4 - a) // 4 + table * ((4 - a) + 4 + (a + 4))
    if kind == "L":
        solved, ba = 4 * 128 * 32 * 64 * 64 * a, T * 64 * 4
        states = 4 * 128 * 32 * 128 * 128 * 4
        weights = a * (D * 12_288 + D * 64 + 4_096 * D)
        # x, q‖k‖v‖z as projected, q, k, v out of the conv kernel
        waits = a * T * (D + 12_288 + 8_192) + weights + solved + ba
        # … the states, o, y and the cotangents d y, d o, d z
        own = waits + states + a * T * 5 * 4_096
        assert (waits, states, own - waits - states) == (
            1_686_372_352, 1_024 * MiB, 1_280 * MiB)     # 1.57, 1, 1.25 GiB
        assert absent == 731_001_856            # 0.68 GiB
        assert own > waits + experts_set        # the compiled step's peak
    else:
        width, kv = 16 * 256, 2 * 256
        weights = a * (D * 2 * width + 2 * D * kv + width * D)
        waits = (a * T * (2 * D + 3 * width + 2 * kv + 2 * width) + weights
                 + T * 16 * 4)
        own = waits + a * T * (2 * D + 5 * width) + weights
        assert absent == 466_747_392
    assert kinds[kind].block_bytes == carried + max(waits + experts_set,
                                                    own) - absent
    assert kinds[kind].grad_bytes == 4 * parts.param_count(
        lambda: qn._layer_init(jax.random.PRNGKey(0), 1, kind, cfg))


# ``peak_memory_in_bytes`` of the cell's step compiled for a v5e (the described
# chip; _exp_pr69_compile.py, the buffer lists under chiprun_out/pr69/): with
# the list the parent kept — commit f5d8d11, the same bytes from this tree with
# that list forced — and with the list this rule keeps — PR 69's tree
PEAK_AT_THE_PARENTS_LIST = 12_494_747_648
PEAK_AT_THE_NEW_LIST = 15_103_342_592
PARENTS_LIST = (
    names.RES_FLASH_O, names.RES_FLASH_LSE, names.RES_DELTA_X,
    names.RES_MOE_KTH, names.RES_MOE_LAST, names.RES_MID, names.RES_K,
    names.RES_V, names.RES_DELTA_BA, names.RES_MOE_PAIR_KEY,
    names.RES_MOE_PAIR_GATE)


@pytest.mark.parametrize("kept,compiled", [
    ("parent", PEAK_AT_THE_PARENTS_LIST), ("new", PEAK_AT_THE_NEW_LIST)])
def test_the_rules_sum_stands_just_over_the_compiled_peak(kept, compiled):
    """Resident + working set + kept against what the compiler holds: at or
    above it and no more than 0.75 GiB above, with the parent's kept list
    and with this rule's — and the compiled step under the limit less the
    reserve, which is all the rule can promise."""
    _, _, kinds, phase, resident, policy = _cells_rule()
    saved = PARENTS_LIST if kept == "parent" else policy.saved
    kept_bytes = sum(k.applications * c.nbytes for k in kinds.values()
                     for c in k.candidates if set(c.names) <= set(saved))
    assert kept == "parent" or kept_bytes == policy.saved_bytes
    estimate = resident + phase.nbytes + kept_bytes
    assert 0 <= estimate - compiled <= 0.75 * 2 ** 30
    assert max(estimate, compiled) <= (family.V5E_BYTES_LIMIT
                                       - blocks.REMAT_RESERVE_BYTES)


def test_kept_projections_are_not_made_again():
    """The ``RES_DELTA_PARTS`` names sit on the in-projections' own outputs:
    with them in the policy a DeltaNet layer's backward holds no second
    ``bsd,de->bse`` product but ``[b, a]``'s, and none with ``delta_ba`` too
    — keeping them spares the four products, not only holds their bytes."""
    cfg = qn.qwen3_next_tiny()
    p = jax.tree.map(lambda t: t[0],
                     qn._layer_init(jax.random.PRNGKey(0), 1, "L", cfg))
    x = jnp.ones((2, cfg.seq_len, cfg.d_model), cfg.dtype)

    def products(jaxpr, above=""):
        for eqn in jaxpr.eqns:
            stack = f"{above}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "dot_general":
                yield stack
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from products(sub, stack)

    def made_again(saved):
        layer = jax.checkpoint(
            partial(qn._layer, cfg=cfg, kind="L"),
            policy=jax.checkpoint_policies.save_only_these_names(*saved))
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda x, p: layer(x, p).astype(jnp.float32).sum(),
            argnums=(0, 1)))(x, p)
        return sum("rematted_computation" in s and "bsd,de->bse" in s
                   for s in products(jaxpr.jaxpr))

    assert made_again(()) == 5
    assert made_again(names.RES_DELTA_PARTS) == 1
    assert made_again(names.RES_DELTA_PARTS + (names.RES_DELTA_BA,)) == 0


@pytest.mark.parametrize("axis", ["ep", "tp", "pp", "cp"])
def test_a_mesh_the_family_cannot_run_on_is_refused(axis):
    class Mesh:
        shape = {axis: 2}

    with pytest.raises(NotImplementedError, match=f"{axis} > 1"):
        qn.mesh_rules(qn.qwen3_next_tiny(), Mesh())


def test_the_step_runs_on_a_data_parallel_mesh():
    """``shard_map`` over the rows: the kernels under fsdp = 2."""
    from ray_tpu.parallel import mesh as mesh_lib

    cfg = qn.qwen3_next_tiny(attention_impl="pallas")
    params, (tokens, targets) = _params(cfg), _batch(cfg)
    alone = jax.jit(lambda p: qn.loss_fn(p, tokens, targets, cfg))(params)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(fsdp=2), jax.devices()[:2])
    with mesh_lib.use_mesh(mesh):
        sharded = jax.jit(lambda p: qn.loss_fn(p, tokens, targets, cfg))(params)
    assert float(sharded) == pytest.approx(float(alone), rel=2e-3)


# ------------------------------------- the configuration, the cell, the family
def _cell():
    from benchmarks.harness import spec

    cell, config, _ = spec.load_cell(CELL)
    return cell, config


def _rehearsal():
    from benchmarks import run
    from benchmarks.harness import spec

    cell, config, mix = spec.load_cell(CELL)
    run._apply_rehearsal(cell, config)
    return cell, config, mix


def test_the_configuration_holds_every_published_width_and_states_its_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model catalog is not on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows
                     if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    cell, config = _cell()
    entry = next(c for c in _benchmark()["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] == published["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    for key, value in published["config"].items():
        if key in entry["reduced"]:
            assert config[key] != value
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 32, 18992)
    assert len(config["reduced"]) == 3 and len(config["assumed"]) >= 8
    assert any("router_aux_loss_coef 0.001" in a for a in config["assumed"])
    assert "EP 16" in config["deployment"] and "7.51 GB" in config["deployment"]
    assert (cell["seq_len"], cell["per_chip_batch"], cell["remat"],
            cell["reference_rows"], cell["reference_grad"], cell["mesh"]) == (
        8192, 4, True, 4, True, {"fsdp": 1})


def test_the_cells_parameters_and_the_familys_arithmetic():
    """625,667,136 parameters, counted by the program from abstract shapes
    and by the family from the file; the two counts of a token's operations
    agree; a held expert sees a quarter of the deployment's tokens."""
    cell, config = _cell()
    cfg = family.program_config(config, cell)
    shapes = family.shapes(config, cell)
    assert qn.param_count(cfg) == shapes["params"] == 625_667_136
    assert f"{shapes['params']:,}" in config["deployment"]
    assert cfg.pattern == "LLLF" and cfg.held == moe.Held(0, 32)
    assert (cfg.n_experts, cfg.top_k, cfg.head_dim, cfg.rotary_dim) == (
        512, 10, 256, 64)
    assert cfg.delta_chunk == gated_delta.CHUNK == family.DELTA_CHUNK
    assert family.train_flops_per_token(shapes) == pytest.approx(
        qn.flops_per_token(cfg), rel=1e-12)
    tokens = cell["per_chip_batch"] * cell["seq_len"]
    assert tokens * cfg.top_k / cfg.n_experts == 640
    assert moe.row_buffer(tokens, 512, 10, 32) == 25600     # 1.25 x 20,480
    # the scan by the chunk form at 64: 65,536 multiply-adds a value head and
    # token, 4,096 of them the key head's two products shared by two
    macs = 16 * 64 * 128 + 32 * (64 * 128 + 64 * 64 + 3 * 128 * 128)
    assert family._delta_macs_per_token(shapes) == macs == qn.delta_scan_macs(
        cfg)
    call = family.gated_delta_call(shapes)
    assert call["flops"] == 3 * 3 * 2 * tokens * macs
    states = tokens // 64 * 32 * 128 * 128 * 4
    assert call["bytes"] == 3 * (3 * tokens * 2 * 8192 + 3 * tokens * 256
                                 + 2 * tokens * 2 * 4096 + 2 * states)
    flash = family.flash_attn_call(shapes)
    assert flash["flops"] == 7 * 4 * 16 * 8192 * 8192 * 256
    experts = family.experts_call(shapes)
    assert experts["flops"] == 9 * 4 * 2 * (tokens * 0.625) * 2048 * 512


def test_the_family_refuses_a_program_without_the_model(monkeypatch):
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "ray_tpu.models.qwen3_next"
        else real(name, *a))
    with pytest.raises(SystemExit, match="cannot run a cell of family "
                                         "qwen3_next"):
        family.shapes(*reversed(_cell()))


@pytest.mark.parametrize("control,refused", [
    ("", ()), ("float8", ("grad_norm",)), ("gate_float8", ("grad_norm",))],
    ids=["program", "float8-reference", "float8-gate"])
def test_the_comparison_that_decides_correct(control, refused):
    """The family's ``reference_check`` at the CPU rehearsal's sizes, judged
    by ``harness/checks.failures`` as run.py judges a run: the bf16 program
    is correct; a switched reference in the program's place is not. (The
    controls on the scan — its operands in float8, its solve in one bf16 pass,
    which acts on the kernels alone — are read at the cell's sizes on the
    chip: PERF.md §6.) The limits are stated for these sizes and this seed."""
    from benchmarks.harness import checks, traffic
    from ray_tpu.parallel import mesh as mesh_lib

    cell, config, mix = _rehearsal()
    seed = 3000000019
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(**cell["mesh"]),
                              jax.devices()[:1])
    bundle = family.build(config, cell, mesh, seed)
    rows = traffic.host_batch(cell["reference_rows"], seed, cell["seq_len"],
                              mix["alphabet"])
    switches = family.controls()[control] if control else {}
    reading = family.reference_check(bundle, rows, config, cell, **switches)
    assert len(reading["expert_load"]) == 4
    assert all(e["pairs_dropped"] == 0 for e in reading["expert_load"])
    summary = {
        "reference": reading,
        "window": {"nonfinite_losses": 0, "losses_tail": [1.0],
                   "first_loss": 2.0, "compiles_in_window": 0},
        "data_ok": True, "step_counter": 3, "steps_run": 3,
        "device_count": cell["chips"]}
    bad = checks.failures(summary, cell, rehearse_cpu=True)
    assert [any(s.startswith(name) for s in bad) for name in refused] == [
        True] * len(refused), (bad, reading["program"])
    assert bool(bad) == bool(refused), (bad, reading["program"])


# --------------------------------------------------- the benchmark's entries
@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_names_the_new_cell_alone_and_imports_no_program(name):
    entry = next(m for m in _benchmark()["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py")
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in tree.body:                        # module level only
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            mods = ([a.name for a in node.names] if isinstance(node, ast.Import)
                    else [node.module or ""])
            assert not any(m.split(".")[0] == "ray_tpu" for m in mods), mods
    reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    assert (reader.UNIT, reader.MOVES, reader.LAYER, reader.SOURCE) == (
        entry["unit"], entry["moves"], entry["layer"], entry["source"])


def test_the_benchmark_gains_one_configuration_and_one_one_chip_cell():
    b = _benchmark()
    # (PR 64's configuration and cell came after this one's)
    assert b["configs"][8]["name"] == CONFIG
    assert b["workloads"][9] == {
        **b["workloads"][9], "name": CELL, "config": CONFIG,
        "traffic": "dataset", "chips": 1}
    assert len(b["configs"]) >= 9 and len(b["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    readers = [m["name"] for m in b["per_layer"]]
    first = readers.index(NEW_READERS[0])
    assert readers[first:first + len(NEW_READERS)] == list(NEW_READERS)
    for name in SHARED_READERS:
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"]
    # the rate and the set-up time, not the p90; the readers written for
    # another family's arithmetic are not this cell's
    p90 = next(m for m in b["end_to_end"] if m["name"] == "step_ms_p90")
    assert CELL not in p90["workloads"]
    for name in ("flash_attn_ms_per_step", "flash_attn_roofline", "mfu_device",
                 "dsv2_mfu_device", "ssd_scan_roofline",
                 "lfm2_flash_attn_roofline"):
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        assert CELL not in entry["workloads"]
    for entry in b["configs"] + b["workloads"]:
        assert len(entry["why"]) <= 200


def test_the_new_benchmark_files_import_no_program_at_module_level():
    for name in ("qwen3_next", "qwen3_next_reference"):
        path = os.path.join(ROOT, "benchmarks", "families", name + ".py")
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        level = tree.body if name == "qwen3_next" else list(ast.walk(tree))
        for node in level:
            if isinstance(node, ast.ImportFrom):
                assert (node.module or "").split(".")[0] != "ray_tpu"
            elif isinstance(node, ast.Import):
                assert all(a.name.split(".")[0] != "ray_tpu" for a in node.names)


_RECORDED = {
    "lfm2_moe": ("lfm2-24b-a2b-l5.dataset",
                 "lfm2-24b-a2b-l5.dataset.1step.scoped.program.json.gz"),
    "nemotron_h": (
        "nemotron-3-super-120b-l11.dataset",
        "nemotron-3-super-120b-l11.dataset.1step.scoped.program.json.gz"),
}
_facts = {}


def _recorded_facts(family_name):
    """The facts a reader would be handed in that cell's traced run: the
    cell's own shapes, v5e's peaks and the recorded trace's reduction."""
    if family_name not in _facts:
        from benchmarks.harness import peaks, program_trace, spec

        cell_name, trace = _RECORDED[family_name]
        cell, config, mix = spec.load_cell(cell_name)
        path = os.path.join(ROOT, "benchmarks", "testdata", trace)
        got = program_trace.reduce_tables(program_trace.read_tables(path))
        assert got["instrumented"]
        fam = importlib.import_module(f"benchmarks.families.{family_name}")
        _facts[family_name] = {
            "cell": cell, "config": config, "traffic": mix, "notes": [],
            "summary": {"shapes": fam.shapes(config, cell)},
            "trace": {"steps": got["steps"], "step_device_ms": 100.0},
            "peaks": peaks.peaks_for("TPU v5 lite"), "driver": {},
            "program_trace": got}
    return _facts[family_name]


@pytest.mark.parametrize("family_name", sorted(_RECORDED))
@pytest.mark.parametrize("name", ("delta_mixer_ms_per_step",
                                  "gated_delta_ms_per_step",
                                  "gated_delta_roofline"))
def test_a_new_reader_reads_nothing_from_another_cells_trace(
        name, family_name):
    """A program without the scope or the kernels — every trace recorded
    before PR 61, and the parent's — gives the reader nothing to read: None,
    no raise."""
    reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    assert reader.read(_recorded_facts(family_name)) is None
