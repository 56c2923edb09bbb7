"""The flash kernels, at the tiling the rule chooses, through the TPU's own
compiler at real widths — for a v5e that is described, not attached. What
interpret mode cannot show (a slice Mosaic cannot lay out, more scoped VMEM
than a kernel may use) fails here, at no chip time — and, since PR 30, the
`gpt2-124m` cells' whole step, to see what the layer scan stacks in the
program the cell's config compiles to. Nothing runs: no time comes from this
file.

Kept in ONE file and behind fixtures: only the xdist worker that is given this
file loads the TPU's library (on-chip-measurement guide, section 2).
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops.attention import (
    flash_attention, flash_attention_with_lse, flash_tiling_decisions,
    mha_backward_chunk,
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape,layout", [
    ((8, 12, 64, 1024), "bhds"),     # gpt2-124m, one chip: 96 rows
    ((25, 8, 64, 1024), "hbds"),     # a gpt2-xl shard under fsdp=4: 200 rows,
                                     # the heads leading as gpt2._block has them
    ((2, 16, 4096, 128), "bhsd"),    # llama-like: hd 128, S 4,096
    ((8, 12, 1024, 64), "bhsd"),     # hd 64 handed over hd-minor: the S-minor
                                     # pair behind a transpose each way
], ids=["gpt2-124m", "gpt2-xl-shard", "llama-4k", "gpt2-124m-hd-minor"])
def test_chosen_tiling_compiles_for_the_v5e(one_chip, shape, layout):
    from ray_tpu.ops.attention import HD_MINOR, S_MINOR

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def grads(q, k, v):
        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, layout=layout,
                                interpret=False)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    hlo = jax.jit(grads).lower(x, x, x).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    S, hd = shape[layout.index("s")], shape[layout.index("d")]
    rows = shape[0] * shape[1]
    mine = {d["kernel"]: d for d in flash_tiling_decisions()
            if (d["rows"], d["Sq"], d["hd"]) == (rows, S, hd)}
    assert set(mine) == {"fwd", "bwd"}
    # the target tile fits at these shapes: Mosaic took what the rule chose
    assert all((d["block_q"], d["block_k"]) == (512, 512)
               for d in mine.values())
    # the pair is the head width's: [rows, hd, S] operands at 64 whatever
    # order the caller's arrays came in, [rows, S, hd] at 128
    pair = S_MINOR if hd == 64 else HD_MINOR
    assert all(d["layout"] == pair for d in mine.values())
    operand = [rows, hd, S] if pair == S_MINOR else [rows, S, hd]
    calls = [l for l in hlo.splitlines() if "tpu_custom_call" in l]
    assert all("bf16[%d,%d,%d]" % tuple(operand) in l for l in calls)
    # a pair handed its own order has nothing to re-lay out at its edge
    if layout in ("bhds", "hbds") or hd == 128:
        assert not [l for l in hlo.splitlines() if "bf16[" in l
                    and re.search(r" (copy|transpose)\(", l)]


@pytest.mark.parametrize("shape,layout,window", [
    ((2, 32, 16384, 128), "bhsd", 2048),   # the Trinity-Mini cell's shard
    ((8, 64, 4096), "hds", 1000),          # the S-minor pair, a window off
                                           # the tile's multiple
], ids=["trinity-16k-hd128", "s-minor-hd64"])
def test_the_windowed_pair_compiles_for_the_v5e(one_chip, shape, layout,
                                                window):
    """The flash pair under a causal window (PR 66), forward and fused
    backward: Mosaic takes the band's dynamic loop bounds on both sides of
    the walk and the straddling tiles' mask of two conditions, at the target
    tile; the event says how much of the triangle the call skips."""
    if layout == "hds":
        shape, layout = (1,) + shape, "bhds"
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def grads(q, k, v):
        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, layout=layout,
                                window=window, interpret=False)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    hlo = jax.jit(grads).lower(x, x, x).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    S, hd = shape[layout.index("s")], shape[layout.index("d")]
    mine = {d["kernel"]: d for d in flash_tiling_decisions()
            if (d["Sq"], d["hd"], d["window"]) == (S, hd, window)}
    assert set(mine) == {"fwd", "bwd"}
    for d in mine.values():
        assert (d["block_q"], d["block_k"]) == (512, 512)
        assert d["tiles_visited"] < d["tiles_causal"]
    if S == 16384:      # five tiles a q tile from the fifth on: 150 of 528
        assert {(d["tiles_visited"], d["tiles_causal"])
                for d in mine.values()} == {(150, 528)}


@pytest.mark.parametrize("S", [4096, 8192])
def test_latent_attentions_widths_compile_for_the_v5e(one_chip, S):
    """The flash pair at the `deepseek-v2-lite-l5.dataset` shard — 16 heads,
    q·k at 192 and v at 128, heads leading, S-minor, rows of 8,192 (and of
    4,096) — forward and backward: Mosaic takes [64, 192, S] and [64, 128, S]
    operands as they are (nothing is padded to 256 or to 192), the target
    tile where the rule gives it, and, for the 8,192-token backward, the
    VMEM limit the call asks for (its whole rows pass the 16 MiB default:
    D16's refusal until PR 55)."""
    from ray_tpu.ops.attention import S_MINOR, VMEM_BUDGET_BYTES

    H, B = 16, 4
    qk = jax.ShapeDtypeStruct((H, B, 192, S), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((H, B, 128, S), jnp.bfloat16, sharding=one_chip)

    def grads(q, k, v):
        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, layout="hbds",
                                scale=0.1147, interpret=False)
            assert o.shape == v.shape
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    hlo = jax.jit(grads).lower(qk, qk, v).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    mine = {d["kernel"]: d for d in flash_tiling_decisions()
            if (d["rows"], d["Sq"], d["hd"], d["hd_v"]) == (64, S, 192, 128)}
    assert set(mine) == {"fwd", "bwd"}
    assert all(d["layout"] == S_MINOR for d in mine.values())
    assert (mine["fwd"]["block_q"], mine["fwd"]["block_k"]) == (512, 512)
    assert (mine["bwd"]["block_q"], mine["bwd"]["block_k"]) == (
        (512, 512) if S == 8192 else (512, 256))
    assert (mine["bwd"]["vmem_estimate"] > VMEM_BUDGET_BYTES) == (S == 8192)
    calls = [l for l in hlo.splitlines() if "tpu_custom_call" in l]
    assert all(f"bf16[64,192,{S}]" in l and f"bf16[64,128,{S}]" in l
               and "bf16[64,256," not in l for l in calls)
    assert not [l for l in hlo.splitlines() if "bf16[" in l
                and re.search(r" (copy|transpose)\(", l)]


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_ring_chunk_kernels_compile_for_the_v5e(one_chip, kernel):
    """Ring attention's building blocks (a q chunk against an earlier kv
    chunk, global offsets): every other test of them interprets."""
    B, S, H, hd = 2, 512, 4, 64
    x = jax.ShapeDtypeStruct((B, S, H, hd), jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((B, H, S), jnp.float32, sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention_with_lse(q, k, v, S, 0, interpret=False)

    def bwd(q, k, v, o, lse, do):
        return mha_backward_chunk(q, k, v, o, lse, do, S, 0, interpret=False)

    lowered = (jax.jit(fwd).lower(x, x, x) if kernel == "fwd"
               else jax.jit(bwd).lower(x, x, x, x, lse, x))
    hlo = lowered.compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1


def test_eva_kernels_compile_for_the_v5e_at_the_cells_shape(one_chip):
    """The EVA aggregation kernels at the `evabyte-6.5b-l4.dataset` shard —
    32 heads of 128 over one row of 32,768 bytes, windows of 2,048, chunks of
    16 — forward and backward: Mosaic takes the 512/512 tile of the flash
    rule and the VMEM limit the backward asks for (its estimate passes the
    16 MiB default: a window's q, k, v, do, dq, dk, dv and the summaries'
    f32 accumulators stand whole)."""
    from ray_tpu.ops import eva_attention as eva
    from ray_tpu.ops.attention import VMEM_BUDGET_BYTES

    B, H, S, hd = 1, 32, 32768, 128
    x = jax.ShapeDtypeStruct((B, H, S, hd), jnp.bfloat16, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((H, hd), jnp.float32, sharding=one_chip)

    def grads(*args):
        def loss(*a):
            return jnp.sum(eva._eva(*a, window=2048, chunk=16,
                                    interpret=False).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)

    hlo = jax.jit(grads).lower(x, x, x, vec, vec).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    mine = {d["kernel"]: d for d in eva.eva_tiling_decisions()
            if (d["rows"], d["Sq"], d["hd"]) == (B * H, S, hd)}
    assert set(mine) == {"fwd", "bwd"}
    assert all((d["block_q"], d["block_k"], d["window"], d["chunk"])
               == (512, 512, 2048, 16) for d in mine.values())
    assert mine["fwd"]["vmem_estimate"] <= VMEM_BUDGET_BYTES
    assert VMEM_BUDGET_BYTES < mine["bwd"]["vmem_estimate"] < 2 * VMEM_BUDGET_BYTES


@pytest.mark.parametrize("shape", [
    (8, 4096, 16, 64, 1, 128, 128),     # the Nemotron cell: 16 heads, 1 group
    (2, 2048, 128, 64, 2, 128, 256),    # 64 heads a group in four tiles, Q 256
    (2, 1024, 8, 64, 4, 128, 128),      # groups of two heads: a tile a group
    (1, 16384, 16, 128, 16, 128, 128),  # the MiniCPM-SALA cell's lightning
                                        # layers: one head a group, P = N
], ids=["nemotron-cell", "hg64-q256", "four-groups", "one-head-a-group"])
def test_scan_kernels_compile_for_the_v5e(one_chip, shape):
    """ops/mamba2's kernel pair (PR 41) at the tile the rule chooses, forward
    and backward, bf16: what interpret mode cannot show — a lane slice, a
    broadcast or a block shape Mosaic cannot lay out, more VMEM than the call
    asked for — and that outside the two calls the compiled scan holds no
    float32 tensor of a chunk's [Q, Q] a head."""
    import functools

    from ray_tpu.ops import mamba2

    B, S, H, P, G, N, Q = shape
    sd = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    args = (sd((B, S, H, P), jnp.bfloat16), sd((B, S, H), jnp.float32),
            sd((H,), jnp.float32), sd((B, S, G, N), jnp.bfloat16),
            sd((B, S, G, N), jnp.bfloat16))
    scan = functools.partial(mamba2._ssd_scan, chunk=Q, interpret=False)
    hlo = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(scan(*a))),
                           argnums=(0, 1, 2, 3, 4))).lower(*args).compile(
                               ).as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    mine = {d["kernel"]: d for d in mamba2.ssd_tiling_decisions()
            if (d["rows"], d["S"], d["Q"], d["group_heads"]) == (
                B, S, Q, H // G)}
    assert set(mine) == {"fwd", "bwd"}
    assert all(d["head_tile"] == min(16, H // G) for d in mine.values())
    per_head = re.findall(rf"f32\[[0-9,]*{Q},{Q}\]", hlo)
    sizes = [math.prod(int(n) for n in t[4:-1].split(",")) for t in per_head]
    assert all(n <= B * (S // Q) * G * Q * Q for n in sizes), set(per_head)


@pytest.mark.parametrize("shape", [
    (4, 8192, 16, 32, 128, 128, 64),    # the Qwen3-Next cell: two value heads
                                        # a key head, stacked at chunk 64
    (1, 2000, 4, 4, 128, 256, 128),     # one value head a key head at chunk
                                        # 128, unequal widths, a padded row
], ids=["qwen3-next-cell", "one-head-c128"])
def test_delta_rule_kernels_compile_for_the_v5e(one_chip, shape):
    """ops/gated_delta's kernels (PR 61; the solve's own since PR 62) at the
    tile the rule chooses, solve, forward and backward, bf16: what interpret
    mode cannot show — the stacked heads' sublane slices, the row / column
    spreads of the gates, the masks' bit arithmetic, the state scratch, X's
    blocks side by side along the lanes — and that k and q reach the kernels
    at the KEY heads' width: no value head's copy of either exists."""
    import functools

    from ray_tpu.ops import gated_delta

    B, S, Hk, Hv, dk, dv, C = shape
    sd = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    key, gate = sd((B, S, Hk, dk), jnp.bfloat16), sd((B, S, Hv), jnp.float32)
    args = (key, key, sd((B, S, Hv, dv), jnp.bfloat16), gate, gate)
    scan = functools.partial(gated_delta._kernel_scan, chunk=C,
                             interpret=False)
    hlo = jax.jit(jax.grad(
        lambda *a: jnp.sum(jnp.sin(scan(*a).astype(jnp.float32))),
        argnums=(0, 1, 2, 3, 4))).lower(*args).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
    padded = -(-S // C) * C
    mine = {d["kernel"]: d for d in gated_delta.delta_tiling_decisions()
            if (d["rows"], d["S"], d["C"], d["key_heads"]) == (B, padded, C, Hk)}
    assert set(mine) == {"solve", "fwd", "bwd"}
    assert all(d["head_tile"] == min(Hv // Hk, 128 // C)
               for d in mine.values())
    # q and k are the calls' operands at the key heads' own width
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert all(f"bf16[{B},{padded},{Hk * dk}]" in line for line in calls)
    # X leaves the solve and enters the other two as the diagonal blocks alone
    ht = min(Hv // Hk, 128 // C)
    blocks = f"bf16[{B},{Hv // ht},{padded // C},{C},{ht * C}]"
    assert all(blocks in line for line in calls)


@pytest.mark.parametrize("S,top_k", [(16384, 64), (2048, 8)],
                         ids=["minicpm-sala-cell", "short-row"])
def test_sparse_attention_kernels_compile_for_the_v5e(one_chip, S, top_k):
    """ops/sparse_attention's three kernels (PR 47) at the tile the rule
    chooses, with the selection before them, bf16, a group of 16 query heads
    on one key-value head at hd 128: what interpret mode cannot show — the
    heads side by side along the lanes, the 0/1 spread of who was given
    what, the scalar-prefetch table, more VMEM than the call asked for."""
    from ray_tpu.ops import sparse_attention as sa

    sizes = sa.SparseSizes(top_k=top_k, window=min(2048, S // 4),
                           dense_len=S // 2)
    q = jax.ShapeDtypeStruct((1, 16, S, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 1, S, 128), jnp.bfloat16, sharding=one_chip)

    def grads(q, k, v):
        def loss(q, k, v):
            ids = sa.sparse_select(q, k, sizes)
            return jnp.sum(sa.attend_chosen(q, k, v, ids, sizes, False
                                            ).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    hlo = jax.jit(grads).lower(q, kv, kv).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
    mine = {d["kernel"]: d for d in sa.sparse_tiling_decisions()
            if (d["S"], d["group_heads"], d["hd"]) == (S, 16, 128)}
    assert set(mine) == {"fwd", "bwd_dq", "bwd_dkv"}
    assert all((d["block_q"], d["block_k"]) == (128, 512)
               for d in mine.values())
    # no [heads, S, S] tensor outside the kernels: logits live in VMEM tiles
    assert not re.findall(rf"\[[0-9,]*{S},{S}\]", hlo)


@pytest.fixture(scope="module")
def minicpm_sala_step(topo):
    """`minicpm-sala-9b-l4.dataset`'s own step compiled for the described
    chip: (compiled, cell, its family)."""
    cell, config, family, mesh = _cell_on(topo, "minicpm-sala-9b-l4.dataset")
    fn, args = family.abstract_step(config, cell, mesh)
    return fn.lower(*args).compile(), cell, family


def test_the_minicpm_sala_cell_step_fits_and_clones_nothing_on_the_v5e(
        minicpm_sala_step):
    """It fits (the compiler's peak leaves 1 GB of 15.75 GiB), nothing
    is rematerialized by the compiler, and its Mosaic calls are the scan's
    pair in the layer scan's forward, recompute and backward and the sparse
    layer's three kernels (the forward once more: recompute)."""
    from ray_tpu.models import blocks

    compiled, cell, family = minicpm_sala_step
    hlo = compiled.as_text()
    assert blocks.compiler_rematerialized(hlo) == []
    assert hlo.count('custom_call_target="tpu_custom_call"') == 6
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak <= family.V5E_BYTES_LIMIT - 10 ** 9, peak / 2 ** 30
    (policy,) = [d for d in blocks.remat_policy_decisions()
                 if (d["n_layer"], d["seq"]) == (4, cell["seq_len"])
                 and d["bytes_limit"] == family.V5E_BYTES_LIMIT]
    assert policy["saved"][0] == "sparse_block_ids"


def test_the_minicpm_sala_cell_step_makes_no_norm_inside_a_lightning_product(
        minicpm_sala_step):
    """PR 49. A lightning layer's five projections make nineteen products a
    step (five forward, four in the recompute, ten backward), each a fusion
    around a `convolution`. Left alone, XLA made the QK-norm's backward inside
    BOTH backward products of q and of k, and the output norm and gate inside
    the out-projection's forward and weight-gradient products, as their
    operand: such a fusion reads TWO token-wide `[16384, 16 x 128]` tensors
    (what the norm is applied to, and what comes back) where a bare product
    reads one, and ran at two thirds of a bare one's speed on the chip
    (PERF.md section 6). With the seams (`parts.made_once`) every one of the
    nineteen reads at most one."""
    compiled, cell, _ = minicpm_sala_step
    hlo = compiled.as_text()
    wide = cell["seq_len"] * 16 * 128
    elements = _elements(hlo)
    bodies = dict(re.findall(r"^(%[\w.\-]+) \([^\n]*\{\n(.*?)^\}", hlo,
                             re.S | re.M))
    products = {}
    for name, args, body, op_name in re.findall(
            r"(%[\w.\-]+) = [^\n]*? fusion\(([^)]*)\)[^\n]*calls=(%[\w.\-]+)"
            r"[^\n]*op_name=\"([^\"]*)\"", hlo):
        if re.search(r"/lightning_attn/(qkv/bsd,dhk->b\w+|proj/bshk,hkd->bsd)/",
                     op_name) and " convolution(" in bodies.get(body, ""):
            products[name] = [a for a in re.findall(r"%[\w.\-]+", args)
                              if elements.get(a) == wide]
    assert len(products) == 19, sorted(products)
    assert {k: v for k, v in products.items() if len(v) > 1} == {}


def _benchmarks_importable():
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)


def _program_trace():
    """The benchmark's reader of a device trace (`benchmarks/harness`)."""
    _benchmarks_importable()
    from benchmarks.harness import program_trace
    return program_trace


def _cell_on(topo, name):
    """(cell, config, its family, its mesh over the described chips) of a
    BENCHMARK.json cell."""
    import importlib

    _benchmarks_importable()
    from benchmarks.harness import spec
    from ray_tpu.parallel import mesh as mesh_lib

    cell, config, _ = spec.load_cell(name)
    family = importlib.import_module(f"benchmarks.families.{config['family']}")
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(**cell["mesh"]),
                              list(topo.devices)[:cell["chips"]])
    return cell, config, family, mesh


@pytest.fixture(scope="module")
def gpt2_124m_step(topo):
    """The `gpt2-124m` cells' whole train step — the cell's own config through
    `program_config`, composed as `make_train_step` composes it, the
    benchmark's optimizer — compiled for one described chip: (compiled, cfg,
    rows a step). What a cell's config compiles to is what PR 27 never
    looked at."""
    from ray_tpu.models import gpt2
    from ray_tpu.train import train_step

    cell, config, family, mesh = _cell_on(topo, "gpt2-124m.resident")
    cfg = family.program_config(config, cell)
    optimizer = family._optimizer()
    step_given, state_sh, batch_sh = train_step._compose_step(
        gpt2, cfg, mesh, optimizer, None)
    params = jax.eval_shape(lambda: gpt2.init(cfg, jax.random.PRNGKey(0)))
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        {"params": params, "opt_state": jax.eval_shape(optimizer.init, params),
         "step": jax.ShapeDtypeStruct((), jnp.int32)},
        state_sh)
    rows = cell["per_chip_batch"] * cell["chips"]
    tokens = jax.ShapeDtypeStruct((rows, cfg.seq_len), jnp.int32,
                                  sharding=batch_sh["tokens"])
    # what a v5e chip states (memory_stats()["bytes_limit"]); without remat
    # nothing reads it
    step = jax.jit(step_given((16909334528, 0)),
                   in_shardings=(state_sh, batch_sh),
                   out_shardings=(state_sh, None), donate_argnums=(0,))
    compiled = step.lower(state, {"tokens": tokens, "targets": tokens}).compile()
    return compiled, cfg, rows


def test_the_124m_cell_step_stacks_one_mlp_wide_residual_on_the_v5e(
        gpt2_124m_step):
    """Its layer scan writes ONE `[12, 8, 1024, 3072]` stack (the MLP's named
    hidden tensor), where AD left alone made it write six (the gelu's
    intermediates), and the step needs 5.07 GiB where that one needed
    9.25."""
    compiled, cfg, rows = gpt2_124m_step
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    wide = re.escape(f"= bf16[{cfg.n_layer},{rows},{cfg.seq_len},{cfg.d_ff}]")
    assert len(re.findall(wide + r"\S* dynamic-update-slice\(", hlo)) == 1
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert need < 5.5 * 2 ** 30, need / 2 ** 30


def _producers(hlo):
    """Every instruction of a compiled program: name → (opcode, operands)."""
    out = {}
    for name, op, args in re.findall(
            r"(%[\w.\-]+) = [^=]*? ([\w\-]+)\(([^)]*)\)", hlo):
        out.setdefault(name, (op, re.findall(r"%[\w.\-]+", args)))
    return out


def test_the_124m_cell_step_hands_the_flash_kernels_what_xla_stores(
        gpt2_124m_step):
    """PR 48, the regression PR 25 could not see. At hd = 64 the block
    projects its heads [H, B, hd, S] and the kernels read [rows, hd, S]: what
    XLA writes the projections' outputs and the layer scan's saved stacks as.
    So nothing re-lays a head tensor out on its way into a kernel: walking
    back from either Mosaic call through what only renames bytes (bitcasts,
    tuple elements, the loop's parameters), the forward call meets the
    projections' own fusions and the backward call the stacks' slices — no
    `copy` and no `transpose` — but for do, which the out-projection's
    backward product makes batch-major (one copy a layer, 25 MB; PERF.md
    §7). And no `copy` at all has a half-filled `[.., 1024, 64]` result: the
    parent's step held seven a layer."""
    compiled, cfg, rows = gpt2_124m_step
    hlo = compiled.as_text()
    made = _producers(hlo)

    def sources(name, seen):
        """The instructions that made ``name``'s bytes."""
        op, args = made.get(name, ("parameter", []))
        if op in ("bitcast", "get-tuple-element", "reshape") and args:
            return set().union(*(sources(a, seen) for a in args[:1]))
        return {(name, op)}

    calls = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = .* custom-call\(([^)]*)\)"
                     r".*tpu_custom_call", line)
        if m:
            kind = "bwd" if "flash_attention_bwd" in line else "fwd"
            calls[kind] = [a for a in re.findall(r"%[\w.\-]+", m.group(2))]
    assert set(calls) == {"fwd", "bwd"}
    relaid = {kind: sorted(n for a in args for n, op in sources(a, set())
                           if op in ("copy", "transpose"))
              for kind, args in calls.items()}
    assert relaid["fwd"] == [], relaid
    assert len(relaid["bwd"]) <= 1, relaid           # do
    for name in relaid["bwd"]:
        assert "/proj/" in re.search(
            re.escape(name) + r" = .*op_name=\"([^\"]*)\"", hlo).group(1)
    S, hd = cfg.seq_len, cfg.head_dim
    padded = [l for l in hlo.splitlines()
              if re.search(rf"= bf16\[[\d,]*{S},{hd}\]\S* copy\(", l)]
    assert padded == [], padded
    # the saved q, k, v, o are four dense [layers, H, B, hd, S] stacks
    stacks = set(re.findall(
        rf"bf16\[{cfg.n_layer},{cfg.n_head},{rows},{hd},{S}\]\{{4,3,2,1,0[:}}]",
        hlo))
    assert stacks, "no row-major [L, H, B, hd, S] stack in the step"


def test_the_evabyte_cell_step_holds_nothing_the_compiler_rematerialized(
        topo, monkeypatch):
    """`evabyte-6.5b-l4.dataset`'s whole train step — the cell's config, the
    step `train_step._compose_step` composes over `models/llama.py`, told a
    v5e's bytes_limit (`families/evabyte.abstract_step`: what
    `harness/rehearse_compile.py` compiles) — compiled for one described chip.
    The remat rule keeps k and the summaries, and XLA's own rematerialization
    pass, which runs when a step does not fit otherwise, cloned nothing: the
    parent's step held the k and v projections and k's rotation a second time
    in every layer's backward (`fusion.507.remat` …, 57 of the step's 1,591
    ms on the chip). One compile, ~20 s alone; under its own limit, not the
    suite's."""
    from conftest import time_limit

    from ray_tpu.models import blocks
    from ray_tpu.ops import cross_entropy
    from ray_tpu.tracing import names

    # recorded once a process by its facts: this test reads its own
    monkeypatch.setattr(blocks, "_decisions", {})
    monkeypatch.setattr(cross_entropy, "_decisions", {})
    cell, config, family, mesh = _cell_on(topo, "evabyte-6.5b-l4.dataset")
    with time_limit(240, "the EvaByte cell's compile for a described v5e"):
        step, args = family.abstract_step(config, cell, mesh)
        hlo = step.lower(*args).compile().as_text()
    assert blocks.compiler_rematerialized(hlo) == []
    # forward, the block's second forward and backward, a layer: the scan
    # holds each once
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
    (d,) = blocks.remat_policy_decisions()
    assert d["bytes_limit"] == family.V5E_BYTES_LIMIT
    assert d["saved"] == [names.RES_K, names.RES_EVA_KT, names.RES_EVA_VT]
    assert (d["mlp_rows"], d["head_rows"]) == (4096, 4096)
    # PR 39: the eight heads' loss in 8 chunks makes its gradient in the
    # forward; what that keeps that is new (the float32 d lm_head, 42 MB) is
    # in the rule's estimate and still leaves the three names room
    (h,) = cross_entropy.head_loss_decisions()
    assert h == dict(batch=1, rows=4096, chunks=8, columns=8 * 320, heads=8,
                     grad_in_forward=True,
                     residual_bytes=32768 * 4096 * 2 + 4096 * 2560 * 4,
                     carry_bytes_a_step=8 * 2 * 4096 * 2560 * 4)
    assert d["saved_bytes"] <= d["budget_bytes"] == 1_347_631_092 - 4096 * 2560 * 4
    assert not [line for line in hlo.splitlines()
                if "lm_head_loss" in line and "rematted_computation" in line]


@pytest.fixture(scope="module")
def nemotron_step(topo):
    """`nemotron-3-super-120b-l11.dataset`'s whole train step, as
    `families/nemotron_h.abstract_step` composes it, compiled for one
    described chip (~40 s alone; under its own limit): the compiled step, and
    the decisions it was traced with (remat rule, chunked head)."""
    from conftest import time_limit

    from ray_tpu.models import blocks
    from ray_tpu.ops import cross_entropy

    # recorded once a process by their facts: read this trace's own
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(blocks, "_decisions", {})
        mp.setattr(cross_entropy, "_decisions", {})
        cell, config, family, mesh = _cell_on(
            topo, "nemotron-3-super-120b-l11.dataset")
        with time_limit(280, "the Nemotron cell's compile for a described v5e"):
            step, args = family.abstract_step(config, cell, mesh)
            compiled = step.lower(*args).compile()
        return (compiled, blocks.remat_policy_decisions(),
                cross_entropy.head_loss_decisions())


def test_the_nemotron_cell_step_multiplies_a_chunks_logits_once(nemotron_step):
    """PR 39: the head — called twice a step, trunk and MTP module, 32 chunks
    of 8 x 128 tokens x 16,384 float32 columns each — makes a chunk's
    gradient where it makes its loss. Each call is ONE loop, in the forward,
    whose body holds three matmuls (logits, d x, d lm_head): the parent's
    held one, and its backward loop three more, the logits' a second time
    under the chunk's `checkpoint` (`rematted_computation`, 53.4 of the
    step's 1,315 ms on the chip)."""
    compiled, _, heads = nemotron_step
    hlo = compiled.as_text()
    scoped = [line for line in hlo.splitlines() if "lm_head_loss" in line]
    assert scoped and not [l for l in scoped if "rematted_computation" in l]
    dots = [re.search(r'op_name="([^"]*)"', l).group(1) for l in scoped
            if re.search(r" (convolution|dot)\(", l)]
    in_loop = [d for d in dots if "lm_head_loss/while/body" in d
               or "lm_head_loss)/while/body" in d]
    assert len(in_loop) == 6 == len(dots), dots
    assert not [d for d in dots if "transpose(jvp" in d], dots
    assert sum("jvp(mtp)" in d for d in dots) == 3, dots
    # the d lm_head of a call is the loop's carry, in float32
    assert len(re.findall(r"= f32\[4096,16384,1\]\S* convolution\(", hlo)) == 2
    (h,) = heads
    # (PR 65: a chunk of 8 x 128 = 1,024 tokens hides the carry and its
    # 64 MiB of logits live in the chip's fast memory: it stays)
    assert h == dict(batch=8, rows=128, chunks=32, columns=16384, heads=1,
                     grad_in_forward=True,
                     residual_bytes=32768 * 4096 * 2 + 4096 * 16384 * 4,
                     carry_bytes_a_step=32 * 2 * 4096 * 16384 * 4)
    # what parts.HEAD_CHUNK_BYTES rests on: the compiler puts a chunk's 64 MiB
    # of logits in the chip's fast memory (memory space 1), in both calls
    assert len(re.findall(r"= f32\[8,128,16384\]\{[^}]*S\(1\)\} convolution\(",
                          hlo)) == 2


def test_the_nemotron_cell_step_keeps_the_routing_and_clones_nothing(
        nemotron_step):
    """PR 42: the same compiled step, with what the remat rule now has room
    for. Its estimate follows the backward's phases — the scan of eight
    layers sets it — and stands a little above what the compiler holds, not
    2.5 GiB above, so there is a GiB to spend: on Δ's projection, on the
    routing's outcome (the `top_k`'s last value and index, the scores, the
    pairs' sorted keys and, since PR 51, their gates), on the gate projection of the Mamba layers and the
    attention layers' q, k, v, o and lse. The step needs 14.13 GiB of 15.75
    (13.36 with nothing kept), XLA's own rematerialization pass — which is
    recompute too — cloned nothing, and the blocks' second forward holds no
    `top_k` sort of [32,768 x 512], no sort of the 262,144 keys and no router
    product; the scatter-adds' own sorts of a pass's 14,336 indices stay."""
    from ray_tpu.models import blocks
    from ray_tpu.tracing import names

    compiled, (d,), _ = nemotron_step
    hlo = compiled.as_text()
    assert blocks.compiler_rematerialized(hlo) == []
    assert compiled.memory_analysis().peak_memory_in_bytes <= 14.6 * 2 ** 30
    assert (d["head_rows"], d["n_layer"]) == (128, 13)
    assert d["saved"] == [
        names.RES_MAMBA_DT, names.RES_MOE_KTH, names.RES_MOE_LAST,
        names.RES_MOE_SCORES, names.RES_MAMBA_Z, names.RES_Q, names.RES_K,
        names.RES_V, names.RES_FLASH_O, names.RES_FLASH_LSE,
        names.RES_MOE_PAIR_KEY, names.RES_MOE_PAIR_GATE]
    assert 0 < d["saved_bytes"] <= d["budget_bytes"] <= 1.25 * 2 ** 30
    assert d["phase"] == "4 x scan(ME)"
    again = [line for line in hlo.splitlines()
             if "rematted_computation" in line]
    assert again and not [l for l in again if "/top_k" in l]
    keys = {math.prod(int(n) for n in re.search(
        r"= \(?\w+\[([\d,]*)\]", l).group(1).split(","))
        for l in again if re.search(r" sort\(", l)}
    # (the 64: the grouped kernels' visits — 56 row tiles + 8 groups —, PR
    # 60, which a recomputed pass orders again)
    assert keys == {14336, 14336 // 256 + 8}, keys
    assert not [l for l in again if re.search(r" (dot|convolution)\(", l)
                and "f32[32768,512]" in l.split(" = ")[1][:40]]
    # and the forward still makes each once an expert layer's program (the
    # scan's body, the trunk's last layer, the MTP module's)
    assert len([l for l in hlo.splitlines() if "/top_k" in l
                and re.search(r" sort\(", l)]) == 3


def test_the_nemotron_cell_step_scans_in_two_kernels_under_the_scope(
        nemotron_step):
    """PR 41: the same compiled step holds the scan's kernel pair — forward,
    the recompute's forward and the backward of its five Mamba layers (one
    loop of four and one alone: six calls) — each custom call under the
    `ssd_scan` scope, where `ssd_scan_ms_per_step` and `ssd_scan_roofline`
    find it; and no float32 [.., 128, 128] of a chunk AND head (the decay
    matrices were 8 x 32 x 16 of them a layer)."""
    compiled, _, _ = nemotron_step
    hlo = compiled.as_text()
    calls = [re.search(r'op_name="([^"]*)"', l).group(1)
             for l in hlo.splitlines()
             if "tpu_custom_call" in l and "ssd_chunk" in l]
    assert len(calls) == 6 and all("/ssd_scan/" in c for c in calls), calls
    assert sum("ssd_chunk_bwd" in c for c in calls) == 2
    assert sum("rematted_computation" in c and "ssd_chunk_fwd" in c
               for c in calls) == 2
    square = {t for t in re.findall(r"f32\[[0-9,]*128,128\]", hlo)
              if math.prod(int(n) for n in t[4:-1].split(",")) > 8 * 32 * 128 * 128}
    assert not square, square


_MOE_T, _MOE_E, _MOE_HELD, _MOE_F = 32768, 512, 8, 2688


@pytest.fixture(scope="module")
def routed_experts_hlo(topo, one_chip):
    """`ops/moe.routed_experts` at the Nemotron cell's shapes (32,768 tokens,
    experts 24–31 of 512 held, top-22, latent 1,024 → 2,688), forward and
    backward, compiled for one described chip: the program text."""
    from ray_tpu.ops import moe
    from ray_tpu.parallel import mesh as mesh_lib

    D, latent, held = 4096, 1024, moe.Held(24, _MOE_HELD)

    def abstract(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {"router_w": abstract((D, _MOE_E), jnp.float32),
         "router_bias": abstract((_MOE_E,), jnp.float32),
         "w1": abstract((held.count, latent, _MOE_F)),
         "w2": abstract((held.count, _MOE_F, latent))}

    def loss(u, ell, p):
        return jnp.sum(moe.routed_experts(u, ell, p, top_k=22, held=held,
                                          scaling=5.0)[0])

    # (the step factory's mesh of the one described chip, as a cell's step
    # traces under: the kernels compile for it and are not interpreted)
    with mesh_lib.use_mesh(mesh_lib.make_mesh(mesh_lib.MeshSpec(),
                                              [topo.devices[0]])):
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            abstract((_MOE_T, D)), abstract((_MOE_T, latent)), p
        ).compile().as_text()


def test_the_held_experts_products_are_grouped_kernels_on_the_v5e(
        routed_experts_hlo):
    """Every one of the six grouped products a pass makes (two forward, two a
    backward operand side) — twice in the program since PR 51: the first pass
    stands outside the loop, further passes inside it — is a grouped kernel
    whose work follows the real group sizes — since PR 60 the program's own
    (`ops/grouped_matmul.py`, which `grouped_tiling` chose for all three
    forms at these shapes: a Mosaic call under the name a trace's reader
    finds the grouped products by, `ragged-dot`, and under NO `moe_routed`
    scope, whose time the reader adds the kernel's to), none the compiler's
    (`ragged-dot-none…`) — and none is expanded into one dense product an
    expert; the row buffer is 1.25× the mean in whole tiles (28 of 512 rows
    for 11,264 pairs), not the worst case."""
    from ray_tpu.ops import grouped_matmul, moe
    from ray_tpu.tracing import names

    hlo = routed_experts_hlo
    rows = moe.row_buffer(_MOE_T, _MOE_E, 22, _MOE_HELD)
    assert rows == 14336 < _MOE_T * _MOE_HELD
    assert {grouped_matmul.grouped_tiling(form, rows, _MOE_HELD, k, n, 2).impl
            for form in grouped_matmul.FORMS
            for k, n in ((1024, _MOE_F), (_MOE_F, 1024))} == {
        grouped_matmul.PALLAS}
    assert not re.search(r"%?ragged-dot-none\S* = ", hlo)
    assert hlo.count('custom_call_target="tpu_custom_call"') == 12
    # as the benchmark's reader files a device instruction (by its HLO name
    # and its op_name's elements): every Mosaic call is the grouped products'
    # kernel, and none counts under `moe_routed` a second time
    classify = _program_trace().classify
    calls = re.findall(r"^\s*(?:ROOT )?%(\S+) = [^\n]*?custom-call\([^\n]*?"
                       r"tpu_custom_call[^\n]*?op_name=\"([^\"]*)\"", hlo, re.M)
    filed = [classify(op, name, "mosaic") for name, op in calls]
    assert len(filed) == 12
    assert {c["kernel"] for c in filed} == {names.RAGGED_DOT_KERNEL}
    assert not [c for c in filed if names.MOE_ROUTED in c["scopes"]], filed
    by_form = {form: sum(f"/grouped_{form}/" in op for _, op in calls)
               for form in grouped_matmul.FORMS}
    assert by_form == {"gmm": 4, "gmm_t": 4, "tgmm": 4}, by_form
    # they say which way they run (the compiler's instructions carried no
    # op_name: direction "other"); a gradient's program holds the backward's
    # twelve — each pass's products made again, then their transposes
    assert {c["direction"] for c in filed} == {"bwd"}
    # no product of the whole buffer with one expert's matrix
    assert f"bf16[{rows},{_MOE_F}]" in hlo and not re.search(
        rf"= \S+\[{_MOE_HELD},{rows},", hlo)


# the four expert cells' grouped products, as `ops/moe._pass_rows` gives them
# to `grouped_dot`: (rows of the buffer, held experts, K, N of W1 / W3); W2's
# is [N, K]
_CELL_PRODUCTS = {
    "deepseek-v2-lite-l5": (61440, 16, 2048, 1408),
    "xing4.0-29b-a4b-l5": (5120, 8, 3584, 1024),
    "lfm2-24b-a2b-l5": (40960, 16, 2048, 1536),
    "nemotron-3-super-120b-l11": (14336, 8, 1024, 2688),
}


@pytest.mark.parametrize("cell", sorted(_CELL_PRODUCTS))
def test_the_grouped_kernels_compile_within_their_stated_vmem_on_the_v5e(
        cell, one_chip):
    """The kernel family of `ops/grouped_matmul.py` at one expert cell's
    shapes, both of an expert's matrix shapes, bf16, compiled for the
    described chip: what interpret mode cannot show — a product contracted
    over the rows' (sublane) dimension, a [K, N] block read transposed, a
    width of 11 or 21 lane tiles as ONE block — each call under the scoped
    VMEM its tiling states (Mosaic refuses one that needs more), which is
    past the default 16 MiB and under the ceiling."""
    from ray_tpu.ops import grouped_matmul as gm
    from ray_tpu.ops.attention import VMEM_BUDGET_BYTES, VMEM_CEILING_BYTES
    from ray_tpu.tracing import names

    rows, held, K, N = _CELL_PRODUCTS[cell]

    def abstract(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for k, n in ((K, N), (N, K)):
        operands = {"gmm": ((rows, k), (held, k, n)),
                    "gmm_t": ((rows, n), (held, k, n)),
                    "tgmm": ((rows, k), (rows, n))}
        for form in gm.FORMS:
            tiling = gm.grouped_tiling(form, rows, held, k, n, 2)
            assert tiling.impl == gm.PALLAS and rows % tiling.row_tile == 0
            assert (VMEM_BUDGET_BYTES < tiling.vmem_estimate
                    <= VMEM_CEILING_BYTES * 2 // 3)
            hlo = jax.jit(functools.partial(
                gm._pallas_product, form, tiling=tiling, interpret=False)
            ).lower(*(abstract(s) for s in operands[form]),
                    abstract((held,), jnp.int32)).compile().as_text()
            (call,) = [op for _, code, op in _instructions(hlo)
                       if code == "custom-call" and "pallas_call" in op]
            assert _program_trace().classify(call, f"grouped_{form}.1", "mosaic")[
                "kernel"] == names.RAGGED_DOT_KERNEL
            # what the call states, and what Mosaic took of it: the
            # estimate is an upper bound that holds
            (line,) = [line for line in hlo.splitlines()
                       if 'custom_call_target="tpu_custom_call"' in line]
            (stated,) = re.findall(
                r'(?<!used_)scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', line)
            (used,) = re.findall(
                r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', line)
            assert int(stated) == (tiling.vmem_estimate
                                   + tiling.vmem_estimate // 2), (form, stated)
            assert int(used) <= tiling.vmem_estimate, (form, used, tiling)


def _elements(hlo):
    """Every instruction's (first) result: name → elements."""
    return {name: math.prod(int(d) for d in dims.split(",") if d)
            for name, dims in re.findall(
                r"(%[\w.\-]+) = \(?\w+\[([\d,]*)\]", hlo)}


def test_a_pass_looks_up_its_own_rows_and_no_more_on_the_v5e(
        routed_experts_hlo):
    """PR 36: what a row costs is looked up by the pass that runs it — its
    token's latent, its gate, and in the backward their transposes — so every
    `gather` and `scatter` of the program is indexed by one pass's rows
    (14,336) and none by passes × rows or more (19 × 14,336 ≥ 262,144: the
    parent gathered the gates of all the passes a layer, 270,336 scalars, and
    scattered their cotangents back, for 11,264 pairs)."""
    from ray_tpu.ops import moe

    hlo = routed_experts_hlo
    rows = moe.row_buffer(_MOE_T, _MOE_E, 22, _MOE_HELD)
    passes = moe.buffer_passes(_MOE_T, _MOE_E, 22, _MOE_HELD)
    assert passes * rows >= _MOE_T * _MOE_HELD
    size = _elements(hlo)
    # the indices are both ops' second operand, one index a looked-up slice
    indexed = [size[m.group(1)] for m in re.finditer(
        r" (?:gather|scatter)\(%[\w.\-]+, (%[\w.\-]+)", hlo)]
    # (the gradient's program holds the backward's: three lookups, two
    # scatter-adds and what the compiler makes of them)
    assert len(indexed) >= 5, indexed
    assert max(indexed) == rows, indexed


def test_routing_gathers_no_score_and_sorts_the_membership_on_the_v5e(
        routed_experts_hlo):
    """PR 34: the gates come from a masked row-sum and a static slice of the
    scores, so no `gather` (and no `scatter`, its transpose) touches a
    [32,768 x 512] array; the pairs are sorted from the [tokens x 8]
    membership, so beside `top_k`'s own sort no `sort` has more than 262,144
    keys, and the one that has them takes two operands — the key, which
    carries its token, and since PR 51 the gate that lies at its place: no
    index rides along, and no gate is looked up afterwards (below)."""
    hlo = routed_experts_hlo

    size = _elements(hlo)
    ops = [line.strip().removeprefix("ROOT ") for line in hlo.splitlines()
           if re.search(r" (gather|scatter|sort)\(", line)]
    # a gather reads its first operand, a scatter writes its result
    read = [size[re.search(r" gather\((%[\w.\-]+)", op).group(1)]
            for op in ops if " gather(" in op]
    written = [size[op.split(" = ")[0]] for op in ops if " scatter(" in op]
    assert read and written
    assert _MOE_T * _MOE_E not in read + written, (read, written)
    sorts = [op for op in ops if " sort(" in op]
    top_k = [op for op in sorts if "/top_k" in op]
    assert top_k and all(
        size[op.split(" = ")[0]] == _MOE_T * _MOE_E for op in top_k)
    operands = {}                                   # keys → operands
    for op in set(sorts) - set(top_k):
        shapes = re.findall(r"\w+\[[\d,]*\]", op.split(" sort(")[0])
        keys = size[op.split(" = ")[0]]
        operands[keys] = max(operands.get(keys, 0), len(shapes))
    assert max(operands) == _MOE_T * _MOE_HELD, operands
    assert operands[_MOE_T * _MOE_HELD] == 2, operands
    # no gather reads the flat [held · T] table of gates: the only scalars
    # that move by key are the gates' cotangents, scatter-added a pass
    assert _MOE_T * _MOE_HELD not in read, read
    assert _MOE_T * _MOE_HELD in written, written


@pytest.fixture(scope="module")
def lfm2_tiny_step(topo):
    """The LFM2 cell's step at every published width and a tiny batch (2 rows
    of 512 tokens: three passes in the worst case; through
    `families/lfm2_moe.abstract_step`) lowered and compiled for one described
    chip, ~25 s: (the compiled step's text, the lowered step's, the program's
    config, the cell as cut)."""
    from conftest import time_limit

    from ray_tpu.models import blocks

    cell, config, family, mesh = _cell_on(topo, "lfm2-24b-a2b-l5.dataset")
    cell.update(seq_len=512, per_chip_batch=2)
    cfg = family.program_config(config, cell)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(blocks, "_decisions", {})
        with time_limit(240, "the LFM2 step's compile for a described v5e"):
            step, args = family.abstract_step(config, cell, mesh)
            lowered = step.lower(*args)
            hlo = lowered.compile().as_text()
    return hlo, lowered.as_text(), cfg, cell


def _instructions(hlo):
    """(result shape, opcode, op_name) of every instruction that has one."""
    return [m.groups() for m in re.finditer(
        r"^\s*(?:ROOT )?%\S+ = (.*?) (\w[\w\-]*)\(.*?op_name=\"([^\"]*)\"",
        hlo, re.M)]


def test_the_lfm2_tiny_step_runs_one_pass_outside_the_loop_on_the_v5e(
        lfm2_tiny_step):
    """PR 51: in the LFM2 cell's step at a tiny batch (`lfm2_tiny_step`) the
    first
    pass of an expert layer stands outside any loop; what only a batch with
    more passes needs is carried by a `while` under `moe_further_passes` —
    every loop of the routed experts, and with it every float32 [held, width,
    d_expert] tensor; no pass looks a gate up
    out of the flat [held · T] table (the sort laid them in pair order); and
    the compiler rematerialized nothing."""
    from ray_tpu.models import blocks
    from ray_tpu.tracing import names

    hlo, text, cfg, cell = lfm2_tiny_step
    assert blocks.compiler_rematerialized(hlo) == []

    ops = [(shape, op) for shape, _, op in _instructions(hlo)]
    routed = [(shape, op) for shape, op in ops if f"/{names.MOE_ROUTED}/" in op]
    further = [(shape, op) for shape, op in routed
               if f"/{names.MOE_FURTHER_PASSES}/" in op]
    assert further and len(further) < len(routed)
    # every loop of the routed experts is the further passes'
    assert all(f"/{names.MOE_FURTHER_PASSES}/while" in op
               for _, op in routed if "/while" in op.split(names.MOE_ROUTED)[1])
    # forward and backward (the backward's second forward of them is dead)
    assert any("transpose(" in op for _, op in further)
    assert any("transpose(" not in op for _, op in further)
    held, width, d_expert = cfg.held_count, cfg.d_model, cfg.d_expert
    sums = re.compile(rf"f32\[{held},({width},{d_expert}|{d_expert},{width})\]")
    assert [op for shape, op in routed
            if sums.search(shape) and (shape, op) not in further] == []
    assert [op for shape, op in further if sums.search(shape)]
    # (the sums' low halves start as zeros a layer, outside the loop: the one
    # thing the one-pass path pays for the passes it does not run)
    low = [op for shape, op in routed if re.search(rf"u16\[{held},", shape)
           and (shape, op) not in further]
    assert low and all(f"/{names.MOE_DISPATCH}/" in op for op in low), low
    # no scalar gather out of the [held · T] table, in the program as traced
    T = cell["per_chip_batch"] * cfg.seq_len
    gathers = re.findall(r'"?stablehlo\.gather"?\(.*?:\s*\((tensor<[^>]*>)', text)
    assert gathers and f"tensor<{held * T}xf32>" not in gathers, set(gathers)


def test_the_conv_gate_kernels_compile_for_the_v5e_at_the_cells_shape(one_chip):
    """PR 53: ops/short_conv's kernel pair at the tile the rule chooses, at
    the LFM2 cell's shape ([8, 4,096, 6,144] bf16, w [3, 2,048]), forward and
    backward: what interpret mode cannot show — the sublane rotations, the
    halo blocks, the lane cuts of the one BCx block, more VMEM than the call
    asked for — and that outside the two calls the compiled op holds no
    [B, S, D] float32 tensor and makes d BCx in no second piece."""
    from ray_tpu.ops import short_conv
    from ray_tpu.ops.attention import VMEM_BUDGET_BYTES, VMEM_CEILING_BYTES

    B, S, D = 8, 4096, 2048
    bcx = jax.ShapeDtypeStruct((B, S, 3 * D), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, D), jnp.float32, sharding=one_chip)
    fwd, bwd = (short_conv.choose_conv_tiling(k, S, D, 2) for k in ("fwd", "bwd"))
    assert fwd[:2] == bwd[:2] == (256, 512)
    assert fwd.vmem_estimate <= VMEM_BUDGET_BYTES < bwd.vmem_estimate
    assert bwd.vmem_estimate <= VMEM_CEILING_BYTES // 2

    dy = jax.ShapeDtypeStruct((B, S, D), jnp.bfloat16, sharding=one_chip)

    def grads(bcx, w, dy):
        y, vjp = jax.vjp(lambda *a: short_conv._conv_gate(*a, False), bcx, w)
        return (y,) + vjp(dy)

    hlo = jax.jit(grads).lower(bcx, w, dy).compile().as_text()
    calls = [l for l in hlo.splitlines() if "tpu_custom_call" in l and " = " in l]
    assert sum("conv_gate_fwd" in l for l in calls) == 1
    assert sum("conv_gate_bwd" in l for l in calls) == 1 and len(calls) == 2
    assert not re.findall(rf"f32\[{B},{S},({D}|{3 * D})\]", hlo)
    assert not re.findall(
        rf"= bf16\[{B},{S},{3 * D}\]\S* (concatenate|pad)\(", hlo)


def test_the_lfm2_tiny_step_gates_and_convolves_in_two_kernels_under_the_scope(
        lfm2_tiny_step):
    """PR 53: the same compiled step holds the conv-gate kernel pair — the
    forward, the recompute's forward and the backward of its conv layers (one
    alone and a loop of three: six calls) — each Mosaic call under the
    `conv_gate` scope, where `conv_gate_ms_per_step` finds it; under that
    scope no [B, S, D] float32 tensor is made outside a Mosaic call (`z` and
    the three shifted gradients were); and d BCx leaves the backward whole:
    no `concatenate` or `pad` makes a [B, S, 3·D] tensor."""
    from ray_tpu.tracing import names

    hlo, _, cfg, cell = lfm2_tiny_step
    B, S, D = cell["per_chip_batch"], cfg.seq_len, cfg.d_model
    ops = _instructions(hlo)
    calls = [op for _, code, op in ops if code == "custom-call"
             and "conv_gate_" in op]
    assert len(calls) == 6, calls
    assert all(f"/{names.CONV_GATE}/" in op and f"/{names.SHORT_CONV}/" in op
               for op in calls), calls
    fwd, bwd = names.CONV_GATE_FWD_KERNEL, names.CONV_GATE_BWD_KERNEL
    assert sum(f"/{bwd}/" in op and "transpose(" in op for op in calls) == 2
    assert sum(f"/{fwd}/" in op and "rematted_computation" in op
               for op in calls) == 2
    assert sum(f"/{fwd}/" in op and "transpose(" not in op for op in calls) == 2
    wide = re.compile(rf"f32\[{B},{S},{D}\]")
    assert [(shape, op) for shape, code, op in ops
            if f"/{names.CONV_GATE}/" in op and code != "custom-call"
            and wide.search(shape)] == []
    assert not re.findall(
        rf"= \w+\[{B},{S},{3 * D}\]\S* (concatenate|pad)\(", hlo)


def test_the_deepseek_cell_step_fits_and_clones_nothing_on_the_v5e(topo):
    """`deepseek-v2-lite-l5.dataset`'s own step — 4 rows of 8,192 tokens, 16
    of 64 experts held — compiled for the described chip: it fits what the
    remat rule works to (the compiler's peak is under the chip's bytes_limit
    less the rule's reserve), nothing is rematerialized by the compiler, and
    its Mosaic calls are the flash pair at 192 / 128 — a forward and a
    backward in the dense layer and in the scan of four expert layers: 4,
    and NO recomputed forward, since the rule keeps the kernel's o and lse
    (PR 56: an expert layer's block is the largest moment of its backward,
    not the moments' sum) — and the held experts' grouped products: 24,
    since PR 60 the program's own kernels (`ops/grouped_matmul.py`), whose
    visits are made by XLA — the compiler's kernel brought 6 metadata
    kernels of its own beside its 24 calls."""
    from ray_tpu.models import blocks
    from ray_tpu.ops.attention import S_MINOR
    from ray_tpu.tracing import names
    from ray_tpu.train.train_step import _resident_bytes

    cell, config, family, mesh = _cell_on(topo, "deepseek-v2-lite-l5.dataset")
    fn, args = family.abstract_step(config, cell, mesh)
    compiled = fn.lower(*args).compile()
    hlo = compiled.as_text()
    assert blocks.compiler_rematerialized(hlo) == []
    assert hlo.count('custom_call_target="tpu_custom_call"') == 4 + 24
    flash = [op for _, code, op in _instructions(hlo)
             if code == "custom-call" and "flash_attention_" in op]
    assert sum(f"/{names.FLASH_FWD_KERNEL}" in op for op in flash) == 2
    assert sum(f"/{names.FLASH_BWD_KERNEL}" in op for op in flash) == 2
    assert not [op for op in flash if "rematted_computation" in op], flash
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak <= family.V5E_BYTES_LIMIT - blocks.REMAT_RESERVE_BYTES, (
        peak / 2 ** 30)
    assert peak >= 0.8 * family.V5E_BYTES_LIMIT         # a deployment's size
    mine = {d["kernel"]: d for d in flash_tiling_decisions()
            if (d["rows"], d["Sq"], d["hd"], d["hd_v"]) == (64, 8192, 192, 128)}
    assert set(mine) == {"fwd", "bwd"}
    assert all(d["layout"] == S_MINOR and d["block_q"] == d["block_k"] == 512
               for d in mine.values())
    (policy,) = [d for d in blocks.remat_policy_decisions()
                 if (d["n_layer"], d["seq"]) == (5, cell["seq_len"])
                 and d["bytes_limit"] == family.V5E_BYTES_LIMIT]
    assert policy["phase"] == "4 x scan(E)"
    assert 5.0e9 <= policy["phase_bytes"] <= 5.4e9
    assert policy["saved"][:2] == [names.RES_FLASH_O, names.RES_FLASH_LSE]
    assert {names.RES_MLA_C, names.RES_MLA_KPE, names.RES_MOE_SCORES} < set(
        policy["saved"])
    assert not {names.RES_Q, names.RES_MID, names.RES_MOE_SHARED_GATE,
                names.RES_MOE_SHARED_UP} & set(policy["saved"])
    # the estimate stays over what the compiler needs with these kept
    assert peak <= (_resident_bytes(args[0]) + policy["phase_bytes"]
                    + policy["saved_bytes"])


def test_the_xing4_cell_step_fits_and_clones_nothing_on_the_v5e(topo):
    """`xing4.0-29b-a4b-l5.dataset`'s own step — 1 row of 8,192 tokens, four
    hyper-connection streams, 8 of 64 experts held, the MTP module — compiled
    for the described chip: it fits what the remat rule works to (the
    compiler's peak is under the chip's bytes_limit less the rule's reserve)
    with the named residuals the rule gave room, nothing is rematerialized by
    the compiler, and its Mosaic calls are the flash pair at 192 / 128 over
    32 heads — a forward and a backward in each of its three runs of layers
    (the dense layer, the scan of four expert layers, the MTP module's) and NO
    recomputed forward, since the rule keeps the kernel's o and lse — and the
    held experts' grouped products with their metadata kernels. Since PR 58
    the hyper-connection's passes over the carry are the four `mhc_*` kernels
    (ops/hyper_connections.py), in each of the three runs of layers: both
    sublayers' mix and write-back forward, the recompute's mix, write-back,
    mix (the carry after attention is made again; the last write-back's
    result is nobody's residual), both sublayers' two backward kernels — every
    one under `/mhc/`, the mix pair under `/mhc/mhc_maps/` too, backward
    instances included; no sublayer's write-back puts the carry together by
    a `concatenate` (the stream's start, outside any block, is `expand`'s)."""
    from ray_tpu.models import blocks, hyper_connections
    from ray_tpu.tracing import names
    from ray_tpu.train.train_step import _resident_bytes

    cell, config, family, mesh = _cell_on(topo, "xing4.0-29b-a4b-l5.dataset")
    fn, args = family.abstract_step(config, cell, mesh)
    compiled = fn.lower(*args).compile()
    hlo = compiled.as_text()
    assert blocks.compiler_rematerialized(hlo) == []
    flash = [op for _, code, op in _instructions(hlo)
             if code == "custom-call" and "flash_attention_" in op]
    assert sum(f"/{names.FLASH_FWD_KERNEL}" in op for op in flash) == 3
    assert sum(f"/{names.FLASH_BWD_KERNEL}" in op for op in flash) == 3
    assert not [op for op in flash if "rematted_computation" in op], flash
    mhc = [op for _, code, op in _instructions(hlo)
           if code == "custom-call" and "/mhc_" in op and "pallas_call" in op]
    assert all(f"/{names.MHC}/" in op for op in mhc), mhc

    def count(kernel, *marks):
        return sum(f"/{kernel}/" in op and all(
            (mark[1:] not in op) if mark[0] == "-" else (mark in op)
            for mark in marks) for op in mhc)

    fwd, bwd, again = "-transpose(", "transpose(", "rematted_computation"
    mix = (names.MHC_MIX_FWD_KERNEL, names.MHC_MIX_BWD_KERNEL)
    assert all(f"/{names.MHC}/{names.MHC_MAPS}/" in op for op in mhc
               if any(f"/{k}/" in op for k in mix)), mhc
    assert not [op for op in mhc if f"/{names.MHC_MAPS}/" in op
                and not any(f"/{k}/" in op for k in mix)], mhc
    assert count(names.MHC_MIX_FWD_KERNEL, fwd) == 3 * 2
    assert count(names.MHC_MIX_FWD_KERNEL, bwd, again) == 3 * 2
    assert count(names.MHC_WRITE_FWD_KERNEL, fwd) == 3 * 2
    assert count(names.MHC_WRITE_FWD_KERNEL, bwd, again) == 3 * 1
    assert count(names.MHC_MIX_BWD_KERNEL, bwd, "-" + again) == 3 * 2
    assert count(names.MHC_WRITE_BWD_KERNEL, bwd, "-" + again) == 3 * 2
    assert len(mhc) == 3 * (4 + 3 + 4)
    carry = f"{cell['seq_len']},{4 * config['hidden_size']}]"
    assert [(shape, op) for shape, code, op in _instructions(hlo)
            if carry in shape and (code == "concatenate" or (
                "concatenate" in op and f"/{names.BLOCK}/" in op))] == []
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak <= family.V5E_BYTES_LIMIT - blocks.REMAT_RESERVE_BYTES, (
        peak / 2 ** 30)
    assert peak >= 0.8 * family.V5E_BYTES_LIMIT         # a deployment's size
    (policy,) = [d for d in blocks.remat_policy_decisions()
                 if (d["n_layer"], d["seq"], d["batch"]) == (
                     6, cell["seq_len"], 1)
                 and d["bytes_limit"] == family.V5E_BYTES_LIMIT]
    assert policy["phase"] == "4 x scan(E)"
    assert policy["saved"][:2] == [names.RES_FLASH_O, names.RES_FLASH_LSE]
    assert names.RES_MID not in policy["saved"]         # the 4-stream carry
    # the estimate stays over what the compiler needs with these kept
    assert peak <= (_resident_bytes(args[0]) + policy["phase_bytes"]
                    + policy["saved_bytes"])
    assert {"streams": 4, "rounds": 20, "stream_dtype": "bfloat16",
            "carry_bytes_per_token": 28672} in hyper_connections.decisions()


@pytest.mark.parametrize("C,heads", [(2048, 16), (4096, 0)],
                         ids=["q-k-with-the-norm", "v-without"])
def test_the_conv_norm_kernels_compile_for_the_v5e_at_the_cells_shape(
        one_chip, C, heads):
    """PR 63: ops/delta_pointwise's first pair at the tile the rule chooses,
    at the Qwen3-Next cell's shapes (q and k [4, 8,192, 2,048] bf16 with the
    per-head norm, v [4, 8,192, 4,096] without, w [4, C]), forward and
    backward: what interpret mode cannot show — the sublane rotations, the
    halo blocks on both sides, a head's lane reduction, more VMEM than
    Mosaic's default — and that outside the two calls the compiled op holds
    no [B, S, C] float32 tensor."""
    from ray_tpu.ops import delta_pointwise as dp
    from ray_tpu.ops.attention import VMEM_BUDGET_BYTES

    B, S = 4, 8192
    x = jax.ShapeDtypeStruct((B, S, C), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((4, C), jnp.float32, sharding=one_chip)

    def grads(x, w, dy):
        y, vjp = jax.vjp(lambda *a: dp._conv_norm(
            *a, heads, 128 ** -0.5 if heads else 1.0, False), x, w)
        return (y,) + vjp(dy)

    hlo = jax.jit(grads).lower(x, w, x).compile().as_text()
    calls = [l for l in hlo.splitlines() if "tpu_custom_call" in l and " = " in l]
    assert sum("delta_conv_norm_fwd" in l for l in calls) == 1
    assert sum("delta_conv_norm_bwd" in l for l in calls) == 1 and len(calls) == 2
    assert not re.findall(rf"f32\[{B},{S},{C}\]", hlo)
    mine = {d["kernel"]: d for d in dp.pointwise_tiling_decisions()
            if (d["rows"], d["S"], d["channels"], d["heads"]) == (B, S, C, heads)}
    assert set(mine) == {dp.CONV_NORM_FWD, dp.CONV_NORM_BWD}
    assert all(d["token_tile"] == 256 and d["vmem_estimate"] <= VMEM_BUDGET_BYTES
               for d in mine.values())


def test_the_gate_norm_kernels_compile_for_the_v5e_at_the_cells_shape(one_chip):
    """PR 63: the second pair at the cell's shape (o, z [4, 8,192, 4,096]
    bf16: 32 heads of 128, one gain vector), forward and backward."""
    from ray_tpu.ops import delta_pointwise as dp
    from ray_tpu.ops.attention import VMEM_BUDGET_BYTES

    B, S, C, heads = 4, 8192, 4096, 32
    o = jax.ShapeDtypeStruct((B, S, C), jnp.bfloat16, sharding=one_chip)
    gain = jax.ShapeDtypeStruct((C // heads,), jnp.float32, sharding=one_chip)

    def grads(o, z, gain, dy):
        y, vjp = jax.vjp(lambda *a: dp._gate_norm(*a, heads, 1e-6, False),
                         o, z, gain)
        return (y,) + vjp(dy)

    hlo = jax.jit(grads).lower(o, o, gain, o).compile().as_text()
    calls = [l for l in hlo.splitlines() if "tpu_custom_call" in l and " = " in l]
    assert sum("delta_gate_norm_fwd" in l for l in calls) == 1
    assert sum("delta_gate_norm_bwd" in l for l in calls) == 1 and len(calls) == 2
    assert not re.findall(rf"f32\[{B},{S},{C}\]", hlo)
    mine = {d["kernel"]: d for d in dp.pointwise_tiling_decisions()
            if (d["rows"], d["S"], d["channels"], d["heads"]) == (B, S, C, heads)}
    assert set(mine) == {dp.GATE_NORM_FWD, dp.GATE_NORM_BWD}
    assert all(d["vmem_estimate"] <= VMEM_BUDGET_BYTES for d in mine.values())


@pytest.mark.parametrize("heads,theta", [(32, 10_000.0), (4, 10_000.0),
                                         (32, None)],
                         ids=["q-window", "k-window", "q-full"])
def test_the_norm_rope_kernels_compile_for_the_v5e_at_the_cells_shape(
        one_chip, heads, theta):
    """PR 67: ops/attention_pointwise's first pair at the Trinity cell's
    shapes (q [2, 16,384, 32 · 128], k at 4 heads, bf16; with the rotation
    and without), forward and backward: what interpret mode cannot show —
    the lane roll, a head's dynamic lane window of a flat block, blocks whose
    two sides lie in two orders — and that outside the two calls the
    compiled op holds no float32 tensor of the whole shape and no copy of
    one (the index maps re-order; nothing else does)."""
    from ray_tpu.ops import attention_pointwise as ap

    B, S, hd = 2, 16384, 128
    x = jax.ShapeDtypeStruct((B, S, heads * hd), jnp.bfloat16, sharding=one_chip)
    dy = jax.ShapeDtypeStruct((B, heads, S, hd), jnp.bfloat16, sharding=one_chip)
    gain = jax.ShapeDtypeStruct((hd,), jnp.float32, sharding=one_chip)

    def grads(x, gain, dy):
        y, vjp = jax.vjp(lambda *a: ap._norm_rope(*a, heads, 1e-5, theta,
                                                  False), x, gain)
        return (y,) + vjp(dy)

    hlo = jax.jit(grads).lower(x, gain, dy).compile().as_text()
    calls = [l for l in hlo.splitlines() if "tpu_custom_call" in l and " = " in l]
    assert sum("head_norm_rope_fwd" in l for l in calls) == 1
    assert sum("head_norm_rope_bwd" in l for l in calls) == 1 and len(calls) == 2
    whole = rf"\[{B},({S},{heads * hd}|{heads},{S},{hd})\]"
    assert not re.findall("f32" + whole, hlo)
    assert not [l for l in hlo.splitlines()
                if re.search(r" = bf16" + whole + r"\S* copy\(", l)]


def test_the_attention_gate_kernels_compile_for_the_v5e_at_the_cells_shape(
        one_chip):
    """PR 67: the second pair at the cell's shape (o [2, 32, 16,384, 128], the
    gate's logits [2, 16,384, 4,096], bf16), forward and backward."""
    from ray_tpu.ops import attention_pointwise as ap

    B, H, S, hd = 2, 32, 16384, 128
    o = jax.ShapeDtypeStruct((B, H, S, hd), jnp.bfloat16, sharding=one_chip)
    z = jax.ShapeDtypeStruct((B, S, H * hd), jnp.bfloat16, sharding=one_chip)

    def grads(o, z, dy):
        y, vjp = jax.vjp(lambda *a: ap._gate(*a, False), o, z)
        return (y,) + vjp(dy)

    hlo = jax.jit(grads).lower(o, z, z).compile().as_text()
    calls = [l for l in hlo.splitlines() if "tpu_custom_call" in l and " = " in l]
    assert sum("attn_gate_fwd" in l for l in calls) == 1
    assert sum("attn_gate_bwd" in l for l in calls) == 1 and len(calls) == 2
    whole = rf"\[{B},({S},{H * hd}|{H},{S},{hd})\]"
    assert not re.findall("f32" + whole, hlo)
    assert not [l for l in hlo.splitlines()
                if re.search(r" = bf16" + whole + r"\S* copy\(", l)]
