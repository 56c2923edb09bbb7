"""`remat=True` recomputes what does not fit (PR 28): the rule as a pure
function of the shard and the chip's free bytes, the named residuals a
policy-`checkpoint` keeps (in the trunk's scan and a pipeline stage's), the
`model/remat_policy` event — and `remat=False`, which keeps every name and
lets the scan stack nothing else (PR 30).

All on the CPU: the Pallas kernels interpret, the chip's memory is stated by
the test through `parallel.mesh.chip_memory`, as the step factory states it on
a TPU.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import tracing
from ray_tpu.core.config import _config
from ray_tpu.models import blocks, gpt2, llama, parts
from ray_tpu.ops import attention
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.tracing import names
from ray_tpu.train import train_step
from ray_tpu.train.train_step import make_gpt2_train_step, synthetic_batch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)               # the benchmark's families

GIB = 2 ** 30
V5E_BYTES_LIMIT = 16909336064          # memory_stats()["bytes_limit"] of a v5e chip

# one chip's share of `gpt2-xl.fsdp4-dataset` (8 rows of the 32) and of a
# `gpt2-124m` step with the same rows
XL = parts.BlockShard(batch=8, seq=1024, d_model=1600, heads=25, head_dim=64,
                      d_ff=6400, vocab=50304, dtype_bytes=2, flash=True,
                      dense_mlp=True)
XL_LAYERS = 48
# its placed state (f32 parameters, two bf16 moments) and the gradients, a chip
XL_RESIDENT = 4_677_897_000
SMALL = XL._replace(d_model=768, heads=12, d_ff=3072)
SMALL_LAYERS = 12
SMALL_RESIDENT = 1_493_700_000

# the block of `evabyte-6.5b-l4.dataset`: one row of 32,768 bytes, four
# published-width layers, 12 B a parameter resident
EVA_CFG = llama.evabyte_6p5b(n_layer=4, remat=True, attention_impl="pallas")
EVA = llama.block_shard(EVA_CFG, 1, EVA_CFG.seq_len, None)
EVA_LAYERS = 4
EVA_RESIDENT = 12 * llama.param_count(EVA_CFG)
# what that cell's step compiles with for a v5e, nothing rematerialized by
# the compiler (test_flash_attention_tpu_compile.py): k, whose float32 copy
# for the summary pass goes with it, and the summaries
EVA_KEPT = (names.RES_K, names.RES_EVA_KT, names.RES_EVA_VT)

FLASH = (names.RES_FLASH_O, names.RES_FLASH_LSE)
QKV = (names.RES_Q, names.RES_K, names.RES_V)
EVERYTHING = FLASH + QKV + (names.RES_MID, names.RES_MLP_HIDDEN)


def _limit_admitting(shard, n_layer, resident, groups):
    """The smallest bytes_limit whose budget holds the first `groups`
    candidates of `shard`."""
    need = sum(n_layer * c.nbytes
               for c in parts.remat_candidates(shard)[:groups])
    return (blocks.REMAT_RESERVE_BYTES + resident
            + parts.rematted_working_set(shard, n_layer) + need)


RULE_CASES = {
    # the cell on the chip it runs on: q, k and v fit beside o and lse, the
    # block's mid-point does not
    "xl_at_15.75GiB": (XL, XL_LAYERS, V5E_BYTES_LIMIT, XL_RESIDENT, FLASH + QKV),
    "xl_room_for_o_lse_only": (
        XL, XL_LAYERS, _limit_admitting(XL, XL_LAYERS, XL_RESIDENT, 1) + 1000,
        XL_RESIDENT, FLASH),
    "xl_nothing_free": (
        XL, XL_LAYERS, _limit_admitting(XL, XL_LAYERS, XL_RESIDENT, 0),
        XL_RESIDENT, ()),
    "xl_over_full": (XL, XL_LAYERS, 4 * GIB, XL_RESIDENT, ()),
    "xl_no_limit_reported": (XL, XL_LAYERS, None, XL_RESIDENT, ()),
    "gpt2_124m_shard": (SMALL, SMALL_LAYERS, V5E_BYTES_LIMIT, SMALL_RESIDENT,
                        EVERYTHING),
    # k before q: keeping it frees the float32 copy a recomputed k stands in
    "evabyte_cell_at_15.75GiB": (EVA, EVA_LAYERS, V5E_BYTES_LIMIT, EVA_RESIDENT,
                                 EVA_KEPT),
    "evabyte_room_for_k_only": (
        EVA, EVA_LAYERS,
        _limit_admitting(EVA, EVA_LAYERS, EVA_RESIDENT, 1) - 4 * 32768 * 4096,
        EVA_RESIDENT, (names.RES_K,)),
}


@pytest.mark.parametrize("case", list(RULE_CASES))
def test_rule_takes_names_in_order_while_they_fit(case):
    shard, n_layer, limit, resident, want = RULE_CASES[case]
    policy = parts.choose_remat_policy(shard, n_layer, limit, resident)
    assert policy.saved == want
    candidates = parts.remat_candidates(shard)
    sizes = {c.names: n_layer * c.nbytes for c in candidates}
    frees = {c.names: c.frees for c in candidates}
    taken = [g for g in sizes if set(g) <= set(policy.saved)]
    # the order of the list, and the bytes of exactly what was taken
    assert policy.saved == tuple(n for g in taken for n in g)
    assert policy.saved_bytes == sum(sizes[g] for g in taken)
    assert policy.saved_bytes <= policy.budget_bytes
    if limit is None:
        assert policy[1:] == (0, 0, 0)
        return
    assert policy.bytes_limit == limit
    # reserve respected: everything counted — the working set less what the
    # kept names freed of it — still leaves it free ...
    counted = (resident + parts.rematted_working_set(shard, n_layer)
               - sum(frees[g] for g in taken) + policy.saved_bytes)
    assert counted + blocks.REMAT_RESERVE_BYTES <= limit or not policy.saved
    # ... and nothing that was left out would have fitted
    for g, size in sizes.items():
        if g not in taken:
            assert policy.saved_bytes + size > policy.budget_bytes + frees[g]


def test_rule_on_the_cells_shards_byte_for_byte():
    """`gpt2-xl.fsdp4-dataset`'s decision is PR 28's to the byte: nothing a
    block with no window, no gate and no weights cast in the loop states
    reaches the working set or the order. The EvaByte cell's is the set its
    step compiles with, beside the rows its MLP and head take."""
    limit = 16_909_334_528          # a v5e's, as the chip states it
    assert parts.rematted_working_set(XL, XL_LAYERS) == 5_457_362_944
    resident = limit - blocks.REMAT_RESERVE_BYTES - 5_457_362_944 - 5_700_332_148
    assert parts.choose_remat_policy(XL, XL_LAYERS, limit, resident) == (
        FLASH + QKV, 5_072_486_400, 5_700_332_148, limit)
    assert all(c.frees == 0 for c in parts.remat_candidates(XL))

    assert (EVA.mlp_rows, EVA.head_rows) == (4096, 4096)
    policy = parts.choose_remat_policy(EVA, EVA_LAYERS, limit, EVA_RESIDENT)
    assert policy.saved == EVA_KEPT
    # k, and two summaries a sixteenth its size, in four layers
    assert policy.saved_bytes == 4 * (32768 * 4096 * 2) * 9 // 8
    k, q = parts.remat_candidates(EVA)[:2]
    assert (k.names, q.names) == ((names.RES_K,), (names.RES_Q,))
    # float32 k: what the summary pass reads, gone from the set with k kept
    assert (k.frees, q.frees) == (32768 * 4096 * 4, 0)
    assert policy.budget_bytes == (
        limit - blocks.REMAT_RESERVE_BYTES - EVA_RESIDENT
        - parts.rematted_working_set(EVA, EVA_LAYERS) + k.frees)


@pytest.mark.parametrize("shard, n_layer", [
    (XL, XL_LAYERS), (SMALL, SMALL_LAYERS), (EVA, EVA_LAYERS)],
    ids=["gpt2-xl-shard", "gpt2-124m", "evabyte"])
def test_one_kind_in_one_scan_has_one_phase_and_it_is_the_sum(shard, n_layer):
    """PR 42: the working set follows the backward's phases. A model of one
    kind in one scan has the head's phase and the scan's; the scan's — every
    block input, the head's terms, the gathered embedding and a block — is
    `rematted_working_set` to the byte, whatever its layers' gradients take,
    and the head's is that less the block (and the gradients not yet made)."""
    kind = blocks.KindShard(n_layer, tuple(parts.remat_candidates(shard)),
                            parts.block_working_set(shard))
    want = parts.rematted_working_set(shard, n_layer)
    for grad_bytes in (0, 123_456_789):
        head, scan = blocks.backward_phases(
            shard, {"block": kind._replace(grad_bytes=grad_bytes)},
            [(("block",), n_layer)])
        assert scan == (f"{n_layer} x scan(block)", want)
        assert head == ("head", want - kind.block_bytes - n_layer * grad_bytes)


def _two_runs():
    """Two kinds in two runs — a scan of four `a` whose stacked gradients are
    large, then one `b` whose block is — on a shard whose head is large."""
    C, K = blocks.RematCandidate, blocks.KindShard
    shard = parts.BlockShard(batch=1, seq=64, d_model=32, heads=1, head_dim=32,
                             d_ff=64, vocab=4096, dtype_bytes=2, flash=False,
                             dense_mlp=False)
    kinds = {"a": K(4, (C(("x",), 1_000, 4_000_000),), 50_000, 400_000),
             "b": K(1, (C(("y",), 1_000, 2_000_000),), 900_000, 10_000)}
    return shard, kinds, [("a", 4), ("b", 1)]


def test_phases_count_what_is_live_together_and_no_more():
    """Each run's phase from its parts: the block inputs that still wait, the
    run's block, the gathered embedding, the head's terms in the last run
    alone, less the gradients of the runs before it — which the resident
    bytes count from the step's start and which are not made yet."""
    shard, kinds, runs = _two_runs()
    x = 64 * 32 * 2
    model = blocks.model_working_set(shard, 5)
    head_terms = model - blocks.model_working_set(shard._replace(vocab=0), 5) \
        - 4096 * 32 * 6
    assert blocks.backward_phases(shard, kinds, runs) == [
        ("head", model - 4 * 400_000 - 10_000),
        ("b", model + 900_000 - 4 * 400_000),
        ("4 x scan(a)", model - x - head_terms + 50_000)]


def test_two_runs_whose_sum_does_not_fit_and_whose_largest_phase_does():
    """The chip holds the state, every gradient and the LARGER phase with
    room for both names; with the phases summed — every gradient beside the
    head's terms and the largest block, as the rule counted until PR 42 —
    it would hold neither."""
    shard, kinds, runs = _two_runs()
    phases = blocks.backward_phases(shard, kinds, runs)
    largest = max(p.nbytes for p in phases)
    summed = blocks.model_working_set(shard, 5) + 900_000
    resident = 3 * (4 * 400_000 + 10_000)
    limit = blocks.REMAT_RESERVE_BYTES + resident + largest + 5_000
    assert summed > largest + 5_000
    kept = blocks.choose_remat_policy_kinds(
        tuple(kinds.values()), largest, limit, resident)
    assert kept == blocks.RematPolicy(("x", "y"), 5_000, 5_000, limit)
    assert blocks.choose_remat_policy_kinds(
        tuple(kinds.values()), summed, limit, resident).saved == ()


@pytest.mark.parametrize("block, rows", [
    # batch, seq, d_model, d_ff, bytes an element
    ((8, 1024, 768, 3072, 2), 1024),         # gpt2-124m's sizes: 50 MB hidden
    ((2, 128, 64, 176, 2), 128),             # llama_tiny
    ((8, 2048, 2048, 5632, 2), 2048),        # llama_1b: 185 MB
    ((8, 4096, 4096, 11008, 2), 512),        # llama_7b: 4,096 tokens a chunk
    ((1, 32768, 4096, 11008, 2), 4096),      # the EvaByte cell: the same
], ids=["gpt2-124m", "llama_tiny", "llama_1b", "llama_7b", "evabyte"])
def test_mlp_rows_come_from_the_blocks_shapes(block, rows):
    """The whole sequence while one hidden tensor stays under 256 MiB; past
    that, chunks whose five hidden tensors take what two [B, S, D] do."""
    assert parts.mlp_rows(*block) == rows
    batch, seq, d_model, d_ff, a = block
    if rows < seq:
        assert 5 * batch * rows * d_ff * a <= 2 * batch * seq * d_model * a
        assert 5 * batch * 2 * rows * d_ff * a > 2 * batch * seq * d_model * a


def test_candidates_are_ordered_by_recompute_flops_per_byte():
    """From the shapes: the flash kernel's o leads at S = 1,024 (about 2·S
    FLOPs an element against d_model for a matmul's output) and trails once
    the sequence is short against the width; equal ones keep the block's
    order; lse rides with o."""
    for shard in (XL, SMALL):
        cands = parts.remat_candidates(shard)
        assert [c.names for c in cands] == [
            FLASH, (names.RES_Q,), (names.RES_K,), (names.RES_V,),
            (names.RES_MID,), (names.RES_MLP_HIDDEN,)]
        ratios = [c.flops / c.nbytes for c in cands]
        assert ratios == sorted(ratios, reverse=True)
    short = parts.remat_candidates(XL._replace(seq=256))
    assert short[-1].names == FLASH
    # bytes from the shapes: bf16 [8, 25, 1024, 64] and f32 [8, 25, 1024]
    by_name = {c.names: c.nbytes for c in parts.remat_candidates(XL)}
    assert by_name[(names.RES_Q,)] == 8 * 25 * 1024 * 64 * 2
    assert by_name[FLASH] == 8 * 25 * 1024 * (64 * 2 + 4)
    assert by_name[(names.RES_MLP_HIDDEN,)] == 8 * 1024 * 6400 * 2
    # a tensor that does not exist is no candidate
    no_flash = parts.remat_candidates(XL._replace(flash=False, dense_mlp=False))
    assert [c.names for c in no_flash] == [
        (names.RES_Q,), (names.RES_K,), (names.RES_V,), (names.RES_MID,)]


def test_block_shard_divides_by_the_mesh_axes_that_split(cpu_mesh8):
    cfg = gpt2.gpt2_tiny()
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(fsdp=2, tp=2, dp=2), cpu_mesh8)
    s = gpt2.block_shard(cfg, 8, cfg.seq_len, mesh, True)
    assert (s.batch, s.heads, s.d_ff, s.vocab) == (
        2, cfg.n_head // 2, cfg.d_ff // 2, cfg.padded_vocab // 2)
    assert (s.seq, s.d_model, s.head_dim, s.dtype_bytes) == (
        cfg.seq_len, cfg.d_model, cfg.head_dim, 2)
    whole = gpt2.block_shard(cfg, 8, cfg.seq_len, None, False)
    assert (whole.batch, whole.heads, whole.flash) == (8, cfg.n_head, False)


# ------------------------------------------------- the checkpoint, end to end
LAYER_LOOPS = {
    "scan": dict(),
    "pp2": dict(pipeline_microbatches=2),
}
BATCH = 4


def _kernel_calls(jaxpr, name, times=1):
    """Calls of the Pallas kernel `name` one evaluation of `jaxpr` makes: a
    scan's body counts once an iteration, a sub-jaxpr once a reference."""
    n = 0
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "pallas_call"
                and name in str(eqn.params.get("name", ""))):
            n += times
        inner = times * eqn.params.get("length", 1) \
            if eqn.primitive.name == "scan" else times
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _kernel_calls(sub, name, inner)
    return n


def _decision(n_layer, batch, bytes_limit):
    """The one recorded decision for these facts (a repeated decision is
    recorded once a process, so the newest need not be this trace's)."""
    (d,) = [d for d in blocks.remat_policy_decisions()
            if (d["n_layer"], d["batch"], d["bytes_limit"])
            == (n_layer, batch, bytes_limit)]
    return d


_runs = {}


def _run(loop, remat, admits, devices):
    """(loss, grads, forward kernel calls of loss-and-gradient, the decision)
    of gpt2_tiny with the interpreted flash kernels; `admits` is how many
    candidate groups the stated chip has room for (None: it states nothing)."""
    key = (loop, remat, admits)
    if key in _runs:
        return _runs[key]
    cfg = gpt2.gpt2_tiny(remat=remat, attention_impl="pallas",
                         **LAYER_LOOPS[loop])
    mesh = (mesh_lib.make_mesh(mesh_lib.MeshSpec(pp=2), devices[:2])
            if loop == "pp2" else None)
    n_layer = cfg.n_layer // 2 if loop == "pp2" else cfg.n_layer
    limit = None
    if admits is not None:
        shard = gpt2.block_shard(cfg, BATCH, cfg.seq_len, mesh, True)
        limit = _limit_admitting(shard, n_layer, 0, admits) + 8
    params = gpt2.init(cfg, jax.random.PRNGKey(0))
    batch = synthetic_batch(cfg, BATCH)

    def loss(p):
        with mesh_lib.use_mesh(mesh), mesh_lib.chip_memory(limit, 0):
            return gpt2.loss_fn(p, batch["tokens"], batch["targets"], cfg)

    fn = jax.value_and_grad(loss)
    calls = _kernel_calls(jax.make_jaxpr(fn)(params).jaxpr,
                          names.FLASH_FWD_KERNEL)
    value, grads = jax.jit(fn)(params)
    decision = _decision(n_layer, BATCH, limit or 0) if remat else None
    _runs[key] = (float(value), grads, calls, decision)
    return _runs[key]


@pytest.mark.parametrize("loop", list(LAYER_LOOPS))
def test_saved_o_and_lse_spare_the_second_forward_kernel_call(loop, cpu_mesh8):
    """With room for o + lse a block's backward has the named tensors and
    does not run the forward kernel again: one call a layer where whole-block
    remat makes two."""
    n_layer = gpt2.gpt2_tiny().n_layer
    _, _, calls_off, _ = _run(loop, False, None, cpu_mesh8)
    _, _, calls_whole, whole = _run(loop, True, None, cpu_mesh8)
    _, _, calls_fit, fit = _run(loop, True, 1, cpu_mesh8)
    # a chip runs every layer once; a pipeline stage runs its half of them
    # in each of the schedule's microbatches + stages - 1 ticks
    once = (2 + 2 - 1) * n_layer // 2 if loop == "pp2" else n_layer
    assert calls_off == once
    assert calls_whole == 2 * once
    assert calls_fit == once
    assert whole["saved"] == [] and whole["bytes_limit"] == 0
    assert tuple(fit["saved"]) == FLASH
    assert 0 < fit["saved_bytes"] <= fit["budget_bytes"]


@pytest.mark.parametrize("loop", list(LAYER_LOOPS))
def test_loss_and_every_gradient_equal_whatever_is_saved(loop, cpu_mesh8):
    """remat=False (every name kept, no rule asked), whole-block remat, o +
    lse saved, everything the rule can save: the same loss and gradients, bit
    for bit (bf16 activations, as the cells run)."""
    want_loss, want, _, _ = _run(loop, False, None, cpu_mesh8)
    for admits in (None, 1, 6):
        loss, grads, _, decision = _run(loop, True, admits, cpu_mesh8)
        if admits == 6:
            assert tuple(decision["saved"]) == EVERYTHING
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want)):
            np.testing.assert_array_equal(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                err_msg=jax.tree_util.keystr(path))
        assert loss == want_loss


def test_named_residuals_are_what_the_checkpoint_saves():
    """`saved_residuals` of one checkpointed block: with room for o + lse
    they are what it keeps beside its arguments, q, k and v are not; with no
    room it keeps its arguments alone."""
    from jax._src.ad_checkpoint import saved_residuals

    cfg = gpt2.gpt2_tiny(remat=True, attention_impl="pallas")
    params = gpt2.init(cfg, jax.random.PRNGKey(0))
    layer = jax.tree.map(lambda p: p[0], params["blocks"])
    x = jnp.zeros((BATCH, cfg.seq_len, cfg.d_model), cfg.dtype)
    shard = gpt2.block_shard(cfg, BATCH, cfg.seq_len, None, True)

    def kept(admits):
        limit = _limit_admitting(shard, cfg.n_layer, 0, admits) + 8
        with mesh_lib.chip_memory(limit, 0):
            block_fn = gpt2._make_block_fn(cfg, BATCH, cfg.seq_len, None,
                                           cfg.n_layer)
            saved = saved_residuals(block_fn, x, layer)
        return sorted((aval.shape, str(aval.dtype)) for aval, why in saved
                      if not why.startswith("from the argument"))

    # in the order the block projects its heads at this width (S-minor, heads
    # first, at gpt2_tiny's 32); lse has the rows' two dims and the sequence
    layout = parts.head_layout(cfg.head_dim)
    dims = dict(b=BATCH, h=cfg.n_head, s=cfg.seq_len, d=cfg.head_dim)
    o = (tuple(dims[c] for c in layout), "bfloat16")
    lse = (tuple(dims[c] for c in layout if c != "d"), "float32")
    assert layout == "hbds"
    assert kept(0) == []
    assert kept(1) == sorted([o, lse])
    assert kept(4) == sorted([o, o, o, o, lse])      # q, k and v beside them
    # remat=False asks no rule and states no chip: every name, whatever fits
    block_fn = gpt2._make_block_fn(gpt2.gpt2_tiny(attention_impl="pallas"),
                                   BATCH, cfg.seq_len, None, cfg.n_layer)
    everything = sorted(
        (aval.shape, str(aval.dtype))
        for aval, why in saved_residuals(block_fn, x, layer)
        if not why.startswith("from the argument"))
    assert everything == kept(6) and len(everything) == len(EVERYTHING)


# ----------------------------------------- remat=False: every name, no more
# sizes a cell's file would state: 4 layers, so that a pipeline stage's share
# (2) is no other loop's length (the schedule's 2 + 2 - 1 ticks), and a
# sequence length that is no other dimension's size
CELL_SIZES = dict(vocab_size=512, n_positions=32, n_layer=4, n_head=4, n_embd=64)


def _stacks(jaxpr, length, seq):
    """(last three dims, dtype) of every activation a `scan` of that length
    anywhere in `jaxpr` stacks for later: its per-iteration outputs with a
    sequence dim."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == length:
            out += [(v.aval.shape[-3:], str(v.aval.dtype))
                    for v in eqn.outvars[eqn.params["num_carry"]:]
                    if seq in v.aval.shape[1:]]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _stacks(sub, length, seq)
    return sorted(out)


@pytest.mark.parametrize("remat", [False, True], ids=["no_remat", "remat"])
@pytest.mark.parametrize("where", ["trunk", "pipeline_stage"])
def test_the_cell_step_stacks_the_named_residuals_and_nothing_else(
        where, remat, cpu_mesh8, monkeypatch):
    """The train step of a config built as the benchmark's cells build theirs
    (`benchmarks/families/gpt2.program_config`: published sizes, `remat`,
    nothing else), with attention resolved as on a TPU: the layer scan stacks
    each block's input and, without remat, its seven named residuals — one
    d_ff-wide tensor a layer, where AD left alone keeps six (the gelu's
    intermediates), which the scan then copies in and out of its stacks. With
    remat and no chip stated, the input alone."""
    from benchmarks.families import gpt2 as family

    # the CPU's choice would be the XLA einsum, whose S×S residuals carry no
    # name; the cells run the kernel (interpreted here)
    monkeypatch.setattr(attention, "resolve_attention",
                        lambda impl, mesh=None: ("pallas", True))
    cfg = family.program_config(CELL_SIZES, dict(remat=remat))
    assert cfg.attention_impl == "auto"
    mesh = (mesh_lib.make_mesh(mesh_lib.MeshSpec(pp=2), cpu_mesh8[:2])
            if where == "pipeline_stage" else None)
    n_layer = cfg.n_layer // 2 if mesh is not None else cfg.n_layer
    bundle = make_gpt2_train_step(cfg, mesh=mesh)
    jaxpr = jax.make_jaxpr(bundle.step_fn)(
        bundle.state, synthetic_batch(cfg, BATCH)).jaxpr
    # a pipeline stage sees one of the schedule's two microbatches at a time
    B = BATCH // 2 if mesh is not None else BATCH
    S, H, hd = cfg.seq_len, cfg.n_head, cfg.head_dim
    x = ((B, S, cfg.d_model), "bfloat16")
    # hd = 64: the heads are projected [H, B, hd, S] (parts.head_layout)
    named = ([((B, hd, S), "bfloat16")] * 4          # q, k, v, the kernel's o
             + [((H, B, S), "float32"),              # its lse
                x,                                   # after the attention add
                ((B, S, cfg.d_ff), "bfloat16")])     # the MLP's hidden
    assert _stacks(jaxpr, n_layer, S) == sorted([x] + ([] if remat else named))


def test_tags_cost_nothing_where_nothing_is_differentiated(monkeypatch):
    """`forward` (inference: no gradient, so no checkpoint and no scan
    residual) lowers to the same StableHLO with the tags as with
    `checkpoint_name` patched to the identity (locations are not printed;
    the counter behind private functions' `@name_<n>` symbols moves with
    every primitive traced, so the numbers are taken off)."""
    import re

    def lowered():
        cfg = gpt2.gpt2_tiny(attention_impl="pallas")
        params = gpt2.init(cfg, jax.random.PRNGKey(0))
        tokens = synthetic_batch(cfg, 2)["tokens"]
        text = jax.jit(lambda p, t: gpt2.forward(p, t, cfg)).lower(
            params, tokens).as_text()
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)

    tagged = lowered()
    for module in (gpt2, attention):
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
    assert lowered() == tagged


def test_step_factory_states_the_chip_and_cpu_states_no_limit(monkeypatch):
    """make_gpt2_train_step reports (bytes_limit, state + gradients) of a
    chip to the model; the CPU backend has no limit, so remat=True keeps
    block inputs only there. A device that states one gets the rule."""
    # decisions are recorded once a process, by their facts: whatever this
    # worker traced before (another file's tiny model, two rows, no limit)
    # is not this test's
    monkeypatch.setattr(blocks, "_decisions", {})
    cfg = gpt2.gpt2_tiny(remat=True, attention_impl="pallas")
    bundle = make_gpt2_train_step(cfg)
    limit, resident = train_step._chip_memory(bundle.mesh, bundle.state)
    nbytes = lambda tree: sum(x.nbytes for x in jax.tree.leaves(tree))
    assert limit is None
    assert resident == nbytes(bundle.state) + nbytes(bundle.state["params"])
    batch = synthetic_batch(cfg, 2)
    bundle.step_fn.lower(bundle.state, batch)
    assert _decision(cfg.n_layer, 2, 0)["saved"] == []

    monkeypatch.setattr(train_step, "_chip_memory",
                        lambda mesh, state: (64 * GIB, resident))
    bundle = make_gpt2_train_step(cfg)
    bundle.step_fn.lower(bundle.state, batch)
    assert tuple(_decision(cfg.n_layer, 2, 64 * GIB)["saved"]) == EVERYTHING


# ------------------------------------------------------------- the counter
@pytest.fixture
def buffer(monkeypatch):
    monkeypatch.setattr(_config, "task_events_enabled", True)
    monkeypatch.setattr(_config, "task_events_sample_rate", 1.0)
    buf = tracing.get_buffer()
    buf.drain(10 ** 6)
    yield buf
    buf.drain(10 ** 6)


def test_remat_policy_event_once_a_distinct_decision(buffer):
    cfg = gpt2.gpt2_tiny(remat=True, attention_impl="pallas")
    params = gpt2.init(cfg, jax.random.PRNGKey(0))
    batch = synthetic_batch(cfg, 2)

    def trace(limit):
        def loss(p):
            with mesh_lib.chip_memory(limit, 12345):
                return gpt2.loss_fn(p, batch["tokens"], batch["targets"], cfg)
        jax.make_jaxpr(jax.grad(loss))(params)

    # limits no other test states, so the decisions are new to this process
    first, second = 3 * GIB + 28, 3 * GIB + 29
    for limit in (first, first, second, first):
        trace(limit)
    component, name = names.REMAT_POLICY.split("/")
    events = [e for e in buffer.drain(10 ** 6)[0]
              if e["component"] == component and e["name"] == name]
    assert [e["args"]["bytes_limit"] for e in events] == [first, second]
    for e in events:
        assert tuple(e["args"]) == names.REMAT_POLICY_ARGS
        assert e["args"]["n_layer"] == cfg.n_layer
        assert (e["args"]["batch"], e["args"]["seq"]) == (2, cfg.seq_len)
        assert set(e["args"]["saved"]) <= set(names.RESIDUALS)
        # GPT-2's block takes the sequence whole
        assert (e["args"]["mlp_rows"], e["args"]["head_rows"]) == (
            cfg.seq_len, cfg.seq_len)
    mine = [d for d in blocks.remat_policy_decisions()
            if d["bytes_limit"] in (first, second)]
    assert [d["bytes_limit"] for d in mine] == [first, second]


def test_no_remat_asks_no_rule_and_records_nothing(buffer):
    cfg = gpt2.gpt2_tiny(attention_impl="pallas")
    params = gpt2.init(cfg, jax.random.PRNGKey(0))
    batch = synthetic_batch(cfg, 2)
    before = len(blocks.remat_policy_decisions())
    with mesh_lib.chip_memory(5 * GIB + 1, 0):
        jax.make_jaxpr(jax.grad(
            lambda p: gpt2.loss_fn(p, batch["tokens"], batch["targets"], cfg)
        ))(params)
    assert len(blocks.remat_policy_decisions()) == before


@pytest.mark.parametrize("value", ["dots", "full", 1, None])
def test_remat_is_a_plain_bool(value):
    with pytest.raises(ValueError, match="remat must be True or False"):
        gpt2.gpt2_tiny(remat=value)


# --------------------------------------------------------------------------- #
# What a pattern family is written from (PR 59): the pattern's bookkeeping
# --------------------------------------------------------------------------- #

HYBRID = "MEMEMEMEM*E"


def test_a_patterns_runs_and_how_many_layers_of_a_kind_each_holds():
    assert blocks.pattern_groups(HYBRID) == [
        ("ME", 4), ("M", 1), ("*", 1), ("E", 1)]
    assert blocks.group_counts(HYBRID) == [
        {"M": 4, "E": 4}, {"M": 1}, {"*": 1}, {"E": 1}]


def test_init_pattern_gives_a_run_a_key_and_in_it_a_kind_its_own():
    """One entry a run, a kind's layers stacked; the keys are split a run and,
    in a run's key, a kind of ``kinds`` in ITS order — whatever kinds the run
    holds: state from a seed is what it was when each family had the loop."""
    rng = jax.random.PRNGKey(3)
    drawn = []

    def layer_init(key, n, kind):
        drawn.append((kind, n, key))
        return {"w": jax.random.normal(key, (n, 2))}

    stacks = blocks.init_pattern(rng, HYBRID, "ME*", layer_init)
    assert [(kind, n) for kind, n, _ in drawn] == [
        ("M", 4), ("E", 4), ("M", 1), ("*", 1), ("E", 1)]
    assert [sorted(group) for group in stacks] == [
        ["E", "M"], ["M"], ["*"], ["E"]]
    assert stacks[0]["M"]["w"].shape == (4, 2)
    run_keys = jax.random.split(rng, 4)
    want = {(g, kind): jax.random.split(run_keys[g], 3)["ME*".index(kind)]
            for g, kind in ((0, "M"), (0, "E"), (1, "M"), (2, "*"), (3, "E"))}
    for (g, kind), (_, _, key) in zip(want, drawn):
        np.testing.assert_array_equal(key, want[g, kind])
    # two kinds of one run, and one kind in two runs, draw different numbers
    assert not np.array_equal(stacks[0]["M"]["w"], stacks[0]["E"]["w"])
    assert not np.array_equal(stacks[0]["M"]["w"][:1], stacks[1]["M"]["w"])


def test_a_name_two_kinds_share_is_one_candidate_of_the_kind_applied_most():
    C = blocks.RematCandidate
    kinds = {
        "A": blocks.KindShard(1, (C(("mid",), 100, 1000, 8),
                                  C(("q",), 50, 700)), 5000, 11),
        "C": blocks.KindShard(3, (C(("bcx",), 30, 300),
                                  C(("mid",), 100, 1000, 8)), 4000, 7),
    }
    got = blocks.one_candidate_a_name(kinds)
    # the carrier is C (3 applications): the 4 layers' bytes, operations and
    # freed bytes over its 3, rounded up; A keeps what is its own alone
    assert got["A"].candidates == (C(("q",), 50, 700),)
    assert got["C"].candidates == (C(("mid",), 134, 1334, 11),
                                   C(("bcx",), 30, 300))
    # nothing else of a kind is touched
    assert [(k.applications, k.block_bytes, k.grad_bytes)
            for k in got.values()] == [(1, 5000, 11), (3, 4000, 7)]
    # the rule then prices the shared name once: 3 x 134 >= 4 x 100 bytes
    assert 3 * 134 >= 4 * 100 > 3 * 133
    alone = {"A": kinds["A"]._replace(candidates=(C(("q",), 50, 700),)),
             "C": kinds["C"]._replace(candidates=(C(("bcx",), 30, 300),))}
    assert blocks.one_candidate_a_name(alone) == alone


def test_aux_by_layer_takes_a_scans_stacked_aux_apart_in_the_layers_order():
    runs = [("ME", 2), ("E", 1)]
    auxes = [[None, {"n": jnp.asarray([10, 11])}], [{"n": jnp.asarray(12)}]]
    assert [int(a["n"]) for a in blocks.aux_by_layer(runs, auxes)] == [
        10, 11, 12]


def test_with_grad_bytes_is_a_layers_parameters_over_the_chips(cpu_mesh8):
    def layer_init(key, n, kind):
        return {"w": jnp.zeros((n, 16, 4 if kind == "a" else 8), jnp.float32)}

    kinds = {"a": blocks.KindShard(2, (), 1), "b": blocks.KindShard(1, (), 1)}
    assert {k: v.grad_bytes for k, v in blocks.with_grad_bytes(
        kinds, layer_init, None).items()} == {"a": 256, "b": 512}
    mesh = mesh_lib.make_mesh(mesh_lib.MeshSpec(fsdp=8), cpu_mesh8)
    assert blocks.with_grad_bytes(kinds, layer_init, mesh)[
        "b"].grad_bytes == 64
