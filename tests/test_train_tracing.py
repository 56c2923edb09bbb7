"""The Train path names its own work (PR 24): scopes and kernel names on the
device, Data / Train / gc spans through ``profile_span``'s two sinks, and the
reader that turns a recorded trace into per-layer numbers.

Everything here runs in-process: no cluster, no chip, seconds a case.
"""

import gc
import glob
import os
import re
import sys
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import tracing
from ray_tpu.core.config import _config
from ray_tpu.tracing import events as tracing_events
from ray_tpu.tracing import names

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# what each lowering covers: the dense model with the Pallas kernels (remat
# on: each block keeps its input alone on the CPU; off: its named residuals),
# and the MoE branch
LOWERINGS = {
    "remat": dict(remat=True, attention_impl="pallas"),
    "no_remat": dict(attention_impl="pallas"),
    "moe": dict(moe_experts=2, attention_impl="xla"),
    # the llama-family block with the EVA mixer (EvaByte, PR 31)
    "eva": dict(remat=True, attention_impl="pallas"),
    # a pattern of layer kinds: Mamba-2, LatentMoE, attention, MTP (PR 33)
    "nemotron": dict(remat=True, attention_impl="pallas"),
    # a pattern of two attention kinds: lightning on the scan's kernels and
    # block-sparse attention past a tiny dense_len (MiniCPM-SALA, PR 47)
    "sala": dict(remat=True),
    # a pattern of PAIRS: short convolution or attention, then a dense MLP or
    # gated experts (LFM2-MoE, PR 50)
    "lfm2": dict(remat=True, attention_impl="pallas"),
}
# each model's own mixer: GPT-2 has the flash kernels, EvaByte the EVA ones
EVA_SCOPES = (names.EVA_ATTENTION, names.EVA_PREP_KV)
EVA_KERNELS = (names.EVA_AGG_FWD_KERNEL, names.EVA_AGG_BWD_KERNEL)
FLASH_KERNELS = (names.FLASH_FWD_KERNEL, names.FLASH_BWD_KERNEL)
SSD_KERNELS = (names.SSD_CHUNK_FWD_KERNEL, names.SSD_CHUNK_BWD_KERNEL)
NEMOTRON_SCOPES = (names.MAMBA, names.SSD_SCAN, names.MOE_ROUTED,
                   names.MOE_DISPATCH, names.MOE_LATENT, names.MOE_SHARED,
                   names.MTP, names.MOE_FURTHER_PASSES)
SPARSE_KERNELS = (names.SPARSE_ATTN_FWD_KERNEL, names.SPARSE_ATTN_BWD_DQ_KERNEL,
                  names.SPARSE_ATTN_BWD_DKV_KERNEL)
SALA_SCOPES = (names.LIGHTNING_ATTN, names.SPARSE_ATTENTION,
               names.SPARSE_SELECT)
LFM2_OWN_SCOPES = (names.SHORT_CONV, names.CONV_GATE)
DENSE_SCOPES = tuple(s for s in names.SCOPES if s != names.MOE
                     and s not in EVA_SCOPES + NEMOTRON_SCOPES + SALA_SCOPES
                     + LFM2_OWN_SCOPES)
EVABYTE_SCOPES = tuple(s for s in names.SCOPES if s not in (
    names.MOE, names.FLASH_ATTENTION) + NEMOTRON_SCOPES + SALA_SCOPES
    + LFM2_OWN_SCOPES)
# every layer of the hybrid is a mixer OR a feed-forward part: one norm a
# layer (ln1), the shared expert under `mlp` inside `moe`
HYBRID_SCOPES = tuple(s for s in names.SCOPES if s != names.LN2
                      and s not in EVA_SCOPES + SALA_SCOPES + LFM2_OWN_SCOPES)
# every layer of LFM2-MoE is an operator AND a feed-forward half: the block's
# six scopes (`mlp` in the dense layer, `moe` with the shared dispatch in the
# expert layers), the flash kernels' and the short convolution's
LFM2_SCOPES = DENSE_SCOPES + (names.MOE, names.MOE_ROUTED, names.MOE_DISPATCH,
                              names.MOE_FURTHER_PASSES) + LFM2_OWN_SCOPES
LFM2_RESIDUALS = (names.RES_CONV_BCX, names.RES_Q, names.RES_K, names.RES_V,
                  names.RES_FLASH_O, names.RES_FLASH_LSE, names.RES_MID,
                  names.RES_MLP_GATE, names.RES_MLP_UP, names.RES_MOE_SCORES,
                  names.RES_MOE_KTH, names.RES_MOE_LAST,
                  names.RES_MOE_PAIR_KEY, names.RES_MOE_PAIR_GATE)
# every layer of MiniCPM-SALA is a mixer AND a SwiGLU MLP: the block's six
# scopes, its two mixers' and the scan's; the sparse branch runs no flash
# kernel
MINICPM_SCOPES = tuple(
    s for s in DENSE_SCOPES[:DENSE_SCOPES.index(names.FLASH_ATTENTION)]
    if s != names.ATTN) + (
    names.SSD_SCAN,) + SALA_SCOPES
NEMOTRON_RESIDUALS = (
    names.RES_MAMBA_Z, names.RES_MAMBA_XBC, names.RES_MAMBA_DT,
    names.RES_SSD_STATES, names.RES_SSD_Y, names.RES_MOE_LATENT,
    names.RES_MOE_SHARED_HIDDEN, names.RES_Q, names.RES_K, names.RES_V,
    names.RES_FLASH_O, names.RES_FLASH_LSE)
_lowered = {}


def _step(key):
    """(bundle, batch of 2) of the tiny train step `key` names, built anew."""
    from ray_tpu.models import gpt2, lfm2_moe, llama, minicpm_sala, nemotron_h
    from ray_tpu.train.train_step import (
        make_gpt2_train_step, make_train_step, synthetic_batch)

    if key == "eva":
        cfg = llama.evabyte_tiny(**LOWERINGS[key])
        bundle = make_train_step(llama, cfg)
    elif key == "nemotron":
        cfg = nemotron_h.nemotron_h_tiny(**LOWERINGS[key])
        bundle = make_train_step(nemotron_h, cfg)
    elif key == "sala":
        cfg = minicpm_sala.minicpm_sala_tiny(**LOWERINGS[key])
        bundle = make_train_step(minicpm_sala, cfg)
    elif key == "lfm2":
        cfg = lfm2_moe.lfm2_moe_tiny(**LOWERINGS[key])
        bundle = make_train_step(lfm2_moe, cfg)
    else:
        cfg = gpt2.gpt2_tiny(**LOWERINGS[key])
        bundle = make_gpt2_train_step(cfg)
    return bundle, synthetic_batch(cfg, 2)


def _lowering(key):
    """(op_names of the lowered train step, its jaxpr as text), made once."""
    if key not in _lowered:
        import jax

        bundle, batch = _step(key)
        text = bundle.step_fn.lower(bundle.state, batch).as_text(debug_info=True)
        _lowered[key] = (
            set(re.findall(r'loc\("([^"]+)"', text)),
            str(jax.make_jaxpr(bundle.step_fn)(bundle.state, batch)))
    return _lowered[key]


def _has_scope(op_names, scope):
    pattern = re.compile(r"(^|[/(])" + re.escape(scope) + r"($|[/)])")
    return any(pattern.search(n) for n in op_names)


@pytest.mark.parametrize("scope", DENSE_SCOPES)
@pytest.mark.parametrize("blocks", ["remat", "no_remat"])
def test_scope_in_lowered_step(blocks, scope):
    op_names, _ = _lowering(blocks)
    assert _has_scope(op_names, scope), f"no op_name carries {scope!r}"


@pytest.mark.parametrize("scope", EVABYTE_SCOPES)
def test_scope_in_lowered_evabyte_step(scope):
    """The llama-family block carries GPT-2's scopes, and the EVA op its two."""
    op_names, _ = _lowering("eva")
    assert _has_scope(op_names, scope), f"no op_name carries {scope!r}"
    if scope == names.EVA_PREP_KV:       # the summary pass, inside the op
        assert _has_scope(op_names, f"{names.EVA_ATTENTION}/{scope}")


@pytest.mark.parametrize("scope", HYBRID_SCOPES)
def test_scope_in_lowered_nemotron_step(scope):
    """Every kind of layer carries the block's scopes and its own, nested as
    tracing/names.py says: the scan inside the mixer, the dispatch inside the
    routed experts, the MTP module's layers and loss inside `mtp`."""
    op_names, _ = _lowering("nemotron")
    assert _has_scope(op_names, scope), f"no op_name carries {scope!r}"
    inside = {names.SSD_SCAN: names.MAMBA, names.MOE_DISPATCH: names.MOE_ROUTED,
              names.MOE_FURTHER_PASSES: names.MOE_ROUTED,
              names.MOE_ROUTED: names.MOE, names.MOE_LATENT: names.MOE,
              names.MOE_SHARED: f"{names.MOE}/{names.MLP}",
              names.MAMBA: names.BLOCK, names.MOE: names.BLOCK}
    if scope in inside:
        assert _has_scope(op_names, f"{inside[scope]}/{scope}")
    if scope == names.MTP:
        for inner in (names.BLOCK, names.LM_HEAD_LOSS, names.LN_F):
            assert any(re.search(rf"{names.MTP}\)*/(.*/)?{inner}", n)
                       for n in op_names), inner


@pytest.mark.parametrize("scope", MINICPM_SCOPES)
def test_scope_in_lowered_minicpm_sala_step(scope):
    """Both kinds of layer carry the block's scopes; the scan stands inside
    the lightning mixer, the selection inside the sparse one, and both
    mixers inside the block."""
    op_names, _ = _lowering("sala")
    assert _has_scope(op_names, scope), f"no op_name carries {scope!r}"
    inside = {names.SSD_SCAN: names.LIGHTNING_ATTN,
              names.SPARSE_SELECT: names.SPARSE_ATTENTION,
              names.LIGHTNING_ATTN: names.BLOCK,
              names.SPARSE_ATTENTION: names.BLOCK}
    if scope in inside:
        assert _has_scope(op_names, f"{inside[scope]}/{scope}")


@pytest.mark.parametrize("scope", LFM2_SCOPES)
def test_scope_in_lowered_lfm2_step(scope):
    """All three kinds of layer carry the block's scopes; the gates and the
    conv stand inside the short-convolution operator, the dispatch inside
    the routed experts, both inside the block."""
    op_names, _ = _lowering("lfm2")
    assert _has_scope(op_names, scope), f"no op_name carries {scope!r}"
    inside = {names.CONV_GATE: names.SHORT_CONV,
              names.SHORT_CONV: names.BLOCK,
              names.MOE_DISPATCH: names.MOE_ROUTED,
              names.MOE_FURTHER_PASSES: names.MOE_ROUTED,
              names.MOE_ROUTED: names.MOE, names.MOE: names.BLOCK}
    if scope in inside:
        assert _has_scope(op_names, f"{inside[scope]}/{scope}")


@pytest.mark.parametrize("residual", LFM2_RESIDUALS)
def test_residual_name_in_lfm2_jaxpr(residual):
    assert residual in names.RESIDUALS
    _, jaxpr = _lowering("lfm2")
    assert f"name={residual}" in jaxpr


def test_lfm2_step_records_its_pattern_and_its_expert_load(buffer):
    """Tracing the step leaves the `model/layer_pattern` decision of its five
    layers of three kinds and a `model/remat_policy` one over them;
    `balance_router_bias` on a batch leaves one `model/expert_load` event an
    expert layer, `layer` its published index, no pair dropped; the grouped
    products are the primitive the compiler's kernel comes from."""
    import jax

    from ray_tpu.models import blocks, lfm2_moe
    from ray_tpu.train.train_step import synthetic_batch

    _, jaxpr = _lowering("lfm2")
    assert "ragged_dot" in jaxpr
    for kernel in FLASH_KERNELS:
        assert f"name={kernel}" in jaxpr
    cfg = lfm2_moe.lfm2_moe_tiny()
    by = {d["pattern"]: d for d in blocks.layer_pattern_decisions()}
    assert tuple(by[cfg.pattern]) == names.LAYER_PATTERN_ARGS
    assert by[cfg.pattern]["applications"] == {"D": 1, "A": 1, "C": 3}
    assert by[cfg.pattern]["groups"] == ["D", "A", "3 x scan(C)"]
    assert any((d["n_layer"], d["batch"], d["seq"]) == (5, 2, cfg.seq_len)
               for d in blocks.remat_policy_decisions())
    batch = synthetic_batch(cfg, 2)
    params = lfm2_moe.init(cfg, jax.random.PRNGKey(0))
    _, loads = lfm2_moe.balance_router_bias(params, batch["tokens"], cfg)
    events = [e for e in _drain(buffer, "model")
              if e["name"] == names.EXPERT_LOAD.split("/")[1]]
    assert [e["args"] for e in events] == loads
    assert [load["layer"] for load in loads] == [2, 3, 4, 5]
    for load in loads:
        assert tuple(load) == names.EXPERT_LOAD_ARGS
        assert load["pairs_dropped"] == 0 and load["tokens"] == 2 * cfg.seq_len


@pytest.mark.parametrize("residual", NEMOTRON_RESIDUALS)
def test_residual_name_in_nemotron_jaxpr(residual):
    assert residual in names.RESIDUALS
    _, jaxpr = _lowering("nemotron")
    assert f"name={residual}" in jaxpr


def test_nemotron_step_records_its_pattern_and_its_expert_load(buffer):
    """Tracing the step leaves a `model/layer_pattern` decision a pattern
    (trunk, MTP module) and a `model/remat_policy` one over all 8 layers;
    `balance_router_bias` on a batch leaves one `model/expert_load` event an
    expert layer, no pair dropped, and gives back the parameters with nothing
    but the selection biases changed."""
    import jax

    from ray_tpu.models import blocks, nemotron_h
    from ray_tpu.train.train_step import synthetic_batch

    _lowering("nemotron")
    cfg = nemotron_h.nemotron_h_tiny()
    by = {d["pattern"]: d for d in blocks.layer_pattern_decisions()}
    assert tuple(by[cfg.pattern]) == names.LAYER_PATTERN_ARGS
    assert by[cfg.pattern]["applications"] == {"M": 2, "E": 3, "*": 1}
    assert by[cfg.pattern]["groups"] == ["2 x scan(ME)", "*", "E"]
    assert by[cfg.mtp_pattern]["groups"] == ["*", "E"]
    assert any((d["n_layer"], d["batch"], d["seq"]) == (8, 2, cfg.seq_len)
               for d in blocks.remat_policy_decisions())
    batch = synthetic_batch(cfg, 2)
    params = nemotron_h.init(cfg, jax.random.PRNGKey(0))
    balanced, loads = nemotron_h.balance_router_bias(
        params, batch["tokens"], batch["targets"], cfg)
    changed = jax.tree_util.tree_map_with_path(
        lambda path, a, b: (path[-1].key, bool((a != b).any())),
        params, balanced)
    assert {name for name, moved in jax.tree.leaves(
        changed, is_leaf=lambda x: isinstance(x, tuple)) if moved} == {
            "router_bias"}
    events = [e for e in _drain(buffer, "model")
              if e["name"] == names.EXPERT_LOAD.split("/")[1]]
    assert [e["args"] for e in events] == loads and len(loads) == 4
    for load in loads:
        assert tuple(load) == names.EXPERT_LOAD_ARGS
        assert load["pairs_dropped"] == 0 and load["tokens"] == 2 * cfg.seq_len
        assert 0 < load["pairs"] <= load["buffer_rows"] * load["buffer_passes"]
        assert load["buffer_fill"] == pytest.approx(
            load["pairs"] / (load["buffer_rows"] * load["buffer_passes"]))


def test_moe_scope_stands_where_mlp_stands():
    op_names, _ = _lowering("moe")
    assert _has_scope(op_names, f"{names.BLOCK}/{names.MOE}")
    assert not _has_scope(op_names, names.MLP)


@pytest.mark.parametrize("blocks", ["remat", "no_remat"])
def test_remat_recompute_keeps_the_block_scopes(blocks):
    """What a block runs again in its backward carries the block's scopes:
    all of it with remat and nothing saved; without remat only the work
    between the named residuals (the layer norms, the gelu), never the
    flash kernel."""
    op_names, _ = _lowering(blocks)
    again = (names.BLOCK_SCOPES if blocks == "remat"
             else (names.LN1, names.LN2, names.MLP))
    for scope in again:
        want = f"rematted_computation/{names.BLOCK}/{scope}/"
        assert any(want in n for n in op_names), want
    recomputed_kernel = [n for n in op_names if "rematted_computation" in n
                         and names.FLASH_FWD_KERNEL in n]
    assert bool(recomputed_kernel) == (blocks == "remat")


def test_every_kernel_of_the_vocabulary_belongs_to_a_model():
    assert set(names.KERNELS) == set(FLASH_KERNELS + EVA_KERNELS + SSD_KERNELS
                                     + SPARSE_KERNELS
                                     + (names.RAGGED_DOT_KERNEL,))


@pytest.mark.parametrize("kernel", names.KERNELS)
def test_kernel_name_in_jaxpr(kernel):
    if kernel == names.RAGGED_DOT_KERNEL:
        # the compiler's kernel: the program writes the primitive it becomes
        _, jaxpr = _lowering("nemotron")
        assert "ragged_dot" in jaxpr
        return
    _, jaxpr = _lowering("eva" if kernel in EVA_KERNELS else
                         "nemotron" if kernel in SSD_KERNELS else
                         "sala" if kernel in SPARSE_KERNELS else "remat")
    assert f"name={kernel}" in jaxpr


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_eva_tiling_decision_of_the_lowered_step(kernel):
    """Tracing the EvaByte step leaves an `ops/eva_tiling` decision per
    kernel, with the vocabulary's args, and a `model/remat_policy` one."""
    from ray_tpu.models import blocks, llama
    from ray_tpu.ops import eva_attention

    _lowering("eva")
    cfg = llama.evabyte_tiny()
    mine = [d for d in eva_attention.eva_tiling_decisions()
            if (d["kernel"], d["rows"], d["Sq"], d["hd"])
            == (kernel, 2 * cfg.n_head, cfg.seq_len, cfg.head_dim)]
    # one a distinct decision: another test file's float32 model, traced in
    # this process, is another (its blocks take twice the VMEM)
    assert mine and len({d["vmem_estimate"] for d in mine}) == len(mine)
    assert all(tuple(d) == names.EVA_TILING_ARGS for d in mine)
    assert all((d["window"], d["chunk"]) == (cfg.window, cfg.chunk)
               for d in mine)
    assert any((d["n_layer"], d["batch"], d["seq"])
               == (cfg.n_layer, 2, cfg.seq_len)
               for d in blocks.remat_policy_decisions())


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_flash_tiling_decision_of_the_lowered_step(kernel, monkeypatch):
    """Tracing the step leaves one `ops/flash_tiling` decision per kernel,
    with the vocabulary's args, for the shard the kernel was given."""
    import jax

    from ray_tpu.models import gpt2
    from ray_tpu.ops import attention

    # the record is the process's: other files trace these tiny shapes with
    # blocks of their own, so this step is traced anew into a record of its own
    monkeypatch.setattr(attention, "_decisions", {})
    bundle, batch = _step("remat")
    jax.make_jaxpr(bundle.step_fn)(bundle.state, batch)
    cfg = gpt2.gpt2_tiny()
    mine = [d for d in attention.flash_tiling_decisions()
            if (d["kernel"], d["rows"], d["Sq"], d["hd"])
            == (kernel, 2 * cfg.n_head, cfg.seq_len, cfg.head_dim)]
    assert len(mine) == 1
    assert tuple(mine[0]) == names.FLASH_TILING_ARGS
    # ... the last of which says which kernel pair the step was traced with:
    # the S-minor one at gpt2_tiny's head width, as at GPT-2's 64
    assert names.FLASH_TILING_ARGS[-1] == "layout"
    assert mine[0]["layout"] == attention.kernel_layout(cfg.head_dim) \
        == attention.S_MINOR == attention.kernel_layout(64)
    assert attention.kernel_layout(128) == attention.HD_MINOR
    # the EVA event keeps the arguments it had (its kernels have one layout)
    assert names.EVA_TILING_ARGS == names.FLASH_TILING_ARGS[:-1] + (
        "window", "chunk")


# ------------------------------------------------------------- profile_span
@pytest.fixture
def buffer(monkeypatch):
    monkeypatch.setattr(_config, "task_events_enabled", True)
    monkeypatch.setattr(_config, "task_events_sample_rate", 1.0)
    buf = tracing.get_buffer()
    buf.drain(10 ** 6)
    yield buf
    buf.drain(10 ** 6)


def _drain(buf, component):
    return [e for e in buf.drain(10 ** 6)[0] if e["component"] == component]


@pytest.fixture
def own_ssd_record(monkeypatch):
    """The scan's record of tilings and `_chunks_call`'s trace cache are the
    process's, and this file's tests share a worker with others': a float32
    step of the tiny hybrid traced there first (tests/test_nemotron_h.py)
    leaves a second pair of decisions for this step's shape — the operands'
    width moves `vmem_estimate` and is not among the event's args — and a
    scan whose shape the cache holds is not traced again, so it records
    nothing. A test that reads the record gets an empty one and an empty
    cache, and hands the cache back empty: what it traced is recorded in a
    dict that goes with it."""
    from ray_tpu.ops import mamba2

    monkeypatch.setattr(mamba2, "_decisions", {})
    mamba2._chunks_call.clear_cache()
    yield
    mamba2._chunks_call.clear_cache()


def test_ssd_tiling_decisions_of_the_traced_step(buffer, own_ssd_record):
    """Tracing the hybrid's step leaves ONE `ops/ssd_tiling` decision a scan
    kernel for its Mamba layers' shape (two `M` layers; forward, recompute
    and backward trace the scan more than once), with the vocabulary's args.
    Each distinct decision is one instant event of component `ops` in the
    task-event buffer (-> `ray_tpu.timeline()`): a shape not traced before
    records its two, and tracing it again records nothing."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import nemotron_h
    from ray_tpu.ops import mamba2

    bundle, batch = _step("nemotron")       # anew: `_lowered` may hold it
    jax.make_jaxpr(bundle.step_fn)(bundle.state, batch)
    cfg = nemotron_h.nemotron_h_tiny()
    step = dict(rows=2, S=cfg.seq_len, Q=cfg.chunk, P=cfg.mamba_head_dim,
                N=cfg.ssm_state,
                group_heads=cfg.mamba_heads // cfg.mamba_groups)
    mine = [d for d in mamba2.ssd_tiling_decisions()
            if {k: d[k] for k in step} == step]
    assert sorted(d["kernel"] for d in mine) == ["bwd", "fwd"]
    assert all(tuple(d) == names.SSD_TILING_ARGS
               and d["group_heads"] % d["head_tile"] == 0 < d["vmem_estimate"]
               for d in mine)

    sd = jax.ShapeDtypeStruct
    odd = (sd((3, 40, 6, 8), jnp.float32), sd((3, 40, 6), jnp.float32),
           sd((6,), jnp.float32), sd((3, 40, 2, 8), jnp.float32),
           sd((3, 40, 2, 8), jnp.float32))
    grad = jax.grad(lambda *a: jnp.sum(mamba2.ssd_scan(*a, 20)),
                    argnums=(0, 1, 2, 3, 4))
    buffer.drain(10 ** 6)
    jax.make_jaxpr(grad)(*odd)
    events = [e for e in _drain(buffer, "ops") if e["name"] == "ssd_tiling"]
    assert [(e["args"]["kernel"], e["args"]["rows"], e["args"]["S"],
             e["args"]["Q"], e["args"]["group_heads"]) for e in events] == [
                 ("fwd", 3, 40, 20, 3), ("bwd", 3, 40, 20, 3)]
    assert all(e["args"] in mamba2.ssd_tiling_decisions() for e in events)
    jax.make_jaxpr(grad)(*odd)
    assert not [e for e in _drain(buffer, "ops") if e["name"] == "ssd_tiling"]


def test_span_without_jax_imports_nothing_and_records(buffer, monkeypatch):
    for mod in [m for m in sys.modules if m == "jax" or m.startswith("jax.")]:
        monkeypatch.delitem(sys.modules, mod)
    with tracing.profile_span("no-jax", {"k": 1}, component="t24"):
        pass
    assert "jax" not in sys.modules
    (event,) = _drain(buffer, "t24")
    assert event["name"] == "no-jax" and event["args"] == {"k": 1}
    assert event["dur"] >= 0.0


def test_hot_span_below_threshold_leaves_buffer_empty(buffer):
    with tracing.profile_span("quick", component="t24",
                              min_dur_s=tracing.PROFILE_MIN_DUR_S):
        pass
    assert _drain(buffer, "t24") == []


def test_hot_span_above_threshold_records_one_event_with_dur(buffer):
    with tracing.profile_span("slow", component="t24",
                              min_dur_s=tracing.PROFILE_MIN_DUR_S):
        time.sleep(0.003)
    (event,) = _drain(buffer, "t24")
    assert event["dur"] >= 0.003 and event["state"] == "PROFILE"


def test_disabled_and_no_profiler_session_records_nothing(buffer, monkeypatch):
    monkeypatch.setattr(_config, "task_events_enabled", False)
    with tracing.profile_span("off", component="t24"):
        pass
    assert len(buffer) == 0


def test_nested_span_carries_its_parent_and_task_ids(buffer):
    with tracing.task_context("task-24", "trace-24"):
        with tracing.profile_span("outer", component="t24"):
            with tracing.profile_span("inner", component="t24"):
                pass
    inner, outer = _drain(buffer, "t24")
    assert inner["args"]["parent"] == f"{names.SPAN_PREFIX}t24/outer"
    assert "parent" not in (outer.get("args") or {})
    assert inner["task_id"] == "task-24" and inner["trace_id"] == "trace-24"


def test_gc_hook_records_a_collection(buffer, monkeypatch):
    monkeypatch.setattr(tracing_events, "PROFILE_MIN_DUR_S", 0.0)
    tracing.install_gc_spans()
    tracing.install_gc_spans()                     # idempotent
    try:
        assert gc.callbacks.count(tracing_events._gc_spans) == 1
        gc.collect(2)
    finally:
        tracing.remove_gc_spans()
    full = [e for e in _drain(buffer, names.GC) if e["name"] == "gen2"]
    assert full and full[0]["args"]["generation"] == 2
    assert "collected" in full[0]["args"]
    assert tracing_events._gc_spans not in gc.callbacks


def test_session_report_records_a_span_and_worker_poll_none(buffer, monkeypatch):
    """`train/report` is read by a metric; `train/poll` had no reader and is
    gone (PR 35): a TrainWorker.poll is an actor task, and the timeline draws
    it as a slice from its lifecycle events, with the same edges."""
    from ray_tpu.train import session as session_mod
    from ray_tpu.train.worker_group import TrainWorker

    monkeypatch.setattr(session_mod, "PROFILE_MIN_DUR_S", 0.0)
    worker = TrainWorker(0, 1)
    worker.session = session_mod._Session(session_mod.TrainContext())
    worker.session.report({"loss": 1.0})
    worker.session.finish()
    items = worker.poll(timeout=0.5)
    assert [i[0] for i in items] == ["report", "done"]
    got = {e["name"]: e for e in _drain(buffer, "train")}
    assert set(got) == {"report"}
    assert not hasattr(names, "TRAIN_POLL") and "train/poll" not in names.SPANS
    poll = dict(task_id="t-poll", name="poll", actor_id="a", node_id="n",
                worker="w", attempt=0)
    trace = tracing.build_chrome_trace([
        dict(poll, state="SUBMITTED", ts=1.0),
        dict(poll, state="RUNNING", ts=1.25),
        dict(poll, state="EXECUTED", ts=2.0),
        dict(poll, state="FINISHED", ts=2.01)])
    (drawn,) = [e for e in trace if e["ph"] == "X"]
    assert (drawn["name"], drawn["cat"]) == ("poll", "actor_task")
    assert drawn["ts"] == 1.25e6 and drawn["dur"] == pytest.approx(0.75e6)


# ------------------------------------------------- the iterator, on a trace
def _trace_iterator(tmp_path, monkeypatch, pause_s):
    """Run iter_batches over in-memory blocks under a CPU profiler trace with
    a consumer that pauses; return the trace's host events."""
    import jax

    from benchmarks.harness import program_trace
    from ray_tpu.data import iterator

    monkeypatch.setattr(ray_tpu, "get", lambda ref: ref)   # blocks, not refs
    blocks = [{"tokens": np.full((24, 32), i, np.int32),
               "targets": np.full((24, 32), -i, np.int32)} for i in range(3)]
    seen = 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        for batch in iterator.iter_batches(
                iter(blocks), batch_size=8, drop_last=True,
                device=jax.devices()[0]):
            assert batch["tokens"].shape == (8, 32)
            seen += 1
            time.sleep(pause_s)
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
    return seen, program_trace.load_tables(xplane)["host"]


def test_iterator_spans_on_the_profiler_clock(tmp_path, monkeypatch, buffer):
    pause_s = 0.05
    seen, host = _trace_iterator(tmp_path, monkeypatch, pause_s)
    assert seen == 9
    by = {}
    for label, _, _, dur_ns, batch in host:
        by.setdefault(label, []).append((dur_ns, batch))
    prefix = names.SPAN_PREFIX
    assert len(by[prefix + names.DATA_GET_BLOCK]) == 3
    assert len(by[prefix + names.DATA_DEVICE_PUT]) == 9
    assert len(by[prefix + names.DATA_ASSEMBLE]) >= 9
    # the iterator's own count ties a batch's spans together
    assert sorted(b for _, b in by[prefix + names.DATA_DEVICE_PUT]) == list(range(9))
    assert {b for _, b in by[prefix + names.DATA_ASSEMBLE]} == set(range(9))
    # no span is held open across a yield: none holds a consumer's pause
    longest = max(d for spans in by.values() for d, _ in spans)
    assert longest < pause_s * 1e9 / 2, longest


# ------------------------------------------------------- the trace's reader
@pytest.mark.parametrize("tf_op, want", [
    ("jit(step)/jvp()/while/body/closed_call/block/mlp/tanh:",
     dict(direction="fwd", scopes=["block", "mlp"], remat=False, stack=False)),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/block/qkv/bsd,dhk->bhsk/dot_general:",
     dict(direction="bwd", scopes=["block", "qkv"], remat=True, stack=False)),
    ("jit(step)/transpose(jvp(lm_head_loss))/bsd,vd->bsv/dot_general:",
     dict(direction="bwd", scopes=["lm_head_loss"], remat=False, stack=False)),
    ("jit(step)/optimizer/mul:",
     dict(direction="optimizer", scopes=["optimizer"], remat=False, stack=False)),
    ("jit(step)/jvp()/while/body/dynamic_update_slice:",
     dict(direction="fwd", scopes=[], remat=False, stack=True)),
    ("jit(step)/jvp(block)/attn/flash_attention/flash_attention_fwd:",
     dict(direction="fwd", scopes=["block", "attn", "flash_attention"],
          kernel="flash_attention_fwd", stack=False)),
    ("", dict(direction="other", scopes=[], remat=False, stack=False)),
    # the compiler's grouped kernel: no op_name of the program's, its own name
    ("ragged-dot-none:", dict(direction="other", scopes=[],
                              kernel="ragged-dot", stack=False)),
])
def test_classify_by_the_programs_names(tf_op, want):
    from benchmarks.harness import program_trace

    got = program_trace.classify(
        tf_op, "ragged-dot-none.3" if "ragged" in tf_op else "fusion.1", "op")
    assert {k: got[k] for k in want} == want


def test_program_trace_check_passes_on_the_recorded_trace(capsys):
    from benchmarks.harness import program_trace

    assert program_trace.check() == 0, capsys.readouterr().out
