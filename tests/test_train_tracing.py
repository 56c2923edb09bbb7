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
    # the DeepSeek-V2 family's layer inside four hyper-connection streams a
    # lane tile wide: the kernel pairs of ops/hyper_connections.py (PR 58)
    "xing4": dict(remat=True, hc_sinkhorn_iters=3),
    # a pattern whose kinds differ in attention: a causal window under RoPE,
    # the whole triangle with no position (AFMoE / Trinity, PR 66)
    "afmoe": dict(remat=True, attention_impl="pallas"),
    # ... at a head of a whole lane tile: the operator's elementwise work is
    # the kernel pairs of ops/attention_pointwise.py (PR 67)
    "afmoe_wide_head": dict(remat=True, attention_impl="pallas", head_dim=128),
    # a pattern of Gated DeltaNet and gated attention layers over experts
    # beside a gated shared one: the delta rule's kernel pair (PR 61)
    "qwen3": dict(remat=True, attention_impl="pallas"),
    # the llama-family block run several times on one set of weights, with
    # sandwich norms and an exit gate after every pass (Ouro, PR 64)
    "ouro": dict(remat=True, attention_impl="pallas"),
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
# latent attention's projections and the balance loss (DeepSeek-V2, PR 55)
DSV2_OWN_SCOPES = (names.MLA_LATENT, names.MOE_AUX)
# the hyper-connected residual path (Xing4.0, PR 57: tests/test_xing4.py holds
# its lowered step)
DSV2_OWN_SCOPES += (names.MHC, names.MHC_MAPS)
# the Gated DeltaNet mixer, its scan and attention's output gate (Qwen3-Next,
# PR 61)
QWEN3_OWN_SCOPES = (names.DELTA_MIXER, names.GATED_DELTA,
                    names.GATED_ATTN_GATE)
DSV2_OWN_SCOPES += QWEN3_OWN_SCOPES     # (no other family's step has them)
DELTA_KERNELS = (names.GATED_DELTA_FWD_KERNEL, names.GATED_DELTA_SOLVE_KERNEL,
                 names.GATED_DELTA_BWD_KERNEL)
# ... and the mixer's elementwise work on either side of the scan (PR 63)
DELTA_KERNELS += (names.DELTA_CONV_NORM_FWD_KERNEL,
                  names.DELTA_CONV_NORM_BWD_KERNEL,
                  names.DELTA_GATE_NORM_FWD_KERNEL,
                  names.DELTA_GATE_NORM_BWD_KERNEL)
# the sandwich norms and the exit gate's objective (Ouro, PR 64)
OURO_OWN_SCOPES = (names.LN1_POST, names.LN2_POST, names.EXIT_GATE)
DSV2_OWN_SCOPES += OURO_OWN_SCOPES      # (no other family's step has them)
# attention by the layer's kind: a window, or every key (AFMoE, PR 66)
AFMOE_OWN_SCOPES = (names.ATTN_WINDOW, names.ATTN_FULL)
DSV2_OWN_SCOPES += AFMOE_OWN_SCOPES
# ... and QK-norm + RoPE and the output gate round its flash pair (PR 67)
AFMOE_KERNELS = (names.HEAD_NORM_ROPE_FWD_KERNEL,
                 names.HEAD_NORM_ROPE_BWD_KERNEL, names.ATTN_GATE_FWD_KERNEL,
                 names.ATTN_GATE_BWD_KERNEL)
CONV_KERNELS = (names.CONV_GATE_FWD_KERNEL, names.CONV_GATE_BWD_KERNEL)
MHC_KERNELS = (names.MHC_MIX_FWD_KERNEL, names.MHC_MIX_BWD_KERNEL,
               names.MHC_WRITE_FWD_KERNEL, names.MHC_WRITE_BWD_KERNEL)
DENSE_SCOPES = tuple(s for s in names.SCOPES if s != names.MOE
                     and s not in EVA_SCOPES + NEMOTRON_SCOPES + SALA_SCOPES
                     + LFM2_OWN_SCOPES + DSV2_OWN_SCOPES)
EVABYTE_SCOPES = tuple(s for s in names.SCOPES if s not in (
    names.MOE, names.FLASH_ATTENTION) + NEMOTRON_SCOPES + SALA_SCOPES
    + LFM2_OWN_SCOPES + DSV2_OWN_SCOPES)
# every layer of the hybrid is a mixer OR a feed-forward part: one norm a
# layer (ln1), the shared expert under `mlp` inside `moe`
HYBRID_SCOPES = tuple(s for s in names.SCOPES if s != names.LN2
                      and s not in EVA_SCOPES + SALA_SCOPES + LFM2_OWN_SCOPES
                      + DSV2_OWN_SCOPES)
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
    from ray_tpu.models import (
        afmoe, deepseek_v2, gpt2, lfm2_moe, llama, minicpm_sala, nemotron_h,
        qwen3_next)
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
    elif key == "xing4":
        cfg = deepseek_v2.xing4_tiny(**LOWERINGS[key])
        bundle = make_train_step(deepseek_v2, cfg)
    elif key == "qwen3":
        cfg = qwen3_next.qwen3_next_tiny(**LOWERINGS[key])
        bundle = make_train_step(qwen3_next, cfg)
    elif key == "ouro":
        cfg = llama.ouro_tiny(**LOWERINGS[key])
        bundle = make_train_step(llama, cfg)
    elif key in ("afmoe", "afmoe_wide_head"):
        cfg = afmoe.afmoe_tiny(**LOWERINGS[key])
        bundle = make_train_step(afmoe, cfg)
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


def _a_further_pass_enters_moe_routed_itself(op_names, scope):
    """Since PR 60 `routed_experts` is no one scope: each part of a pass
    enters `moe_routed` itself and the grouped products stand outside it (a
    trace's reader adds their kernel's time to the scope's, once) — so the
    further passes' loop is `moe_routed`'s neighbour, with the scope inside
    its body, and no grouped product under it."""
    if scope != names.MOE_FURTHER_PASSES:
        return
    assert _has_scope(op_names, f"{scope}/while/body/{names.MOE_ROUTED}")
    products = [n for n in op_names if "ragged_dot" in n]
    assert products and not [n for n in products if names.MOE_ROUTED in n]


@pytest.mark.parametrize("scope", HYBRID_SCOPES)
def test_scope_in_lowered_nemotron_step(scope):
    """Every kind of layer carries the block's scopes and its own, nested as
    tracing/names.py says: the scan inside the mixer, the dispatch inside the
    routed experts, the MTP module's layers and loss inside `mtp`."""
    op_names, _ = _lowering("nemotron")
    assert _has_scope(op_names, scope), f"no op_name carries {scope!r}"
    inside = {names.SSD_SCAN: names.MAMBA, names.MOE_DISPATCH: names.MOE_ROUTED,
              names.MOE_FURTHER_PASSES: names.MOE,
              names.MOE_ROUTED: names.MOE, names.MOE_LATENT: names.MOE,
              names.MOE_SHARED: f"{names.MOE}/{names.MLP}",
              names.MAMBA: names.BLOCK, names.MOE: names.BLOCK}
    if scope in inside:
        assert _has_scope(op_names, f"{inside[scope]}/{scope}")
    _a_further_pass_enters_moe_routed_itself(op_names, scope)
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
              names.MOE_FURTHER_PASSES: names.MOE,
              names.MOE_ROUTED: names.MOE, names.MOE: names.BLOCK}
    if scope in inside:
        assert _has_scope(op_names, f"{inside[scope]}/{scope}")
    _a_further_pass_enters_moe_routed_itself(op_names, scope)


@pytest.mark.parametrize("scope", QWEN3_OWN_SCOPES + (
    names.MOE, names.MOE_ROUTED, names.MOE_DISPATCH, names.MOE_SHARED,
    names.MOE_AUX, names.FLASH_ATTENTION, names.QKV, names.ATTN, names.PROJ))
def test_scope_in_lowered_qwen3_next_step(scope):
    """Both kinds of layer carry the block's scopes and their own: the scan
    inside the DeltaNet mixer, the output gate inside `attn`, the shared
    expert under `mlp` inside `moe`."""
    op_names, _ = _lowering("qwen3")
    assert _has_scope(op_names, scope), f"no op_name carries {scope!r}"
    inside = {names.GATED_DELTA: names.DELTA_MIXER,
              names.DELTA_MIXER: names.BLOCK,
              names.GATED_ATTN_GATE: names.ATTN,
              names.MOE_DISPATCH: names.MOE_ROUTED,
              names.MOE_SHARED: f"{names.MOE}/{names.MLP}",
              names.MOE: names.BLOCK}
    if scope in inside:
        assert _has_scope(op_names, f"{inside[scope]}/{scope}")


@pytest.mark.parametrize("scope", OURO_OWN_SCOPES + (
    names.EMBED, names.BLOCK, names.LN1, names.QKV, names.ATTN, names.PROJ,
    names.LN2, names.MLP, names.LN_F, names.LM_HEAD_LOSS, names.OPTIMIZER,
    names.FLASH_ATTENTION))
def test_scope_in_lowered_ouro_step(scope):
    """The looped step carries the llama block's scopes, each output norm
    under a scope of its own (the attention's inside `proj`, the MLP's beside
    `mlp` in the block), the loop-end norm as `ln_f` and the gate's objective
    under `exit_gate` (tests/test_ouro.py holds the two loops themselves)."""
    op_names, _ = _lowering("ouro")
    assert _has_scope(op_names, scope), f"no op_name carries {scope!r}"
    inside = {names.LN1_POST: f"{names.BLOCK}/{names.PROJ}",
              names.LN2_POST: names.BLOCK, names.LN1: names.BLOCK}
    if scope in inside:
        assert _has_scope(op_names, f"{inside[scope]}/{scope}")
    if scope == names.EXIT_GATE:     # the objective's, not a block's
        assert not any(names.BLOCK in n and scope in n for n in op_names)


@pytest.mark.parametrize("scope", AFMOE_OWN_SCOPES + (
    names.GATED_ATTN_GATE, names.LN1_POST, names.LN2_POST, names.EMBED,
    names.BLOCK, names.LN1, names.QKV, names.ATTN, names.PROJ, names.LN2,
    names.MLP, names.MOE, names.MOE_ROUTED, names.MOE_DISPATCH,
    names.MOE_SHARED, names.LN_F, names.LM_HEAD_LOSS, names.OPTIMIZER,
    names.FLASH_ATTENTION))
def test_scope_in_lowered_afmoe_step(scope):
    """All three kinds of layer carry the block's scopes; attention stands
    under its KIND's scope inside `attn` — the flash kernels and the output
    gate inside that —, each output norm under a scope of its own (the
    attention's inside `proj`), the shared expert under `mlp` inside `moe`."""
    op_names, _ = _lowering("afmoe")
    assert _has_scope(op_names, scope), f"no op_name carries {scope!r}"
    inside = {names.ATTN_WINDOW: f"{names.BLOCK}/{names.ATTN}",
              names.ATTN_FULL: f"{names.BLOCK}/{names.ATTN}",
              names.LN1_POST: f"{names.BLOCK}/{names.PROJ}",
              names.LN2_POST: names.BLOCK,
              names.MOE_SHARED: f"{names.MOE}/{names.MLP}",
              names.MOE: names.BLOCK}
    if scope in inside:
        assert _has_scope(op_names, f"{inside[scope]}/{scope}")
    if scope in AFMOE_OWN_SCOPES:       # the kernels and the gate, by kind
        for inner in (names.FLASH_ATTENTION, names.GATED_ATTN_GATE):
            assert _has_scope(op_names, f"{names.ATTN}/{scope}/{inner}")


def test_afmoe_step_records_its_pattern_and_its_windowed_calls():
    """Tracing the step leaves the `model/layer_pattern` decision of `DWFWW`
    (three kinds, the last two window layers one scan), a `model/remat_policy`
    one over them, and `ops/flash_tiling` decisions that tell the window
    layers' calls from the full layer's: `window` and how many tile pairs of
    the triangle the call visits."""
    from ray_tpu.models import afmoe, blocks
    from ray_tpu.ops import attention

    _lowering("afmoe")
    cfg = afmoe.afmoe_tiny()
    by = {d["pattern"]: d for d in blocks.layer_pattern_decisions()}
    assert tuple(by["DWFWW"]) == names.LAYER_PATTERN_ARGS
    assert by["DWFWW"]["applications"] == {"D": 1, "W": 3, "F": 1}
    assert by["DWFWW"]["groups"] == ["D", "W", "F", "2 x scan(W)"]
    assert any((d["n_layer"], d["batch"], d["seq"]) == (5, 2, cfg.seq_len)
               for d in blocks.remat_policy_decisions())
    mine = [d for d in attention.flash_tiling_decisions()
            if (d["rows"], d["Sq"], d["hd"])
            == (2 * cfg.n_head, cfg.seq_len, cfg.head_dim)]
    assert {(d["kernel"], d["window"]) for d in mine} == {
        (k, w) for k in ("fwd", "bwd") for w in (0, cfg.sliding_window)}
    for d in mine:
        assert tuple(d) == names.FLASH_TILING_ARGS
        # one tile a row of 64: the band IS the triangle's one pair
        assert d["tiles_visited"] == d["tiles_causal"] == 1


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
                                     + SPARSE_KERNELS + CONV_KERNELS
                                     + MHC_KERNELS + DELTA_KERNELS
                                     + AFMOE_KERNELS
                                     + (names.RAGGED_DOT_KERNEL,))


def _routed_experts_gradient_jaxpr():
    """`ops/moe.routed_experts` and its gradient at the smallest shapes the
    program's grouped kernels take (128-wide experts, a 512-row buffer)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    T, D, held = 256, 128, moe.Held(0, 4)
    p = {"router_w": jnp.zeros((D, 8)), "router_bias": jnp.zeros((8,)),
         "w1": jnp.zeros((held.count, D, D)), "w2": jnp.zeros((held.count, D, D))}
    assert moe.row_buffer(T, 8, 2, held.count) == 512

    def loss(u, p):
        return jnp.sum(moe.routed_experts(u, u, p, top_k=2, held=held,
                                          scaling=1.0)[0])

    return str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
        jnp.zeros((T, D)), p))


@pytest.mark.parametrize("kernel", names.KERNELS)
def test_kernel_name_in_jaxpr(kernel):
    if kernel == names.RAGGED_DOT_KERNEL:
        # the grouped products: at a toy's widths the compiler's kernel —
        # the program writes the primitive it becomes —, at whole lane tiles
        # the program's own three (ops/grouped_matmul.py, PR 60), which a
        # trace's reader finds under this name as a scope (`classify` below)
        _, jaxpr = _lowering("nemotron")
        assert "ragged_dot" in jaxpr
        jaxpr = _routed_experts_gradient_jaxpr()
        assert "ragged_dot" not in jaxpr
        for form in ("gmm", "gmm_t", "tgmm"):
            assert f"name=grouped_{form}" in jaxpr
        return
    _, jaxpr = _lowering("eva" if kernel in EVA_KERNELS else
                         "nemotron" if kernel in SSD_KERNELS else
                         "sala" if kernel in SPARSE_KERNELS else
                         "lfm2" if kernel in CONV_KERNELS else
                         "xing4" if kernel in MHC_KERNELS else
                         "qwen3" if kernel in DELTA_KERNELS else
                         "afmoe_wide_head" if kernel in AFMOE_KERNELS else
                         "remat")
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
    # ... and, last, v's and o's width beside q's and k's `hd` (PR 55: equal
    # anywhere but in latent attention)
    assert names.FLASH_TILING_ARGS[8:10] == ("layout", "hd_v")
    assert mine[0]["hd_v"] == mine[0]["hd"] == cfg.head_dim
    assert attention.kernel_layout(192, 128) == attention.S_MINOR
    assert attention.kernel_layout(256, 128) == attention.HD_MINOR
    assert mine[0]["layout"] == attention.kernel_layout(cfg.head_dim) \
        == attention.S_MINOR == attention.kernel_layout(64)
    assert attention.kernel_layout(128) == attention.HD_MINOR
    # the EVA event keeps the arguments it had (its kernels have one layout)
    assert names.EVA_TILING_ARGS == names.FLASH_TILING_ARGS[:8] + (
        "window", "chunk")
    # ... and, since PR 66, the causal window (0: none: GPT-2's) and how much
    # of the triangle's tile pairs the call visits (all of them without one)
    assert names.FLASH_TILING_ARGS[10:] == ("window", "tiles_visited",
                                            "tiles_causal")
    assert mine[0]["window"] == 0
    assert mine[0]["tiles_visited"] == mine[0]["tiles_causal"] > 0


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
@pytest.mark.parametrize("tf_op, hlo_name, want", [
    (case[0], (case + ("fusion.1",))[2], case[1]) for case in [
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
    # a looped stack (PR 64): the block inside the passes' loop AND the
    # layers', an output norm under its own scope inside `proj`; the loop-end
    # norm in the outer body alone; the gate's objective outside both; the
    # INNER loop's slices and stacks are layer-stack traffic as ever, and so
    # are the outer loop's own (its last three elements read the same)
    ("jit(step)/jvp()/while/body/while/body/closed_call/checkpoint/block/"
     "proj/ln1_post/mul:",
     dict(direction="fwd", scopes=["block", "proj", "ln1_post"], remat=False,
          stack=False)),
    ("jit(step)/transpose(jvp())/while/body/while/body/closed_call/"
     "checkpoint/rematted_computation/block/ln2_post/rsqrt:",
     dict(direction="bwd", scopes=["block", "ln2_post"], remat=True,
          stack=False)),
    ("jit(step)/jvp()/while/body/ln_f/mul:",
     dict(direction="fwd", scopes=["ln_f"], remat=False, stack=False)),
    ("jit(step)/transpose(jvp(exit_gate))/log_sigmoid/logistic:",
     dict(direction="bwd", scopes=["exit_gate"], remat=False, stack=False)),
    ("jit(step)/transpose(jvp())/while/body/while/body/"
     "dynamic_update_slice:",
     dict(direction="bwd", scopes=[], remat=False, stack=True)),
    ("jit(step)/transpose(jvp())/while/body/add_any:",
     dict(direction="bwd", scopes=[], remat=False, stack=False)),
    # the compiler's grouped kernel: no op_name of the program's, its own name
    ("ragged-dot-none:", dict(direction="other", scopes=[],
                              kernel="ragged-dot", stack=False),
     "ragged-dot-none.3"),
    # the program's grouped kernels (PR 60; op_names of the described-v5e
    # compiles): the instruction is named after the form, the reader's name
    # is a scope around the call, which AD wraps — found as the SAME kernel,
    # under `block` and `moe` but under no `moe_routed` (whose time the
    # reader adds this kernel's to), with a direction and the recompute's mark
    ("jit(step)/jvp(block)/moe/jvp(ragged-dot)/grouped_gmm/pallas_call:",
     dict(direction="fwd", scopes=["block", "moe"], kernel="ragged-dot",
          remat=False, stack=False), "grouped_gmm.4"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/block/moe/transpose(jvp(ragged-dot))/"
     "grouped_tgmm/pallas_call:",
     dict(direction="bwd", scopes=["block", "moe"], kernel="ragged-dot",
          remat=True, stack=False), "grouped_tgmm.7"),
    ("jit(loss)/transpose(jvp(moe_further_passes))/while/body/"
     "transpose(jvp(ragged-dot))/grouped_gmm_t/pallas_call:",
     dict(direction="bwd", scopes=["moe_further_passes"],
          kernel="ragged-dot", stack=False), "grouped_gmm_t.24"),
]])
def test_classify_by_the_programs_names(tf_op, hlo_name, want):
    from benchmarks.harness import program_trace

    got = program_trace.classify(tf_op, hlo_name, "op")
    assert {k: got[k] for k in want} == want


def test_program_trace_check_passes_on_the_recorded_trace(capsys):
    from benchmarks.harness import program_trace

    assert program_trace.check() == 0, capsys.readouterr().out


# ----------------------------------------- the step says what it did (PR 52)
STEP_COUNTERS = names.TRAIN_STEP_COUNTERS.split("/")[1]
EXPERT_FAMILIES = {"nemotron": "nemotron_h", "lfm2": "lfm2_moe"}


def _expert_step(key, **extra):
    """(model module, tiny config, bundle with a plain-SGD optimizer — the
    default schedule's first step has rate 0 and would hide the gradients —,
    batch of 2 on the device)."""
    import importlib

    import jax
    import optax

    from ray_tpu.train.train_step import make_train_step, synthetic_batch

    model = importlib.import_module(f"ray_tpu.models.{EXPERT_FAMILIES[key]}")
    cfg = getattr(model, EXPERT_FAMILIES[key] + "_tiny")(**extra)
    bundle = make_train_step(model, cfg, optimizer=optax.sgd(0.1))
    batch = jax.device_put(synthetic_batch(cfg, 2), bundle.data_sharding)
    return model, cfg, bundle, batch


def _expert_biases(model, cfg):
    """Where each expert layer's selection bias stands in the parameters, in
    the layers' published order: (path to the stack, row)."""
    from ray_tpu.models import blocks

    is_expert = (model.EXPERTS.__getitem__ if hasattr(model, "EXPERTS")
                 else "E".__eq__)
    out = []
    for top, pattern in ((("blocks",), cfg.pattern),
                         (("mtp", "blocks"), getattr(cfg, "mtp_pattern", ""))):
        for g, (sub, reps) in enumerate(blocks.pattern_groups(pattern)):
            seen = {}
            for kind in sub * reps:
                if is_expert(kind):
                    out.append((top + (g, kind, "router_bias"),
                                seen.get(kind, 0)))
                seen[kind] = seen.get(kind, 0) + 1
    return out


def _pushed(params, where, cfg):
    """``params`` with ONE expert layer's router sent onto the first
    ``top_k`` experts held here, every token's every choice."""
    import jax.numpy as jnp

    path, row = where
    ids = jnp.arange(cfg.n_experts)
    onto = (ids >= cfg.held.first) & (ids < cfg.held.first + cfg.top_k)

    def put(tree, path):
        if not path:
            return tree.at[row].set(jnp.where(onto, 1.0, 0.0))
        if isinstance(tree, dict):
            return {**tree, path[0]: put(tree[path[0]], path[1:])}
        return [put(t, path[1:]) if i == path[0] else t
                for i, t in enumerate(tree)]

    return put(params, path)


def _own(state):
    """A copy of ``state`` a step may take (every step donates its state)."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(jnp.copy, state)


@pytest.mark.parametrize("key", EXPERT_FAMILIES)
def test_step_counters_are_the_layers_held_load(key, monkeypatch):
    """`metrics["counters"]` of a toy step, layer by layer in published
    order: what `moe.held_load` says (`buffer_passes`, `pairs`,
    `max_per_expert`) of the SAME inputs — the same layer's normed stream
    inside the same step —, on the batch as the routers stand and with each
    expert layer's router in turn pushed onto held experts, so that this
    layer and no other runs three passes over a (shrunk) row buffer; loss, gradient norm and every
    stepped parameter bit-equal to the same step without the aux."""
    import types

    import jax
    import optax

    from ray_tpu.ops import moe
    from ray_tpu.train.train_step import make_train_step

    # a row buffer the pushed layer's pairs (tokens · top_k) fill three
    # times and a layer as initialised once or twice
    model, cfg, bundle, batch = _expert_step(key)
    tokens = batch["tokens"].size
    rows = -(-2 * tokens * cfg.top_k // 5)
    monkeypatch.setattr(moe, "row_buffer", lambda *shape: rows)
    model, cfg, bundle, batch = _expert_step(key)
    spec = model.step_counters(cfg)
    assert spec.fields == names.STEP_EXPERT_LOAD_ARGS
    assert spec.static(tokens) == {"buffer_rows": rows,
                                   "held": cfg.held_count}
    where = _expert_biases(model, cfg)
    assert len(where) == len(spec.layers)

    # the same step with no aux: a module that offers no counters
    plain = types.SimpleNamespace(
        init=model.init, logical_axes=model.logical_axes,
        loss_fn=model.loss_fn, mesh_rules=model.mesh_rules)
    bare = make_train_step(plain, cfg, optimizer=optax.sgd(0.1))

    # ... and the same step whose layers hand out held_load's own numbers
    real = moe.routed_experts

    def by_held_load(u, ell, p, *, form=moe.RELU2_EXPERT, **routing):
        load = moe.held_load(u, p, **routing)
        return real(u, ell, p, form=form, **routing)[0], dict(zip(
            names.STEP_EXPERT_LOAD_ARGS,
            (load["buffer_passes"], load["pairs"], load["max_per_expert"])))

    monkeypatch.setattr(moe, "routed_experts", by_held_load)
    witness = make_train_step(model, cfg, optimizer=optax.sgd(0.1))
    monkeypatch.setattr(moe, "routed_experts", real)

    for layer in [None] + list(range(len(where))):
        params = bundle.state["params"]
        if layer is not None:
            params = _pushed(params, where[layer], cfg)
        state = {**bundle.state, "params": params}
        new, metrics = bundle.step_fn(_own(state), batch)
        counters = np.asarray(metrics["counters"])
        assert counters.shape == (len(where), 3) and counters.dtype == np.int32
        _, said = witness.step_fn(_own(state), batch)
        np.testing.assert_array_equal(counters, np.asarray(said["counters"]))
        passes, pairs, fullest = counters.T
        if layer is None:       # (half the experts are held in the LFM2 toy)
            assert (passes <= 2).all() and (pairs < tokens * cfg.top_k).all()
        else:
            assert pairs[layer] == tokens * cfg.top_k and passes[layer] == 3
            assert fullest[layer] == tokens
            assert (np.delete(passes, layer) <= 2).all()
        assert (passes == -(-pairs // rows)).all() and (fullest <= pairs).all()
        new_bare, bare_metrics = bare.step_fn(_own(state), batch)
        assert set(bare_metrics) == {"loss", "grad_norm"}
        for name in bare_metrics:
            np.testing.assert_array_equal(np.asarray(metrics[name]),
                                          np.asarray(bare_metrics[name]))
        for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(new_bare),
                        strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(metrics["grad_norm"]) > 0


def test_model_without_step_counters_lowers_to_the_parents_step():
    """A model that offers no counters is asked what it was always asked:
    no `counters` among its metrics, and its lowered step is, to the letter,
    the one the step had before any model could offer them."""
    import jax

    from ray_tpu.models import gpt2
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.train import train_step as ts

    bundle, batch = _step("no_remat")
    assert not hasattr(gpt2, "step_counters")
    assert ts._offered_counters(gpt2, bundle.cfg) is None
    _, metrics = bundle.step_fn.eval_shape(bundle.state, batch)
    assert set(metrics) == {"loss", "grad_norm"}

    optimizer = ts.default_optimizer()
    _, state_sh, batch_sh = ts._compose_step(
        gpt2, bundle.cfg, bundle.mesh, optimizer, None)
    memory = ts._chip_memory(bundle.mesh, bundle.state)

    def step(state, batch):                 # the step as PR 51 composed it
        tokens, targets = batch["tokens"], batch["targets"]
        with mesh_lib.use_mesh(bundle.mesh), mesh_lib.chip_memory(*memory):
            loss, grads = jax.value_and_grad(gpt2.loss_fn)(
                state["params"], tokens, targets, bundle.cfg)
        new_params, new_opt, gnorm = ts._apply_optimizer(
            optimizer, grads, state)
        new_state = {"params": new_params, "opt_state": new_opt,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss, "grad_norm": gnorm}

    parents = jax.jit(step, in_shardings=(state_sh, batch_sh),
                      out_shardings=(state_sh, None), donate_argnums=(0,))
    assert (bundle.step_fn.lower(bundle.state, batch).as_text()
            == parents.lower(bundle.state, batch).as_text())


def test_steps_callable_is_the_jitted_step_to_its_callers(buffer, monkeypatch):
    """What `harness/loop.py` and the compile-count tests ask of the step
    object: `.lower(...).compile()` and `_cache_size()` as the bare jit gave
    them — one compile however many calls —, the call under a
    `ray_tpu:train/step` span numbered by the call."""
    import jax

    from ray_tpu.train import train_step as ts

    monkeypatch.setattr(ts, "PROFILE_MIN_DUR_S", 0.0)
    bundle, batch = _step("no_remat")
    batch = jax.device_put(batch, bundle.data_sharding)
    assert names.TRAIN_STEP in names.SPANS
    assert bundle.step_fn._cache_size() == 0
    state = bundle.state
    for _ in range(3):
        state, metrics = bundle.step_fn(state, batch)
    assert bundle.step_fn._cache_size() == 1
    mem = bundle.step_fn.lower(state, batch).compile().memory_analysis()
    assert mem.temp_size_in_bytes > 0
    assert bundle.step_fn._cache_size() == 1
    assert bundle.step_fn.eval_shape(state, batch)[1]["loss"].shape == ()
    spans = [e for e in _drain(buffer, "train") if e["name"] == "step"]
    assert [e["args"]["step"] for e in spans] == [1, 2, 3]
    assert all(e["dur"] > 0 for e in spans)
    assert not [e for e in spans if e["name"] == STEP_COUNTERS]


class _Made:
    """Stands where a step's counters array stands: ready when told."""

    def __init__(self, rows, ready):
        self.rows, self.ready = np.asarray(rows, np.int32), ready

    def is_ready(self):
        return self.ready

    def __array__(self, dtype=None, copy=None):
        return self.rows


def test_a_looped_steps_exit_distribution_is_the_event_of_its_own_kind(buffer):
    """The Ouro step's counters are not expert loads: ONE row of float32
    bits, decoded by the step's callable into a `train/step_counters` event
    of kind `exit_distribution` — a list a field, the passes beside them."""
    from ray_tpu.tracing import step_counters

    bundle, batch = _step("ouro")
    step_counters.drain(wait=True)
    buffer.drain(10 ** 6)
    _, metrics = bundle.step_fn(bundle.state, batch)
    assert step_counters.drain(wait=True) == 1
    (args,) = [e["args"] for e in _drain(buffer, "train")
               if e["name"] == STEP_COUNTERS]
    passes = bundle.cfg.ut_steps
    fields = [f"{names.STEP_EXIT_PASS}{t + 1}" for t in range(passes)]
    assert list(args) == list(names.TRAIN_STEP_COUNTERS_ARGS) + fields + [
        names.STEP_EXIT_ENTROPY, *names.EXIT_DISTRIBUTION_STATIC_ARGS]
    assert args["kind"] == names.EXIT_DISTRIBUTION_KIND
    assert (args["layers"], args["passes"]) == ([bundle.cfg.n_layer - 1],
                                                passes)
    said = np.asarray(metrics["counters"]).view(np.float32)[0]
    assert [args[f][0] for f in fields + [names.STEP_EXIT_ENTROPY]] == (
        said.tolist())
    assert sum(args[f][0] for f in fields) == pytest.approx(1.0, abs=1e-5)
    # and the reader of the expert cells' loads takes it for none of its own
    from benchmarks.harness import step_counters as reader

    assert reader.KIND != args["kind"]


def test_drain_records_what_is_ready_in_order_and_waits_for_nothing(buffer):
    """An entry whose array the device has not made stays pending — and so
    does every later one, ready or not: the events go in the steps' order —
    and is recorded by a later call; `wait=True` takes them all."""
    from ray_tpu.tracing import step_counters

    def decode(rows):
        return {"kind": "expert_load", "layers": [0],
                "passes": rows[:, 0].tolist()}

    step_counters.drain(wait=True)
    buffer.drain(10 ** 6)
    made = [_Made([[n, 0, 0]], ready=n != 2) for n in (1, 2, 3)]
    for n, array in enumerate(made, start=1):
        step_counters.watch(n, 100.0 + n, array, decode)

    def recorded():
        return [e["args"] for e in _drain(buffer, "train")
                if e["name"] == STEP_COUNTERS]

    assert step_counters.drain() == 1
    (first,) = recorded()
    assert first == {"step": 1, "kind": "expert_load", "t_dispatch": 101.0,
                     "layers": [0], "passes": [1]}
    assert tuple(first)[:4] == names.TRAIN_STEP_COUNTERS_ARGS
    assert step_counters.drain() == 0 and not recorded()   # 2 is not made
    made[1].ready = True
    assert step_counters.drain() == 2
    assert [a["step"] for a in recorded()] == [2, 3]
    # with `wait` nothing is asked whether it is ready
    step_counters.watch(4, 104.0, _Made([[4, 0, 0]], ready=False), decode)
    assert step_counters.drain() == 0
    assert step_counters.drain(wait=True) == 1
    assert [a["passes"] for a in recorded()] == [[4]]
    assert step_counters.drain(wait=True) == 0
    # `train.report` is not a drain: a loop fetches its loss and reports with
    # an idle device behind it, and a fetch there is on the step's path
    from ray_tpu.train import session as session_mod

    step_counters.watch(5, 105.0, _Made([[5, 0, 0]], ready=True), decode)
    session = session_mod._Session(session_mod.TrainContext())
    session.report({"loss": 1.0})
    session.finish()
    assert not recorded() and len(step_counters._pending) == 1
    assert step_counters.drain() == 1


def _fit_hybrid(steps):
    """A local `fit()` of the toy hybrid for ``steps`` steps with the loop a
    user writes (the loss fetched a step, reported every other step); the
    session's record, and how often the recorder fetched."""
    import jax

    from ray_tpu import train
    from ray_tpu.tracing import step_counters

    fetched = []
    real = jax.device_get

    def loop(config):
        model, cfg, bundle, batch = _expert_step("nemotron")
        state = bundle.state
        for n in range(config["steps"]):
            state, metrics = bundle.step_fn(state, batch)
            assert metrics["counters"].shape == (4, 3)
            if n % 2:
                train.report({"loss": float(metrics["loss"])})
        train.report({"steps": config["steps"]})

    def counting(x):
        fetched.append(x)
        return real(x)

    ray_tpu.shutdown()
    ray_tpu.init(local_mode=True)
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "device_get", counting)
            result = train.JaxTrainer(
                loop, train_loop_config={"steps": steps},
                scaling_config=train.ScalingConfig(num_workers=1)).fit()
    finally:
        ray_tpu.shutdown()
    assert result.error is None and result.metrics["steps"] == steps
    return ray_tpu.timeline(), fetched


def _named(trace, full_name):
    cat, name = full_name.split("/")
    return [e for e in trace if e.get("cat") == cat and e["name"] == name]


def test_fit_leaves_one_counters_event_a_step_the_last_included(monkeypatch):
    """N steps of a local `fit()` leave exactly N `train/step_counters`
    events in the session's record, steps 1 … N in order — the last one's,
    which no later step drained, by the loop thread's wait before
    `train/loop_done` —, each one small fetch; all before `loop_done`."""
    monkeypatch.setattr(_config, "task_events_enabled", True)
    monkeypatch.setattr(_config, "task_events_sample_rate", 1.0)
    steps = 5
    trace, fetched = _fit_hybrid(steps)
    events = _named(trace, names.TRAIN_STEP_COUNTERS)
    assert [e["args"]["step"] for e in events] == list(range(1, steps + 1))
    assert len(fetched) == steps
    (done,) = _named(trace, names.TRAIN_LOOP_DONE)
    assert all(e["ts"] <= done["ts"] for e in events)
    for e in events:
        args = e["args"]
        assert tuple(k for k in args if k in names.TRAIN_STEP_COUNTERS_ARGS) \
            == names.TRAIN_STEP_COUNTERS_ARGS
        assert args["kind"] == names.EXPERT_LOAD_KIND
        assert args["layers"] == [0, 1, 2, 3] and args["held"] == 8
        for field in names.STEP_EXPERT_LOAD_ARGS:
            assert len(args[field]) == 4
        assert args["passes"] == [1, 1, 1, 1]
        assert args["t_dispatch"] <= e["ts"] / 1e6
        assert args["trace_id"] == done["args"]["trace_id"]
    stamps = [e["args"]["t_dispatch"] for e in events]
    assert stamps == sorted(stamps)


def test_fit_with_the_event_plane_off_watches_and_fetches_nothing(monkeypatch):
    monkeypatch.setattr(_config, "task_events_enabled", False)
    from ray_tpu.tracing import step_counters

    trace, fetched = _fit_hybrid(3)
    assert not fetched and not step_counters._pending
    assert not _named(trace or [], names.TRAIN_STEP_COUNTERS)


# --------------------------------------- the benchmark's readers of both
COUNTER_READERS = ("moe_passes_per_step", "moe_multi_pass_steps",
                   "moe_load_imbalance")
STEP_RECORD = os.path.join(ROOT, "benchmarks", "testdata",
                           "step-counters.rehearsal")


def _reader(metric):
    """(the reader's module, its BENCHMARK.json entry)."""
    import importlib
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == metric)
    return importlib.import_module(f"benchmarks.layer_metrics.{metric}"), entry


def _record_facts(events, summary):
    from benchmarks.harness import session_timeline

    return {"summary": summary, "notes": [],
            "session_timeline": session_timeline.parse(events)}


def _counters_event(step, t_dispatch, passes, pairs, fullest, held=8):
    return {"name": STEP_COUNTERS, "cat": "train", "ph": "i", "s": "t",
            "ts": (t_dispatch + 0.5) * 1e6, "pid": 1, "tid": 1,
            "args": {"step": step, "kind": names.EXPERT_LOAD_KIND,
                     "t_dispatch": t_dispatch, "layers": [0, 1],
                     "passes": passes, "pairs": pairs,
                     "max_per_expert": fullest, "buffer_rows": 100,
                     "held": held}}


@pytest.mark.parametrize("metric", COUNTER_READERS)
def test_counter_reader_against_the_recorded_session(metric):
    """Each reader on the `train/*` events of a recorded rehearsal of the
    Nemotron cell: the number `benchmarks/testdata/` holds for the window
    its summary's wall clocks cut; the entry names the expert cells."""
    import gzip
    import json

    with gzip.open(STEP_RECORD + ".json.gz", "rt") as f:
        events = json.load(f)
    with open(STEP_RECORD + ".expected.json") as f:
        expected = json.load(f)
    reader, entry = _reader(metric)
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        entry["unit"], entry["layer"], entry["moves"], entry["source"])
    assert entry["workloads"] == ["nemotron-3-super-120b-l11.dataset",
                                  "lfm2-24b-a2b-l5.dataset",
                                  "deepseek-v2-lite-l5.dataset",
                                  "xing4.0-29b-a4b-l5.dataset",
                                  "qwen3-next-80b-a3b-l4.dataset",
                                  "trinity-mini-l5.dataset"]
    facts = _record_facts(events, expected["summary"])
    assert reader.read(facts) == pytest.approx(expected["metrics"][metric],
                                               rel=1e-12)
    first, last = expected["window_steps"]
    assert [s["step"] for s in facts["step_counters_window"]] == list(
        range(first, last + 1))
    assert expected["steps_recorded"] > last


def test_counter_readers_on_steps_that_ran_further_passes():
    """The readers' arithmetic where it matters: of the window's three steps
    (a fourth was dispatched before it, a fifth after) one ran a second pass
    in one layer."""
    events = [
        _counters_event(1, 9.0, [3, 3], [250, 250], [250, 250]),
        _counters_event(2, 10.5, [1, 1], [80, 64], [10, 16]),
        _counters_event(3, 11.5, [1, 2], [96, 160], [12, 60]),
        _counters_event(4, 12.5, [1, 1], [80, 80], [10, 10]),
        _counters_event(5, 14.0, [3, 3], [250, 250], [250, 250]),
    ]
    summary = {"t_window_wall": 10.0, "t_end_wall": 13.0}
    want = {"moe_passes_per_step": (2 + 3 + 2) / 3,
            "moe_multi_pass_steps": 100 / 3,
            # the worst layer a step: 16·8/64 − 1, 60·8/160 − 1, 10·8/80 − 1
            "moe_load_imbalance": 100 * (1.0 + 2.0 + 0.0) / 3}
    for metric in COUNTER_READERS:
        reader, _ = _reader(metric)
        assert reader.read(_record_facts(events, summary)) == pytest.approx(
            want[metric], rel=1e-12), metric


@pytest.mark.parametrize("metric", COUNTER_READERS
                         + ("step_dispatch_ms_per_step",))
def test_new_reader_finds_nothing_where_the_program_says_nothing(metric):
    """No record, a record without the event (the parent's program under this
    PR's `benchmarks/`), events outside the window, no trace: `None`, so the
    line leaves the metric out."""
    reader, _ = _reader(metric)
    summary = {"t_window_wall": 10.0, "t_end_wall": 13.0}
    other = {"name": "report", "cat": "train", "ph": "X", "ts": 11e6,
             "dur": 5.0, "pid": 1, "tid": 1, "args": {}}
    outside = _counters_event(1, 9.0, [1, 1], [80, 80], [10, 10])
    for facts in ({"summary": summary, "notes": [], "session_timeline": None},
                  _record_facts([other], summary),
                  _record_facts([other, outside], summary)):
        facts.update(trace=None, program_trace=None)
        assert reader.read(facts) is None


def test_step_dispatch_reader_reads_the_programs_span(monkeypatch):
    """`step_dispatch_ms_per_step` is the traced window's time under
    `ray_tpu:train/step` a step, as `report_enqueue_ms_per_step` is under
    `train/report`; from a program whose vocabulary lacks the span, nothing
    — not the 0 a layer with other spans would read."""
    from benchmarks.harness import program_trace

    reader, entry = _reader("step_dispatch_ms_per_step")
    assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
        entry["unit"], entry["layer"], entry["moves"], entry["source"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        import json
        assert entry["workloads"] == [
            w["name"] for w in json.load(f)["workloads"]]
    facts = {"program_trace": {
        "host_span_ms": {"train/step": 3.0, "train/report": 1.0},
        "host_steps": 2}}
    assert reader.read(facts) == 1.5
    enqueue, _ = _reader("report_enqueue_ms_per_step")
    assert enqueue.read(facts) == 0.5
    monkeypatch.setattr(program_trace.names, "SPANS", tuple(
        s for s in names.SPANS if s != names.TRAIN_STEP))
    assert reader.read(facts) is None and enqueue.read(facts) == 0.5
