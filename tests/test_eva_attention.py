"""EVA attention (ops/eva_attention.py) and the EvaByte model built on it
(models/llama.py with the `eva` mixer, PR 31), against the benchmark's plain
float32 reference (benchmarks/families/evabyte_reference.py, which imports
nothing from ray_tpu): the op alone forward and backward, the visibility rule
itself, the model through `make_train_step`, the eight-head targets and the
remat rule on this block's shapes.

All on the CPU: the Pallas kernels interpret.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import blocks, llama, parts
from ray_tpu.ops import eva_attention as eva
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.tracing import names
from ray_tpu.train.train_step import make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)               # the benchmark's families

from benchmarks.families import evabyte_reference as reference  # noqa: E402

WINDOW, CHUNK = 64, 8
SEQ = 4 * WINDOW
V5E_BYTES_LIMIT = 16_909_334_528


def _inputs(hd, heads=2, seq=SEQ, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v, w = (jax.random.normal(key, (1, heads, seq, hd), jnp.float32)
                  for key in ks[:4])
    phi = 0.3 * jax.random.normal(ks[4], (heads, hd))
    mu = 0.3 * jax.random.normal(ks[5], (heads, hd))
    return (q, k, v, phi, mu), w


def _reference_attention(*args):
    with jax.default_matmul_precision("highest"):
        return reference.eva_attention(*args, WINDOW, CHUNK)


IMPLS = {
    "pallas_interpret": lambda *a: eva.eva_attention(
        *a, window=WINDOW, chunk=CHUNK),
    "xla": lambda *a: eva.eva_attention_xla(*a, window=WINDOW, chunk=CHUNK),
}


@pytest.mark.parametrize("hd", [32, 128])
@pytest.mark.parametrize("impl", list(IMPLS))
def test_op_matches_the_reference_forward_and_backward(impl, hd):
    """Output and the gradient with respect to q, k, v, phi and mu, at four
    windows of 64 with chunks of 8."""
    args, w = _inputs(hd)
    want = _reference_attention(*args)
    got = IMPLS[impl](*args)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * w),
                               argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, r in zip("q k v phi mu".split(), grads(IMPLS[impl]),
                          grads(_reference_attention)):
        np.testing.assert_allclose(
            g, r, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(r))),
            err_msg=name)


@pytest.mark.parametrize("chunk", [4, 16], ids=["whole_blocks", "partial_block"])
def test_kernels_walk_several_tiles_and_summary_blocks(chunk):
    """A window of two q tiles (1,024 over tiles of 512), three windows. With
    chunks of 4 a window has 256 summaries and a summary block is 256: whole
    blocks, unmasked; with chunks of 16 it has 64 against a block of 192: the
    masked last block. Kernel against the XLA formulation, both directions."""
    window = 1024
    args, w = _inputs(32, heads=1, seq=3 * window, seed=1)
    run = lambda f: jax.value_and_grad(
        lambda *a: jnp.sum(f(*a, window=window, chunk=chunk) * w),
        argnums=(0, 1, 2, 3, 4))(*args)
    (want, want_g), (got, got_g) = run(eva.eva_attention_xla), run(eva.eva_attention)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, r in zip(got_g, want_g):
        np.testing.assert_allclose(g, r, rtol=1e-4,
                                   atol=2e-6 * float(jnp.max(jnp.abs(r))))
    mine = [d for d in eva.eva_tiling_decisions()
            if (d["Sq"], d["window"], d["chunk"]) == (3 * window, window, chunk)]
    assert {d["kernel"] for d in mine} == {"fwd", "bwd"}
    assert all(tuple(d) == names.EVA_TILING_ARGS and d["block_q"] == 512
               for d in mine)


# ------------------------------------------------------ the visibility rule
T = 2 * WINDOW + 20           # a query in the third window, mid-chunk


def _out(q, k, v, kt, vt, impl):
    if impl == "xla":
        return eva.eva_agg_xla(q, k, v, kt, vt, window=WINDOW, chunk=CHUNK)
    return eva._eva_agg(q, k, v, kt, vt, WINDOW, CHUNK, True)


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
def test_a_query_sees_its_window_so_far_and_earlier_windows_summaries(impl):
    (q, k, v, phi, mu), _ = _inputs(32)
    kt, vt = eva.eva_prep_kv(k, v, phi, mu, chunk=CHUNK)
    base = _out(q, k, v, kt, vt, impl)[0, :, T]
    per_win = WINDOW // CHUNK

    def moved(**changed):
        a = dict(k=k, v=v, kt=kt, vt=vt)
        for name, index in changed.items():
            a[name] = a[name].at[:, :, index].add(1.0)
        return not np.allclose(_out(q, a["k"], a["v"], a["kt"], a["vt"],
                                    impl)[0, :, T], base, atol=1e-6)

    # a later position, in its window and past it: unseen
    assert not moved(k=T + 1) and not moved(v=T + 1) and not moved(k=3 * WINDOW)
    # its own position and an earlier one of its window: seen, exactly
    assert moved(k=T) and moved(v=2 * WINDOW)
    # any chunk of its OWN window's summaries, even those wholly before it
    assert not moved(kt=2 * per_win) and not moved(vt=2 * per_win + 1)
    # a later window's summary: unseen; an earlier window's: seen
    assert not moved(kt=3 * per_win)
    assert moved(kt=0) and moved(vt=2 * per_win - 1)
    # a raw key of an earlier window is unseen by the aggregation (it reaches
    # the query only through its chunk's summary) ...
    assert not moved(k=5)
    # ... and seen through the whole op
    whole = lambda k_: eva.eva_attention_xla(
        q, k_, v, phi, mu, window=WINDOW, chunk=CHUNK)[0, :, T]
    assert not np.allclose(whole(k.at[:, :, 5].add(1.0)), whole(k), atol=1e-6)


def test_with_phi_and_mu_zero_the_remote_term_is_the_chunk_means():
    """phi = 0 weighs a chunk's keys equally, mu = 0 adds nothing: the
    summaries are the chunk means, and the output is the softmax, computed
    here by hand, over [means of earlier windows' chunks ; own window so
    far]."""
    (q, k, v, _, _), _ = _inputs(32, heads=1)
    zero = jnp.zeros((1, 32))
    got = eva.eva_attention(q, k, v, zero, zero, window=WINDOW, chunk=CHUNK)
    q_, k_, v_ = (np.asarray(a[0, 0], np.float64) for a in (q, k, v))
    k_mean = k_.reshape(-1, CHUNK, 32).mean(1)
    v_mean = v_.reshape(-1, CHUNK, 32).mean(1)
    for t in (3, WINDOW, T, SEQ - 1):
        start = t // WINDOW * WINDOW
        keys = np.concatenate([k_mean[:start // CHUNK], k_[start:t + 1]])
        values = np.concatenate([v_mean[:start // CHUNK], v_[start:t + 1]])
        logits = keys @ q_[t] / np.sqrt(32)
        p = np.exp(logits - logits.max())
        np.testing.assert_allclose(got[0, 0, t], p @ values / p.sum(),
                                   rtol=2e-5, atol=2e-6)


# ----------------------------------------------------------------- the model
def _sizes(cfg):
    return dict(eps=cfg.rms_eps, theta=cfg.rope_theta, window=cfg.window,
                chunk=cfg.chunk, n_pred_heads=cfg.n_pred_heads)


def _batch(cfg, rows=2, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, 64, (rows, cfg.seq_len)).astype(np.int32)
    targets = np.roll(tokens, -1, 1)
    targets[:, -1] = -1
    return tokens, targets


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_model_through_make_train_step_matches_the_reference(impl):
    """Tiny EvaByte, float32, seeded weights, through the one step factory:
    the loss and every gradient leaf against the reference, and the step's
    own reported loss and gradient norm."""
    cfg = llama.evabyte_tiny(dtype=jnp.float32, attention_impl=impl,
                             remat=impl == "pallas")
    bundle = make_train_step(llama, cfg, rng=jax.random.PRNGKey(7))
    params = jax.tree.map(np.asarray, bundle.state["params"])
    tokens, targets = _batch(cfg)

    def ref(p):
        with jax.default_matmul_precision("highest"):
            return reference.loss(p, tokens, targets, _sizes(cfg))

    want, want_g = jax.value_and_grad(ref)(params)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, tokens, targets, cfg)))(params)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert set(got_g["blocks"]) == set(want_g["blocks"]) >= {"eva_phi", "eva_mu"}
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree.leaves(want_g)):
        np.testing.assert_allclose(
            g, r, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(r))),
            err_msg=jax.tree_util.keystr(path))
    _, metrics = bundle.step_fn(
        bundle.state, jax.device_put({"tokens": tokens, "targets": targets},
                                     bundle.data_sharding))
    np.testing.assert_allclose(metrics["loss"], want, rtol=1e-6)
    np.testing.assert_allclose(metrics["grad_norm"],
                               optax.global_norm(want_g), rtol=1e-5)
    # dropping the remote term is another function: the comparison sees it
    with jax.default_matmul_precision("highest"):
        dropped = jax.grad(lambda p: reference.loss(
            p, tokens, targets, _sizes(cfg), with_summaries=False))(params)
    assert abs(float(optax.global_norm(dropped) / optax.global_norm(want_g))
               - 1) > 2.0 ** -7


def test_head_targets_shift_one_array_and_end_with_the_row():
    targets = jnp.asarray([[10, 11, 12, 13, -1], [20, -1, 22, 23, 24]])
    got = np.asarray(parts.head_targets(targets, 3))
    assert got.shape == (2, 5, 3)
    np.testing.assert_array_equal(got[0, :, 0], [10, 11, 12, 13, -1])
    np.testing.assert_array_equal(got[0, :, 1], [11, 12, 13, -1, -1])
    np.testing.assert_array_equal(got[0, :, 2], [12, 13, -1, -1, -1])
    np.testing.assert_array_equal(got[1, :, 2], [22, 23, 24, -1, -1])
    np.testing.assert_array_equal(
        got, np.moveaxis(np.asarray(reference.head_targets(targets, 3)), 0, 2))
    # one head: the targets themselves, and the loss is the plain one
    np.testing.assert_array_equal(parts.head_targets(targets, 1)[..., 0], targets)


def test_loss_chunks_the_head_and_the_mlp_to_the_same_numbers(monkeypatch):
    """With the chunk limits lowered so that the tiny model's head and MLP go
    through in pieces: the same loss and gradients as whole."""
    cfg = llama.evabyte_tiny(dtype=jnp.float32)
    params = llama.init(cfg, jax.random.PRNGKey(1))
    tokens, targets = _batch(cfg)
    run = lambda: jax.value_and_grad(
        lambda p: llama.loss_fn(p, tokens, targets, cfg))(params)
    want, want_g = run()
    rows = cfg.seq_len // 4
    monkeypatch.setattr(parts, "HEAD_CHUNK_BYTES",
                        2 * rows * cfg.n_pred_heads * cfg.head_vocab * 4)
    monkeypatch.setattr(parts, "HEAD_CHUNK_TOKENS", 2 * rows)
    monkeypatch.setattr(parts, "MLP_CHUNK_BYTES", 2 * rows * cfg.d_ff * 4)
    shard = llama.block_shard(cfg, 2, cfg.seq_len, None)
    # an MLP past its limit goes in chunks whose five hidden tensors take
    # what two [B, S, D] do: 5 x 32 x 352 under 2 x 256 x 128
    assert (shard.head_rows, shard.mlp_rows) == (rows, 32)
    got, got_g = run()
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-7)


# ----------------------------------------- the remat rule on this block
PUBLISHED = llama.evabyte_6p5b(n_layer=4, remat=True, attention_impl="pallas")
# f32 parameters and gradients, two bf16 moments: 12 B a parameter
PUBLISHED_RESIDENT = 12 * llama.param_count(PUBLISHED)


def test_rule_on_the_published_block_keeps_what_fits():
    shard = llama.block_shard(PUBLISHED, 1, PUBLISHED.seq_len, None)
    assert (shard.d_ff, shard.head_dim, shard.window, shard.chunk,
            shard.vocab) == (11008, 128, 2048, 16, 8 * 320)
    by_name = {c.names: c.nbytes for c in parts.remat_candidates(shard)}
    tokens = 32768
    assert by_name[(names.RES_Q,)] == tokens * 4096 * 2
    assert by_name[(names.RES_EVA_O, names.RES_EVA_LSE)] == tokens * 32 * (128 * 2 + 4)
    # the summaries: 1/16 the size of k and v
    assert by_name[(names.RES_EVA_KT, names.RES_EVA_VT)] == 2 * tokens // 16 * 4096 * 2
    # the MLP takes the sequence in chunks: its hidden tensors are no
    # candidates, and no flash name is one
    assert not {names.RES_MLP_GATE, names.RES_MLP_UP, names.RES_FLASH_O} & {
        n for g in by_name for n in g}
    policy = parts.choose_remat_policy(shard, 4, V5E_BYTES_LIMIT,
                                       PUBLISHED_RESIDENT)
    assert 0 < policy.saved_bytes <= policy.budget_bytes
    freed = sum(c.frees for c in parts.remat_candidates(shard)
                if set(c.names) <= set(policy.saved))
    assert (PUBLISHED_RESIDENT + parts.rematted_working_set(shard, 4) - freed
            + policy.saved_bytes + blocks.REMAT_RESERVE_BYTES) <= V5E_BYTES_LIMIT
    assert set(policy.saved) <= set(names.RESIDUALS)
    # with nothing free, nothing; with no limit stated, nothing
    assert parts.choose_remat_policy(shard, 4, 4 * 2 ** 30,
                                     PUBLISHED_RESIDENT).saved == ()
    assert parts.choose_remat_policy(shard, 4, None, 0).saved == ()


def test_rule_arithmetic_equals_the_traced_shapes():
    """`saved_residuals` of one checkpointed EvaByte block: with room for
    everything, what it keeps beside its arguments is the candidates' names,
    byte for byte."""
    from jax._src.ad_checkpoint import saved_residuals

    cfg = llama.evabyte_tiny(remat=True, attention_impl="pallas")
    batch = 2
    params = llama.init(cfg, jax.random.PRNGKey(0))
    layer = jax.tree.map(lambda p: p[0], params["blocks"])
    x = jnp.zeros((batch, cfg.seq_len, cfg.d_model), cfg.dtype)
    shard = llama.block_shard(cfg, batch, cfg.seq_len, None)
    candidates = parts.remat_candidates(shard)
    assert {n for c in candidates for n in c.names} == {
        names.RES_Q, names.RES_K, names.RES_V, names.RES_EVA_O,
        names.RES_EVA_LSE, names.RES_EVA_KT, names.RES_EVA_VT, names.RES_MID,
        names.RES_MLP_GATE, names.RES_MLP_UP}

    def kept(limit):
        with mesh_lib.chip_memory(limit, 0):
            block_fn = parts.checkpoint_block(
                lambda x, p: llama._block(x, p, cfg), True, shard, cfg.n_layer)
            saved = saved_residuals(block_fn, x, layer)
        return sum(np.prod(aval.shape) * aval.dtype.itemsize
                   for aval, why in saved
                   if not why.startswith("from the argument"))

    everything = sum(c.nbytes for c in candidates)
    roomy = (blocks.REMAT_RESERVE_BYTES + cfg.n_layer * everything
             + parts.rematted_working_set(shard, cfg.n_layer) + 8)
    assert kept(roomy) == everything
    assert kept(blocks.REMAT_RESERVE_BYTES) == 0
    decision = [d for d in blocks.remat_policy_decisions()
                if d["bytes_limit"] == roomy]
    assert len(decision) == 1 and len(decision[0]["saved"]) == 10
    assert (decision[0]["mlp_rows"], decision[0]["head_rows"]) == (
        cfg.seq_len, cfg.seq_len)


# ------------------------------------------------------------ the benchmark
def test_family_arithmetic_is_issue_31s():
    """The family's own FLOP count and kernel work, at the cell's shapes:
    5.31 GFLOP a token, 1.07 TFLOP and 1.1 GB a forward call."""
    from benchmarks.families import evabyte as family
    from benchmarks.harness import spec

    cell, config, _ = spec.load_cell("evabyte-6.5b-l4.dataset")
    shapes = family.shapes(config, cell)
    assert shapes["params"] == llama.param_count(family.program_config(config, cell))
    assert round(family.train_flops_per_token(shapes) / 1e9, 2) == 5.31
    fwd = family.eva_call(shapes, "eva_agg_fwd")
    bwd = family.eva_call(shapes, "eva_agg_bwd")
    assert round(fwd["flops"] / 1e12, 2) == 1.07 and round(fwd["bytes"] / 1e9, 1) == 1.1
    assert bwd["flops"] == 2.5 * fwd["flops"]


READERS = ("eva_attn_ms_per_step", "eva_agg_roofline",
           "eva_prep_kv_ms_per_step", "eva_mfu_device")


def _facts(recorded):
    """What run.py hands a reader after a `--trace 1` run of the cell, from a
    trace recorded on the chip (benchmarks/testdata/)."""
    from benchmarks.families import evabyte as family
    from benchmarks.harness import peaks, program_trace, spec

    testdata = os.path.join(ROOT, "benchmarks", "testdata")
    with open(os.path.join(testdata, recorded)) as f:
        expected = json.load(f)
    got = program_trace.reduce_tables(program_trace.read_tables(
        os.path.join(testdata, expected["program_source"])))
    cell, config, mix = spec.load_cell("evabyte-6.5b-l4.dataset")
    return {
        "cell": cell, "config": config, "traffic": mix, "notes": [],
        "summary": {"shapes": family.shapes(config, cell)},
        "trace": expected.get("trace_facts", {"steps": 1.0}),
        "peaks": peaks.peaks_for("TPU v5 lite"), "program_trace": got,
    }


@pytest.mark.parametrize("reader", READERS)
def test_new_readers_read_the_recorded_trace_of_the_cell(reader):
    """The four per-layer metrics of PR 31 on one traced step recorded on a
    v5e: a number each, a share of a peak never over 100 %."""
    import importlib

    module = importlib.import_module(f"benchmarks.layer_metrics.{reader}")
    facts = _facts("evabyte-6.5b-l4.dataset.1step.scoped.program_expected.json")
    value = module.read(facts)
    want = {"eva_attn_ms_per_step": 212.94, "eva_prep_kv_ms_per_step": 31.51,
            "eva_agg_roofline": 53.67, "eva_mfu_device": 55.51}[reader]
    assert value == pytest.approx(want, rel=2e-3)
    if module.UNIT == "%":
        assert 0 < value <= 100
    entry = [m for m in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
             ["per_layer"] if m["name"] == reader]
    assert entry and entry[0]["workloads"] == ["evabyte-6.5b-l4.dataset"]
    assert (entry[0]["unit"], entry[0]["layer"]) == (module.UNIT, module.LAYER)
    # a program that names no EVA scope or kernel (the parent of PR 31; here
    # the recorded gpt2-xl step): nothing to read, nothing raised
    bare = _facts("gpt2-xl.fsdp4.1step.scoped.program_expected.json")
    if reader != "eva_mfu_device":         # that one reads the step, not a scope
        assert module.read(bare) is None


def test_cell_rehearses_on_the_cpu_and_ends_correct():
    """`run.py --workload evabyte-6.5b-l4.dataset --rehearse-cpu`: driver,
    trainer, Dataset, the reference check (loss and gradient norm) and the
    loop, tiny, end to end."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "evabyte-6.5b-l4.dataset", "--seed", "3000000019",
         "--seconds", "2", "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=280)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert "grad-norm" in out.stdout
