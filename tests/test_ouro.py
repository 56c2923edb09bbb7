"""The looped (Ouro) configs of ``ray_tpu/models/llama.py`` — a stack of layers
run ``ut_steps`` times on ONE set of weights (``blocks.run_repeated``),
sandwich norms, the final norm inside the loop, a head and an exit gate after
every pass, the exit-weighted objective — against their plain float32
reference (``benchmarks/families/ouro_reference.py``): the loss and every
leaf's gradient at 1, 2 and 4 passes, with and without ``remat``; the
gradient of a shared layer as the sum over the passes of the untied
reference's; the reduction at one pass; the defaults' lowering as it was
before the fields existed; the exit distribution at its edges and the
weighted head's cotangents; the remat rule told layers and applications
apart at the cell's shapes; one outer loop in the step — and the benchmark's
new entries: the configuration's published widths, the family's arithmetic,
the comparison that decides ``correct`` with its controls, each reader this
PR adds."""

import ast
import dataclasses
import hashlib
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.families import ouro as family  # noqa: E402
from benchmarks.families import ouro_reference as reference  # noqa: E402
from ray_tpu.models import blocks, llama, parts  # noqa: E402
from ray_tpu.ops import cross_entropy  # noqa: E402
from ray_tpu.tracing import names  # noqa: E402

CELL = "ouro-2.6b-l8.dataset"
CONFIG = "ouro-2.6b-l8"
NEW_READERS = ("ouro_mfu_device", "ouro_flash_attn_roofline",
               "exit_gate_ms_per_step", "loop_expected_passes")
# accepted readers of a kernel or a span this family's step has
SHARED_READERS = ("flash_fwd_ms_per_step", "flash_bwd_ms_per_step",
                  "step_dispatch_ms_per_step", "data_wait_ms_per_step",
                  "report_ms_per_step", "setup_compile_s")


def _batch(cfg, rows=2, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, 64, (rows, cfg.seq_len)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1)
    targets[:, -1] = -1
    return tokens, targets


def _params(cfg, seed=0):
    """Seeded weights with every gain and the gate's bias off their drawn
    values, so that one the program forgot would show."""
    params = llama.init(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))

    def moved(path, x):
        key = getattr(path[-1], "key", "")
        if key.endswith("norm") or key == "exit_b":
            return x + 0.1 * jax.random.normal(next(keys), x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(moved, params)


def _sizes(cfg, **switches):
    return family.reference_sizes(cfg, **switches)


def _value_and_grad(loss_of, params):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss_of))(params)


def _worst(got, want):
    """The largest relative error of a leaf, by norm."""
    return max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b)
                           / (jnp.linalg.norm(b) + 1e-30)), got, want)))


# ------------------------------------------------- program against reference
@pytest.mark.parametrize("remat", [False, True], ids=["kept", "remat"])
@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    (jnp.float32, 2e-6, 5e-5), (jnp.bfloat16, 1e-3, 0.15)],
    ids=["float32", "bfloat16"])
@pytest.mark.parametrize("passes", [1, 2, 4])
def test_loss_and_every_gradient_equal_the_reference(passes, dtype, loss_tol,
                                                     grad_tol, remat):
    cfg = llama.ouro_tiny(ut_steps=passes, dtype=dtype, remat=remat)
    params = _params(cfg)
    tokens, targets = _batch(cfg)
    loss, grads = _value_and_grad(
        lambda p: llama.loss_fn(p, tokens, targets, cfg), params)
    want, want_grads = _value_and_grad(
        lambda p: reference.loss(p, tokens, targets, _sizes(cfg)), params)
    assert float(loss) == pytest.approx(float(want), rel=loss_tol)
    assert set(grads) == set(want_grads) >= {"exit_w", "exit_b"}
    if passes == 1:
        # one pass: p = 1 whatever the gate says, and nothing reaches it
        assert float(jnp.abs(grads["exit_w"]).max()) == 0.0
        grads, want_grads = (
            {k: v for k, v in g.items() if not k.startswith("exit_")}
            for g in (grads, want_grads))
    assert _worst(grads, want_grads) < grad_tol


def test_a_shared_layers_gradient_is_the_sum_over_the_passes_of_the_untied():
    """The reference with T parameter sets, each a copy of the shared one:
    its gradient for pass t's set is that pass's contribution, and the
    program's gradient of the shared layers is their sum."""
    cfg = llama.ouro_tiny(ut_steps=4, dtype=jnp.float32)
    params = _params(cfg)
    tokens, targets = _batch(cfg)
    grads = _value_and_grad(
        lambda p: llama.loss_fn(p, tokens, targets, cfg), params)[1]
    untied = {**params, "blocks": jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.ut_steps,) + a.shape),
        params["blocks"])}
    apart = _value_and_grad(lambda p: reference.loss(
        p, tokens, targets, _sizes(cfg, untied=True)), untied)[1]
    summed = jax.tree.map(lambda a: a.sum(0), apart["blocks"])
    assert _worst(grads["blocks"], summed) < 5e-5
    # every pass contributes: no one of them is the whole
    one = jax.tree.map(lambda a: a[0], apart["blocks"])
    assert _worst(grads["blocks"], one) > 0.1
    # ... and the reference's own switch drops exactly one of them
    dropped = _value_and_grad(lambda p: reference.loss(
        p, tokens, targets, _sizes(cfg, drop_pass=1)), params)[1]
    but_one = jax.tree.map(lambda a: a.sum(0) - a[1], apart["blocks"])
    assert _worst(dropped["blocks"], but_one) < 5e-5


def test_one_pass_is_the_plain_cross_entropy_of_the_sandwich_norm_model():
    cfg = llama.ouro_tiny(ut_steps=1, dtype=jnp.float32)
    params = _params(cfg)
    tokens, targets = _batch(cfg)
    loss, said = jax.jit(lambda p: llama.loss_fn(
        p, tokens, targets, cfg, counters=True))(params)
    p1, entropy = np.asarray(said).view(np.float32)[0]
    assert (p1, entropy) == (1.0, 0.0)
    plain = dataclasses.replace(cfg, exit_gate=False)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p: llama.loss_fn(p, tokens, targets, plain))(
            {k: v for k, v in params.items() if not k.startswith("exit_")})
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    # and the sandwich norms are in it: without them the loss is another
    bare = dataclasses.replace(plain, sandwich_norm=False)
    assert abs(float(jax.jit(lambda p: llama.loss_fn(
        p, tokens, targets, bare))(params)) - float(want)) > 1e-4


# recorded on the parent of PR 64 (commit 16fc2be): sha256 of
# jit(value_and_grad(loss_fn)).lower(abstract params, tokens, targets).as_text()
LOWERED_BEFORE = {
    "llama_tiny": (llama.llama_tiny, {},
                   "551ef7054d90e6f163d2afa2d4c0bf2c71ad86e91b1a884c8e08434dff"
                   "60e097"),
    "llama_tiny_remat": (llama.llama_tiny, {"remat": True},
                         "0ccc2c5ce13a7e2130c5567533067e07806a58438cdf3231aa2a"
                         "bad01bd13ab9"),
    "evabyte_tiny": (llama.evabyte_tiny, {"remat": True},
                     "17231789d675e95dcb4e79166e245c91a6f2820a35164baad2969383"
                     "719a66f1"),
}


@pytest.mark.parametrize("name", sorted(LOWERED_BEFORE))
def test_with_the_new_fields_off_the_family_lowers_as_it_did(name):
    """Loss and gradients of a config that says nothing of loops, sandwich
    norms or gates are today's bit for bit: the lowered text is the one the
    parent commit gave (the chunked head without weights among it)."""
    preset, overrides, want = LOWERED_BEFORE[name]
    cfg = preset(**overrides)
    assert (cfg.ut_steps, cfg.sandwich_norm, cfg.exit_gate) == (1, False,
                                                                False)
    assert llama.step_counters(cfg) is None
    p = jax.eval_shape(lambda: llama.init(cfg, jax.random.PRNGKey(0)))
    assert not {"exit_w", "exit_b"} & set(p)
    tok = jax.ShapeDtypeStruct((2, cfg.seq_len), jnp.int32)
    text = jax.jit(jax.value_and_grad(
        lambda p, a, b: llama.loss_fn(p, a, b, cfg))).lower(
        p, tok, tok).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == want


def test_the_new_weights_draw_nothing_from_the_old_ones_keys():
    plain = llama.init(llama.llama_tiny(), jax.random.PRNGKey(3))
    looped = llama.init(llama.llama_tiny(
        ut_steps=2, sandwich_norm=True, exit_gate=True), jax.random.PRNGKey(3))
    for key in ("wte", "lm_head", "final_norm"):
        assert np.array_equal(plain[key], looped[key])
    for key, leaf in plain["blocks"].items():
        assert np.array_equal(leaf, looped["blocks"][key])
    assert float(jnp.std(looped["exit_w"])) == pytest.approx(0.02, rel=0.3)
    assert float(looped["exit_b"][0]) == 0.0
    assert float(looped["blocks"]["mlp_out_norm"].min()) == 1.0


# ------------------------------------------ the objective and the weighted head
@pytest.mark.parametrize("bias", [-60.0, -8.0, 0.0, 8.0, 60.0])
def test_the_exit_distribution_sums_to_one_at_its_edges(bias):
    """λ near 0 and near 1 in every pass: no log of 0, no NaN, the mass on
    the passes sums to 1 and loss and gradient are finite."""
    cfg = llama.ouro_tiny(ut_steps=4, dtype=jnp.float32)
    params = {**llama.init(cfg, jax.random.PRNGKey(0)),
              "exit_b": jnp.full((1,), bias, jnp.float32)}
    tokens, targets = _batch(cfg)
    (loss, said), grads = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, tokens, targets, cfg, counters=True),
        has_aux=True))(params)
    *p, entropy = np.asarray(said).view(np.float32)[0]
    assert sum(p) == pytest.approx(1.0, abs=1e-5)
    assert 0.0 <= entropy <= np.log(4) + 1e-5
    assert p[0 if bias > 0 else -1] > (0.99 if abs(bias) > 1 else 0.1)
    assert np.isfinite(float(loss))
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    want_p = reference.loss_parts(params, tokens, targets, _sizes(cfg))[3]
    assert np.allclose(p, want_p, atol=1e-5)


def _head_case(seed=0, B=3, S=32, D=16, V=40):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (B, S, D), jnp.float32)
    head = jax.random.normal(keys[1], (D, V), jnp.float32) * 0.3
    targets = jax.random.randint(keys[2], (B, S, 1), -1, V)     # some ignored
    weights = jax.random.uniform(keys[3], (B, S, 1), jnp.float32)
    return x, targets, head, weights


def test_the_weighted_heads_cotangents_are_the_written_out_ones():
    """Σ w · nll / N with N the valid targets: d w = nll / N, w inside d x and
    d lm_head — against jax.nn.log_softmax, differentiated by AD."""
    x, targets, head, weights = _head_case()
    valid = targets[..., 0] >= 0
    n = int(valid.sum())

    def plain(x, head, w):
        logp = jax.nn.log_softmax(jnp.einsum("bsd,dv->bsv", x, head), -1)
        nll = -jnp.take_along_axis(
            logp, jnp.where(valid, targets[..., 0], 0)[..., None], -1)[..., 0]
        return jnp.sum(jnp.where(valid, w[..., 0] * nll, 0.0)) / n, nll

    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(
            lambda x, h, w: cross_entropy.chunked_head_xent(x, targets, h, 8, w),
            argnums=(0, 1, 2))(x, head, weights)
        (want, nll), want_grads = jax.value_and_grad(
            plain, argnums=(0, 1, 2), has_aux=True)(x, head, weights)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for g, w in zip(grads, want_grads):
        assert np.allclose(g, w, rtol=1e-4, atol=1e-7)
    assert np.allclose(grads[2][..., 0], jnp.where(valid, nll, 0.0) / n,
                       rtol=1e-5, atol=1e-8)
    # without differentiation the same value, and no gradient work
    assert float(cross_entropy.chunked_head_xent(
        x, targets, head, 8, weights)) == pytest.approx(float(want), rel=1e-6)


def test_the_weighted_head_at_weights_one_is_the_unweighted_head():
    x, targets, head, _ = _head_case(seed=1)
    ones = jnp.ones(targets.shape, jnp.float32)

    def both(fn):
        return jax.value_and_grad(fn, argnums=(0, 1))(x, head)

    got, grads = both(lambda x, h: cross_entropy.chunked_head_xent(
        x, targets, h, 8, ones))
    want, want_grads = both(lambda x, h: cross_entropy.chunked_head_xent(
        x, targets, h, 8))
    assert float(got) == float(want)
    for g, w in zip(grads, want_grads):
        assert np.array_equal(g, w)
    # parts.lm_head_loss hands the weights through, chunked whatever the size
    loss = parts.lm_head_loss(x, targets[..., 0], head, jnp.float32,
                              weights=ones[..., 0])
    assert float(loss) == pytest.approx(float(want), rel=1e-6)


# ------------------------------------------------------------ the loop itself
def _scans(jaxpr):
    """[(length, the scans inside its body)] of a jaxpr's scans, through
    every call and checkpoint, in order."""
    out = []
    for eqn in jaxpr.eqns:
        inner = [v for v in eqn.params.values()
                 if hasattr(v, "jaxpr") or hasattr(v, "eqns")]
        for sub in inner:
            found = _scans(getattr(sub, "jaxpr", sub))
            if eqn.primitive.name == "scan":
                out.append((eqn.params["length"], found))
            else:
                out += found
    return out


def test_the_step_holds_one_outer_loop_and_one_flash_forward_a_body():
    """Compile time and program size follow the distinct runs, not T x L: the
    forward is ONE scan over the passes around ONE scan over the layers, and
    the flash forward is traced once in it (interpreted off a TPU)."""
    cfg = llama.ouro_tiny(ut_steps=3, n_layer=4, attention_impl="pallas",
                          remat=True)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tokens, targets = _batch(cfg)
    fwd = jax.make_jaxpr(lambda p: llama.loss_fn(p, tokens, targets, cfg))(
        params)
    layer_loops = [s for s in _scans(fwd.jaxpr) if s[0] == 3]
    assert len(layer_loops) == 1                  # the passes
    assert [n for n, _ in layer_loops[0][1]][:1] == [4]   # the layers, inside
    text = str(fwd)
    assert text.count(f"name={names.FLASH_FWD_KERNEL}") == 1
    grad = str(jax.make_jaxpr(jax.grad(
        lambda p: llama.loss_fn(p, tokens, targets, cfg)))(params))
    assert 1 <= grad.count(f"name={names.FLASH_FWD_KERNEL}") <= 2
    assert grad.count(f"name={names.FLASH_BWD_KERNEL}") == 1
    loop = next(d for d in blocks.loop_decisions()
                if (d["passes"], d["layers"]) == (3, 4))
    assert loop["applications"] == 12
    assert loop["grad_stack_bytes"] == 4 * sum(
        a.size for a in jax.tree.leaves(params["blocks"]))
    pattern = next(d for d in blocks.layer_pattern_decisions()
                   if d.get("passes") == 3 and d["pattern"] == "BBBB")
    assert pattern["applications"] == {"B": 12}
    assert pattern["groups"] == ["4 x scan(B)"]


def test_run_repeated_is_the_passes_written_out():
    """blocks.run_repeated against a Python loop over the passes, value and
    the shared stacks' gradient; the states come out stacked."""
    stacks = [{"B": {"w": jax.random.normal(jax.random.PRNGKey(0), (3, 5, 5))
                     * 0.3}}]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5))
    block = {"B": lambda x, p: jnp.tanh(x @ p["w"])}

    def between(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    def looped(stacks):
        return blocks.run_repeated(block, "BBB", x, stacks, 4, between)

    def written_out(stacks):
        h, out = x, []
        for _ in range(4):
            h = between(blocks.run_pattern(block, "BBB", h, stacks))
            out.append(h)
        return jnp.stack(out)

    assert looped(stacks).shape == (4, 2, 5)
    assert np.allclose(looped(stacks), written_out(stacks), atol=1e-6)
    g = jax.grad(lambda s: jnp.sum(looped(s) ** 3))(stacks)
    want = jax.grad(lambda s: jnp.sum(written_out(s) ** 3))(stacks)
    assert np.allclose(g[0]["B"]["w"], want[0]["B"]["w"], atol=1e-5)


# ------------------------------------------------------------------- the rule
def _cell():
    from benchmarks.harness import spec

    cell, config, _ = spec.load_cell(CELL)
    return cell, config


def _benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_at_the_cells_shapes_the_rule_tells_layers_from_applications():
    """The cell's step on a chip that states a v5e's bytes: 32 applications
    wait and are what a kept residual is copied for, 8 slices of weight
    gradients exist, the loop holds one more pass's stack of them and the
    passes' states; the rule keeps the flash kernel's two and q."""
    cell, config = _cell()
    cfg = dataclasses.replace(family.program_config(config, cell),
                              attention_impl="pallas")
    shard = llama.block_shard(cfg, cell["per_chip_batch"], cell["seq_len"],
                              None)
    # (PR 65: the four passes' 256 rows are a chunk of 1,024 tokens — 192 MiB
    # of float32 logits: 64 MiB held 256 tokens, too few to hide the carry)
    assert (shard.passes, shard.out_norms, shard.head_rows) == (4, True, 256)
    layer_bytes = 4 * (4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048)
    assert layer_bytes == 4 * 51_388_416
    kind = blocks.KindShard(8 * shard.passes,
                            tuple(parts.remat_candidates(shard)),
                            parts.block_working_set(shard), layer_bytes)
    assert kind.applications == 32
    runs = [(("block",), 8)]
    phases = blocks.backward_phases(shard, {"block": kind}, runs)
    phase = max(phases, key=lambda p: p.nbytes)
    assert phase.name == "8 x scan(block)"
    block_input = 8192 * 2048 * 2
    one_pass = blocks.backward_phases(
        shard._replace(passes=1), {"block": kind._replace(
            applications=8, grad_bytes=0)}, runs)[1].nbytes
    # the other passes' block inputs and head rows, the loop's states (h_t,
    # the norm's input, d h_t) and ONE more stack of the layers' gradients
    assert phase.nbytes - one_pass == (
        24 * block_input + 3 * 4 * block_input + 8 * layer_bytes
        + 3 * 256 * 49152 * 8)
    resident = 12 * llama.param_count(cfg)
    assert round(resident / 1e7) == 735            # 7.35 GB, the config's file
    policy = blocks.choose_remat_policy_kinds(
        (kind,), phase.nbytes, family.V5E_BYTES_LIMIT, resident)
    assert policy.saved == (names.RES_FLASH_O, names.RES_FLASH_LSE,
                            names.RES_Q)
    per_application = 8192 * 16 * (128 * 2 + 4) + 8192 * 2048 * 2
    assert policy.saved_bytes == 32 * per_application <= policy.budget_bytes


def test_the_remat_event_says_passes_and_applications():
    cfg = llama.ouro_tiny(ut_steps=3, remat=True, attention_impl="pallas")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tokens, targets = _batch(cfg)
    jax.make_jaxpr(lambda p: llama.loss_fn(p, tokens, targets, cfg))(params)
    said = [d for d in blocks.remat_policy_decisions()
            if d.get("passes") == 3 and d["seq"] == cfg.seq_len
            and d["n_layer"] == cfg.n_layer]
    assert said and tuple(said[0]) == (names.REMAT_POLICY_ARGS
                                       + names.REMAT_POLICY_LOOP_ARGS)
    assert (said[0]["n_layer"], said[0]["applications"]) == (2, 6)
    # a model of one pass says what it always said
    plain = llama.llama_tiny(remat=True, attention_impl="pallas")
    t2, g2 = _batch(plain)
    jax.make_jaxpr(lambda p: llama.loss_fn(p, t2, g2, plain))(
        llama.init(plain, jax.random.PRNGKey(0)))
    assert any(tuple(d) == names.REMAT_POLICY_ARGS and d["seq"] == 128
               for d in blocks.remat_policy_decisions())


def test_a_step_says_its_mean_exit_distribution_among_its_counters():
    from ray_tpu.train.train_step import make_train_step

    cfg = llama.ouro_tiny(ut_steps=4)
    offered = llama.step_counters(cfg)
    assert offered.kind == names.EXIT_DISTRIBUTION_KIND
    assert offered.fields == offered.float_fields == (
        "exit_p1", "exit_p2", "exit_p3", "exit_p4", names.STEP_EXIT_ENTROPY)
    assert offered.static(128) == {"passes": 4}
    bundle = make_train_step(llama, cfg)
    tokens, targets = _batch(cfg)
    _, metrics = bundle.step_fn(bundle.state, {"tokens": tokens,
                                               "targets": targets})
    said = np.asarray(metrics["counters"])
    assert said.shape == (1, 5) and said.dtype == np.int32
    *p, entropy = said.view(np.float32)[0]
    assert sum(p) == pytest.approx(1.0, abs=1e-5)
    # born near [1/2, 1/4, 1/8, 1/8]
    assert np.allclose(p, [0.5, 0.25, 0.125, 0.125], atol=0.03)
    assert sum((t + 1) * q for t, q in enumerate(p)) == pytest.approx(
        1.875, abs=0.1)
    assert entropy == pytest.approx(1.2130, abs=0.02)


# ------------------------------------------------- the benchmark's new entries
def test_the_configuration_holds_every_published_width_and_states_its_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model catalog is not on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["name"] == "Ouro-2.6B")
    cell, config = _cell()
    entry = next(c for c in _benchmark()["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] == published["source_url"]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    for key, value in published["config"].items():
        if key in entry["reduced"]:
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert config["published"]["num_hidden_layers"] == 48
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 8
    assert len(config["reduced"]) == 2
    for letter in "abcdef":
        assert any(a.startswith(f"({letter})") for a in config["assumed"])
    assert "612,438,017" in config["deployment"]
    assert "7.35 GB" in config["deployment"]
    assert (cell["seq_len"], cell["per_chip_batch"], cell["remat"],
            cell["reference_rows"], cell["reference_grad"], cell["mesh"],
            cell["trace_steps"]) == (8192, 1, True, 1, True, {"fsdp": 1}, 2)


def test_the_cells_parameters_and_the_familys_arithmetic():
    cell, config = _cell()
    cfg = family.program_config(config, cell)
    shapes = family.shapes(config, cell)
    assert llama.param_count(cfg) == shapes["params"] == 612_438_017
    assert (cfg.ut_steps, cfg.sandwich_norm, cfg.exit_gate, cfg.exit_beta,
            cfg.n_layer, cfg.head_dim, cfg.rms_eps, cfg.rope_theta) == (
        4, True, True, 0.05, 8, 128, 1e-6, 1e6)
    assert cfg == llama.ouro_2p6b(n_layer=8, seq_len=8192, remat=True)
    per_token = family.train_flops_per_token(shapes)
    layer, head = 4 * 2048 ** 2 + 3 * 2048 * 5632, 2048 * 49152
    assert per_token == 6.0 * (32 * layer + 4 * (head + 2048)
                               + 32 * 2 * 2048 * 8193 / 2)
    assert 15.4e9 < per_token < 15.6e9             # the issue's "about 15.5"
    flash = family.flash_attn_call(shapes)
    assert flash["flops"] == 32 * 7 * 16 * 8192 * 8192 * 128
    assert flash["bytes"] == 32 * (12 * 16 * 8192 * 128 * 2
                                   + 8 * 16 * 8192)


def test_the_family_refuses_a_program_without_the_loop(monkeypatch):
    monkeypatch.delattr(names, "LOOP")
    with pytest.raises(SystemExit, match="cannot run a cell of family ouro"):
        family.shapes(*reversed(_cell()))


def _rehearsal():
    from benchmarks.harness import spec

    cell, config, mix = spec.load_cell(CELL)
    with open(os.path.join(ROOT, "benchmarks", "testdata",
                           "rehearse-ouro.json")) as f:
        tiny = json.load(f)
    config.update(tiny["config"])
    cell.update(tiny["cell"])
    return cell, config, mix


@pytest.mark.parametrize("control,refused", [
    ("", ()), ("float8", ("grad_norm",)),
    ("one_pass_fewer", ("loss", "grad_norm")),
    ("pass_grad_dropped", ("grad_norm",)), ("no_entropy", ("loss",)),
    ("unnormed_carry", ("grad_norm",))],
    ids=["program", "float8-reference", "one-pass-fewer", "pass-grad-dropped",
         "no-entropy", "unnormed-carry"])
def test_the_comparison_that_decides_correct(control, refused):
    """The family's ``reference_check`` at the CPU rehearsal's sizes, judged
    by ``harness/checks.failures`` as run.py judges a run: the bf16 program
    is correct; each switched reference in the program's place is not. The
    limits are stated for these sizes (testdata/rehearse-ouro.json says from
    which readings); the cell's own stand in families/ouro.py."""
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


def test_a_wrong_gate_fails_the_cell_inside_a_right_global_norm():
    """The gate's gradient is a sliver of the global norm: ``reference_check``
    holds it to a limit of its own — against its own norm, or GATE_FLOOR of
    the global one where its own is smaller still — and adds 1.0 to what
    harness/checks compares where it is outside."""
    gate = {}

    def readings(*a, **k):
        ref = {"loss": 5.0, "grad_norm": 10.0, "gate_grad_norm": gate["ref"],
               "stack_grad_norm": 8.0, "exit_p": [0.5, 0.25, 0.125, 0.125]}
        return {"program": {**ref, "gate_grad_norm": gate["program"]},
                "reference": ref, "rows": 1, "with_grad": True,
                "loss_rtol": family.LOSS_RTOL,
                "grad_norm_rtol": family.GRAD_NORM_RTOL,
                "gate_grad_rtol": family.GATE_GRAD_RTOL,
                "stack_grad_rtol": family.STACK_GRAD_RTOL,
                "exit_p_atol": family.EXIT_P_ATOL}

    def compared(ref, program):
        gate.update(ref=ref, program=program)
        return family.reference_check(None, None, None, None)[
            "program"]["grad_norm"]

    real, family.readings = family.readings, readings
    try:
        rtol, floor = family.GATE_GRAD_RTOL, family.GATE_FLOOR * 10.0
        assert compared(1.0, 1.0 + 0.5 * rtol) == 10.0
        assert compared(1.0, 1.0 + 2 * rtol) == 20.0
        # a gate born exiting early: its norm under the floor
        assert compared(0.1, 0.1 + 0.5 * rtol * floor) == 10.0
        assert compared(0.1, 0.1 + 2 * rtol * floor) == 20.0
    finally:
        family.readings = real


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
    # (a later PR's configuration, cell and readers come after them: PR 66's)
    assert b["configs"][9]["name"] == CONFIG
    assert b["workloads"][10] == {
        **b["workloads"][10], "name": CELL, "config": CONFIG,
        "traffic": "dataset", "chips": 1}
    assert len(b["configs"]) >= 10 and len(b["workloads"]) >= 11
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    metrics = [m["name"] for m in b["per_layer"]]
    first = metrics.index(NEW_READERS[0])
    assert metrics[first:first + len(NEW_READERS)] == list(NEW_READERS)
    for name in SHARED_READERS:
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"][-2:]
    # every list that held the Qwen3-Next cell holds this one, but the
    # experts' and that family's own; the rate and the set-up time, not p90
    for entry in b["per_layer"]:
        held = entry.get("workloads", [])
        if ("qwen3-next-80b-a3b-l4.dataset" in held and len(held) > 1
                and not entry["name"].startswith("moe_")):
            assert CELL in held, entry["name"]
    p90 = next(m for m in b["end_to_end"] if m["name"] == "step_ms_p90")
    assert CELL not in p90["workloads"]
    for name in ("flash_attn_ms_per_step", "flash_attn_roofline", "mfu_device",
                 "eva_mfu_device", "qwen3_next_flash_attn_roofline",
                 "moe_passes_per_step"):
        entry = next(m for m in b["per_layer"] if m["name"] == name)
        assert CELL not in entry["workloads"]
    for entry in b["configs"] + b["workloads"]:
        assert len(entry["why"]) <= 200


def test_the_new_benchmark_files_import_no_program_at_module_level():
    for name in ("ouro", "ouro_reference"):
        path = os.path.join(ROOT, "benchmarks", "families", name + ".py")
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        level = tree.body if name == "ouro" else list(ast.walk(tree))
        for node in level:
            if isinstance(node, ast.ImportFrom):
                assert (node.module or "").split(".")[0] != "ray_tpu"
            elif isinstance(node, ast.Import):
                assert all(a.name.split(".")[0] != "ray_tpu" for a in node.names)


def _recorded_facts():
    """The facts a reader would be handed in the EvaByte cell's traced run
    (the same block code, no loop, no gate): its shapes, v5e's peaks and the
    recorded trace's reduction."""
    from benchmarks.families import evabyte
    from benchmarks.harness import peaks, program_trace, spec

    cell, config, mix = spec.load_cell("evabyte-6.5b-l4.dataset")
    path = os.path.join(
        ROOT, "benchmarks", "testdata",
        "evabyte-6.5b-l4.dataset.1step.scoped.program.json.gz")
    got = program_trace.reduce_tables(program_trace.read_tables(path))
    assert got["instrumented"]
    return {"cell": cell, "config": config, "traffic": mix, "notes": [],
            "summary": {"shapes": evabyte.shapes(config, cell),
                        "t_window_wall": 0.0, "t_end_wall": 1.0},
            "trace": {"steps": got["steps"], "step_device_ms": 100.0},
            "peaks": peaks.peaks_for("TPU v5 lite"), "driver": {},
            "program_trace": got, "session_timeline": None}


@pytest.mark.parametrize("name", ("ouro_flash_attn_roofline",
                                  "exit_gate_ms_per_step",
                                  "loop_expected_passes"))
def test_a_new_reader_reads_nothing_from_another_cells_trace(name):
    """A program without the scope, the flash calls or the counters — every
    trace recorded before PR 64, and the parent's — gives the reader nothing
    to read: None, no raise."""
    reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    assert reader.read(_recorded_facts()) is None


def test_loop_expected_passes_reads_the_windows_events():
    reader = importlib.import_module(
        "benchmarks.layer_metrics.loop_expected_passes")

    def event(t, p):
        return {"start": t, "end": t, "args": {
            "step": 1, "kind": "exit_distribution", "t_dispatch": t,
            "layers": [7], "passes": 4, "exit_entropy": [1.2],
            **{f"exit_p{i + 1}": [q] for i, q in enumerate(p)}}}

    load = {"start": 5.0, "end": 5.0, "args": {
        "step": 2, "kind": "expert_load", "t_dispatch": 5.0}}
    facts = {"summary": {"t_window_wall": 4.0, "t_end_wall": 8.0},
             "session_timeline": {"spans": {"train/step_counters": [
                 event(3.0, [1.0, 0.0, 0.0, 0.0]),      # before the window
                 event(5.0, [0.5, 0.25, 0.125, 0.125]), load,
                 event(7.0, [0.25, 0.25, 0.25, 0.25])]}}}
    assert reader.read(facts) == pytest.approx((1.875 + 2.5) / 2)
