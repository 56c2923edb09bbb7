"""The seam of the model layer, read off the source with `ast`:

    ops/ <- models/blocks.py <- models/parts.py <- models/{gpt2,llama,...}.py
            models/hyper_connections.py <-´

`blocks.py` (the layer loop, the step's half of the remat rule) imports no
module of `ray_tpu.models`; `parts.py` (what more than one family is built
from) imports of them only `blocks`; `hyper_connections.py` (a residual path
of n streams any family's layer may wrap its sublayers with, PR 57) none; a
model file imports only those three —
never another model — and no module reads a name with a leading underscore off
another one of them. A model is added beside the others, not inside one
(ROADMAP D21: PRs 31, 33 and 42 edited `gpt2.py`, the control cell's file, to
add a model, and PR 37 was refused on that cell).

A family that may not import its neighbour does not copy from it either (PR
59; ROADMAP D25): no top-level function of a model file has the body of one
of another module of the layer. What two families share has ONE home behind
the seam — `blocks.py` what counts a PATTERN (`group_counts`, `init_pattern`,
`with_grad_bytes`, `aux_by_layer`, `one_candidate_a_name`), `parts.py` what
is, or prices, a HALF of a layer (`swiglu`, `in_row_chunks`, `swiglu_price`,
`routing_candidates`, `gated_experts_working_set`, `all_but`,
`param_count`), `ops/moe.py` what prices or reports its own kernels
(`sort_ops`, `record_expert_loads`) — and a family calls it."""

import ast
import functools
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = os.path.join(ROOT, "ray_tpu", "models")
MODEL_FILES = sorted(f[:-3] for f in os.listdir(MODELS)
                     if f.endswith(".py") and f != "__init__.py")
SHARED = {"blocks": set(), "parts": {"blocks"}, "hyper_connections": set()}


def _tree(path):
    with open(path) as f:
        return ast.parse(f.read(), path)


def _model_imports(tree):
    """The modules of ray_tpu.models a file imports, anywhere in it."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module == "ray_tpu.models":
                out |= {a.name for a in node.names}
            elif node.module.startswith("ray_tpu.models."):
                out.add(node.module.split(".")[2])
        elif isinstance(node, ast.Import):
            out |= {a.name.split(".")[2] for a in node.names
                    if a.name.startswith("ray_tpu.models.")}
    return out


def _private_reads(tree, own):
    """(line, module, name) wherever a `_name` is read off one of the model
    layer's modules other than ``own``: `module._name`, or `from
    ray_tpu.models.module import _name`."""
    others = set(MODEL_FILES) - {own}
    out = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute) and node.attr.startswith("_")
                and not node.attr.startswith("__")):
            base = node.value
            name = (base.id if isinstance(base, ast.Name)
                    else base.attr if isinstance(base, ast.Attribute) else None)
            if name in others:
                out.append((node.lineno, name, node.attr))
        elif (isinstance(node, ast.ImportFrom) and node.module
              and node.module.startswith("ray_tpu.models.")
              and node.module.split(".")[2] in others):
            out += [(node.lineno, node.module.split(".")[2], a.name)
                    for a in node.names if a.name.startswith("_")]
    return out


def test_there_are_models_to_hold_to_the_seam():
    assert {"blocks", "parts", "gpt2", "llama", "nemotron_h"} <= set(MODEL_FILES)


@pytest.mark.parametrize("name", MODEL_FILES)
def test_a_model_file_imports_blocks_and_parts_and_no_other_model(name):
    allowed = SHARED.get(name, set(SHARED))
    got = _model_imports(_tree(os.path.join(MODELS, name + ".py")))
    assert got <= allowed, (
        f"models/{name}.py imports {sorted(got - allowed)} of ray_tpu.models; "
        f"it may import {sorted(allowed) or 'none of them'}")


def test_blocks_names_no_mixer_and_no_mlp():
    """Of a model's shard `blocks.py` reads the stream's and the head's terms
    and the rows the MLP takes (the event's args), nothing of what the block
    is made of."""
    of_a_block = {"window", "chunk", "flash", "kv_heads", "mlp_hidden",
                  "dense_mlp", "heads", "head_dim", "d_ff", "cast_in_loop"}
    read = {node.attr for node in ast.walk(
        _tree(os.path.join(MODELS, "blocks.py")))
        if isinstance(node, ast.Attribute)}
    assert not read & of_a_block, sorted(read & of_a_block)


def _sources():
    for folder, _, files in os.walk(os.path.join(ROOT, "ray_tpu")):
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(folder, f)
    yield os.path.join(ROOT, "chip_smoke.py")


def test_no_private_name_of_a_model_module_is_read_from_outside_it():
    found = []
    for path in _sources():
        own = (os.path.basename(path)[:-3]
               if os.path.dirname(path) == MODELS else None)
        found += [f"{os.path.relpath(path, ROOT)}:{line}: {module}.{attr}"
                  for line, module, attr in _private_reads(_tree(path), own)]
    assert not found, "\n".join(found)


def test_the_hybrid_config_carries_nothing_for_another_models_functions():
    """`NemotronHConfig` had three class attributes only so that llama's
    functions, which took a whole config, found what they read."""
    from ray_tpu.models import nemotron_h

    for name in ("mixer", "norm_unit_offset", "n_pred_heads"):
        assert not hasattr(nemotron_h.NemotronHConfig, name), name


# --------------------------------------------------------------------------- #
# No copies across the seam (PR 59)
# --------------------------------------------------------------------------- #

# the modules a model file's one-line body may hand its work to
HOMES = {"blocks", "parts", "hyper", "moe"}
# pairs that stay, by name, and why
KEPT_ALIKE = {
    # a family's public forward over an untied head: its own trunk, then one
    # product — two lines that name the family's own functions
    frozenset({("llama", "forward"), ("minicpm_sala", "forward")}),
    # each module's own registry of recorded decisions, listed: one line
    frozenset({("blocks", "remat_policy_decisions"),
               ("hyper_connections", "decisions")}),
}


@functools.lru_cache(maxsize=None)
def _bodies(name):
    """{function: its body as `ast.dump` gives it, the docstring and every
    annotation dropped} for the top-level functions of models/``name``.py —
    but for a body that is one ``return`` of a call into a shared home: that
    is what USING the home looks like, and two families that use it alike
    are not copies of each other."""
    out = {}
    for node in _tree(os.path.join(MODELS, name + ".py")).body:
        if not isinstance(node, ast.FunctionDef):
            continue
        body = node.body
        if (isinstance(body[0], ast.Expr)
                and isinstance(body[0].value, ast.Constant)
                and isinstance(body[0].value.value, str)):
            body = body[1:]
        if len(body) == 1 and isinstance(body[0], ast.Return):
            call = body[0].value
            if (isinstance(call, ast.Call)
                    and isinstance(call.func, ast.Attribute)
                    and isinstance(call.func.value, ast.Name)
                    and call.func.value.id in HOMES):
                continue
        for inner in ast.walk(ast.Module(body=body, type_ignores=[])):
            if isinstance(inner, ast.arg):
                inner.annotation = None
            elif isinstance(inner, ast.FunctionDef):
                inner.returns = None
        out[node.name] = ast.dump(ast.Module(body=body, type_ignores=[]))
    return out


@pytest.mark.parametrize("name", MODEL_FILES)
def test_no_function_of_a_model_file_has_the_body_of_another_modules(name):
    """It failed on the tree before PR 59: `_group_counts` x 4, `_stack_init`
    x 3, `_sort_ops` x 3, `_layer_bytes` x 3, `_one_candidate_a_name` x 2,
    `_dense` / `_mlp` x 3, `decays` / `_is_buffer` / `param_count` x 2."""
    mine = _bodies(name)
    found = []
    for other in MODEL_FILES:
        if other == name:
            continue
        for theirs, body in _bodies(other).items():
            found += [
                f"models/{name}.py:{fn} = models/{other}.py:{theirs}"
                for fn, my_body in mine.items() if my_body == body
                and frozenset({(name, fn), (other, theirs)}) not in KEPT_ALIKE]
    assert not found, "\n".join(found)
