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
add a model, and PR 37 was refused on that cell)."""

import ast
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
