"""What every model of ``ray_tpu.models`` runs its layers on, and nothing of
any one model: the layer loop over a PATTERN of layer kinds (a run of a repeated
sub-pattern is one ``lax.scan`` over the kinds' stacked parameters) and the
half of the remat rule that is about the STEP — the backward's phases, the
ONE choice of what ``remat=True`` keeps over all the kinds' applications, its
``model/remat_policy`` event and the policy-``checkpoint`` of each kind.

A model states its layers as KindShards (how often a kind is applied, what a
layer of it may keep, what its backward holds) and its own share of the step
as a shard: any tuple with ``batch``, ``seq``, ``d_model``, ``dtype_bytes``,
``vocab``, ``head_rows`` and ``mlp_rows`` (parts.BlockShard is one) and,
where the layers' carry is wider than ``d_model`` — a hyper-connected model's
n streams —, ``carry_width``: the stack of block inputs is priced at the
carry's width, the head and the embedding at ``d_model``. Nothing here reads
more of it: no mixer, no MLP is named in this file, and it imports no other
module of ``ray_tpu.models``.
"""

from __future__ import annotations

import math
import re
from typing import (Any, Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.tracing import get_buffer, names as scopes


class RematCandidate(NamedTuple):
    names: Tuple[str, ...]   # residuals kept together
    nbytes: int              # what they take, a layer
    flops: int               # what making them again costs, a layer
    frees: int = 0           # bytes of the step's working set that are there
                             # only while these are made again, not kept


class RematPolicy(NamedTuple):
    saved: Tuple[str, ...]   # names.RESIDUALS a block keeps, in the order taken
    saved_bytes: int         # what they take on a chip, over n_layer layers
    budget_bytes: int        # what was free for them, with what keeping them
                             # freed (0: no limit is known)
    bytes_limit: int         # the chip's own figure the budget came from, or 0


# what the rule leaves free: the benchmark's fit rule keeps the same
# (benchmarks/README.md), and the working-set arithmetic below is an estimate
REMAT_RESERVE_BYTES = 2 ** 30

Shard = Any     # the model's share of a step on one chip (module docstring)

_decisions: Dict[tuple, Dict[str, Any]] = {}
_patterns: Dict[str, Dict[str, Any]] = {}


class KindShard(NamedTuple):
    """What the remat rule needs of one kind of layer on one chip: how often
    the kind is applied, what a block of it may keep (parts.remat_candidates,
    or the kind's own arithmetic), what its whole residual set takes while its
    backward runs (parts.block_working_set), and the bytes of one application's
    weight gradients (backward_phases takes those not made yet off a run's
    phase; a model of one run of layers has none to take off and states 0).
    Under run_repeated ``applications`` counts every PASS's — a kept residual
    is copied once an application — while ``grad_bytes`` stays ONE layer's:
    the passes share the weights, and backward_phases is told the passes."""
    applications: int
    candidates: Tuple[RematCandidate, ...]
    block_bytes: int
    grad_bytes: int = 0


# a run of the layers as pattern_groups names it: the kinds of its repeated
# sub-pattern (keys of the model's KindShards) and the repeats
Run = Tuple[Sequence[str], int]


class Phase(NamedTuple):
    name: str                # "head", or the run as model/layer_pattern has it
    nbytes: int              # the fully rematted step's working set, in it


def run_name(run: Run) -> str:
    sub, reps = run
    return f"{reps} x scan({''.join(sub)})" if reps > 1 else "".join(sub)


def model_working_set(s: Shard, n_layer: int) -> int:
    """parts.rematted_working_set's part that no block decides: the stack of
    ``n_layer`` block inputs (applications, under run_repeated), the LM
    head's logits, the gathered embedding."""
    return n_layer * _block_input(s) + _head_terms(s) + _gathered(s)


def _block_input(s: Shard) -> int:
    """A layer's input as run_pattern carries it: ``d_model`` wide unless the
    shard states a wider carry."""
    width = getattr(s, "carry_width", 0) or s.d_model
    return s.batch * s.seq * width * s.dtype_bytes


def _passes(s: Shard) -> int:
    """How often the shard's model runs its layers on one set of weights
    (run_repeated): 1 unless the shard states ``passes``."""
    return getattr(s, "passes", 1) or 1


def _head_terms(s: Shard) -> int:
    a = s.dtype_bytes
    # (a looped model's ONE head call takes every pass's rows: its batch).
    # A chunked head's logits are dead once its forward scan ends — no
    # compiled peak of six cells moved when their chunks grew two- to
    # eightfold (PERF.md §6, PR 65) — so in the last run's phase these
    # bytes stand ABOVE what the compiler holds; no kept list turns on
    # them yet
    head = (_passes(s) * s.batch * (s.head_rows or s.seq) * s.vocab
            * (2 * a + 4))
    if s.head_rows:
        # a head in chunks makes its gradient in the forward and keeps it
        # (ops/cross_entropy.chunked_head_xent): d x stands where the chunked
        # x stood, the running float32 d lm_head is new
        head += s.d_model * s.vocab * 4
    return head


def _loop_terms(s: Shard, kinds: Dict[str, "KindShard"],
                runs: Sequence["Run"]) -> int:
    """What run_repeated's outer loop itself holds while its backward runs,
    0 for a model of one pass: the passes' loop-end states beside their
    cotangents (the head hands back all of them at once) and what the
    function between the passes keeps of its input (the un-normed x of a
    loop-end norm), and ONE pass's stacks of weight gradients — a pass's
    backward writes its own before they are added to the running sum, which
    the resident bytes count."""
    passes = _passes(s)
    if passes == 1:
        return 0
    states = 3 * passes * s.batch * s.seq * s.d_model * s.dtype_bytes
    return states + sum(reps * sum(kinds[k].grad_bytes for k in sub)
                        for sub, reps in runs)


def _gathered(s: Shard) -> int:
    return s.vocab * s.d_model * (s.dtype_bytes + 4)


def backward_phases(s: Shard, kinds: Dict[str, KindShard],
                    runs: Sequence[Run]) -> List[Phase]:
    """The fully rematted step's working set by the phases of its backward,
    in the order it meets them: the head's, then every run of the layers from
    the last to the first. The step needs the LARGEST of them, not their sum.
    In a run's phase stand the block inputs that still wait (its own and those of
    the runs before it), the largest block of the run's kinds, the gathered
    embedding, and — in the last run, whose backward starts on them — the
    head's terms; what does NOT stand there yet are the weight gradients of
    the runs before it, which the step's resident bytes count from the start.
    A run's own gradients all count: a scan writes its stacked gradients from
    the moment its backward starts. A model of one kind in one scan has one
    such phase, parts.rematted_working_set to the byte (its head phase is
    that less the block). The MEMEMEMEM*E + *E hybrid's head, MTP module
    and last five layers are dead by the time the scan of eight writes 1.7 GiB
    of gradients: summed, the estimate stood 2.5 GiB over the compiled step
    (PERF.md §6, PR 42).

    A model that runs ``runs`` several times on one set of weights
    (run_repeated; the shard states ``passes``) meets them LAYERS x PASSES
    times: the backward starts in the LAST pass, where every earlier pass's
    block inputs still wait beside its own, and the loop holds what
    _loop_terms says. Its weight gradients are the layers', not the
    applications': the running sum is resident from the start (nothing is
    taken off), one more pass's stacks stand beside it."""
    layers = [reps * len(sub) for sub, reps in runs]
    grads = [reps * sum(kinds[k].grad_bytes for k in sub) for sub, reps in runs]
    passes = _passes(s)
    earlier = loop = 0
    if passes > 1:
        earlier = (passes - 1) * sum(layers) * _block_input(s)
        loop = _loop_terms(s, kinds, runs)
        grads = [0] * len(runs)
    phases = [Phase("head", model_working_set(s, passes * sum(layers))
                    - sum(grads))]
    for i in reversed(range(len(runs))):
        live = (sum(layers[:i + 1]) * _block_input(s) + _gathered(s)
                + max(kinds[k].block_bytes for k in runs[i][0])
                - sum(grads[:i]) + earlier + loop)
        if i == len(runs) - 1:
            live += _head_terms(s)
        phases.append(Phase(run_name(runs[i]), live))
    return phases


def choose_remat_policy_kinds(kinds: Sequence[KindShard], working_set: int,
                              bytes_limit: Optional[int],
                              resident_bytes: int) -> RematPolicy:
    """THE rule for what ``remat=True`` keeps besides each block's input, for
    layers of any number of kinds: walk every kind's candidates (most
    recompute FLOPs per byte first) and take each whose copies — one an
    application of its kind — still fit what the chip has free: its
    bytes_limit less the reserve, what is resident (state and gradients) and
    the fully rematted step's ``working_set`` (the largest of
    backward_phases), plus what keeping it frees of that set. With no limit
    stated, nothing."""
    if bytes_limit is None:
        return RematPolicy((), 0, 0, 0)
    budget = bytes_limit - REMAT_RESERVE_BYTES - resident_bytes - working_set
    ranked = sorted(((c, k.applications) for k in kinds for c in k.candidates),
                    key=lambda cn: (-cn[0].flops / cn[0].nbytes, -cn[0].frees))
    saved, used = [], 0
    for c, n in ranked:
        if used + n * c.nbytes <= budget + c.frees:
            saved.extend(c.names)
            used += n * c.nbytes
            budget += c.frees
    return RematPolicy(tuple(saved), used, max(0, budget), bytes_limit)


def one_candidate_a_name(kinds: Dict[str, KindShard]) -> Dict[str, KindShard]:
    """``kinds`` with every set of names a candidate of ONE kind. Kinds that
    share halves share names, and a checkpoint policy keeps a NAME — in every
    layer that has it, whichever kind's candidate the rule took: an
    operator's ``block_mid`` may be in every kind, the routing's names in
    those with experts. Each shared set goes to the kind applied most, at the
    bytes and operations of all the layers that have it, spread over that
    kind's applications — so the rule takes or leaves it once, for what it
    really costs. For a model to call on its KindShards where its kinds
    share names; not part of choose_remat_policy_kinds (ROADMAP D25 (b))."""
    layers: Dict[Tuple[str, ...], Dict[str, RematCandidate]] = {}
    for kind, shard in kinds.items():
        for c in shard.candidates:
            layers.setdefault(c.names, {})[kind] = c
    kept: Dict[str, list] = {kind: [] for kind in kinds}
    for names, by_kind in layers.items():
        carrier = max(by_kind, key=lambda kind: kinds[kind].applications)
        # (bytes, operations, bytes freed) over all the layers that have the
        # names, an application of the carrier
        spread = [-(-sum(kinds[k].applications * c[field]
                         for k, c in by_kind.items())
                    // kinds[carrier].applications) for field in (1, 2, 3)]
        kept[carrier].append(RematCandidate(names, *spread))
    return {kind: shard._replace(candidates=tuple(kept[kind]))
            for kind, shard in kinds.items()}


def remat_policy_decisions() -> List[Dict[str, Any]]:
    """Every distinct remat decision this process has traced a model with, as
    the ``model/remat_policy`` events carry them."""
    return list(_decisions.values())


def compiler_rematerialized(hlo: str) -> List[str]:
    """The instructions of a compiled step (``compiled.as_text()``) that
    XLA's own rematerialization pass made: it clones what it frees early and
    marks the clone's name ``.remat``. Each is recompute the rule did not
    choose — the budget it spent was not there (PERF.md §6, PR 32)."""
    return re.findall(r"^\s*(?:ROOT )?%?(\S*\.remat\S*) = ", hlo, re.M)


def _remat_policy(shard: Shard, kinds: Dict[str, KindShard],
                  runs: Sequence[Run]) -> RematPolicy:
    """choose_remat_policy_kinds for the step being traced, recorded. A static
    choice has no hit rate; its counter is the choice: each distinct one goes
    once, as an instant event, to the task-event buffer
    (→ ``ray_tpu.timeline()``), with the phase of the backward that set the
    working set and its bytes. ``shard`` is the model's: the stream, the
    head and the rows the head and the MLP take at a time."""
    from ray_tpu.parallel import mesh as mesh_lib

    applications = sum(k.applications for k in kinds.values())
    passes = _passes(shard)
    n_layer = applications // passes
    phase = max(backward_phases(shard, kinds, runs), key=lambda p: p.nbytes)
    policy = choose_remat_policy_kinds(
        tuple(kinds.values()), phase.nbytes, *mesh_lib.current_chip_memory())
    args = dict(zip(scopes.REMAT_POLICY_ARGS,
                    (n_layer, shard.batch, shard.seq, list(policy.saved))
                    + policy[1:] + (shard.mlp_rows or shard.seq,
                                    shard.head_rows or shard.seq) + phase))
    if passes > 1:
        args.update(zip(scopes.REMAT_POLICY_LOOP_ARGS, (passes, applications)))
    key = (shard, tuple(kinds.items()), tuple(runs)) + policy
    if key not in _decisions:
        _decisions[key] = args
        component, name = scopes.REMAT_POLICY.split("/")
        get_buffer().record_profile(name, component=component, args=args)
    return policy


def checkpoint_kinds(block_fns: Dict[str, Callable], remat: bool,
                     shard: Shard, kinds: Dict[str, KindShard],
                     runs: Sequence[Run]) -> Dict[str, Callable]:
    """Each kind's ``block_fn(x, layer_params)`` as run_pattern calls it: a
    policy-``checkpoint`` that keeps the block's input and, of the named
    residuals (tracing/names.RESIDUALS), those the ONE rule gave room —
    over all the kinds' applications together, in the largest phase of the
    backward over ``runs`` (every run of the layers the step applies, in the
    forward's order) — with ``remat`` and all of them without."""
    saved = (_remat_policy(shard, kinds, runs).saved if remat
             else scopes.RESIDUALS)
    policy = jax.checkpoint_policies.save_only_these_names(*saved)
    return {kind: jax.checkpoint(fn, policy=policy)
            for kind, fn in block_fns.items()}


def pattern_groups(pattern: str) -> List[Tuple[str, int]]:
    """A pattern of layer kinds, one character a layer, as runs of a repeated
    sub-pattern: ``"MEMEMEMEM*E"`` → ``[("ME", 4), ("M", 1), ("*", 1),
    ("E", 1)]``, twelve layers of one kind → ``[("B", 12)]``. Greedy from the
    left: the repeat that covers most layers, of equal ones the shortest
    sub-pattern."""
    groups, i = [], 0
    while i < len(pattern):
        best = (pattern[i], 1)
        for width in range(1, (len(pattern) - i) // 2 + 1):
            sub, reps = pattern[i:i + width], 1
            while pattern.startswith(sub, i + reps * width):
                reps += 1
            if reps > 1 and reps * width > best[1] * len(best[0]):
                best = (sub, reps)
        groups.append(best)
        i += best[1] * len(best[0])
    return groups


def group_counts(pattern: str) -> List[Dict[str, int]]:
    """[{kind: layers of it}] a run of pattern_groups(pattern)."""
    return [{kind: reps * sub.count(kind) for kind in dict.fromkeys(sub)}
            for sub, reps in pattern_groups(pattern)]


def init_pattern(rng, pattern: str, kinds: str, layer_init: Callable):
    """The layers of ``pattern`` as run_pattern takes them: one entry a run of
    the pattern, a kind's layers of the run stacked in the order they come,
    each stack the model's ``layer_init(key, n, kind)``: ``n`` stacked layers
    of ``kind``. A run has a key of its own and in it every kind of ``kinds``
    (the model's kinds, in its own order) one: what a kind draws does not
    depend on which other kinds the run holds."""
    groups = group_counts(pattern)
    out = []
    for counts, group_key in zip(groups, jax.random.split(rng, len(groups))):
        keys = dict(zip(kinds, jax.random.split(group_key, len(kinds))))
        out.append({kind: layer_init(keys[kind], n, kind)
                    for kind, n in counts.items()})
    return out


def pattern_logical_axes(stack_init: Callable, layer_axes: Dict[str, Any]
                         ) -> Dict[str, Any]:
    """The logical axes of a pattern family's parameter tree with an untied
    head — an embedding, init_pattern's stacks (``stack_init(rng)``, nothing
    made), a final gain, a head —: each stacked tensor's by its name in
    ``layer_axes``."""
    layers = jax.eval_shape(lambda: stack_init(jax.random.PRNGKey(0)))
    return {"wte": ("vocab", "embed"),
            "blocks": [{kind: {name: layer_axes[name] for name in stack}
                        for kind, stack in group.items()} for group in layers],
            "final_norm": ("embed",), "lm_head": ("embed", "vocab")}


def with_grad_bytes(kinds: Dict[str, KindShard], layer_init: Callable,
                    mesh) -> Dict[str, KindShard]:
    """``kinds`` with each kind's ``grad_bytes``: the bytes of one layer of
    its parameters (init_pattern's ``layer_init``, nothing made), which its
    weight gradients take again, on one chip of ``mesh``. A chip holds no
    less than its even share: backward_phases takes gradients that do not
    exist yet OFF what is resident, so the least is the safe figure."""
    chips = mesh.devices.size if mesh is not None else 1

    def layer_bytes(kind):
        layer = jax.eval_shape(
            lambda: layer_init(jax.random.PRNGKey(0), 1, kind))
        return sum(math.prod(p.shape) * p.dtype.itemsize
                   for p in jax.tree.leaves(layer))

    return {kind: shard._replace(grad_bytes=layer_bytes(kind) // chips)
            for kind, shard in kinds.items()}


def run_pattern(block_fns: Dict[str, Callable], pattern: str, x,
                stacks: Sequence[Dict[str, Any]], with_aux: bool = False):
    """x through ``pattern``'s layers, one character a layer: kind ``c`` is
    ``block_fns[c](x, layer_params)``. ``stacks`` holds the parameters, one
    entry a run of pattern_groups(pattern): ``stacks[g][c]`` stacks the
    run's layers of kind ``c`` in the order they come. A run of a repeated
    sub-pattern is ONE ``lax.scan`` over its own stacks, whose body holds the
    sub-pattern's layers — compile time and program size follow the number of
    distinct runs, not the depth, and no stack is sliced or copied; a layer
    outside any repeat is applied where it stands.

    ``with_aux``: every block function returns ``(x, aux)`` and the result is
    ``(x, auxes)``, ``auxes[g][i]`` the aux of the i-th layer of run g's
    sub-pattern (stacked over the repeats where the run is a scan). The seam
    serves the training forward too: a checkpoint_kinds block may return an
    aux, and one of integers (a layer's counters) costs the backward nothing
    — a ``checkpoint``'s integer outputs and a scan's integer ``ys`` need no
    residual and have no cotangent. A float32 scalar a layer (aux_column: a
    term of the loss) rides the same seam and does have one."""
    auxes = []
    for (sub, reps), group in zip(pattern_groups(pattern), stacks, strict=True):
        per_rep = {kind: sub.count(kind) for kind in dict.fromkeys(sub)}
        xs = {kind: group[kind] if n == 1 else jax.tree.map(
            lambda a, n=n: a.reshape((reps, n) + a.shape[1:]), group[kind])
            for kind, n in per_rep.items()}

        def body(x, layer_params, sub=sub, per_rep=per_rep):
            seen = {kind: 0 for kind in per_rep}
            aux = []
            for kind in sub:
                p = layer_params[kind]
                if per_rep[kind] > 1:
                    p = jax.tree.map(lambda a: a[seen[kind]], p)
                seen[kind] += 1
                x = block_fns[kind](x, p)
                if with_aux:
                    x, a = x
                    aux.append(a)
            return x, (aux if with_aux else None)

        if reps > 1:
            x, aux = lax.scan(body, x, xs)
        else:
            x, aux = body(x, jax.tree.map(lambda a: a[0], xs))
        auxes.append(aux)
    return (x, auxes) if with_aux else x


_loops: Dict[tuple, Dict[str, Any]] = {}


def loop_decisions() -> List[Dict[str, Any]]:
    """Every distinct looped stack this process has traced a model with, as
    the ``model/loop`` events carry them."""
    return list(_loops.values())


def _record_loop(pattern: str, stacks, repeats: int, heads: str) -> None:
    """The ``model/loop`` event of run_repeated: once per distinct decision,
    at trace time. ``grad_stack_bytes`` is one float32 copy of the stacks'
    gradients on one chip of the mesh in use (its even share)."""
    from ray_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.current_mesh()
    chips = mesh.devices.size if mesh is not None else 1
    nbytes = sum(math.prod(a.shape) * 4 for a in jax.tree.leaves(stacks))
    args = dict(zip(scopes.LOOP_ARGS, (
        repeats, len(pattern), repeats * len(pattern), nbytes // chips, heads)))
    key = tuple(args.values())
    if key not in _loops:
        _loops[key] = args
        component, name = scopes.LOOP.split("/")
        get_buffer().record_profile(name, component=component, args=args)


def run_repeated(block_fns: Dict[str, Callable], pattern: str, x,
                 stacks: Sequence[Dict[str, Any]], repeats: int,
                 between: Callable, heads: str = ""):
    """x through ``pattern``'s layers ``repeats`` times over the SAME
    ``stacks`` (run_pattern's), ``between(x)`` after the last layer of every
    pass: what it returns is what that pass hands out AND what the next pass
    starts from. The result stacks the passes' states, ``[repeats, ...]``.

    ONE ``lax.scan`` over the passes around run_pattern's own: compile time
    and program size follow the distinct runs of ``pattern``, not repeats x
    depth. The stacks are the outer scan's constants, so AD's transpose
    carries ONE running sum of their gradients through the passes and adds
    each pass's stacks to it as the pass's backward ends — never ``repeats``
    stacks side by side. The block inputs a pass's scans keep are stacked
    over the passes in turn: repeats x layers of them wait (``KindShard.
    applications``; backward_phases is told the shard's ``passes``).
    ``heads`` is the model's word in the ``model/loop`` event for how its
    heads are called on the passes' states."""
    _record_loop(pattern, stacks, repeats, heads)
    record_layer_pattern(pattern, repeats)

    def one_pass(x, _):
        h = between(run_pattern(block_fns, pattern, x, stacks))
        return h, h

    _, states = lax.scan(one_pass, x, None, length=repeats)
    return states


class StepCounters(NamedTuple):
    """What a model's compiled step says of itself every step, as its module's
    ``step_counters(cfg)`` states it (None, or no such function: nothing):
    the step's ``metrics["counters"]`` is ONE int32 array [layers, fields] —
    packed_aux of the aux its loss hands out beside the loss — and the
    ``train/step_counters`` event a step (tracing/step_counters.py) is this,
    decoded."""
    kind: str                       # what a row is (names.EXPERT_LOAD_KIND)
    fields: Tuple[str, ...]         # the columns, in order
    layers: Tuple[int, ...]         # the rows: published ids of the layers
    static: Callable[[int], Dict[str, int]]   # a batch's tokens → what every
                                    # step's event says besides (sizes that
                                    # the numbers are read against)
    # the columns that are a float32's BITS (a loss term a layer: the array
    # stays one int32 output; whoever decodes it views these as float32)
    float_fields: Tuple[str, ...] = ()


def packed_aux(auxes: Sequence[Sequence[Any]], fields: Sequence[str],
               float_fields: Sequence[str] = ()):
    """run_pattern's ``auxes`` (several patterns' joined, in the order they
    ran) as one int32 array [layers with an aux, len(fields)], the layers in
    the order they are applied: a layer's aux is a dict of int32 scalars
    (stacked over a scan's repeats) or None. A field of ``float_fields`` is
    a float32 a layer and goes in as its bits, a constant to AD."""
    def column(a, f):
        if f not in float_fields:
            return a[f]
        return lax.bitcast_convert_type(
            lax.stop_gradient(a[f]).astype(jnp.float32), jnp.int32)

    rows = []
    for aux in auxes:
        have = [jnp.stack([column(a, f) for f in fields], axis=-1)
                for a in aux if a is not None]
        if have:        # [repeats, layers of the sub-pattern, fields], or one
            rows.append(jnp.stack(have, axis=-2).reshape(-1, len(fields)))
    return jnp.concatenate(rows).astype(jnp.int32)


def aux_column(auxes: Sequence[Sequence[Any]], field: str):
    """One field of run_pattern's ``auxes`` as an array [layers with an aux],
    in the order the layers are applied, in its own dtype and differentiable:
    how a float32 loss term a layer (a balance loss) leaves the layer loop —
    a scan's float ``ys`` — to be added to the loss inside what the step
    differentiates."""
    rows = []
    for aux in auxes:
        have = [a[field] for a in aux if a is not None]
        if have:        # [repeats, layers of the sub-pattern], or one a layer
            rows.append(jnp.stack(have, axis=-1).reshape(-1))
    return jnp.concatenate(rows)


def aux_by_layer(runs: Sequence[Run], auxes: Sequence[Sequence[Any]]) -> list:
    """run_pattern's ``auxes`` over ``runs`` (several patterns' joined, in the
    order they ran) as one entry a layer that has an aux, in the order the
    layers are applied: a scan's stacked aux taken apart by its repeats."""
    out = []
    for (sub, reps), aux in zip(runs, auxes, strict=True):
        for r in range(reps):
            out += [jax.tree.map(lambda t: t[r], a) if reps > 1 else a
                    for a in aux if a is not None]
    return out


def with_leaf(pattern: str, stacks: Sequence[Dict[str, Any]], name: str,
              rows) -> List[Dict[str, Any]]:
    """aux_by_layer's way back: ``stacks`` (run_pattern's, of ``pattern``)
    with the leaf ``name`` of every layer whose kind has one replaced by the
    next entry of the iterator ``rows`` — one a layer, in the order the
    layers are applied; several patterns draw from one iterator in turn —,
    in the old leaf's dtype and sharding."""
    out = []
    for (sub, reps), group in zip(pattern_groups(pattern), stacks, strict=True):
        new = {kind: [] for kind, stack in group.items() if name in stack}
        for kind in sub * reps:
            if kind in new:
                new[kind].append(next(rows))
        out.append({kind: stack if kind not in new else {
            **stack, name: jax.device_put(
                jnp.stack(new[kind]).astype(stack[name].dtype),
                stack[name].sharding)} for kind, stack in group.items()})
    return out


def record_layer_pattern(pattern: str, passes: int = 1) -> None:
    """The ``model/layer_pattern`` event of a model whose layers are of more
    than one kind, or run more than once (run_repeated: ``passes``, and the
    event then says them): the pattern of one pass, how often each kind is
    applied — over all the passes — and which runs are one scan; once per
    distinct pattern, at trace time."""
    key = pattern if passes == 1 else f"{passes} x ({pattern})"
    if key in _patterns:
        return
    groups = pattern_groups(pattern)
    _patterns[key] = dict(zip(scopes.LAYER_PATTERN_ARGS, (
        pattern, {kind: passes * pattern.count(kind)
                  for kind in dict.fromkeys(pattern)},
        [run_name(run) for run in groups])))
    if passes > 1:
        _patterns[key]["passes"] = passes
    component, name = scopes.LAYER_PATTERN.split("/")
    get_buffer().record_profile(name, component=component,
                                args=_patterns[key])


def layer_pattern_decisions() -> List[Dict[str, Any]]:
    """Every distinct pattern this process has traced a model with, as the
    ``model/layer_pattern`` events carry them."""
    return list(_patterns.values())


def run_blocks(block_fn, x, layers):
    """x through the blocks whose parameters are stacked in ``layers``: the
    one-kind case of run_pattern."""
    n_layer = jax.tree.leaves(layers)[0].shape[0]
    return run_pattern({"B": block_fn}, "B" * n_layer, x, [{"B": layers}])
