"""Whole-pipeline XLA fusion: collapse transformer chains into one dispatch.

The executor launches every transformer node as its own XLA dispatch with
a host round-trip between nodes. This module closes that gap at the
*plan* level:
:class:`NodeFusionRule` rewrites maximal chains of array-in/array-out
transformers (``BatchTransformer`` subclasses implementing
``apply_arrays``) into a single :class:`FusedTransformerOperator` whose
``apply_arrays`` composes the member functions inside ONE ``jax.jit`` —
so a k-node featurization chain costs one dispatch instead of k
dispatches + k host syncs, and every inter-member buffer lives entirely
inside the compiled computation where XLA frees/reuses it automatically
(the moral equivalent of donating each inter-node buffer; no buffer ever
returns to the host between members).

Fusion boundaries — nodes that always stay unfused:

- ``CacherOperator`` nodes: an auto-cache materialization point must stay
  a real node so its output is memoized/pinned (it is not a
  ``BatchTransformer``, so the type gate excludes it).
- Estimator fits and ``DelegatingOperator`` applications (fit-time
  control flow is host-side by design).
- Saveable-prefix cut points: any node in the optimizer's prefix map is
  about to have its result written to the process state table and must
  keep its own identity.
- Transformers that override ``apply``/``apply_batch`` with bespoke host
  behavior (e.g. ragged masked-descriptor encoders, sparse densifiers),
  or that set ``fusable = False`` (ops that manage their own sharding
  and dispatch, like the ring kernel mapper).

A fitted graph's gather of array branches is fused too (:func:`fuse_graph`
only): where one node or source feeds k >= 2 fusable branches whose only
consumer is a ``GatherTransformer``, and the gather's only consumer is a
``VectorCombiner``, the branches, the gather and the combiner become one
:class:`FusedGather`, which the chain pass then composes with what
follows (TIMIT's scoring: the cosine branches, their concatenation, the
mapper and the arg-max in one program). The optimizer's fit-time batch
does not do this: there a fused gather would make the estimator's input
a single chain, and ``StreamingPlanRule`` would move an in-core fit onto
the streamed fold, another solver path.

Ordering: fusion is the LAST optimizer batch — after auto-cache — so
cache decisions profile real node boundaries and remain byte-identical
to pre-fusion plans. ``Pipeline.fit`` applies the same rewrite to the
transformer-only fitted graph, so serving (``FittedPipeline.
compiled_apply`` + ``utils/aot.warm_buckets``) warms the *fused*
executable per shape bucket and keeps its zero-recompile-after-warmup
guarantee. See docs/OPTIMIZER.md.
"""

from __future__ import annotations

import logging
import threading
from contextlib import ExitStack, contextmanager
from typing import List, Optional, Sequence, Tuple

from ..envknobs import env_disabled
from ..obs import cost as _cost
from ..obs import names as _names
from .graph import Graph, NodeId, SinkId
from .operators import TransformerOperator
from .pipeline import BatchTransformer, feat_scope
from .rules import PrefixMap, Rule

logger = logging.getLogger(__name__)


# ------------------------------------------------------------------ enablement

# Tri-state: None → env default (on unless KEYSTONE_FUSION=off/0). Tests
# flip it with set_fusion_enabled / fusion_disabled to build unfused
# reference pipelines for parity checks.
_enabled: Optional[bool] = None
_enabled_lock = threading.Lock()


def fusion_enabled() -> bool:
    if _enabled is not None:
        return _enabled
    return not env_disabled("KEYSTONE_FUSION")


def set_fusion_enabled(value: Optional[bool]) -> None:
    """Force fusion on/off process-wide; ``None`` restores the env default."""
    global _enabled
    with _enabled_lock:
        _enabled = value


@contextmanager
def fusion_disabled():
    """Scoped off-switch (parity tests build the unfused reference here)."""
    global _enabled
    with _enabled_lock:
        prev = _enabled
        _enabled = False
    try:
        yield
    finally:
        with _enabled_lock:
            _enabled = prev


# ------------------------------------------------------------------- fusability


def _overrides(op, method: str) -> bool:
    return getattr(type(op), method, None) is not getattr(BatchTransformer, method)


def is_fusable(op) -> bool:
    """True when ``op``'s whole batch semantics are its ``apply_arrays``.

    Requires a ``BatchTransformer`` that (a) actually implements
    ``apply_arrays``, (b) does NOT override the generic ``apply`` /
    ``apply_batch`` wrappers (a bespoke override means the op does
    something the composed-array chain would silently skip — masked
    descriptors, sparse densification), and (c) has not opted out via
    ``fusable = False``.
    """
    if not isinstance(op, BatchTransformer):
        return False
    if not getattr(op, "fusable", True):
        return False
    if not _overrides(op, "apply_arrays"):
        return False
    if _overrides(op, "apply") or _overrides(op, "apply_batch"):
        return False
    return True


# ------------------------------------------------------------------ fused op


class FusedTransformerOperator(BatchTransformer):
    """One operator standing in for a chain of array transformers.

    ``apply_arrays`` composes the members' ``apply_arrays`` inside a
    single ``jax.jit``: one dispatch, one device round-trip, and every
    intermediate buffer stays device-side inside the compiled program
    (XLA aliases/frees them — none is ever materialized to a host-visible
    handle). The inherited :meth:`BatchTransformer.apply_batch` supplies
    the framework conventions exactly once for the whole chain (masked
    descriptors pass through, pad rows re-zeroed at the end — valid
    because ``apply_arrays`` is row-independent by contract, so
    once-at-the-end equals once-per-member).

    The jitted chain is built lazily (pickle-safe: the executable is
    dropped by ``__getstate__``) and increments
    ``keystone_fusion_compiles_total`` at trace time — once per new
    shape/dtype, never on cached executions — so the compilation-cache
    story covers fused executables too. The members' jax arrays (weights,
    feature banks) are ARGUMENTS of the program, not constants of it:
    chains of the same structure (member types, their other attributes,
    the arrays' shapes and dtypes) share one jitted callable through a
    bounded module cache, and one executable, which the persistent cache
    finds again for a model fitted anew. Every optimizer run of an
    unfitted pipeline builds a fresh FusedTransformerOperator, and a
    refit builds new weights: without sharing each would retrace and
    recompile the whole chain. A member that reads one of its arrays'
    VALUES while it is traced has the chain close over its members
    instead (the arrays constants again, one program per instance). If
    a member turns out not to be traceable at all, the chain falls back
    to eager composition
    (still one logical node, dispatch-fused no longer, logged once);
    runtime failures of the compiled chain (OOM, device errors)
    propagate — they are the caller's reliability layer's business, not
    a reason to silently unfuse.
    """

    _is_fused = True

    def __init__(self, members: Sequence[TransformerOperator]):
        flat: List[TransformerOperator] = []
        for m in members:
            # Re-fusing a fused node flattens instead of nesting.
            if isinstance(m, FusedTransformerOperator):
                flat.extend(m.members)
            else:
                flat.append(m)
        # A fused gather is several transformers already: alone it is
        # one compiled program too.
        if len(flat) < 2 and not isinstance(flat[0] if flat else None, FusedGather):
            raise ValueError("FusedTransformerOperator needs >= 2 members")
        self.members = tuple(flat)
        self._jitted = None
        self._eager_fallback = False
        self._lift = True

    @property
    def label(self) -> str:
        return "Fused[" + "+".join(self.member_labels) + "]"

    @property
    def member_labels(self) -> Tuple[str, ...]:
        return tuple(
            str(getattr(m, "label", type(m).__name__)) for m in self.members
        )

    def __getstate__(self):
        state = self.__dict__.copy()
        state["_jitted"] = None  # jitted callables don't pickle
        return state

    def host_span(self, dataset):
        """The members' own host spans, all around the one dispatch (none
        of a chain's members has one, as a rule: then nothing is opened)."""
        return _host_spans(self, self.members, dataset)

    def _chain(self, x):
        for m in self.members:
            with feat_scope(m):
                x = m.apply_arrays(x)
        return x

    def _compiled(self):
        """``(jitted, arrays)``: the shared program and this chain's
        arrays, its last argument."""
        if self._jitted is None:
            # (an operator pickled before the arrays were lifted has no `_lift`)
            self._jitted = _shared_chain_jit(self.members, getattr(self, "_lift", True))
        return self._jitted

    def _run(self, data):
        jitted, arrays = self._compiled()
        result = jitted(data, arrays)
        # Cost-observatory attribution (obs/cost.py): a single
        # thread-local read when no harvest frame is active (the
        # serving hot path); under an executor frame the fused
        # chain's flop/byte facts are harvested through the jit
        # trace cache at node finalize — zero extra compiles.
        _cost.note_jit_call("fused_chain", jitted, (data, arrays))
        return result

    def apply_arrays(self, data):
        if self._eager_fallback:
            return self._chain(data)
        lifted = getattr(self, "_lift", True)
        try:
            return self._run(data)
        except _trace_error_types() as e:
            error = e
        except ValueError as e:  # an argument on other devices than the input
            if not lifted:
                raise
            error = e
        if lifted:
            # A member may read one of its arrays' VALUES while it is
            # traced, or hold an array committed to other devices than
            # the input's: close over the members instead (one program
            # for this chain alone), latched only where that runs.
            _evict_chain_jit(self.members, lift=True)
            self._lift, self._jitted = False, None
            try:
                return self._run(data)
            except _trace_error_types():
                self._lift, self._jitted = True, None
        if isinstance(error, ValueError):
            raise error
        # A member that escaped the fusability gate (host-side value
        # branching, stale cached tracers) — degrade to the exact
        # eager semantics rather than failing the pipeline. ONLY
        # jax trace-construction failures land here: a runtime error
        # from the compiled chain (OOM, device fault, a TypeError
        # from a malformed payload) propagates so the reliability
        # layer sees it and the single-dispatch guarantee is never
        # silently dropped.
        value = self._chain(data)  # raises if the INPUT was the problem
        # The eager retry succeeded → the chain genuinely doesn't
        # trace; only now latch the fallback (a failing retry leaves
        # the operator fused for the next, valid batch). Evict the
        # shared jit too: the next fused operator built over these
        # same members must not fetch the known-broken callable and
        # pay the failing trace again.
        self._eager_fallback = True
        self._jitted = None
        _evict_chain_jit(self.members, lift=False)
        logger.warning(
            "fused chain %s not jit-traceable (%s: %s); falling back to "
            "eager member-by-member composition",
            self.label, type(error).__name__, str(error)[:200],
        )
        return value


def _host_spans(op, parts, dataset):
    own = [p for p in parts if type(p).host_span is not BatchTransformer.host_span]
    if not own:
        return BatchTransformer.host_span(op, dataset)
    stack = ExitStack()
    for p in own:
        stack.enter_context(p.host_span(dataset))
    return stack


class FusedGather(BatchTransformer):
    """k fusable branches over one input, gathered and concatenated: what
    ``GatherTransformer`` then ``VectorCombiner`` give, as one array
    transformer that a fused chain composes with its successors.

    ``apply_arrays`` applies each branch to the same input (a fused
    branch by its bare composition, so nothing nests a jit) and hands the
    outputs to the combiner's own ``apply_arrays``: each flattened per
    row, concatenated on the last axis, in branch order. Built by
    :func:`fuse_graph` only, and always inside a
    :class:`FusedTransformerOperator`, which compiles it."""

    def __init__(self, branches: Sequence[TransformerOperator], combiner):
        self.branches = tuple(branches)
        self.combiner = combiner

    @property
    def label(self) -> str:
        """``Gather[<branch>,...]``, a run of equal branches as ``<n>*<branch>``."""
        labels = [str(getattr(b, "label", type(b).__name__)) for b in self.branches]
        runs: List[List] = []
        for label in labels:
            if runs and runs[-1][1] == label:
                runs[-1][0] += 1
            else:
                runs.append([1, label])
        return "Gather[" + ",".join(
            label if n == 1 else f"{n}*{label}" for n, label in runs
        ) + "]"

    def host_span(self, dataset):
        return _host_spans(self, self.branches, dataset)

    def apply_arrays(self, x):
        outs = []
        for branch in self.branches:
            if isinstance(branch, FusedTransformerOperator):
                outs.append(branch._chain(x))
            else:
                with feat_scope(branch):
                    outs.append(branch.apply_arrays(x))
        with feat_scope(self.combiner):
            return self.combiner.apply_arrays(tuple(outs))


def _trace_error_types():
    import jax

    return (
        jax.errors.JAXTypeError,  # concretization / tracer-conversion
        jax.errors.UnexpectedTracerError,
    )


# One jitted callable per chain STRUCTURE, shared by every
# FusedTransformerOperator built over members of that structure: each
# optimizer run of an UNFITTED pipeline constructs a fresh fused operator,
# and each fit fresh weights, and a per-operator jit would retrace +
# recompile the identical chain on every apply. The members' jax arrays
# are the program's last argument (``_lifted``), so the key is what a
# trace can depend on besides their values: member types, their other
# attributes, the arrays' shapes and dtypes. A member kept whole (an
# attribute that does not hash, or the chain closed over after a member
# read an array's value) is keyed on its id, and its entry holds it, so
# the id is not reused while the entry lives. Bounded LRU for the same
# reason as linalg's ``_bcd_remat_fn`` cache: a whole member pins its
# weights, so retired chains must age out rather than accumulate — 32
# entries comfortably covers live pipelines. (ModelRegistry itself keeps
# every published version for rollback, so in serving processes the
# registry, not this cache, is what holds retired models.)
_CHAIN_JIT_CACHE: "OrderedDict[tuple, object]" = None  # type: ignore
_CHAIN_JIT_MAX = 32
_chain_cache_lock = threading.Lock()


def _lifted(m):
    """``(key, template, arrays)`` of one member: ``streaming._lift_member``'s
    (its jax arrays taken out, the rest keyed), and through the operators
    that a fused gather or a fused branch holds."""
    from .streaming import _lift_member

    if isinstance(m, FusedGather):
        keys, templates, arrays = zip(*(_lifted(b) for b in m.branches + (m.combiner,)))
        return (FusedGather, keys), FusedGather(templates[:-1], templates[-1]), arrays
    if isinstance(m, FusedTransformerOperator):
        keys, templates, arrays = zip(*(_lifted(x) for x in m.members))
        return (FusedTransformerOperator, keys), FusedTransformerOperator(templates), arrays
    return _lift_member(m)


def _bound(template, arrays):
    """``_lifted``'s inverse: the member to trace, its arrays put back."""
    from .streaming import _bind_chain

    if not arrays:  # kept whole, or nothing to put back
        return template
    if isinstance(template, FusedGather):
        ops = [_bound(t, a) for t, a in zip(template.branches + (template.combiner,), arrays)]
        return FusedGather(ops[:-1], ops[-1])
    if isinstance(template, FusedTransformerOperator):
        return FusedTransformerOperator(
            [_bound(t, a) for t, a in zip(template.members, arrays)]
        )
    return _bind_chain([template], [arrays])[0]


def _chain_parts(members: tuple, lift: bool):
    from .streaming import _whole_member

    parts = [(_lifted if lift else _whole_member)(m) for m in members]
    keys, templates, arrays = zip(*parts)
    return (lift,) + keys, templates, arrays


def _evict_chain_jit(members: tuple, lift: bool) -> None:
    key, _, _ = _chain_parts(members, lift)
    with _chain_cache_lock:
        if _CHAIN_JIT_CACHE is not None:
            _CHAIN_JIT_CACHE.pop(key, None)


def _shared_chain_jit(members: tuple, lift: bool = True):
    """``(jitted, arrays)``: the shared ``fused_chain(x, arrays)`` of this
    chain's structure and the arrays that are this chain's own."""
    global _CHAIN_JIT_CACHE
    import jax

    key, templates, arrays = _chain_parts(members, lift)
    with _chain_cache_lock:
        if _CHAIN_JIT_CACHE is None:
            from collections import OrderedDict

            _CHAIN_JIT_CACHE = OrderedDict()
        hit = _CHAIN_JIT_CACHE.get(key)
        if hit is not None:
            _CHAIN_JIT_CACHE.move_to_end(key)
            return hit, arrays

    compiles_c = _names.metric(_names.FUSION_COMPILES)

    def fused_chain(x, arrays):
        # Trace-time side effect: fires once per new shape/dtype, never
        # on cached executions — the fused-compile counter.
        compiles_c.inc()
        for t, own in zip(templates, arrays):
            m = _bound(t, own)
            with feat_scope(m):  # each member's operations keep its name
                x = m.apply_arrays(x)
        return x

    jitted = jax.jit(fused_chain)
    with _chain_cache_lock:
        _CHAIN_JIT_CACHE[key] = jitted
        _CHAIN_JIT_CACHE.move_to_end(key)
        while len(_CHAIN_JIT_CACHE) > _CHAIN_JIT_MAX:
            _CHAIN_JIT_CACHE.popitem(last=False)
    return jitted, arrays


# --------------------------------------------------------------------- the rule


class NodeFusionRule(Rule):
    """Rewrite maximal fusable chains into single fused nodes.

    A chain ``v1 → v2 → … → vk`` (k ≥ 2) qualifies when every member is
    fusable (:func:`is_fusable`), unary, outside the prefix map, and each
    interior member's ONLY consumer is its successor (a second consumer —
    node or sink — needs the intermediate value on the host side of the
    fused program, so the chain is cut there). The final member may fan
    out freely: its consumers are repointed at the fused node.
    """

    def apply(self, graph: Graph, prefixes: PrefixMap) -> Tuple[Graph, PrefixMap]:
        if not fusion_enabled():
            return graph, prefixes
        chains = _find_chains(graph, prefixes)
        if not chains:
            return graph, prefixes
        members_total = 0
        for chain in chains:
            graph = _fuse_chain(graph, chain)
            members_total += len(chain)
        _names.metric(_names.FUSION_CHAINS).inc(len(chains))
        _names.metric(_names.FUSION_FUSED_NODES).inc(members_total)
        _names.metric(_names.FUSION_DISPATCHES_SAVED).inc(
            members_total - len(chains)
        )
        return graph, prefixes


def _find_chains(graph: Graph, prefixes: PrefixMap) -> List[List[NodeId]]:
    dependents = graph.dependents()

    def fusable(node: NodeId) -> bool:
        return (
            node not in prefixes  # saveable-prefix cut point
            and len(graph.get_dependencies(node)) == 1
            and is_fusable(graph.get_operator(node))
        )

    def sole_successor(node: NodeId) -> Optional[NodeId]:
        deps = dependents.get(node, [])
        if len(deps) != 1 or isinstance(deps[0], SinkId):
            return None
        (succ,) = deps
        if fusable(succ) and graph.get_dependencies(succ) == (node,):
            return succ
        return None

    chains: List[List[NodeId]] = []
    consumed = set()
    for node in sorted(graph.nodes):
        if node in consumed or not fusable(node):
            continue
        # Only start at a chain head: a fusable predecessor would have
        # already absorbed this node.
        (dep,) = graph.get_dependencies(node)
        if (
            isinstance(dep, NodeId)
            and dep not in consumed
            and fusable(dep)
            and sole_successor(dep) == node
        ):
            continue
        chain = [node]
        nxt = sole_successor(node)
        while nxt is not None:
            chain.append(nxt)
            nxt = sole_successor(chain[-1])
        if len(chain) >= 2:
            chains.append(chain)
            consumed.update(chain)
    return chains


def _fuse_chain(graph: Graph, chain: List[NodeId]) -> Graph:
    ops = [graph.get_operator(n) for n in chain]
    deps0 = graph.get_dependencies(chain[0])
    graph, fused_node = graph.add_node(FusedTransformerOperator(ops), deps0)
    graph = graph.replace_dependency(chain[-1], fused_node)
    for node in reversed(chain):
        graph = graph.remove_node(node)
    return graph


def _branch(graph: Graph, dependents, prefixes: PrefixMap, end, consumer):
    """``(input, [nodes, input side first])``: the longest run of fusable
    unary nodes that ends in ``end``, each read by its successor alone
    (``end`` by ``consumer`` alone); None where ``end`` is no such node."""
    path: List[NodeId] = []
    cur = end
    while (
        isinstance(cur, NodeId)
        and cur not in prefixes
        and dependents[cur] == [consumer]
        and len(graph.get_dependencies(cur)) == 1
        and is_fusable(graph.get_operator(cur))
    ):
        path.append(cur)
        consumer, cur = cur, graph.get_dependencies(cur)[0]
    if not path:
        return None
    return cur, path[::-1]


def _fold_gathers(graph: Graph, prefixes: PrefixMap) -> Graph:
    """Each ``VectorCombiner`` over a ``GatherTransformer`` of k >= 2
    fusable branches of one input, replaced by one
    ``FusedTransformerOperator([FusedGather])`` on that input."""
    from ..ops.util.gather import GatherTransformer
    from ..ops.util.vectors import VectorCombiner

    folded = 0
    for node in sorted(graph.nodes):
        if node not in graph.operators:  # a branch of an earlier fold
            continue
        combiner = graph.get_operator(node)
        deps = graph.get_dependencies(node)
        if not (
            isinstance(combiner, VectorCombiner)
            and node not in prefixes
            and len(deps) == 1
            and isinstance(deps[0], NodeId)
            and isinstance(graph.get_operator(deps[0]), GatherTransformer)
            and deps[0] not in prefixes
        ):
            continue
        gather = deps[0]
        dependents = graph.dependents()
        ends = graph.get_dependencies(gather)
        if dependents[gather] != [node] or len(ends) < 2:
            continue
        branches = [_branch(graph, dependents, prefixes, end, gather) for end in ends]
        if any(b is None for b in branches) or len({b[0] for b in branches}) != 1:
            continue
        ops = []
        for _, path in branches:
            members = [graph.get_operator(n) for n in path]
            ops.append(members[0] if len(members) == 1 else FusedTransformerOperator(members))
        fused = FusedTransformerOperator([FusedGather(ops, combiner)])
        graph, fused_node = graph.add_node(fused, (branches[0][0],))
        graph = graph.replace_dependency(node, fused_node)
        graph = graph.remove_node(node).remove_node(gather)
        for _, path in branches:
            for n in reversed(path):
                graph = graph.remove_node(n)
        folded += 1
    if folded:
        _names.metric(_names.FUSION_GATHERS).inc(folded)
    return graph


def fuse_graph(graph: Graph, prefixes: Optional[PrefixMap] = None) -> Graph:
    """Fuse a FITTED (transformer-only) graph: its gathers of fusable
    branches into :class:`FusedGather`, then :class:`NodeFusionRule`'s
    chains (``Pipeline.fit`` fuses the fitted graph this way; the serving
    registry re-fuses artifacts saved unfused)."""
    prefixes = dict(prefixes or {})
    if fusion_enabled():
        graph = _fold_gathers(graph, prefixes)
    out, _ = NodeFusionRule().apply(graph, prefixes)
    return out
