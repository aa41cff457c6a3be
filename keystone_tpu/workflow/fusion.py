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
    story covers fused executables too. Chains over the same member
    operator instances share one jitted callable through a bounded
    module cache: every optimizer run of an unfitted pipeline builds a
    fresh FusedTransformerOperator, and without sharing each apply would
    retrace + recompile the whole chain. If a member turns out not to be
    traceable after all, the chain falls back to eager composition
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
        if len(flat) < 2:
            raise ValueError("FusedTransformerOperator needs >= 2 members")
        self.members = tuple(flat)
        self._jitted = None
        self._eager_fallback = False

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
        own = [
            m for m in self.members
            if type(m).host_span is not BatchTransformer.host_span
        ]
        if not own:
            return BatchTransformer.host_span(self, dataset)
        stack = ExitStack()
        for m in own:
            stack.enter_context(m.host_span(dataset))
        return stack

    def _chain(self, x):
        for m in self.members:
            with feat_scope(m):
                x = m.apply_arrays(x)
        return x

    def _compiled(self):
        if self._jitted is None:
            self._jitted = _shared_chain_jit(self.members)
        return self._jitted

    def apply_arrays(self, data):
        if self._eager_fallback:
            return self._chain(data)
        try:
            jitted = self._compiled()
            result = jitted(data)
            # Cost-observatory attribution (obs/cost.py): a single
            # thread-local read when no harvest frame is active (the
            # serving hot path); under an executor frame the fused
            # chain's flop/byte facts are harvested through the jit
            # trace cache at node finalize — zero extra compiles.
            _cost.note_jit_call("fused_chain", jitted, (data,))
            return result
        except _trace_error_types() as e:
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
            _evict_chain_jit(self.members)
            logger.warning(
                "fused chain %s not jit-traceable (%s: %s); falling back to "
                "eager member-by-member composition",
                self.label, type(e).__name__, str(e)[:200],
            )
            return value


def _trace_error_types():
    import jax

    return (
        jax.errors.JAXTypeError,  # concretization / tracer-conversion
        jax.errors.UnexpectedTracerError,
    )


# One jitted callable per member-instance tuple, shared by every
# FusedTransformerOperator built over those instances: each optimizer run
# of an UNFITTED pipeline constructs a fresh fused operator, and a
# per-operator jit would retrace + recompile the identical chain on every
# apply. Keys are member ids; the cached value keeps strong refs to the
# members so ids can never be recycled while an entry lives. Bounded LRU
# for the same reason as linalg's ``_bcd_remat_fn`` cache: each entry
# pins a compiled executable AND its member operators (fitted weights),
# so retired chains must age out rather than accumulate — 32 entries
# comfortably covers live pipelines while bounding what eviction-lagged
# models can pin. (ModelRegistry itself keeps every published version
# for rollback, so in serving processes the registry, not this cache, is
# what holds retired models.)
_CHAIN_JIT_CACHE: "OrderedDict[Tuple[int, ...], Tuple[tuple, object]]" = None  # type: ignore
_CHAIN_JIT_MAX = 32
_chain_cache_lock = threading.Lock()


def _evict_chain_jit(members: tuple) -> None:
    with _chain_cache_lock:
        if _CHAIN_JIT_CACHE is not None:
            _CHAIN_JIT_CACHE.pop(tuple(id(m) for m in members), None)


def _shared_chain_jit(members: tuple):
    global _CHAIN_JIT_CACHE
    import jax

    key = tuple(id(m) for m in members)
    with _chain_cache_lock:
        if _CHAIN_JIT_CACHE is None:
            from collections import OrderedDict

            _CHAIN_JIT_CACHE = OrderedDict()
        hit = _CHAIN_JIT_CACHE.get(key)
        if hit is not None:
            _CHAIN_JIT_CACHE.move_to_end(key)
            return hit[1]

    compiles_c = _names.metric(_names.FUSION_COMPILES)

    def fused_chain(x):
        # Trace-time side effect: fires once per new shape/dtype, never
        # on cached executions — the fused-compile counter.
        compiles_c.inc()
        for m in members:
            with feat_scope(m):  # each member's operations keep its name
                x = m.apply_arrays(x)
        return x

    jitted = jax.jit(fused_chain)
    with _chain_cache_lock:
        _CHAIN_JIT_CACHE[key] = (members, jitted)
        _CHAIN_JIT_CACHE.move_to_end(key)
        while len(_CHAIN_JIT_CACHE) > _CHAIN_JIT_MAX:
            _CHAIN_JIT_CACHE.popitem(last=False)
    return jitted


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


def fuse_graph(graph: Graph, prefixes: Optional[PrefixMap] = None) -> Graph:
    """Apply :class:`NodeFusionRule` directly to a graph (``Pipeline.fit``
    fuses the transformer-only fitted graph this way; the serving
    registry re-fuses artifacts saved unfused)."""
    out, _ = NodeFusionRule().apply(graph, dict(prefixes or {}))
    return out
