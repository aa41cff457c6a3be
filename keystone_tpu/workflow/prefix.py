"""Structural prefixes: cross-pipeline memoization keys.

A *prefix* is the operator tree feeding a node — a structural fingerprint
of "everything computed to produce this value". Two nodes in different
pipelines with equal prefixes computed the same thing, so the executor's
result for one can be spliced into the other
(reference: workflow/Prefix.scala:4-30, workflow/ExtractSaveablePrefixes.scala:9-22).

A prefix only exists when the node's ancestry contains no unbound sources
(a value depending on a free input is not a constant).
"""

from __future__ import annotations

import weakref
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from .graph import Graph, NodeId, NodeOrSourceId, SourceId


@dataclass(frozen=True)
class Prefix:
    """Hashable operator-tree fingerprint."""

    tree: Tuple  # nested (operator, (child trees...))

    def __repr__(self) -> str:
        return f"Prefix({hash(self.tree):#x})"


def find_prefix(graph: Graph, node: NodeOrSourceId) -> Optional[Prefix]:
    """Build the prefix of ``node``, or None if it depends on a source.

    Operators participate by object identity (the default ``Operator``
    hash/eq) or by value when an operator defines structural equality.
    """
    tree = _tree(graph, node)
    if tree is None:
        return None
    return Prefix(tree)


def _tree(graph: Graph, vid: NodeOrSourceId):
    if isinstance(vid, SourceId):
        return None
    op = graph.get_operator(vid)
    children = []
    for dep in graph.get_dependencies(vid):
        sub = _tree(graph, dep)
        if sub is None:
            return None
        children.append(sub)
    return (op, tuple(children))


def _anchor(op: Any) -> Any:
    """The object whose identity ``op`` brings to a prefix's equality: the
    dataset of a bound input, the datum of a bound datum (their operators
    are equal where these are the same object), else the operator itself."""
    from .operators import DatasetOperator, DatumOperator

    if isinstance(op, DatasetOperator):
        return op.dataset
    if isinstance(op, DatumOperator):
        return op.datum
    return op


def _weak_key(tree: Tuple, anchors: List[Any]) -> Tuple:
    """``tree`` with each operator replaced by its class and its anchor's
    id; the anchors are appended to ``anchors``. (A plain function: a
    recursive closure would be a reference cycle holding the anchors
    until the next garbage collection.)"""
    op, children = tree
    anchor = _anchor(op)
    anchors.append(anchor)
    return (type(op), id(anchor), tuple(_weak_key(c, anchors) for c in children))


class PrefixTable:
    """prefix -> the result computed for it, kept for as long as a later
    pipeline can still ask for it.

    A prefix is equal to another only where every operator in its tree
    is the other's by identity (or, for a bound input, binds the same
    dataset object). So once one of those objects is gone no graph can
    present the prefix again, and the entry, with the fitted estimator it
    pins (device memory: a kernel model is its training rows and duals),
    goes with it. The table therefore holds the tree's objects weakly and
    keys an entry by their ids; an object that cannot be referenced
    weakly (a datum that is a plain int or tuple) is held strongly, and
    its entries stay, as every entry did before the table had this rule.

    Results of one ``Pipeline`` applied twice, or of two pipelines over
    the same estimator object and data, are found as before: their
    objects are alive, held by the caller.
    """

    def __init__(self) -> None:
        self._entries: Dict[Tuple, Tuple[Any, List[Any]]] = {}

    @staticmethod
    def _key(prefix: Prefix) -> Tuple[Tuple, List[Any]]:
        anchors: List[Any] = []
        return _weak_key(prefix.tree, anchors), anchors

    def __setitem__(self, prefix: Prefix, expression: Any) -> None:
        key, anchors = self._key(prefix)

        def drop(_ref, key=key, entries=self._entries) -> None:
            entries.pop(key, None)

        held = []
        for anchor in anchors:
            try:
                held.append(weakref.ref(anchor, drop))
            except TypeError:  # not weakly referenceable: pinned, as before
                held.append(anchor)
        self._entries[key] = (expression, held)

    def __getitem__(self, prefix: Prefix) -> Any:
        return self._entries[self._key(prefix)[0]][0]

    def __contains__(self, prefix: Prefix) -> bool:
        return self._key(prefix)[0] in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        self._entries.clear()
