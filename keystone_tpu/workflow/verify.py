"""Plan-time static verification: shapes, dtypes, and feasibility before
any data touches a device.

KeystoneML's signature move is reasoning about the whole pipeline before
executing it — the optimizer inspects the DAG to plan caching and
solvers. This module extends that plan-time reasoning to *correctness
and feasibility*: an abstract interpreter propagates
``jax.ShapeDtypeStruct`` specs through the (optimized) graph via
``jax.eval_shape`` — pure tracing, ZERO device execution and ZERO XLA
compiles — and emits :class:`Diagnostic`s with severities for the
failure classes that today only surface deep inside a jit trace at fit
time, or as a steady-state recompile in serving:

========  ========  ====================================================
code      severity  meaning
========  ========  ====================================================
KV101     error     shape/dtype mismatch at a node boundary
KV102     warning   silent float64 widening introduced by a node
KV201     info      fusion-ineligible node / chain cut, with the reason
KV202     info      streaming-ineligible estimator fit, with the reason
KV301     error     serving batch bucket not in the warmed bucket set
                    (the steady-state-recompile hazard)
KV302     warning   estimated peak bytes exceed the device memory budget
KV303     warning   Gram/sufficient-stat state for a streamed fit does
                    not fit the device memory budget
KV305     error     a refit-published candidate's apply spec or bucket
                    set disagrees with the incumbent's warmed buckets
                    (the steady-state-recompile hazard on the publish
                    path; :func:`verify_refit_publish`)
KV306     error     a persisted mid-stream resume entry's fingerprints
                    (dataset/labels content digest, featurize-chain
                    digest, featurized width/dtype) disagree with the
                    re-planned pipeline — seeding a fold from it would
                    silently corrupt the fit (:func:`verify_stream_resume`)
KV307     error     a serving boot image's environment fingerprints
                    (format version, jax version, backend, device kind,
                    weights digest) disagree with the loading worker's —
                    serving through its executables could return garbage;
                    the image is refused and the worker falls back to the
                    classic warm path (:func:`verify_boot_image`)
KV308     error     a streamed fit routed onto the sketched tier
                    (keystone_tpu/sketch) is infeasible or meaningless:
                    even the O(s·d) sketch state exceeds the device
                    memory budget (no further rung exists below the
                    sketch), or the sketch size fails the conditioning
                    heuristic (s below the label width / dual-solve
                    floor), so the sketched objective's error bound is
                    vacuous
KV401     error     dependency cycle in the graph
KV402     info      node not statically analyzable (no ``out_spec``,
                    not eval_shape-able) — propagation continues unknown
========  ========  ====================================================

(Lint-rule codes KV501-KV505 live in ``keystone_tpu/lint/rules.py``,
concurrency codes KV601-KV605 in ``keystone_tpu/lint/concurrency.py``;
all three tiers emit the shared :class:`keystone_tpu.diagnostics.
Diagnostic`, and docs/VERIFICATION.md documents the whole table.)

The ``out_spec`` protocol
-------------------------

Operators may define ``out_spec(in_specs)`` where ``in_specs`` is one
abstract value per graph dependency. For transformers the abstract
values are pytrees of ``jax.ShapeDtypeStruct``; the return value is the
output spec pytree. For estimators the return value is a
:class:`TransformerSpec` — the abstract value of the *fitted
transformer* edge, which the verifier later applies to the delegating
node's data specs. Raise :class:`SpecMismatch` for inputs the operator
cannot accept; return :data:`UNKNOWN` (or any part of it) where the
answer is data-dependent.

Operators without ``out_spec`` still verify when they are fusable
``BatchTransformer``s (``apply_arrays`` chains): the verifier falls back
to ``jax.eval_shape`` over ``apply_arrays``, so the whole fused serving
path is covered for free. See docs/VERIFICATION.md for the contract.

Entry points: :func:`verify_graph` / :func:`verify_pipeline` (the
``keystone-tpu check --pipeline`` engine), and :func:`verify_and_enforce`
— called from ``Pipeline.fit()`` and ``ModelRegistry.load_fitted``,
warn-by-default, ``KEYSTONE_VERIFY=strict`` to raise
:class:`VerificationError`, ``KEYSTONE_VERIFY=off`` to skip.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..diagnostics import ERROR, INFO, WARNING, Diagnostic
from ..envknobs import env_str
from ..obs import names as _names
from .analysis import GraphCycleError, linearize_whole
from .graph import Graph, GraphId, NodeId, SinkId, SourceId
from .operators import (
    DatasetOperator,
    DatumOperator,
    DelegatingOperator,
    EstimatorOperator,
    ExpressionOperator,
    Operator,
    TransformerOperator,
)

logger = logging.getLogger(__name__)

#: code → (default severity, short title). docs/VERIFICATION.md documents
#: every row; tests/workflow/test_verify.py enforces the sync.
CODES: Dict[str, Tuple[str, str]] = {
    "KV101": (ERROR, "shape/dtype mismatch at node boundary"),
    "KV102": (WARNING, "silent float64 widening"),
    "KV201": (INFO, "fusion-ineligible node"),
    "KV202": (INFO, "streaming-ineligible fit"),
    "KV203": (INFO, "sharding-ineligible fit"),
    "KV301": (ERROR, "serving bucket not warmed"),
    "KV302": (WARNING, "estimated peak memory exceeds budget"),
    "KV303": (WARNING, "streamed-fit Gram state exceeds memory budget"),
    "KV304": (ERROR, "sharded per-device residency exceeds memory budget"),
    "KV305": (ERROR, "refit candidate disagrees with incumbent warm state"),
    "KV306": (ERROR, "stale stream-resume entry refused"),
    "KV307": (ERROR, "stale boot image refused"),
    "KV308": (ERROR, "sketched-fit state infeasible or bound too weak"),
    "KV401": (ERROR, "dependency cycle"),
    "KV402": (INFO, "node not statically analyzable"),
}


class _Unknown:
    """Singleton abstract value: statically unknowable, propagates."""

    def __repr__(self) -> str:  # pragma: no cover - repr only
        return "UNKNOWN"


UNKNOWN = _Unknown()


class SpecMismatch(Exception):
    """Raised by ``out_spec``/``apply_spec`` when an input spec is one
    the operator can never accept (wrong rank, wrong width, row-count
    disagreement). Becomes a KV101 error diagnostic."""


@dataclass
class NodeAnnotation:
    """Per-node result of spec propagation: what the verifier believes
    flows out of this node, and roughly how many bytes it holds."""

    node: str
    label: str
    spec: str
    est_bytes: Optional[int] = None

    def to_json(self) -> Dict[str, Any]:
        return {
            "node": self.node,
            "label": self.label,
            "spec": self.spec,
            "est_bytes": self.est_bytes,
        }


@dataclass
class VerifyReport:
    diagnostics: List[Diagnostic] = field(default_factory=list)
    annotations: List[NodeAnnotation] = field(default_factory=list)
    seconds: float = 0.0
    context: str = ""
    #: Per-fit partition decisions the verifier derived (mesh shape, row
    #: PartitionSpec, eligibility/fallback reason) — the explainable
    #: sharding plan ``keystone-tpu check --pipeline --json`` surfaces.
    partition: List[Dict[str, Any]] = field(default_factory=list)

    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == ERROR]

    def warnings(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == WARNING]

    @property
    def ok(self) -> bool:
        return not self.errors()

    def by_code(self, code: str) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.code == code]

    def to_json(self) -> Dict[str, Any]:
        out = {
            "context": self.context,
            "ok": self.ok,
            "seconds": round(self.seconds, 4),
            "diagnostics": [d.to_json() for d in self.diagnostics],
            "nodes": [a.to_json() for a in self.annotations],
        }
        if self.partition:
            out["partition"] = self.partition
        return out

    def render(self) -> str:
        lines = [
            f"verify[{self.context}]: {len(self.annotations)} nodes, "
            f"{len(self.errors())} errors, {len(self.warnings())} warnings, "
            f"{len(self.diagnostics)} diagnostics, {self.seconds * 1e3:.1f} ms"
        ]
        lines += [d.render() for d in self.diagnostics]
        return "\n".join(lines)


class VerificationError(RuntimeError):
    """Strict-mode failure: plan-time verification found errors."""

    def __init__(self, report: VerifyReport):
        self.report = report
        errors = "; ".join(d.render() for d in report.errors())
        super().__init__(
            f"plan-time verification failed ({report.context}): {errors} "
            "— set KEYSTONE_VERIFY=warn to downgrade, see docs/VERIFICATION.md"
        )


# ------------------------------------------------------------ abstract values


class TransformerSpec:
    """Abstract value of a fitted-transformer edge (an estimator node's
    output): maps apply-time input specs to output specs.

    ``fn(data_spec) -> out_spec`` may raise :class:`SpecMismatch`; pass
    ``fn=None`` for a fitted transformer whose apply shape is
    data-dependent (the verifier then propagates :data:`UNKNOWN`).
    """

    def __init__(self, fn: Optional[Callable[[Any], Any]] = None, label: str = ""):
        self._fn = fn
        self.label = label

    def apply_spec(self, data_spec: Any) -> Any:
        if self._fn is None:
            return UNKNOWN
        return self._fn(data_spec)

    def __repr__(self) -> str:  # pragma: no cover - repr only
        return f"TransformerSpec[{self.label or 'unknown'}]"


def _leaves(spec: Any) -> List[Any]:
    """ShapeDtypeStruct-ish leaves of an abstract value (empty for
    UNKNOWN / TransformerSpec)."""
    if spec is UNKNOWN or spec is None or isinstance(spec, TransformerSpec):
        return []
    import jax

    return [
        leaf
        for leaf in jax.tree_util.tree_leaves(spec)
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
    ]


def spec_bytes(spec: Any) -> Optional[int]:
    """Estimated bytes of an abstract value (None when unknown)."""
    leaves = _leaves(spec)
    if not leaves:
        return None
    total = 0
    import numpy as np

    for leaf in leaves:
        size = 1
        for dim in leaf.shape:
            size *= int(dim)
        total += size * np.dtype(leaf.dtype).itemsize
    return total


def _render_spec(spec: Any) -> str:
    if spec is UNKNOWN:
        return "unknown"
    if isinstance(spec, TransformerSpec):
        return repr(spec)
    leaves = _leaves(spec)
    if not leaves:
        return "unknown"
    return ", ".join(
        f"{tuple(int(d) for d in leaf.shape)}:{leaf.dtype}" for leaf in leaves
    )


def _single_matrix(spec: Any) -> Optional[Any]:
    """The single rank>=1 array leaf of a spec, or None when the spec is
    unknown / not a single array."""
    leaves = _leaves(spec)
    if len(leaves) != 1:
        return None
    return leaves[0]


def _rows(spec: Any) -> Optional[int]:
    leaf = _single_matrix(spec)
    if leaf is None or not leaf.shape:
        return None
    return int(leaf.shape[0])


def _width(spec: Any) -> Optional[int]:
    leaf = _single_matrix(spec)
    if leaf is None or len(leaf.shape) < 2:
        return None
    return int(leaf.shape[-1])


def _result_dtype(*specs: Any):
    """float64 if any input leaf (or bare dtype argument) is float64,
    else float32 — the dtype discipline of the solver layer (everything
    is cast to f32 unless the caller explicitly trafficks in f64)."""
    import numpy as np

    for spec in specs:
        if isinstance(spec, np.dtype):
            if spec == np.float64:
                return np.dtype(np.float64)
            continue
        for leaf in _leaves(spec):
            if np.dtype(leaf.dtype) == np.float64:
                return np.dtype(np.float64)
    return np.dtype(np.float32)


# ------------------------------------------------- out_spec helpers (for ops)


def dense_fit_spec(
    in_specs: Sequence[Any],
    label: str,
    out_width: Optional[int] = None,
) -> TransformerSpec:
    """Shared ``out_spec`` for estimators that fit a row-matrix into a
    dense map ``(m, d) -> (m, k)``.

    ``in_specs[0]`` is the feature spec (n, d); ``in_specs[1]`` (when
    present) the labels. ``out_width`` fixes k (num_classes, dims);
    ``None`` takes k from the labels' width (1 for rank-1 labels).
    Validates what is statically knowable — feature rank, train-time row
    agreement between features and labels, apply-time width agreement —
    and leaves the rest unknown.
    """
    import jax

    x = _single_matrix(in_specs[0]) if in_specs else None
    y_spec = in_specs[1] if len(in_specs) > 1 else None
    d = None
    dtype = _result_dtype(*in_specs)
    if x is not None:
        if len(x.shape) != 2:
            raise SpecMismatch(
                f"{label}: features must be a rank-2 (rows, features) "
                f"matrix, got shape {tuple(x.shape)}"
            )
        d = int(x.shape[1])
        n = int(x.shape[0])
        y = _single_matrix(y_spec) if y_spec is not None else None
        if y is not None and y.shape and int(y.shape[0]) != n:
            raise SpecMismatch(
                f"{label}: features have {n} rows but labels have "
                f"{int(y.shape[0])} rows"
            )
    k = out_width
    if k is None and y_spec is not None:
        y = _single_matrix(y_spec)
        if y is not None:
            k = int(y.shape[1]) if len(y.shape) >= 2 else 1

    def apply_fn(data_spec: Any) -> Any:
        leaf = _single_matrix(data_spec)
        if leaf is None:
            return UNKNOWN
        if len(leaf.shape) < 2:
            raise SpecMismatch(
                f"{label}: fitted map expects rank-2 input, got shape "
                f"{tuple(leaf.shape)}"
            )
        if d is not None and int(leaf.shape[-1]) != d:
            raise SpecMismatch(
                f"{label}: fitted on {d}-wide features but applied to "
                f"{int(leaf.shape[-1])}-wide input"
            )
        if k is None:
            return UNKNOWN
        out_shape = tuple(leaf.shape[:-1]) + (k,)
        return jax.ShapeDtypeStruct(out_shape, _result_dtype(data_spec, dtype))

    return TransformerSpec(apply_fn, label=f"{label}(d={d},k={k})")


def projection_fit_spec(
    in_specs: Sequence[Any], label: str, dims: int
) -> TransformerSpec:
    """``out_spec`` for projection estimators (PCA families): the fitted
    transformer replaces the LAST axis (descriptor width d) with
    ``dims``, preserving leading axes — covers both flat (m, d) rows and
    (m, cols, d) descriptor stacks."""
    import jax

    x = _single_matrix(in_specs[0]) if in_specs else None
    d = int(x.shape[-1]) if x is not None and len(x.shape) >= 2 else None

    def apply_fn(data_spec: Any) -> Any:
        leaf = _single_matrix(data_spec)
        if leaf is None:
            return UNKNOWN
        if len(leaf.shape) < 2:
            raise SpecMismatch(
                f"{label}: projection expects rank>=2 input, got shape "
                f"{tuple(leaf.shape)}"
            )
        if d is not None and int(leaf.shape[-1]) != d:
            raise SpecMismatch(
                f"{label}: fitted on {d}-wide descriptors but applied to "
                f"{int(leaf.shape[-1])}-wide input"
            )
        out_shape = tuple(leaf.shape[:-1]) + (int(dims),)
        return jax.ShapeDtypeStruct(out_shape, _result_dtype(data_spec))

    return TransformerSpec(apply_fn, label=f"{label}(d={d},dims={dims})")


def elementwise_fit_spec(in_specs: Sequence[Any], label: str) -> TransformerSpec:
    """``out_spec`` for estimators whose fitted transformer preserves the
    input spec exactly (scalers, whiteners): shape and dtype pass
    through, width checked against the training width when both are
    known."""
    x = _single_matrix(in_specs[0]) if in_specs else None
    d = int(x.shape[-1]) if x is not None and len(x.shape) >= 2 else None

    def apply_fn(data_spec: Any) -> Any:
        leaf = _single_matrix(data_spec)
        if leaf is None:
            return UNKNOWN
        if d is not None and len(leaf.shape) >= 2 and int(leaf.shape[-1]) != d:
            raise SpecMismatch(
                f"{label}: fitted on {d}-wide input but applied to "
                f"{int(leaf.shape[-1])}-wide input"
            )
        return data_spec

    return TransformerSpec(apply_fn, label=f"{label}(d={d})")


# ------------------------------------------------------------ the interpreter


def _dataset_spec(dataset: Any, probe_objects: bool) -> Any:
    """Spec of a bound dataset — shapes/dtypes read off host metadata,
    never moving data. ObjectDatasets decode one item to learn the
    per-item shape only when ``probe_objects`` (the CLI path; the
    fit-hook path stays zero-cost)."""
    import jax
    import numpy as np

    from ..data.dataset import ArrayDataset, ObjectDataset

    if isinstance(dataset, ArrayDataset):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                tuple(int(d) for d in np.shape(a)),
                np.dtype(getattr(a, "dtype", np.float32)),
            ),
            dataset.data,
        )
    if isinstance(dataset, ObjectDataset) and probe_objects and len(dataset):
        first = dataset.take(1)[0]
        n = len(dataset)
        return jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(
                (n,) + tuple(np.asarray(leaf).shape), np.asarray(leaf).dtype
            ),
            first,
        )
    return UNKNOWN


def _datum_spec(datum: Any) -> Any:
    import jax
    import numpy as np

    if hasattr(datum, "shape") and hasattr(datum, "dtype"):
        return jax.ShapeDtypeStruct(
            tuple(int(d) for d in datum.shape), np.dtype(datum.dtype)
        )
    return UNKNOWN


def _eval_shape_apply(op: Any, in_spec: Any) -> Any:
    """eval_shape over ``apply_arrays``, honoring the masked-descriptor
    dict convention ({"desc": ..., "valid": ...}) the batch path uses."""
    import jax

    if (
        isinstance(in_spec, dict)
        and "desc" in in_spec
        and "valid" in in_spec
    ):
        out = jax.eval_shape(op.apply_arrays, in_spec["desc"])
        return {"desc": out, "valid": in_spec["valid"]}
    return jax.eval_shape(op.apply_arrays, in_spec)


class _Interpreter:
    def __init__(
        self,
        graph: Graph,
        diagnostics: List[Diagnostic],
        probe_objects: bool,
    ):
        self.graph = graph
        self.diagnostics = diagnostics
        self.probe_objects = probe_objects
        self.specs: Dict[GraphId, Any] = {}

    def diag(self, code: str, message: str, node=None, **details) -> None:
        severity, _title = CODES[code]
        self.diagnostics.append(
            Diagnostic(
                code=code,
                severity=severity,
                message=message,
                node=None if node is None else repr(node),
                details=details,
            )
        )

    # ---------------------------------------------------------------- nodes
    def node_out_spec(self, node: NodeId, op: Operator, in_specs: List[Any]) -> Any:
        from ..ops.util.misc import CacherOperator
        from .fusion import FusedTransformerOperator, is_fusable
        from .pipeline import Identity
        from .streaming import StreamingFitOperator

        label = str(getattr(op, "label", type(op).__name__))

        # Explicit protocol wins — it can see what tracing can't (what a
        # fit will produce).
        out_spec = getattr(op, "out_spec", None)
        if callable(out_spec):
            try:
                return out_spec(in_specs)
            except SpecMismatch as e:
                self.diag("KV101", str(e), node=node, op=label)
                return UNKNOWN
            except Exception as e:  # a broken out_spec must not kill planning
                self.diag(
                    "KV402",
                    f"{label}: out_spec failed ({type(e).__name__}: {e})",
                    node=node,
                    op=label,
                )
                return UNKNOWN

        if isinstance(op, DatasetOperator):
            return _dataset_spec(op.dataset, self.probe_objects)
        if isinstance(op, DatumOperator):
            return _datum_spec(op.datum)
        if isinstance(op, ExpressionOperator):
            # A spliced already-computed expression: if it has been
            # forced, read the value's metadata; otherwise unknown.
            value = getattr(op.expression, "_value", None)
            if value is not None and hasattr(value, "data"):
                return _dataset_spec(value, self.probe_objects)
            return UNKNOWN
        if isinstance(op, (CacherOperator, Identity)):
            return in_specs[0] if in_specs else UNKNOWN

        if isinstance(op, DelegatingOperator):
            transformer = in_specs[0] if in_specs else UNKNOWN
            data = in_specs[1] if len(in_specs) > 1 else UNKNOWN
            if isinstance(transformer, TransformerSpec):
                try:
                    return transformer.apply_spec(data)
                except SpecMismatch as e:
                    self.diag("KV101", str(e), node=node, op=label)
                    return UNKNOWN
            return UNKNOWN

        if isinstance(op, StreamingFitOperator):
            return self._streaming_fit_spec(node, op, in_specs)

        if isinstance(op, EstimatorOperator):
            self.diag(
                "KV402",
                f"{label}: estimator has no out_spec — fitted-transformer "
                "shape unknown at plan time (docs/VERIFICATION.md "
                "documents the protocol)",
                node=node,
                op=label,
            )
            return TransformerSpec(None, label=label)

        if isinstance(op, FusedTransformerOperator) or (
            isinstance(op, TransformerOperator) and is_fusable(op)
        ):
            in_spec = in_specs[0] if in_specs else UNKNOWN
            if not _leaves(in_spec):
                return UNKNOWN
            try:
                return _eval_shape_apply(op, in_spec)
            except Exception as e:
                self.diag(
                    "KV101",
                    f"{label}: apply_arrays rejects input "
                    f"{_render_spec(in_spec)} ({type(e).__name__}: "
                    f"{str(e)[:300]})",
                    node=node,
                    op=label,
                )
                return UNKNOWN

        self.diag(
            "KV402",
            f"{label}: no out_spec and not an eval_shape-able "
            "apply_arrays transformer",
            node=node,
            op=label,
        )
        return UNKNOWN

    def _streaming_fit_spec(
        self, node: NodeId, op: Any, in_specs: List[Any]
    ) -> Any:
        """A StreamingFitOperator: featurized spec = chain over the raw
        data spec; the wrapped estimator's out_spec (when present) then
        gives the fitted-transformer edge. Also records the featurized
        width for the Gram-feasibility check."""
        label = str(getattr(op, "label", type(op).__name__))
        data_spec = in_specs[0] if in_specs else UNKNOWN
        feat_spec = data_spec
        if _leaves(data_spec) and op.members:
            import jax

            try:
                # Cast-to-float first, like the real chunk step.
                def chain(x):
                    import jax.numpy as jnp

                    def cast(a):
                        if jnp.issubdtype(a.dtype, jnp.floating):
                            return a
                        return a.astype(jnp.float32)

                    x = jax.tree_util.tree_map(cast, x)
                    for m in op.members:
                        x = m.apply_arrays(x)
                    return x

                feat_spec = jax.eval_shape(chain, data_spec)
            except Exception as e:
                self.diag(
                    "KV101",
                    f"{label}: featurize chain rejects input "
                    f"{_render_spec(data_spec)} ({type(e).__name__}: "
                    f"{str(e)[:300]})",
                    node=node,
                    op=label,
                )
                feat_spec = UNKNOWN
        self.specs[("feat", node)] = feat_spec  # side-channel for gram check
        est_out_spec = getattr(op.estimator, "out_spec", None)
        if callable(est_out_spec):
            try:
                return est_out_spec([feat_spec] + list(in_specs[1:]))
            except SpecMismatch as e:
                self.diag("KV101", str(e), node=node, op=label)
                return UNKNOWN
            except Exception as e:
                self.diag(
                    "KV402",
                    f"{label}: estimator out_spec failed "
                    f"({type(e).__name__}: {e})",
                    node=node,
                    op=label,
                )
                return UNKNOWN
        return TransformerSpec(None, label=label)


# ----------------------------------------------------------- eligibility scan


def _fusion_diagnostics(graph: Graph, interp: _Interpreter) -> None:
    """Why is each transformer not (or no longer) fusable? Mirrors the
    NodeFusionRule gates so the reasons are the rule's reasons."""
    from ..ops.util.misc import CacherOperator
    from .fusion import FusedTransformerOperator, _overrides, is_fusable
    from .pipeline import BatchTransformer

    dependents = graph.dependents()
    for node in sorted(graph.nodes):
        op = graph.get_operator(node)
        label = str(getattr(op, "label", type(op).__name__))
        if isinstance(op, FusedTransformerOperator):
            continue
        if isinstance(op, CacherOperator):
            interp.diag(
                "KV201",
                f"{label}: Cacher boundary — chains never fuse across a "
                "cache materialization point",
                node=node,
                reason="cacher-boundary",
            )
            continue
        if not isinstance(op, BatchTransformer):
            continue
        if is_fusable(op):
            consumers = dependents.get(node, [])
            node_consumers = [c for c in consumers if isinstance(c, NodeId)]
            if len(consumers) > 1 and node_consumers:
                interp.diag(
                    "KV201",
                    f"{label}: multi-consumer interior — {len(consumers)} "
                    "consumers need this value host-side, so a fused chain "
                    "is cut here",
                    node=node,
                    reason="multi-consumer",
                )
            continue
        if not getattr(op, "fusable", True):
            reason = "opted out (fusable=False — op manages its own dispatch)"
            key = "opt-out"
        elif not _overrides(op, "apply_arrays"):
            reason = "does not implement apply_arrays"
            key = "no-apply-arrays"
        else:
            reason = (
                "bespoke apply/apply_batch override — whole-batch semantics "
                "are not its apply_arrays"
            )
            key = "bespoke-apply"
        interp.diag(
            "KV201",
            f"{label}: not fusable — {reason}",
            node=node,
            reason=key,
        )


def _streaming_diagnostics(
    graph: Graph, interp: _Interpreter, memory_limit: Optional[int]
) -> None:
    from .streaming import (
        StreamingFitOperator,
        stream_chunk_rows,
        stream_min_rows,
    )

    floor = max(2 * stream_chunk_rows(), stream_min_rows())
    for node in sorted(graph.nodes):
        op = graph.get_operator(node)
        label = str(getattr(op, "label", type(op).__name__))
        if isinstance(op, StreamingFitOperator):
            if _plan_state_kind(interp, node, op) == "sketch":
                _sketch_feasibility(graph, interp, node, op, memory_limit)
            else:
                _gram_feasibility(graph, interp, node, op, memory_limit)
            continue
        if not isinstance(op, EstimatorOperator):
            continue
        if not getattr(op, "supports_fit_stream", False):
            interp.diag(
                "KV202",
                f"{label}: estimator does not implement fit_stream — fit "
                "materializes the full feature matrix",
                node=node,
                reason="no-fit-stream",
            )
            continue
        # Supports streaming but was not rewritten: explain with the
        # planner's own gates.
        deps = graph.get_dependencies(node)
        head = deps[0] if deps else None
        reason, key = "no chunkable bound dataset upstream", "no-bound-data"
        if isinstance(head, NodeId):
            head_op = graph.get_operator(head)
            if isinstance(head_op, DatasetOperator):
                try:
                    n = len(head_op.dataset)
                except Exception:
                    n = -1
                if 0 <= n < floor:
                    reason = (
                        f"dataset holds {n} rows, below the streaming floor "
                        f"{floor} (max(2*chunk_rows, KEYSTONE_STREAM_MIN_ROWS))"
                    )
                    key = "below-row-floor"
        interp.diag(
            "KV202",
            f"{label}: fit_stream-capable but not planned onto the "
            f"streaming engine — {reason}",
            node=node,
            reason=key,
        )


def _partition_diagnostics(
    graph: Graph,
    interp: _Interpreter,
    memory_limit: Optional[int],
    report: VerifyReport,
) -> None:
    """The partitioner's own view of every fit in the plan, re-derived
    (never re-recorded — the last plan's report and metrics stay
    untouched): KV203 explains a single-device fallback with the
    partitioner's reason key; KV304 errors when an ELIGIBLE sharded plan
    still cannot fit its per-device slice next to the replicated O(d²)
    statistics — sharding divides the rows, not the Gram."""
    from ..parallel.partitioner import Partitioner
    from .streaming import StreamingFitOperator, stream_chunk_rows

    part = Partitioner()
    for node in sorted(graph.nodes):
        op = graph.get_operator(node)
        if not isinstance(op, EstimatorOperator):
            continue
        label = str(getattr(op, "label", type(op).__name__))
        deps = graph.get_dependencies(node)
        in_spec = interp.specs.get(deps[0], UNKNOWN) if deps else UNKNOWN
        rows = _rows(in_spec)
        streaming = isinstance(op, StreamingFitOperator)
        pinned = getattr(op, "partition", None)
        if pinned is not None:
            # Post-optimizer graphs carry the plan's own decision both
            # ways (eligible or recorded fallback) — report THAT, never
            # a re-derivation that could disagree with the runtime.
            decision = pinned
        else:
            target = op.estimator if streaming else op
            opt_out = getattr(target, "partitionable", True) is False
            # Same inputs the plan rule feeds the partitioner: the raw
            # upstream width as the featurized-width proxy, and the
            # estimator's 2-D protocol opt-in.
            model_ok = getattr(target, "supports_model_axis", False)
            width = _width(in_spec)
            if streaming:
                decision = part.decide_stream(
                    label, op.chunk_rows or stream_chunk_rows(), rows=rows,
                    record=False, opt_out=opt_out,
                    width=width, model_ok=model_ok,
                )
            else:
                decision = part.decide_fit(
                    label, rows, record=False, opt_out=opt_out,
                    width=width, model_ok=model_ok,
                )
        report.partition.append(decision.to_json())
        if not decision.eligible:
            interp.diag(
                "KV203",
                f"{label}: fit is not partition-managed "
                f"({decision.reason}"
                + (f": {decision.detail}" if decision.detail else "")
                + ") — streamed/serve fallbacks run single-device, "
                "in-core fits keep the legacy ambient-mesh path",
                node=node,
                reason=decision.reason,
            )
            continue

        if memory_limit is None:
            continue
        # Per-device residency of the SHARDED plan: the row slice (2× for
        # the centered/featurized working copy) plus the un-sharded
        # statistics every device carries in full.
        in_bytes = spec_bytes(in_spec)
        if streaming:
            feat = interp.specs.get(("feat", node))
            d = _width(feat) if feat is not None else None
            chunk = decision.chunk_rows or stream_chunk_rows()
            row_bytes = (
                (in_bytes // max(rows, 1)) if (in_bytes and rows) else None
            )
            slice_bytes = (
                2 * chunk * row_bytes // decision.shards if row_bytes else 0
            )
        else:
            d = _width(in_spec)
            slice_bytes = 2 * in_bytes // decision.shards if in_bytes else 0
        k = 1
        if len(deps) > 1:
            k = _width(interp.specs.get(deps[1])) or 1
        # 2-D layouts block the feature-indexed statistics (Gram rows,
        # cross-product rows, feature sums) over the model axis — only
        # the label-sized remainder stays replicated per model shard.
        p_m = max(1, int(getattr(decision, "model_shards", 1) or 1))
        stat_bytes = 2 * 4 * ((d * d + d * k + d) // p_m + k) if d else 0
        per_device = slice_bytes + stat_bytes
        if per_device > memory_limit:
            axis_hint = (
                "raise KEYSTONE_PARTITION_MODEL_SHARDS or use the "
                "sketched tier"
                if p_m > 1
                else "sharding divides rows, not the O(d²) state; use "
                "the sketched tier or a model-axis layout"
            )
            interp.diag(
                "KV304",
                f"{label}: sharded over {decision.shards}"
                + (f"×{p_m}" if p_m > 1 else "")
                + " devices the "
                f"per-device residency is still ~{per_device / 1e9:.2f} GB "
                f"(row slice {slice_bytes / 1e9:.2f} GB + "
                + ("feature-blocked" if p_m > 1 else "replicated")
                + f" statistics {stat_bytes / 1e9:.2f} GB) against a "
                f"{memory_limit / 1e9:.2f} GB budget — " + axis_hint,
                node=node,
                shards=decision.shards,
                model_shards=p_m,
                per_device_bytes=per_device,
                memory_limit=memory_limit,
            )


def _gram_feasibility(
    graph: Graph,
    interp: _Interpreter,
    node: NodeId,
    op: Any,
    memory_limit: Optional[int],
) -> None:
    """O(d²) sufficient statistics must fit next to two chunk buffers —
    the whole point of the streamed fit is bounded residency, so an
    infeasible Gram should be caught at plan time, not as an OOM ten
    minutes into ingest."""
    if memory_limit is None:
        return
    feat_spec = interp.specs.get(("feat", node))
    d = _width(feat_spec) if feat_spec is not None else None
    if d is None:
        return
    label = str(getattr(op, "label", type(op).__name__))
    # carry (gram d², cross d·k, sums) + the donated update's transient
    # double-residency: 2× is the engine's working-set model.
    k = 1
    deps = graph.get_dependencies(node)
    if len(deps) > 1:
        k = _width(interp.specs.get(deps[1])) or 1
    gram_bytes = 2 * 4 * (d * d + d * k + d + k)
    if gram_bytes > memory_limit:
        interp.diag(
            "KV303",
            f"{label}: streamed fit needs ~{gram_bytes / 1e9:.2f} GB of "
            f"Gram state (d={d}, k={k}) but the device memory budget is "
            f"{memory_limit / 1e9:.2f} GB — use the sketched/rematerialized "
            "tier instead",
            node=node,
            d=d,
            k=k,
            gram_bytes=gram_bytes,
            memory_limit=memory_limit,
        )


def _plan_state_kind(interp: _Interpreter, node: NodeId, op: Any) -> str:
    """Which stream-state kind this fit will produce at plan time —
    mirrors the solver ladder's width-based dispatch so the feasibility
    check inspects the rung that will actually run."""
    from ..refit.state import SketchStreamStateMixin

    est = getattr(op, "estimator", None)
    if isinstance(est, SketchStreamStateMixin):
        return "sketch"
    feat_spec = interp.specs.get(("feat", node))
    d = _width(feat_spec) if feat_spec is not None else None
    solver_for = getattr(est, "_stream_solver", None)
    if callable(solver_for) and d is not None:
        try:
            return str(getattr(solver_for(d), "stream_state_kind", "gram"))
        except Exception:
            return "gram"
    return "gram"


def _sketch_feasibility(
    graph: Graph,
    interp: _Interpreter,
    node: NodeId,
    op: Any,
    memory_limit: Optional[int],
) -> None:
    """The sketched tier is the LAST memory rung — below it there is
    nothing to degrade to, so an O(s·d) state that still misses the
    budget, or a sketch size too small for its error bound to mean
    anything (s below the dual-solve / label-width floor), is a plan
    error (KV308), not a warning like the Gram tier's KV303."""
    from ..envknobs import env_int
    from ..sketch.core import sketch_state_bytes
    from ..sketch.solvers import default_sketch_size

    feat_spec = interp.specs.get(("feat", node))
    d = _width(feat_spec) if feat_spec is not None else None
    if d is None:
        return
    label = str(getattr(op, "label", type(op).__name__))
    k = 1
    deps = graph.get_dependencies(node)
    if len(deps) > 1:
        k = _width(interp.specs.get(deps[1])) or 1
    est = getattr(op, "estimator", None)
    s = (
        env_int("KEYSTONE_SKETCH_SIZE", 0)
        or int(getattr(est, "sketch_size", 0) or 0)
        or default_sketch_size(d)
    )
    # Conditioning / bound heuristic: the finish is a dual s×s ridge
    # whose solution spans at most s directions — with s below a small
    # multiple of the label width (or a hard floor) the sketched
    # objective's error bound is vacuous. Checked even without a memory
    # budget: a bad sketch size is wrong on any device.
    floor = max(32, 4 * (k + 1))
    if s < floor:
        interp.diag(
            "KV308",
            f"{label}: sketch size s={s} is below the conditioning floor "
            f"{floor} (max(32, 4*(k+1)) with k={k}) — the dual ridge "
            "finish spans too few directions for the sketch-and-solve "
            "error bound to hold; raise KEYSTONE_SKETCH_SIZE",
            node=node,
            d=d,
            k=k,
            sketch_size=s,
            floor=floor,
        )
        return
    if memory_limit is None:
        return
    # carry (SA s·d, SY s·k, s1, sums) + the donated update's transient
    # double-residency: same 2× working-set model as the Gram check.
    state_bytes = 2 * sketch_state_bytes(s, d, k)
    if state_bytes > memory_limit:
        interp.diag(
            "KV308",
            f"{label}: even the sketched tier needs ~{state_bytes / 1e9:.2f} "
            f"GB of state (s={s}, d={d}, k={k}) against a "
            f"{memory_limit / 1e9:.2f} GB budget — no lower-memory rung "
            "exists; shrink KEYSTONE_SKETCH_SIZE or the feature width",
            node=node,
            d=d,
            k=k,
            sketch_size=s,
            state_bytes=state_bytes,
            memory_limit=memory_limit,
        )


_AUTO = object()


def verify_graph(
    graph: Graph,
    source_specs: Optional[Dict[SourceId, Any]] = None,
    *,
    buckets: Optional[Sequence[int]] = None,
    warmed_buckets: Optional[Sequence[int]] = None,
    device_memory_bytes: Any = _AUTO,
    probe_objects: bool = False,
    context: str = "graph",
) -> VerifyReport:
    """Statically verify a plan graph. Pure host-side analysis: specs
    propagate via ``out_spec``/``jax.eval_shape`` — no device execution,
    no XLA compiles (asserted by scripts/check_smoke.sh via the compile
    counter)."""
    t0 = time.perf_counter()
    report = VerifyReport(context=context)
    interp = _Interpreter(graph, report.diagnostics, probe_objects)
    if device_memory_bytes is _AUTO:
        # CPU test meshes report no capacity: the memory check then only
        # runs with an explicit budget.
        from ..parallel.mesh import device_memory_limit_bytes

        device_memory_bytes = device_memory_limit_bytes()
    memory_limit = device_memory_bytes

    try:
        order = linearize_whole(graph)
    except GraphCycleError as e:
        interp.diag("KV401", str(e))
        report.seconds = time.perf_counter() - t0
        _publish(report, context)
        return report

    peak_node_bytes = 0
    peak_node = None
    for vid in order:
        if isinstance(vid, SourceId):
            interp.specs[vid] = (source_specs or {}).get(vid, UNKNOWN)
            continue
        if isinstance(vid, SinkId):
            interp.specs[vid] = interp.specs.get(
                graph.get_sink_dependency(vid), UNKNOWN
            )
            continue
        op = graph.get_operator(vid)
        in_specs = [
            interp.specs.get(d, UNKNOWN) for d in graph.get_dependencies(vid)
        ]
        out = interp.node_out_spec(vid, op, in_specs)
        interp.specs[vid] = out

        label = str(getattr(op, "label", type(op).__name__))
        out_bytes = spec_bytes(out)
        report.annotations.append(
            NodeAnnotation(
                node=repr(vid),
                label=label,
                spec=_render_spec(out),
                est_bytes=out_bytes,
            )
        )
        # Silent widening: a float64 output from non-float64 inputs.
        import numpy as np

        out_leaves = _leaves(out)
        if out_leaves and any(
            np.dtype(leaf.dtype) == np.float64 for leaf in out_leaves
        ):
            in_leaves = [
                leaf for spec in in_specs for leaf in _leaves(spec)
            ]
            in_has_f64 = any(
                np.dtype(leaf.dtype) == np.float64 for leaf in in_leaves
            )
            # A node with no known input leaves (a source/dataset node,
            # or all-UNKNOWN inputs) cannot have WIDENED anything — f64
            # there is the data's own dtype, not a silent cast.
            if in_leaves and not in_has_f64:
                interp.diag(
                    "KV102",
                    f"{label}: output widens to float64 from narrower "
                    "inputs — 2× the bytes and a silent slow path on "
                    "accelerators",
                    node=vid,
                    op=label,
                )
        live = (out_bytes or 0) + sum(
            spec_bytes(spec) or 0 for spec in in_specs
        )
        if live > peak_node_bytes:
            peak_node_bytes, peak_node = live, (vid, label)

    if memory_limit is not None and peak_node_bytes > memory_limit:
        interp.diag(
            "KV302",
            f"estimated peak residency ~{peak_node_bytes / 1e9:.2f} GB at "
            f"node {peak_node[0]!r} ({peak_node[1]}) exceeds the device "
            f"memory budget {memory_limit / 1e9:.2f} GB",
            node=peak_node[0],
            peak_bytes=peak_node_bytes,
            memory_limit=memory_limit,
        )

    _fusion_diagnostics(graph, interp)
    _streaming_diagnostics(graph, interp, memory_limit)
    _partition_diagnostics(graph, interp, memory_limit, report)

    if buckets:
        # The serving-path partition decision rides the report too, so
        # `check --pipeline --buckets` explains the sharded (or not)
        # serve placement next to the warm-set check below.
        try:
            from ..parallel.partitioner import Partitioner

            report.partition.append(
                Partitioner()
                .decide_serve("serving", buckets, record=False)
                .to_json()
            )
        except Exception:  # pragma: no cover - decision is advisory
            pass
        warmed = set(int(b) for b in (warmed_buckets or ()))
        missing = sorted(set(int(b) for b in buckets) - warmed)
        if missing:
            interp.diag(
                "KV301",
                f"serving buckets {missing} are not in the warmed set "
                f"{sorted(warmed)} — every batch padded onto them compiles "
                "at serve time (steady-state recompile hazard; "
                "utils/aot.warm_buckets)",
                missing=missing,
                warmed=sorted(warmed),
            )

    report.seconds = time.perf_counter() - t0
    _publish(report, context)
    return report


def _apply_out_spec(model: Any, example_spec: Any):
    """Shape-only trace of a fitted model's batch apply on one request
    spec — zero device execution. Returns a ``(kind, rendering)`` pair:
    the two trace engines (``jax.eval_shape`` over ``apply_arrays`` vs
    the graph verifier's sink annotation) render specs differently, so a
    comparison is only meaningful between like kinds — the caller must
    never diff a mapper's repr against a pipeline's annotation string
    (that would flag every cross-kind publish). UNKNOWN when the model's
    apply path isn't statically traceable (bespoke apply_batch etc.)."""
    import jax

    apply_arrays = getattr(model, "apply_arrays", None)
    if apply_arrays is None and hasattr(model, "graph"):
        # FittedPipeline: propagate through the verifier itself and read
        # the sink annotation — the same engine load_fitted uses.
        try:
            report = verify_graph(
                model.graph,
                {model.source: example_spec},
                context="refit-spec-probe",
            )
            sink_dep = model.graph.get_sink_dependency(model.sink)
            for ann in report.annotations:
                if ann.node == repr(sink_dep):
                    return ("graph", ann.spec)
        except Exception:
            return UNKNOWN
        return UNKNOWN
    if apply_arrays is None:
        return UNKNOWN
    try:
        out = jax.eval_shape(apply_arrays, example_spec)
        return ("arrays", repr(out))
    except Exception:
        return UNKNOWN


def verify_refit_publish(
    candidate: Any,
    incumbent: Any,
    example: Any = None,
    buckets: Optional[Sequence[int]] = None,
    warmed_buckets: Optional[Sequence[int]] = None,
    context: str = "refit-publish",
) -> VerifyReport:
    """The publish-path face of the steady-state-recompile hazard
    (docs/REFIT.md, docs/VERIFICATION.md KV305).

    A refit-published candidate serves through the INCUMBENT's warmed
    executables: the fleet re-warms exactly the bucket set it already
    holds, so a candidate whose apply spec (per-bucket output
    shape/dtype) or required bucket set disagrees with what the
    incumbent warmed compiles at serve time — on live traffic, after the
    swap ack said "warm". This check is pure tracing (``jax.eval_shape``
    / spec propagation), zero device execution, and runs before every
    controller publish.
    """
    t0 = time.perf_counter()
    report = VerifyReport(context=context)
    interp = _Interpreter(Graph(), report.diagnostics, probe_objects=False)

    if buckets is not None:
        want = set(int(b) for b in buckets)
        warmed = set(int(b) for b in (warmed_buckets or ()))
        missing = sorted(want - warmed)
        if missing:
            interp.diag(
                "KV305",
                f"candidate's serving buckets {missing} are not in the "
                f"incumbent's warmed set {sorted(warmed)} — every batch "
                "padded onto them compiles at serve time, AFTER the "
                "publish settled (steady-state recompile on the publish "
                "path; re-warm the new buckets before swapping)",
                missing=missing,
                warmed=sorted(warmed),
            )

    if example is not None and incumbent is not None:
        import jax
        import numpy as np

        def leaf_spec(a):
            dtype = getattr(a, "dtype", None)
            if dtype is None:
                dtype = np.asarray(a).dtype
            return jax.ShapeDtypeStruct(
                (1,) + tuple(np.shape(a)), np.dtype(dtype)
            )

        try:
            spec = jax.tree_util.tree_map(leaf_spec, example)
        except Exception:
            spec = None
        if spec is not None:
            cand_out = _apply_out_spec(candidate, spec)
            inc_out = _apply_out_spec(incumbent, spec)
            if (
                cand_out is not UNKNOWN
                and inc_out is not UNKNOWN
                # Same trace engine only: the two renderings are not
                # comparable across kinds (a mapper candidate over a
                # pipeline incumbent would otherwise ALWAYS mismatch).
                and cand_out[0] == inc_out[0]
                and cand_out[1] != inc_out[1]
            ):
                interp.diag(
                    "KV305",
                    "candidate's apply spec "
                    f"{cand_out[1]} != incumbent's {inc_out[1]} for the "
                    "same request — the warmed executables cannot serve "
                    "it (shape/dtype drift in the refit candidate)",
                    candidate_spec=str(cand_out[1]),
                    incumbent_spec=str(inc_out[1]),
                )

    report.seconds = time.perf_counter() - t0
    _publish(report, context)
    return report


def verify_stream_resume(
    cursor: Any,
    current: Dict[str, Any],
    context: str = "stream-resume",
) -> VerifyReport:
    """The durable-fit face of stale-state corruption (docs/RELIABILITY.md
    "Durable fits", docs/VERIFICATION.md KV306).

    A mid-stream resume entry seeds a fold with sufficient statistics
    captured over a PREFIX of the dataset — sound only when the fresh
    process's re-planned pipeline reproduces the exact same features for
    the exact same rows. The resume key is deliberately coarse (it names
    the logical fit, so re-planned pipelines FIND their entry); this
    check is the content-level gate: any disagreement between the
    cursor's fingerprints and the re-planned pipeline's — dataset or
    labels content digest, featurize-chain digest (weights included),
    featurized width or dtype — refuses the entry. Stale resume must be
    a loud refusal and a from-scratch re-ingest, never a silently
    corrupted fit. Pure host-side comparison, zero device execution.

    ``cursor`` is a :class:`~keystone_tpu.reliability.durable.StreamCursor`;
    ``current`` maps the same fingerprint field names to the re-planned
    pipeline's values.
    """
    t0 = time.perf_counter()
    report = VerifyReport(context=context)
    interp = _Interpreter(Graph(), report.diagnostics, probe_objects=False)
    checks = (
        ("dataset_digest", "dataset content digest"),
        ("labels_digest", "labels content digest"),
        ("chain_digest", "featurize-chain digest"),
        ("feature_width", "featurized width"),
        ("feature_dtype", "featurized dtype"),
    )
    for field_name, title in checks:
        have = getattr(cursor, field_name)
        want = current.get(field_name)
        if have != want:
            interp.diag(
                "KV306",
                f"resume entry's {title} ({str(have)[:16]}) disagrees with "
                f"the re-planned pipeline's ({str(want)[:16]}) — seeding "
                "the fold from this entry would silently corrupt the fit; "
                "the entry is refused and the fit re-ingests from scratch",
                field=field_name,
                entry=str(have)[:16],
                planned=str(want)[:16],
            )
    report.seconds = time.perf_counter() - t0
    _publish(report, context)
    return report


#: manifest/environment fields verify_boot_image compares, with the human
#: titles its diagnostics use. serving/bootimage.py builds both sides.
BOOT_IMAGE_FINGERPRINTS: Tuple[Tuple[str, str], ...] = (
    ("format_version", "artifact format version"),
    ("jax_version", "jax version"),
    ("backend", "jax backend"),
    ("device_kind", "device kind"),
    ("weights_digest", "fitted-weights digest"),
)


def verify_boot_image(
    manifest: Dict[str, Any],
    current: Dict[str, Any],
    context: str = "boot-image",
) -> VerifyReport:
    """The serving face of stale-state corruption (docs/SERVING.md
    "Elastic fleet", docs/VERIFICATION.md KV307).

    A boot image carries AOT-serialized bucket executables plus the
    fitted weights they were exported from — sound to serve through only
    when the loading worker's environment matches the builder's: same
    artifact format, same jax version (export/deserialize compatibility),
    same backend and device kind (the serialized executables ride the
    persistent compilation cache, which is environment-keyed exactly like
    ProfileStore entries), and the same weights digest (an image whose
    executables baked different weights than ``model.pkl`` would answer
    with the WRONG model). Any disagreement refuses the image: the worker
    falls back to the classic warm path — slower first request, never
    garbage. Pure host-side comparison, zero device execution.

    ``manifest`` and ``current`` both map the fingerprint field names
    from :data:`BOOT_IMAGE_FINGERPRINTS` to their values (the image's
    recorded environment vs the loading process's observed one).
    """
    t0 = time.perf_counter()
    report = VerifyReport(context=context)
    interp = _Interpreter(Graph(), report.diagnostics, probe_objects=False)
    for field_name, title in BOOT_IMAGE_FINGERPRINTS:
        have = manifest.get(field_name)
        want = current.get(field_name)
        if have != want:
            interp.diag(
                "KV307",
                f"boot image's {title} ({str(have)[:24]}) disagrees with "
                f"this worker's ({str(want)[:24]}) — serving through its "
                "executables could return garbage; the image is refused "
                "and the worker warms through the classic path",
                field=field_name,
                image=str(have)[:24],
                worker=str(want)[:24],
            )
    report.seconds = time.perf_counter() - t0
    _publish(report, context)
    return report


def verify_pipeline(
    pipeline: Any,
    input_spec: Any = None,
    **kwargs: Any,
) -> VerifyReport:
    """Verify a ``Pipeline`` or ``FittedPipeline``: binds ``input_spec``
    (a ShapeDtypeStruct pytree for the pipeline's input batch) to the
    unbound source when given."""
    graph = pipeline.graph
    source_specs = {}
    source = getattr(pipeline, "source", None)
    if input_spec is not None and source is not None and source in graph.sources:
        source_specs[source] = input_spec
    kwargs.setdefault("context", type(pipeline).__name__)
    return verify_graph(graph, source_specs or None, **kwargs)


def _publish(report: VerifyReport, context: str) -> None:
    _names.metric(_names.VERIFY_RUNS).inc(context=context)
    _names.metric(_names.VERIFY_NODES).inc(len(report.annotations))
    _names.metric(_names.VERIFY_SECONDS).observe(report.seconds)
    diag_c = _names.metric(_names.VERIFY_DIAGNOSTICS)
    for d in report.diagnostics:
        diag_c.inc(code=d.code, severity=d.severity)


# ----------------------------------------------------------------- enforcement


def verification_mode() -> str:
    """``KEYSTONE_VERIFY``: ``warn`` (default — log and continue),
    ``strict`` (errors raise :class:`VerificationError`), ``off``."""
    raw = env_str("KEYSTONE_VERIFY", "warn").lower()
    if raw in ("off", "0", "disabled", "none"):
        return "off"
    if raw == "strict":
        return "strict"
    return "warn"


def verify_and_enforce(
    graph: Graph,
    context: str,
    source_specs: Optional[Dict[SourceId, Any]] = None,
    **kwargs: Any,
) -> Optional[VerifyReport]:
    """The fit/load hook: verify under the ``KEYSTONE_VERIFY`` mode.

    ``warn`` logs error/warning diagnostics and never interferes;
    ``strict`` raises :class:`VerificationError` when errors were found.
    An internal verifier failure is logged and swallowed in BOTH modes —
    a bug in the verifier must never take down a fit that would have
    succeeded (only *verified* findings raise).
    """
    mode = verification_mode()
    if mode == "off":
        return None
    try:
        report = verify_graph(
            graph, source_specs, context=context, **kwargs
        )
    except Exception:
        logger.warning(
            "plan-time verification of %s failed internally (ignored)",
            context,
            exc_info=True,
        )
        return None
    for d in report.diagnostics:
        if d.severity == ERROR:
            logger.warning("plan-time verify [%s]: %s", context, d.render())
        elif d.severity == WARNING:
            logger.info("plan-time verify [%s]: %s", context, d.render())
    if mode == "strict" and not report.ok:
        raise VerificationError(report)
    return report
