"""Pull-based memoized graph execution + the process-wide pipeline env.

TPU-native re-design of the reference's interpreter
(reference: workflow/GraphExecutor.scala:14-81, workflow/PipelineEnv.scala:7-37).

``GraphExecutor`` optimizes its graph once (on first pull), then recursively
executes dependencies with memoization. Results are lazy ``Expression``s:
forcing a ``DatasetExpression``'s ``get`` is what actually runs XLA
computations, exactly as forcing an RDD ran Spark jobs in the reference.

``PipelineEnv`` holds the prefix-state table used for cross-pipeline reuse
of fit estimators and cached datasets, plus the active optimizer stack and
the reliability hooks (retry policy, checkpoint store) the executor
consults per node — see keystone_tpu/reliability/ and docs/RELIABILITY.md.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

from ..data.dataset import ArrayDataset, Dataset
from ..obs import names as _names
from ..obs import spans as _spans
from ..obs.device import to_device
from ..reliability import faultinject
from ..reliability.recovery import reset_recovery_log
from .graph import Graph, GraphId, NodeId, SinkId, SourceId
from .operators import DatasetExpression, EstimatorOperator, Expression
from .prefix import Prefix, PrefixTable, find_prefix
from .tracing import timed_execute


def _executor_counters():
    """Resolve the executor's always-on counters (schema-driven). Cached
    per GraphExecutor (executors are per-application, so a test-time
    registry reset can't strand handles for long)."""
    return (
        _names.metric(_names.NODES_EXECUTED),
        _names.metric(_names.MEMO_HITS),
        _names.metric(_names.AUTOCACHE_HITS),
        _names.metric(_names.AUTOCACHE_MISSES),
    )


def _is_cacher(op) -> bool:
    from ..ops.util.misc import CacherOperator

    return isinstance(op, CacherOperator)


def _uploads_its_input(op) -> bool:
    """Whether ``op`` applies a batch through ``BatchTransformer.apply_batch``
    as it stands: the wrapper whose first act on a host batch is to upload
    it whole. A subclass with an ``apply_batch`` of its own may want the
    host's copy (native extractors, patchers), and so does every operator
    that is no ``BatchTransformer``."""
    from .pipeline import BatchTransformer

    return (
        isinstance(op, BatchTransformer)
        and type(op).apply_batch is BatchTransformer.apply_batch
    )


class _SharedUpload:
    """One node's host-resident output, uploaded once for the
    ``consumers`` batch transformers that read it directly.

    Left alone, each of them uploads the whole batch for itself
    (``BatchTransformer.apply_batch``): the same rows k times over the
    bus, on the blocking path of a request. Here the first one forced
    makes the upload (``to_device``: enqueued, not waited for) under the
    producing operator's class as ``site``, and every one is handed the
    same device arrays, so its own ``to_device`` finds nothing to do.
    What such a consumer would not upload whole passes through as it
    was produced: arrays already on a device, the masked-descriptor
    dictionary (``desc`` alone goes up, in the consumer), a
    ``BucketedDataset``, an ``ObjectDataset``.

    The copy belongs to one executor and so to one execution, and is let
    go of when the last consumer has it. Nothing is kept on the dataset,
    on the numpy array or across calls: the next execution uploads
    again. The executor's pull forces consumers one after another, so
    there is no lock."""

    def __init__(self, produced: Expression, site: str, consumers: int):
        self._produced = produced
        self._site = site
        self._consumers = consumers
        self._handed = 0
        self._device: Optional[ArrayDataset] = None

    def hand(self) -> Dataset:
        """The dataset one consumer computes on (each calls this once)."""
        dataset = self._produced.get()
        self._handed += 1
        if self._handed == 1:
            self._device = self._upload(dataset)
        elif self._device is not None:
            _names.metric(_names.H2D_REUSES).inc(site=self._site)
        device = self._device
        if self._handed >= self._consumers:
            self._device = None
        return dataset if device is None else device

    def _upload(self, dataset: Dataset) -> Optional[ArrayDataset]:
        from .pipeline import is_masked_descriptors

        if not isinstance(dataset, ArrayDataset) or is_masked_descriptors(dataset.data):
            return None
        data = to_device(dataset.data, site=self._site, consumers=self._consumers)
        if data is dataset.data:  # no host leaf: nothing was uploaded
            return None
        return ArrayDataset(data, dataset.num_examples)


class PipelineEnv:
    """Process-wide executor state (reference: PipelineEnv.scala:7-37)."""

    _instance: Optional["PipelineEnv"] = None
    _lock = threading.Lock()

    def __init__(self):
        self.state = PrefixTable()  # prefix -> Expression, while it can be asked for
        self._optimizer = None
        # Reliability hooks — both default OFF (zero per-node overhead).
        # retry_policy: a reliability.RetryPolicy applied to every node
        # forcing (transient faults retried, per-node deadline enforced).
        # checkpoint: a reliability.CheckpointStore; estimator fits write
        # through and digest-matching fits restore instead of refitting.
        self.retry_policy = None
        self.checkpoint = None

    @classmethod
    def get_or_create(cls) -> "PipelineEnv":
        with cls._lock:
            if cls._instance is None:
                cls._instance = PipelineEnv()
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        """Drop all global state — required between tests
        (reference: test fixture PipelineContext.scala:9-25). Clears the
        recovery ledger too: it is per-run state like the prefix table."""
        with cls._lock:
            cls._instance = None
        reset_recovery_log()

    @property
    def optimizer(self):
        if self._optimizer is None:
            from .rules import default_optimizer

            self._optimizer = default_optimizer()
        return self._optimizer

    @optimizer.setter
    def optimizer(self, value) -> None:
        self._optimizer = value


class GraphExecutor:
    """Memoized recursive interpreter over an (optionally optimized) graph."""

    def __init__(self, graph: Graph, optimize: bool = True):
        self._raw_graph = graph
        self._optimize = optimize
        self._optimized: Optional[Graph] = None
        self._prefixes: Dict[NodeId, Prefix] = {}
        self._memo: Dict[GraphId, Expression] = {}
        self._counters = None  # resolved lazily, once per executor
        # Host outputs uploaded once for several batch transformers, and
        # the reverse edges of the graph they were counted on.
        self._uploads: Dict[NodeId, _SharedUpload] = {}
        self._dependents: Optional[Dict[NodeId, List[GraphId]]] = None
        self._dependents_of: Optional[Graph] = None
        #: Partition decisions the planner recorded for THIS plan
        #: (parallel/partitioner.py), captured at optimize time — a
        #: stable per-executor snapshot for programmatic consumers that
        #: outlive later optimizer runs (the global
        #: ``last_partition_report()`` describes only the LAST plan).
        #: Pinned by tests/workflow/test_partition.py.
        self.partition_decisions: list = []

    @property
    def graph(self) -> Graph:
        """The optimized graph (optimizes on first access)."""
        if self._optimized is None:
            if self._optimize:
                from ..parallel.partitioner import (
                    last_partition_report,
                    partition_report_generation,
                )

                env = PipelineEnv.get_or_create()
                generation = partition_report_generation()
                with _spans.span("optimize"):
                    self._optimized, self._prefixes = env.optimizer.execute(
                        self._raw_graph
                    )
                # Only adopt the report if THIS optimize ran a partition
                # batch (the reset bumps the generation) — a custom
                # stack without one must not inherit a previous plan's
                # decisions. (Optimizer runs are process-serial in
                # practice; concurrent optimizes would interleave the
                # global report either way.)
                if partition_report_generation() != generation:
                    self.partition_decisions = last_partition_report()
            else:
                self._optimized = self._raw_graph
        return self._optimized

    @property
    def raw_graph(self) -> Graph:
        return self._raw_graph

    def execute(self, graph_id: GraphId) -> Expression:
        graph = self.graph
        if self._counters is None:
            self._counters = _executor_counters()
        nodes_c, memo_c, cache_hit_c, cache_miss_c = self._counters
        if graph_id in self._memo:
            # Memo hits are the executor-level reuse signal; hits on Cacher
            # nodes specifically are the auto-cache planner's payoff (each
            # one is a recomputation of the cached subtree avoided).
            if isinstance(graph_id, NodeId):
                memo_c.inc()
                if _is_cacher(graph.get_operator(graph_id)):
                    cache_hit_c.inc()
            return self._memo[graph_id]
        if isinstance(graph_id, SourceId):
            raise ValueError(
                f"cannot execute unbound source {graph_id}: bind pipeline inputs first"
            )
        if isinstance(graph_id, SinkId):
            result = self.execute(graph.get_sink_dependency(graph_id))
            self._memo[graph_id] = result
            return result

        op = graph.get_operator(graph_id)
        deps = [self._input(op, d) for d in graph.get_dependencies(graph_id)]
        nodes_c.inc()
        if _is_cacher(op):
            cache_miss_c.inc()
        expression = timed_execute(op, deps)

        prefix = self._prefixes.get(graph_id)
        expression = _wrap_reliability(op, deps, expression, prefix)

        # Prefix write-back: make this node's result reusable by later
        # pipelines (reference: GraphExecutor.scala:65-71).
        if prefix is not None:
            PipelineEnv.get_or_create().state[prefix] = expression

        self._memo[graph_id] = expression
        return expression

    def _input(self, op, dep: GraphId) -> Expression:
        """``dep``'s result as ``op`` reads it: the memoized expression,
        or, for a batch transformer that is one of several on ``dep``, a
        hand-out of their shared upload (:class:`_SharedUpload`). Decided
        from the graph this executor runs, the optimized or fused one,
        and from nothing else."""
        produced = self.execute(dep)
        if not (
            isinstance(dep, NodeId)
            and isinstance(produced, DatasetExpression)
            and _uploads_its_input(op)
        ):
            return produced
        shared = self._uploads.get(dep)
        if shared is None:
            graph = self.graph
            if self._dependents_of is not graph:  # Pipeline.fit splices as it goes
                self._dependents, self._dependents_of = graph.dependents(), graph
            consumers = sum(
                isinstance(c, NodeId) and _uploads_its_input(graph.get_operator(c))
                for c in set(self._dependents[dep])
            )
            if consumers < 2:
                return produced
            site = type(graph.get_operator(dep)).__name__
            shared = self._uploads[dep] = _SharedUpload(produced, site, consumers)
        return DatasetExpression(shared.hand)


def _wrap_reliability(
    op, deps, expression: Expression, prefix: Optional[Prefix]
) -> Expression:
    """Layer the reliability hooks around a node's lazy result.

    Expressions are call-by-name memoized and a failing thunk leaves the
    memo unset, so re-forcing after a failure genuinely re-executes — which
    is what makes wrapping the *expression* (not the eager execute call)
    the right retry boundary: the heavy work happens at force time.

    Wrapping order, innermost out:
      1. fault injection — stands in for the op itself failing;
      2. checkpoint — a digest hit skips the op (and any injected faults:
         restored work is not re-executed, same as lineage recovery);
      3. retry + per-node deadline — sees injected and real faults alike.
    All three default off; with none active the original expression is
    returned untouched.

    Each attempt executes the op FRESH (``op.execute`` is cheap — it only
    builds lazy thunks; deps stay memoized) rather than re-entering the
    shared Expression: after a deadline abandonment the watchdog thread
    may still be inside the old expression's ``get`` holding its memo
    lock, and a retry re-entering it would block behind the hung attempt
    (``Expression.get`` is lock-guarded, so the race is gone — but the
    hang would remain). The wrapper expression below memoizes the one
    successful result for all downstream readers.
    """
    env = PipelineEnv.get_or_create()
    injector = faultinject.current()
    policy = env.retry_policy
    store = env.checkpoint
    checkpointable = (
        store is not None and prefix is not None and isinstance(op, EstimatorOperator)
    )
    if injector is None and policy is None and not checkpointable:
        return expression

    label = str(getattr(op, "label", type(op).__name__))
    first = expression

    def thunk(_first=[first]):
        # First attempt consumes the already-built expression; retries get
        # a fresh one (see docstring).
        inner = _first.pop() if _first else timed_execute(op, deps)
        return inner.get()

    if injector is not None:
        thunk = injector.wrap(label, thunk)
    if checkpointable:
        inner_thunk = thunk
        thunk = lambda: store.get_or_compute(prefix, inner_thunk, label=label)  # noqa: E731
    if policy is not None:
        attempt = thunk
        thunk = lambda: policy.call(attempt, label=label)  # noqa: E731
    return type(expression)(thunk)
