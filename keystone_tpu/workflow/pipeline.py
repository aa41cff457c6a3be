"""Typed pipeline API: Transformer / Estimator / LabelEstimator / Pipeline.

TPU-native re-design of the reference's public facade
(reference: workflow/Transformer.scala:18-70, workflow/Estimator.scala:10-62,
workflow/LabelEstimator.scala:13-100, workflow/Chainable.scala:13-126,
workflow/Pipeline.scala:22-155, workflow/FittedPipeline.scala:22-48).

Semantics preserved from the reference:

- ``a >> b >> est.with_data(data)`` builds an immutable DAG; nothing runs
  until a result is forced.
- Applying a pipeline yields lazy ``PipelineDataset``/``PipelineDatum``
  handles; forcing ``.get()`` runs the optimizer once, then executes with
  memoization.
- Estimators bound to data fit **once** per process even across repeated
  applications — results are memoized under structural prefixes in the
  process-wide state table.
- ``Pipeline.fit()`` executes every estimator, splices the fit transformers
  in place, prunes fit-time-only branches, and returns a serializable
  ``FittedPipeline`` containing only transformers.

What is different on TPU: datasets are sharded device batches rather than
RDDs, and transformer ``apply_batch`` implementations are jitted XLA
computations over whole batches rather than per-partition JVM loops.
"""

from __future__ import annotations

import pickle
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

from ..data.dataset import ArrayDataset, Dataset, ObjectDataset, as_dataset
from ..obs import spans as _spans
from ..obs.device import to_device
from .executor import GraphExecutor, PipelineEnv
from .graph import Graph, NodeId, NodeOrSourceId, SinkId, SourceId
from .operators import (
    DatasetOperator,
    DatumOperator,
    DelegatingOperator,
    EstimatorOperator,
    Expression,
    TransformerOperator,
)
from .rules import UnusedBranchRemovalRule


# --------------------------------------------------------------------- results


class PipelineResult:
    """Lazy handle on a pipeline output
    (reference: workflow/PipelineResult.scala:13-20)."""

    def __init__(self, executor: GraphExecutor, sink: SinkId, graph: Graph):
        self._executor = executor
        self._sink = sink
        self.graph = graph  # unoptimized graph, for further composition

    def get(self) -> Any:
        return self._executor.execute(self._sink).get()


class PipelineDataset(PipelineResult):
    """Lazy dataset result; duck-types enough of Dataset for evaluators."""

    def collect(self) -> List[Any]:
        return self.get().collect()

    def __len__(self) -> int:
        return len(self.get())


class PipelineDatum(PipelineResult):
    pass


# -------------------------------------------------------------------- chaining


class Chainable:
    """Mixin providing ``then`` / ``>>`` composition
    (reference: workflow/Chainable.scala:13-126)."""

    def to_pipeline(self) -> "Pipeline":
        raise NotImplementedError

    def then(self, nxt: "Chainable") -> "Pipeline":
        """``self`` then ``nxt`` (reference ``andThen``)."""
        this = self.to_pipeline()
        other = nxt.to_pipeline()
        combined, _, sink_map = this.graph.connect_graph(other.graph, {other.source: this.sink})
        return Pipeline(combined, this.source, sink_map[other.sink])

    def then_estimator(self, est: "Estimator", data: Union[Dataset, PipelineDataset, Any]) -> "Pipeline":
        """Fit ``est`` on this pipeline applied to ``data``; result applies
        self then the fit transformer (reference: Chainable.scala estimator
        overloads of andThen)."""
        return self.then(est.with_data(self.to_pipeline().apply(data)))

    def then_label_estimator(
        self,
        est: "LabelEstimator",
        data: Union[Dataset, PipelineDataset, Any],
        labels: Union[Dataset, PipelineDataset, Any],
    ) -> "Pipeline":
        return self.then(est.with_data(self.to_pipeline().apply(data), labels))

    def __rshift__(self, nxt: "Chainable") -> "Pipeline":
        return self.then(nxt)


# ----------------------------------------------------------------- transformer


class Transformer(TransformerOperator, Chainable):
    """Typed unary transformer (reference: workflow/Transformer.scala:18-70).

    Subclasses implement ``apply`` (one datum) and optionally override
    ``apply_batch`` with a device-batched implementation.
    """

    def apply(self, datum: Any) -> Any:
        raise NotImplementedError

    def apply_batch(self, dataset: Dataset) -> Dataset:
        return dataset.map(self.apply)

    def chunk_applier(self) -> Optional[Callable[[Dataset], Dataset]]:
        """A function to apply to consecutive row chunks of one batch, in
        order, whose outputs laid end to end equal ``apply_batch`` on the
        whole batch to the bit; ``None`` (the default) where the batch form
        is not row by row, or nobody has said that it is. A new function a
        call: one that carries something from chunk to chunk (a sampler's
        generator) starts afresh. The graph executor runs a chain of such
        transformers over row chunks where the chain would not fit the
        device whole (workflow/executor.py, ``_RowChain``)."""
        return None

    # Operator protocol -----------------------------------------------------
    def single_transform(self, datums: List[Any]) -> Any:
        return self.apply(datums[0])

    def batch_transform(self, datasets: List[Dataset]) -> Dataset:
        return self.apply_batch(datasets[0])

    # Chaining --------------------------------------------------------------
    def to_pipeline(self) -> "Pipeline":
        graph = Graph()
        graph, source = graph.add_source()
        graph, node = graph.add_node(self, [source])
        graph, sink = graph.add_sink(node)
        return Pipeline(graph, source, sink)

    def __call__(self, data: Any) -> Any:
        if isinstance(data, (Dataset, PipelineDataset)):
            return self.to_pipeline().apply(data)
        return self.apply(data)

    @staticmethod
    def from_fn(fn: Callable[[Any], Any], batch_fn: Optional[Callable] = None, name: str = "") -> "Transformer":
        return _FnTransformer(fn, batch_fn, name)


class _FnTransformer(Transformer):
    def __init__(self, fn, batch_fn=None, name=""):
        self.fn = fn
        self.batch_fn = batch_fn
        self.name = name or getattr(fn, "__name__", "fn")

    @property
    def label(self) -> str:
        return self.name

    def apply(self, datum):
        return self.fn(datum)

    def apply_batch(self, dataset):
        if self.batch_fn is not None and isinstance(dataset, ArrayDataset):
            return dataset.map_batched(self.batch_fn)
        return dataset.map(self.fn)


class Identity(Transformer):
    """reference: workflow/Identity.scala:11"""

    def apply(self, datum: Any) -> Any:
        return datum

    def apply_batch(self, dataset: Dataset) -> Dataset:
        return dataset


def feat_scope(op: Any):
    """``jax.named_scope("feat/<ClassName>")`` around one transformer's
    ``apply_arrays``: the stable name its operations carry in a device
    trace (metadata only), given to every featurizer from one place. It
    names what is TRACED under it (a fused chain's members, a featurizer's
    own scan or jitted helper); a primitive dispatched eagerly keeps its
    bare ``jit(cos)/cos``, whatever scope is open."""
    import jax

    return jax.named_scope("feat/" + type(op).__name__)


def is_masked_descriptors(data: Any) -> bool:
    """The masked descriptor convention ({"desc": (N, n_pad, d), "valid":
    (N, n_pad)} from ops.images.native): batch transformers act on the
    descriptors and validity flows through untouched."""
    return isinstance(data, dict) and "desc" in data and "valid" in data


class BatchTransformer(Transformer):
    """Transformer whose native form is whole-batch array computation.

    Subclasses implement ``apply_arrays(pytree) -> pytree`` (jit-friendly);
    per-datum apply wraps it with a singleton batch dimension.

    Batch application preserves the framework-wide invariant that rows past
    ``num_examples`` (mesh padding) stay exactly zero, so downstream
    Gram/gradient accumulations over the data axis are unaffected by
    padding no matter what elementwise work happens in between.

    ``apply_arrays`` must also be row-independent (output row i depends
    only on input row i) and jit-traceable — the contract the fusion pass
    (workflow/fusion.py) relies on to compose consecutive transformers
    into one compiled dispatch. Ops that manage their own sharding or
    dispatch set ``fusable = False`` to opt out.
    """

    #: Chain-fusion opt-out (see workflow/fusion.py).
    fusable: bool = True
    #: True only on FusedTransformerOperator (dispatch accounting label).
    _is_fused: bool = False

    def apply_arrays(self, data: Any) -> Any:
        raise NotImplementedError

    def chunk_applier(self) -> Optional[Callable[[Dataset], Dataset]]:
        """``apply_batch`` as it stands here: ``apply_arrays`` is row
        independent by contract. A subclass with an ``apply_batch`` of its
        own says for itself whether that is (``FisherVector`` does)."""
        if type(self).apply_batch is BatchTransformer.apply_batch:
            return self.apply_batch
        return None

    def host_span(self, dataset: ArrayDataset):
        """A span of this transformer's own around its application to
        ``dataset``, on the host (the extractors' ``image:*``); none by
        default."""
        return _spans._NOOP_SPAN_CM

    def apply(self, datum: Any) -> Any:
        import jax
        import jax.numpy as jnp

        # jnp.asarray keeps device arrays on device (np.asarray would force
        # a host round-trip per datum) and still handles scalars/lists.
        batched = jax.tree_util.tree_map(lambda a: jnp.asarray(a)[None], datum)
        out = self.apply_arrays(batched)
        return jax.tree_util.tree_map(lambda a: a[0], out)

    def apply_batch(self, dataset: Dataset) -> Dataset:
        import jax
        import jax.numpy as jnp

        from ..data.dataset import BucketedDataset

        if isinstance(dataset, BucketedDataset):
            # Native-resolution path: one static-shape application per
            # size bucket (each bucket compiles once, like any batch).
            return dataset.map_datasets(self.apply_batch)
        # Dispatch accounting: each batch application of a transformer is
        # one host→device round trip. The fused-vs-unfused split is the
        # direct evidence for the fusion pass (a k-node chain fused into
        # one operator counts 1 here instead of k) — see workflow/fusion.py
        # and the bench `fusion` leg. Bucketed batches count per bucket
        # (each bucket genuinely dispatches), via the recursion above.
        # A fused operator that latched its eager fallback no longer
        # dispatches once — count its members as unfused so the CI-gated
        # 1-dispatch invariant actually detects fusion degrading. (The
        # single batch that triggers the latch is counted fused — the
        # latch flips mid-apply — every batch after it is counted true.)
        from ..obs import names as _names

        counter = _names.metric(_names.FUSION_BATCH_DISPATCHES)
        if self._is_fused and getattr(self, "_eager_fallback", False):
            counter.inc(len(self.members), fused="0")
        else:
            counter.inc(fused="1" if self._is_fused else "0")
        if isinstance(dataset, ObjectDataset):
            dataset = dataset.to_arrays()
        assert isinstance(dataset, ArrayDataset)
        if is_masked_descriptors(dataset.data):
            # Safe for the chain between extractor and FisherVector
            # (elementwise maps and PCA matmuls keep zero rows zero).
            desc = to_device(dataset.data["desc"], site=type(self).__name__)
            with feat_scope(self):
                out = self.apply_arrays(desc)
            return ArrayDataset(
                {"desc": out, "valid": dataset.data["valid"]},
                dataset.num_examples,
            )
        # The upload of a host-resident batch, made explicit where the
        # first jnp operation of `apply_arrays` used to make it. Where
        # this transformer is one of several on the same node's output,
        # the executor has uploaded it once for all of them
        # (executor._SharedUpload) and there is no host leaf left here.
        data = to_device(dataset.data, site=type(self).__name__)
        with self.host_span(dataset), feat_scope(self):
            out = ArrayDataset(self.apply_arrays(data), dataset.num_examples)
        if out.physical_rows > out.num_examples:
            real_row = jnp.arange(out.physical_rows) < out.num_examples

            def zero_pad_rows(a):
                # where (not multiply): ops like log/div turn zero pad rows
                # into NaN/Inf, and 0*NaN is NaN — select restores exact 0.
                m = real_row.reshape((-1,) + (1,) * (a.ndim - 1))
                return jnp.where(m, a, jnp.zeros((), dtype=a.dtype))

            out = ArrayDataset(
                jax.tree_util.tree_map(zero_pad_rows, out.data), out.num_examples
            )
        return out


# ------------------------------------------------------------------ estimators


class Estimator(EstimatorOperator):
    """Unsupervised estimator (reference: workflow/Estimator.scala:10-62)."""

    def fit(self, data: Dataset) -> Transformer:
        raise NotImplementedError

    def fit_datasets(self, datasets: List[Dataset]) -> TransformerOperator:
        return self.fit(datasets[0])

    def with_data(self, data: Union[Dataset, PipelineDataset, Any]) -> "Pipeline":
        """Bind training data now; returns a pipeline applying the (lazily)
        fit transformer to its input (reference: Estimator.scala:29-46)."""
        graph = Graph()
        graph, data_dep = _attach_data(graph, data)
        graph, est_node = graph.add_node(self, [data_dep])
        graph, source = graph.add_source()
        graph, delegating = graph.add_node(DelegatingOperator(), [est_node, source])
        graph, sink = graph.add_sink(delegating)
        return Pipeline(graph, source, sink)


class LabelEstimator(EstimatorOperator):
    """Supervised estimator (reference: workflow/LabelEstimator.scala:13-100)."""

    def fit(self, data: Dataset, labels: Dataset) -> Transformer:
        raise NotImplementedError

    def fit_datasets(self, datasets: List[Dataset]) -> TransformerOperator:
        return self.fit(datasets[0], datasets[1])

    def with_data(
        self,
        data: Union[Dataset, PipelineDataset, Any],
        labels: Union[Dataset, PipelineDataset, Any],
    ) -> "Pipeline":
        graph = Graph()
        graph, data_dep = _attach_data(graph, data)
        graph, labels_dep = _attach_data(graph, labels)
        graph, est_node = graph.add_node(self, [data_dep, labels_dep])
        graph, source = graph.add_source()
        graph, delegating = graph.add_node(DelegatingOperator(), [est_node, source])
        graph, sink = graph.add_sink(delegating)
        return Pipeline(graph, source, sink)


def _attach_data(graph: Graph, data: Any):
    """Attach a dataset (or lazy pipeline result graph) to ``graph``."""
    if isinstance(data, PipelineDataset):
        combined, _, sink_map = graph.add_graph(data.graph)
        inner_sink = sink_map[data._sink]
        dep = combined.get_sink_dependency(inner_sink)
        return combined.remove_sink(inner_sink), dep
    dataset = as_dataset(data)
    graph, node = graph.add_node(DatasetOperator(dataset), [])
    return graph, node


# -------------------------------------------------------------------- pipeline


class Pipeline(Chainable):
    """A single-input single-output dataflow with fit-on-demand semantics."""

    def __init__(self, graph: Graph, source: SourceId, sink: SinkId):
        self.graph = graph
        self.source = source
        self.sink = sink

    def to_pipeline(self) -> "Pipeline":
        return self

    # ------------------------------------------------------------------ apply
    def apply(self, data: Any) -> PipelineResult:
        if isinstance(data, PipelineDataset):
            combined, _, sink_map = data.graph.add_graph(self.graph)
            new_source = _find_mapped_source(self.graph, self.source, combined, data.graph)
            inner_dep = combined.get_sink_dependency(data._sink)
            combined = combined.remove_sink(data._sink)
            combined = combined.replace_dependency(new_source, inner_dep)
            combined = combined.remove_source(new_source)
            sink = sink_map[self.sink]
            return PipelineDataset(GraphExecutor(combined), sink, combined)
        if isinstance(data, (Dataset, list, tuple)) or _is_array(data):
            dataset = as_dataset(data)
            graph, node = self.graph.add_node(DatasetOperator(dataset), [])
            graph = graph.replace_dependency(self.source, node)
            graph = graph.remove_source(self.source)
            return PipelineDataset(GraphExecutor(graph), self.sink, graph)
        # single datum
        graph, node = self.graph.add_node(DatumOperator(data), [])
        graph = graph.replace_dependency(self.source, node)
        graph = graph.remove_source(self.source)
        return PipelineDatum(GraphExecutor(graph), self.sink, graph)

    def __call__(self, data: Any) -> PipelineResult:
        return self.apply(data)

    # -------------------------------------------------------------------- fit
    def fit(self) -> "FittedPipeline":
        """Execute all estimator fits and return a transformer-only pipeline
        (reference: Pipeline.scala:38-65).

        Before any fit executes, the OPTIMIZED graph goes through the
        plan-time static verifier (workflow/verify.py): shape/dtype
        mismatches, float64 widening, and infeasible streamed fits are
        diagnosed from specs alone — warn-by-default,
        ``KEYSTONE_VERIFY=strict`` raises ``VerificationError`` here
        instead of failing minutes later inside a jit trace."""
        from .verify import verify_and_enforce

        # Top-level phases are siblings, with no root span over the fit:
        # a trace reader that names a gap by the first span covering it
        # would otherwise put every gap down to the root (PERF.md 7).
        env = PipelineEnv.get_or_create()
        with _spans.span("fit:plan"):
            graph, prefixes = env.optimizer.execute(self.graph)
        with _spans.span("fit:verify"):
            verify_and_enforce(graph, context="fit")
        executor = GraphExecutor(graph, optimize=False)
        executor._prefixes = prefixes

        for node in sorted(graph.nodes):
            op = graph.operators.get(node)
            if not isinstance(op, DelegatingOperator):
                continue
            deps = graph.get_dependencies(node)
            transformer_dep, data_deps = deps[0], deps[1:]
            fit_transformer = executor.execute(transformer_dep).get()
            if not isinstance(fit_transformer, TransformerOperator):
                raise TypeError(
                    f"delegating node {node} resolved to {type(fit_transformer).__name__}"
                )
            with _spans.span("fit:splice"):
                graph = graph.set_operator(node, fit_transformer)
                graph = graph.set_dependencies(node, data_deps)
                # keep executor and graph views consistent for later delegating nodes
                executor._optimized = graph
                executor._memo.pop(node, None)

        with _spans.span("fit:fuse"):
            graph, _ = UnusedBranchRemovalRule().apply(graph, {})
            # The spliced graph is transformer-only: newly-adjacent chains
            # (fit transformer next to its featurization) fuse into single
            # compiled dispatches for the apply/serving path. The optimizer's
            # own fusion batch can't see these chains — they exist only after
            # delegating nodes collapse.
            return FittedPipeline(graph, self.source, self.sink).fused()

    # ------------------------------------------------------------------ gather
    @staticmethod
    def gather(branches: Sequence[Chainable]) -> "Pipeline":
        """Merge parallel branches into one pipeline emitting, per input,
        the list of branch outputs (reference: Pipeline.scala:119-154)."""
        from ..ops.util.gather import GatherTransformer

        graph = Graph()
        graph, source = graph.add_source()
        ends: List[NodeOrSourceId] = []
        for branch in branches:
            bp = branch.to_pipeline()
            combined, source_map, sink_map = graph.add_graph(bp.graph)
            mapped_source = source_map[bp.source]
            combined = combined.replace_dependency(mapped_source, source)
            combined = combined.remove_source(mapped_source)
            mapped_sink = sink_map[bp.sink]
            ends.append(combined.get_sink_dependency(mapped_sink))
            graph = combined.remove_sink(mapped_sink)
        graph, gather_node = graph.add_node(GatherTransformer(), ends)
        graph, sink = graph.add_sink(gather_node)
        return Pipeline(graph, source, sink)

    def to_dot(self) -> str:
        return self.graph.to_dot()


def _is_array(x: Any) -> bool:
    import numpy as np

    return hasattr(x, "shape") and hasattr(x, "dtype") and not isinstance(x, (np.generic,))


def _find_mapped_source(
    orig_graph: Graph, orig_source: SourceId, combined: Graph, base_graph: Graph
) -> SourceId:
    """Locate where ``orig_source`` landed after ``base_graph.add_graph(orig)``.

    ``add_graph`` remaps ids deterministically (sorted order past max id), so
    recompute the mapping the same way.
    """
    _, source_map, _ = base_graph.add_graph(orig_graph)
    return source_map[orig_source]


# ------------------------------------------------------------- fitted pipeline


class FittedPipeline(Transformer):
    """Transformer-only pipeline: serializable, no estimators, no re-fitting
    (reference: workflow/FittedPipeline.scala:22-48)."""

    def __init__(self, graph: Graph, source: SourceId, sink: SinkId):
        self.graph = graph
        self.source = source
        self.sink = sink
        # Serving-loop fast path: the datum-bound graph is built once and
        # reused; only the DatumOperator's payload is swapped per call,
        # under a lock so concurrent serving calls can't read each
        # other's datum. Safe because per-datum execution runs with
        # optimize=False — a fresh executor per call, no cross-call memo,
        # no prefix write-back keyed on the (mutated) operator.
        self._datum_op: Optional[DatumOperator] = None
        self._datum_graph: Optional[Graph] = None
        self._datum_lock = threading.Lock()
        self._compiled: Optional["CompiledApply"] = None

    def __getstate__(self):
        # save() must not pickle the last served datum (or the lock, or
        # the serving handle's bound graph/payload).
        state = self.__dict__.copy()
        state["_datum_op"] = None
        state["_datum_graph"] = None
        state["_datum_lock"] = None
        state["_compiled"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._datum_lock = threading.Lock()
        # Artifacts saved before the serving layer existed lack the slot.
        self._compiled = None

    def apply(self, datum: Any) -> Any:
        with self._datum_lock:
            with _spans.span("apply:bind"):
                if self._datum_graph is None:
                    self._datum_op = DatumOperator(datum)
                    graph, node = self.graph.add_node(self._datum_op, [])
                    graph = graph.replace_dependency(self.source, node)
                    self._datum_graph = graph.remove_source(self.source)
                else:
                    self._datum_op.datum = datum
                executor = GraphExecutor(self._datum_graph, optimize=False)
            return executor.execute(self.sink).get()

    def apply_batch(self, dataset: Dataset) -> Dataset:
        with _spans.span("apply:bind"):
            graph, node = self.graph.add_node(DatasetOperator(dataset), [])
            graph = graph.replace_dependency(self.source, node)
            graph = graph.remove_source(self.source)
            executor = GraphExecutor(graph, optimize=False)
        return executor.execute(self.sink).get()

    def fused(self) -> "FittedPipeline":
        """This pipeline with transformer chains collapsed into single
        compiled dispatches (workflow/fusion.py). Returns ``self`` when
        fusion is disabled or nothing fuses; otherwise a NEW pipeline
        (graph surgery never mutates in place). ``Pipeline.fit`` calls
        this, and the serving registry re-fuses loaded artifacts that
        were saved before fusion existed."""
        from .fusion import fuse_graph, fusion_enabled

        if not fusion_enabled():
            return self
        graph = fuse_graph(self.graph)
        if graph == self.graph:
            return self
        return FittedPipeline(graph, self.source, self.sink)

    def compiled_apply(self) -> "CompiledApply":
        """The serving-loop batch handle: graph bound once, only the
        dataset payload swapped per call (the batch analog of the datum
        fast path above). Cached on the pipeline — all servers applying
        this fitted pipeline share one handle."""
        if self._compiled is None:
            self._compiled = CompiledApply(self)
        return self._compiled

    # ---------------------------------------------------------- serialization
    def save(self, path: str) -> None:
        with open(path, "wb") as f:
            pickle.dump(self, f)

    @staticmethod
    def load(path: str) -> "FittedPipeline":
        with open(path, "rb") as f:
            out = pickle.load(f)
        if not isinstance(out, FittedPipeline):
            raise TypeError(f"{path} does not contain a FittedPipeline")
        return out


class CompiledApply:
    """Reusable batch-apply handle over a :class:`FittedPipeline`.

    ``apply_batch`` rebuilds the datum-bound graph on every call; a
    serving loop calls apply thousands of times per second, so this
    handle binds the graph ONCE and swaps only the ``DatasetOperator``
    payload per call, under a lock (same contract as the datum fast
    path: per-call execution runs optimize=False with a fresh executor,
    so no cross-call memo or prefix write-back sees the mutation).

    Shape discipline is the caller's job: feeding batches whose padded
    physical shapes cycle through a small bucket set means the jitted
    transformer bodies underneath hit XLA's executable cache instead of
    recompiling — see serving/batcher.py and utils/aot.warm_buckets.

    Multi-device serving: an eligible ``partition`` decision
    (parallel/partitioner.py, installed by ``attach_serving_partition``
    at warmup/load) places each batch's rows ``NamedSharding``-sharded
    over the mesh before binding, so the warmed executables run
    data-parallel. Placement is a pure function of the batch's physical
    rows (a bucket either always shards or never does), so the warmed
    layout set is exactly the steady-state layout set — zero
    steady-state compiles preserved.
    """

    def __init__(self, fitted: FittedPipeline):
        self._fitted = fitted
        self._op: Optional[DatasetOperator] = None
        self._graph: Optional[Graph] = None
        self._lock = threading.Lock()
        self.calls = 0
        #: PartitionDecision or None (parallel/partitioner.py).
        self.partition = None
        self._imbalance_gauge = None

    def __call__(self, dataset: Union[Dataset, Any]) -> Dataset:
        if not isinstance(dataset, Dataset):
            dataset = as_dataset(dataset)
        # One read: the attach path may swap the decision concurrently,
        # and placement + accounting must see the same one.
        partition = self.partition
        if partition is not None and isinstance(dataset, ArrayDataset):
            from ..parallel.partitioner import shard_rows

            physical = dataset.physical_rows
            dataset = ArrayDataset(
                shard_rows(partition, dataset.data),
                num_examples=dataset.num_examples,
            )
            if physical and physical % partition.shards == 0:
                if self._imbalance_gauge is None:
                    from ..obs import names as _names

                    self._imbalance_gauge = _names.metric(
                        _names.PARTITION_IMBALANCE
                    )
                self._imbalance_gauge.set(
                    1.0 - dataset.num_examples / physical, kind="serve"
                )
        fitted = self._fitted
        with self._lock:
            with _spans.span("apply:bind"):
                if self._graph is None:
                    self._op = DatasetOperator(dataset)
                    graph, node = fitted.graph.add_node(self._op, [])
                    graph = graph.replace_dependency(fitted.source, node)
                    self._graph = graph.remove_source(fitted.source)
                else:
                    self._op.dataset = dataset
                self.calls += 1
                executor = GraphExecutor(self._graph, optimize=False)
            return executor.execute(fitted.sink).get()
