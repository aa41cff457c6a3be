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
import weakref
from typing import Any, Dict, List, Optional, Tuple

from ..data.dataset import ArrayDataset, Dataset
from ..obs import names as _names
from ..obs import spans as _spans
from ..obs.device import to_device
from ..parallel.mesh import device_memory_limit_bytes
from ..reliability import faultinject
from ..reliability.recovery import reset_recovery_log
from .graph import Graph, GraphId, NodeId, SinkId, SourceId
from .operators import (
    DatasetExpression,
    DelegatingOperator,
    EstimatorOperator,
    Expression,
    TransformerOperator,
    node_span,
)
from .prefix import Prefix, PrefixTable, find_prefix
from .tracing import current_trace, timed_execute


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


# ------------------------------------------------------------- row chains
#
# A chain of row-by-row transformers (``Transformer.chunk_applier``) that a
# graph runs node by node holds every member's output at once: the memo
# keeps each until the execution ends. Where a host batch would not fit
# the device that way (2,048 images through dense SIFT are 13.8 GB of
# descriptors that only ever feed a sampler or a 2,048-float encoding) the
# chain runs over row chunks instead, and what is kept is what leaves its
# last member.

# The device's memory is asked for as its LIMIT (``device_memory_limit_bytes``:
# None on a backend that reports none, as the CPU, where nothing is ever
# chunked unless a test says otherwise) and not as what is free just now:
# ``bytes_in_use`` at the moment a chain is reached counts whatever earlier
# nodes have enqueued and the device has not finished, which differs from
# run to run, and a chain near the line would run whole in one request and
# in chunks (other programs, compiled on the spot) in the next. What else
# is resident (a fitted model) is the estimate's headroom to cover.

#: What a member's program holds besides its input and output, as a
#: multiple of the chain's largest output. Set from ONE program, dense
#: SIFT, the hungriest there is: it holds 3.1 times its descriptors (5.0
#: GiB beside 1.6 GiB at 256 images of 256 x 256, by the v5e compiler's
#: own memory analysis, PR 36; tests/workflow/test_row_chain_on_tpu.py
#: compiles it again and holds it under this), rounded up. By the same
#: analysis LCS holds 2.0 times its output and the Fisher encoder 2.5
#: times its input; a matmul holds next to nothing, so a chain of those
#: is chunked earlier than it must be (to the bit the same answer, in
#: more dispatches).
TEMPORARIES = 4.0


_SPEC_CACHE: "weakref.WeakKeyDictionary[Any, Dict[Any, Any]]" = weakref.WeakKeyDictionary()


def _spec_key(spec: Any):
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(spec)
    return treedef, tuple((tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves)


def _out_spec(transformer, in_spec):
    """``transformer``'s output spec for a batch of ``in_spec`` (a pytree
    of ``jax.ShapeDtypeStruct``), or None where nothing says: its
    ``out_spec`` (the verifier's protocol), else ``jax.eval_shape`` over
    its ``apply_arrays``. Kept per transformer instance and input spec: a
    fitted pipeline asks the same question every request."""
    import jax

    from .verify import UNKNOWN

    try:
        per_op = _SPEC_CACHE.setdefault(transformer, {})
    except TypeError:  # not weakly referenceable
        per_op = {}
    key = _spec_key(in_spec)
    if key not in per_op:
        out = None
        try:
            if callable(getattr(transformer, "out_spec", None)):
                out = transformer.out_spec([in_spec])
            elif hasattr(transformer, "apply_arrays"):
                # a fused chain's `apply_arrays` goes through its jit and
                # counts a trace; `_chain` is the same composition, bare
                fn = getattr(transformer, "_chain", transformer.apply_arrays)
                out = jax.eval_shape(fn, in_spec)
        except Exception:  # a spec nobody can give is no reason to fail a run
            out = None
        if out is UNKNOWN or not jax.tree_util.tree_leaves(out):
            out = None
        per_op[key] = out
    return per_op[key]


def _spec_bytes(spec) -> int:
    import jax
    import numpy as np

    return sum(
        int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(spec)
    )


def _take_rows(leaf, start: int, stop: int, rows: int):
    """Rows [start, stop) of the host array ``leaf``, zero rows appended
    up to ``rows`` (a view where nothing is appended)."""
    import numpy as np

    piece = leaf[start:stop]
    short = rows - (stop - start)
    if short <= 0:
        return piece
    return np.pad(piece, [(0, short)] + [(0, 0)] * (piece.ndim - 1))


def _join_rows(pieces: List[Any]):
    import numpy as np

    if all(isinstance(p, np.ndarray) for p in pieces):
        return np.concatenate(pieces, axis=0)
    import jax.numpy as jnp

    return jnp.concatenate([jnp.asarray(p) for p in pieces], axis=0)


class _RowChain:
    """The row-by-row transformers that end in one node, from the first
    whose input is already there (a bound dataset, a forced node, a node
    that is not row by row) down to that node, and the decision whether
    they run whole or over row chunks.

    Whole is the executor's ordinary pull and nothing else, and is what
    happens wherever nothing says otherwise: a backend that reports no
    memory, a head that is no plain array batch, a member whose output
    nobody can state, and a head that is ALREADY ON THE DEVICE whole
    (what follows it may still expand, but the batch has shown that it
    fits, and the chains of every cell that ran before this rule existed
    are of that kind: they run the programs they ran).

    A host batch runs in chunks where the estimate of a whole run (the
    head's bytes and every member's output, all alive together, and
    ``TEMPORARIES`` times the largest output for the programs' own
    workspace) passes the device's memory; a chunk is the largest power
    of two of rows whose estimate fits. Each chunk goes through every
    member's ``chunk_applier`` in turn (one compiled program a member:
    the last chunk is padded up to the chunk's shape and trimmed again),
    a member's output is let go of as soon as the next has it, the chunk
    is waited for before the next is enqueued (what is enqueued is
    allocated), and the last member's outputs are joined."""

    def __init__(self, head: Dataset, members: List[Tuple[Any, Any]]):
        self.head = head
        self.members = members  # [(node's operator, the transformer applied)]

    def chunk_rows(self, limit: Optional[int]) -> Optional[int]:
        """None: whole. Else the rows of a chunk."""
        import jax
        import numpy as np

        from .pipeline import is_masked_descriptors
        from .verify import _dataset_spec

        head = self.head
        if limit is None or not isinstance(head, ArrayDataset):
            return None
        leaves = jax.tree_util.tree_leaves(head.data)
        if (
            is_masked_descriptors(head.data) or head.num_examples < 2
            or not all(isinstance(leaf, np.ndarray) for leaf in leaves)
        ):
            return None
        spec = _dataset_spec(head, False)
        held, largest = _spec_bytes(spec), 0
        for _, transformer in self.members:
            spec = _out_spec(transformer, spec)
            if spec is None:
                return None
            held += _spec_bytes(spec)
            largest = max(largest, _spec_bytes(spec))
        estimate = held + TEMPORARIES * largest
        if estimate <= limit:
            return None
        a_row = estimate / head.physical_rows
        chunk = 1
        while chunk * 2 * a_row <= limit and chunk * 2 < head.num_examples:
            chunk *= 2
        return chunk

    def run(self, chunk_rows: int) -> Dataset:
        import jax

        head, n = self.head, self.head.num_examples
        starts = range(0, n, chunk_rows)
        appliers = [t.chunk_applier() for _, t in self.members]
        outputs: List[ArrayDataset] = []
        with _spans.span(
            "exec:chunks", rows=n, chunk_rows=chunk_rows, chunks=len(starts),
            reason="footprint", members=len(self.members),
        ):
            _names.metric(_names.EXEC_CHUNKS).inc(len(starts), reason="footprint")
            for start in starts:
                stop = min(start + chunk_rows, n)
                chunk: Dataset = ArrayDataset(
                    jax.tree_util.tree_map(
                        lambda leaf: _take_rows(leaf, start, stop, chunk_rows), head.data
                    ),
                    num_examples=stop - start,
                )
                for (op, _), applier in zip(self.members, appliers):
                    with node_span(op):
                        chunk = applier(chunk)
                kept = chunk.num_examples
                if chunk.physical_rows != kept:
                    chunk = ArrayDataset(
                        jax.tree_util.tree_map(lambda leaf: leaf[:kept], chunk.data), kept
                    )
                # every chunk enqueued has its buffers allocated: no
                # further ahead of the device than this one
                jax.block_until_ready(chunk.data)  # keystone: allow-sync
                outputs.append(chunk)
        joined = jax.tree_util.tree_map(
            lambda *leaves: _join_rows(list(leaves)), *[o.data for o in outputs]
        )
        return ArrayDataset(joined, num_examples=sum(o.num_examples for o in outputs))


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
        # Nodes whose row chain was found to fit whole: not asked again.
        self._whole: set = set()
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
        if self._ends_row_chain(graph_id):
            expression = timed_execute(
                op, deps,
                execute=lambda deps: DatasetExpression(
                    lambda: self._run_row_chain(graph_id, op, deps)
                ),
            )
        else:
            expression = timed_execute(op, deps)

        prefix = self._prefixes.get(graph_id)
        expression = _wrap_reliability(op, deps, expression, prefix)

        # Prefix write-back: make this node's result reusable by later
        # pipelines (reference: GraphExecutor.scala:65-71).
        if prefix is not None:
            PipelineEnv.get_or_create().state[prefix] = expression

        self._memo[graph_id] = expression
        return expression

    def _expression(self, graph_id: GraphId) -> Expression:
        """``graph_id``'s expression, looked at from inside a thunk: the
        memoized one where there is one (no memo hit is counted: nothing
        of the graph asked), else the ordinary pull."""
        found = self._memo.get(graph_id)
        return found if found is not None else self.execute(graph_id)

    # ------------------------------------------------------------ row chains
    def _row_wise(self, node: GraphId, resolve: bool):
        """``(data dependency, transformer)`` where ``node`` applies a
        row-by-row transformer to one dataset, else None. A delegating
        node's transformer is its estimator's fit: looked at only where
        ``resolve`` (forcing the fit, as the node's own thunk would first
        of all)."""
        if not isinstance(node, NodeId):
            return None
        graph = self.graph
        op = graph.get_operator(node)
        deps = graph.get_dependencies(node)
        if isinstance(op, DelegatingOperator) and len(deps) == 2:
            if not resolve:
                return deps[1], None
            transformer = self._expression(deps[0]).get()
            data = deps[1]
        elif isinstance(op, TransformerOperator) and len(deps) == 1:
            transformer, data = op, deps[0]
        else:
            return None
        applier = getattr(transformer, "chunk_applier", None)
        if applier is None or applier() is None:
            return None
        return data, transformer

    def _ends_row_chain(self, node: NodeId) -> bool:
        """Whether ``node`` and the node it reads are both (as far as can
        be said without fitting anything) row by row: only then is there
        a chain to think about when ``node`` is forced."""
        here = self._row_wise(node, resolve=False)
        return here is not None and self._row_wise(here[0], resolve=False) is not None

    def _chain_above(self, node: NodeId, past_forced: bool):
        """``(head, [(operator, transformer)] from the head down, their
        nodes)``: the row-by-row nodes that end in ``node``. The chain
        that can run starts after a node whose output is already there;
        ``past_forced`` walks on through such nodes, to the chain there
        would have been had nothing forced them."""
        members: List[Tuple[Any, Any]] = []
        nodes: List[NodeId] = []
        cur: GraphId = node
        while True:
            memo = self._memo.get(cur)
            if not past_forced and cur != node and memo is not None and memo.forced:
                break
            found = self._row_wise(cur, resolve=True)
            if found is None:
                break
            members.append((self.graph.get_operator(cur), found[1]))
            nodes.append(cur)
            cur = found[0]
        members.reverse()
        return cur, members, nodes

    def _run_row_chain(self, node: NodeId, op, deps) -> Dataset:
        """``node``'s dataset: the operator's own thunk where its chain
        fits the device whole (or nothing can be said), over row chunks
        where it does not."""
        if node in self._whole:  # a longer chain it is part of fits whole
            return op.execute(deps).get()
        limit = device_memory_limit_bytes()
        head, members, nodes = self._chain_above(node, past_forced=False)
        chunk_rows = None
        if len(members) >= 2:
            chain = _RowChain(self._expression(head).get(), members)
            chunk_rows = chain.chunk_rows(limit)
        if chunk_rows is None:
            self._refuse_what_a_session_keeps_whole(node, op, limit)
            self._whole.update(nodes)  # decided once for all of them
            return op.execute(deps).get()
        return chain.run(chunk_rows)

    def _refuse_what_a_session_keeps_whole(self, node: NodeId, op, limit) -> None:
        """A span session (or ``trace()``) forces every node whole as it
        is reached, so under one no chain is left to run in chunks. Where
        the chain would have, say so before the program that cannot fit
        is launched: tracing must not decide whether a fit fits silently."""
        if current_trace() is None and _spans.active_session() is None:
            return
        head, members, _ = self._chain_above(node, past_forced=True)
        if len(members) < 2:
            return
        rows = _RowChain(self._expression(head).get(), members).chunk_rows(limit)
        if rows is not None:
            raise RuntimeError(
                f"node:{getattr(op, 'label', type(op).__name__)}: the chain of {len(members)} "
                f"row-by-row transformers that ends here does not fit the device whole and "
                f"would run in chunks of {rows} rows, but a span session (or trace()) forces "
                f"every node whole as it is reached. Run this outside the session: the "
                f"program's spans reach a jax.profiler trace without one."
            )

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
