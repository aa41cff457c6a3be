"""Whole-pipeline fusion: chain detection, boundaries, parity, dispatch
accounting, and serving integration (workflow/fusion.py)."""

import pickle

import numpy as np
import pytest

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.obs import names as _names
from keystone_tpu.workflow import (
    BatchTransformer,
    FittedPipeline,
    FusedTransformerOperator,
    Pipeline,
    fuse_graph,
    fusion_disabled,
)
from keystone_tpu.workflow.executor import PipelineEnv
from keystone_tpu.workflow.fusion import NodeFusionRule, is_fusable
from keystone_tpu.workflow.rules import default_optimizer


class Scale(BatchTransformer):
    def __init__(self, c):
        self.c = float(c)

    @property
    def label(self):
        return f"Scale[{self.c}]"

    def apply_arrays(self, x):
        return x * self.c


class Shift(BatchTransformer):
    def __init__(self, c):
        self.c = float(c)

    @property
    def label(self):
        return f"Shift[{self.c}]"

    def apply_arrays(self, x):
        return x + self.c


class CustomBatch(BatchTransformer):
    """Overrides apply_batch → must never fuse."""

    def apply_arrays(self, x):
        return x

    def apply_batch(self, dataset):
        return dataset


def _chain(*ops):
    pipe = ops[0].to_pipeline()
    for op in ops[1:]:
        pipe = pipe.then(op)
    return pipe


def _append_operator(pipe, op):
    """Append a bare TransformerOperator (no Chainable mixin — e.g. a
    CacherOperator) to a pipeline's sink by direct graph surgery."""
    graph = pipe.graph
    graph, node = graph.add_node(op, [graph.get_sink_dependency(pipe.sink)])
    graph = graph.set_sink_dependency(pipe.sink, node)
    return Pipeline(graph, pipe.source, pipe.sink)


def _fused_ops(graph):
    return [
        op for op in graph.operators.values()
        if isinstance(op, FusedTransformerOperator)
    ]


def _labels(graph):
    return sorted(
        getattr(op, "label", type(op).__name__) for op in graph.operators.values()
    )


def _dispatch_counts():
    c = _names.metric(_names.FUSION_BATCH_DISPATCHES)
    return c.value(fused="1"), c.value(fused="0")


x4 = np.arange(24, dtype=np.float32).reshape(4, 6)


# ----------------------------------------------------------------- structure


def test_four_node_chain_fuses_to_one_node():
    pipe = _chain(Scale(2), Shift(1), Scale(3), Shift(-2))
    res = pipe(ArrayDataset(x4))
    res.get()
    graph = res._executor.graph
    fused = _fused_ops(graph)
    assert len(fused) == 1
    assert fused[0].member_labels == (
        "Scale[2.0]", "Shift[1.0]", "Scale[3.0]", "Shift[-2.0]",
    )
    # only the dataset node and the fused node remain
    assert len(graph.nodes) == 2


def test_fusion_rule_is_in_default_optimizer():
    # fusion is the last STRUCTURAL batch; only the streaming planner
    # (which absorbs already-fused chains), the measured-knob pass
    # (which re-parameterizes, never restructures), and the partition
    # pass (which pins placement decisions onto final operators) may
    # follow it.
    names = [b.name for b in default_optimizer().batches]
    assert names[-4:] == ["fusion", "streaming", "measured-knobs", "partition"]
    from keystone_tpu.workflow.rules import auto_caching_optimizer

    names = [b.name for b in auto_caching_optimizer().batches]
    # fusion strictly after auto-cache: cache planning sees real nodes
    assert names.index("fusion") == names.index("auto-cache") + 1
    assert names[-3:] == ["streaming", "measured-knobs", "partition"]


def test_cacher_is_a_fusion_boundary():
    from keystone_tpu.ops.util.misc import CacherOperator

    # Scale→Shift → Cacher → Scale→Shift: one fused chain each side
    pipe = _append_operator(_chain(Scale(2), Shift(1)), CacherOperator())
    pipe = pipe.then(Scale(3)).then(Shift(4))
    fused_graph = fuse_graph(pipe.graph)
    fused = _fused_ops(fused_graph)
    assert len(fused) == 2
    assert sorted(f.member_labels for f in fused) == [
        ("Scale[2.0]", "Shift[1.0]"),
        ("Scale[3.0]", "Shift[4.0]"),
    ]
    assert any(
        isinstance(op, CacherOperator) for op in fused_graph.operators.values()
    )


def test_prefix_marked_node_is_not_fused():
    pipe = _chain(Scale(2), Shift(1), Scale(3))
    graph = pipe.graph
    # mark the middle node as a saveable-prefix cut point
    middle = next(
        n for n in graph.nodes if graph.get_operator(n).label == "Shift[1.0]"
    )
    out, _ = NodeFusionRule().apply(graph, {middle: object()})
    # the cut point keeps its own node; the remaining neighbors are
    # singletons, so nothing fuses at all
    assert _fused_ops(out) == []
    assert middle in out.nodes


def test_branch_point_cuts_chain():
    """A node consumed by two downstream nodes stays host-visible."""
    a = Scale(2)
    pipe_a = a.to_pipeline()
    b1 = pipe_a.then(Shift(1)).then(Scale(5))
    gathered = Pipeline.gather([b1, pipe_a.then(Shift(3))])
    res = gathered(ArrayDataset(x4))
    got = res.get()
    graph = res._executor.graph
    for fused in _fused_ops(graph):
        # chains never swallow the shared Scale[2.0] producer
        assert "Scale[2.0]" not in fused.member_labels
    with fusion_disabled():
        PipelineEnv.reset()
        ref = gathered(ArrayDataset(x4)).get()
    for g, r in zip(got.collect(), ref.collect()):
        np.testing.assert_allclose(
            np.asarray(g, dtype=np.float32), np.asarray(r, dtype=np.float32),
            rtol=1e-6,
        )


def test_bespoke_apply_batch_is_not_fusable():
    assert is_fusable(Scale(2))
    assert not is_fusable(CustomBatch())  # overrides apply_batch
    from keystone_tpu.ops.util.misc import CacherOperator

    assert not is_fusable(CacherOperator())  # not a BatchTransformer
    from keystone_tpu.ops.learning.kernel import KernelBlockLinearMapper

    assert KernelBlockLinearMapper.fusable is False  # explicit opt-out


def test_fusable_opt_out_flag():
    class OptedOut(Scale):
        fusable = False

    pipe = _chain(OptedOut(2), Shift(1), Scale(3))
    out = fuse_graph(pipe.graph)
    fused = _fused_ops(out)
    assert len(fused) == 1
    assert "Scale" in fused[0].member_labels[0] or fused[0].member_labels == (
        "Shift[1.0]", "Scale[3.0]",
    )


def test_nested_fusion_flattens():
    inner = FusedTransformerOperator([Scale(2), Shift(1)])
    outer = FusedTransformerOperator([inner, Scale(3)])
    assert outer.member_labels == ("Scale[2.0]", "Shift[1.0]", "Scale[3.0]")


# --------------------------------------------------------------------- parity


def _parity(pipe, data, rel=1e-5):
    PipelineEnv.reset()
    got = pipe(data).get()
    PipelineEnv.reset()
    with fusion_disabled():
        ref = pipe(data).get()
    g = np.asarray(got.data, dtype=np.float64)
    r = np.asarray(ref.data, dtype=np.float64)
    err = np.linalg.norm(g - r) / max(np.linalg.norm(r), 1e-30)
    assert err <= rel, f"fused vs unfused rel_err {err}"
    return g


def test_parity_mnist_fft_featurizer():
    from keystone_tpu.pipelines.mnist_random_fft import (
        MnistRandomFFTConfig,
        build_featurizer,
    )

    featurizer = build_featurizer(MnistRandomFFTConfig(num_ffts=2), image_size=64)
    x = np.random.default_rng(0).normal(size=(16, 64)).astype(np.float32)
    _parity(featurizer, ArrayDataset(x))


def test_parity_cifar_patch_chain():
    from keystone_tpu.ops.images.core import (
        Convolver,
        ImageVectorizer,
        Pooler,
        SymmetricRectifier,
    )

    rng = np.random.default_rng(1)
    filters = rng.normal(size=(4, 3 * 3 * 3)).astype(np.float32)
    chain = _chain(
        Convolver(filters, img_channels=3, normalize_patches=False),
        SymmetricRectifier(alpha=0.25),
        Pooler(2, 2, None, "sum"),
        ImageVectorizer(),
    )
    imgs = rng.normal(size=(6, 8, 8, 3)).astype(np.float32)
    res = chain(ArrayDataset(imgs))
    graph = res._executor.graph
    assert len(_fused_ops(graph)) == 1
    assert len(_fused_ops(graph)[0].members) == 4
    _parity(chain, ArrayDataset(imgs))


def test_parity_with_cacher_boundary():
    from keystone_tpu.ops.util.misc import CacherOperator

    pipe = _append_operator(_chain(Scale(2), Shift(1)), CacherOperator())
    pipe = pipe.then(Scale(0.5)).then(Shift(-3))
    _parity(pipe, ArrayDataset(x4), rel=1e-6)


def test_parity_padded_rows_stay_zero():
    """Pad-row re-zeroing once at the end equals once per member."""
    data = ArrayDataset(np.ones((6, 4), np.float32), num_examples=4)
    pipe = _chain(Shift(2), Scale(3), Shift(-1))
    PipelineEnv.reset()
    out = pipe(data).get()
    assert out.num_examples == 4
    arr = np.asarray(out.data)
    np.testing.assert_array_equal(arr[4:], 0.0)
    PipelineEnv.reset()
    with fusion_disabled():
        ref = pipe(data).get()
    np.testing.assert_allclose(arr, np.asarray(ref.data), rtol=1e-6)


# ---------------------------------------------------------- dispatch counting


def test_four_node_chain_is_exactly_one_dispatch():
    pipe = _chain(Scale(2), Shift(1), Scale(3), Shift(-2))
    data = ArrayDataset(np.ones((4, 6), np.float32))

    PipelineEnv.reset()
    before_f, before_u = _dispatch_counts()
    pipe(data).get()
    after_f, after_u = _dispatch_counts()
    assert after_f - before_f == 1, "fused chain must dispatch exactly once"
    assert after_u - before_u == 0

    PipelineEnv.reset()
    with fusion_disabled():
        before_f, before_u = _dispatch_counts()
        pipe(data).get()
        after_f, after_u = _dispatch_counts()
    assert after_f - before_f == 0
    assert after_u - before_u == 4, "unfused chain pays one dispatch per node"


def test_fused_chain_compiles_once():
    from keystone_tpu.utils.compilation_cache import (
        compile_count,
        install_compile_counter,
    )

    install_compile_counter()
    PipelineEnv.reset()
    fitted = _chain(Scale(7), Shift(2), Scale(0.5), Shift(1)).fit()
    assert len(_fused_ops(fitted.graph)) == 1
    # fresh, never-seen shape so the fused executable must compile here
    before = compile_count()
    fitted.apply_batch(ArrayDataset(np.ones((5, 11), np.float32)))
    delta = compile_count() - before
    assert delta == 1, f"4-node fused chain compiled {delta} executables"
    # steady state (the serving contract): same shape, zero compiles
    before = compile_count()
    fitted.apply_batch(ArrayDataset(np.ones((5, 11), np.float32)))
    assert compile_count() - before == 0


def test_fusion_metrics_move():
    reg_before = {
        "chains": _names.metric(_names.FUSION_CHAINS).total(),
        "nodes": _names.metric(_names.FUSION_FUSED_NODES).total(),
        "saved": _names.metric(_names.FUSION_DISPATCHES_SAVED).total(),
        "compiles": _names.metric(_names.FUSION_COMPILES).total(),
    }
    pipe = _chain(Scale(2), Shift(1), Scale(3))
    PipelineEnv.reset()
    pipe(ArrayDataset(np.ones((3, 9), np.float32))).get()
    assert _names.metric(_names.FUSION_CHAINS).total() - reg_before["chains"] == 1
    assert _names.metric(_names.FUSION_FUSED_NODES).total() - reg_before["nodes"] == 3
    assert _names.metric(_names.FUSION_DISPATCHES_SAVED).total() - reg_before["saved"] == 2
    assert _names.metric(_names.FUSION_COMPILES).total() - reg_before["compiles"] >= 1


def test_repeated_unfitted_apply_shares_one_compiled_chain():
    """Every optimizer run builds a fresh FusedTransformerOperator, but
    chains over the same member instances share one jitted callable —
    re-applying an unfitted pipeline must not retrace/recompile."""
    from keystone_tpu.utils.compilation_cache import (
        compile_count,
        install_compile_counter,
    )

    install_compile_counter()
    pipe = _chain(Scale(1.5), Shift(2), Scale(3))
    PipelineEnv.reset()
    pipe(ArrayDataset(np.ones((6, 7), np.float32))).get()  # compiles once
    before = compile_count()
    for _ in range(3):
        PipelineEnv.reset()
        pipe(ArrayDataset(np.ones((6, 7), np.float32))).get()
    assert compile_count() - before == 0, (
        "re-optimized fused chains over the same members recompiled"
    )


def test_a_refitted_chain_of_the_same_structure_builds_no_program():
    """The members' arrays are arguments of the fused program, not
    constants: a model fitted anew (other weights, the same shapes) runs
    the program the first one built, and gives its own answer."""
    from keystone_tpu.utils.compilation_cache import (
        compile_count,
        install_compile_counter,
    )

    install_compile_counter()
    x = ArrayDataset(x_rows)
    first, _ = _fit_both(_scoring(3, path=True))
    a = np.asarray(first.apply_batch(x).data)
    refit, unfused = _fit_both(_scoring(3, path=True, seed=40))
    before = compile_count()
    b = np.asarray(refit.apply_batch(x).data)
    assert compile_count() - before == 0
    np.testing.assert_array_equal(b, np.asarray(unfused.apply_batch(x).data))
    assert not np.array_equal(a, b)


def test_a_member_that_reads_an_array_value_is_closed_over():
    """A member that needs an array's VALUE while traced keeps it a
    constant: the chain is still one compiled dispatch."""
    import jax.numpy as jnp

    class Repeat(BatchTransformer):
        def __init__(self, times):
            self.times = jnp.asarray(times)

        def apply_arrays(self, x):
            return jnp.tile(x, (1, int(self.times)))

    pipe = _chain(Shift(1), Repeat(2))
    PipelineEnv.reset()
    fitted = pipe.fit()
    (fused,) = _fused_ops(fitted.graph)
    before_f, before_u = _dispatch_counts()
    out = fitted.apply_batch(ArrayDataset(np.ones((3, 4), np.float32)))
    assert _dispatch_counts() == (before_f + 1, before_u)
    np.testing.assert_array_equal(np.asarray(out.data), 2.0 * np.ones((3, 8)))
    assert fused._eager_fallback is False and fused._lift is False


def test_a_member_array_on_one_device_serves_a_batch_sharded_over_all():
    """An array committed to one device is no argument of a program whose
    input is sharded over the mesh: the chain closes over it, as before."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs a mesh of two devices or more")

    class Weigh(BatchTransformer):
        def __init__(self, w):
            self.w = jax.device_put(w, devices[0])

        def apply_arrays(self, x):
            return x @ self.w

    w = np.arange(12, dtype=np.float32).reshape(6, 2)
    pipe = _chain(Shift(1), Weigh(w))
    PipelineEnv.reset()
    fitted = pipe.fit()
    (fused,) = _fused_ops(fitted.graph)
    mesh = Mesh(np.array(devices), ("data",))
    rows = 2 * len(devices)
    x = jax.device_put(np.ones((rows, 6), np.float32), NamedSharding(mesh, PartitionSpec("data")))
    out = fitted.apply_batch(ArrayDataset(x))
    np.testing.assert_array_equal(np.asarray(out.data), np.full((rows, 6), 2.0) @ w)
    assert fused._lift is False and fused._eager_fallback is False


def test_untraceable_member_falls_back_to_eager():
    class HostBranch(BatchTransformer):
        """Reads a concrete value at trace time — not jit-traceable."""

        def apply_arrays(self, x):
            if float(np.asarray(x).sum()) >= 0:  # host read of a tracer
                return x * 2.0
            return x

    pipe = _chain(Shift(1), HostBranch())
    PipelineEnv.reset()
    fitted = pipe.fit()
    (fused,) = _fused_ops(fitted.graph)
    out = fitted.apply_batch(ArrayDataset(np.ones((3, 4), np.float32)))
    np.testing.assert_allclose(np.asarray(out.data), 4.0)
    assert fused._eager_fallback is True


def test_runtime_errors_propagate_without_unfusing():
    """Only trace failures demote to eager; a runtime error from the
    chain must propagate (reliability layer's business) and must NOT
    silently drop the single-dispatch guarantee."""
    class Boom(Scale):
        def apply_arrays(self, x):
            raise RuntimeError("device exploded")

    pipe = _chain(Scale(2), Boom(1))
    PipelineEnv.reset()
    fitted = pipe.fit()
    (fused,) = _fused_ops(fitted.graph)
    with pytest.raises(RuntimeError, match="device exploded"):
        fitted.apply_batch(ArrayDataset(np.ones((3, 4), np.float32)))
    assert fused._eager_fallback is False


# ------------------------------------------------------- autocache stability


def test_autocache_decisions_identical_with_fusion_on():
    """Cache insertion happens before fusion, so the set of inserted
    Cacher nodes must not depend on the fusion switch."""
    from keystone_tpu.ops.util.misc import CacherOperator
    from keystone_tpu.workflow.rules import auto_caching_optimizer

    def cachers(with_fusion: bool):
        PipelineEnv.reset()
        env = PipelineEnv.get_or_create()
        env.optimizer = auto_caching_optimizer(strategy="aggressive")
        shared = _chain(Scale(2), Shift(1))
        fan = Pipeline.gather([shared.then(Scale(3)), shared.then(Shift(5))])
        if with_fusion:
            res = fan(ArrayDataset(x4))
        else:
            with fusion_disabled():
                res = fan(ArrayDataset(x4))
        graph = res._executor.graph
        return sum(
            isinstance(op, CacherOperator) for op in graph.operators.values()
        )

    assert cachers(True) == cachers(False)


# -------------------------------------------------------------- serialization


def test_fused_fitted_pipeline_pickles(tmp_path):
    pipe = _chain(Scale(2), Shift(1), Scale(3))
    PipelineEnv.reset()
    fitted = pipe.fit()
    assert len(_fused_ops(fitted.graph)) == 1
    path = str(tmp_path / "fused.pkl")
    fitted.save(path)
    loaded = FittedPipeline.load(path)
    out = loaded.apply_batch(ArrayDataset(x4))
    ref = fitted.apply_batch(ArrayDataset(x4))
    np.testing.assert_allclose(np.asarray(out.data), np.asarray(ref.data))


def test_registry_refuses_nothing_and_refuses_loaded_artifacts(tmp_path):
    """Artifacts saved UNFUSED are re-fused by the serving registry —
    through both load doors (fitted artifact and reliability checkpoint)."""
    import pickle as _pickle

    from keystone_tpu.serving.registry import ModelRegistry

    with fusion_disabled():
        PipelineEnv.reset()
        fitted = _chain(Scale(2), Shift(1), Scale(3)).fit()
    assert _fused_ops(fitted.graph) == []
    path = str(tmp_path / "unfused.pkl")
    fitted.save(path)
    registry = ModelRegistry()
    entry = registry.load_fitted("m", path)
    assert len(_fused_ops(entry.model.graph)) == 1
    out = entry.batch_apply(ArrayDataset(x4))
    np.testing.assert_allclose(
        np.asarray(out.data),
        np.asarray(fitted.apply_batch(ArrayDataset(x4)).data),
        rtol=1e-6,
    )
    # checkpoint door: same re-fusion
    with open(tmp_path / "abcdef123456.pkl", "wb") as f:
        _pickle.dump(fitted, f)
    ckpt = registry.load_checkpoint("c", str(tmp_path), "abcdef")
    assert len(_fused_ops(ckpt.model.graph)) == 1


# ------------------------------------------------------------------- serving


@pytest.mark.serving
def test_serving_zero_compiles_after_warmup_with_fusion():
    from keystone_tpu.serving import PipelineServer, ServingConfig
    from keystone_tpu.serving.synthetic import (
        synthetic_chain_pipeline,
        synthetic_requests,
    )

    d = 16
    fitted = synthetic_chain_pipeline(num_nodes=4, d=d, fused=True)
    assert len(_fused_ops(fitted.graph)) == 1
    server = PipelineServer(
        fitted, config=ServingConfig(max_batch=4, max_wait_ms=1.0, queue_depth=64)
    ).start()
    try:
        server.warmup(np.zeros((d,), np.float32))
        for f in server.submit_many(synthetic_requests(24, d=d)):
            f.result(timeout=30)
        stats = server.stats()
    finally:
        server.stop()
    assert stats["served"] == 24
    assert stats["xla_compiles_since_warmup"] == 0


def test_synthetic_chain_fused_unfused_parity():
    from keystone_tpu.serving.synthetic import synthetic_chain_pipeline

    d = 8
    x = np.random.default_rng(3).normal(size=(5, d)).astype(np.float32)
    fused = synthetic_chain_pipeline(num_nodes=5, d=d, seed=7, fused=True)
    unfused = synthetic_chain_pipeline(num_nodes=5, d=d, seed=7, fused=False)
    assert len(_fused_ops(fused.graph)) == 1
    assert _fused_ops(unfused.graph) == []
    a = np.asarray(fused.apply_batch(ArrayDataset(x)).data, dtype=np.float64)
    b = np.asarray(unfused.apply_batch(ArrayDataset(x)).data, dtype=np.float64)
    err = np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)
    assert err <= 1e-5


# ------------------------------------------------- fitted gathers of branches


def _branches(k, path=False, seed=0, d_in=6, d_out=8):
    """k fusable branches over one input: cosine features, the last of
    them a chain of two nodes where ``path`` (a branch may be a path)."""
    from keystone_tpu.ops.stats.core import CosineRandomFeatures

    out = [
        CosineRandomFeatures.create(d_in, d_out, 0.7, seed=seed + i).to_pipeline()
        for i in range(k)
    ]
    if path:
        out[-1] = _chain(Scale(1.5), Shift(-2))
    return out


def _scoring(k, path=False, seed=0, classes=5, with_mapper=True, with_classifier=True):
    from keystone_tpu.ops.learning.block import BlockLinearMapper
    from keystone_tpu.ops.util.labels import MaxClassifier
    from keystone_tpu.ops.util.vectors import VectorCombiner

    pipe = Pipeline.gather(_branches(k, path, seed)) >> VectorCombiner()
    if with_mapper:
        rng = np.random.default_rng(seed + k)
        d = 8 * k - (2 if path else 0)
        pipe = pipe >> BlockLinearMapper(
            rng.normal(size=(d, classes)).astype(np.float32), 8,
            intercept=rng.normal(size=classes).astype(np.float32),
            feature_mean=rng.normal(size=d).astype(np.float32),
        )
        if with_classifier:
            pipe = pipe >> MaxClassifier()
    return pipe


def _fit_both(pipe):
    PipelineEnv.reset()
    fitted = pipe.fit()
    PipelineEnv.reset()
    with fusion_disabled():
        unfused = pipe.fit()
    return fitted, unfused


def _gathers_folded():
    return _names.metric(_names.FUSION_GATHERS).total()


x_rows = np.random.default_rng(9).normal(size=(7, 6)).astype(np.float32)


@pytest.mark.parametrize("k,path", [(2, False), (3, False), (3, True)])
def test_fitted_gather_into_a_linear_map_is_one_fused_operator(k, path):
    from keystone_tpu.ops.learning.block import BlockLinearMapper
    from keystone_tpu.ops.util.labels import MaxClassifier
    from keystone_tpu.workflow.fusion import FusedGather

    before = _gathers_folded()
    fitted, unfused = _fit_both(_scoring(k, path))
    assert _gathers_folded() - before == 1
    (fused,) = _fused_ops(fitted.graph)
    assert len(fitted.graph.nodes) == 1
    gather, mapper, classifier = fused.members
    assert isinstance(gather, FusedGather) and len(gather.branches) == k
    assert gather.label == "Gather[" + (
        f"{k - 1}*CosineRandomFeatures,Fused[Scale[1.5]+Shift[-2.0]]" if path
        else f"{k}*CosineRandomFeatures"
    ) + "]"
    assert isinstance(mapper, BlockLinearMapper)  # a direct member
    assert isinstance(classifier, MaxClassifier)

    before_f, before_u = _dispatch_counts()
    got = np.asarray(fitted.apply_batch(ArrayDataset(x_rows)).data)
    after_f, after_u = _dispatch_counts()
    assert (after_f - before_f, after_u - before_u) == (1, 0)
    np.testing.assert_array_equal(
        got, np.asarray(unfused.apply_batch(ArrayDataset(x_rows)).data)
    )


@pytest.mark.parametrize("k", [2, 3])
def test_fused_gather_features_to_the_bit_and_scores_to_rounding(k):
    fitted, unfused = _fit_both(_scoring(k, with_mapper=False))
    assert len(_fused_ops(fitted.graph)) == 1
    np.testing.assert_array_equal(
        np.asarray(fitted.apply_batch(ArrayDataset(x_rows)).data),
        np.asarray(unfused.apply_batch(ArrayDataset(x_rows)).data),
    )
    fitted, unfused = _fit_both(_scoring(k, path=k == 3, with_classifier=False))
    got = np.asarray(fitted.apply_batch(ArrayDataset(x_rows)).data)
    ref = np.asarray(unfused.apply_batch(ArrayDataset(x_rows)).data)
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-5 * np.abs(ref).max())


def test_fused_gather_keeps_padded_rows_zero_and_per_datum_apply():
    fitted, unfused = _fit_both(_scoring(3, path=True, with_classifier=False))
    data = ArrayDataset(np.ones((6, 6), np.float32), num_examples=4)
    out = fitted.apply_batch(data)
    assert out.num_examples == 4
    np.testing.assert_array_equal(np.asarray(out.data)[4:], 0.0)
    # (an unfused graph runs a two-node branch a datum at a time as a
    # row chain, which takes datasets only: the reference here has none)
    fitted, unfused = _fit_both(_scoring(3, with_classifier=False))
    for row in x_rows[:3]:
        got, ref = np.asarray(fitted.apply(row)), np.asarray(unfused.apply(row))
        assert got.shape == ref.shape
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-5 * np.abs(ref).max())
    # the features of one datum: a one-row product, summed in its own order
    fitted, unfused = _fit_both(_scoring(2, with_mapper=False))
    got, ref = np.asarray(fitted.apply(x_rows[0])), np.asarray(unfused.apply(x_rows[0]))
    assert got.shape == ref.shape == (16,)
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)


def test_fused_gather_pickles_and_refuses_to_itself(tmp_path):
    fitted, _ = _fit_both(_scoring(3, path=True))
    path = str(tmp_path / "gather.pkl")
    fitted.save(path)
    loaded = FittedPipeline.load(path)
    before = _gathers_folded()
    assert loaded.fused() is loaded
    assert fitted.fused() is fitted
    assert _gathers_folded() == before
    np.testing.assert_array_equal(
        np.asarray(loaded.apply_batch(ArrayDataset(x_rows)).data),
        np.asarray(fitted.apply_batch(ArrayDataset(x_rows)).data),
    )


def _gather_of(branches):
    from keystone_tpu.ops.util.vectors import VectorCombiner

    return Pipeline.gather(branches) >> VectorCombiner()


def test_gather_with_a_bespoke_branch_is_left_alone():
    from keystone_tpu.ops.util.gather import GatherTransformer

    pipe = _gather_of(_branches(2) + [CustomBatch().to_pipeline()])
    before = _gathers_folded()
    fitted, _ = _fit_both(pipe)
    assert _gathers_folded() == before
    assert any(isinstance(op, GatherTransformer) for op in fitted.graph.operators.values())


def test_gather_whose_branch_has_a_second_consumer_is_left_alone():
    from keystone_tpu.ops.util.gather import GatherTransformer

    pipe = _gather_of(_branches(2))
    graph = pipe.graph
    end = graph.get_dependencies(
        next(n for n in graph.nodes if isinstance(graph.get_operator(n), GatherTransformer))
    )[0]
    graph, _ = graph.add_sink(end)  # the branch's output is read a second time
    before = _gathers_folded()
    out = fuse_graph(graph)
    assert _gathers_folded() == before
    assert any(isinstance(op, GatherTransformer) for op in out.operators.values())


def test_timit_in_core_fit_is_not_planned_onto_streaming(monkeypatch):
    """Fit time keeps the gather form: folded there, the estimator's
    input would be one chain, and a bound input above 2 x chunk rows
    would be streamed (another solver path)."""
    from keystone_tpu.pipelines import timit
    from keystone_tpu.workflow.fusion import FusedGather
    from keystone_tpu.workflow.streaming import stream_chunk_rows

    monkeypatch.setenv("KEYSTONE_STREAM_CHUNK_ROWS", "64")
    train = timit.synthetic_timit(256, seed=3)
    assert len(train.data) > 2 * stream_chunk_rows()
    config = timit.TimitConfig(num_cosines=2, num_cosine_features=64, reg=5.0, num_epochs=1)
    plans = _names.metric(_names.STREAM_PLANS).total()
    PipelineEnv.reset()
    fitted = timit.build_pipeline(config, train).fit()
    assert _names.metric(_names.STREAM_PLANS).total() == plans
    (fused,) = _fused_ops(fitted.graph)
    assert isinstance(fused.members[0], FusedGather)
