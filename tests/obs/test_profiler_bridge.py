"""The span layer's bridge to the profiler: every `span()` is also a
`ks:<name>` TraceAnnotation, so the program's phases sit in the
profiler's trace, on the device's clock, with or without a session.

One profiler trace (a process holds one at a time) around two tiny
`Pipeline.fit`s and one `apply_batch` feeds the tests of names, nesting
and stability; the kernels' `jax.named_scope`s are read from lowered
text, which needs no device.
"""

import glob
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.obs import metrics, names, spans

ROWS, DIM, CLASSES = 256, 24, 5


def _pipeline(seed):
    """TIMIT's form at a tiny size: two gathered cosine branches over one
    host input, a block solver, an argmax."""
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator
    from keystone_tpu.ops.stats.core import CosineRandomFeatures
    from keystone_tpu.ops.util.labels import ClassLabelIndicators, MaxClassifier
    from keystone_tpu.ops.util.vectors import VectorCombiner
    from keystone_tpu.workflow import Pipeline

    rng = np.random.default_rng(seed)
    x = rng.standard_normal((ROWS, DIM)).astype(np.float32)
    y = rng.integers(0, CLASSES, ROWS).astype(np.int32)
    branches = [
        CosineRandomFeatures.create(DIM, 16, 0.1, seed=s).to_pipeline() for s in (1, 2)
    ]
    featurizer = Pipeline.gather(branches) >> VectorCombiner()
    labels = ClassLabelIndicators(CLASSES)(ArrayDataset(y))
    pipeline = featurizer.then_label_estimator(
        BlockLeastSquaresEstimator(16, num_iter=2, reg=0.0), ArrayDataset(x), labels
    ) >> MaxClassifier()
    return pipeline, x


def _host_events(trace_dir):
    """(name, start_ns, end_ns) of the `ks:` and `op:` events of a trace."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("ks:", "op:")):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Host events of: fit, fit (other data), apply_batch; each operation
    under an `op:<name>` annotation of the test's own."""
    _pipeline(0)[0].fit()  # compile outside the trace
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    assert spans.active_session() is None
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for i, seed in enumerate((1, 2)):
            pipeline, x = _pipeline(seed)
            with jax.profiler.TraceAnnotation(f"op:fit{i}"):
                fitted = pipeline.fit()
        with jax.profiler.TraceAnnotation("op:apply"):
            np.asarray(fitted.apply_batch(ArrayDataset(x)).data)
    finally:
        jax.profiler.stop_trace()
    return _host_events(trace_dir)


def _inside(events, operation):
    (_, lo, hi) = next(e for e in events if e[0] == "op:" + operation)
    return [e for e in events if e[0].startswith("ks:") and lo <= e[1] and e[2] <= hi]


@pytest.mark.parametrize("name", [
    "ks:fit:plan", "ks:fit:verify", "ks:optimize:rules", "ks:h2d",
    "ks:node:CosineRandomFeatures", "ks:node:BlockLeastSquaresEstimator",
    "ks:solver:fit", "ks:solver:prepare", "ks:solver:reg_floor", "ks:solver:bcd",
    "ks:fit:splice", "ks:fit:fuse",
])
def test_a_profiler_trace_of_a_fit_holds_the_programs_phases(traced, name):
    assert name in {e[0] for e in _inside(traced, "fit0")}


@pytest.mark.parametrize("name", [
    "ks:apply:bind", "ks:h2d", "ks:node:CosineRandomFeatures", "ks:node:VectorCombiner",
    "ks:node:Fused[BlockLinearMapper+MaxClassifier]",
])
def test_a_profiler_trace_of_apply_batch_holds_the_programs_phases(traced, name):
    assert name in {e[0] for e in _inside(traced, "apply")}


@pytest.mark.parametrize("operation", ["fit0", "apply"])
def test_node_spans_of_one_operation_are_siblings_in_time(traced, operation):
    """A node's span is its own work, opened after its dependencies are
    forced: however the pulls nest, no node span overlaps another."""
    nodes = [e for e in _inside(traced, operation) if e[0].startswith("ks:node:")]
    assert len(nodes) >= 4
    for (_, _, end), (_, start, _) in zip(nodes, nodes[1:]):
        assert end <= start


def test_phases_nest_as_documented(traced):
    events = _inside(traced, "fit0")

    def one(name):
        return next(e for e in events if e[0] == name)

    def within(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    assert within(one("ks:optimize:rules"), one("ks:fit:plan"))
    assert within(one("ks:solver:fit"), one("ks:node:BlockLeastSquaresEstimator"))
    assert within(one("ks:solver:bcd"), one("ks:solver:fit"))
    # x feeds both cosine branches and goes up once, when the first of
    # them forces its input: ahead of that node's span and inside no
    # node's; the labels' upload is their one consumer's own, inside it
    nodes = [e for e in events if e[0].startswith("ks:node:")]
    shared, own = [e for e in events if e[0] == "ks:h2d"]
    assert shared[2] <= one("ks:node:CosineRandomFeatures")[1]
    assert not any(within(shared, node) for node in nodes)
    assert any(within(own, node) for node in nodes)
    # top-level phases are siblings: nothing spans the whole fit
    assert not any(within(one("ks:fit:plan"), e) for e in events if e[0] != "ks:fit:plan"
                   and not e[0].startswith("ks:optimize"))


def test_two_fits_give_the_same_names_and_no_name_holds_an_id(traced):
    first = [e[0] for e in _inside(traced, "fit0")]
    second = [e[0] for e in _inside(traced, "fit1")]
    assert first == second  # the same spans in the same order
    for name in set(first) | {e[0] for e in _inside(traced, "apply")}:
        assert not re.search(r"0x[0-9a-fA-F]+|\d{4,}|[0-9a-f]{12,}", name), name


def test_without_session_or_profiler_a_span_leaves_nothing_behind():
    assert spans.active_session() is None
    with spans.span("fit:plan", rows=3) as sp:
        assert sp is spans.NOOP_SPAN
        sp.set_attribute("k", 1)  # accepted and dropped
        assert spans.current_span() is spans.NOOP_SPAN
        assert spans.current_context() is None
    assert getattr(spans._state, "stack", None) in (None, [])
    with pytest.raises(KeyError):  # an exception passes through the annotation
        with spans.span("x"):
            raise KeyError("k")


def test_a_span_constructed_but_never_entered_annotates_nothing():
    cm = spans.span("never-entered")
    del cm  # a TraceAnnotation does its work in __enter__/__exit__ alone


@pytest.mark.parametrize("jax_is", ["not imported", "not importable"])
def test_obs_imports_and_spans_work_in_a_process_without_jax(jax_is):
    """The jax-free stub workers and the serving supervisor import `obs`;
    the bridge must not import jax for them, nor fail where it is absent."""
    block = "sys.modules['jax'] = None  # any `import jax` now raises ImportError\n"
    code = (
        "import sys\n"
        + (block if jax_is == "not importable" else "")
        + "from keystone_tpu.obs import spans\n"
        "with spans.span('a', k=1) as sp:\n"
        "    assert sp is spans.NOOP_SPAN\n"
        "with spans.tracing_session('t') as session:\n"
        "    with spans.span('b'):\n"
        "        pass\n"
        "assert [s.name for s in session.spans()] == ['b']\n"
        "sys.modules.pop('jax', 0)\n"
        "assert 'jax' not in sys.modules and 'jax.profiler' not in sys.modules\n"
        "print('ok')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "ok"


def test_the_shipped_timit_builder_opens_the_build_phase():
    """Drawing the random features on the host is a phase of each TIMIT
    fit before `Pipeline.fit` is called at all (PERF.md section 5): the
    entry point's builder has a span of its own, and the draw of the bank
    one inside it, on the calling thread (`host_idle_ms.build.fit` books
    idle time to the innermost `ks:build:` span: a worker opens none),
    with the bank's `branches` and `workers`; the counter takes one count
    a bank."""
    import threading

    from keystone_tpu.data.loaders.csv import LabeledData
    from keystone_tpu.pipelines import timit

    config = timit.TimitConfig(num_cosines=2, num_cosine_features=16, num_epochs=1)
    x = np.zeros((32, 440), np.float32)
    train = LabeledData(ArrayDataset(np.zeros(32, np.int32)), ArrayDataset(x))
    registry = metrics.get_registry()

    def counted():
        metric = registry.get(names.FEATURE_DRAWS)
        return metric.value(workers="2") if metric else 0.0

    before = counted()
    with spans.tracing_session("t") as session:
        timit.build_pipeline(config, train)
    draw, build = session.spans()
    assert (draw.name, build.name) == ("build:draw", "build:pipeline")
    assert draw.parent_id == build.span_id
    assert draw.thread_id == build.thread_id == threading.get_ident()
    assert draw.attributes == {"branches": 2, "workers": 2}
    assert counted() - before == 1


def _names_under_session(sync_timings):
    pipeline, x = _pipeline(1)
    with spans.tracing_session("t", sync_timings=sync_timings) as session:
        fitted = pipeline.fit()
        fitted.apply_batch(ArrayDataset(x))
    return [s.name for s in session.spans()], session


@pytest.mark.parametrize("sync_timings", [True, False], ids=["sync", "nosync"])
def test_with_a_session_the_same_spans_are_recorded_once(traced, sync_timings):
    """`timed_execute`'s span and the thunk's are one span: a session
    records every name the profiler saw without it, each node once."""
    recorded, session = _names_under_session(sync_timings)
    seen = [e[0][len("ks:"):] for e in _inside(traced, "fit0") + _inside(traced, "apply")]
    # (the executor also times the constant input nodes, which compute
    # nothing and so have no span of their own in a plain run)
    inputs = [n for n in recorded if n.startswith("node:Dataset[")]
    assert len(inputs) == 3  # x and y of the fit, x of the apply
    assert sorted(n for n in recorded if n not in inputs) == sorted(seen)
    nodes = session.find("node:")
    assert all("seconds" in s.attributes for s in nodes)  # timed_execute's, with its attributes
    assert all((s.attributes.get("synced") is False) == (not sync_timings) for s in nodes)
    (fused,) = session.find("node:Fused[")
    assert "BlockLinearMapper" in fused.attributes["fused_members"]


def test_with_a_session_the_profiler_sees_each_node_once(tmp_path):
    pipeline, x = _pipeline(1)
    fitted = pipeline.fit()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with spans.tracing_session("t"):
            fitted.apply_batch(ArrayDataset(x))
    finally:
        jax.profiler.stop_trace()
    nodes = [e[0] for e in _host_events(str(tmp_path)) if e[0].startswith("ks:node:")]
    assert sorted(nodes) == sorted([
        "ks:node:CosineRandomFeatures", "ks:node:CosineRandomFeatures", "ks:node:Gather",
        "ks:node:VectorCombiner", "ks:node:Fused[BlockLinearMapper+MaxClassifier]",
        "ks:node:Dataset[n=256]",  # the executor times the constant input too
    ])


def test_one_host_input_of_two_branches_is_uploaded_once():
    """One host input, two cosine branches: one upload, counted under the
    operator whose output it was, and one branch handed the other's copy
    (two uploads under `CosineRandomFeatures` until PR 33)."""
    pipeline, x = _pipeline(3)
    fitted = pipeline.fit()

    def counted(site):
        return tuple(
            names.metric(name).value(site=site)
            for name in (names.H2D_BYTES, names.H2D_TRANSFERS, names.H2D_REUSES)
        )

    def gained(site, since):
        return tuple(now - was for now, was in zip(counted(site), since))

    source0, cosine0 = counted("DatasetOperator"), counted("CosineRandomFeatures")
    fitted.apply_batch(ArrayDataset(x))
    assert gained("DatasetOperator", source0) == (x.nbytes, 1, 1)
    assert gained("CosineRandomFeatures", cosine0) == (0, 0, 0)
    device_input = ArrayDataset(jnp.asarray(x))
    fitted.apply_batch(device_input)  # already on the device: nothing to upload
    assert gained("DatasetOperator", source0) == (x.nbytes, 1, 1)


@pytest.mark.parametrize("num_iter,mode", [(5, "reused"), (1, "single_pass")])
def test_the_in_core_solve_says_whether_it_reuses_its_factors(num_iter, mode):
    """`solver:bcd` carries `factor_reuse`, and the counter takes one
    count a `block_coordinate_descent` call under the same mode; beside
    them `gram_panels` and `keystone_gram_symmetric_total`, "1" at a
    block this narrow (`linalg.gram_sym`'s single matmul)."""
    from keystone_tpu.ops.learning.block import BlockLeastSquaresEstimator

    rng = np.random.default_rng(4)
    x = ArrayDataset(rng.standard_normal((ROWS, 32)).astype(np.float32))
    y = ArrayDataset(rng.standard_normal((ROWS, CLASSES)).astype(np.float32))
    registry = metrics.get_registry()

    def counted(label):
        metric = registry.get(names.BCD_FACTOR_REUSE)
        return metric.value(mode=label) if metric else 0.0

    def panelled():
        metric = registry.get(names.GRAM_SYMMETRIC)
        return metric.value(panels="1") if metric else 0.0

    other = "single_pass" if mode == "reused" else "reused"
    before, before_other, before_panelled = counted(mode), counted(other), panelled()
    with spans.tracing_session("t") as session:
        BlockLeastSquaresEstimator(16, num_iter=num_iter, reg=0.0).fit(x, y)
    (bcd,) = session.find("solver:bcd")
    assert bcd.attributes["factor_reuse"] == mode
    assert bcd.attributes["gram_panels"] == "1"
    assert counted(mode) - before == 1
    assert counted(other) == before_other
    assert panelled() - before_panelled == 1


# ------------------------------------------------- scopes on the kernels


def _mesh1d():
    from keystone_tpu.parallel.mesh import make_mesh

    return make_mesh(devices=jax.devices()[:8])


def _lower_bcd(variant):
    from keystone_tpu.parallel import linalg
    from keystone_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, make_mesh

    f32 = jnp.float32
    a = jax.ShapeDtypeStruct((64, 16), f32)
    y = jax.ShapeDtypeStruct((64, 3), f32)
    reg = jax.ShapeDtypeStruct((), f32)
    if variant == "in_core":
        return linalg._bcd_fn(_mesh1d(), 2, 8, False).lower(a, y, reg)
    if variant == "rematerialized":
        def block_fn(b, offset, rows):
            return jnp.ones((rows, 8), f32) * (b + 1)

        return linalg._bcd_remat_fn(_mesh1d(), 2, 8, 2, block_fn).lower(y, reg)
    if variant == "two_d":
        mesh = make_mesh((4, 2), (DATA_AXIS, MODEL_AXIS), devices=jax.devices()[:8])
        return linalg._bcd2d_fn(mesh, 2, 4).lower(a, y, reg)
    if variant == "from_gram":
        gc = jax.ShapeDtypeStruct((16, 16), f32)
        cc = jax.ShapeDtypeStruct((16, 3), f32)
        return linalg._bcd_gram_fn(2, 8).lower(gc, cc, reg)
    assert variant == "streamed"
    panel = jax.ShapeDtypeStruct((64, 8), f32)
    mask = jax.ShapeDtypeStruct((64, 1), f32)
    mu = jax.ShapeDtypeStruct((8,), f32)
    w_b = jax.ShapeDtypeStruct((8, 3), f32)
    return linalg._bcd_stream_step_fn(_mesh1d()).lower(panel, mask, mu, y, y, w_b, reg)


@pytest.mark.parametrize(
    "variant", ["in_core", "rematerialized", "two_d", "from_gram", "streamed"]
)
def test_every_bcd_variant_carries_the_same_five_scopes(variant):
    from keystone_tpu.parallel.linalg import BCD_SCOPES

    text = _lower_bcd(variant).as_text(debug_info=True)
    assert BCD_SCOPES == (
        "bcd/residual", "bcd/gram", "bcd/cross", "bcd/cholesky", "bcd/update"
    )
    for scope in BCD_SCOPES:  # at the head of a scan body's location, or after `jit(...)/`
        assert re.search(rf'["/]{scope}/', text), scope
    assert re.search(r"bcd/gram/dot_general|bcd/gram/dynamic_slice", text)
    assert "bcd/cholesky/jit(_cholesky)" in text


def test_the_streamed_gram_fold_carries_its_scope():
    from keystone_tpu.parallel import linalg

    carry = linalg.gram_stream_init(8, 3)
    x, y = jnp.ones((16, 8)), jnp.ones((16, 3))
    text = jax.jit(linalg.gram_stream_step).lower(carry, x, y).as_text(debug_info=True)
    assert "gram/step/dot_general" in text


def test_the_cosine_featurizer_and_the_mapper_carry_their_scopes():
    """`feat/<ClassName>` comes from one place (workflow.pipeline.feat_scope)
    wherever a chain is traced; the mapper adds its own inside it."""
    from keystone_tpu.ops.learning.block import BlockLinearMapper
    from keystone_tpu.ops.stats.core import CosineRandomFeatures
    from keystone_tpu.workflow.fusion import FusedTransformerOperator

    cosine = CosineRandomFeatures.create(DIM, 16, 0.1, seed=1)
    mapper = BlockLinearMapper(jnp.ones((16, CLASSES)), 16, jnp.zeros(CLASSES), jnp.zeros(16))
    fused = FusedTransformerOperator([cosine, mapper])
    x = jnp.ones((8, DIM))
    text = fused._compiled().lower(x).as_text(debug_info=True)
    assert "feat/CosineRandomFeatures/cos" in text
    assert "feat/CosineRandomFeatures/dot_general" in text
    assert "feat/BlockLinearMapper/mapper/apply/dot_general" in text
    eager = jax.jit(fused._chain).lower(x).as_text(debug_info=True)  # the fallback composition
    assert "feat/CosineRandomFeatures/cos" in eager and "mapper/apply" in eager
