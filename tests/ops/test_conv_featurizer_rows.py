"""The fused convolution featurizer at CIFAR RandomPatch's size: its
panel bounded in rows as well as filters, the kernel's form against XLA's
and which of them runs, one program for every filter bank, its patch
statistics at HIGHEST, and its spans, scopes and counters (`image:conv`,
`build:filters`, `conv/*`, `keystone_conv_panels_total`,
`keystone_conv_kernel_panels_total`, `keystone_conv_panel_bytes`)."""

import re
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from keystone_tpu.data.dataset import ArrayDataset, default_ingest_workers
from keystone_tpu.obs import names, spans
from keystone_tpu.ops.images import Convolver, FusedConvFeaturizer, Pooler, SymmetricRectifier
from keystone_tpu.ops.images import core
from keystone_tpu.parallel import mesh


def _featurizer(num_filters=37, filter_block=8, seed=0):
    rng = np.random.default_rng(seed)
    filters = rng.normal(size=(num_filters, 6 * 6 * 3)).astype(np.float32) * 0.1
    conv = Convolver(filters, 3, normalize_patches=True)
    return FusedConvFeaturizer(conv, SymmetricRectifier(alpha=0.25), Pooler(13, 14, None, "sum"), filter_block)


def _images(rows, seed=1):
    return np.random.default_rng(seed).integers(0, 256, size=(rows, 32, 32, 3)).astype(np.float32)


def _limit_for_rows(featurizer, rows):
    """A device limit under which `rows` rows of panel are the share."""
    fb = min(featurizer.filter_block, featurizer.conv.num_filters)
    return int(rows * 27 * 27 * fb * 4 / core.PANEL_SHARE)


# ------------------------------------------------------------ rows bounded


@pytest.mark.parametrize(
    "rows,row_block,num_filters,filter_block",
    [(13, 4, 37, 8), (13, 8, 37, 16), (16, 8, 37, 37), (9, 2, 20, 7), (5, 4, 12, 12)],
    ids=["ragged-rows-ragged-filters", "one-short-block", "even-rows-one-filter-block", "pairs", "one-ragged-block"],
)
def test_the_row_bounded_featurizer_is_the_whole_batch_form_to_the_bit(rows, row_block, num_filters, filter_block):
    featurizer = _featurizer(num_filters, filter_block)
    x = jnp.asarray(_images(rows))
    conv = featurizer.conv
    args = (x, conv.kernel, conv.filter_sums, conv.offset)
    whole = core._featurize(*args, spec=featurizer.spec, row_block=rows)
    bounded = core._featurize(*args, spec=featurizer.spec, row_block=row_block)
    assert bounded.shape == whole.shape == (rows, 2 * 2 * 2 * num_filters)
    assert np.array_equal(np.asarray(bounded), np.asarray(whole))


def test_the_row_block_is_the_largest_power_of_two_whose_panel_takes_its_share(monkeypatch):
    featurizer = _featurizer(37, 8)
    monkeypatch.setattr(mesh, "device_memory_limit_bytes", lambda: None)
    assert featurizer.row_block(8192, 32, 32) == 8192  # no memory reported: whole
    monkeypatch.setattr(mesh, "device_memory_limit_bytes", lambda: _limit_for_rows(featurizer, 100))
    assert featurizer.row_block(8192, 32, 32) == 64
    assert featurizer.row_block(100, 32, 32) == 100 and featurizer.row_block(101, 32, 32) == 64
    held = featurizer.panels(1000, 32, 32)
    assert held == {"row_block": 64, "panels": 16 * 5, "panel_bytes": 64 * 27 * 27 * 8 * 4}


def test_at_cifars_widths_a_v5e_holds_512_rows_of_panel_whatever_the_batch(monkeypatch):
    """ISSUE 40's arithmetic: a (8,192, 27, 27, 512) float32 panel is
    12.2 GB; bounded, it is 512 rows, 0.76 GB, at 8,192 images as at 1,024."""
    featurizer = _featurizer(10000, 512)
    monkeypatch.setattr(mesh, "device_memory_limit_bytes", lambda: 16_000_000_000)
    for rows in (1024, 8192, 50000):
        held = featurizer.panels(rows, 32, 32)
        assert held["row_block"] == 512 and held["panel_bytes"] == 512 * 729 * 512 * 4 < 0.8e9
    assert featurizer.panels(8192, 32, 32)["panels"] == 16 * 20


def test_apply_arrays_takes_the_row_block_it_decides(monkeypatch):
    featurizer = _featurizer(12, 5)
    monkeypatch.setattr(mesh, "device_memory_limit_bytes", lambda: _limit_for_rows(featurizer, 3))
    x = _images(7)
    bounded = np.asarray(featurizer.apply_arrays(jnp.asarray(x)))
    monkeypatch.setattr(mesh, "device_memory_limit_bytes", lambda: None)
    assert np.array_equal(bounded, np.asarray(featurizer.apply_arrays(jnp.asarray(x))))


# ------------------------------------------------------ the kernel's form


@pytest.fixture
def kernel_form(monkeypatch):
    """A switch to the kernel's form as a TPU takes it, run in the Pallas
    interpreter. The form is decided when `_featurize` is traced, so its
    traces are dropped on the switch and after the test."""

    def switch():
        interpreted = partial(core._pooled_kernel, interpret=True)
        monkeypatch.setattr(core, "_conv_form", lambda spec, x_dim, y_dim: "kernel")
        monkeypatch.setattr(core, "_pooled_kernel", interpreted)
        core._featurize.clear_cache()

    yield switch
    core._featurize.clear_cache()


def _bf16_exact(a):
    """`a` rounded to bfloat16 and held in float32: the filters a product
    at the MXU default would multiply, so that XLA's float32 form on the
    CPU and the kernel's bfloat16 products are the same products."""
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))


def _kernel_case(num_filters, filter_block, pool="sum", normalize=True, whiten=True, seed=0):
    from keystone_tpu.ops.learning.zca import ZCAWhitener

    rng = np.random.default_rng(seed)
    filters = _bf16_exact(rng.normal(size=(num_filters, 6 * 6 * 3)) * 0.1)
    whitener = ZCAWhitener(np.eye(108, dtype=np.float32), rng.normal(size=108).astype(np.float32) * 3) if whiten else None
    conv = Convolver(filters, 3, whitener=whitener, normalize_patches=normalize, var_constant=10.0)
    return FusedConvFeaturizer(conv, SymmetricRectifier(alpha=0.25), Pooler(13, 14, None, pool), filter_block)


@pytest.mark.parametrize(
    "num_filters,filter_block,pool,normalize,tile",
    [(512, 512, "sum", True, None), (600, 512, "sum", True, 256), (37, 16, "max", True, None), (24, 8, "sum", False, None)],
    ids=["cifar-block", "filters-no-multiple-of-the-tile", "max-pooling", "no-normalisation"],
)
def test_the_kernel_pools_what_xlas_form_pools(monkeypatch, kernel_form, num_filters, filter_block, pool, normalize, tile):
    """The kernel in the Pallas interpreter against XLA's form: CIFAR's
    geometry (32 x 32 x 3, 6 x 6 filters, pool 14 / 13, alpha 0.25,
    variance constant 10, the whitener's offset) on 3 images, a row tile
    short; apart by float32's summation order alone."""
    from keystone_tpu.ops.pallas import conv_pool

    if tile is not None:  # several filter tiles of two products each, the last one ragged
        monkeypatch.setattr(conv_pool, "FILTER_TILE", tile)
        monkeypatch.setattr(conv_pool, "LANES", tile // 2)
    featurizer = _kernel_case(num_filters, filter_block, pool, normalize)
    conv = featurizer.conv
    x = jnp.asarray(_images(3, seed=2))
    args = (x, conv.kernel, conv.filter_sums, conv.offset)
    xla = np.asarray(core._featurize(*args, spec=featurizer.spec, row_block=3))
    kernel_form()
    kernel = np.asarray(core._featurize(*args, spec=featurizer.spec, row_block=3))
    assert kernel.shape == xla.shape == (3, 2 * 2 * 2 * num_filters)
    np.testing.assert_allclose(kernel, xla, rtol=1e-5, atol=1e-5 * np.abs(xla).max())


def test_the_solvers_block_takes_the_featurizers_form(kernel_form):
    """`conv_block._conv_bcd_step_fn` featurizes one solver block through
    `_pooled_block`: its kernel form against its XLA form."""
    featurizer = _kernel_case(16, 16)
    conv, spec = featurizer.conv, featurizer.spec
    kblocks, fs, off = core._pack_filters(conv.kernel, conv.filter_sums, conv.offset, 16)
    x = jnp.asarray(_images(5, seed=3))
    m, sd = core._norm_stats(spec, x)
    xla = np.asarray(core._pooled_block(spec, x, kblocks[0], fs[0], off[0], m, sd))
    kernel_form()
    kernel = np.asarray(core._pooled_block(spec, x, kblocks[0], fs[0], off[0], m, sd))
    assert kernel.shape == xla.shape == (5, 2 * 2 * 2 * 16)
    np.testing.assert_allclose(kernel, xla, rtol=1e-5, atol=1e-5 * np.abs(xla).max())


@pytest.mark.parametrize(
    "backend,pixel_function,image,vmem,form",
    [
        ("tpu", None, 32, 128 * 2**20, "kernel"), ("tpu", np.abs, 32, 128 * 2**20, "xla"),
        ("cpu", None, 32, 128 * 2**20, "xla"), ("tpu", None, 256, 128 * 2**20, "xla"),
        ("tpu", None, 32, 64 * 2**20, "xla"), ("tpu", None, 32, 0, "xla"),
    ],
    ids=[
        "a-tpu-takes-the-kernel", "a-pixel-function-takes-xla", "the-cpu-takes-xla", "a-step-past-vmem-takes-xla",
        "a-chip-with-less-vmem-takes-xla", "a-chip-pallas-does-not-know-takes-xla",
    ],
)
def test_the_form_follows_the_backend_and_what_the_kernel_expresses(monkeypatch, backend, pixel_function, image, vmem, form):
    from keystone_tpu.ops.pallas import conv_pool

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(conv_pool, "vmem_bytes", lambda: vmem)
    conv = Convolver(np.zeros((10000, 108), np.float32), 3)
    featurizer = FusedConvFeaturizer(conv, SymmetricRectifier(alpha=0.25), Pooler(13, 14, pixel_function, "sum"), 512)
    assert core._conv_form(featurizer.spec, image, image) == form


def test_a_chip_pallas_does_not_know_has_no_vmem_for_the_kernel():
    """`pltpu.get_tpu_info` raises off its table of TPU generations (here,
    the CPU): `vmem_bytes` reads 0, and `fits` sends the featurizer to
    XLA's form rather than into a Mosaic compile that cannot be sized."""
    from keystone_tpu.ops.pallas import conv_pool

    assert conv_pool.vmem_bytes() == 0
    assert not conv_pool.fits(27, 32, 128, 10000, 0) and conv_pool.fits(27, 32, 128, 10000, 128 * 2**20)


# ------------------------------------------------------ one program


def test_a_second_featurizer_with_other_filters_traces_and_builds_nothing():
    from keystone_tpu.utils.compilation_cache import compile_count, install_compile_counter

    install_compile_counter()
    x = jnp.asarray(_images(6))
    first = _featurizer(37, 8, seed=3).apply_batch(ArrayDataset(x))
    jax.block_until_ready(first.data)
    before, traced = compile_count(), core._featurize._cache_size()
    second = _featurizer(37, 8, seed=4).apply_batch(ArrayDataset(x))
    jax.block_until_ready(second.data)
    assert compile_count() == before and core._featurize._cache_size() == traced
    assert not np.array_equal(np.asarray(first.data), np.asarray(second.data))


def test_a_second_fresh_random_patch_pipeline_with_new_filters_builds_nothing():
    """What the benchmark's fit cell counts as `window_compiles.fit`: a new
    Pipeline over other images, which learns other filters and whitener,
    builds and loads no program once one fit of its shape has run. (Its
    first APPLICATION builds the fused chain of its members once, as every
    fitted pipeline's does: `fusion._shared_chain_jit` is keyed on them.)"""
    from keystone_tpu.pipelines import cifar
    from keystone_tpu.utils.compilation_cache import compile_count, install_compile_counter

    install_compile_counter()
    config = cifar.RandomCifarConfig(num_filters=24, filter_block=8, reg=10.0, whitening_epsilon=1e-5)

    def fit(seed):
        rng = np.random.default_rng(seed)
        x, y = _images(64, seed), rng.integers(0, 10, 64).astype(np.int32)
        filters, whitener = cifar.learn_random_patch_filters(ArrayDataset(x), config)
        train = ArrayDataset({"image": x, "label": y})
        cifar.build_random_patch(train, config, filters, whitener).fit()
        return filters

    first = fit(11)
    before = compile_count()
    second = fit(12)
    assert not np.array_equal(first, second)
    assert compile_count() == before


# ------------------------------------------------------ precision


def _convolutions(lowered_text):
    """[(precision of the operands, output channels)] of every convolution."""
    found = []
    for line in lowered_text.splitlines():
        if "stablehlo.convolution" in line:
            precision = re.search(r"precision_config = \[#stablehlo<precision (\w+)>", line).group(1)
            channels = int(re.search(r"-> tensor<(?:\d+x)+(\d+)xf32>", line).group(1))
            found.append((precision, channels))
    return found


def test_the_patch_statistics_are_summed_at_highest_and_the_main_convolution_as_shipped(kernel_form):
    featurizer = _featurizer(20, 8)
    conv = featurizer.conv
    text = core._featurize.lower(
        jnp.zeros((4, 32, 32, 3)), conv.kernel, conv.filter_sums, conv.offset,
        spec=featurizer.spec, row_block=2,
    ).as_text()
    assert sorted(_convolutions(text)) == [("DEFAULT", 8), ("HIGHEST", 1), ("HIGHEST", 1)]
    # the unfused Convolver shares the same statistics
    unfused = jax.jit(conv.apply_arrays).lower(jnp.zeros((2, 32, 32, 3))).as_text()
    assert sorted(_convolutions(unfused)) == [("DEFAULT", 20), ("HIGHEST", 1), ("HIGHEST", 1)]
    # in the kernel's form (its body as the interpreter lowers it): the same
    # statistics, and the main product of bfloat16 patches and filters
    # summed in float32, whatever the filters' dtype
    kernel_form()
    kernel_text = core._featurize.lower(
        jnp.zeros((4, 32, 32, 3)), conv.kernel, conv.filter_sums, conv.offset,
        spec=featurizer.spec, row_block=2,
    ).as_text()
    statistics = [line for line in kernel_text.splitlines() if "stablehlo.convolution" in line and "xf32>" in line]
    assert len(statistics) == 2 and all("precision HIGHEST" in line for line in statistics)
    products = [line for line in kernel_text.splitlines() if "stablehlo.dot_general" in line]
    assert products and all(
        re.search(r": \(tensor<\d+x128xbf16>, tensor<128x\d+xbf16>\) -> tensor<\d+x\d+xf32>", line) for line in products
    )


# ------------------------------------------------------ spans, scopes, counters


@pytest.fixture
def session():
    with spans.tracing_session("conv-spans", sync_timings=False) as s:
        yield s


def _named(session, name):
    return [s for s in session.spans() if s.name == name]


def test_image_conv_says_rows_filters_and_its_panels_and_counts_them(session, monkeypatch):
    featurizer = _featurizer(37, 8)
    monkeypatch.setattr(mesh, "device_memory_limit_bytes", lambda: _limit_for_rows(featurizer, 4))
    panels, panel_bytes = names.metric(names.CONV_PANELS), names.metric(names.CONV_PANEL_BYTES)
    before = panels.value(site="FusedConvFeaturizer")
    featurizer.to_pipeline()(ArrayDataset(_images(10))).get()
    (span,) = _named(session, "image:conv")
    assert span.attributes == {"rows": 10, "filters": 37, "row_block": 4, "filter_block": 8, "panels": 3 * 5, "form": "xla"}
    assert panels.value(site="FusedConvFeaturizer") - before == 15
    assert panel_bytes.value(site="FusedConvFeaturizer") == 4 * 27 * 27 * 8 * 4


@pytest.mark.parametrize("form", ["kernel", "xla"])
def test_image_conv_says_its_form_and_the_kernel_counts_its_panels(session, monkeypatch, form):
    """`keystone_conv_kernel_panels_total` over `keystone_conv_panels_total`
    is the share of panels the kernel computed: all of them where the
    span says `kernel`, none where it says `xla`."""
    featurizer = _featurizer(37, 8)
    monkeypatch.setattr(mesh, "device_memory_limit_bytes", lambda: _limit_for_rows(featurizer, 4))
    monkeypatch.setattr(core, "_conv_form", lambda spec, x_dim, y_dim: form)
    panels, kernel_panels = names.metric(names.CONV_PANELS), names.metric(names.CONV_KERNEL_PANELS)
    before = panels.value(site="FusedConvFeaturizer"), kernel_panels.value(site="FusedConvFeaturizer")
    with featurizer.host_span(ArrayDataset(_images(10))):
        pass
    (span,) = _named(session, "image:conv")
    assert span.attributes["form"] == form and span.attributes["panels"] == 15
    counted = panels.value(site="FusedConvFeaturizer") - before[0], kernel_panels.value(site="FusedConvFeaturizer") - before[1]
    assert counted == ((15, 15) if form == "kernel" else (15, 0))


def test_a_fused_chain_headed_by_the_featurizer_opens_image_conv_inside_its_node(session):
    from keystone_tpu.ops.stats.core import StandardScalerModel

    featurizer = _featurizer(12, 5)
    width = 2 * 2 * 2 * 12
    pipeline = featurizer.to_pipeline() >> StandardScalerModel(np.zeros(width, np.float32), np.ones(width, np.float32))
    pipeline(ArrayDataset(_images(3))).get()
    (span,) = _named(session, "image:conv")
    (node,) = [s for s in session.spans() if s.name.startswith("node:Fused[")]
    assert span.parent_id == node.span_id and span.attributes["rows"] == 3


def test_build_filters_spans_the_filter_learning(session):
    from keystone_tpu.pipelines import cifar

    config = cifar.RandomCifarConfig(num_filters=16, whitening_epsilon=1e-5)
    filters, _ = cifar.learn_random_patch_filters(ArrayDataset(_images(8)), config, whitener_size=500)
    (span,) = _named(session, "build:filters")
    assert span.attributes == {
        "patches": 500, "filters": 16, "dim": 108, "workers": default_ingest_workers(),
    }
    assert filters.shape == (16, 108)


@pytest.mark.parametrize("path", ["fit", "apply", "kernel"])
def test_the_conv_scopes_sit_inside_the_featurizers_on_either_path(kernel_form, path):
    """What `scope_ms.conv.fit` reads: `conv/stats`, `conv/panel` and
    `conv/pool` under `feat/FusedConvFeaturizer`, in the program a fit
    runs (the featurizer's own, eagerly), in the fused chain a request
    runs, and in the kernel's form, whose kernel runs under `conv/panel`
    (as the interpreter lowers it here; the compiled kernel's custom call
    for a described v5e: tests/workflow/test_row_chain_on_tpu.py)."""
    from keystone_tpu.workflow.fusion import _shared_chain_jit
    from keystone_tpu.ops.stats.core import StandardScalerModel

    featurizer = _featurizer(12, 5)
    x = jnp.zeros((4, 32, 32, 3))
    if path == "kernel":
        kernel_form()
    if path != "apply":
        conv = featurizer.conv
        compiled = core._featurize.lower(
            x, conv.kernel, conv.filter_sums, conv.offset, spec=featurizer.spec, row_block=2,
        ).compile()
    else:
        width = 2 * 2 * 2 * 12
        chain = (featurizer, StandardScalerModel(np.zeros(width, np.float32), np.ones(width, np.float32)))
        jitted, arrays = _shared_chain_jit(chain)
        compiled = jitted.lower(x, arrays).compile()
    names_seen = set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    kernel_ops = [n for n in names_seen if "/conv_pool/" in n]
    if path == "kernel":
        assert kernel_ops and all("feat/FusedConvFeaturizer/" in n and "/conv/panel/" in n for n in kernel_ops)
    else:
        assert not kernel_ops
    for scope in ("conv/stats", "conv/panel", "conv/pool"):
        assert any("feat/FusedConvFeaturizer/" in n and f"/{scope}/" in n for n in names_seen), scope


# ------------------------------------------------------ filter learning on the host


@pytest.mark.parametrize("stride,window", [(1, 6), (2, 4), (3, 5)])
def test_the_windows_are_the_explicit_loops_and_stay_on_the_host(stride, window):
    from keystone_tpu.ops.images import Windower

    imgs = np.random.default_rng(5).normal(size=(3, 11, 9, 3)).astype(np.float32)
    out = Windower(stride, window).apply_batch(ArrayDataset(imgs))
    loop = np.stack([
        img[x:x + window, y:y + window, :]
        for img in imgs
        for x in range(0, 11 - window + 1, stride)
        for y in range(0, 9 - window + 1, stride)
    ])
    assert isinstance(out.data, np.ndarray) and np.array_equal(out.data, loop)
    assert np.array_equal(Windower(stride, window).apply(imgs[1]), loop[len(loop) // 3:2 * len(loop) // 3])


def test_sampling_the_windows_before_vectorizing_them_keeps_the_same_rows():
    """`learn_random_patch_filters` samples the windows on the host and
    vectorizes only the sample: the same rows as vectorizing all of them on
    the device and sampling there, to the bit."""
    from keystone_tpu.ops.images import ImageVectorizer, Windower
    from keystone_tpu.ops.stats.core import Sampler

    windows = Windower(1, 6).apply_batch(ArrayDataset(_images(4)))
    sampled = Sampler(500, seed=9).apply_batch(windows)
    assert isinstance(sampled.data, np.ndarray)
    first = np.asarray(ImageVectorizer().apply_batch(sampled).data)
    on_device = ArrayDataset(jnp.asarray(windows.data))
    second = np.asarray(Sampler(500, seed=9).apply_batch(ImageVectorizer().apply_batch(on_device)).data)
    assert first.shape == (500, 108) and np.array_equal(first, second)
