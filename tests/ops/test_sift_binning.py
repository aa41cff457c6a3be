"""Dense SIFT's spatial binning as two band products a plane (PR 37):
``sift._spatial_binning`` against the zero-boundary convolution it stands
for, the rule that chooses between the two on either side of its width, the
extractor against a plain binning (sums of shifted copies) by the
reference's within-1 gate, and what the traced program may and may not hold."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from keystone_tpu.ops.images import sift
from keystone_tpu.ops.images.sift import (
    BAND_COLUMNS_PER_TAP,
    SIFTExtractor,
    _band_product,
    _binning_as_products,
    _separable_conv,
    _spatial_binning,
    _triangular_kernel,
)

BIN_SIZES = (4, 6, 8, 10)  # the four scales' bins: 7, 11, 15, 19 taps


def _planes(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).random(shape).astype(np.float32) * 4.0)


def _shifted_copies(x, kernel, axis):
    """Same-size correlation along ``axis``, zero outside: one shifted copy a tap."""
    pad = len(kernel) // 2
    widths = [(0, 0)] * x.ndim
    widths[axis] = (pad, pad)
    padded = np.pad(np.asarray(x, np.float64), widths)
    taps = [np.take(padded, np.arange(t, t + x.shape[axis]), axis=axis) * float(k) for t, k in enumerate(kernel)]
    return np.sum(taps, axis=0)


@pytest.mark.parametrize("dtype", [None, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(6, 40, 40), (5, 44, 31)], ids=["square", "non-square"])
@pytest.mark.parametrize("bin_size", BIN_SIZES)
def test_the_products_are_the_zero_boundary_convolution(bin_size, shape, dtype):
    kernel = _triangular_kernel(bin_size)
    assert len(kernel) == 2 * bin_size - 1
    planes = _planes(shape, seed=bin_size)
    conv = np.asarray(_separable_conv(planes, kernel, "zero", dtype))
    product = np.asarray(_spatial_binning(planes, kernel, dtype))
    assert product.shape == conv.shape and product.dtype == np.float32
    # float32: the sum's order, nothing else; bfloat16: one rounding of the
    # operands and of the intermediate, 2**-8 each, in either form.
    room = 1e-5 if dtype is None else 2.0 ** -6
    assert np.abs(product - conv).max() <= room * np.abs(conv).max()


@pytest.mark.parametrize("axis", [1, 2])
@pytest.mark.parametrize("bin_size", [4, 10])
@pytest.mark.parametrize("length", [24, 257])
def test_a_band_product_is_the_sum_of_shifted_copies_along_either_axis(length, bin_size, axis):
    kernel = _triangular_kernel(bin_size)
    shape = [3, 24, 24]
    shape[axis] = length
    x = _planes(tuple(shape), seed=length)
    out = np.asarray(_band_product(x, kernel, axis))
    assert out.shape == tuple(shape)
    want = _shifted_copies(x, kernel, axis)
    assert np.abs(out - want).max() <= 1e-5 * np.abs(want).max()


def test_a_band_product_refuses_an_even_kernel():
    with pytest.raises(ValueError, match="odd kernel"):
        _band_product(_planes((1, 8, 8)), np.ones(4, np.float32), 1)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _primitives(fn, *args):
    return [e.primitive.name for e in _equations(jax.make_jaxpr(fn)(*args).jaxpr)]


@pytest.mark.parametrize("long_axis", [1, 2])
@pytest.mark.parametrize("over", [0, 1], ids=["at-the-rule", "one-over"])
def test_the_rule_keeps_the_convolution_for_an_axis_too_long_for_the_taps(over, long_axis):
    """`_binning_as_products`: a function of the static axis lengths and the
    taps, written once; on either side of it the same numbers by another path."""
    kernel = _triangular_kernel(2)  # 3 taps: the rule's width is 384 columns
    length = BAND_COLUMNS_PER_TAP * len(kernel) + over
    shape = [2, 16, 16]
    shape[long_axis] = length
    assert _binning_as_products(shape[1], shape[2], len(kernel)) == (not over)
    planes = _planes(tuple(shape), seed=over)
    names = _primitives(lambda p: _spatial_binning(p, kernel), planes)
    if over:
        assert names.count("conv_general_dilated") == 2 and "dot_general" not in names
    else:
        assert names.count("dot_general") == 2 and "conv_general_dilated" not in names
    got = np.asarray(_spatial_binning(planes, kernel))
    want = _shifted_copies(_shifted_copies(planes, kernel, 1), kernel, 2)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_the_rule_at_the_flagships_and_a_native_buckets_sizes():
    assert all(_binning_as_products(256, 256, 2 * b - 1) for b in BIN_SIZES)
    assert all(_binning_as_products(512, 384, 2 * b - 1) for b in BIN_SIZES)
    assert [_binning_as_products(1000, 600, 2 * b - 1) for b in BIN_SIZES] == [False, True, True, True]


@pytest.mark.parametrize("dtype", [None, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_extractor_against_a_plain_binning_by_the_within_1_gate(monkeypatch, dtype):
    rng = np.random.default_rng(5)
    yy, xx = np.meshgrid(np.arange(56), np.arange(64))
    batch = np.stack(
        [0.5 + 0.3 * np.sin(0.21 * xx + 0.13 * (i + 1) * yy) + rng.normal(0, 0.05, xx.shape) for i in range(3)]
    )
    batch = jnp.asarray(np.clip(batch, 0, 1).astype(np.float32))
    got = np.asarray(SIFTExtractor(binning_dtype=dtype).apply_arrays(batch))

    def plain(planes, kernel, dtype=None):
        out = _shifted_copies(_shifted_copies(planes, kernel, 1), kernel, 2)
        return jnp.asarray(out.astype(np.float32))

    monkeypatch.setattr(sift, "_spatial_binning", plain)
    want = np.asarray(SIFTExtractor().apply_arrays(batch))
    assert got.shape == want.shape == (3, sum(SIFTExtractor().grid_counts(64, 56)), 128)
    assert want.max() > 100  # descriptors, not a flat image's zeros
    within_1 = (np.abs(got - want) <= 1.0).mean()
    assert within_1 > 0.995, f"within-1 fraction {within_1:.5f}"
    if dtype is None:
        assert (got != want).mean() < 1e-3  # float32: a handful of entries on a quantization edge


@pytest.mark.parametrize("masked", [False, True], ids=["one_scale", "one_scale_masked"])
def test_a_scale_holds_the_smoothers_two_convolutions_and_two_products_without_batch(masked):
    ext = SIFTExtractor()
    x = jnp.zeros((2, 64, 48), jnp.float32)
    if masked:
        closed = jax.make_jaxpr(lambda a: ext._one_scale_masked(a, jnp.asarray([[64, 48], [50, 40]]), 3))(x)
    else:
        closed = jax.make_jaxpr(lambda a: ext._one_scale(a, 3))(x)
    eqns = list(_equations(closed.jaxpr))
    names = [e.primitive.name for e in eqns]
    assert names.count("conv_general_dilated") == 2  # the smoothing, and no third
    assert "while" not in names and "scan" not in names
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 2
    for dot in dots:
        (contract_lhs, contract_rhs), (batch_lhs, batch_rhs) = dot.params["dimension_numbers"]
        assert tuple(batch_lhs) == tuple(batch_rhs) == ()
        assert len(contract_lhs) == 1 and tuple(contract_rhs) == (0,)
        assert dot.params["precision"] is not None and "HIGHEST" in str(dot.params["precision"])
        assert dot.params["preferred_element_type"] == jnp.float32
    # last axis first (a 2-D product as the planes stand), then the middle one
    assert [tuple(d.params["dimension_numbers"][0][0]) for d in dots] == [(2,), (1,)]
