"""`obs.device.to_device` uploads a batch whose last dimension is narrow
(an image batch's three channels) flat and gives it its shape on the
device: the same array, without the runtime's host-side transposes."""

import numpy as np
import pytest

import jax

from keystone_tpu.obs import device, names


@pytest.mark.parametrize(
    "shape,flat",
    [((6, 16, 16, 3), True), ((4, 9, 64), True), ((8, 440), False), ((5, 7, 200), False), ((3,), False)],
    ids=["images", "descriptors", "rows", "wide-last-dimension", "vector"],
)
def test_an_upload_is_the_same_array_whatever_way_it_went_up(shape, flat, monkeypatch):
    x = np.random.default_rng(0).random(shape).astype(np.float32)
    went_up = []
    real = jax.numpy.asarray
    monkeypatch.setattr(jax.numpy, "asarray", lambda a, *args, **kw: went_up.append(np.shape(a)) or real(a, *args, **kw))
    before = names.metric(names.H2D_BYTES).value(site="test")
    out = device.to_device(x, site="test")
    assert isinstance(out, jax.Array) and out.shape == shape and out.dtype == x.dtype
    assert np.array_equal(np.asarray(out), x)
    assert went_up == [(shape[0], int(np.prod(shape[1:])))] if flat else went_up == [shape]
    assert names.metric(names.H2D_BYTES).value(site="test") - before == x.nbytes


def test_device_arrays_and_empty_batches_pass_through():
    on_device = jax.numpy.ones((2, 4, 4, 3))
    assert device.to_device(on_device, site="test") is on_device
    empty = device.to_device(np.zeros((0, 4, 4, 3), np.float32), site="test")
    assert empty.shape == (0, 4, 4, 3)
