"""Dense SIFT vs a committed OpenCV fixture — the external oracle.

The reference validated its native SIFT against MATLAB vl_phow output
with a committed fixture and a tolerance test
(reference: src/test/scala/keystoneml/utils/external/VLFeatSuite.scala:34-52).
Here the oracle is OpenCV's SIFT evaluated at our dense grid's keypoints
(generated once by scripts/make_sift_fixture.py; OpenCV is not needed to
run the test). Exact equality is not expected — OpenCV uses a Gaussian
spatial window, vl_dsift semantics use a flat window — so the assertion
is cosine similarity of the quantized descriptors under the fixed
convention map, which still breaks loudly on any axis-order,
orientation-binning, normalization, or quantization bug.
"""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu.ops.images.sift import SIFTExtractor

FIXTURE_DIR = os.path.join(
    os.path.dirname(__file__), "..", "fixtures", "sift_opencv"
)
BIN_SIZE = 4
STEP = 4
IMG_SIZE = 80

# Convention map from our (xbin, ybin, orient) layout to OpenCV's,
# probed over sizes/shifts (see scripts/make_sift_fixture.py docstring):
# swap the spatial bin axes, roll orientation by 6.
SWAP_XY = True
ORIENT_ROLL = 6


def _make_image(seed: int) -> np.ndarray:
    from scipy.ndimage import gaussian_filter

    rng = np.random.default_rng(seed)
    base = rng.random((IMG_SIZE, IMG_SIZE)).astype(np.float32)
    img = gaussian_filter(base, 3.0, mode="nearest")
    return (img - img.min()) / (img.max() - img.min())


def _to_opencv_layout(desc: np.ndarray) -> np.ndarray:
    d = desc.reshape(-1, 4, 4, 8)
    if SWAP_XY:
        d = np.transpose(d, (0, 2, 1, 3))
    return np.roll(d, ORIENT_ROLL, axis=-1).reshape(-1, 128)


def _load_fixture(seed: int) -> np.ndarray:
    return np.loadtxt(
        os.path.join(FIXTURE_DIR, f"opencv_dsift_seed{seed}.csv"), delimiter=","
    ).astype(np.float32)


def _cosines_vs_fixture(desc: np.ndarray, fixture: np.ndarray) -> np.ndarray:
    mapped = _to_opencv_layout(desc)
    na = np.linalg.norm(mapped, axis=1) + 1e-9
    nb = np.linalg.norm(fixture, axis=1) + 1e-9
    return (mapped * fixture).sum(axis=1) / (na * nb)


@pytest.mark.parametrize("seed", [42, 7])
def test_sift_matches_opencv_fixture(seed):
    fixture = _load_fixture(seed)

    img = _make_image(seed)
    # The fixture image is [0,1]·255-quantized before OpenCV sees it;
    # match that exactly so the comparison is apples-to-apples.
    img_q = (img * 255).astype(np.uint8).astype(np.float32) / 255.0
    ext = SIFTExtractor(step_size=STEP, bin_size=BIN_SIZE, scales=1, scale_step=1)
    ours = np.asarray(ext.apply_arrays(jnp.asarray(img_q[None])))[0]
    assert ours.shape == fixture.shape

    cos = _cosines_vs_fixture(ours, fixture)

    # A wrong axis order / orientation roll drops mean cosine below ~0.75
    # (probed); correct implementation sits near 0.98.
    assert cos.mean() > 0.95, f"mean cosine {cos.mean():.3f}"
    assert np.quantile(cos, 0.1) > 0.9, f"p10 cosine {np.quantile(cos, 0.1):.3f}"


def test_convention_map_is_the_best_one():
    """The committed (swap, roll) convention must be the argmax over all
    candidate maps — guards against the map silently compensating for a
    future axis bug in the extractor."""
    seed = 42
    fixture = _load_fixture(seed)
    img = _make_image(seed)
    img_q = (img * 255).astype(np.uint8).astype(np.float32) / 255.0
    ext = SIFTExtractor(step_size=STEP, bin_size=BIN_SIZE, scales=1, scale_step=1)
    ours = np.asarray(ext.apply_arrays(jnp.asarray(img_q[None])))[0]

    def mean_cos(cand):
        na = np.linalg.norm(cand, axis=1) + 1e-9
        nb = np.linalg.norm(fixture, axis=1) + 1e-9
        return float(((cand * fixture).sum(axis=1) / (na * nb)).mean())

    o = ours.reshape(-1, 4, 4, 8)
    scores = {}
    for swap in (False, True):
        base = np.transpose(o, (0, 2, 1, 3)) if swap else o
        for rev in (False, True):
            ob = base[..., ::-1] if rev else base
            for shift in range(8):
                scores[(swap, rev, shift)] = mean_cos(
                    np.roll(ob, shift, axis=-1).reshape(-1, 128)
                )
    best = max(scores, key=scores.get)
    assert best == (SWAP_XY, False, ORIENT_ROLL), (
        f"best map {best} (cos {scores[best]:.3f}) != committed "
        f"({SWAP_XY}, False, {ORIENT_ROLL}) (cos {scores[(SWAP_XY, False, ORIENT_ROLL)]:.3f})"
    )


def test_bf16_binning_passes_the_reference_tolerance():
    """bf16 spatial binning must hold the
    reference's own acceptance gate vs the fp32 build: 99.5% of
    x512-quantized entries within 1 (VLFeatSuite.scala:47-52), plus the
    OpenCV-fixture cosine gate. (Full-pyramid bf16 was measured FAILING
    this gate at 97.5% — the smoother feeds a gradient stencil that
    amplifies rounding — which is why only the binning conv has a dtype
    knob.)"""
    img = _make_image(42)
    img_q = (img * 255).astype(np.uint8).astype(np.float32) / 255.0
    batch = jnp.asarray(img_q[None])

    f32 = np.asarray(
        SIFTExtractor(step_size=STEP, bin_size=BIN_SIZE, scales=1).apply_arrays(batch)
    )[0]
    b16 = np.asarray(
        SIFTExtractor(
            step_size=STEP, bin_size=BIN_SIZE, scales=1,
            binning_dtype=jnp.bfloat16,
        ).apply_arrays(batch)
    )[0]
    close = np.abs(b16.astype(np.float64) - f32.astype(np.float64)) <= 1.0
    assert close.mean() > 0.995, f"within-1 fraction {close.mean():.4f}"

    cos = _cosines_vs_fixture(b16, _load_fixture(42))
    assert cos.mean() > 0.95, f"mean cosine {cos.mean():.3f}"


def test_bf16_binning_masked_path_matches_native():
    """The production native-resolution path (apply_arrays_masked) under
    bf16 binning: padded-bucket descriptors must stay within-1 of the
    SAME extractor's native-size run — the parity the imagenet_native
    workload relies on if the default ever flips."""
    ext = SIFTExtractor(scale_step=1, binning_dtype=jnp.bfloat16)
    rng = np.random.default_rng(3)
    small, big = 40, 64
    img = rng.random((small, small)).astype(np.float32)
    padded = np.pad(img, ((0, big - small), (0, big - small)), mode="edge")
    desc, valid = ext.apply_arrays_masked(
        jnp.asarray(padded[None]), jnp.asarray([[small, small]], jnp.int32)
    )
    native = np.asarray(ext.apply_arrays(jnp.asarray(img[None])))
    got = np.asarray(desc)[0][np.asarray(valid)[0]]
    assert got.shape == native[0].shape
    frac = (np.abs(got - native[0]) <= 1.0).mean()
    assert frac > 0.995, frac
