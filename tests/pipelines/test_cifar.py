"""End-to-end CIFAR workload tests on synthetic data.

Mirrors the reference's strategy of running full pipelines in local mode
and asserting they learn (reference: RandomPatchCifar's structure; the
suite-level analog of KernelModelSuite's learnability checks).
"""

import numpy as np
import pytest

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.data.loaders.cifar import decode_cifar_bytes
from keystone_tpu.pipelines import cifar


def make_synthetic_cifar(n, seed=0):
    """Class-dependent mean images + noise: trivially learnable."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, size=n).astype(np.int32)
    protos = rng.normal(size=(10, 32, 32, 3)) * 40 + 128
    images = protos[labels] + rng.normal(size=(n, 32, 32, 3)) * 10
    images = np.clip(images, 0, 255).astype(np.float32)
    return ArrayDataset({"image": images, "label": labels})


def test_cifar_binary_decode_layout():
    # one record: label 7, R plane all 1, G plane all 2, B plane all 3,
    # except R[x=1,y=2] = 9
    rec = np.zeros(1 + 3072, dtype=np.uint8)
    rec[0] = 7
    rec[1 : 1025] = 1
    rec[1025 : 2049] = 2
    rec[2049 :] = 3
    rec[1 + 1 * 32 + 2] = 9
    ds = decode_cifar_bytes(rec.tobytes())
    img = np.asarray(ds.data["image"])[0]
    assert np.asarray(ds.data["label"])[0] == 7
    assert img[0, 0, 0] == 1 and img[0, 0, 1] == 2 and img[0, 0, 2] == 3
    assert img[1, 2, 0] == 9


def test_linear_pixels_learns():
    # n must exceed the 1024 grayscale features for the OLS normal equations
    # to be well-posed (the reference runs this with n=50000).
    train = make_synthetic_cifar(1536)
    pipeline = cifar.build_linear_pixels(train)
    images = ArrayDataset(train.data["image"], train.num_examples)
    from keystone_tpu.evaluation.multiclass import MulticlassClassifierEvaluator

    ev = MulticlassClassifierEvaluator(10).evaluate(pipeline(images), train.data["label"])
    assert ev.total_error < 0.15


@pytest.mark.parametrize("solver", ["block", "kernel", "conv_block"])
def test_random_patch_cifar_learns(solver):
    train = make_synthetic_cifar(192, seed=1)
    config = cifar.RandomCifarConfig(
        num_filters=32,
        patch_steps=4,
        reg=1.0 if solver in ("block", "conv_block") else 1e-4,
        kernel_block_size=64,
        gamma=1e-3,
    )
    images = ArrayDataset(train.data["image"], train.num_examples)
    filters, whitener = cifar.learn_random_patch_filters(images, config, whitener_size=2000)
    assert filters.shape == (32, 6 * 6 * 3)
    pipeline = cifar.build_random_patch(train, config, filters, whitener, solver=solver)
    from keystone_tpu.evaluation.multiclass import MulticlassClassifierEvaluator

    ev = MulticlassClassifierEvaluator(10).evaluate(pipeline(images), train.data["label"])
    assert ev.total_error < 0.2


def _write_cifar_binary(path, ds):
    """Encode a synthetic ArrayDataset back to CIFAR binary records."""
    images = np.asarray(ds.data["image"]).astype(np.uint8)  # (n, 32, 32, 3)
    labels = np.asarray(ds.data["label"]).astype(np.uint8)
    planes = images.transpose(0, 3, 1, 2).reshape(len(labels), -1)  # (n, 3072)
    records = np.concatenate([labels[:, None], planes], axis=1).astype(np.uint8)
    records.tofile(path)


def test_random_patch_cifar_augmented_learns(tmp_path):
    train = make_synthetic_cifar(96, seed=2)
    path = tmp_path / "cifar_train.bin"
    _write_cifar_binary(str(path), train)
    config = cifar.RandomCifarConfig(
        train_location=str(path),
        test_location=str(path),
        num_filters=24,
        patch_steps=4,
        reg=1.0,
        num_random_images_augment=3,
        seed=3,
    )
    results = cifar.run(config, variant="random_patch_augmented")
    assert results["num_augmented_train"] == 96 * 3
    # train == test and the classes are linearly separable prototypes:
    # augmented voting should beat chance (0.9 error) comfortably
    assert results["test_error"] < 0.5


def _pipeline_patches(images, config, whitener_size):
    """The reference's composition, written out: the kept images, every
    window of them, a sample, vectorized, row-normalised."""
    from keystone_tpu.ops.images import ImageVectorizer, Windower
    from keystone_tpu.ops.stats.core import Sampler

    n, x_dim, y_dim = images.shape[:3]
    s, w = config.patch_steps, config.patch_size
    per_image = ((x_dim - w) // s + 1) * ((y_dim - w) // s + 1)
    want = max(1, min(n, (2 * whitener_size) // per_image + 1))
    if want < n:
        images = images[np.random.default_rng(config.seed).choice(n, size=want, replace=False)]
    windows = Windower(s, w).apply_batch(ArrayDataset(images))
    sampled = Sampler(whitener_size, seed=config.seed).apply_batch(windows)
    vectors = np.asarray(ImageVectorizer().apply_batch(sampled).data, dtype=np.float64)
    return cifar.normalize_rows(vectors, 10.0)


@pytest.mark.parametrize(
    "n, shape, steps, whitener_size, dtype",
    [
        (20, (32, 32, 3), 1, 2000, np.float32),  # 6 of 20 images kept
        (20, (32, 32, 3), 2, 1000, np.float32),  # stride 2
        (3, (32, 32, 3), 1, 2000, np.float32),  # every image kept
        (2, (32, 32, 3), 1, 5000, np.float32),  # fewer windows (1,458) than asked for
        (9, (11, 9, 3), 1, 40, np.float32),  # not square
        (7, (13, 10, 3), 2, 30, np.uint8),  # not square, stride 2, bytes
        (5, (32, 32, 3), 1, 3000, np.float64),  # held as float32 on the way
    ],
)
def test_gathered_patches_are_the_pipelines_to_the_bit(monkeypatch, n, shape, steps, whitener_size, dtype):
    """`sample_random_patches` gathers only the sampled windows from the
    host images: the rows of Windower > Sampler > ImageVectorizer >
    normalize_rows, bit for bit, in one row block inline or in many on
    several host threads."""
    rng = np.random.default_rng(n)
    images = (rng.random((n, *shape)) * 255).astype(dtype)
    config = cifar.RandomCifarConfig(patch_steps=steps, seed=77)
    expected = _pipeline_patches(images, config, whitener_size)
    assert expected.shape == (min(whitener_size, len(expected)), 6 * 6 * 3)
    for rows, workers in ((cifar._GATHER_ROWS, 1), (7, 5)):
        monkeypatch.setenv("KEYSTONE_INGEST_WORKERS", str(workers))
        monkeypatch.setattr(cifar, "_GATHER_ROWS", rows)
        got = cifar.sample_random_patches(ArrayDataset(images), config, whitener_size)
        assert got.dtype == np.float64 and np.array_equal(got, expected)


def test_filter_learning_gathers_the_pipelines_patches_without_a_node_span(monkeypatch):
    """`build:filters` says how many host threads gathered the patches and
    opens no `node:` span inside; the filters and the whitener are the
    ones learned from the written-out pipeline's patches, to the bit."""
    from keystone_tpu.data.dataset import default_ingest_workers
    from keystone_tpu.obs import spans

    images = make_synthetic_cifar(12, seed=4).data["image"]
    config = cifar.RandomCifarConfig(num_filters=40, patch_steps=2, seed=5)

    with spans.tracing_session("t") as session:
        filters, whitener = cifar.learn_random_patch_filters(ArrayDataset(images), config, 1500)
    (learn,) = session.spans()
    assert learn.name == "build:filters"
    assert learn.attributes["workers"] == default_ingest_workers()

    monkeypatch.setattr(
        cifar, "sample_random_patches", lambda data, cfg, size: _pipeline_patches(np.asarray(data.data), cfg, size)
    )
    ref_filters, ref_whitener = cifar.learn_random_patch_filters(ArrayDataset(images), config, 1500)
    assert np.array_equal(filters, ref_filters)
    assert np.array_equal(np.asarray(whitener.whitener), np.asarray(ref_whitener.whitener))
    assert np.array_equal(np.asarray(whitener.means), np.asarray(ref_whitener.means))
