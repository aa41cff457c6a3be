"""cifar-rp10k-8k: the calls into the program, and its seeded data.

The only file of this configuration that imports keystone_tpu. The
pipeline is built as `pipelines.cifar.run` builds variant `random_patch`
(`keystone-tpu cifar --variant random_patch`): filters and whitener
learned from the images, then `build_random_patch(..., solver="block")`
and `Pipeline.fit`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import numpy as np

from benchmark.harness import program
from keystone_tpu.ops.images.core import FusedConvFeaturizer
from keystone_tpu.pipelines import cifar

# Named at import, before any data is made: a checkout whose featurizer
# holds the whole batch's panel (a commit before PR 40, which cannot fit
# 8,192 images on one chip) fails here, in seconds.
_ROW_BLOCK = FusedConvFeaturizer.row_block

LABEL_RULE_SEED = 54321  # the fixed rule behind the labels, the same for every seed
RULE_GRID = 4  # the rule sees the image as 4 x 4 block means per channel
FLAT_SIDE = 18  # the low-contrast square: 13 x 13 = 169 of the 729 patch positions lie wholly inside
FLAT_LEVELS = (140, 250)  # its base level, a whole number drawn per image
FLAT_JITTER = 2  # and every pixel within +-2 levels of it

_LAST_FIT = None  # (config, data, seed) of the newest fit, for `probe`


@dataclass
class Fitted:
    """A fitted pipeline, its training images, and what the reference is
    given of it."""

    pipeline: Any
    train_x: np.ndarray
    given: dict
    program_config: Any = None


def make_data(config: dict, seed: int, rows: int, index: int) -> dict:
    """Data set `index` of this seed, on the host: uniform whole-number
    pixels 0..255 as float32, and in every image one square of
    `FLAT_SIDE` whose pixels lie within `FLAT_JITTER` levels of a bright
    base level (sky, a wall: where a patch's variance is a few levels
    squared beside the variance constant 10, and its squared pixels, about
    40,000, are what bfloat16 rounds by up to 128). The label is the argmax
    of a fixed linear rule over the image's block means."""
    size, channels = config["image_size"], config["num_channels"]
    rng = np.random.default_rng([seed, 1000 + index])
    x = rng.integers(0, 256, size=(rows, size, size, channels), dtype=np.uint8).astype(np.float32)
    at = rng.integers(0, size - FLAT_SIDE + 1, size=(rows, 2))
    level = rng.integers(*FLAT_LEVELS, size=(rows, 1, 1, 1))
    jitter = rng.integers(-FLAT_JITTER, FLAT_JITTER + 1, size=(rows, FLAT_SIDE, FLAT_SIDE, channels))
    u = np.arange(FLAT_SIDE)
    rows_of = np.arange(rows)[:, None, None]
    x[rows_of, at[:, :1, None] + u[None, :, None], at[:, 1:, None] + u[None, None, :]] = level + jitter
    cell = size // RULE_GRID
    pooled = x.reshape(rows, RULE_GRID, cell, RULE_GRID, cell, channels).mean(axis=(2, 4))
    rule = np.random.default_rng(LABEL_RULE_SEED).normal(size=(RULE_GRID * RULE_GRID * channels, config["num_classes"]))
    y = np.argmax((pooled.reshape(rows, -1) - 127.5) @ rule, axis=1).astype(np.int32)
    return {"x": x, "y": y}


def _program_config(config: dict, seed: int) -> cifar.RandomCifarConfig:
    return cifar.RandomCifarConfig(
        num_filters=config["num_filters"],
        whitening_epsilon=config["whitening_epsilon"],
        patch_size=config["patch_size"],
        patch_steps=config["patch_steps"],
        pool_size=config["pool_size"],
        pool_stride=config["pool_stride"],
        alpha=config["alpha"],
        reg=config["reg"],
        filter_block=config["filter_block"],
        seed=seed,
    )


def _chain(pipeline):
    """The members of the fitted pipeline's one fused chain: featurizer,
    standardiser, mapper, classifier."""
    ops = [op for op in pipeline.graph.operators.values() if hasattr(op, "members")]
    if len(ops) != 1:
        raise RuntimeError(f"expected one fused chain, found {len(ops)}")
    return ops[0].members


def fit(config: dict, data: dict, seed: int) -> Fitted:
    """One fit as `keystone-tpu cifar --variant random_patch` does it:
    filters and whitener from the images, a new Pipeline, the weights
    ready on the device."""
    import jax

    from keystone_tpu.data.dataset import ArrayDataset

    global _LAST_FIT
    _LAST_FIT = (config, data, seed)
    program_config = _program_config(config, seed)
    train = ArrayDataset({"image": data["x"], "label": data["y"]})
    filters, whitener = cifar.learn_random_patch_filters(ArrayDataset(data["x"]), program_config)
    pipeline = cifar.build_random_patch(train, program_config, filters, whitener, solver="block").fit()
    jax.block_until_ready(program.block_mapper(pipeline).weights)
    given = {
        "filters": np.asarray(filters, np.float32),
        "whitener": np.asarray(whitener.whitener, np.float32),
        "whitener_means": np.asarray(whitener.means, np.float32),
    }
    return Fitted(pipeline, data["x"], given, program_config)


def probe(run):
    """For `readers/scope_ms.py`: one more fit on the newest data set, as a
    function of no arguments (its fitted pipeline is let go of at once)."""
    if _LAST_FIT is None:
        return None
    config, data, seed = _LAST_FIT

    def again() -> None:
        fit(config, data, seed)

    return again


def _features(fitted: Fitted, x: np.ndarray):
    from keystone_tpu.data.dataset import ArrayDataset

    return _chain(fitted.pipeline)[0].apply_batch(ArrayDataset(x))


def scores(config: dict, fitted: Fitted, x: np.ndarray, seed: int) -> np.ndarray:
    """The program's real-valued class scores for `x`: every member of the
    fitted chain but the final argmax. The features and the scores are
    kept, for the reference to compare."""
    data = _features(fitted, x)
    fitted.given["heldout_features"] = np.asarray(data.data)
    for member in _chain(fitted.pipeline)[1:-1]:
        data = member.apply_batch(data)
    fitted.given["heldout_scores"] = out = np.asarray(data.data)
    return out


def given(fitted: Fitted) -> dict:
    """The filters, the whitener and its means (computed with, and held to
    the patches), the sampled patches that the whitener was fitted on
    (sampled again from the training images: the same rows for the same
    seed), and what the program computed (compared): the held-out rows'
    features and scores that `scores` last made, and the training images'
    features, made again by the fitted featurizer (the fit's own program)."""
    from keystone_tpu.data.dataset import ArrayDataset

    if "heldout_scores" not in fitted.given:
        raise RuntimeError("`scores` has not run on this fit: there are no held-out features to give")
    patches = cifar.sample_random_patches(ArrayDataset(fitted.train_x), fitted.program_config)
    return dict(
        fitted.given,
        patches=patches.astype(np.float32),
        train_features=np.asarray(_features(fitted, fitted.train_x).data),
    )


def health(fitted: Fitted) -> list[str]:
    return program.fit_health(fitted.pipeline)
