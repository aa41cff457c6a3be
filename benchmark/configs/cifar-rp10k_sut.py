"""cifar-rp10k: the calls into the program, and its seeded data.

The only file of this configuration that imports keystone_tpu. The
pipeline is built as `pipelines.cifar.run` builds variant `random_patch`:
filters and whitener learned from the images, then the block solver.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import numpy as np

from benchmark.harness import program

LABEL_RULE_SEED = 54321  # the fixed rule behind the labels, the same for every seed
RULE_GRID = 4  # the rule sees the image as 4 x 4 block means per channel


@dataclass
class Fitted:
    """A fitted pipeline and what the reference is given of it."""

    pipeline: Any
    given: dict


def make_data(config: dict, seed: int, rows: int, index: int) -> dict:
    """Data set `index` of this seed, on the host: uniform whole-number
    pixels 0..255 as float32; the label is the argmax of a fixed linear
    rule over the image's block means."""
    size, channels = config["image_size"], config["num_channels"]
    rng = np.random.default_rng([seed, 1000 + index])
    x = rng.integers(0, 256, size=(rows, size, size, channels), dtype=np.uint8).astype(np.float32)
    cell = size // RULE_GRID
    pooled = x.reshape(rows, RULE_GRID, cell, RULE_GRID, cell, channels).mean(axis=(2, 4))
    rule = np.random.default_rng(LABEL_RULE_SEED).normal(
        size=(RULE_GRID * RULE_GRID * channels, config["num_classes"])
    )
    centred = pooled.reshape(rows, -1) - 127.5
    y = np.argmax(centred @ rule, axis=1).astype(np.int32)
    return {"x": x, "y": y}


def _program_config(config: dict, seed: int):
    from keystone_tpu.pipelines.cifar import RandomCifarConfig

    return RandomCifarConfig(
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
    from keystone_tpu.pipelines import cifar

    program_config = _program_config(config, seed)
    train = ArrayDataset({"image": data["x"], "label": data["y"]})
    filters, whitener = cifar.learn_random_patch_filters(ArrayDataset(data["x"]), program_config)
    pipeline = cifar.build_random_patch(
        train, program_config, filters, whitener, solver="block"
    ).fit()
    jax.block_until_ready(program.block_mapper(pipeline).weights)
    given = {
        "filters": np.asarray(filters, np.float32),
        "whitener_means": np.asarray(whitener.means, np.float32),
    }
    return Fitted(pipeline, given)


def given(fitted: Fitted) -> dict:
    return fitted.given


def apply(fitted: Fitted, x: np.ndarray) -> np.ndarray:
    from keystone_tpu.data.dataset import ArrayDataset

    return np.asarray(fitted.pipeline.apply_batch(ArrayDataset(x)).data)


def scores(config: dict, fitted: Fitted, x: np.ndarray, seed: int) -> np.ndarray:
    """The program's real-valued class scores for `x`: every member of
    the fitted chain but the final argmax."""
    from keystone_tpu.data.dataset import ArrayDataset

    data = ArrayDataset(x)
    for member in _chain(fitted.pipeline)[:-1]:
        data = member.apply_batch(data)
    return np.asarray(data.data)


def health(fitted: Fitted) -> list[str]:
    return program.fit_health(fitted.pipeline)
