"""timit-rf16k: the calls into the program, and its seeded data.

The only file of this configuration that imports keystone_tpu. The
pipeline is built exactly as `keystone-tpu timit` builds it
(`pipelines.timit.build_pipeline`), on data made here from the seed.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import program

LABEL_RULE_SEED = 54321  # the fixed rule behind the labels, the same for every seed


def make_data(config: dict, seed: int, rows: int, index: int) -> dict:
    """Data set `index` of this seed, on the host: the program's
    `synthetic_timit` rule, copied (x ~ N(0, 1); the label is the argmax
    of a fixed linear rule over the 440 inputs)."""
    rng = np.random.default_rng([seed, 1000 + index])
    x = rng.standard_normal(size=(rows, config["input_dim"]), dtype=np.float32)
    rule = np.random.default_rng(LABEL_RULE_SEED).normal(
        size=(config["input_dim"], config["num_classes"])
    ).astype(np.float32)
    y = np.argmax(x @ rule, axis=1).astype(np.int32)
    return {"x": x, "y": y}


def _program_config(config: dict, seed: int):
    from keystone_tpu.pipelines.timit import TimitConfig

    return TimitConfig(
        num_cosines=config["num_cosines"],
        num_cosine_features=config["num_cosine_features"],
        gamma=config["gamma"],
        rf_type=config["rf_type"],
        reg=config["reg"],
        num_epochs=config["num_epochs"],
        seed=seed,
    )


def fit(config: dict, data: dict, seed: int):
    """One fit as a user of `keystone-tpu timit` gets it: a new Pipeline
    over host-resident data, fitted, the weights ready on the device."""
    import jax

    from keystone_tpu.data.dataset import ArrayDataset
    from keystone_tpu.data.loaders.csv import LabeledData
    from keystone_tpu.pipelines import timit

    train = LabeledData(ArrayDataset(data["y"]), ArrayDataset(data["x"]))
    fitted = timit.build_pipeline(
        _program_config(config, seed), train, config["input_dim"]
    ).fit()
    jax.block_until_ready(program.block_mapper(fitted).weights)
    return fitted


def given(fitted) -> dict:
    """Nothing: the reference makes the weights from the seed itself."""
    return {}


def apply(fitted, x: np.ndarray) -> np.ndarray:
    """One scoring request: host rows in, host labels out."""
    from keystone_tpu.data.dataset import ArrayDataset

    return np.asarray(fitted.apply_batch(ArrayDataset(x)).data)


def scores(config: dict, fitted, x: np.ndarray, seed: int) -> np.ndarray:
    """The program's real-valued class scores for `x`: its featurizer,
    then its fitted mapper, without the final argmax."""
    from keystone_tpu.data.dataset import ArrayDataset
    from keystone_tpu.pipelines import timit

    featurizer = timit.build_featurizer(_program_config(config, seed), config["input_dim"])
    features = featurizer(ArrayDataset(x)).get()
    return np.asarray(program.block_mapper(fitted).apply_batch(features).data)


def health(fitted) -> list[str]:
    return program.fit_health(fitted)
