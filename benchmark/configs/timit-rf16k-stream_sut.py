"""timit-rf16k-stream: the calls into the program, and its seeded data.

The only file of this configuration that imports keystone_tpu. The
pipeline is built exactly as `keystone-tpu timit` builds it
(`pipelines.timit.build_pipeline`), on data made here from the seed. At
this configuration's rows the feature matrix does not fit the devices, so
the entry point yields its single-chain form, which the streaming plan
rule absorbs: `Pipeline.fit` folds row chunks into one Gram carry a chip.
The chunk is handed over by the means a user has, the program's
`KEYSTONE_STREAM_CHUNK_ROWS`, set before the first fit.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.harness import program
from keystone_tpu.pipelines import timit

LABEL_RULE_SEED = 54321  # the fixed rule behind the labels, the same for every seed

# Named at import, before any data is made: a checkout whose entry point
# has no streaming form (a commit before PR 30) fails here, in seconds,
# and not after a fit of 32 GiB of features has been tried in core.
build_scoring_featurizer = timit.build_stacked_featurizer


def make_data(config: dict, seed: int, rows: int, index: int) -> dict:
    """Data set `index` of this seed, on the host: timit-rf16k's rule
    (the program's `synthetic_timit`: x ~ N(0, 1); the label is the argmax
    of a fixed linear rule over the 440 inputs)."""
    rng = np.random.default_rng([seed, 1000 + index])
    x = rng.standard_normal(size=(rows, config["input_dim"]), dtype=np.float32)
    rule = np.random.default_rng(LABEL_RULE_SEED).normal(
        size=(config["input_dim"], config["num_classes"])
    ).astype(np.float32)
    y = np.argmax(x @ rule, axis=1).astype(np.int32)
    return {"x": x, "y": y}


def _program_config(config: dict, seed: int):
    return timit.TimitConfig(
        num_cosines=config["num_cosines"],
        num_cosine_features=config["num_cosine_features"],
        gamma=config["gamma"],
        rf_type=config["rf_type"],
        reg=config["reg"],
        num_epochs=config["num_epochs"],
        seed=seed,
    )


def fit(config: dict, data: dict, seed: int):
    """One fit as a user of `keystone-tpu timit` gets it at this size: a
    new Pipeline over host-resident data, streamed in chunks of
    `chunk_rows` over every chip, the weights ready on the device."""
    import jax

    from keystone_tpu.data.dataset import ArrayDataset
    from keystone_tpu.data.loaders.csv import LabeledData

    os.environ["KEYSTONE_STREAM_CHUNK_ROWS"] = str(config["chunk_rows"])
    train = LabeledData(ArrayDataset(data["y"]), ArrayDataset(data["x"]))
    fitted = timit.build_pipeline(
        _program_config(config, seed), train, config["input_dim"]
    ).fit()
    jax.block_until_ready(program.block_mapper(fitted).weights)
    return fitted


def given(fitted) -> dict:
    """Nothing: the reference makes the weights from the seed itself."""
    return {}


def scores(config: dict, fitted, x: np.ndarray, seed: int) -> np.ndarray:
    """The program's real-valued class scores for `x`: its featurizer,
    then its fitted mapper, without the final argmax."""
    from keystone_tpu.data.dataset import ArrayDataset

    featurizer = build_scoring_featurizer(_program_config(config, seed), config["input_dim"])
    features = featurizer(ArrayDataset(x)).get()
    return np.asarray(program.block_mapper(fitted).apply_batch(features).data)


def health(fitted) -> list[str]:
    """The program's own signs of a fit gone wrong, and of a fit that was
    not the one this cell measures: not streamed, not over every chip,
    or with a program traced after its first chunk."""
    import jax

    from keystone_tpu.workflow.streaming import last_stream_report

    problems = program.fit_health(fitted)
    report = last_stream_report()
    if report is None:
        return problems + ["no fit of this process streamed"]
    if report.shards != len(jax.devices()):
        problems.append(f"the fold ran over {report.shards} of {len(jax.devices())} devices")
    if report.chunks * report.chunk_rows < report.num_examples:
        problems.append(f"{report.chunks} chunks of {report.chunk_rows} do not cover {report.num_examples} rows")
    if report.compiles_steady_state:
        problems.append(f"{report.compiles_steady_state} programs traced after the first chunk")
    return problems
