"""timit-krr: the calls into the program, and its seeded data.

The only file of this configuration that imports keystone_tpu. The
pipeline is built exactly as `keystone-tpu timit-kernel` builds it
(`pipelines.timit.build_pipeline` with `solver="kernel"`): class
indicators on the labels, `KernelRidgeRegression` over the Gaussian
kernel on the raw frames, the arg-max; fitted by `Pipeline.fit`, on data
made here from the seed by timit-rf16k's rule.
"""

from __future__ import annotations

import numpy as np

from keystone_tpu.pipelines import timit

LABEL_RULE_SEED = 54321  # the fixed rule behind the labels, the same for every seed

# Named at import, before any data is made: a checkout whose TIMIT entry
# point has no kernel form (a commit before PR 34) fails here, in
# seconds, with a TypeError, and not after 131,072 rows were drawn twice.
_KERNEL_FORM = timit.TimitConfig(solver="kernel")


def make_data(config: dict, seed: int, rows: int, index: int) -> dict:
    """Data set `index` of this seed, on the host: timit-rf16k's rule,
    copied (the program's `synthetic_timit`: x ~ N(0, 1); the label is
    the argmax of a fixed linear rule over the 440 inputs)."""
    rng = np.random.default_rng([seed, 1000 + index])
    x = rng.standard_normal(size=(rows, config["input_dim"]), dtype=np.float32)
    rule = np.random.default_rng(LABEL_RULE_SEED).normal(
        size=(config["input_dim"], config["num_classes"])
    ).astype(np.float32)
    y = np.argmax(x @ rule, axis=1).astype(np.int32)
    return {"x": x, "y": y}


def _program_config(config: dict, seed: int):
    return timit.TimitConfig(
        solver="kernel",
        kernel_gamma=config["kernel_gamma"],
        kernel_block_size=config["block_size"],
        kernel_num_epochs=config["num_epochs"],
        reg=config["reg"],
        seed=seed,  # the block permuter
    )


def kernel_mapper(pipeline):
    """The fitted KernelBlockLinearMapper inside a fitted pipeline's
    graph (it opts out of fusion, so it is a node of its own)."""
    from keystone_tpu.ops.learning.kernel import KernelBlockLinearMapper

    found = [
        m
        for op in pipeline.graph.operators.values()
        for m in getattr(op, "members", (op,))
        if isinstance(m, KernelBlockLinearMapper)
    ]
    if len(found) != 1:
        raise RuntimeError(f"expected one KernelBlockLinearMapper, found {len(found)}")
    return found[0]


def fit(config: dict, data: dict, seed: int):
    """One fit as a user of `keystone-tpu timit-kernel` gets it: a new
    Pipeline over host-resident data, fitted, the duals ready on the
    device."""
    import jax

    from keystone_tpu.data.dataset import ArrayDataset
    from keystone_tpu.data.loaders.csv import LabeledData

    train = LabeledData(ArrayDataset(data["y"]), ArrayDataset(data["x"]))
    fitted = timit.build_pipeline(
        _program_config(config, seed), train, config["input_dim"]
    ).fit()
    jax.block_until_ready(kernel_mapper(fitted).duals)
    return fitted


def given(fitted) -> dict:
    """Nothing: the reference makes the block order from the seed itself."""
    return {}


def scores(config: dict, fitted, x: np.ndarray, seed: int) -> np.ndarray:
    """The program's real-valued class scores for `x`: its fitted mapper
    through `apply_batch`, without the final argmax."""
    from keystone_tpu.data.dataset import ArrayDataset

    return np.asarray(kernel_mapper(fitted).apply_batch(ArrayDataset(x)).data)


def health(fitted) -> list[str]:
    """Ways a kernel fit can go wrong without raising: an out-of-memory
    error makes the solver's ladder halve the block and go on (the mapper
    then carries `degradation` and a smaller block), or the reliability
    layer recovered from something."""
    from keystone_tpu import reliability

    problems = []
    degradation = getattr(kernel_mapper(fitted), "degradation", None)
    if degradation is not None:
        problems.append(f"the mapper reports degradation: {degradation}")
    events = reliability.get_recovery_log().summary()["events"]
    if events:
        problems.append(f"the recovery log is not empty: {events}")
    return problems
