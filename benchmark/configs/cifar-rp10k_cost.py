"""cifar-rp10k: the operations and bytes one fit and one scoring request
need, from the cell's shapes alone. Counted as timit-rf16k_cost.py counts
them: multiply-adds as 2 operations whatever the precision, compulsory
bytes only, the featurizer once (an implementation that featurizes twice
does not get credit for the second pass)."""

from __future__ import annotations

F32 = 4


def _shapes(config: dict) -> dict:
    s, c = config["patch_size"], config["num_channels"]
    out = config["image_size"] - s + 1  # valid convolution
    half = config["pool_size"] // 2
    pools = len(range(half, out, config["pool_stride"]))
    return {
        "positions": out * out,
        "patch": s * s * c,
        "features": pools * pools * 2 * config["num_filters"],
    }


def _featurize_flops(config: dict, rows: int) -> float:
    sh = _shapes(config)
    return 2.0 * rows * sh["positions"] * sh["patch"] * config["num_filters"]


def fit_cost(config: dict, rows: int) -> dict:
    sh = _shapes(config)
    n, k, b = rows, config["num_classes"], config["block_size"]
    d = -(-sh["features"] // b) * b
    steps = (d // b) * config["num_epochs"]
    per_step = 2 * n * b * b + 3 * (2 * n * b * k) + b ** 3 / 3 + 2 * b * b * k
    flops = _featurize_flops(config, n) + steps * per_step
    image = config["image_size"] ** 2 * config["num_channels"]
    # read the images and labels, write the features and read them back
    # for standardising and for each block step, write the weights
    nbytes = F32 * (n * image + n * k + 2 * n * sh["features"] + steps * n * b + d * k)
    return {"flops": float(flops), "bytes": float(nbytes)}


def apply_cost(config: dict, rows: int) -> dict:
    sh = _shapes(config)
    k = config["num_classes"]
    flops = _featurize_flops(config, rows) + 2.0 * rows * sh["features"] * k
    image = config["image_size"] ** 2 * config["num_channels"]
    nbytes = F32 * (rows * image + config["num_filters"] * sh["patch"] + sh["features"] * (k + 2) + rows)
    return {"flops": float(flops), "bytes": float(nbytes)}
