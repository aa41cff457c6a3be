"""cifar-rp10k-8k: the operations and bytes one fit, one scoring request
and one featurization need, from the cell's shapes alone.

Operations are the algorithm's multiply-adds counted as 2 each, whatever
precision they run at: the convolution's at the MXU default (bfloat16
inputs), the solver's float32 at HIGHEST (six bfloat16 passes, so the
solver's share of the bfloat16 peak cannot pass a sixth). The patch
statistics (two one-channel box sums, a hundredth of the convolution)
are left out. Bytes are the compulsory traffic: the images read, the
filters read, the features written once; a panel that goes through the
chip's memory and back is not compulsory, and shows in the share.
"""

from __future__ import annotations

F32 = 4


def _shapes(config: dict) -> dict:
    s, c = config["patch_size"], config["num_channels"]
    out = config["image_size"] - s + 1  # valid convolution
    pools = len(range(config["pool_size"] // 2, out, config["pool_stride"]))
    return {
        "positions": out * out,
        "patch": s * s * c,
        "features": pools * pools * 2 * config["num_filters"],
        "image": config["image_size"] ** 2 * c,
    }


def conv_cost(config: dict, rows: int) -> dict:
    """The featurizer alone (`conv/*` under `feat/FusedConvFeaturizer`):
    the one large product, the images in, the filters in, the features out."""
    sh = _shapes(config)
    flops = 2.0 * rows * sh["positions"] * sh["patch"] * config["num_filters"]
    nbytes = F32 * (rows * sh["image"] + config["num_filters"] * sh["patch"] + rows * sh["features"])
    return {"flops": float(flops), "bytes": float(nbytes)}


def fit_cost(config: dict, rows: int) -> dict:
    """One fit: featurize, standardise, one pass of BCD over the blocks."""
    sh = _shapes(config)
    n, k, b = rows, config["num_classes"], config["block_size"]
    d = -(-sh["features"] // b) * b
    steps = (d // b) * config["num_epochs"]
    # a block step: the Gram (n b^2), the cross and two residual products (3 n b k), one Cholesky, the solves
    per_step = 2 * n * b * b + 3 * (2 * n * b * k) + b ** 3 / 3 + 2 * b * b * k
    conv = conv_cost(config, n)
    # and the features read back to standardise, the standardised copy written and read once a block step
    nbytes = conv["bytes"] + F32 * (n * k + 2 * n * sh["features"] + steps * n * b + d * k)
    return {"flops": conv["flops"] + steps * per_step, "bytes": float(nbytes)}


def apply_cost(config: dict, rows: int) -> dict:
    sh = _shapes(config)
    k = config["num_classes"]
    conv = conv_cost(config, rows)
    flops = conv["flops"] + 2.0 * rows * sh["features"] * k
    nbytes = F32 * (rows * sh["image"] + config["num_filters"] * sh["patch"] + sh["features"] * (k + 2) + rows)
    return {"flops": float(flops), "bytes": float(nbytes)}
