"""timit-rf16k: the operations and bytes one fit and one scoring request
need, from the cell's shapes alone.

Operations are the algorithm's multiply-adds counted as 2 each, whatever
precision they run at: a float32 matmul at HIGHEST takes six bfloat16
passes on the MXU, so the solver's share of the bfloat16 peak cannot pass
about a sixth, and a cheaper precision shows as a gain. Bytes are the
compulsory traffic: what has to be read and written once, not what an
implementation that materialises intermediates moves.
"""

from __future__ import annotations

F32 = 4


def fit_cost(config: dict, rows: int) -> dict:
    n = rows
    d_in = config["input_dim"]
    d = config["num_cosines"] * config["num_cosine_features"]
    k = config["num_classes"]
    b = config["block_size"]
    steps = (d // b) * config["num_epochs"]
    featurize = 2 * n * d_in * d
    # per block step: the Gram, A_b W_b, A_b^T R and A_b dW products, and
    # a Cholesky factorisation (b^3 / 3) with two triangular solves
    per_step = 2 * n * b * b + 3 * (2 * n * b * k) + b ** 3 / 3 + 2 * b * b * k
    flops = featurize + steps * per_step
    # read x and y, write and re-read the feature matrix once per block
    # step it takes part in, write the weights
    nbytes = F32 * (n * d_in + n * k + n * d + steps * n * b + d * k)
    return {"flops": float(flops), "bytes": float(nbytes)}


def apply_cost(config: dict, rows: int) -> dict:
    d_in = config["input_dim"]
    d = config["num_cosines"] * config["num_cosine_features"]
    k = config["num_classes"]
    flops = 2 * rows * d_in * d + 2 * rows * d * k
    # rows in, weights once, one int32 label per row out
    nbytes = F32 * (rows * d_in + d_in * d + d + d * k + k + rows)
    return {"flops": float(flops), "bytes": float(nbytes)}
