"""timit-krr: the operations and bytes one fit and one scoring request
need, from the shapes alone.

Operations are the algorithm's multiply-adds counted as 2 each, whatever
precision they run at: every product here is float32 at HIGHEST, six
bfloat16 passes on the MXU, so the share of the bfloat16 peak cannot pass
a sixth. The exp of the kernel's epilogue (one per panel entry, n b a
block step) is left out: it is not a multiply-add and runs on another
unit. Bytes are the compulsory traffic: the rows and the duals are read
once a block step and the panel is never written (an implementation that
writes and reads it again moves 2 x 4 n b more a step, and shows it in
this share).
"""

from __future__ import annotations

F32 = 4


def fit_cost(config: dict, rows: int) -> dict:
    n, d, k, b = rows, config["input_dim"], config["num_classes"], config["block_size"]
    steps = config["num_epochs"] * -(-n // b)
    # a block step: the panel's distance matmul (n x d x b), the residual
    # K_b^T W (b x n x k), K_bb (b x d x b), K_bb W_b and the two
    # triangular solves (2 b^2 k and 2 x b^2 k), one Cholesky (b^3 / 3)
    flops = steps * (2 * n * b * d + 2 * n * b * k + 2 * b * b * d + 4 * b * b * k + b ** 3 / 3)
    nbytes = F32 * steps * (n * d + n * k + b * k) + F32 * (n * k + n * k)  # + labels read, duals written
    return {"flops": float(flops), "bytes": float(nbytes)}


def apply_cost(config: dict, rows: int) -> dict:
    """Scoring `rows` rows against the configuration's train rows."""
    n, d, k = config["rows"], config["input_dim"], config["num_classes"]
    flops = 2 * rows * n * d + 2 * rows * n * k
    nbytes = F32 * (rows * d + n * d + n * k + rows * k)
    return {"flops": float(flops), "bytes": float(nbytes)}
