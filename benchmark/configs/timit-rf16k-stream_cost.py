"""timit-rf16k-stream: the operations and bytes ONE CHIP needs for one
streamed fit, from the cell's shapes alone.

`kernel_roofline_pct` divides the least time by the device's busy time a
fit, which the harness takes as the mean over chips
(`Reduction.busy_inside`), and by ONE chip's peak. So the cost is one
chip's share: a `1 / chips` part of the row-wise work (featurizing and the
fold into the Gram carry), and all of the finish, which every chip runs
on the reduced statistics (the reduction leaves them replicated).

Operations are the algorithm's multiply-adds counted as 2 each, whatever
precision they run at: the Gram fold is float32 at HIGHEST, six bfloat16
passes on the MXU, so its share of the bfloat16 peak cannot pass a sixth.
The block steps on the statistics are counted as the algorithm needs them:
one Cholesky factorisation a BLOCK (the program today factors a block
again in every epoch: PERF.md section 7). Bytes are the compulsory traffic.
"""

from __future__ import annotations

F32 = 4


def fit_cost(config: dict, rows: int) -> dict:
    chips = config["chips"]
    n = rows / chips  # this chip's rows of every chunk
    d_in = config["input_dim"]
    d = config["num_cosines"] * config["num_cosine_features"]
    k = config["num_classes"]
    b = config["block_size"]
    blocks = d // b
    steps = blocks * config["num_epochs"]
    # row-wise, a chip's share: featurize, fold into G (d x d), C (d x k)
    fold = 2 * n * d_in * d + 2 * n * d * d + 2 * n * d * k
    # replicated finish: per step G[b, :] W and G[b, b] W_b and two
    # triangular solves; per block one factorisation
    finish = steps * (2 * b * d * k + 2 * b * b * k + 2 * b * b * k) + blocks * b ** 3 / 3
    # read this chip's x and y once; read and write the Gram carry once a
    # chunk at best, counted once; read the reduced Gram once a step
    nbytes = F32 * (n * d_in + n * k + 2 * d * d + 2 * d * k + steps * b * d + d * k)
    return {"flops": float(fold + finish), "bytes": float(nbytes)}

