"""imagenet-siftlcs-fv: the operations and bytes one fit and one scoring
request need, from the shapes alone.

Counted from the algorithm, not from what the program happens to do. A
request's bytes are the compulsory traffic: every plane that has to
exist is written once and read once, every descriptor is written once
and read once by the projection that follows it, and nothing else moves
(an implementation that holds the eight orientation planes of a scale in
three layouts, or writes the Hellinger copy of its descriptors, moves
more and shows it in this share). Operations are multiply-adds counted
as 2 each, whatever precision they run at (the program's convolutions
and products are float32 at HIGHEST: six bfloat16 passes on the MXU);
square roots, arc tangents and exponentials are left out (they are no
multiply-adds and run on another unit). The work is bandwidth-bound on a
v5e by a wide margin, so the roofline this feeds is a bandwidth one.
"""

from __future__ import annotations

import math

F32 = 4
ORIENTATIONS, BINS = 8, 4


def _sift_scales(config: dict):
    """(bin, smoothing taps, descriptors) of every scale that has any."""
    x_dim, y_dim = config["image_size"]
    out = []
    for s in range(config["sift_scales"]):
        b = config["sift_bin_size"] + 2 * s
        step = config["sift_step_size"] + s * config["sift_scale_step"]
        off = max(0, (1 + 2 * config["sift_scales"]) - 3 * s)
        span = (BINS - 1) * b
        nx = max(0, (x_dim - 1 - off - span) // step + 1)
        ny = max(0, (y_dim - 1 - off - span) // step + 1)
        if nx * ny:
            out.append((b, 2 * max(1, math.ceil(4.0 * b / 6.0)) + 1, nx * ny))
    return out


def _lcs_keypoints(config: dict) -> int:
    x_dim, y_dim = config["image_size"]
    start, stride = config["lcs_border"], config["lcs_stride"]
    return len(range(start, x_dim - start, stride)) * len(range(start, y_dim - start, stride))


def image_cost(config: dict) -> dict:
    """One image through both branches, the mapper and top-5."""
    x_dim, y_dim = config["image_size"]
    pixels, c = x_dim * y_dim, config["image_channels"]
    d, k = config["desc_dim"], config["vocab_size"]
    classes, features = config["num_classes"], config["feature_dim"]
    flops, nbytes = 0.0, 0.0

    # the image goes up and is read by either branch; the gray plane
    nbytes += F32 * pixels * c * 2 + F32 * pixels * 2
    flops += 2 * pixels * c
    for b, taps, descriptors in _sift_scales(config):
        # separable smoothing, the gradient, 8 orientation planes, their
        # separable triangular binning (2 b - 1 taps), the normalisations
        flops += 2 * pixels * 2 * taps + 8 * pixels + 2 * ORIENTATIONS * pixels
        flops += 2 * pixels * ORIENTATIONS * 2 * (2 * b - 1)
        flops += 6 * descriptors * 128
        # smoothed plane and the binned planes: written once, read once;
        # the descriptors: written once (read by the projection, below)
        nbytes += F32 * pixels * 2 + F32 * pixels * ORIENTATIONS * 2
        nbytes += F32 * descriptors * 128
    sift = sum(n for _, _, n in _sift_scales(config))
    lcs, lcs_width = _lcs_keypoints(config), BINS * BINS * c * 2
    # LCS: two separable box filters over x and x^2, the deviations, the gather
    flops += 2 * pixels * c * 2 * 2 * config["lcs_patch"] + 4 * pixels * c
    nbytes += F32 * pixels * c * 2 * 2 + F32 * lcs * lcs_width
    for n, width in ((sift, 128), (lcs, lcs_width)):
        # projection to d (reads the descriptors, writes the reduced ones),
        # posteriors (two products of d x k) and the two moment products
        # (read the reduced descriptors once more), the (d, 2 k) encoding
        flops += 2 * n * width * d + 2 * 2 * n * d * k + 2 * 2 * n * d * k
        nbytes += F32 * n * width + F32 * n * d * 2 + F32 * d * 2 * k * 2
    flops += 2 * features * classes
    nbytes += F32 * (features + classes)
    return {"flops": flops, "bytes": nbytes}


def apply_cost(config: dict, rows: int) -> dict:
    """A scoring request of `rows` images; the mapper's weights are read once."""
    one = image_cost(config)
    weights = F32 * config["feature_dim"] * config["num_classes"]
    return {"flops": float(rows * one["flops"]), "bytes": float(rows * one["bytes"] + weights)}


def fit_cost(config: dict, rows: int) -> dict:
    """A fit: three passes over either branch's extractor (the PCA's
    samples, the mixture's, the encoding), EM at its iteration limit, and
    the per-class solves by their shared factorisation (Woodbury)."""
    one = image_cost(config)
    d, k, f, classes = config["desc_dim"], config["vocab_size"], config["feature_dim"], config["num_classes"]
    samples = config["num_gmm_samples"]
    em = config["gmm"]["max_iterations"] * 2 * (2 * 2 * samples * d * k)
    solve = 2 * rows * f * f + f**3 / 3 + classes * 2 * f * f * 8
    return {
        "flops": float(3 * rows * one["flops"] + 2 * em + solve),
        "bytes": float(3 * rows * one["bytes"] + 2 * config["gmm"]["max_iterations"] * F32 * samples * d),
    }
