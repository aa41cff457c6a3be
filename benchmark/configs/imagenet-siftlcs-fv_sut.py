"""imagenet-siftlcs-fv: the calls into the program, and its seeded data.

The only file of this configuration that imports keystone_tpu. The
pipeline is built exactly as `keystone-tpu imagenet-sift-lcs-fv` builds
it (`pipelines.imagenet.build_pipeline`, then `Pipeline.fit`, then
`FittedPipeline.apply_batch` on host images): nothing of
`pipelines/imagenet_streaming.py`, no loop over rows here. Where 2,048
images of dense SIFT do not fit the chip, the program's executor runs the
chain over row chunks by itself.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.harness import program
from keystone_tpu.ops.stats.core import ColumnSampler
from keystone_tpu.pipelines import imagenet

# Named at import, before any data is made: a checkout whose sampler
# cannot say which columns it picks, or whose mixtures do not keep how
# they were fitted (a commit before PR 36), fails here, in seconds.
_SAMPLE_INDICES = ColumnSampler.sample_indices
_FLAT_SHARE = 0.375  # the side of an image's flat square, as a share of its side


def make_data(config: dict, seed: int, rows: int, index: int) -> dict:
    """Data set `index` of this seed, on the host: `rows` images of
    `image_size` x 3, float32 in 0-255 as the loader hands them, and their
    labels. No data set is on this machine, so an image is made to have
    what dense SIFT and colour statistics read: an oriented wave whose
    direction, wavelength and colour are its class's (what the model can
    learn), a coarse 8 x 8 colour mosaic and fine noise of its own, and one
    square of even brightness: a colour texture whose luminance (the gray
    plane SIFT reads) is constant, so the descriptors inside it are under
    SIFT's contrast threshold and are zeroed (between 1% and 50% of all,
    `PERF.md`) while every colour channel still varies there (a square
    flat in colour too makes LCS's standard deviation the square root of
    float32 rounding noise, on which no two implementations agree). Every
    class is there twice before any is there a third time, and a third
    time before any is there a fourth. Made `_BLOCK` images at a
    time, each block from a generator of its own: what one block needs
    stays in the host's cache and the blocks are made side by side on
    host threads (making the data is set-up time)."""
    classes = config["num_classes"]
    rng = np.random.default_rng([seed, 1000 + index])
    # every class twice, then the seed's choice of classes once more each,
    # round by round: the largest class's count is a function of `rows`
    # alone (3 at 2,048 rows of 1,000 classes), and with it the shape of
    # the solver's per-class windows, which a program is compiled for
    rounds = [rng.permutation(classes) for _ in range(max(0, -(-(rows - 2 * classes) // classes)))]
    y = np.concatenate([np.tile(np.arange(classes), 2), *rounds])[:rows]
    y = rng.permutation(y).astype(np.int32)
    x = np.empty((rows, *config["image_size"], 3), np.float32)

    def fill(block: int) -> None:
        rows_of = slice(block * _BLOCK, min((block + 1) * _BLOCK, rows))
        _images(config, np.random.default_rng([seed, 1000 + index, block]), y[rows_of], x[rows_of])

    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, range(-(-rows // _BLOCK))))  # numpy's loops release the lock
    return {"x": x, "y": y}


_BLOCK = 64
# A colour direction the gray plane does not see: 0.1140 b + 0.5870 g +
# 0.2989 r = 0 (the program's and the reference's luminance of a BGR pixel).
_NO_LUMINANCE = np.array([1.0, -0.5, (0.5 * 0.5870 - 0.1140) / 0.2989], np.float32)


def _images(config: dict, rng, y: np.ndarray, out: np.ndarray) -> None:
    side_x, side_y = config["image_size"]
    classes, rows = config["num_classes"], len(y)
    rule = np.random.default_rng(20260)  # the classes' looks: the same for every seed
    direction = rule.uniform(0.0, np.pi, classes)
    wavelength = rule.uniform(6.0, 40.0, classes)
    colour = rule.uniform(0.3, 1.0, (classes, 3)).astype(np.float32)

    u = np.arange(side_x, dtype=np.float32)[None, :, None]
    v = np.arange(side_y, dtype=np.float32)[None, None, :]
    fx = (np.cos(direction) / wavelength).astype(np.float32)[y][:, None, None]
    fy = (np.sin(direction) / wavelength).astype(np.float32)[y][:, None, None]
    phase = rng.uniform(0.0, 2 * np.pi, rows).astype(np.float32)[:, None, None]
    wave = np.sin(np.float32(2 * np.pi) * (fx * u + fy * v) + phase)  # (rows, X, Y)

    mosaic = rng.uniform(-40.0, 40.0, (rows, 8, 8, 3)).astype(np.float32)
    mosaic = np.repeat(np.repeat(mosaic, -(-side_x // 8), axis=1), -(-side_y // 8), axis=2)
    np.multiply(60.0 * wave[..., None], colour[y][:, None, None, :], out=out)
    out += 127.5
    out += mosaic[:, :side_x, :side_y]
    out += rng.integers(-4, 5, size=out.shape, dtype=np.int8)

    flat_x, flat_y = int(_FLAT_SHARE * side_x), int(_FLAT_SHARE * side_y)
    x0 = rng.integers(0, side_x - flat_x + 1, rows)[:, None, None]
    y0 = rng.integers(0, side_y - flat_y + 1, rows)[:, None, None]
    inside = (u >= x0) & (u < x0 + flat_x) & (v >= y0) & (v < y0 + flat_y)
    level = rng.uniform(90.0, 160.0, (rows, 1, 1, 1)).astype(np.float32)
    texture = rng.integers(-40, 41, size=(rows, side_x, side_y, 1)).astype(np.float32)
    np.copyto(out, level + texture * _NO_LUMINANCE, where=inside[..., None])
    np.clip(out, 0.0, 255.0, out=out)


def _program_config(config: dict, seed: int):
    return imagenet.ImageNetSiftLcsFVConfig(
        reg=config["reg"],
        mixture_weight=config["mixture_weight"],
        desc_dim=config["desc_dim"],
        vocab_size=config["vocab_size"],
        sift_scale_step=config["sift_scale_step"],
        lcs_stride=config["lcs_stride"],
        lcs_border=config["lcs_border"],
        lcs_patch=config["lcs_patch"],
        num_pca_samples=config["num_pca_samples"],
        num_gmm_samples=config["num_gmm_samples"],
        num_classes=config["num_classes"],
        image_size=tuple(config["image_size"]),
        solver_block_size=config["block_size"],
        seed=seed,  # the samplers' columns and the mixtures' k-means++ start
    )


def _branches(pipeline) -> dict:
    """{"sift" | "lcs": (the branch's fitted BatchPCATransformer, its
    fitted FisherVector)}: the LCS branch is the one whose encoder has
    the LCS extractor upstream, and the projection is what the encoder
    reads."""
    from keystone_tpu.ops.images.fisher import FisherVector
    from keystone_tpu.ops.images.lcs import LCSExtractor
    from keystone_tpu.ops.learning.pca import BatchPCATransformer

    graph, found = pipeline.graph, {}
    for node, op in graph.operators.items():
        if not isinstance(op, FisherVector):
            continue
        above, cur = [], node
        while cur in graph.operators:
            above.append(graph.operators[cur])
            deps = graph.get_dependencies(cur)
            cur = deps[0] if deps else None
        if not isinstance(above[1], BatchPCATransformer):
            raise RuntimeError(f"expected a projection under the encoder, found {above[1].label}")
        found["lcs" if any(isinstance(a, LCSExtractor) for a in above) else "sift"] = (above[1], op)
    if sorted(found) != ["lcs", "sift"]:
        raise RuntimeError(f"expected a SIFT and an LCS encoder, found {sorted(found)}")
    return found


def fit(config: dict, data: dict, seed: int):
    """One fit as a user of `keystone-tpu imagenet-sift-lcs-fv` gets it: a
    new Pipeline over host-resident images, fitted through `Pipeline.fit`,
    the weights ready on the device."""
    import jax

    from keystone_tpu.data.dataset import ArrayDataset
    from keystone_tpu.ops.util.labels import ClassLabelIndicators

    cfg = _program_config(config, seed)
    images = ArrayDataset(data["x"])
    labels = ClassLabelIndicators(cfg.num_classes).apply_batch(ArrayDataset(data["y"]))
    fitted = imagenet.build_pipeline(cfg, images, labels).fit()
    jax.block_until_ready(program.block_mapper(fitted).weights)
    fitted.bench_columns = sampled_columns(config, seed, len(data["y"]))  # for `given`
    return fitted


def given(fitted) -> dict:
    """What is random in the program and no part of the model, as arrays:
    the samplers' columns (the program's own rule, asked of its sampler
    again with the arguments `build_pipeline` gave it) and how each
    branch's mixture was started and how many EM updates it took. And
    what the reference holds to its own: each branch's fitted projection
    and mixture, and the encodings of the held-out rows that `scores`
    last scored."""
    held = getattr(fitted, "bench_heldout_encodings", None)
    if held is None:
        raise RuntimeError("`scores` has not run on this fit: there are no held-out encodings to give")
    out = dict(fitted.bench_columns, heldout_encodings=held)
    for branch, (projection, encoder) in _branches(fitted).items():
        record = encoder.gmm.fit_record
        means0, vars0, weights0 = record["start"]
        out[f"{branch}_gmm_means0"] = means0
        out[f"{branch}_gmm_vars0"] = vars0
        out[f"{branch}_gmm_weights0"] = weights0
        out[f"{branch}_gmm_updates"] = np.asarray(record["updates"], np.int32)
        # the fitted codebooks: the reference holds them to its own fit, and
        # takes the checked basis for its coordinates (its file says why)
        out[f"{branch}_components"] = np.asarray(projection.components)
        out[f"{branch}_gmm_means"] = np.asarray(encoder.gmm.means).T
        out[f"{branch}_gmm_variances"] = np.asarray(encoder.gmm.variances).T
        out[f"{branch}_gmm_weights"] = np.asarray(encoder.gmm.weights)
    return out


def sampled_columns(config: dict, seed: int, rows: int) -> dict:
    """{"sift_columns", "lcs_columns"}: (rows, samples an image) each."""
    from keystone_tpu.ops.images.lcs import LCSExtractor
    from keystone_tpu.ops.images.sift import SIFTExtractor

    cfg = _program_config(config, seed)
    side_x, side_y = cfg.image_size
    per_image = max(1, cfg.num_pca_samples // max(1, rows))
    if per_image != max(1, cfg.num_gmm_samples // max(1, rows)):
        raise RuntimeError("the PCA's and the mixture's samplers differ: the reference reads one set")
    lcs = LCSExtractor(cfg.lcs_stride, cfg.lcs_border, cfg.lcs_patch)
    columns = {
        "sift": sum(SIFTExtractor(scale_step=cfg.sift_scale_step).grid_counts(side_x, side_y)),
        "lcs": len(range(lcs.stride_start, side_x - lcs.stride_start, lcs.stride))
        * len(range(lcs.stride_start, side_y - lcs.stride_start, lcs.stride)),
    }
    sampler = ColumnSampler(per_image, seed=cfg.seed)
    return {
        f"{branch}_columns": sampler.sample_indices(
            np.random.default_rng(sampler.seed), rows, c
        ).astype(np.int32)
        for branch, c in columns.items()
    }


_LAST_REQUEST = None  # (fitted, images) of the newest request, for `probe`


def apply(fitted, x: np.ndarray) -> np.ndarray:
    """One scoring request: host images in, the best class of each out
    (top-5's first column: the driver checks a request's shape and its
    agreement with the arg-max of the scores)."""
    from keystone_tpu.data.dataset import ArrayDataset

    global _LAST_REQUEST
    _LAST_REQUEST = (fitted, x)
    return np.asarray(fitted.apply_batch(ArrayDataset(x)).data)[:, 0]


def probe(run):
    """For `readers/scope_ms.py`: the newest request again, as a function
    of no arguments. The model it holds is some 20 MB on the device (two
    projections, two mixtures, the 4,096 x 1,000 weights), beside which
    the reference has the whole chip."""
    if _LAST_REQUEST is None:
        return None
    fitted, x = _LAST_REQUEST
    return lambda: apply(fitted, x)


def scores(config: dict, fitted, x: np.ndarray, seed: int) -> np.ndarray:
    """The program's real-valued class scores for `x`: the fitted
    pipeline up to its gathered 4,096 features (kept, for the reference
    to compare), then its fitted mapper, without the final top-5."""
    from keystone_tpu.data.dataset import ArrayDataset
    from keystone_tpu.ops.util.vectors import VectorCombiner
    from keystone_tpu.workflow.pipeline import FittedPipeline

    graph = fitted.graph
    (combiner,) = [n for n, op in graph.operators.items() if isinstance(op, VectorCombiner)]
    graph, sink = graph.add_sink(combiner)
    features = FittedPipeline(graph, fitted.source, sink).apply_batch(ArrayDataset(x))
    fitted.bench_heldout_encodings = np.asarray(features.data)  # for `given`
    return np.asarray(program.block_mapper(fitted).apply_batch(features).data)


def health(fitted) -> list[str]:
    return program.fit_health(fitted)
