"""Streaming flagship: ImageNet SIFT+LCS+FV at ≥50k images on one chip.

The Pipeline-API flagship (``imagenet.py``) materializes every stage's
output dataset — correct, optimizer-visible, and the right default at
moderate scale, but the descriptor tensors of 50k images (~3k descriptors
× 128 dims each) are ~75 GB and cannot exist on any single chip. The
reference hits the same wall and streams: each executor featurizes its
partition and feeds the solver incrementally (reference:
pipelines/images/imagenet/ImageNetSiftLcsFV.scala:96-136 keeps
featurization lazy per RDD partition; descriptors never globally
materialize).

This module is the TPU analog, built on three facts about the host link
(docs/PERFORMANCE.md; the numbers there date from 2026-07 and are not
re-measured):
  1. per-dispatch host latency and host→device bandwidth — not MXU
     time — dominate naive per-bucket loops, so each bucket must be ONE
     fused XLA computation (featurize → Hellinger →
     PCA-project → Fisher-encode → normalize, BOTH branches) whose output
     is a tiny (N, 2·D·2K) row block;
  2. host→device transfer scales with bytes, so images cross as uint8
     (4× less than float32) and are cast on device;
  3. dispatch is async, so uploads of bucket i+1 overlap compute of
     bucket i (double-buffering) with a bounded in-flight window.

Phases (mirroring the reference's config:
ImageNetSiftLcsFV.scala:146-167 — λ=6e-5, mixtureWeight=0.25, descDim=64,
vocabSize=16, BCD 4096, top-5):
  A. fit_codebooks: descriptor samples from a bucket subset → column PCA
     (128→descDim) + diagonal GMM (vocabSize) per branch.
  B. encode: fused per-bucket-shape jit, pipelined over buckets.
  C. solve: BlockWeightedLeastSquaresEstimator on the (n, 2·D·2K) rows.
  D. predict + top-5 error on a held-out split.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..data.dataset import ArrayDataset
from ..ops.images.core import GrayScaler, PixelScaler
from ..ops.images.fisher import FisherVector, GMMFisherVectorEstimator
from ..ops.images.lcs import LCSExtractor
from ..ops.images.sift import SIFTExtractor
from ..ops.learning.pca import compute_pca, enforce_sign_convention
from ..ops.learning.weighted import BlockWeightedLeastSquaresEstimator
from ..ops.stats.core import NormalizeRows, SignedHellingerMapper
from ..ops.util.labels import TopKClassifier
from .imagenet import ImageNetSiftLcsFVConfig, top_k_err_percent


@dataclass
class FlagshipCodebooks:
    """Fitted per-branch PCA components (desc_d, pca_d) + FisherVector."""

    sift_pca: jnp.ndarray
    sift_fv: FisherVector
    lcs_pca: jnp.ndarray
    lcs_fv: FisherVector

    @property
    def fv_dim(self) -> int:
        d = self.sift_pca.shape[1]
        return d * 2 * self.sift_fv.gmm.k + d * 2 * self.lcs_fv.gmm.k


class StreamingFlagship:
    """Fused-per-bucket SIFT+LCS+FV featurizer (see module docstring)."""

    def __init__(self, config: Optional[ImageNetSiftLcsFVConfig] = None,
                 sift_binning_dtype=None):
        self.config = config or ImageNetSiftLcsFVConfig()
        c = self.config
        self._pix = PixelScaler()
        self._gray = GrayScaler()
        self._hell = SignedHellingerMapper()
        self._norm = NormalizeRows()
        # binning_dtype=bfloat16 runs the 8-orientation spatial-binning
        # convs (the bulk of SIFT's conv work) in bf16 — passes the
        # reference's 99.5%-within-1 gate (docs/PERFORMANCE.md); default
        # decided by the bench's on-chip A/B.
        self._sift_binning_dtype = sift_binning_dtype
        self._sift = SIFTExtractor(scale_step=c.sift_scale_step,
                                   binning_dtype=sift_binning_dtype)
        self._lcs = LCSExtractor(
            stride=c.lcs_stride, stride_start=c.lcs_border,
            sub_patch_size=c.lcs_patch,
        )
        self.codebooks: Optional[FlagshipCodebooks] = None
        # jax.jit caches compiled executables by input shape, so one
        # wrapper serves every bucket shape; granularity in the
        # bucketizer bounds how many distinct shapes (= compilations)
        # can exist.
        self._sample_jit = jax.jit(self._sample_descriptors, static_argnums=(2,))
        self._encode_jit = jax.jit(self._encode_bucket)

    # ----------------------------------------------------------- raw stages

    def _branch_descriptors(self, images_f32, dims):
        """Padded uint8/float images → masked (desc, valid) per branch.
        SIFT consumes the grayscale of [0,1]-scaled pixels; LCS consumes
        raw-scale RGB (reference: ImageNetSiftLcsFV.scala:99-115)."""
        gray = self._gray.apply_arrays(self._pix.apply_arrays(images_f32))
        sift_desc, sift_valid = self._sift.apply_arrays_masked(gray, dims)
        sift_desc = self._hell.apply_arrays(sift_desc)
        lcs_desc, lcs_valid = self._lcs.apply_arrays_masked(images_f32, dims)
        return (sift_desc, sift_valid), (lcs_desc, lcs_valid)

    def _sample_descriptors(self, images, dims, per_image: int, key):
        """Fused featurize + on-device uniform sample of ``per_image``
        valid descriptors per image per branch (Gumbel top-k over the
        validity mask — no host-side ragged indexing). ``key`` is
        per-bucket (r4 advisor: deriving it from the fixed config seed in
        here made every bucket of a given shape pick descriptors at
        identical image positions — a correlated codebook sample)."""
        x = images.astype(jnp.float32)
        (sd, sv), (ld, lv) = self._branch_descriptors(x, dims)

        def sample(desc, valid, key):
            n, npad, d = desc.shape
            take = min(per_image, npad)
            g = jax.random.gumbel(key, (n, npad))
            scores = jnp.where(valid > 0, g, -jnp.inf)
            idx = jax.lax.top_k(scores, take)[1]            # (n, take)
            picked = jnp.take_along_axis(desc, idx[..., None], axis=1)
            ok = jnp.take_along_axis(valid, idx, axis=1)    # guards npad<take
            return picked.reshape(n * take, d), ok.reshape(n * take)

        ks, kl = jax.random.split(key)
        s_flat, s_ok = sample(sd, sv, ks)
        l_flat, l_ok = sample(ld, lv, kl)
        return s_flat, s_ok, l_flat, l_ok

    def fit_codebooks(
        self,
        sample_buckets: Iterable[Dict[str, np.ndarray]],
        per_image: Optional[int] = None,
    ) -> FlagshipCodebooks:
        """Phase A: PCA (desc→descDim) + GMM (vocabSize) per branch from
        descriptor samples of ``sample_buckets``
        (reference: ImageNetSiftLcsFV.scala:22-73, numPcaSamples=1e7)."""
        c = self.config
        per_image = per_image or 64
        s_parts, l_parts = [], []
        base_key = jax.random.PRNGKey(c.seed)
        for i, b in enumerate(sample_buckets):
            img = jax.device_put(np.asarray(b["image"]))
            dims = jax.device_put(np.asarray(b["dims"]))
            s_flat, s_ok, l_flat, l_ok = self._sample_jit(
                img, dims, per_image, jax.random.fold_in(base_key, i)
            )
            s_parts.append(np.asarray(s_flat)[np.asarray(s_ok) > 0])
            l_parts.append(np.asarray(l_flat)[np.asarray(l_ok) > 0])
        s_samples = jnp.asarray(np.concatenate(s_parts, axis=0))
        l_samples = jnp.asarray(np.concatenate(l_parts, axis=0))

        books = []
        for samples in (s_samples, l_samples):
            comps = enforce_sign_convention(compute_pca(samples, c.desc_dim))
            projected = samples @ comps
            fv = GMMFisherVectorEstimator(c.vocab_size, seed=c.seed).fit(
                ArrayDataset(projected)
            )
            books.append((comps, fv))
        self.codebooks = FlagshipCodebooks(
            sift_pca=books[0][0], sift_fv=books[0][1],
            lcs_pca=books[1][0], lcs_fv=books[1][1],
        )
        # The GMM parameters ride into _encode_bucket as closure
        # constants, so a re-fit must drop the traced executables — a
        # stale cache would silently combine new PCA args with old GMMs.
        self._encode_jit = jax.jit(self._encode_bucket)
        return self.codebooks

    def adopt_codebooks(self, codebooks: FlagshipCodebooks) -> None:
        """Share already-fitted codebooks (e.g. an A/B twin with a
        different extractor precision); rebuilds the encode jit for the
        same staleness reason as fit_codebooks."""
        self.codebooks = codebooks
        self._encode_jit = jax.jit(self._encode_bucket)

    # ------------------------------------------------------- persistence

    def save(self, path: str, model=None) -> None:
        """Persist config + fitted codebooks (+ optionally the trained
        linear model) — the streaming path's FittedPipeline.save analog
        (reference: workflow/FittedPipeline.scala:10-22 'may be written
        to and from disk'). Arrays pickle as host numpy."""
        import pickle

        assert self.codebooks is not None, "fit_codebooks first"
        cb = self.codebooks
        payload = {
            "config": self.config,
            # The extractor precision is part of the model: features a
            # persisted solver was trained on must reproduce on load.
            "sift_binning_dtype": (
                None if self._sift_binning_dtype is None
                else np.dtype(self._sift_binning_dtype).name
            ),
            "codebooks": {
                "sift_pca": np.asarray(cb.sift_pca),
                "lcs_pca": np.asarray(cb.lcs_pca),
                "sift_gmm": _gmm_arrays(cb.sift_fv.gmm),
                "lcs_gmm": _gmm_arrays(cb.lcs_fv.gmm),
            },
            "model": model,
        }
        with open(path, "wb") as f:
            pickle.dump(payload, f)

    @classmethod
    def load(cls, path: str) -> Tuple["StreamingFlagship", object]:
        """Returns (flagship ready to encode, saved model or None)."""
        import pickle

        from ..ops.learning.gmm import GaussianMixtureModel

        with open(path, "rb") as f:
            payload = pickle.load(f)
        dtype_name = payload.get("sift_binning_dtype")
        fs = cls(
            payload["config"],
            sift_binning_dtype=None if dtype_name is None else jnp.dtype(dtype_name),
        )
        cb = payload["codebooks"]
        fs.adopt_codebooks(FlagshipCodebooks(
            sift_pca=jnp.asarray(cb["sift_pca"]),
            sift_fv=FisherVector(GaussianMixtureModel(*cb["sift_gmm"])),
            lcs_pca=jnp.asarray(cb["lcs_pca"]),
            lcs_fv=FisherVector(GaussianMixtureModel(*cb["lcs_gmm"])),
        ))
        return fs, payload.get("model")

    def _encode_bucket(self, images, dims, sift_pca, lcs_pca):
        """Phase B kernel: ONE XLA computation from padded images to
        normalized combined FV rows (N, 2·D·2K). The GMM parameters ride
        as closure constants (self.codebooks is set before jit tracing).
        """
        x = images.astype(jnp.float32)
        (sd, sv), (ld, lv) = self._branch_descriptors(x, dims)
        cb = self.codebooks

        def finish(desc, valid, pca, fv):
            reduced = desc @ pca                        # (N, npad, descDim)
            enc = fv.apply_arrays_masked(reduced, valid)
            flat = enc.reshape(enc.shape[0], -1)        # MatrixVectorizer
            flat = self._norm.apply_arrays(flat)
            flat = self._hell.apply_arrays(flat)
            return self._norm.apply_arrays(flat)

        s_rows = finish(sd, sv, sift_pca, cb.sift_fv)
        l_rows = finish(ld, lv, lcs_pca, cb.lcs_fv)
        return jnp.concatenate([s_rows, l_rows], axis=1)  # VectorCombiner

    def encode_buckets(
        self,
        buckets: Iterable[Dict[str, np.ndarray]],
        prefetch: int = 2,
        on_rows: Optional[Callable[[np.ndarray, Dict], None]] = None,
        mesh=None,
    ) -> Optional[np.ndarray]:
        """Phase B driver: pipelined featurize+encode over host buckets.

        Uploads (uint8, async ``device_put``) run ``prefetch`` buckets
        ahead of compute; result rows are fetched one bucket behind the
        dispatch frontier so transfer, MXU work, and host copies overlap.
        ``on_rows(rows, bucket)`` streams row blocks to the caller (e.g.
        directly into a solver's accumulator); without it the full
        (n, fv_dim) matrix is returned — at descDim=64, vocabSize=16
        that is 16 KB/image, ~0.8 GB for 50k images, host-resident.

        With ``mesh`` given, each bucket's rows are sharded over the
        mesh's data axis (rows zero-padded to the shard count with
        full-bucket dims; pad outputs are dropped at the gather) and the
        fused encode runs as one GSPMD computation — the data-parallel
        featurize path for multi-chip.

        The pipelined loop itself is the workflow layer's shared
        streaming engine (``workflow.streaming.stream_pipelined``) — the
        same stage/compute/drain structure that backs general chunked
        fits now, rather than a bespoke copy here.
        """
        from ..workflow.streaming import stream_pipelined

        assert self.codebooks is not None, "fit_codebooks first"
        out_rows: List[np.ndarray] = []
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.mesh import row_axes, row_shard_count

            ndev = row_shard_count(mesh)
            axes = row_axes(mesh)

            def shard(b):
                img = np.ascontiguousarray(b["image"])
                dims = np.asarray(b["dims"])
                pad = (-len(dims)) % ndev
                if pad:
                    img = np.concatenate(
                        [img, np.zeros((pad,) + img.shape[1:], img.dtype)]
                    )
                    dims = np.concatenate(
                        [dims, np.tile(np.asarray(img.shape[1:3], dims.dtype),
                                       (pad, 1))]
                    )
                img_s = jax.device_put(
                    img, NamedSharding(mesh, P(axes, None, None, None))
                )
                dims_s = jax.device_put(dims, NamedSharding(mesh, P(axes, None)))
                return img_s, dims_s
        else:
            def shard(b):
                return (
                    jax.device_put(np.ascontiguousarray(b["image"])),
                    jax.device_put(np.asarray(b["dims"])),
                )

        def compute(staged, b):
            img_s, dims_s = staged
            return self._encode_jit(
                img_s, dims_s, self.codebooks.sift_pca, self.codebooks.lcs_pca
            )

        def consume(dev, b):
            rows = np.asarray(dev)[: len(b["dims"])]
            if on_rows is not None:
                on_rows(rows, b)
            else:
                out_rows.append(rows)

        stream_pipelined(
            buckets, stage=shard, compute=compute, consume=consume,
            prefetch=prefetch,
        )
        return None if on_rows is not None else (
            np.concatenate(out_rows, axis=0) if out_rows else None
        )


# ---------------------------------------------------------------------------
# On-device synthetic workload: ≥50k images with LEARNABLE class structure
# and zero host→device image traffic (ingest is measured separately by the
# bench's ingest leg; this isolates the framework's device pipeline the
# way BASELINE.md's solver table isolates the reference's solvers).
# ---------------------------------------------------------------------------


def _gmm_arrays(gmm) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    return (
        np.asarray(gmm.means),
        np.asarray(gmm.variances),
        np.asarray(gmm.weights),
    )


def run_native_resolution_streaming(
    config: Optional[ImageNetSiftLcsFVConfig] = None,
    granularity: int = 32,
    max_rows: int = 64,
    codebook_sample_buckets: int = 8,
) -> dict:
    """Native-resolution flagship over REAL tar-of-JPEG data through the
    streaming path — the at-scale counterpart of
    ``imagenet.run_native_resolution`` (which materializes every stage
    through the workflow layer and is the correctness/optimizer path).
    Loader → size buckets (uint8) → codebooks from a bucket sample →
    fused pipelined encode → mixture-weighted solve → train top-5.
    """
    from ..data.buckets import bucket_labels, bucketize_dataset
    from ..data.loaders.imagenet import load_imagenet
    from ..ops.util.labels import TopKClassifier as _TopK

    cfg = config or ImageNetSiftLcsFVConfig()
    if not cfg.train_location or not cfg.label_path:
        raise ValueError(
            "imagenet workloads need --train-location (tar-of-JPEGs) and "
            "--label-path (reference: ImageNetSiftLcsFV.scala:75-141)"
        )
    t: Dict[str, float] = {}
    t0 = time.perf_counter()
    ds = load_imagenet(cfg.train_location, cfg.label_path, resize=None)
    buckets = bucketize_dataset(ds, granularity=granularity, max_rows=max_rows)
    for b in buckets:
        # JPEG-decoded native-size pixels are integral 0..255: uint8
        # buckets quarter the host→device traffic with zero value change.
        if b.images.dtype != np.uint8:
            b.images = np.clip(b.images, 0, 255).astype(np.uint8)
    labels = bucket_labels(buckets)
    t["load_bucketize_s"] = round(time.perf_counter() - t0, 1)

    fs = StreamingFlagship(cfg)
    t0 = time.perf_counter()
    stride = max(1, len(buckets) // codebook_sample_buckets)
    fs.fit_codebooks(
        ({"image": b.images, "dims": b.dims}
         for b in buckets[::stride][:codebook_sample_buckets]),
    )
    t["codebook_fit_s"] = round(time.perf_counter() - t0, 1)

    t0 = time.perf_counter()
    feats = fs.encode_buckets(
        ({"image": b.images, "dims": b.dims} for b in buckets), prefetch=2
    )
    t["encode_s"] = round(time.perf_counter() - t0, 1)
    n = feats.shape[0]
    t["encode_images_per_sec"] = round(n / max(t["encode_s"], 1e-9), 1)

    y = -np.ones((n, cfg.num_classes), np.float32)
    y[np.arange(n), labels] = 1.0
    est = BlockWeightedLeastSquaresEstimator(
        cfg.solver_block_size, num_iter=1, reg=cfg.reg,
        mixture_weight=cfg.mixture_weight,
    )
    t0 = time.perf_counter()
    model = est.fit(ArrayDataset(feats), ArrayDataset(y))
    float(jnp.sum(model.weights))
    t["solve_s"] = round(time.perf_counter() - t0, 1)

    scores = model.apply_batch(ArrayDataset(feats))
    topk = _TopK(min(5, cfg.num_classes)).apply_batch(scores)
    t.update({
        "num_train": int(n),
        "num_buckets": len(buckets),
        "train_top5_err_percent": round(
            top_k_err_percent(np.asarray(topk.data), labels), 2
        ),
        "fv_dim_combined": int(fs.codebooks.fv_dim),
    })

    if cfg.test_location:
        # Held-out evaluation, same contract as the Pipeline flagship
        # (reference: ImageNetSiftLcsFV.scala:138-141 TEST error).
        ds_t = load_imagenet(cfg.test_location, cfg.label_path, resize=None)
        buckets_t = bucketize_dataset(ds_t, granularity=granularity,
                                      max_rows=max_rows)
        for b in buckets_t:
            if b.images.dtype != np.uint8:
                b.images = np.clip(b.images, 0, 255).astype(np.uint8)
        labels_t = bucket_labels(buckets_t)
        feats_t = fs.encode_buckets(
            ({"image": b.images, "dims": b.dims} for b in buckets_t),
            prefetch=2,
        )
        scores_t = model.apply_batch(ArrayDataset(feats_t))
        topk_t = _TopK(min(5, cfg.num_classes)).apply_batch(scores_t)
        t["num_test"] = int(feats_t.shape[0])
        t["test_top5_err_percent"] = round(
            top_k_err_percent(np.asarray(topk_t.data), labels_t), 2
        )
    return t


def _synth_images(key, labels, size: int):
    """Device-side learnable synthetic images: per-class smooth template
    (an (8,8,3) field seeded by the class id, bilinearly upsampled —
    strong class-specific gradients for SIFT/LCS) + i.i.d. noise."""

    def template(label):
        k = jax.random.fold_in(jax.random.PRNGKey(7), label)
        low = jax.random.uniform(k, (8, 8, 3), minval=0.0, maxval=255.0)
        return jax.image.resize(low, (size, size, 3), method="bilinear")

    noise = 28.0 * jax.random.normal(key, (labels.shape[0], size, size, 3))
    return jnp.clip(jax.vmap(template)(labels) + noise, 0.0, 255.0)


def synth_batch_fn(flagship: StreamingFlagship, size: int):
    """Returns jit(fn)(key, labels) → (N, fv_dim): generation fuses INTO
    the encode computation — one dispatch, no image crosses the link."""

    def fn(key, labels):
        imgs = _synth_images(key, labels, size)
        dims = jnp.full((labels.shape[0], 2), size, dtype=jnp.int32)
        return flagship._encode_bucket(
            imgs, dims, flagship.codebooks.sift_pca, flagship.codebooks.lcs_pca
        )

    return jax.jit(fn)


def run_flagship_ondevice(
    num_train: int = 50_000,
    num_test: int = 5_000,
    num_classes: int = 1_000,
    image_size: int = 256,
    batch: int = 64,
    config: Optional[ImageNetSiftLcsFVConfig] = None,
    progress_s: Optional[float] = None,
) -> dict:
    """Flagship end-to-end at the reference's published config and scale
    (reference: ImageNetSiftLcsFV.scala:146-167): fit codebooks, featurize
    + Fisher-encode ``num_train`` images, solve 1000 classes with the
    mixture-weighted block solver, and report top-5 error on a held-out
    split — wall-clock per phase, images/sec, and accuracy in one dict."""
    cfg = config or ImageNetSiftLcsFVConfig()
    fs = StreamingFlagship(cfg)
    t: Dict[str, float] = {}

    def scale_meta() -> dict:
        return {
            "num_train": num_train, "num_test": num_test,
            "num_classes": num_classes, "image_size": image_size,
            "fv_dim_combined": int(fs.codebooks.fv_dim),
        }

    # Phase A on device-generated sample batches (same distribution).
    t0 = time.perf_counter()
    rng = np.random.default_rng(cfg.seed)

    def synth_host_batches(num_batches: int) -> Iterator[Dict[str, np.ndarray]]:
        # Codebook fitting reuses the encode-side generator through a tiny
        # host hop: generate on device, pull, re-present as a bucket.
        gen = jax.jit(lambda key, labels: _synth_images(key, labels, image_size))
        for i in range(num_batches):
            labels = jnp.asarray(rng.integers(0, num_classes, batch))
            imgs = np.asarray(gen(jax.random.PRNGKey(1000 + i), labels))
            yield {"image": imgs.astype(np.uint8),
                   "dims": np.full((batch, 2), image_size, np.int32)}

    fs.fit_codebooks(synth_host_batches(4), per_image=64)
    t["codebook_fit_s"] = round(time.perf_counter() - t0, 1)

    # Phase B: device-generated encode, one dispatch per batch, pipelined
    # through the shared streaming engine (upload/stage of batch i+1
    # overlaps compute of batch i; results drain one behind).
    from ..workflow.streaming import stream_pipelined

    enc = synth_batch_fn(fs, image_size)
    labels_all = rng.integers(0, num_classes, num_train + num_test)
    feats = np.empty((num_train + num_test, fs.codebooks.fv_dim), np.float32)
    t0 = time.perf_counter()
    done = 0
    last_report = t0

    def batch_ranges():
        for start in range(0, num_train + num_test, batch):
            yield start, min(start + batch, num_train + num_test)

    def stage(rng_range):
        start, stop = rng_range
        lab = jnp.asarray(labels_all[start:stop])
        if len(lab) < batch:  # pad tail to the compiled batch shape
            lab = jnp.pad(lab, (0, batch - len(lab)))
        return lab

    def compute(lab, rng_range):
        return enc(jax.random.PRNGKey(rng_range[0]), lab)

    def consume(dev, rng_range):
        nonlocal done, last_report
        s, e = rng_range
        feats[s:e] = np.asarray(dev)[: e - s]
        done = e
        if progress_s and time.perf_counter() - last_report > progress_s:
            last_report = time.perf_counter()
            print(f"encoded {done}/{num_train + num_test} "
                  f"({done / (last_report - t0):.1f} img/s)", flush=True)

    stream_pipelined(
        batch_ranges(), stage=stage, compute=compute, consume=consume,
        prefetch=1,
    )
    encode_s = time.perf_counter() - t0
    t["encode_s"] = round(encode_s, 1)
    t["encoded_images"] = int(done)
    t["encode_images_per_sec"] = round(done / max(encode_s, 1e-9), 1)

    # Phase C: the reference's solver at its config (λ, mixtureWeight, bs).
    y = -np.ones((num_train, num_classes), np.float32)
    y[np.arange(num_train), labels_all[:num_train]] = 1.0
    est = BlockWeightedLeastSquaresEstimator(
        cfg.solver_block_size, num_iter=1, reg=cfg.reg,
        mixture_weight=cfg.mixture_weight,
    )
    t0 = time.perf_counter()
    model = est.fit(ArrayDataset(feats[:num_train]), ArrayDataset(y))
    float(jnp.sum(model.weights))
    t["solve_s"] = round(time.perf_counter() - t0, 1)

    # Phase D: top-5 on held-out (reference: TopKClassifier(5) :136).
    t0 = time.perf_counter()
    scores = model.apply_batch(ArrayDataset(feats[num_train:]))
    topk = TopKClassifier(min(5, num_classes)).apply_batch(scores)
    top5 = top_k_err_percent(np.asarray(topk.data), labels_all[num_train:])
    t["predict_s"] = round(time.perf_counter() - t0, 1)

    t.update({
        **scale_meta(),
        "top5_err_percent": round(top5, 2),
        "end_to_end_fit_s": round(
            t["codebook_fit_s"] + t["encode_s"] + t["solve_s"], 1
        ),
        "data": "device-generated class templates + noise (host ingest "
                "measured separately by the ingest bench leg)",
    })
    return t
