"""PCA / ZCA / KMeans / GMM / NaiveBayes / LDA vs golden references
(reference suites: PCASuite, ZCAWhitenerSuite, KMeansPlusPlusSuite,
GaussianMixtureModelSuite, NaiveBayesSuite, LinearDiscriminantAnalysisSuite)."""

import numpy as np
import pytest

from keystone_tpu.data.dataset import ArrayDataset, ObjectDataset
from keystone_tpu.ops.learning.gmm import GaussianMixtureModelEstimator, GaussianMixtureModel
from keystone_tpu.ops.learning.kmeans import KMeansModel, KMeansPlusPlusEstimator
from keystone_tpu.ops.learning.lda import LinearDiscriminantAnalysis
from keystone_tpu.ops.learning.naive_bayes import NaiveBayesEstimator
from keystone_tpu.ops.learning.pca import (
    ApproximatePCAEstimator,
    ColumnPCAEstimator,
    DistributedPCAEstimator,
    PCAEstimator,
)
from keystone_tpu.ops.learning.zca import ZCAWhitenerEstimator


def numpy_pca(x, dims):
    xc = x - x.mean(0)
    _, _, vt = np.linalg.svd(xc, full_matrices=False)
    v = vt.T
    col_max, col_absmax = v.max(0), np.abs(v).max(0)
    signs = np.where(col_max == col_absmax, 1.0, -1.0)
    return (v * signs)[:, :dims]


@pytest.fixture
def x():
    rng = np.random.default_rng(0)
    base = rng.normal(size=(300, 4)) @ np.diag([5.0, 2.0, 1.0, 0.1])
    return (base @ rng.normal(size=(4, 8))).astype(np.float32)


def test_local_pca_matches_numpy(x):
    model = PCAEstimator(3).fit(ArrayDataset(x))
    expected = numpy_pca(x, 3)
    np.testing.assert_allclose(np.asarray(model.components), expected, atol=2e-3)


def test_distributed_pca_matches_local(x):
    local = PCAEstimator(3).fit(ArrayDataset(x))
    dist = DistributedPCAEstimator(3).fit(ArrayDataset(x))
    # compare up to sign per column (eigh vs svd sign conventions are fixed
    # by the shared convention, but tiny eigenvalues can flip)
    a, b = np.asarray(local.components), np.asarray(dist.components)
    for i in range(3):
        assert min(np.linalg.norm(a[:, i] - b[:, i]), np.linalg.norm(a[:, i] + b[:, i])) < 5e-2


def test_approximate_pca_spans_top_subspace(x):
    exact = numpy_pca(x, 2)
    approx = np.asarray(ApproximatePCAEstimator(2, q=5).fit(ArrayDataset(x)).components)
    # subspace comparison: projection matrices should agree
    p_exact = exact @ exact.T
    p_approx = approx @ approx.T
    assert np.linalg.norm(p_exact - p_approx) < 0.1


def test_pca_transformer_projects(x):
    model = PCAEstimator(3).fit(ArrayDataset(x))
    out = np.asarray(model.apply_batch(ArrayDataset(x)).data)
    assert out.shape == (300, 3)


def test_column_pca_on_descriptor_matrices():
    rng = np.random.default_rng(1)
    mats = [rng.normal(size=(20, 6)).astype(np.float32) for _ in range(10)]
    est = ColumnPCAEstimator(dims=2)
    model = est.fit(ObjectDataset(mats))
    out = model.apply(mats[0])
    assert out.shape == (20, 2)


def test_column_pca_optimize_accepts_vector_items():
    # Regression: plain (d,) feature-vector datasets (one row per item,
    # e.g. pooled features feeding PCA inside a Pipeline) used to raise
    # IndexError in optimize(), silently skipping the cost-model choice.
    from keystone_tpu.workflow.optimize import DataStats

    rng = np.random.default_rng(4)
    vecs = ArrayDataset(rng.normal(size=(50, 8)).astype(np.float32))
    est = ColumnPCAEstimator(dims=2)
    stats = DataStats(n_total=50, num_shards=1, n_per_shard=[50])
    chosen = est.optimize([vecs], stats)
    assert chosen in (est.local, est.distributed)


def test_zca_whitens_covariance():
    rng = np.random.default_rng(2)
    x = (rng.normal(size=(500, 6)) @ rng.normal(size=(6, 6))).astype(np.float32)
    model = ZCAWhitenerEstimator(eps=1e-6).fit_single(x)
    out = (x - np.asarray(model.means)) @ np.asarray(model.whitener)
    cov = out.T @ out / (len(x) - 1)
    np.testing.assert_allclose(cov, np.eye(6), atol=0.05)


def test_zca_holds_the_null_direction_of_row_normalised_patches():
    """Row-normalised patches each sum to zero: their covariance C has a
    null direction, which epsilon 1e-5 scales by 316. The whitener is
    (C + eps I)^(-1/2) of the float64 covariance to float32's rounding of
    it (a float32 SVD of these patches reads 8e-7 to 1.9e-6 here)."""
    from keystone_tpu.pipelines.cifar import normalize_rows

    rng = np.random.default_rng(4)
    patches = normalize_rows(rng.integers(0, 256, size=(20000, 108)).astype(np.float64), 10.0)
    model = ZCAWhitenerEstimator(eps=1e-5).fit_single(patches)
    means = np.asarray(model.means, np.float64)
    centred = patches - means
    lam, vec = np.linalg.eigh(centred.T @ centred / (len(patches) - 1) + 1e-5 * np.eye(108))
    own = (vec / np.sqrt(lam)) @ vec.T
    assert np.linalg.norm(np.asarray(model.whitener, np.float64) - own) / np.linalg.norm(own) < 1e-7


def test_kmeans_recovers_separated_clusters():
    rng = np.random.default_rng(3)
    centers = np.array([[0, 0], [10, 10], [-10, 10]], dtype=np.float32)
    x = np.concatenate([c + 0.5 * rng.normal(size=(100, 2)) for c in centers]).astype(np.float32)
    model = KMeansPlusPlusEstimator(3, 20, seed=0).fit(ArrayDataset(x))
    fitted = np.asarray(model.means)
    # every true center has a fitted center nearby
    for c in centers:
        assert np.min(np.linalg.norm(fitted - c, axis=1)) < 1.0
    # one-hot assignment output
    assign = np.asarray(model.apply_batch(ArrayDataset(x)).data)
    assert assign.shape == (300, 3)
    np.testing.assert_allclose(assign.sum(axis=1), 1.0)
    # points from the same true cluster agree
    assert (assign[:100].argmax(1) == assign[0].argmax()).all()


def test_gmm_recovers_separated_clusters():
    rng = np.random.default_rng(4)
    x = np.concatenate([
        rng.normal(loc=0.0, scale=1.0, size=(300, 3)),
        rng.normal(loc=8.0, scale=2.0, size=(300, 3)),
    ]).astype(np.float32)
    est = GaussianMixtureModelEstimator(k=2, max_iterations=50, min_cluster_size=10, seed=0)
    model = est.fit(ArrayDataset(x))
    means = np.asarray(model.means)  # (d, k)
    m0, m1 = means[:, 0], means[:, 1]
    lo, hi = sorted([np.mean(m0), np.mean(m1)])
    assert abs(lo - 0.0) < 1.0 and abs(hi - 8.0) < 1.0
    post = np.asarray(model.apply_batch(ArrayDataset(x)).data)
    assert post.shape == (600, 2)
    np.testing.assert_allclose(post.sum(axis=1), 1.0, atol=1e-5)
    # posteriors nearly hard for well-separated clusters
    assert (post[:300].argmax(1) == post[0].argmax()).mean() > 0.99


def test_gmm_csv_roundtrip(tmp_path):
    means = np.array([[0.0, 1.0], [2.0, 3.0]])
    variances = np.array([[1.0, 1.0], [2.0, 2.0]])
    weights = np.array([0.4, 0.6])
    np.savetxt(tmp_path / "m.csv", means, delimiter=",")
    np.savetxt(tmp_path / "v.csv", variances, delimiter=",")
    np.savetxt(tmp_path / "w.csv", weights, delimiter=",")
    model = GaussianMixtureModel.load(
        str(tmp_path / "m.csv"), str(tmp_path / "v.csv"), str(tmp_path / "w.csv")
    )
    assert model.k == 2 and model.dim == 2


def test_naive_bayes_separates():
    rng = np.random.default_rng(5)
    # word-count-ish data: class 0 favors features 0-4, class 1 favors 5-9
    n = 400
    y = rng.integers(0, 2, size=n)
    rates = np.where(y[:, None] == 0,
                     np.array([[5.0] * 5 + [0.5] * 5]),
                     np.array([[0.5] * 5 + [5.0] * 5]))
    x = rng.poisson(rates).astype(np.float32)
    model = NaiveBayesEstimator(2).fit(ArrayDataset(x), ArrayDataset(y.astype(np.int32)))
    scores = np.asarray(model.apply_batch(ArrayDataset(x)).data)
    acc = (scores.argmax(1) == y).mean()
    assert acc > 0.95
    assert scores.shape == (n, 2)


def test_lda_separates_classes():
    rng = np.random.default_rng(6)
    x = np.concatenate([
        rng.normal(loc=[0, 0, 0], size=(100, 3)),
        rng.normal(loc=[5, 5, 0], size=(100, 3)),
    ]).astype(np.float32)
    y = np.array([0] * 100 + [1] * 100, dtype=np.int32)
    model = LinearDiscriminantAnalysis(1).fit(ArrayDataset(x), ArrayDataset(y))
    proj = np.asarray(model.apply_batch(ArrayDataset(x)).data).ravel()
    # 1-D projection separates the classes
    t = (proj[:100].mean() + proj[100:].mean()) / 2
    acc = ((proj < t) == (y == (0 if proj[:100].mean() < t else 1))).mean()
    assert acc > 0.95
