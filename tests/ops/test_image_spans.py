"""The flagship's spans, counters and device-side names (PR 36): the
extractors' `image:*` host spans with their attributes, the descriptor
counter, the fit's `pca:fit`, `gmm:fit` > `gmm:em`, `solver:weighted`, and
the `feat/<Class>` scopes in the programs' HLO."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from keystone_tpu.data.dataset import ArrayDataset
from keystone_tpu.obs import names, spans
from keystone_tpu.ops.images.core import GrayScaler, PixelScaler
from keystone_tpu.ops.images.fisher import FisherVector, _fisher_encode
from keystone_tpu.ops.images.lcs import LCSExtractor, _lcs_descriptors
from keystone_tpu.ops.images.sift import SIFTExtractor
from keystone_tpu.ops.learning.gmm import GaussianMixtureModel, GaussianMixtureModelEstimator
from keystone_tpu.ops.learning.pca import BatchPCATransformer, ColumnPCAEstimator, _project_stack
from keystone_tpu.ops.learning.weighted import BlockWeightedLeastSquaresEstimator
from keystone_tpu.ops.stats.core import SignedHellingerMapper
from keystone_tpu.ops.util.labels import ClassLabelIndicators


@pytest.fixture
def session():
    with spans.tracing_session("image-spans", sync_timings=False) as s:
        yield s


def _named(session, name):
    return [s for s in session.spans() if s.name == name]


def _images(rows=6):
    return (np.random.default_rng(1).random((rows, 48, 48, 3)) * 255).astype(np.float32)


def test_the_fused_sift_prefix_opens_image_sift_with_rows_scales_and_descriptors(session):
    pipeline = PixelScaler().to_pipeline() >> GrayScaler() >> SIFTExtractor() >> SignedHellingerMapper()
    counter = names.metric(names.IMAGE_DESCRIPTORS)
    before = counter.value(extractor="SIFTExtractor")
    out = pipeline(ArrayDataset(_images())).get()
    assert out.data.shape == (6, 151, 128)
    (span,) = _named(session, "image:sift")
    assert span.attributes == {"rows": 6, "scales": 4, "descriptors": 151, "binning": "product"}
    assert counter.value(extractor="SIFTExtractor") - before == 6 * 151
    (node,) = [s for s in session.spans() if s.name.startswith("node:Fused[")]
    assert span.parent_id == node.span_id  # inside the fused node's span


@pytest.mark.parametrize(
    "side,binning", [(256, "product"), (1000, "conv+product"), (2500, "conv")], ids=["products", "by-scale", "convolutions"]
)
def test_image_sift_says_which_form_the_binning_took(session, side, binning):
    """`binning` (PR 37): the form of the spatial binning at this image
    size, by the one rule that chooses it (`sift._binning_as_products`)."""
    extractor = SIFTExtractor()
    with extractor.host_span(ArrayDataset(np.zeros((2, side, 40), np.float32))):
        pass
    (span,) = _named(session, "image:sift")
    assert span.attributes["binning"] == binning == extractor.binning_form(side, 40)


def test_lcs_pca_and_fisher_open_their_spans(session):
    gmm = GaussianMixtureModel(np.zeros((8, 3), np.float32), np.ones((8, 3), np.float32), np.full(3, 1 / 3))
    pipeline = (
        LCSExtractor().to_pipeline() >> BatchPCATransformer(np.eye(96, 8, dtype=np.float32)) >> FisherVector(gmm)
    )
    counter = names.metric(names.IMAGE_DESCRIPTORS)
    before = counter.value(extractor="LCSExtractor")
    out = pipeline(ArrayDataset(_images())).get()
    assert out.data.shape == (6, 8, 6)
    assert _named(session, "image:lcs")[0].attributes == {"rows": 6, "descriptors": 16}
    assert _named(session, "image:pca")[0].attributes == {"rows": 6, "descriptors": 16, "dims": 8}
    assert _named(session, "image:fisher")[0].attributes == {"rows": 6, "centres": 3}
    assert counter.value(extractor="LCSExtractor") - before == 6 * 16


def test_the_fits_own_spans_pca_gmm_and_the_weighted_solver(session):
    rng = np.random.default_rng(0)
    descriptors = ArrayDataset(rng.normal(size=(40, 30, 12)).astype(np.float32))
    ColumnPCAEstimator(4).fit(descriptors)
    (pca,) = _named(session, "pca:fit")
    assert pca.attributes["samples"] == 1200 and pca.attributes["dims"] == 4
    assert pca.attributes["method"] in ("tsqr", "local")

    samples = np.concatenate([rng.normal(c, 1.0, size=(300, 4)) for c in (-4.0, 0.0, 4.0)]).astype(np.float32)
    model = GaussianMixtureModelEstimator(3, seed=1).fit(ArrayDataset(samples))
    (fit,), (em,) = _named(session, "gmm:fit"), _named(session, "gmm:em")
    assert em.parent_id == fit.span_id and em.attributes["samples"] == 900
    assert em.attributes["iterations"] == model.fit_record["iterations"] >= 1

    x = rng.normal(size=(48, 16)).astype(np.float32)
    labels = ClassLabelIndicators(4).apply_batch(ArrayDataset((np.arange(48) % 4).astype(np.int32)))
    BlockWeightedLeastSquaresEstimator(8, 1, 1e-3, 0.25).fit(ArrayDataset(x), labels)
    (solver,) = _named(session, "solver:weighted")
    assert {k: solver.attributes[k] for k in ("classes", "blocks", "rows")} == {"classes": 4, "blocks": 2, "rows": 48}


@pytest.mark.parametrize(
    "scope,lowered",
    [
        ("feat/LCSExtractor", lambda: _lcs_descriptors.lower(jnp.zeros((2, 48, 48, 3)), 4, 16, 6, (-10, -4, 2, 8))),
        ("feat/BatchPCATransformer", lambda: jax.jit(_project_stack.__wrapped__).lower(
            jnp.zeros((2, 16, 96)), jnp.zeros((96, 8)))),
        ("feat/FisherVector", lambda: jax.jit(_fisher_encode.__wrapped__).lower(
            jnp.zeros((2, 16, 8)), jnp.zeros((8, 3)), jnp.ones((8, 3)), jnp.full((3,), 1 / 3), jnp.float32(1e-4))),
    ],
    ids=["lcs", "pca", "fisher"],
)
def test_the_programs_operations_carry_their_transformers_name(scope, lowered):
    """What `benchmark/readers/scope_ms.py` tells device time apart by:
    the scope is part of every operation's name in the lowered program,
    whoever traced it first."""
    text = lowered().as_text(debug_info=True)
    assert scope in text
