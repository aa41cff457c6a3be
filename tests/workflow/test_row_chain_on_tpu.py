"""The executor's row-chain rule against the v5e compiler's own memory
analysis: the flagship's extractors compiled HERE, at their real sizes,
for a chip that is described and not attached (nothing runs; no time or
result comes of this). The rule estimates a program's workspace as
`executor.TEMPORARIES` times the chain's largest output: these compiles
are where that number comes from, and they hold it there.

Every TPU compile of the suite lives in this one file, and the topology
is described inside a fixture: one process may hold the TPU's library,
and every xdist worker imports every test file."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from keystone_tpu.workflow import executor

GIB = 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without the chip: keep these out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compiled(fn, one_chip, *shapes):
    specs = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for s in shapes]
    return jax.jit(fn).lower(*specs).compile()


def _memory(fn, one_chip, *shapes):
    analysis = _compiled(fn, one_chip, *shapes).memory_analysis()
    return analysis.argument_size_in_bytes, analysis.output_size_in_bytes, analysis.temp_size_in_bytes


def _sift_prefix(x):
    from keystone_tpu.ops.images.core import GrayScaler, PixelScaler
    from keystone_tpu.ops.images.sift import SIFTExtractor
    from keystone_tpu.ops.stats.core import SignedHellingerMapper

    for op in (PixelScaler(), GrayScaler(), SIFTExtractor(), SignedHellingerMapper()):
        x = op.apply_arrays(x)
    return x


def test_dense_sift_at_256_images_holds_under_four_times_its_descriptors(one_chip):
    """The request's fused prefix (PixelScaler + GrayScaler + SIFTExtractor
    + SignedHellingerMapper) at 256 images of 256 x 256 x 3."""
    args, out, temp = _memory(_sift_prefix, one_chip, (256, 256, 256, 3))
    assert out == 256 * 13165 * 128 * 4
    # 3.1 times with the binning as convolutions (PR 36), 1.5 as band products (PR 37)
    assert 1.2 * out < temp < 2.0 * out < executor.TEMPORARIES * out
    # and the whole-run estimate of the request's SIFT chain covers what
    # the program needs while it runs: its input, output and workspace
    pca_out, encoding = out // 2, 256 * 2048 * 4
    estimate = args + out + pca_out + 2 * encoding + executor.TEMPORARIES * out
    assert args + out + temp < estimate < 15.75 * GIB  # a request fits whole
    assert 2 * estimate > 15.75 * GIB  # and twice the rows do not


def test_lcs_at_the_fits_2048_images_fits_the_chip_whole(one_chip):
    from keystone_tpu.ops.images.lcs import _lcs_body

    offsets = np.arange(-10, 9, 6)
    args, out, temp = _memory(
        lambda x: _lcs_body(x, 4, 16, 6, offsets), one_chip, (2048, 256, 256, 3)
    )
    assert out == 2048 * 3136 * 96 * 4 and temp < executor.TEMPORARIES * out
    pca_out = out * 64 // 96
    estimate = args + out + pca_out + executor.TEMPORARIES * out
    assert args + out + temp + pca_out < estimate < 15.75 * GIB


def _encode(x, means, variances, weights):
    from keystone_tpu.ops.images.fisher import _fisher_encode

    return _fisher_encode.__wrapped__(x, means, variances, weights, jnp.float32(1e-4))


_MIXTURE = ((64, 16), (64, 16), (16,))  # means, variances, weights: 16 Gaussians of 64


def test_the_fisher_encoding_of_a_request_needs_less_than_the_sift_before_it(one_chip):
    args, out, temp = _memory(_encode, one_chip, (256, 13165, 64), *_MIXTURE)
    assert out == 256 * 64 * 32 * 4
    assert temp < executor.TEMPORARIES * 256 * 13165 * 128 * 4 / 2


def _copies_through_linear_memory(compiled) -> dict:
    """How often the compiled program loops or writes a buffer slice by
    slice: what a reshape that is no bitcast under the chip's tiling
    turns into (256 images x 13,165 descriptors merged into one axis
    are four `while` loops and 84 `dynamic-update-slice`s, 45 ms a
    request on the chip: PERF.md section 6, PR 39)."""
    text = compiled.as_text()
    return {op: text.count(f" {op}(") for op in ("while", "dynamic-update-slice")}


@pytest.mark.parametrize("descriptors", [13165, 3136], ids=["sift", "lcs"])
def test_the_fisher_encoder_never_merges_images_with_descriptors(one_chip, descriptors):
    compiled = _compiled(_encode, one_chip, (256, descriptors, 64), *_MIXTURE)
    assert _copies_through_linear_memory(compiled) == {"while": 0, "dynamic-update-slice": 0}
    if descriptors == 13165:
        # 2.01 GiB with the two reshapes and their linear buffers, 1.81 without
        assert compiled.memory_analysis().temp_size_in_bytes < 1.9 * GIB


@pytest.fixture
def kernel_form(monkeypatch):
    """The featurizer's panels in the kernel's form, as on the chip: the
    form is decided when a program is traced, from the backend, which is
    the CPU here."""
    from keystone_tpu.ops.images import core

    monkeypatch.setattr(core, "_conv_form", lambda spec, x_dim, y_dim: "kernel")
    core._featurize.clear_cache()
    yield
    core._featurize.clear_cache()


def _cifar_featurizer(filter_block=512):
    from keystone_tpu.ops.images import Convolver, FusedConvFeaturizer, Pooler, SymmetricRectifier

    conv = Convolver(np.zeros((10000, 108), np.float32), 3)
    return FusedConvFeaturizer(conv, SymmetricRectifier(alpha=0.25), Pooler(13, 14, None, "sum"), filter_block)


def _one_kernel_call_under_conv_panel(text):
    import re

    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    (op_name,) = re.findall(r'op_name="([^"]*)"', calls[0])
    assert "/conv/panel/" in op_name
    return op_name


def test_the_cifar_featurizer_pools_its_panels_in_the_kernel_and_holds_no_panel(one_chip, kernel_form):
    """`_featurize` at CIFAR RandomPatch's widths, 8,192 images in row
    blocks of 512, in the kernel's form (`ops/pallas/conv_pool.py`): the
    Mosaic kernel compiles (its VMEM is the chip's to refuse), runs under
    `feat/FusedConvFeaturizer/.../conv/panel/`, and no float32 (512, 27,
    27, 512) panel is left: the compiler's temporaries are under one
    panel, 0.76 GB (1.13 GiB in XLA's form, PERF.md section 6)."""
    from keystone_tpu.ops.images import core

    spec = _cifar_featurizer().spec

    def featurize(x, kernel, fsums, offset):
        return core._featurize(x, kernel, fsums, offset, spec=spec, row_block=512)

    compiled = _compiled(featurize, one_chip, (8192, 32, 32, 3), (6, 6, 3, 10000), (10000,), (10000,))
    text = compiled.as_text()
    assert "feat/FusedConvFeaturizer/" in _one_kernel_call_under_conv_panel(text)
    assert "f32[512,27,27,512]" not in text
    analysis = compiled.memory_analysis()
    assert analysis.output_size_in_bytes == 8192 * 80000 * 4
    assert analysis.temp_size_in_bytes < 512 * 27 * 27 * 512 * 4


def test_the_convolutional_block_solvers_step_pools_in_the_kernel(one_chip, kernel_form):
    """`conv_block._conv_bcd_step_fn`, one BCD update that featurizes its
    filter block inside `shard_map`, at CIFAR's widths (a 4,096-wide
    block: 512 filters, 8,192 images in chunks of 512, 10 classes): the
    same kernel compiles there, once, under `conv/panel`, and no
    float32 (512, 27, 27, 512) panel is left."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from keystone_tpu.ops.learning.conv_block import _conv_bcd_step_fn
    from keystone_tpu.parallel.mesh import DATA_AXIS

    mesh = Mesh(np.array(list(one_chip.device_set)), (DATA_AXIS,))
    step = _conv_bcd_step_fn(mesh, _cifar_featurizer(), 512, True, 8, 512, 2, 2)
    rows, replicated = NamedSharding(mesh, P(DATA_AXIS)), NamedSharding(mesh, P())
    shapes = [
        ((8192, 32, 32, 3), rows), ((8192, 1), rows), ((8192, 10), rows), ((8192, 10), rows), ((4096, 10), replicated),
        ((6, 6, 3, 512), replicated), ((512,), replicated), ((512,), replicated), ((), replicated), ((), replicated),
    ]
    specs = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding) for shape, sharding in shapes]
    text = step.lower(*specs).compile().as_text()
    _one_kernel_call_under_conv_panel(text)
    assert "f32[512,27,27,512]" not in text


def test_the_fisher_encoder_behind_its_projection_is_one_program_without_a_loop(one_chip):
    """As the request's fused chain has it: the signed root, the PCA
    projection, the encoder."""
    from keystone_tpu.ops.learning.pca import _project_stack
    from keystone_tpu.ops.stats.core import SignedHellingerMapper

    def chain(x, basis, *mixture):
        x = SignedHellingerMapper().apply_arrays(x)
        return _encode(_project_stack.__wrapped__(x, basis), *mixture)

    compiled = _compiled(chain, one_chip, (256, 13165, 128), (128, 64), *_MIXTURE)
    assert _copies_through_linear_memory(compiled) == {"while": 0, "dynamic-update-slice": 0}
