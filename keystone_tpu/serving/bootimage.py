"""Boot images: AOT-serialized warm state for zero-cold-start workers.

A classic worker pays its warm-up at boot: trace + lower + XLA-compile
one executable per batch bucket before it can answer its first request
(seconds even on CPU, tens of seconds on TPU). A *boot image* moves that
work to build time. ``build_boot_image`` exports one
``jax.export``-serialized executable per bucket from a fitted model,
bundles the fitted weights and the persistent-compilation-cache entries
those executables hydrate from, and stamps the whole artifact with the
environment fingerprints the ProfileStore already keys on (jax version,
backend, device kind). A freshly spawned worker then *loads* instead of
warming: deserialize (milliseconds), answer the first request off a
cache-hit executable, and finish warming the remaining buckets off the
bundled cache — no steady-state XLA compiles from that point on.

Staleness is a refusal, never silent garbage: ``load_boot_image`` runs
:func:`~keystone_tpu.workflow.verify.verify_boot_image` (KV307) over the
manifest fingerprints and raises :class:`BootImageRefused` on any
mismatch — the worker falls back to the classic warm path and says so in
the recovery ledger. Build time carries the complementary gate: the
exported executables are re-loaded and checked for numeric parity
against the classic apply path (full AND partial occupancy) before the
manifest is written, so an image that would serve wrong numbers is never
produced in the first place.

Layout of an image directory::

    manifest.json     fingerprints, buckets, example spec, file map
    model.pkl         the fitted model (fallback path + refit source)
    bucket_<b>.bin    jax.export-serialized executable per bucket
    cache/            persistent-compilation-cache entries for the above

Padding semantics: executables are exported at FULL occupancy (the
masking of dead pad rows in ``BatchTransformer.apply_batch`` burns the
trace-time ``num_examples`` into the program, so a partial-occupancy
export would mask the wrong rows). The wrapper re-applies the pad-row
zeroing eagerly after the exported call — identical numbers to the
classic path on every row, real or pad. Module import stays
stdlib-only; jax loads lazily inside the build/load calls.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import shutil
import time
from typing import Any, Dict, Optional, Tuple

from ..obs import names as _names

FORMAT_VERSION = 1
MANIFEST = "manifest.json"
WEIGHTS = "model.pkl"
CACHE_DIR = "cache"


class BootImageError(RuntimeError):
    """Build-side failure: the image could not be produced soundly."""


class BootImageRefused(RuntimeError):
    """Load-side refusal: KV307 fingerprint mismatch (or a corrupt
    artifact). Carries the verify report when one was produced."""

    def __init__(self, message: str, report: Any = None):
        super().__init__(message)
        self.report = report


def environment_fingerprints() -> Dict[str, Any]:
    """The loading/building process's side of the KV307 comparison —
    same identity a ProfileStore entry is keyed on."""
    import jax

    return {
        "format_version": FORMAT_VERSION,
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
    }


def _digest(path: str) -> str:
    h = hashlib.sha1()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _fallback_apply(model: Any):
    """The classic apply path for ``model`` — same resolution order as
    :meth:`ModelRegistry.ModelEntry.batch_apply`, so the wrapper's
    missing-bucket fallback serves exactly what a classic worker would."""
    compiled = getattr(model, "compiled_apply", None)
    if compiled is not None:
        return compiled()
    apply_batch = getattr(model, "apply_batch", None)
    if apply_batch is not None:
        return apply_batch
    batch_transform = getattr(model, "batch_transform", None)
    if batch_transform is not None:
        return lambda dataset: batch_transform([dataset])
    raise BootImageError(
        f"model ({type(model).__name__}) has no apply path (expected "
        "compiled_apply / apply_batch / batch_transform)"
    )


class BootImageModel:
    """A served model backed by deserialized boot-image executables.

    Exposes ``apply_batch`` (and deliberately NOT ``compiled_apply``) so
    :meth:`ModelEntry.batch_apply` routes straight here. Buckets the
    image never exported delegate to the bundled fitted model's classic
    path — slower, never wrong.
    """

    def __init__(self, manifest: Dict[str, Any], executables: Dict[int, Any],
                 model: Any = None, model_loader: Optional[Any] = None):
        self.manifest = manifest
        self._model = model
        #: deferred fitted-model unpickle: the weights pickle costs more
        #: than every executable deserialize combined, and steady state
        #: never touches it — only a fallback bucket (or a refit reading
        #: the incumbent) pays the load. Integrity is already settled
        #: before deferral: weights_digest covers the file bytes.
        self._model_loader = model_loader
        self._executables = executables
        self._fallback = None  # resolved lazily: only a missing bucket pays it
        self.fallback_batches = 0

    @property
    def model(self) -> Any:
        if self._model is None and self._model_loader is not None:
            self._model = self._model_loader()
            self._model_loader = None
        return self._model

    @property
    def buckets(self) -> Tuple[int, ...]:
        return tuple(sorted(self._executables))

    def apply_batch(self, dataset: Any) -> Any:
        import jax
        import jax.numpy as jnp

        from ..data.dataset import ArrayDataset

        exe = self._executables.get(dataset.physical_rows)
        if exe is None:
            if self._fallback is None:
                self._fallback = _fallback_apply(self.model)
            self.fallback_batches += 1
            return self._fallback(dataset)
        out = exe.call(dataset.data)
        n = dataset.num_examples
        physical = dataset.physical_rows
        if physical > n:
            # The executable ran at full occupancy; re-zero the pad rows
            # eagerly so every row matches the classic apply path.
            real_row = jnp.arange(physical) < n
            def zero_pad_rows(a):
                m = real_row.reshape((-1,) + (1,) * (a.ndim - 1))
                return jnp.where(m, a, jnp.zeros((), dtype=a.dtype))
            out = jax.tree_util.tree_map(zero_pad_rows, out)
        return ArrayDataset(out, n)

    def warm(self, only: Optional[int] = None) -> float:
        """Execute each bucket once (zeros input) so later traffic is all
        cache-resident. ``only=b`` warms a single bucket — the worker
        warms the first-request bucket inline and the rest in background.
        Returns seconds spent."""
        import jax
        import numpy as np

        spec = self.manifest["example"]
        dtype = np.dtype(spec["dtype"])
        t0 = time.perf_counter()
        for b, exe in sorted(self._executables.items()):
            if only is not None and b != only:
                continue
            x = np.zeros((b,) + tuple(spec["shape"]), dtype)
            jax.block_until_ready(exe.call(x))
        return time.perf_counter() - t0


def _active_cache_dir() -> Optional[str]:
    """The process's persistent-cache directory, enabling the program's
    own (utils/compilation_cache.py) when none is configured yet. Boot
    images copy entries out of and into this directory; they never
    re-point it — where the cache lives is the launcher's decision
    (``JAX_COMPILATION_CACHE_DIR``) or the one fixed path."""
    import jax

    from ..utils.compilation_cache import enable_persistent_cache

    return enable_persistent_cache() or jax.config.jax_compilation_cache_dir or None


def _cache_entries(cache_dir: Optional[str]) -> set:
    if not cache_dir or not os.path.isdir(cache_dir):
        return set()
    return set(os.listdir(cache_dir))


#: File-name prefix jax gives the persistent-cache entries of
#: ``Exported.call`` programs — what a boot image's executables compile to.
_EXPORTED_ENTRY_PREFIX = "jit_call_exported-"


def build_boot_image(
    spec: Dict[str, Any],
    out_dir: str,
    *,
    buckets: Optional[Tuple[int, ...]] = None,
    model_name: str = "default",
    max_batch: int = 8,
) -> Dict[str, Any]:
    """Build a boot image for the model ``spec`` names (same spec doors a
    worker accepts) into ``out_dir``. Returns the manifest. Raises
    :class:`BootImageError` when the exported executables fail the
    numeric parity gate against the classic path."""
    import jax
    import numpy as np
    from jax import export as jax_export

    from ..data.dataset import ArrayDataset
    from .config import default_bucket_sizes
    from .registry import ModelRegistry
    from .worker import _load_spec

    t0 = time.perf_counter()
    buckets = tuple(sorted(set(int(b) for b in (buckets or default_bucket_sizes(max_batch)))))
    registry = ModelRegistry()
    example = _load_spec(registry, model_name, spec)
    if example is None:
        raise BootImageError(
            f"spec {sorted(spec)} implies no request shape; boot images "
            "need an example to fix the exported input spec"
        )
    example = np.asarray(example)
    entry = registry.resolve(model_name)
    batch_apply = entry.batch_apply

    os.makedirs(out_dir, exist_ok=True)
    image_cache = os.path.join(out_dir, CACHE_DIR)
    os.makedirs(image_cache, exist_ok=True)

    # Export each bucket at FULL occupancy (see module docstring), then
    # immediately round-trip it through deserialize+call — that one call
    # is what writes the persistent cache entries a loading worker will
    # hydrate from.
    def fn(data):
        out = batch_apply(ArrayDataset(data))
        return getattr(out, "data", out)

    from ..utils.compilation_cache import cache_hit_count, install_compile_counter

    executables: Dict[int, Any] = {}
    files: Dict[str, str] = {}
    active = _active_cache_dir()
    install_compile_counter()
    before, hits_before = _cache_entries(active), cache_hit_count()
    for b in buckets:
        in_spec = jax.ShapeDtypeStruct((b,) + example.shape, example.dtype)
        blob = jax_export.export(jax.jit(fn))(in_spec).serialize()
        filename = f"bucket_{b}.bin"
        with open(os.path.join(out_dir, filename), "wb") as f:
            f.write(bytes(blob))
        files[str(b)] = filename
        executables[b] = jax_export.deserialize(blob)
        jax.block_until_ready(
            executables[b].call(
                np.zeros((b,) + example.shape, example.dtype)
            )
        )
    # Bundle what those calls wrote. A program the active cache already
    # held wrote nothing new and its entry cannot be told from other
    # exported programs', so after any hit every exported-call entry is
    # bundled: a superset, never a gap.
    bundle = _cache_entries(active) - before
    if cache_hit_count() > hits_before:
        bundle |= {n for n in before if n.startswith(_EXPORTED_ENTRY_PREFIX)}
    for name in bundle:
        shutil.copy2(os.path.join(active, name), os.path.join(image_cache, name))

    with open(os.path.join(out_dir, WEIGHTS), "wb") as f:
        pickle.dump(entry.model, f)

    manifest: Dict[str, Any] = dict(environment_fingerprints())
    manifest.update(
        {
            "model_name": model_name,
            "model_version": entry.version,
            "source": entry.source,
            "created_at": time.time(),
            "buckets": list(buckets),
            "example": {
                "shape": list(example.shape),
                "dtype": str(example.dtype),
            },
            "weights_digest": _digest(os.path.join(out_dir, WEIGHTS)),
            "executables": files,
        }
    )

    _parity_gate(manifest, executables, entry, example)

    with open(os.path.join(out_dir, MANIFEST), "w") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
    _names.metric(_names.BOOTIMAGE_BUILDS).inc()
    _names.metric(_names.BOOTIMAGE_BUILD_SECONDS).observe(
        time.perf_counter() - t0
    )
    return manifest


def _parity_gate(manifest, executables, entry, example) -> None:
    """Refuse to produce an image whose executables disagree with the
    classic apply path. Checks the largest bucket at full occupancy AND
    (when the bucket holds >1 row) partial occupancy — the case the
    full-occupancy export + eager re-mask must get right."""
    import numpy as np

    from ..data.dataset import ArrayDataset

    wrapper = BootImageModel(manifest, executables, entry.model)
    b = max(executables)
    rng = np.random.default_rng(0)
    data = rng.standard_normal((b,) + example.shape).astype(example.dtype)
    for n in {b, max(1, b - 1)}:
        classic = entry.batch_apply(ArrayDataset(data, num_examples=n))
        imaged = wrapper.apply_batch(ArrayDataset(data, num_examples=n))
        got = np.asarray(imaged.data)[:n]
        want = np.asarray(classic.data)[:n]
        if not np.allclose(got, want, rtol=1e-5, atol=1e-6):
            raise BootImageError(
                f"parity gate failed at bucket {b} occupancy {n}: exported "
                f"executable disagrees with the classic apply path by "
                f"{float(np.max(np.abs(got - want)))} — image not written"
            )


def _install_cache_entries(image_cache: str) -> None:
    """Make the image's bundled persistent-cache entries visible to this
    process by copying them into the active cache directory."""
    active = _active_cache_dir()
    if active is None or not os.path.isdir(image_cache):
        return
    if os.path.abspath(active) == os.path.abspath(image_cache):
        return
    os.makedirs(active, exist_ok=True)
    for name in os.listdir(image_cache):
        target = os.path.join(active, name)
        if not os.path.exists(target):
            shutil.copy2(os.path.join(image_cache, name), target)


def load_boot_image(image_dir: str, verify: bool = True) -> BootImageModel:
    """Load a boot image: KV307-verify the manifest fingerprints, install
    the bundled cache entries, and deserialize every bucket executable.
    The fitted-weights pickle is digest-verified here but unpickled
    lazily (first fallback bucket or refit read) — it is the single
    largest load cost and steady state never needs it. Raises
    :class:`BootImageRefused` on any fingerprint mismatch
    (``KEYSTONE_VERIFY=off`` skips the gate) or corrupt artifact —
    callers fall back to the classic warm path."""
    from ..reliability.recovery import get_recovery_log
    from ..workflow.verify import verification_mode, verify_boot_image

    t0 = time.perf_counter()
    loads = _names.metric(_names.BOOTIMAGE_LOADS)
    manifest_path = os.path.join(image_dir, MANIFEST)
    try:
        with open(manifest_path) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as exc:
        loads.inc(status="refused")
        raise BootImageRefused(f"unreadable boot image manifest: {exc}")

    current = environment_fingerprints()
    current["weights_digest"] = _digest(os.path.join(image_dir, WEIGHTS)) \
        if os.path.exists(os.path.join(image_dir, WEIGHTS)) else None
    if verify and verification_mode() != "off":
        report = verify_boot_image(manifest, current)
        if not report.ok:
            loads.inc(status="refused")
            get_recovery_log().record(
                "bootimage_refused",
                image_dir,
                codes=[d.code for d in report.errors()],
                fields=[d.details.get("field") for d in report.errors()],
            )
            raise BootImageRefused(
                "boot image refused (KV307): "
                + "; ".join(d.message for d in report.errors()),
                report=report,
            )

    from jax import export as jax_export

    _install_cache_entries(os.path.join(image_dir, CACHE_DIR))
    weights_path = os.path.join(image_dir, WEIGHTS)

    def load_weights() -> Any:
        with open(weights_path, "rb") as f:
            return pickle.load(f)

    try:
        executables: Dict[int, Any] = {}
        for b, filename in manifest.get("executables", {}).items():
            with open(os.path.join(image_dir, filename), "rb") as f:
                executables[int(b)] = jax_export.deserialize(f.read())
    except Exception as exc:
        loads.inc(status="refused")
        raise BootImageRefused(f"corrupt boot image artifact: {exc}")

    loads.inc(status="loaded")
    _names.metric(_names.BOOTIMAGE_LOAD_SECONDS).observe(
        time.perf_counter() - t0
    )
    get_recovery_log().record(
        "bootimage_loaded",
        image_dir,
        buckets=manifest.get("buckets"),
        model_version=manifest.get("model_version"),
    )
    return BootImageModel(manifest, executables, model_loader=load_weights)
