"""Command-line workload runner.

The scopt analog (reference: each workload object carries an
``OptionParser`` over its config case class, e.g.
pipelines/images/cifar/RandomPatchCifar.scala:101-114,
pipelines/images/imagenet/ImageNetSiftLcsFV.scala:171-207). Here one
argparse subcommand per workload is generated from the workload's config
dataclass: field names become ``--flags``, field types become parsers,
dataclass defaults become defaults — so pipeline authors only declare the
dataclass, exactly as reference authors only declared the case class.

Mesh/runtime knobs the reference put in the launcher environment
(KEYSTONE_MEM, OMP_NUM_THREADS; reference: bin/run-pipeline.sh:9-42) map
to ``--platform`` / ``--device-count`` here.

Usage:
    python -m keystone_tpu <workload> [--flag value ...]
    python -m keystone_tpu --list
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import sys
import typing
from typing import Any, Callable, Dict, Optional, Tuple


def _field_parser(field_type: Any) -> Optional[Callable[[str], Any]]:
    """Map a dataclass field annotation to an argparse type callable."""
    origin = typing.get_origin(field_type)
    if origin is typing.Union:  # Optional[T]
        args = [a for a in typing.get_args(field_type) if a is not type(None)]
        return _field_parser(args[0]) if len(args) == 1 else str
    if origin in (tuple, Tuple):
        inner = typing.get_args(field_type)

        def parse_tuple(text: str):
            parts = [p for p in text.replace("x", ",").split(",") if p]
            caster = inner[0] if inner else int
            return tuple(caster(p) for p in parts)

        return parse_tuple
    if field_type is bool:
        return lambda s: s.lower() in ("1", "true", "yes")
    if field_type in (int, float, str):
        return field_type
    return None


def add_config_arguments(parser: argparse.ArgumentParser, config_cls) -> None:
    """Generate ``--flag`` options from a config dataclass."""
    for field in dataclasses.fields(config_cls):
        caster = _field_parser(field.type if not isinstance(field.type, str)
                               else typing.get_type_hints(config_cls)[field.name])
        if caster is None:
            continue
        default = (
            field.default
            if field.default is not dataclasses.MISSING
            else field.default_factory()  # type: ignore[misc]
        )
        parser.add_argument(
            "--" + field.name.replace("_", "-"),
            dest=field.name,
            type=caster,
            default=default,
            help=f"(default: {default!r})",
        )


def build_config(config_cls, args: argparse.Namespace):
    names = {f.name for f in dataclasses.fields(config_cls)}
    return config_cls(**{k: v for k, v in vars(args).items() if k in names})


def add_refit_arguments(parser: argparse.ArgumentParser) -> None:
    """Flags for ``keystone-tpu refit`` — wired here (stdlib-only) so the
    CLI's --help/--list paths never import the refit/workflow packages
    (whose fold path imports jax); ``refit.daemon.refit_from_args``
    consumes the parsed namespace at dispatch time."""
    parser.add_argument(
        "--rounds", type=int, default=6,
        help="drifting-workload rounds to run",
    )
    parser.add_argument(
        "--dim", type=int, default=16, help="synthetic feature width",
    )
    parser.add_argument(
        "--classes", type=int, default=4, help="synthetic class count",
    )
    parser.add_argument(
        "--rows-per-round", type=int, default=1024,
        help="labeled rows fed to the tap per round",
    )
    parser.add_argument(
        "--serve-requests", type=int, default=192,
        help="live requests served through the pipeline per round",
    )
    parser.add_argument(
        "--chunk-rows", type=int, default=256,
        help="chunk rows for the incremental fold",
    )
    parser.add_argument(
        "--drift", type=float, default=0.2,
        help="per-round drift of the true weights",
    )
    parser.add_argument(
        "--quiet-round", type=int, default=2,
        help="round that feeds too few rows (a ledgered skip); 0 disables",
    )
    parser.add_argument(
        "--bad-round", type=int, default=4,
        help="round whose candidate is corrupted post-eval (exercises "
        "auto-rollback); 0 disables",
    )
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--store-dir", default=None,
        help="checkpoint-store directory for the stream state "
        "(default: a fresh temp dir)",
    )
    parser.add_argument(
        "--watch-gate", choices=("margin", "sequential"),
        default="margin", dest="watch_gate",
        help="post-publish watch rule: fixed margin floor, or the "
        "anytime-valid sequential gate (docs/OBSERVABILITY.md "
        "\"Quality plane\")",
    )
    parser.add_argument(
        "--adaptive-decay", action="store_true", dest="adaptive_decay",
        help="let the quality plane's drift detector shrink state_decay "
        "under detected score drift",
    )


def add_fit_arguments(parser: argparse.ArgumentParser) -> None:
    """Flags for ``keystone-tpu fit`` — wired here (stdlib-only) so the
    CLI's --help/--list paths never import the workflow package (whose
    __init__ imports jax); ``workflow.fitcmd.fit_from_args`` consumes
    the parsed namespace at dispatch time."""
    parser.add_argument(
        "--rows", type=int, default=1024, help="synthetic training rows",
    )
    parser.add_argument(
        "--dim", type=int, default=16, help="synthetic feature width",
    )
    parser.add_argument(
        "--classes", type=int, default=3, help="synthetic label width",
    )
    parser.add_argument(
        "--chunk-rows", type=int, default=128,
        help="streamed chunk rows (pinned so resume cursors align "
        "across processes)",
    )
    parser.add_argument(
        "--ckpt-chunks", type=int, default=None,
        help="chunks between mid-fit checkpoint commits "
        "(default KEYSTONE_STREAM_CKPT_CHUNKS; 0 disables)",
    )
    parser.add_argument("--reg", type=float, default=1e-3)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--store-dir", required=True,
        help="checkpoint-store directory (resume entries + fitted "
        "prefixes live here)",
    )
    parser.add_argument(
        "--out", default=None,
        help="write fitted predictions on the fixed probe batch here "
        "(.npz; the smoke's parity artifact)",
    )
    parser.add_argument(
        "--expect-resume", action="store_true",
        help="exit 2 unless this fit resumed from a persisted cursor",
    )
    parser.add_argument(
        "--drift-data", type=float, default=0.0,
        help="perturb the training matrix by this constant (same shape, "
        "different content — the seeded KV306 stale-resume case)",
    )
    parser.add_argument(
        "--solver", choices=("gram", "sketch"), default="gram",
        help="streamed state family: 'gram' accumulates the O(d²) "
        "sufficient statistics, 'sketch' the O(s·d) randomized sketch "
        "(docs/SOLVERS.md — the very-wide rung under test in "
        "scripts/sketch_smoke.sh)",
    )


def add_explain_arguments(parser: argparse.ArgumentParser) -> None:
    """Flags for ``keystone-tpu explain`` — wired here (stdlib-only) so
    --help/--list never import the workflow package (whose __init__
    imports jax); ``workflow.explain.explain_from_args`` consumes the
    parsed namespace at dispatch time."""
    parser.add_argument(
        "--pipeline", default="synthetic", metavar="PATH|synthetic",
        help="FittedPipeline.save artifact to explain, or 'synthetic' "
        "(featurize chain + block solve under the auto-cache optimizer)",
    )
    parser.add_argument(
        "--rows", type=int, default=2048,
        help="synthetic training rows (fit cost scales with this)",
    )
    parser.add_argument(
        "--dim", type=int, default=64,
        help="feature width: the synthetic pipeline's, or — for "
        "--pipeline PATH — the loaded artifact's expected input width "
        "(the eval batch is built at this width)",
    )
    parser.add_argument(
        "--classes", type=int, default=4, help="synthetic label width",
    )
    parser.add_argument(
        "--passes", type=int, default=3,
        help="plan executions: pass 1 pays compiles (cold, never "
        "drift-scored), later passes measure steady state",
    )
    parser.add_argument(
        "--seed-drift", type=float, default=0.0, metavar="FACTOR",
        help="corrupt stored autocache measurements by FACTOR× before "
        "running (CI negative control: the drift sentinel must flag it)",
    )
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=None, help="write report JSON here")
    parser.add_argument(
        "--json", action="store_true", dest="as_json",
        help="print EXPLAIN_JSON: line instead of the human table",
    )
    parser.add_argument(
        "--schedule", action="store_true",
        help="run the co-scheduled serving+refit demo and print the mesh "
        "schedule instead: per lease — who ran, what displaced or "
        "deferred it, predicted vs measured wall, price provenance "
        "(docs/SCHEDULING.md)",
    )


def add_tune_arguments(parser: argparse.ArgumentParser) -> None:
    """Flags for ``keystone-tpu tune`` — wired here (stdlib-only) so the
    CLI's --help/--list paths never import the workflow package (whose
    __init__ imports jax); ``workflow.tune.tune_from_args`` consumes the
    parsed namespace at dispatch time."""
    parser.add_argument(
        "--tasks", default="stream,solver,blocksparse",
        help="comma-separated tune tasks (stream, solver, blocksparse)",
    )
    parser.add_argument(
        "--rows", type=int, default=8192,
        help="synthetic problem rows (pick the shape class you serve)",
    )
    parser.add_argument(
        "--dim", type=int, default=256, help="synthetic feature width",
    )
    parser.add_argument(
        "--classes", type=int, default=4, help="synthetic label width",
    )
    parser.add_argument(
        "--budget", type=int, default=None,
        help="max measured candidates per task (default KEYSTONE_TUNE_BUDGET)",
    )
    parser.add_argument(
        "--seed", type=int, default=None,
        help="exploration seed (default KEYSTONE_TUNE_SEED)",
    )
    parser.add_argument(
        "--time-budget-s", type=float, default=None,
        help="per-task wall budget (default KEYSTONE_TUNE_TIME_S)",
    )
    parser.add_argument("--out", default=None, help="write result JSON here")


# ----------------------------------------------------------------- registry


# name → (module, config class name, run callable name, kwargs, description).
# Static strings only: --list and help must not import jax/pipelines.
WORKLOADS: Dict[str, Tuple[str, str, str, Dict[str, Any], str]] = {
    "mnist-random-fft": (
        "mnist_random_fft", "MnistRandomFFTConfig", "run", {},
        "MNIST random-FFT featurization + linear solve",
    ),
    "timit": (
        "timit", "TimitConfig", "run", {},
        "TIMIT cosine random features + block solve",
    ),
    "timit-kernel": (
        "timit", "TimitConfig", "run", {"solver": "kernel"},
        "TIMIT exact Gaussian kernel ridge regression (dual block Gauss-Seidel)",
    ),
    "voc-sift-fisher": (
        "voc", "SIFTFisherConfig", "run", {},
        "VOC 2007 SIFT + Fisher Vector + block least squares",
    ),
    "imagenet-sift-lcs-fv": (
        "imagenet", "ImageNetSiftLcsFVConfig", "run", {},
        "ImageNet dual-branch SIFT+LCS Fisher Vector pipeline",
    ),
    "imagenet-native": (
        "imagenet", "ImageNetSiftLcsFVConfig", "run_native_resolution", {},
        "ImageNet SIFT+LCS+FV with per-image native-resolution featurization",
    ),
    "imagenet-native-streaming": (
        "imagenet_streaming", "ImageNetSiftLcsFVConfig",
        "run_native_resolution_streaming", {},
        "Native-resolution flagship via the fused streaming path (at-scale)",
    ),
    "amazon-reviews": (
        "text", "AmazonReviewsConfig", "run_amazon", {},
        "Amazon reviews n-gram logistic/LBFGS text pipeline",
    ),
    "newsgroups": (
        "text", "NewsgroupsConfig", "run_newsgroups", {},
        "20 Newsgroups n-gram naive-bayes/least-squares pipeline",
    ),
    "stupid-backoff": (
        "stupid_backoff", "StupidBackoffConfig", "run", {},
        "Stupid Backoff n-gram language model",
    ),
    **{
        "cifar-" + v.replace("_", "-"): (
            "cifar", "RandomCifarConfig", "run", {"variant": v},
            f"CIFAR-10 {v} workload",
        )
        for v in (
            "linear_pixels", "random", "random_patch", "random_patch_fused",
            "random_patch_kernel", "random_patch_augmented",
            "random_patch_kernel_augmented",
        )
    },
}


def _resolve(name: str) -> Tuple[Any, Callable[..., dict]]:
    """Import one workload's module and bind (config_cls, run_fn)."""
    import importlib

    module_name, config_name, run_name, kwargs, _desc = WORKLOADS[name]
    module = importlib.import_module(
        f".pipelines.{module_name}", package="keystone_tpu"
    )
    config_cls = getattr(module, config_name)
    run_fn = getattr(module, run_name)
    if kwargs:
        bound = run_fn

        def run_fn(config, _bound=bound, _kw=kwargs):
            return _bound(config, **_kw)

    return config_cls, run_fn


def _apply_platform_flags(argv: list) -> None:
    """Apply --platform / --device-count from raw argv before jax loads."""
    import os

    def flag_value(flag: str) -> Optional[str]:
        for i, a in enumerate(argv):
            if a == flag and i + 1 < len(argv):
                return argv[i + 1]
            if a.startswith(flag + "="):
                return a.split("=", 1)[1]
        return None

    from .envknobs import env_str

    device_count = flag_value("--device-count")
    if device_count:
        flags = env_str("XLA_FLAGS")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={device_count}"
        ).strip()
    platform = flag_value("--platform")
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)


def main(argv: Optional[list] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    parser = argparse.ArgumentParser(
        prog="keystone_tpu",
        description="TPU-native ML pipeline framework — workload runner",
    )
    parser.add_argument("--list", action="store_true", help="list workloads")
    parser.add_argument(
        "--platform",
        default=None,
        help="force a JAX platform (cpu/tpu) before device init",
    )
    parser.add_argument(
        "--device-count",
        type=int,
        default=None,
        help="virtual CPU device count (XLA_FLAGS host platform override)",
    )
    parser.add_argument("--log-level", default="INFO")
    sub = parser.add_subparsers(dest="workload")

    # Platform knobs must land before anything imports jax — pre-scan argv
    # since resolving the selected workload imports its pipeline module.
    _apply_platform_flags(argv)

    # Only the selected workload's module is imported; --list and top-level
    # --help stay jax-free.
    selected = next((a for a in argv if a in WORKLOADS), None)
    resolved: Dict[str, Tuple[Any, Callable[..., dict]]] = {}
    for name, entry in WORKLOADS.items():
        sp = sub.add_parser(name, help=entry[-1])
        if name == selected:
            config_cls, run_fn = _resolve(name)
            resolved[name] = (config_cls, run_fn)
            add_config_arguments(sp, config_cls)

    # The online serving front-end (docs/SERVING.md): JSON requests on
    # stdin, responses on stdout. Flag wiring is plain argparse from the
    # serving package (stdlib-only import — help stays jax-free).
    from .serving.server import add_serve_arguments

    serve_parser = sub.add_parser(
        "serve",
        help="serve a fitted pipeline: micro-batched inference over stdin/JSON",
    )
    add_serve_arguments(serve_parser)

    # Observability harness (docs/OBSERVABILITY.md): run the synthetic
    # pipeline under full instrumentation, write a Perfetto-loadable
    # Chrome trace + a Prometheus snapshot. Stdlib-only flag wiring.
    from .obs.profile import add_profile_arguments

    profile_parser = sub.add_parser(
        "profile",
        help="profile a pipeline: spans + metrics → Chrome trace + Prometheus",
    )
    add_profile_arguments(profile_parser)

    # Fleet observability plane (docs/OBSERVABILITY.md "Fleet tracing"):
    # drive a traffic sweep against a real multiworker fleet under
    # cross-process tracing, emit the merged Perfetto trace + /metrics
    # scrape artifacts. Stdlib-only flag wiring; the default stub
    # backend keeps the whole run jax-free.
    from .obs.fleet import add_trace_arguments

    trace_parser = sub.add_parser(
        "trace",
        help="fleet trace: multiworker traffic sweep → merged Perfetto "
        "trace + Prometheus scrape + flight-recorder artifacts",
    )
    add_trace_arguments(trace_parser)

    # Perf-regression gate (docs/OBSERVABILITY.md): compare two BENCH
    # json artifacts leg by leg with noise-aware tolerances. Entirely
    # stdlib — CI runs it without a backend.
    from .obs.benchdiff import add_bench_diff_arguments

    bench_diff_parser = sub.add_parser(
        "bench-diff",
        help="compare two BENCH_*.json artifacts; exit 1 on perf regression",
    )
    add_bench_diff_arguments(bench_diff_parser)

    # Static tier (docs/VERIFICATION.md): keystone-lint over the
    # codebase and/or plan-time graph verification of a pipeline —
    # all before any data touches a device. Stdlib-only flag wiring.
    from .lint.check import add_check_arguments

    check_parser = sub.add_parser(
        "check",
        help="static checks: --lint the codebase, --concurrency the lock "
        "discipline, --pipeline verify a plan graph, --store the profile "
        "store's provenance",
    )
    add_check_arguments(check_parser)

    # Cost observatory (docs/OBSERVABILITY.md "Cost observatory"): run a
    # plan under per-node roofline attribution and the predicted-vs-
    # measured drift sentinel — the "why is this pipeline slow" report.
    # Stdlib-only flag wiring, same rule as tune.
    explain_parser = sub.add_parser(
        "explain",
        help="cost observatory: per-node predicted vs measured cost, "
        "roofline placement, decision provenance, drift sentinel",
    )
    add_explain_arguments(explain_parser)

    # Offline autotuner (docs/AUTOTUNING.md): budgeted measured search
    # over the plan-knob space, winners persisted to the profile store
    # under the keys MeasuredKnobRule replays. Flag wiring lives HERE,
    # not in workflow/tune.py: importing any workflow submodule executes
    # the package __init__, which imports jax — and --list/--help must
    # stay jax-free (pinned by tests/lint/test_check_cli.py).
    tune_parser = sub.add_parser(
        "tune",
        help="offline autotuner: search chunk/block/precision/threshold "
        "knobs per shape class, persist winners to the profile store",
    )
    add_tune_arguments(tune_parser)

    # Quality plane (docs/OBSERVABILITY.md "Quality plane"): the
    # operator-facing report over score streams, drift state, and
    # anytime-valid decision gates — run on a deterministic seeded
    # scenario so scripts/quality_smoke.sh can assert its decisions.
    # Stdlib-only flag wiring AND dispatch (the plane itself is jax-free).
    from .obs.quality_cli import add_quality_arguments

    quality_parser = sub.add_parser(
        "quality",
        help="quality-plane report: score streams, drift state, open "
        "sequential tests, decisions with evidence",
    )
    add_quality_arguments(quality_parser)

    # Continuous refit (docs/REFIT.md): the drifting-workload closed
    # loop — serve, tap, incremental fold, shadow-eval, publish, watch,
    # auto-rollback — with a final REFIT_STATS: JSON line the chaos
    # smoke asserts on. Stdlib-only flag wiring, same rule as tune.
    refit_parser = sub.add_parser(
        "refit",
        help="continuous-refit demo loop: drifting traffic absorbed by "
        "incremental refits with shadow eval and auto-rollback",
    )
    add_refit_arguments(refit_parser)

    # Durable fits (docs/RELIABILITY.md "Durable fits"): one streamed
    # fit with mid-fit cursor checkpoints; killed runs resume in a
    # fresh process via the same command. The engine under
    # scripts/elastic_smoke.sh. Stdlib-only flag wiring, same rule as
    # tune.
    fit_parser = sub.add_parser(
        "fit",
        help="durable streamed fit: mid-stream checkpoints, crash "
        "resume (--expect-resume), KV306 stale-entry refusal",
    )
    add_fit_arguments(fit_parser)

    args = parser.parse_args(argv)
    logging.basicConfig(
        level=getattr(logging, args.log_level.upper(), logging.INFO),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )

    if args.list or not args.workload:
        for name, entry in sorted(WORKLOADS.items()):
            print(f"{name:28s} {entry[-1]}")
        print(f"{'serve':28s} online serving front-end (micro-batched, stdin/JSON)")
        print(f"{'profile':28s} instrumented run → Chrome trace + Prometheus snapshot")
        print(
            f"{'trace':28s} fleet trace: multiworker sweep → merged "
            "Perfetto trace + /metrics scrape"
        )
        print(f"{'bench-diff':28s} compare two BENCH json artifacts, fail on regression")
        print(
            f"{'check':28s} static tier: keystone-lint + concurrency "
            "analysis + plan-time graph verification"
        )
        print(
            f"{'explain':28s} cost observatory: predicted vs measured "
            "per node, roofline placement, drift sentinel"
        )
        print(
            f"{'tune':28s} offline autotuner: measured knob search → "
            "profile-store winners"
        )
        print(
            f"{'quality':28s} quality-plane report: score streams, drift "
            "state, anytime-valid decision gates"
        )
        print(
            f"{'refit':28s} continuous-refit loop: incremental retrain + "
            "shadow eval + auto-rollback"
        )
        print(
            f"{'explain --schedule':28s} mesh co-scheduler: serving + "
            "leased background folds on one mesh, preempt/resume proof"
        )
        print(
            f"{'fit':28s} durable streamed fit: mid-stream checkpoints + "
            "crash resume + KV306 stale-entry refusal"
        )
        return 0

    # Multi-host launch (bin/launch-pod.sh sets KEYSTONE_DISTRIBUTED=1;
    # runbook: docs/MULTIHOST.md): join the pod's distributed runtime
    # BEFORE any device use so every host sees the global device set.
    from .envknobs import env_set

    if env_set("KEYSTONE_DISTRIBUTED"):
        from .parallel.mesh import distributed_init

        distributed_init()

    if args.workload == "serve":
        from .serving.server import serve_from_args

        return serve_from_args(args)

    if args.workload == "trace":
        from .obs.fleet import trace_from_args

        return trace_from_args(args)

    if args.workload == "bench-diff":
        from .obs.benchdiff import bench_diff_from_args

        return bench_diff_from_args(args)

    if args.workload == "check":
        from .lint.check import check_from_args

        return check_from_args(args)

    if args.workload == "explain":
        from .utils.compilation_cache import enable_persistent_cache
        from .workflow.explain import explain_from_args

        enable_persistent_cache()  # later passes/runs measure steady state
        return explain_from_args(args)

    if args.workload == "tune":
        from .utils.compilation_cache import enable_persistent_cache
        from .workflow.tune import tune_from_args

        enable_persistent_cache()  # measured runs warm the same cache
        return tune_from_args(args)

    if args.workload == "quality":
        from .obs.quality_cli import quality_from_args

        return quality_from_args(args)

    if args.workload == "refit":
        from .refit.daemon import refit_from_args
        from .utils.compilation_cache import enable_persistent_cache

        enable_persistent_cache()  # warm folds/warmups across runs
        return refit_from_args(args)

    if args.workload == "fit":
        from .utils.compilation_cache import enable_persistent_cache
        from .workflow.fitcmd import fit_from_args

        enable_persistent_cache()  # resumed processes re-use warm steps
        return fit_from_args(args)

    if args.workload == "profile":
        from .obs.profile import profile_from_args
        from .utils.compilation_cache import (
            enable_persistent_cache,
            install_compile_counter,
        )

        enable_persistent_cache()
        install_compile_counter()  # compile counts belong in the profile
        return profile_from_args(args)

    # Warm repeat runs: compiled XLA programs persist across processes
    # (KEYSTONE_COMPILATION_CACHE=off to disable). Enabled only on the
    # workload path so --list / --help stay jax-free.
    from .utils.compilation_cache import enable_persistent_cache

    enable_persistent_cache()

    config_cls, run_fn = resolved[args.workload]
    config = build_config(config_cls, args)
    results = run_fn(config)
    print(json.dumps({"workload": args.workload, **printable_results(results)}))
    return 0


def printable_results(results: dict) -> dict:
    """JSON-serializable view of a workload's results dict: true scalars
    become floats, small arrays become lists (e.g. the VOC run's (20,)
    per-class AP), large arrays and non-serializable objects are skipped."""
    import numpy as _np

    printable = {}
    for k, v in results.items():
        if isinstance(v, (int, float, str)):
            printable[k] = v
        elif hasattr(v, "item"):
            if _np.ndim(v) == 0 or getattr(v, "size", 0) == 1:
                printable[k] = float(_np.asarray(v).reshape(()))
            elif getattr(v, "size", 0) <= 64:
                printable[k] = _np.asarray(v).tolist()
    return printable


if __name__ == "__main__":
    sys.exit(main())
