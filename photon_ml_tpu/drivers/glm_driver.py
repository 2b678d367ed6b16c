"""Legacy GLM training driver.

The analogue of the reference's ``com.linkedin.photon.ml.Driver`` ("GLMDriver"
— [CONFIRMED-BASELINE], SURVEY.md §2, §3.1): the end-to-end single-GLM
pipeline

    read → index → summarize → normalize → train over a regularization-weight
    grid (warm-started) → validate → select best → write model(s)

run as stages with artifacts written to the output directory.  Where the
reference launches a Spark job per stage, here ingest happens on the host and
every training stage is one jitted TPU program; with >1 device the grid runs
data-parallel over the mesh (parallel/distributed.py).

Usage:
    python -m photon_ml_tpu.drivers.glm_driver \
        --train-data a1a --task logistic --reg-type l2 \
        --reg-weights 0.1,1,10 --output-dir /tmp/out
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import telemetry as telemetry_mod
from photon_ml_tpu.analysis import sanitizers
from photon_ml_tpu.data import libsvm
from photon_ml_tpu.data.dataset import make_glm_data
from photon_ml_tpu.data.index_map import INTERCEPT_KEY, IndexMap
from photon_ml_tpu.data.normalization import (
    NormalizationContext,
    NormalizationType,
    build_normalization,
)
from photon_ml_tpu.data.stats import summarize
from photon_ml_tpu.evaluation.evaluators import (
    default_evaluator_for_task,
    get_evaluator,
)
from photon_ml_tpu.io.model_store import save_glm_model
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.optim.problem import (
    DEVICE_SOLVERS,
    HOST_LOOP_SOLVERS,
    GlmOptimizationConfig,
    GlmOptimizationProblem,
    OptimizerConfig,
    OptimizerType,
)
from photon_ml_tpu.optim.regularization import RegularizationContext, RegularizationType
from photon_ml_tpu.utils.compile_cache import (
    add_compile_cache_arg,
    enable_from_args,
)
from photon_ml_tpu.utils.device_report import (
    CompileClock,
    bytes_in_use,
    describe_devices,
    describe_layout,
    runtime_block,
)
from photon_ml_tpu.utils.logging import PhotonLogger
from photon_ml_tpu.utils.timer import Timer
from photon_ml_tpu.utils.tracker import OptimizationStatesTracker


def build_arg_parser() -> argparse.ArgumentParser:
    """CLI surface mirroring the reference Driver's ``Params``."""
    p = argparse.ArgumentParser(
        prog="glm_driver", description="TPU-native GLM training driver"
    )
    p.add_argument("--train-data", required=True, help="LIBSVM training file")
    p.add_argument("--validate-data", help="LIBSVM validation file (optional)")
    p.add_argument("--output-dir", required=True)
    p.add_argument(
        "--task",
        default="logistic",
        help="logistic | linear | poisson | smoothed_hinge (or reference "
        "TaskType names like LOGISTIC_REGRESSION)",
    )
    p.add_argument(
        "--optimizer", default="lbfgs", choices=[t.value for t in OptimizerType]
    )
    p.add_argument(
        "--solver",
        choices=DEVICE_SOLVERS + HOST_LOOP_SOLVERS,
        help="the solver, by name: lbfgs | owlqn | tron | spg run on the "
        "device (optim/); admm | block_cd run a host-side loop "
        "(solvers/).  Unset keeps the historical routing (bounds → spg, "
        "any L1 → owlqn, else --optimizer) bitwise.  admm and block_cd "
        "run sharded: over the --data-parallel mesh when available, else "
        "over --solver-shards logical shards on one device",
    )
    p.add_argument(
        "--solver-shards",
        type=int,
        default=0,
        help="logical shard count for host-loop solvers without a mesh "
        "(0 = auto: 2, or the solver_options 'shards' knob)",
    )
    p.add_argument(
        "--solver-option",
        action="append",
        default=[],
        metavar="KEY=VALUE",
        help="solver-specific knob (repeatable), e.g. --solver-option "
        "rho=1.0 --solver-option n_blocks=8 (see docs/solvers.md)",
    )
    p.add_argument(
        "--reg-type",
        default="none",
        choices=[t.value for t in RegularizationType],
    )
    p.add_argument("--reg-weights", default="0.0", help="comma-separated λ grid")
    p.add_argument("--elastic-net-alpha", type=float, default=0.5)
    p.add_argument(
        "--normalization",
        default="none",
        choices=[t.value for t in NormalizationType],
    )
    p.add_argument("--max-iters", type=int, default=100)
    p.add_argument("--tolerance", type=float, default=1e-7)
    p.add_argument(
        "--coefficient-bounds",
        help="JSON file mapping feature key -> [lower, upper] box "
        "constraints (the reference's constraint map); unlisted features "
        "are unconstrained",
    )
    p.add_argument("--intercept", action="store_true", default=True)
    p.add_argument("--no-intercept", dest="intercept", action="store_false")
    p.add_argument("--compute-variances", action="store_true")
    p.add_argument("--evaluator", help="AUC | RMSE | ... (default: per task)")
    p.add_argument(
        "--output-mode",
        default="best",
        choices=["best", "all"],
        help="write only the selected model or every grid point "
        "(the reference's ModelOutputMode)",
    )
    p.add_argument("--n-features", type=int, help="fixed feature-space width")
    p.add_argument(
        "--resume",
        action="store_true",
        help="continue from the λ-grid checkpoint in the output dir "
        "(skips already-solved weights, keeps the warm-start chain)",
    )
    p.add_argument(
        "--initial-model",
        help="saved model Avro to warm-start the grid from (the reference's "
        "incremental training)",
    )
    p.add_argument(
        "--data-parallel",
        choices=["off", "auto"],
        default="off",
        help="auto: with >1 device, shard rows over a mesh and run the "
        "whole λ grid with one fused psum per objective evaluation (the "
        "reference's treeAggregate loop on ICI)",
    )
    p.add_argument(
        "--training-report",
        action="store_true",
        help="write report.json + report.html to the output dir: "
        "per-lambda convergence traces, bootstrap CIs on the validation "
        "metric, Hosmer-Lemeshow calibration (logistic), and "
        "|coef|*std feature importance (the reference's old diagnostics "
        "package, SURVEY.md 5.1)",
    )
    p.add_argument(
        "--max-retries",
        type=int,
        default=0,
        help="automatic recovery from TRANSIENT failures (lost device, "
        "transport drop, preemption): re-enter training up to this many "
        "times, resuming from the λ-grid checkpoint so finished work is "
        "never repeated (the Spark cluster manager's task-retry analogue). "
        "0 disables",
    )
    p.add_argument(
        "--retry-backoff",
        type=float,
        default=5.0,
        help="initial seconds between retries (exponential, x2 per "
        "attempt, capped at 300s)",
    )
    p.add_argument(
        "--precise-accumulation",
        action="store_true",
        help="accumulate the objective VALUE in float64 (the reference's "
        "Breeze f64 end-to-end; here f64 on the value reduction only — "
        "gradient sums stay f32 tree reductions). At 1e9 rows the f32 "
        "value rounds at ~1e-7 relative, competing with tight convergence "
        "tolerances. Costs one emulated-f64 pass per evaluation on TPU",
    )
    p.add_argument(
        "--stream-chunk-rows",
        type=int,
        default=0,
        help="out-of-core training: keep the dataset in host RAM as chunks "
        "of this many rows and stream them through HBM per objective "
        "evaluation (double-buffered device_put). 0 = device-resident. "
        "Datasets larger than HBM train this way; L-BFGS, OWL-QN "
        "(L1/elastic-net) and smooth TRON all stream",
    )
    p.add_argument(
        "--stream-storage-dir",
        help="with --stream-chunk-rows: spill the chunk store to .npy "
        "files in this directory and train from disk-backed (memmap) "
        "leaves — host RAM stops bounding the trainable size, disk does "
        "(the reference's MEMORY_AND_DISK RDD persistence)",
    )
    p.add_argument(
        "--stream-prefetch-depth",
        type=int,
        default=2,
        help="with --stream-chunk-rows: how many chunks the background "
        "ingest pipeline keeps in flight, and how many dispatched chunk "
        "programs the consumer runs ahead of its carry sync (HBM holds "
        "at most 2x this many chunks). 2 = the classic double buffer; 1 "
        "serializes transfer and compute (measurement baseline)",
    )
    p.add_argument(
        "--stream-chunk-fuse",
        type=int,
        default=1,
        help="with --stream-chunk-rows: fold this many chunks into one "
        "device dispatch (an in-program lax.scan over a stacked "
        "super-chunk) — amortizes per-dispatch overhead when chunks are "
        "small. Single-device only; 1 disables fusion",
    )
    p.add_argument(
        "--stream-batch-linesearch",
        choices=["on", "off"],
        default="on",
        help="with --stream-chunk-rows: evaluate a bracket of line-search "
        "candidate steps in ONE streamed pass (identical trial sequence, "
        "roughly half the passes per solve). 'off' streams one trial per "
        "pass",
    )
    p.add_argument(
        "--stream-compress",
        choices=["off", "lossless", "fp16", "int8"],
        default="off",
        help="with --stream-chunk-rows: compressed chunk wire formats — "
        "chunks cross the host->device link encoded (delta/downcast "
        "index blocks, {0,1} bitmaps, fp16/int8 feature quantization) "
        "and are dequantized ON DEVICE inside the per-chunk program. "
        "'lossless' keeps every solve bitwise identical to the raw "
        "stream; fp16/int8 add bounded quantization error for a bigger "
        "wire win. Single-host only",
    )
    p.add_argument(
        "--stream-hot-budget-mb",
        type=float,
        default=0.0,
        help="with --stream-chunk-rows: keep up to this many MB of "
        "(wire) chunk buffers RESIDENT in HBM across passes — the "
        "importance-aware working-set cache: admission/eviction is "
        "re-scored each pass from per-chunk gradient contributions, hot "
        "chunks skip pack+transfer entirely. Bitwise neutral; "
        "single-device only. 0 disables",
    )
    p.add_argument(
        "--telemetry",
        choices=["on", "off"],
        default="on",
        help="unified telemetry (events.jsonl + trace.json + metrics.json "
        "in the output dir, summary in the log). 'off' reduces every "
        "instrumented site to one branch",
    )
    p.add_argument(
        "--metrics-port",
        type=int,
        default=None,
        help="expose the live ops plane on this port while the run is "
        "in flight (/metrics Prometheus exposition, /snapshot JSON, "
        "/healthz); 0 binds an ephemeral port; omit to disable",
    )
    p.add_argument(
        "--metrics-interval-s",
        type=float,
        default=1.0,
        help="interval of the metrics_ts.jsonl time-series sampler "
        "(live registry snapshots in the output dir; 0 disables)",
    )
    add_compile_cache_arg(p)
    return p


def make_fit_once(
    X_train,
    y_train,
    X_val,
    y_val,
    *,
    task: str = "logistic",
    reg_type: str = "l2",
    elastic_net_alpha: float = 0.5,
    optimizer: str = "lbfgs",
    max_iters: int = 100,
    tolerance: float = 1e-8,
    suite=None,
    val_weights=None,
    solver: Optional[str] = None,
    solver_options: tuple = (),
):
    """Reusable single-fit entry for the tuning orchestrator
    (photon_ml_tpu/tuning/): ``fit_once(params, resource, warm_start) ->
    (metric, metrics, coefficients)``.

    ``params[0]`` is the regularization weight λ.  ``resource`` > 0 caps
    the optimizer's iteration budget (an ASHA rung's resource; 0 uses
    ``max_iters``), and ``warm_start`` seeds the solve — the executor
    chains a promoted trial from its own previous rung and a fresh trial
    from the nearest completed λ's coefficients, the λ-path warm-start
    pattern this driver's own grid loop uses.  Data uploads once; every
    trial at one rung level shares one compiled solver (λ, w0 are traced
    arguments), so a parallel sweep adds no recompiles.

    Exposes ``fit_once.suite`` and ``fit_once.larger_is_better`` so
    callers wire the orchestrator's direction without re-deriving it.
    """
    import threading

    from photon_ml_tpu.evaluation.suite import EvaluationSuite

    if suite is None:
        from photon_ml_tpu.ops import losses as losses_lib

        suite = EvaluationSuite.for_task(losses_lib.get(task).name)
    host_kind = solver in HOST_LOOP_SOLVERS
    if host_kind and hasattr(X_train, "todense"):
        # The host-loop solvers shard dense row blocks; tuning-scale designs
        # densify cheaply (the distributed grid path takes sparse).
        X_train = np.asarray(X_train.todense(), np.float32)
    data = make_glm_data(X_train, y_train)
    y_val = np.asarray(y_val)
    problems: dict[int, GlmOptimizationProblem] = {}
    sharded_solves: dict[int, object] = {}
    lock = sanitizers.tracked(threading.Lock(), "glm.problem_cache")

    def _problem(iters: int) -> GlmOptimizationProblem:
        # One problem (= one jitted solver) per distinct iteration
        # budget, shared across trials and threads.
        with lock:
            p = problems.get(iters)
            if p is None:
                p = problems[iters] = GlmOptimizationProblem(
                    task,
                    GlmOptimizationConfig(
                        optimizer=OptimizerConfig(
                            optimizer=OptimizerType(optimizer),
                            max_iters=iters,
                            tolerance=tolerance,
                            solver=solver,
                            solver_options=tuple(solver_options),
                        ),
                        regularization=RegularizationContext(
                            RegularizationType(reg_type), elastic_net_alpha
                        ),
                    ),
                )
            return p

    def _sharded_solve(iters: int):
        # Host-loop counterpart of the per-iters problem cache: one
        # bound solver (logical shards, one compiled step program) per
        # iteration budget.
        from photon_ml_tpu.solvers import HOST_SOLVERS
        from photon_ml_tpu.solvers import sharded as solvers_sharded

        problem = _problem(iters)
        with lock:
            s = sharded_solves.get(iters)
            if s is None:
                n_shards = solvers_sharded.resolve_shard_count(
                    problem.config.optimizer
                )
                dist = solvers_sharded.stack_resident(data, n_shards)
                s = sharded_solves[iters] = HOST_SOLVERS[solver](
                    problem, dist, None, None
                )
            return s

    def fit_once(params, resource=0, warm_start=None):
        iters = int(resource) if resource else max_iters
        w0 = (
            None
            if warm_start is None
            else jnp.asarray(np.asarray(warm_start, np.float32))
        )
        lam = float(np.asarray(params).ravel()[0])
        if host_kind:
            res = _sharded_solve(iters)(lam, w0)
        else:
            res = _problem(iters).solve_single_device(
                data, reg_weight=lam, w0=w0
            )
        w = np.asarray(res.w, np.float32)
        scores = np.asarray(X_val @ w).ravel()
        metric, all_metrics = suite.evaluate_primary(
            scores, y_val, val_weights
        )
        return metric, all_metrics, w

    fit_once.suite = suite
    fit_once.larger_is_better = suite.primary_evaluator.larger_is_better
    return fit_once


def run(argv: Optional[Sequence[str]] = None) -> dict:
    args = build_arg_parser().parse_args(argv)
    # x64 is process-global jax state; restore it afterwards so one
    # --precise-accumulation run can't leak f64 defaults into later
    # in-process runs (tests, library users).
    prev_x64 = None
    if args.precise_accumulation:
        prev_x64 = bool(jax.config.jax_enable_x64)
        jax.config.update("jax_enable_x64", True)
    try:
        return _run(args)
    finally:
        if prev_x64 is not None:
            jax.config.update("jax_enable_x64", prev_x64)


def _run(args) -> dict:
    os.makedirs(args.output_dir, exist_ok=True)
    # The logger and telemetry hub own process-level resources (file
    # handles, the process-current hub slot); context managers release
    # them on ANY exit — repeated in-process driver runs (tests,
    # hyperparameter search) must not leak either.
    with PhotonLogger(args.output_dir) as logger:
        tel = telemetry_mod.Telemetry(
            output_dir=args.output_dir,
            logger=logger,
            enabled=args.telemetry != "off",
        )
        with tel, tel.span(
            "run", driver="glm_driver", task=args.task
        ), telemetry_mod.mount_ops_plane(
            tel, port=args.metrics_port,
            interval_s=args.metrics_interval_s, logger=logger,
        ), CompileClock() as clock:
            return _run_impl(args, logger, tel, clock)


def _run_impl(args, logger, tel, clock) -> dict:
    timer = Timer().start()
    cache_dir = enable_from_args(args, logger)
    from photon_ml_tpu.parallel.multihost import initialize_logged

    initialize_logged(logger)
    logger.info("device: %s", describe_devices())
    # What the training matrix became and what placing it left on each
    # device — reported in the result so a run shows which kernels it
    # exercised (the layout follows the backend and the data size).
    placed = {"layout": None, "bytes": None}

    def note_placement(features, shards: int = 1) -> None:
        layout = describe_layout(features, shards)
        placed.update(layout=layout, bytes=bytes_in_use())
        logger.info("feature layout: %s", layout)

    # Stage 1: read ---------------------------------------------------------
    with tel.span("read", path=args.train_data):
        X_train, y_train = libsvm.read_libsvm(
            args.train_data, n_features=args.n_features,
            add_intercept=args.intercept,
        )
    d = X_train.shape[1]
    logger.info(
        "read %d rows x %d features from %s", X_train.shape[0], d, args.train_data
    )
    # The LIBSVM path has positional features; the index map gives them names
    # (feature "j" + intercept last), as FeatureIndexingDriver would.
    names = [f"f{j}" for j in range(d - 1)] if args.intercept else [
        f"f{j}" for j in range(d)
    ]
    index_map = IndexMap.build(names, add_intercept=args.intercept)

    # Stage 2: summarize + normalization ------------------------------------
    data_parallel = args.data_parallel == "auto" and len(jax.devices()) > 1
    if args.stream_storage_dir and args.stream_chunk_rows <= 0:
        # Silently ignoring the flag would hand the user a fully
        # RAM-resident run on exactly the oversized dataset the flag
        # exists for.
        raise ValueError(
            "--stream-storage-dir requires --stream-chunk-rows > 0"
        )
    if args.stream_chunk_fuse > 1 and data_parallel:
        # StreamingObjective would refuse this at construction anyway,
        # but only after the (possibly long) chunk-store ingest.
        raise ValueError(
            "--stream-chunk-fuse > 1 is single-device only (the scan-"
            "fused program does not compose with the mesh reduction)"
        )
    if args.stream_hot_budget_mb > 0 and data_parallel:
        raise ValueError(
            "--stream-hot-budget-mb > 0 is single-device only (a cached "
            "chunk would pin sharded buffers across the mesh)"
        )
    streaming = args.stream_chunk_rows > 0
    with tel.span("summarize", rows=int(X_train.shape[0]), features=int(d)):
        if data_parallel or streaming:
            # The sharded path uploads the matrix across the mesh (and the
            # streamed path never uploads it whole); a second full
            # single-device copy just for summarization would defeat both.
            from photon_ml_tpu.data.stats import summarize_host

            train_data = None
            summary = summarize_host(X_train)
        else:
            train_data = make_glm_data(X_train, y_train)
            note_placement(train_data.features)
            summary = summarize(train_data)
    norm_type = NormalizationType(args.normalization)
    normalization = (
        None
        if norm_type is NormalizationType.NONE
        else build_normalization(norm_type, summary, index_map.intercept_index)
    )
    summary_out = {
        "mean": np.asarray(summary.mean).tolist(),
        "variance": np.asarray(summary.variance).tolist(),
        "min": np.asarray(summary.min).tolist(),
        "max": np.asarray(summary.max).tolist(),
        "nnz": np.asarray(summary.nnz).tolist(),
        "count": float(summary.count),
    }
    with open(os.path.join(args.output_dir, "feature_summary.json"), "w") as f:
        json.dump(summary_out, f)
    # Avro artifact too, as the reference writes (SURVEY.md §5.5).
    from photon_ml_tpu.io.summary_store import save_feature_summary

    save_feature_summary(
        summary, index_map,
        os.path.join(args.output_dir, "feature_summary.avro"),
    )

    # Stage 3: train over the λ grid ----------------------------------------
    solver_options = []
    for kv in args.solver_option:
        if "=" not in kv:
            raise SystemExit(
                f"--solver-option must be KEY=VALUE, got {kv!r}"
            )
        k, _, v = kv.partition("=")
        solver_options.append((k.strip(), v.strip()))
    if args.solver_shards:
        solver_options.append(("shards", args.solver_shards))
    host_solver = args.solver in HOST_LOOP_SOLVERS
    if host_solver:
        if streaming:
            raise SystemExit(
                f"--solver {args.solver} runs over sharded resident "
                "data; it does not compose with --streaming (the "
                "streamed pass loop IS the on-device solvers' "
                "distribution story)"
            )
        if args.compute_variances:
            raise SystemExit(
                f"--solver {args.solver} does not support "
                "--compute-variances"
            )
        if args.coefficient_bounds:
            raise SystemExit(
                f"--solver {args.solver} does not support "
                "--coefficient-bounds (only spg does)"
            )
    problem = GlmOptimizationProblem(
        args.task,
        GlmOptimizationConfig(
            optimizer=OptimizerConfig(
                optimizer=OptimizerType(args.optimizer),
                max_iters=args.max_iters,
                tolerance=args.tolerance,
                solver=args.solver,
                solver_options=tuple(solver_options),
            ),
            regularization=RegularizationContext(
                RegularizationType(args.reg_type), args.elastic_net_alpha
            ),
            compute_variances=args.compute_variances,
        ),
        normalization=normalization,
        accumulate="f64" if args.precise_accumulation else "f32",
    )
    reg_weights = [float(s) for s in args.reg_weights.split(",")]
    l1_mask = None
    if args.intercept and index_map.intercept_index is not None:
        l1_mask = jnp.ones((d,), jnp.float32).at[index_map.intercept_index].set(0.0)

    bounds = None
    if args.coefficient_bounds:
        # Box constraints apply to the coefficients the solver actually
        # optimizes; under normalization those live in scaled space where
        # a per-feature box does not map back to the user's box — reject
        # rather than silently constrain the wrong quantity.  Streamed /
        # data-parallel composition is not wired up.
        if normalization is not None:
            raise SystemExit(
                "--coefficient-bounds requires --normalization none"
            )
        if streaming or data_parallel:
            raise SystemExit(
                "--coefficient-bounds is single-device resident-data only"
            )
        if args.compute_variances:
            # The diag-inverse-Hessian variance assumes an interior
            # optimum; it is wrong for coefficients pinned at an active
            # bound (nonzero gradient there).
            raise SystemExit(
                "--coefficient-bounds is incompatible with "
                "--compute-variances"
            )
        with open(args.coefficient_bounds) as f:
            bounds_map = json.load(f)
        lower = np.full((d,), -np.inf, np.float32)
        upper = np.full((d,), np.inf, np.float32)
        unknown = [k for k in bounds_map if index_map.get_index(k) < 0]
        if unknown:
            raise SystemExit(
                f"--coefficient-bounds names unknown features: {unknown[:5]}"
            )
        for key, (lo, hi) in bounds_map.items():
            lo, hi = float(lo), float(hi)
            if np.isnan(lo) or np.isnan(hi) or lo > hi:
                # json.load accepts NaN literals, and jnp.clip with
                # lower > upper silently returns upper — both would
                # train a wrong model without a word.
                raise SystemExit(
                    f"--coefficient-bounds: invalid bounds for {key!r}: "
                    f"[{lo}, {hi}]"
                )
            idx = index_map.get_index(key)
            lower[idx], upper[idx] = lo, hi
        bounds = (jnp.asarray(lower), jnp.asarray(upper))
        logger.info(
            "box constraints on %d of %d coefficients", len(bounds_map), d
        )

    # Checkpoint/resume + incremental training (SURVEY.md §5.3/§5.4): each
    # solved λ is persisted; --resume skips finished λs bit-exactly;
    # --initial-model seeds the warm-start chain from a saved model.
    from photon_ml_tpu.io.checkpoint import GridCheckpointer
    from photon_ml_tpu.io.model_store import load_glm_model

    # Fingerprint the RESOLVED box constraints (the arrays the solver
    # actually sees): a --resume against a checkpoint written under
    # different bounds would warm-start the remaining λs from
    # incompatibly-constrained coefficients and silently blend two
    # models (the CD locked-set guard's failure mode, ADVICE r5).
    bounds_fp = None
    if bounds is not None:
        import hashlib

        h = hashlib.sha256()
        h.update(np.ascontiguousarray(np.asarray(bounds[0])).tobytes())
        h.update(np.ascontiguousarray(np.asarray(bounds[1])).tobytes())
        bounds_fp = h.hexdigest()

    ckpt = GridCheckpointer(os.path.join(args.output_dir, "checkpoints"))
    if args.resume:
        saved_fp = ckpt.load_meta().get("bounds_fingerprint")
        if ckpt.exists() and saved_fp != bounds_fp:
            raise SystemExit(
                "--resume: the grid checkpoint was written under "
                f"different --coefficient-bounds (saved fingerprint "
                f"{saved_fp}, this run {bounds_fp}); clear "
                f"{ckpt.path} or rerun with the matching bounds"
            )
        solved = ckpt.load()
    else:
        # A stale checkpoint (possibly from a run on different data or
        # normalization) must not survive into a later --resume.
        ckpt.clear()
        solved = {}
    if solved:
        logger.info(
            "resuming: %d of %d grid points already solved",
            len(solved), len(reg_weights),
        )

    w0 = None
    if args.initial_model:
        glm0, _ = load_glm_model(args.initial_model, index_map)
        w0 = jnp.asarray(np.asarray(glm0.coefficients.means, np.float32))
        if normalization is not None:
            # Saved models live in the original feature space; the solver
            # works in scaled-coefficient space.
            w0 = normalization.original_to_model(w0)
        logger.info("warm-starting from %s", args.initial_model)

    mesh = None
    stream = None
    if streaming:
        from photon_ml_tpu.data.streaming import make_streaming_glm_data
        from photon_ml_tpu.optim.streaming import ensure_streamable

        # Reject unstreamable configs BEFORE the (possibly large) ingest.
        ensure_streamable(problem.config)
        n_shards = 1
        if data_parallel:
            from photon_ml_tpu.parallel.distributed import data_mesh

            mesh = data_mesh()
            n_shards = mesh.devices.size
        stream = make_streaming_glm_data(
            X_train, y_train, chunk_rows=args.stream_chunk_rows,
            use_pallas=False if n_shards > 1 else "auto",
            n_shards=n_shards,
            storage_dir=args.stream_storage_dir,
        )
        logger.info(
            "streaming: %d chunks x %d rows (%.1f MB host), %d shard(s)",
            stream.n_chunks, stream.chunk_rows,
            stream.nbytes() / 1e6, n_shards,
        )
        placed["layout"] = (
            f"streamed {describe_layout(stream.chunks[0].features)}"
        )
    elif data_parallel:
        from photon_ml_tpu.parallel.distributed import data_mesh

        mesh = data_mesh()
        logger.info("data-parallel: %d-device mesh", len(jax.devices()))

    def train(attempt: int):
        """One training attempt over the λ grid.  Re-entered by the
        watchdog after a transient failure (SURVEY.md §5.3): checkpointed
        λs are reloaded so finished work is never repeated, and device-
        resident data is re-placed (a lost device invalidates buffers)."""
        solved_now = dict(solved)
        if attempt:
            solved_now.update(ckpt.load())
            logger.info(
                "retry %d: %d grid points restored from checkpoints",
                attempt, len(solved_now),
            )
        solved_acc = dict(solved_now)

        def on_solved(lam, w):
            solved_acc[lam] = np.asarray(w)
            ckpt.save(
                solved_acc, extra_meta={"bounds_fingerprint": bounds_fp}
            )

        if streaming:
            from photon_ml_tpu.optim.streaming import streaming_run_grid

            # Chunks are host-resident numpy; nothing to re-place.
            return streaming_run_grid(
                problem, stream, reg_weights, w0=w0, mesh=mesh,
                solved=solved_now, on_solved=on_solved, l1_mask=l1_mask,
                prefetch_depth=args.stream_prefetch_depth,
                chunk_fuse=args.stream_chunk_fuse,
                batch_linesearch=args.stream_batch_linesearch == "on",
                compress=args.stream_compress,
                hot_budget_bytes=int(args.stream_hot_budget_mb * 1e6),
            )
        if data_parallel:
            from photon_ml_tpu.parallel.distributed import shard_glm_data

            if host_solver:
                # its own loop around the mesh's step program
                from photon_ml_tpu.solvers.sharded import (
                    run_grid_sharded as run_grid,
                )
            else:
                # one shard_map program a solve
                from photon_ml_tpu.parallel.distributed import (
                    run_grid_distributed as run_grid,
                )
            dist = shard_glm_data(X_train, y_train, mesh)
            note_placement(dist.data.features, dist.n_shards)
            return run_grid(
                problem, dist, mesh, reg_weights, w0=w0, l1_mask=l1_mask,
                solved=solved_now, on_solved=on_solved,
            )
        if host_solver:
            # No mesh: a host-loop solver still runs sharded, over
            # logical row blocks on one device (same step program as the
            # mesh path, vmap + axis-0 sum standing in for the psum).
            from photon_ml_tpu.parallel.distributed import shard_glm_data
            from photon_ml_tpu.solvers import sharded as solvers_sharded

            n_shards = solvers_sharded.resolve_shard_count(
                problem.config.optimizer
            )
            X_sh = X_train
            if args.solver == "block_cd" and hasattr(X_sh, "todense"):
                # block CD reads per-shard columns; densify (LIBSVM
                # inputs at driver scale fit — the mesh path keeps
                # sparse for admm).
                X_sh = np.asarray(X_sh.todense(), np.float32)
                logger.info(
                    "block_cd: densified %d x %d design for column "
                    "access", X_sh.shape[0], X_sh.shape[1],
                )
            dist = shard_glm_data(X_sh, y_train, None, n_shards=n_shards)
            note_placement(dist.data.features, n_shards)
            logger.info(
                "solver %s: %d logical shard(s)", args.solver, n_shards
            )
            return solvers_sharded.run_grid_sharded(
                problem, dist, None, reg_weights, w0=w0, l1_mask=l1_mask,
                solved=solved_now, on_solved=on_solved,
            )
        data = train_data if attempt == 0 else make_glm_data(
            X_train, y_train
        )
        return problem.run_grid(
            data, reg_weights, w0=w0, l1_mask=l1_mask,
            solved=solved_now, on_solved=on_solved, bounds=bounds,
        )

    from photon_ml_tpu.utils.watchdog import (
        RetryPolicy,
        RetryStats,
        run_with_retries,
    )

    retry_stats = RetryStats()
    with tel.span(
        "train", grid_points=len(reg_weights),
        streaming=streaming, data_parallel=data_parallel,
    ):
        grid = run_with_retries(
            train,
            RetryPolicy(
                max_retries=args.max_retries,
                backoff_seconds=args.retry_backoff,
            ),
            logger,
            stats=retry_stats,
        )
    grid_walls = getattr(problem, "grid_wall_seconds", {})
    for lam, _, res in grid:
        if res is None:
            logger.info("lambda=%g: restored from checkpoint", lam)
            continue
        tracker = OptimizationStatesTracker.from_solve_result(
            res, wall_seconds=grid_walls.get(lam, float("nan"))
        )
        logger.info(
            "lambda=%g: value=%.8g iters=%d converged=%s wall=%.3fs",
            lam, float(res.value), tracker.iterations, tracker.converged,
            tracker.wall_seconds,
        )

    # Stage 4: validate + select --------------------------------------------
    evaluator = (
        get_evaluator(args.evaluator)
        if args.evaluator
        else default_evaluator_for_task(problem.task)
    )
    if args.validate_data:
        X_val, y_val = libsvm.read_libsvm(
            args.validate_data, n_features=d - (1 if args.intercept else 0),
            add_intercept=args.intercept,
            # Features unseen at training time contribute nothing, they must
            # not abort the job after all training compute is spent.
            drop_out_of_range=True,
        )
    else:
        X_val, y_val = X_train, y_train
    host_scoring = data_parallel or streaming
    val_data = None if host_scoring else (
        make_glm_data(X_val, y_val) if args.validate_data else train_data
    )

    report = None
    if args.training_report:
        from photon_ml_tpu.diagnostics import (
            TrainingReport,
            bootstrap_metric_ci,
            feature_importance,
            hosmer_lemeshow,
        )

        report = TrainingReport(task=problem.task)
        # Loop-invariant report inputs (d can be millions; the λ loop
        # must not rebuild them per grid point, and names resolve lazily
        # for just the top-k rendered rows).
        report_std = np.sqrt(
            np.maximum(np.asarray(summary.variance), 0.0)
        )

    metrics = {}
    best: tuple[float, GeneralizedLinearModel] | None = None
    best_metric = None
    with tel.span(
        "validate", rows=int(len(y_val)),
        evaluator=type(evaluator).__name__,
    ):
        for lam, model, res in grid:
            if host_scoring:
                # Host scipy matvec: validation never needs a device round
                # trip of a full unsharded copy.
                scores = np.asarray(
                    X_val @ np.asarray(model.coefficients.means, np.float32)
                ).ravel()
                val_weights = None
            else:
                scores = np.asarray(model.compute_score(val_data))
                val_weights = np.asarray(val_data.weights)
            m = evaluator.evaluate(scores, y_val, val_weights)
            metrics[lam] = m
            logger.info(
                "lambda=%g: %s=%.6f", lam, type(evaluator).__name__, m
            )
            if best_metric is None or evaluator.better_than(m, best_metric):
                best_metric, best = m, (lam, model)
            if report is not None:
                if res is not None:
                    report.add_convergence(lam, res.values, res.grad_norms)
                report.add_metric(
                    type(evaluator).__name__, lam,
                    bootstrap_metric_ci(
                        lambda s, l: evaluator.evaluate(s, l, None),
                        scores, np.asarray(y_val),
                    ),
                )
                if problem.task == "logistic":
                    report.add_calibration(
                        lam, hosmer_lemeshow(scores, np.asarray(y_val))
                    )
                report.add_importance(lam, feature_importance(
                    np.asarray(model.coefficients.means),
                    feature_std=report_std,
                    name_fn=index_map.index_to_name,
                ))

    # Stage 5: write --------------------------------------------------------
    assert best is not None
    best_lam, best_model = best
    with tel.span("write", output_mode=args.output_mode):
        to_write = grid if args.output_mode == "all" else [
            (lam, mdl, res) for lam, mdl, res in grid if lam == best_lam
        ]
        for lam, model, _ in to_write:
            out = os.path.join(args.output_dir, f"model_lambda_{lam:g}.avro")
            save_glm_model(model, index_map, out, model_id=f"lambda={lam:g}")
        index_map.save(args.output_dir)
    result = {
        "best_lambda": best_lam,
        "metrics": {str(k): v for k, v in metrics.items()},
        "evaluator": type(evaluator).__name__,
        "n_rows": int(X_train.shape[0]),
        "n_features": int(d),
        "wall_seconds": timer.stop(),
        "solver_wall_seconds": {
            str(lam): w for lam, w in sorted(grid_walls.items())
        },
        # Final objective per solved λ (absent for checkpoint-restored
        # points): a non-finite value here is a failed run however the
        # validation metric looks.
        "objective_values": {
            str(lam): float(res.value)
            for lam, _, res in grid if res is not None
        },
        "runtime": runtime_block(
            clock, cache_dir, placed["layout"], placed["bytes"]
        ),
    }
    if retry_stats.retries or retry_stats.failures:
        result["retry"] = retry_stats.snapshot()
    if report is not None:
        jpath, hpath = report.save(args.output_dir)
        result["report"] = {"json": jpath, "html": hpath}
        logger.info("training report: %s", hpath)
    with open(os.path.join(args.output_dir, "training_result.json"), "w") as f:
        json.dump(result, f, indent=2)
    tel.gauge("run_wall_seconds").set(result["wall_seconds"])
    logger.info(
        "selected lambda=%g (%s=%.6f) in %.2fs",
        best_lam, type(evaluator).__name__, best_metric, result["wall_seconds"],
    )
    return result


def main() -> None:
    run(sys.argv[1:])


if __name__ == "__main__":
    main()
