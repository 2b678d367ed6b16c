"""GAME training driver.

The analogue of the reference's ``GameTrainingDriver``
([CONFIRMED-BASELINE], SURVEY.md §2, §3.2): validate params → read GAME Avro
data → build feature index maps → ``GameEstimator.fit`` over the coordinate
configuration → evaluate → save the GameModel (fixed-effect + per-entity
coefficient Avro files).

The coordinate configuration comes from a JSON file (the reference's
spark.ml ``Param`` surface), e.g.::

    {
      "task": "logistic",
      "iterations": 3,
      "evaluator": "auc",
      "coordinates": [
        {"name": "fixed", "type": "fixed", "feature_shard": "global",
         "optimizer": "lbfgs", "max_iters": 50, "tolerance": 1e-7,
         "reg_type": "l2", "reg_weight": 1.0},
        {"name": "per_user", "type": "random", "feature_shard": "userFeatures",
         "entity_key": "userId", "optimizer": "lbfgs", "max_iters": 30,
         "reg_type": "l2", "reg_weight": 1.0, "max_rows_per_entity": 4096}
      ]
    }
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Optional, Sequence

import numpy as np

from photon_ml_tpu import telemetry as telemetry_mod
from photon_ml_tpu.analysis import sanitizers
from photon_ml_tpu.data.game_reader import read_game_avro
from photon_ml_tpu.evaluation.suite import EvaluationSuite
from photon_ml_tpu.game.estimator import (
    FactoredRandomEffectCoordinateConfig,
    FixedEffectCoordinateConfig,
    GameEstimator,
    GameTransformer,
    RandomEffectCoordinateConfig,
)
from photon_ml_tpu.io.game_store import save_game_model
from photon_ml_tpu.optim.problem import (
    GlmOptimizationConfig,
    OptimizerConfig,
    OptimizerType,
)
from photon_ml_tpu.optim.regularization import RegularizationContext, RegularizationType
from photon_ml_tpu.ops import losses as losses_lib
from photon_ml_tpu.utils.compile_cache import (
    add_compile_cache_arg,
    enable_from_args,
)
from photon_ml_tpu.utils.device_report import (
    CompileClock,
    bytes_in_use,
    describe_devices,
    runtime_block,
)
from photon_ml_tpu.utils.logging import PhotonLogger
from photon_ml_tpu.utils.timer import Timer


def expand_config_grid(coordinate_specs: Sequence[dict]) -> list[dict]:
    """Expand the JSON coordinate list into the coordinate-config GRID the
    reference's GameEstimator fits (SURVEY.md §3.2 "for each
    coordinate-config combination"): a spec may give ``reg_weights`` (a list)
    instead of scalar ``reg_weight``; the grid is the cross product of every
    coordinate's variants.  Returns a list of name→config mappings."""
    import dataclasses as _dc
    import itertools

    per_coord = []
    for spec in coordinate_specs:
        name, base = parse_coordinate_config(spec)
        weights = spec.get("reg_weights")
        variants = (
            [_dc.replace(base, reg_weight=float(w)) for w in weights]
            if weights
            else [base]
        )
        per_coord.append((name, variants))
    return [
        {name: cfg for (name, _), cfg in zip(per_coord, combo)}
        for combo in itertools.product(*[v for _, v in per_coord])
    ]


def parse_coordinate_config(spec: dict):
    """One JSON coordinate spec → (name, CoordinateConfig)."""
    solver = spec.get("solver")
    solver_options = tuple(
        sorted((str(k), str(v)) for k, v in
               dict(spec.get("solver_options", {})).items())
    )
    opt = GlmOptimizationConfig(
        optimizer=OptimizerConfig(
            optimizer=OptimizerType(spec.get("optimizer", "lbfgs")),
            max_iters=int(spec.get("max_iters", 100)),
            tolerance=float(spec.get("tolerance", 1e-7)),
            # "solver" names a solver (optim.problem.choose_solver,
            # docs/solvers.md); unset keeps the historical routing
            # bitwise.  "solver_options" is a JSON object of knobs.
            solver=solver if solver is None else str(solver),
            solver_options=solver_options,
        ),
        regularization=RegularizationContext(
            RegularizationType(spec.get("reg_type", "none")),
            float(spec.get("elastic_net_alpha", 0.5)),
        ),
        compute_variances=bool(spec.get("compute_variances", False)),
    )
    name = spec["name"]
    if spec["type"] == "fixed":
        return name, FixedEffectCoordinateConfig(
            feature_shard=spec["feature_shard"],
            optimization=opt,
            reg_weight=float(spec.get("reg_weight", 0.0)),
            down_sampling_rate=float(spec.get("down_sampling_rate", 1.0)),
            # >0: train this coordinate out-of-core (host-RAM chunks of
            # this many rows streamed through HBM — game/streaming.py).
            streaming_chunk_rows=int(spec.get("streaming_chunk_rows", 0)),
            # chunks the ingest pipeline keeps in flight when streaming.
            prefetch_depth=int(spec.get("prefetch_depth", 2)),
            # chunks folded per device dispatch (lax.scan) when streaming;
            # amortizes per-dispatch overhead for small chunks.
            chunk_fuse=int(spec.get("chunk_fuse", 1)),
            # batch line-search trials into one streamed pass per bracket.
            batch_linesearch=bool(spec.get("batch_linesearch", True)),
            # compressed chunk wire format when streaming
            # (off|lossless|fp16|int8) — on-device dequant, lossless is
            # bitwise neutral.
            stream_compress=str(spec.get("stream_compress", "off")),
            # MB of wire chunk buffers kept HBM-resident across passes
            # (importance-aware working-set cache; single-device only).
            stream_hot_budget_mb=float(
                spec.get("stream_hot_budget_mb", 0.0)
            ),
        )
    if spec["type"] == "random":
        return name, RandomEffectCoordinateConfig(
            feature_shard=spec["feature_shard"],
            entity_key=spec["entity_key"],
            optimization=opt,
            reg_weight=float(spec.get("reg_weight", 0.0)),
            max_rows_per_entity=spec.get("max_rows_per_entity"),
            bucket_growth=float(spec.get("bucket_growth", 2.0)),
            # bucket-boundary policy: "geometric" | "cost_model" (the
            # repacker, game/data.py) + its program budget and seed.
            repack=str(spec.get("repack", "geometric")),
            program_budget=int(spec.get("program_budget", 16)),
            repack_seed=int(spec.get("repack_seed", 0)),
            # mesh bucket-ladder placement threshold (game/hierarchical.py).
            split_factor=float(spec.get("split_factor", 0.5)),
            # >0: train this coordinate out-of-core (entity blocks stay in
            # host RAM, streamed through HBM in pass groups bounded by this
            # many megabytes — game/ooc_random.py).
            device_budget_bytes=int(
                float(spec.get("device_budget_mb", 0)) * 2**20
            ),
            prefetch_depth=int(spec.get("prefetch_depth", 2)),
            # MB of out-of-core static slice payloads kept HBM-resident
            # across passes (hot working-set cache; bitwise neutral).
            hot_budget_mb=float(spec.get("hot_budget_mb", 0.0)),
        )
    if spec["type"] in ("factored_random", "factored"):
        proj_rw = spec.get("projection_reg_weight")
        return name, FactoredRandomEffectCoordinateConfig(
            feature_shard=spec["feature_shard"],
            entity_key=spec["entity_key"],
            rank=int(spec["rank"]),
            optimization=opt,
            reg_weight=float(spec.get("reg_weight", 0.0)),
            projection_reg_weight=(
                None if proj_rw is None else float(proj_rw)
            ),
            alternations=int(spec.get("alternations", 2)),
            max_rows_per_entity=spec.get("max_rows_per_entity"),
            bucket_growth=float(spec.get("bucket_growth", 2.0)),
            repack=str(spec.get("repack", "geometric")),
            program_budget=int(spec.get("program_budget", 16)),
            repack_seed=int(spec.get("repack_seed", 0)),
            device_budget_bytes=int(
                float(spec.get("device_budget_mb", 0)) * 2**20
            ),
            prefetch_depth=int(spec.get("prefetch_depth", 2)),
        )
    raise ValueError(f"unknown coordinate type {spec['type']!r}")


def make_fit_once(
    task: str,
    coordinate_configs: dict,
    shards: dict,
    ids: dict,
    response,
    validation,
    *,
    weight=None,
    offset=None,
    suite=None,
    mesh=None,
    device_metrics: bool = False,
):
    """Reusable single-fit entry for the tuning orchestrator
    (photon_ml_tpu/tuning/): ``fit_once(params, resource, warm_start) ->
    (metric, metrics, None)``.

    ``params`` carries one regularization weight per coordinate (in
    ``coordinate_configs`` order) and ``resource`` the number of CD
    iterations (an ASHA rung's budget; 0 uses the config count of 1).
    ``warm_start`` is accepted but unused — GAME coordinate state does
    not warm-start across trials; ASHA's cross-rung refits are whole
    fits at a larger iteration budget.

    Trials mutate per-coordinate ``reg_weight`` (a traced argument), so
    one coordinate build serves MANY trials — but never two in-flight
    trials at once: coordinates carry mutable per-fit state.  Builds
    live in a checkout pool per iteration budget, so the number of
    builds is bounded by the executor's peak concurrency (not
    trials × rungs) and builds are reused across searches sharing this
    ``fit_once``.
    """
    import threading

    import dataclasses as _dc

    from photon_ml_tpu.evaluation.suite import EvaluationSuite

    if suite is None:
        suite = EvaluationSuite.for_task(losses_lib.get(task).name)
    evaluator = suite.primary_evaluator
    names = list(coordinate_configs)
    # Never pay the coefficient-variance finalize cost per tuning point
    # (same policy as this driver's built-in tuning mode).
    base_configs = {
        nm: _dc.replace(
            cfg,
            optimization=_dc.replace(
                cfg.optimization, compute_variances=False
            ),
        )
        for nm, cfg in coordinate_configs.items()
    }
    v_shards, v_ids, v_resp, v_weight, v_offset = validation[:5]
    v_groups = (
        np.asarray(v_ids[suite.group_column])
        if suite.group_column is not None
        else None
    )
    pools: dict[int, list] = {}
    pool_lock = sanitizers.tracked(threading.Lock(), "game.checkout_pool")

    def _checkout(resource: int):
        n_iter = int(resource) if resource else 1
        with pool_lock:
            free = pools.setdefault(n_iter, [])
            if free:
                return n_iter, free.pop()
        est = GameEstimator(
            task, base_configs, n_iterations=n_iter, mesh=mesh,
            device_metrics=device_metrics,
        )
        coords = est.build_coordinates(shards, ids, response, weight, offset)
        return n_iter, (est, coords)

    def fit_once(params, resource=0, warm_start=None):
        n_iter, inst = _checkout(resource)
        try:
            est, coords = inst
            for coord, xi in zip(coords, np.asarray(params, float).ravel()):
                coord.reg_weight = float(xi)
            model, _ = est.fit_coordinates(
                coords, response, weight, offset, evaluator
            )
        finally:
            with pool_lock:
                pools[n_iter].append(inst)
        scores = GameTransformer(model).transform(v_shards, v_ids, v_offset)
        metric, all_metrics = suite.evaluate_primary(
            scores, v_resp, v_weight, group_ids=v_groups
        )
        return metric, all_metrics, None

    fit_once.suite = suite
    fit_once.larger_is_better = evaluator.larger_is_better
    fit_once.names = names
    return fit_once


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="game_training_driver", description="TPU-native GAME training"
    )
    p.add_argument("--train-data", required=True, help="GAME Avro file")
    p.add_argument("--validate-data", help="GAME Avro validation file")
    p.add_argument("--config", required=True, help="coordinate config JSON")
    p.add_argument("--output-dir", required=True)
    p.add_argument(
        "--resume",
        action="store_true",
        help="continue coordinate descent from the checkpoint in the "
        "output dir (bit-exact with the uninterrupted run)",
    )
    p.add_argument(
        "--initial-model",
        help="saved GameModel directory to warm-start from (the reference's "
        "incremental training); its index maps are used to read the data",
    )
    p.add_argument(
        "--locked-coordinates",
        help="comma-separated coordinate names held at --initial-model "
        "instead of retrained (the reference's partial retraining)",
    )
    p.add_argument(
        "--data-parallel",
        choices=["off", "auto"],
        default="off",
        help="auto: with >1 device, shard rows (fixed effects) and the "
        "entity axis (random effects) over a mesh of all devices — the "
        "reference's Spark-cluster layout on ICI",
    )
    p.add_argument(
        "--pipeline-coordinates",
        action="store_true",
        help="overlap coordinate updates' offset-independent host work "
        "(the next coordinate prestages its first pass groups while the "
        "current one solves — game/descent.py); bitwise identical to "
        "the serial schedule",
    )
    p.add_argument(
        "--device-metrics",
        action="store_true",
        help="compute per-update train/validation metrics ON DEVICE "
        "(only metric scalars cross to host — the at-scale validation "
        "path). Requires an ungrouped evaluation suite",
    )
    p.add_argument(
        "--max-retries",
        type=int,
        default=0,
        help="automatic recovery from TRANSIENT failures (lost device, "
        "transport drop): re-enter training up to this many times. The "
        "single-config path resumes from the per-iteration CD checkpoint; "
        "a config GRID resumes at the completed-grid-point boundary "
        "(each finished point's model is checkpointed). 0 disables",
    )
    p.add_argument(
        "--retry-backoff",
        type=float,
        default=5.0,
        help="initial seconds between retries (exponential, x2 per "
        "attempt, capped at 300s)",
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


def run(argv: Optional[Sequence[str]] = None) -> dict:
    args = build_arg_parser().parse_args(argv)
    os.makedirs(args.output_dir, exist_ok=True)
    # Context-managed logger + telemetry: both own process-level
    # resources that must release on ANY exit (see glm_driver).
    with PhotonLogger(args.output_dir) as logger:
        tel = telemetry_mod.Telemetry(
            output_dir=args.output_dir,
            logger=logger,
            enabled=args.telemetry != "off",
        )
        with tel, tel.span(
            "run", driver="game_training_driver"
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

    with open(args.config) as f:
        config = json.load(f)
    task = config.get("task", "logistic")
    config_grid = expand_config_grid(config["coordinates"])
    coordinate_configs = config_grid[0]
    # Evaluation suite (reference: EvaluationSuite / MultiEvaluator — a LIST
    # of evaluators per run, the first driving model selection).
    group_column = config.get("evaluator_group_column")
    if "evaluators" in config:
        suite = EvaluationSuite.from_specs(
            config["evaluators"], group_column=group_column
        )
    elif "evaluator" in config:
        suite = EvaluationSuite.from_specs(
            [config["evaluator"]], group_column=group_column
        )
    else:
        suite = EvaluationSuite.for_task(losses_lib.get(task).name)
        if group_column is not None:
            import dataclasses as _dc

            suite = _dc.replace(suite, group_column=group_column)
    evaluator = suite.primary_evaluator

    # Incremental training (SURVEY.md §5.4): a prior model fixes the feature
    # index maps — the data is read through them so coefficient vectors line
    # up column-for-column with the saved model.
    initial_model = None
    with tel.span("read", path=args.train_data):
        if args.initial_model:
            from photon_ml_tpu.io.game_store import load_game_model

            initial_model, initial_imaps = load_game_model(
                args.initial_model
            )
            shards, ids, response, weight, offset, _, index_maps = (
                read_game_avro(
                    args.train_data, index_maps=initial_imaps, logger=logger
                )
            )
            index_maps = initial_imaps
            logger.info("incremental training from %s", args.initial_model)
        else:
            shards, ids, response, weight, offset, _, index_maps = (
                read_game_avro(args.train_data)
            )
    logger.info(
        "read %d rows; shards: %s; id columns: %s",
        len(response),
        {k: v.shape for k, v in shards.items()},
        list(ids),
    )

    # Optional per-shard feature summaries (the reference writes feature
    # summary Avro artifacts — SURVEY.md §5.5).
    if config.get("feature_summaries", False):
        from photon_ml_tpu.data.stats import summarize_host
        from photon_ml_tpu.io.summary_store import save_feature_summary

        summary_dir = os.path.join(args.output_dir, "feature-summaries")
        os.makedirs(summary_dir, exist_ok=True)
        for shard_name, shard_matrix in shards.items():
            save_feature_summary(
                summarize_host(shard_matrix, weight),
                index_maps[shard_name],
                os.path.join(summary_dir, f"{shard_name}.avro"),
            )
        logger.info(
            "wrote feature summaries for %s", sorted(shards)
        )

    n_cd_iterations = int(config.get("iterations", 1))
    validation = None
    if args.validate_data:
        with tel.span("read", path=args.validate_data, validation=True):
            validation = read_game_avro(
                args.validate_data, index_maps=index_maps, logger=logger
            )

    result = {"task": task, "n_rows": int(len(response))}

    # Optional hyperparameter tuning over per-coordinate regularization
    # weights (the reference's BAYESIAN|RANDOM tuning mode inside
    # GameTrainingDriver — SURVEY.md §3.5).
    mesh = None
    if args.data_parallel == "auto":
        import jax

        if len(jax.devices()) > 1:
            from photon_ml_tpu.parallel.distributed import data_mesh

            mesh = data_mesh()
            logger.info(
                "data-parallel: %d-device mesh (rows + entity axis sharded)",
                len(jax.devices()),
            )

    locked = tuple(
        s.strip() for s in (args.locked_coordinates or "").split(",")
        if s.strip()
    )
    if locked and not args.initial_model:
        raise SystemExit("--locked-coordinates requires --initial-model")

    tuning = config.get("tuning")
    if tuning:
        if locked:
            # Tuning sweeps every coordinate's reg weight and refits all
            # of them per evaluation — a locked coordinate would be
            # silently retrained during the search, then locked only in
            # the final fit (inconsistent selection).
            raise SystemExit(
                "--locked-coordinates is incompatible with tuning mode"
            )
        if validation is None:
            raise ValueError("hyperparameter tuning requires --validate-data")
        import dataclasses as _dc

        from photon_ml_tpu.hyperparameter.search import (
            GaussianProcessSearch,
            RandomSearch,
        )

        names = list(coordinate_configs)
        lo, hi = tuning.get("range", [1e-3, 1e3])
        v_shards, v_ids, v_resp, v_weight, v_offset, _, _ = validation

        # Datasets and jitted solvers are built ONCE; each tuning point only
        # mutates reg_weight (a traced argument) — no recompiles, no
        # re-grouping/upload of random-effect shards.
        # Tuning evaluates by score metric only — never pay the
        # coefficient-variance finalize cost per tuning point.
        tuning_configs = {
            nm: _dc.replace(
                cfg,
                optimization=_dc.replace(
                    cfg.optimization, compute_variances=False
                ),
            )
            for nm, cfg in coordinate_configs.items()
        }
        tuning_est = GameEstimator(
            task, tuning_configs, n_cd_iterations, mesh=mesh,
            device_metrics=args.device_metrics,
        )
        tuning_coords = tuning_est.build_coordinates(
            shards, ids, response, weight, offset
        )

        v_groups = (
            np.asarray(v_ids[suite.group_column])
            if suite.group_column is not None
            else None
        )

        def evaluate(x):
            for coord, xi in zip(tuning_coords, x):
                coord.reg_weight = float(xi)
            mdl, _ = tuning_est.fit_coordinates(
                tuning_coords, response, weight, offset, evaluator
            )
            scores = GameTransformer(mdl).transform(v_shards, v_ids, v_offset)
            metric = evaluator.evaluate(
                scores, v_resp, v_weight, group_ids=v_groups
            )
            logger.info("tuning: reg=%s -> %.6f", list(map(float, x)), metric)
            return metric

        search_cls = (
            GaussianProcessSearch
            if tuning.get("mode", "bayesian") == "bayesian"
            else RandomSearch
        )
        search = search_cls([(lo, hi)] * len(names), log_scale=True, seed=0)
        with tel.span(
            "tuning", mode=tuning.get("mode", "bayesian"),
            iterations=int(tuning.get("iterations", 10)),
        ):
            found = search.find(
                evaluate,
                int(tuning.get("iterations", 10)),
                maximize=evaluator.larger_is_better,
            )
        coordinate_configs = {
            nm: _dc.replace(coordinate_configs[nm], reg_weight=float(xi))
            for nm, xi in zip(names, found.best_params)
        }
        config_grid = [coordinate_configs]  # tuning supersedes any grid
        result["tuning"] = {
            "best_reg_weights": dict(zip(names, map(float, found.best_params))),
            "best_metric": found.best_value,
            "n_evaluations": len(found.history),
        }
        logger.info("tuning selected %s", result["tuning"]["best_reg_weights"])

    val_tuple = None
    if validation is not None:
        v_shards, v_ids, v_resp, v_weight, v_offset, _, _ = validation
        val_tuple = (v_shards, v_ids, v_resp, v_weight, v_offset)

    # Checkpointing: per-CD-iteration for a single config, per-grid-point
    # for a config grid (a finished point's model persists; an interrupted
    # point re-fits, earlier points are skipped).
    checkpointer = None
    grid_checkpointer = None
    checkpoint_enabled = bool(config.get("checkpoint", True))
    if checkpoint_enabled:
        ckpt_dir = os.path.join(args.output_dir, "checkpoints")
        if len(config_grid) == 1:
            from photon_ml_tpu.io.checkpoint import (
                CoordinateDescentCheckpointer,
            )

            checkpointer = CoordinateDescentCheckpointer(ckpt_dir)
            if not args.resume:
                # A stale checkpoint from a previous job must not silently
                # hijack a fresh run.
                checkpointer.clear()
        else:
            from photon_ml_tpu.io.checkpoint import GameGridCheckpointer

            grid_checkpointer = GameGridCheckpointer(ckpt_dir, index_maps)
            if not args.resume:
                grid_checkpointer.clear()
    elif args.resume:
        raise ValueError(
            '--resume requires checkpointing ("checkpoint": false is set '
            "in the config JSON)"
        )

    estimator = GameEstimator(
        task, coordinate_configs, n_iterations=n_cd_iterations, logger=logger,
        mesh=mesh, device_metrics=args.device_metrics,
        pipeline=args.pipeline_coordinates,
    )
    from photon_ml_tpu.utils.watchdog import (
        RetryPolicy,
        RetryStats,
        run_with_retries,
    )

    retry_policy = RetryPolicy(
        max_retries=args.max_retries, backoff_seconds=args.retry_backoff
    )
    retry_stats = RetryStats()
    if len(config_grid) > 1:
        if locked:
            raise SystemExit(
                "--locked-coordinates is single-config only (a locked "
                "coordinate has nothing to sweep)"
            )
        # Config-grid fit with validation-driven selection (SURVEY.md §3.2).
        with tel.span(
            "train", grid_points=len(config_grid),
            cd_iterations=n_cd_iterations,
        ):
            model, grid_results = run_with_retries(
                lambda attempt: estimator.fit_grid(
                    config_grid, shards, ids, response, weight=weight,
                    offset=offset, validation=val_tuple, suite=suite,
                    initial_model=initial_model,
                    grid_checkpointer=grid_checkpointer,
                ),
                retry_policy, logger, stats=retry_stats,
            )
        best = next(r for r in grid_results if r["best"])
        history = best["history"]
        result["grid"] = [
            {
                "grid_index": r["grid_index"],
                "reg_weights": {
                    nm: cfg.reg_weight for nm, cfg in r["configs"].items()
                },
                "metric": r["metric"],
                "selected_by": r["selected_by"],
                "best": r["best"],
            }
            for r in grid_results
        ]
        logger.info(
            "config grid: %d points, best index %d (%s = %s)",
            len(grid_results), best["grid_index"], best["selected_by"],
            best["metric"],
        )
    else:
        # A retry resumes from the per-iteration CD checkpoint (the
        # CoordinateDescent loop reloads it on entry — SURVEY.md §5.3).
        with tel.span("train", cd_iterations=n_cd_iterations):
            model, history = run_with_retries(
                lambda attempt: estimator.fit(
                    shards, ids, response, weight=weight, offset=offset,
                    validation=val_tuple, suite=suite,
                    initial_model=initial_model, checkpointer=checkpointer,
                    locked_coordinates=locked,
                ),
                retry_policy, logger, stats=retry_stats,
            )
    result["history"] = history
    result["train_metric"] = history[-1].get("train_metric") if history else None
    if history and "validation" in history[-1]:
        result["per_iteration_validation"] = True
        result["validation_suite"] = history[-1]["validation"]

    if validation is not None:
        v_shards, v_ids, v_resp, v_weight, v_offset, _, _ = validation
        with tel.span("validate", rows=int(len(v_resp))):
            v_scores = GameTransformer(model).transform(
                v_shards, v_ids, v_offset
            )
            v_groups = (
                np.asarray(v_ids[suite.group_column])
                if suite.group_column is not None
                else None
            )
            result["validation_metric"] = evaluator.evaluate(
                v_scores, v_resp, v_weight, group_ids=v_groups
            )
        logger.info(
            "validation %s = %.6f",
            type(evaluator).__name__, result["validation_metric"],
        )

    with tel.span("write"):
        save_game_model(
            model, index_maps, os.path.join(args.output_dir, "models")
        )
    if retry_stats.retries or retry_stats.failures:
        result["retry"] = retry_stats.snapshot()
    # The training data is still placed here (the estimator holds its
    # coordinates), so this is the per-device footprint of the fit.
    result["runtime"] = runtime_block(
        clock, cache_dir, estimator.feature_layouts, bytes_in_use()
    )
    result["wall_seconds"] = timer.stop()
    with open(os.path.join(args.output_dir, "training_result.json"), "w") as f:
        json.dump(result, f, indent=2)
    tel.gauge("run_wall_seconds").set(result["wall_seconds"])
    logger.info("GAME training done in %.2fs", result["wall_seconds"])
    return result


def main() -> None:
    run(sys.argv[1:])


if __name__ == "__main__":
    main()
