"""GAME estimator / transformer: the programmatic API.

The analogue of the reference's spark.ml-style ``GameEstimator`` /
``GameTransformer`` (SURVEY.md §2, §3.4): ``fit`` builds per-coordinate
datasets from feature shards + entity-id columns, runs coordinate descent,
and returns a ``GameModel``; ``transform`` scores data with a trained model
(unseen entities contribute 0, as in the reference).

Reference call shape (SURVEY.md §3.2):
    GameEstimator.fit(trainData, validationData, coordinateConfigs)
Here the "DataFrame" is (shards, ids, response, weight, offset) host arrays:
``shards`` maps feature-shard name → scipy CSR (the reference's per-shard
feature bags), ``ids`` maps id-column name → per-row entity keys.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.dataset import make_glm_data
from photon_ml_tpu.evaluation.evaluators import Evaluator
from photon_ml_tpu.game.coordinates import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.data import (
    FixedEffectDataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.game.descent import CoordinateDescent
from photon_ml_tpu.game.model import (
    EntityTable,
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_ml_tpu.ops import losses as losses_lib
from photon_ml_tpu.optim.problem import GlmOptimizationConfig
from photon_ml_tpu.telemetry import layer_span


@dataclasses.dataclass(frozen=True)
class FixedEffectCoordinateConfig:
    """Reference: ``FixedEffectCoordinateConfiguration`` (incl. its
    down-sampling rate, applied to this coordinate's TRAINING loss only)."""

    feature_shard: str
    optimization: GlmOptimizationConfig = GlmOptimizationConfig()
    reg_weight: float = 0.0
    #: <1.0 down-samples training rows for this coordinate (negatives only
    #: for binary tasks, uniform otherwise), re-weighting survivors so the
    #: objective stays unbiased.  Scoring always covers every row: dropped
    #: rows get training weight 0, not removal, so shapes stay static.
    down_sampling_rate: float = 1.0
    #: >0 trains this coordinate OUT-OF-CORE: the shard lives in host RAM
    #: as chunks of this many rows, streamed through HBM per objective
    #: pass (game/streaming.py) — for fixed-effect datasets larger than
    #: device memory.  All three optimizers stream (L-BFGS, OWL-QN for
    #: L1/elastic-net, smooth TRON).
    streaming_chunk_rows: int = 0
    #: chunks the ingest pipeline keeps in flight when streaming (2 = the
    #: classic double buffer; the consumer additionally syncs a window of
    #: this many carries behind dispatch, so HBM holds ≤ 2× this many
    #: chunks).
    prefetch_depth: int = 2
    #: chunks folded per device dispatch via an in-program lax.scan when
    #: streaming (single-device only) — amortizes per-dispatch overhead
    #: for small chunks; 1 disables fusion.
    chunk_fuse: int = 1
    #: evaluate a bracket of line-search candidates per streamed pass
    #: (identical trial sequence, roughly half the passes per solve).
    batch_linesearch: bool = True
    #: compressed chunk wire format when streaming: off|lossless|fp16|
    #: int8 (data/staging.py).  Chunks cross the link encoded and are
    #: dequantized on device inside the per-chunk program; "lossless"
    #: keeps every solve bitwise identical to the raw stream.
    stream_compress: str = "off"
    #: >0 keeps up to this many MB of (wire) chunk buffers RESIDENT in
    #: HBM across streamed passes, admission/eviction re-scored each
    #: pass from per-chunk gradient contributions — hot chunks skip
    #: pack + transfer entirely (single-device only, bitwise neutral).
    stream_hot_budget_mb: float = 0.0


@dataclasses.dataclass(frozen=True)
class RandomEffectCoordinateConfig:
    """Reference: ``RandomEffectCoordinateConfiguration`` (entity id column +
    feature shard + optimization; ``max_rows_per_entity`` is the active-set
    cap of the reference's active/passive split)."""

    feature_shard: str
    entity_key: str
    optimization: GlmOptimizationConfig = GlmOptimizationConfig()
    reg_weight: float = 0.0
    max_rows_per_entity: Optional[int] = None
    #: geometric bucket grid for per-entity size bucketing (2.0 = pow2);
    #: larger values consolidate long tails into fewer compiled programs.
    bucket_growth: float = 2.0
    #: bucket-boundary policy (game/data.py): "geometric" keeps the
    #: classic growth ladder; "cost_model" runs the repacker —
    #: boundaries chosen from the entity size histogram to minimize
    #: padding FLOPs under the compiled-program budget (deterministic
    #: under repack_seed).
    repack: str = "geometric"
    #: max compiled per-bucket programs the repacker may spend.
    program_budget: int = 16
    #: tie-break seed for the repacker (results are a pure function of
    #: (histogram, budget, seed)).
    repack_seed: int = 0
    #: mesh placement threshold (game/hierarchical.py): a bucket whose
    #: solve cost is >= split_factor × the ideal per-device share is
    #: SPLIT over the mesh; smaller buckets pack whole onto devices by
    #: cost-balanced assignment.  Applies to the mesh resident path and
    #: the out-of-core path.
    split_factor: float = 0.5
    #: >0 trains this coordinate OUT-OF-CORE: entity blocks stay in host
    #: RAM and stream through HBM in double-buffered pass groups bounded
    #: by this many bytes (game/ooc_random.py) — for random-effect
    #: datasets larger than device memory.  Per-entity coefficients live
    #: host-resident between passes.  Composes with a mesh (the budget
    #: then bounds per-device bytes).
    device_budget_bytes: int = 0
    #: pass groups the ingest pipeline keeps in flight when out-of-core
    #: (each group sized to device_budget_bytes / prefetch_depth).
    prefetch_depth: int = 2
    #: >0 keeps up to this many MB of out-of-core pass groups' STATIC
    #: slice payloads resident across passes (the streamed fixed
    #: effect's hot working-set cache, generalized): hot groups skip
    #: host pack + h2d transfer and stream only warm starts /
    #: coefficients.  Bitwise neutral.
    hot_budget_mb: float = 0.0


@dataclasses.dataclass(frozen=True)
class FactoredRandomEffectCoordinateConfig:
    """Reference: ``FactoredRandomEffectCoordinateConfiguration`` — random
    effects constrained to a shared rank-``rank`` projection (w_e = V u_e,
    see game/factored.py).  Dataset shape is identical to a plain random
    effect, so grid points share built datasets with it."""

    feature_shard: str
    entity_key: str
    rank: int
    optimization: GlmOptimizationConfig = GlmOptimizationConfig()
    reg_weight: float = 0.0
    projection_reg_weight: Optional[float] = None
    alternations: int = 2
    max_rows_per_entity: Optional[int] = None
    bucket_growth: float = 2.0
    #: bucket-boundary policy + budget + seed — shared with the plain
    #: random-effect config (identical dataset shape, shared cache).
    repack: str = "geometric"
    program_budget: int = 16
    repack_seed: int = 0
    #: >0 trains this coordinate OUT-OF-CORE (game/ooc_factored.py):
    #: entity blocks stream in budget-bounded pass groups, latent vectors
    #: host-resident between passes, and the shared projection V fits by
    #: host-loop L-BFGS with one streamed pass per evaluation.
    device_budget_bytes: int = 0
    #: pass groups the ingest pipeline keeps in flight when out-of-core.
    prefetch_depth: int = 2


CoordinateConfig = (
    FixedEffectCoordinateConfig
    | RandomEffectCoordinateConfig
    | FactoredRandomEffectCoordinateConfig
)


class GameEstimator:
    """Reference: ``GameEstimator`` (SURVEY.md §3.4).

    ``coordinate_configs`` is an ORDERED name→config mapping; coordinate
    update order is the reference's ``coordinateUpdateSequence``.
    """

    def __init__(
        self,
        task: str,
        coordinate_configs: dict[str, CoordinateConfig],
        n_iterations: int = 1,
        logger=None,
        mesh=None,
        device_metrics: bool = False,
        pipeline: bool = False,
    ):
        """``mesh``: a ``jax.sharding.Mesh`` with a ``"data"`` axis enables
        the multi-chip path — rows sharded for fixed effects (whole solver
        inside shard_map, one fused psum per objective evaluation) and the
        entity axis sharded for random effects (the reference's Spark
        executor-parallel layout — SURVEY.md §2 parallelism table).

        ``device_metrics``: per-update train/validation metrics compute ON
        DEVICE (evaluation/device.py) — score arrays never cross to host,
        only metric scalars do (the 1B-row validation contract; the
        reference computes metrics where the data lives).  Requires an
        ungrouped suite; evaluators with no device implementation fall
        back to one host pullback.

        ``pipeline``: overlap coordinate updates' offset-independent
        host work — while one coordinate solves, the NEXT one prestages
        its first pass groups (game/descent.py).  Results are bitwise
        identical to the serial schedule."""
        self.task = losses_lib.get(task).name  # canonicalize aliases
        self.coordinate_configs = dict(coordinate_configs)
        self.device_metrics = device_metrics
        self.n_iterations = n_iterations
        self.logger = logger
        self.mesh = mesh
        self.pipeline = bool(pipeline)
        #: coordinate name → feature layout of the most recent fit (see
        #: ``Coordinate.feature_layout``); the drivers report it.
        self.feature_layouts: dict[str, str] = {}

    def build_coordinates(self, shards, ids, response, weight=None, offset=None):
        """Build per-coordinate datasets + coordinate objects once.  Tuning
        loops reuse them across evaluations (mutating ``coord.reg_weight``,
        a traced argument — no recompilation, no dataset rebuild)."""
        return self._build_coordinates(
            self.coordinate_configs, shards, ids, response, weight, offset
        )

    def _build_coordinates(self, coordinate_configs, shards, *args, **kw):
        """One ``game.build`` layer span around the datasets' builds: its
        children are each fixed effect's ``data.make_glm_data`` and each
        random effect's ``game.group`` and ``game.place``."""
        with layer_span(
            "game.build", coordinates=list(coordinate_configs)
        ):
            return self._build_datasets(
                coordinate_configs, shards, *args, **kw)

    @staticmethod
    def dataset_key(cfg: "CoordinateConfig") -> tuple:
        """Cache key identifying the DATASET a config needs — grid points
        differing only in optimizer/regularization share built datasets (the
        reference builds per-coordinate datasets once, outside the config
        grid — SURVEY.md §3.2)."""
        if isinstance(cfg, FixedEffectCoordinateConfig):
            return (
                "fixed", cfg.feature_shard, cfg.down_sampling_rate,
                cfg.streaming_chunk_rows,
            )
        # Plain and factored random effects need the SAME dataset shape,
        # so they share cache entries deliberately.  The repack knobs
        # change the realized block layout, so they are part of the
        # dataset's identity.
        return (
            "random",
            cfg.feature_shard,
            cfg.entity_key,
            cfg.max_rows_per_entity,
            cfg.bucket_growth,
            cfg.repack,
            cfg.program_budget,
            cfg.repack_seed,
        )

    def _build_datasets(
        self,
        coordinate_configs,
        shards,
        ids,
        response,
        weight,
        offset,
        dataset_cache: Optional[dict] = None,
    ):
        n = len(response)
        weight = np.ones(n, np.float32) if weight is None else np.asarray(weight, np.float32)
        cache = {} if dataset_cache is None else dataset_cache
        coordinates = []
        for name, cfg in coordinate_configs.items():
            shard = shards[cfg.feature_shard]
            key = self.dataset_key(cfg)
            if isinstance(cfg, FixedEffectCoordinateConfig):
                def train_weight(cfg=cfg):
                    # Down-sampling runs ONLY on cache miss — grid/tuning
                    # points hitting the cache never pay the O(n) pass.
                    if cfg.down_sampling_rate >= 1.0:
                        return weight
                    from photon_ml_tpu.data.sampling import (
                        BinaryClassificationDownSampler,
                        DefaultDownSampler,
                    )

                    binary = self.task in ("logistic", "smoothed_hinge")
                    sampler = (
                        BinaryClassificationDownSampler(cfg.down_sampling_rate)
                        if binary
                        else DefaultDownSampler(cfg.down_sampling_rate)
                    )
                    idx, w_kept = sampler.downsample(response, weight)
                    tw = np.zeros(n, np.float32)
                    tw[idx] = w_kept
                    return tw

                if cfg.streaming_chunk_rows > 0:
                    from photon_ml_tpu.data.streaming import (
                        make_streaming_glm_data,
                    )
                    from photon_ml_tpu.game.streaming import (
                        StreamingFixedEffectCoordinate,
                    )

                    stream = cache.get(key)
                    if stream is None:
                        # With a mesh, chunks are built pre-sharded (one
                        # row block per device) and each objective pass
                        # runs under shard_map with one fused psum —
                        # streamed DP composed with the rest of the
                        # descent (BASELINE config 5's shape: streaming
                        # AND multi-device AND GAME at once).
                        stream = make_streaming_glm_data(
                            shard, response, weights=train_weight(),
                            chunk_rows=cfg.streaming_chunk_rows,
                            n_shards=(
                                1 if self.mesh is None
                                else self.mesh.devices.size
                            ),
                        )
                        cache[key] = stream
                    coordinates.append(StreamingFixedEffectCoordinate(
                        name, stream, self.task, cfg.optimization,
                        cfg.reg_weight, feature_shard=cfg.feature_shard,
                        mesh=self.mesh,
                        prefetch_depth=cfg.prefetch_depth,
                        chunk_fuse=cfg.chunk_fuse,
                        batch_linesearch=cfg.batch_linesearch,
                        compress=cfg.stream_compress,
                        hot_budget_bytes=int(
                            cfg.stream_hot_budget_mb * 1e6
                        ),
                    ))
                    continue
                if self.mesh is not None:
                    coordinates.append(
                        self._distributed_fixed(
                            name, cfg, shard, response, train_weight,
                            cache, key,
                        )
                    )
                    continue
                dataset = cache.get(key)
                if dataset is None:
                    data = make_glm_data(
                        shard, response, weights=train_weight(),
                    )
                    dataset = FixedEffectDataset(data=data, n_global_rows=n)
                    cache[key] = dataset
                coordinates.append(
                    FixedEffectCoordinate(
                        name,
                        dataset,
                        self.task,
                        cfg.optimization,
                        cfg.reg_weight,
                        feature_shard=cfg.feature_shard,
                    )
                )
            else:
                factored = isinstance(cfg, FactoredRandomEffectCoordinateConfig)
                if cfg.device_budget_bytes > 0:
                    # Host-resident dataset, cached separately from the
                    # device-resident one the resident path builds.
                    ooc_key = ("ooc_ds",) + key
                    dataset = cache.get(ooc_key)
                    if dataset is None:
                        dataset = build_random_effect_dataset(
                            ids[cfg.entity_key],
                            shard,
                            np.asarray(response, np.float32),
                            weight,
                            max_rows_per_entity=cfg.max_rows_per_entity,
                            bucket_growth=cfg.bucket_growth,
                            repack=cfg.repack,
                            program_budget=cfg.program_budget,
                            repack_seed=cfg.repack_seed,
                            name=name,
                            device=False,
                        )
                        cache[ooc_key] = dataset
                    if factored:
                        from photon_ml_tpu.game.ooc_factored import (
                            OutOfCoreFactoredRandomEffectCoordinate,
                        )

                        coordinates.append(
                            OutOfCoreFactoredRandomEffectCoordinate(
                                name, dataset, self.task, cfg.optimization,
                                rank=cfg.rank, reg_weight=cfg.reg_weight,
                                projection_reg_weight=(
                                    cfg.projection_reg_weight
                                ),
                                alternations=cfg.alternations,
                                feature_shard=cfg.feature_shard,
                                entity_key=cfg.entity_key,
                                device_budget_bytes=cfg.device_budget_bytes,
                                mesh=self.mesh,
                                prefetch_depth=cfg.prefetch_depth,
                            )
                        )
                        continue
                    from photon_ml_tpu.game.ooc_random import (
                        OutOfCoreRandomEffectCoordinate,
                    )

                    coordinates.append(OutOfCoreRandomEffectCoordinate(
                        name, dataset, self.task, cfg.optimization,
                        cfg.reg_weight, feature_shard=cfg.feature_shard,
                        entity_key=cfg.entity_key,
                        device_budget_bytes=cfg.device_budget_bytes,
                        mesh=self.mesh,
                        prefetch_depth=cfg.prefetch_depth,
                        split_factor=cfg.split_factor,
                        hot_budget_bytes=int(cfg.hot_budget_mb * 1e6),
                    ))
                    continue
                if self.mesh is not None:
                    coordinates.append(
                        self._distributed_random(
                            name, cfg, shard, ids, response, weight,
                            cache, key, factored=factored,
                        )
                    )
                    continue
                dataset = cache.get(key)
                if dataset is None:
                    dataset = build_random_effect_dataset(
                        ids[cfg.entity_key],
                        shard,
                        np.asarray(response, np.float32),
                        weight,
                        max_rows_per_entity=cfg.max_rows_per_entity,
                        bucket_growth=cfg.bucket_growth,
                        repack=cfg.repack,
                        program_budget=cfg.program_budget,
                        repack_seed=cfg.repack_seed,
                        name=name,
                    )
                    cache[key] = dataset
                if factored:
                    from photon_ml_tpu.game.factored import (
                        FactoredRandomEffectCoordinate,
                    )

                    coordinates.append(
                        FactoredRandomEffectCoordinate(
                            name,
                            dataset,
                            self.task,
                            cfg.optimization,
                            rank=cfg.rank,
                            reg_weight=cfg.reg_weight,
                            projection_reg_weight=cfg.projection_reg_weight,
                            alternations=cfg.alternations,
                            feature_shard=cfg.feature_shard,
                            entity_key=cfg.entity_key,
                        )
                    )
                    continue
                coordinates.append(
                    RandomEffectCoordinate(
                        name,
                        dataset,
                        self.task,
                        cfg.optimization,
                        cfg.reg_weight,
                        feature_shard=cfg.feature_shard,
                        entity_key=cfg.entity_key,
                    )
                )
        return coordinates

    def _distributed_fixed(
        self, name, cfg, shard, response, train_weight_fn, cache, key
    ):
        """Row-sharded fixed effect (mesh path).  Grid points sharing the
        dataset AND optimizer config reuse the sharded data and compiled
        shard_map programs via a shallow copy (reg_weight is traced)."""
        import copy

        from photon_ml_tpu.game.distributed import (
            DistributedFixedEffectCoordinate,
        )

        cache_key = ("dist",) + key
        cached = cache.get(cache_key)
        if cached is not None and cached[0] == cfg.optimization:
            coord = copy.copy(cached[1])
            coord.name = name
            coord.reg_weight = cfg.reg_weight
            return coord
        # The sharded dataset is cached independently of the optimizer
        # config (same pattern as _distributed_random): a config change
        # re-jits but never re-shards/re-uploads the matrix.
        ds_key = ("dist_ds",) + key
        dist = cache.get(ds_key)
        coord = DistributedFixedEffectCoordinate(
            name, shard, np.asarray(response, np.float32), self.mesh,
            self.task, cfg.optimization, cfg.reg_weight,
            feature_shard=cfg.feature_shard,
            # weights (incl. the O(n) down-sampling pass) only matter when
            # the sharded dataset is actually (re)built.
            weights=None if dist is not None else train_weight_fn(),
            dist=dist,
        )
        cache[ds_key] = coord.dist
        cache[cache_key] = (cfg.optimization, coord)
        return coord

    def _distributed_random(
        self, name, cfg, shard, ids, response, weight, cache, key,
        factored: bool = False,
    ):
        """Mesh-sharded random effect — plain or factored; same reuse
        rules as :meth:`_distributed_fixed`.  The plain path routes to
        the hierarchical bucket-ladder coordinate (game/hierarchical.py):
        big buckets split over the mesh, the long tail packs whole onto
        devices.  The factored path keeps the legacy everything-split
        layout (its projection accumulator cannot commit to devices)."""
        import copy

        from photon_ml_tpu.game.distributed import (
            entity_sharded_factored_coordinate,
        )
        from photon_ml_tpu.game.hierarchical import (
            ShardedBucketRandomEffectCoordinate,
        )

        cfg_sig = (
            (cfg.optimization, cfg.rank, cfg.alternations)
            if factored else (cfg.optimization, cfg.split_factor)
        )
        cache_key = ("dist", factored) + key
        cached = cache.get(cache_key)
        if cached is not None and cached[0] == cfg_sig:
            coord = copy.copy(cached[1])
            coord.name = name
            coord.reg_weight = cfg.reg_weight
            if factored:
                coord.projection_reg_weight = (
                    cfg.reg_weight
                    if cfg.projection_reg_weight is None
                    else cfg.projection_reg_weight
                )
            return coord
        # The expensive entity re-grouping is cached independently of the
        # optimizer config; a config change only re-places blocks on the
        # mesh.
        ds_key = ("dist_ds",) + key
        dataset = cache.get(ds_key)
        if dataset is None:
            dataset = build_random_effect_dataset(
                ids[cfg.entity_key],
                shard,
                np.asarray(response, np.float32),
                np.asarray(weight, np.float32),
                max_rows_per_entity=cfg.max_rows_per_entity,
                bucket_growth=cfg.bucket_growth,
                repack=cfg.repack,
                program_budget=cfg.program_budget,
                repack_seed=cfg.repack_seed,
                name=name,
                device=False,  # the coordinate places blocks on the mesh
            )
            cache[ds_key] = dataset
        if factored:
            coord = entity_sharded_factored_coordinate(
                name, dataset, self.mesh, self.task, cfg.optimization,
                rank=cfg.rank, reg_weight=cfg.reg_weight,
                projection_reg_weight=cfg.projection_reg_weight,
                alternations=cfg.alternations,
                feature_shard=cfg.feature_shard,
                entity_key=cfg.entity_key,
            )
        else:
            coord = ShardedBucketRandomEffectCoordinate(
                name, dataset, self.mesh, self.task, cfg.optimization,
                cfg.reg_weight, feature_shard=cfg.feature_shard,
                entity_key=cfg.entity_key,
                split_factor=cfg.split_factor,
            )
        cache[cache_key] = (cfg_sig, coord)
        return coord

    def fit(
        self,
        shards: dict,
        ids: dict,
        response: np.ndarray,
        weight: Optional[np.ndarray] = None,
        offset: Optional[np.ndarray] = None,
        evaluator: Optional[Evaluator] = None,
        validation=None,
        suite=None,
        initial_model: Optional[GameModel] = None,
        checkpointer=None,
        locked_coordinates: Sequence[str] = (),
    ) -> tuple[GameModel, list]:
        """Train; returns (model, per-coordinate-update history).

        ``validation`` is ``(shards, ids, response[, weight[, offset]])``;
        with it, every history entry carries the full validation
        ``EvaluationSuite`` after that coordinate update (the reference's
        per-iteration validation tracking — SURVEY.md §3.2).

        ``initial_model`` warm-starts coordinate descent from a previously
        trained GameModel (the reference's incremental training);
        ``locked_coordinates`` holds named coordinates at that model
        instead of retraining them (the reference's partial retraining);
        ``checkpointer`` enables per-iteration checkpoint + resume (see
        game/descent.py)."""
        coordinates = self._build_coordinates(
            self.coordinate_configs, shards, ids, response, weight, offset
        )
        train_groups = None
        if suite is not None and suite.group_column is not None:
            train_groups = np.asarray(ids[suite.group_column])
        return self.fit_coordinates(
            coordinates, response, weight, offset, evaluator,
            validation=validation, suite=suite,
            initial_model=initial_model, checkpointer=checkpointer,
            train_group_ids=train_groups,
            locked_coordinates=locked_coordinates,
        )

    @staticmethod
    def initial_states_from_model(
        coordinates, model: GameModel
    ) -> dict:
        """Project a saved GameModel onto pre-built coordinates' state
        layout: fixed effects take the coefficient vector directly; random
        effects materialize each bucket's (E, D) local-space matrix from the
        entity→sparse-coefficient table.  Coordinates absent from the model
        start from zero (state None).

        The datasets MUST have been built from data read with the saved
        model's index maps — stored coefficients are matched by global
        column id, so a different index map silently means different
        features.  Width mismatches are caught; same-width re-orderings
        cannot be (exactly as in the reference, where incremental training
        requires the prior run's feature index maps)."""
        states: dict = {}
        for c in coordinates:
            sub = model.models.get(c.name)
            if sub is None:
                continue
            if isinstance(sub, FixedEffectModel):
                w = np.asarray(sub.model.coefficients.means, np.float32)
                # Distributed fixed coordinates have no .dataset; both
                # expose the feature width.
                width = (
                    c.n_features
                    if hasattr(c, "n_features")
                    else c.dataset.data.n_features
                )
                if w.shape[0] != width:
                    raise ValueError(
                        f"initial model coordinate {c.name!r} has "
                        f"{w.shape[0]} features but the dataset has "
                        f"{width}; read the data with the initial model's "
                        "index maps"
                    )
                states[c.name] = jnp.asarray(w)
            elif isinstance(sub, RandomEffectModel):
                from photon_ml_tpu.game.factored import (
                    FactoredRandomEffectCoordinate,
                )

                if isinstance(c, FactoredRandomEffectCoordinate):
                    # A factored coordinate's state is (u_list, V); the
                    # saved model stores only the materialized w_e = V u_e,
                    # and the factorization is not recoverable from it.
                    # Start this coordinate cold (the reference's factored
                    # coordinates likewise don't warm-start from plain
                    # random-effect models).
                    continue
                if sub.n_features != c.dataset.n_features:
                    raise ValueError(
                        f"initial model coordinate {c.name!r} has "
                        f"{sub.n_features} features but the dataset has "
                        f"{c.dataset.n_features}; read the data with the "
                        "initial model's index maps"
                    )
                blocks_states = []
                for block, ids in zip(c.dataset.blocks, c.dataset.entity_ids):
                    cmap = np.asarray(block.col_map)
                    # Entity-sharded blocks are mesh-padded beyond the real
                    # lanes; padding lanes warm-start at zero.
                    mat = np.zeros(cmap.shape, np.float32)
                    mat[: len(ids)] = sub.coefficient_matrix_for(
                        cmap[: len(ids)], ids
                    )
                    blocks_states.append(jnp.asarray(mat))
                states[c.name] = blocks_states
        return states

    def fit_coordinates(
        self,
        coordinates,
        response,
        weight=None,
        offset=None,
        evaluator: Optional[Evaluator] = None,
        validation=None,
        suite=None,
        validation_scorers: Optional[dict] = None,
        initial_model: Optional[GameModel] = None,
        checkpointer=None,
        train_group_ids=None,
        locked_coordinates: Sequence[str] = (),
    ) -> tuple[GameModel, list]:
        """Run coordinate descent over pre-built coordinates (see
        :meth:`build_coordinates`) and finalize the GameModel.

        ``validation_scorers`` (name → scorer, see game/validation.py) lets
        grid/tuning loops reuse scorers built once per shared dataset.

        ``locked_coordinates`` (partial retraining, the reference's locked
        coordinate list): each named coordinate takes its coefficients from
        ``initial_model`` and is never retrained — its scores still enter
        every other coordinate's offsets, and its sub-model is carried into
        the returned GameModel unchanged."""
        from photon_ml_tpu.evaluation.suite import EvaluationSuite

        self.feature_layouts = {c.name: c.feature_layout for c in coordinates}
        if self.logger is not None:
            self.logger.info("feature layouts: %s", self.feature_layouts)
        n = len(response)
        response = np.asarray(response, np.float32)
        base_offsets = (
            np.zeros(n, np.float32) if offset is None else np.asarray(offset, np.float32)
        )
        if suite is None:
            suite = (
                EvaluationSuite.from_specs([evaluator])
                if evaluator is not None
                else EvaluationSuite.for_task(self.task)
            )
        primary = suite.primary_evaluator
        w_host = None if weight is None else np.asarray(weight, np.float32)

        val_ctx = None
        if validation is not None:
            v_shards, v_ids, v_resp = validation[0], validation[1], validation[2]
            v_weight = validation[3] if len(validation) > 3 else None
            v_offset = validation[4] if len(validation) > 4 else None
            scorers = validation_scorers or {
                c.name: c.make_validation_scorer(v_shards, v_ids)
                for c in coordinates
            }
            n_val = len(v_resp)
            # Per-group evaluation (per-query AUC / precision@k): the
            # suite's group column names an id column of the validation set.
            v_groups = None
            if suite.group_column is not None:
                v_groups = np.asarray(v_ids[suite.group_column])
            val_ctx = {
                "scorers": scorers,
                "resp": np.asarray(v_resp, np.float32),
                "groups": v_groups,
                "weight": None if v_weight is None else np.asarray(v_weight, np.float32),
                "base": (
                    np.zeros(n_val, np.float32)
                    if v_offset is None
                    else np.asarray(v_offset, np.float32)
                ),
                # Per-coordinate validation scores, refreshed incrementally:
                # only the just-updated coordinate re-scores each step.
                "scores": {
                    c.name: np.zeros(n_val, np.float32) for c in coordinates
                },
            }

        primed = [False]  # becomes True once every live state has scored

        device_metrics = self.device_metrics
        if device_metrics and (
            suite.group_column is not None or train_group_ids is not None
        ):
            raise ValueError(
                "device_metrics computes GLOBAL metrics; grouped "
                "evaluation (suite group_column="
                f"{suite.group_column!r} / explicit train_group_ids) is "
                "host-side"
            )
        if device_metrics:
            from photon_ml_tpu.evaluation.device import device_evaluator_fn

            # Labels/weights/offsets go to device ONCE; every per-update
            # evaluation then stays device-side and pulls back scalars
            # only — no O(n_rows) transfer per coordinate update.
            resp_dev = jnp.asarray(response)
            w_dev = None if w_host is None else jnp.asarray(w_host)
            base_dev = jnp.asarray(base_offsets)
            primary_dev = device_evaluator_fn(primary)
            if val_ctx is not None:
                val_ctx["resp_dev"] = jnp.asarray(val_ctx["resp"])
                val_ctx["weight_dev"] = (
                    None if val_ctx["weight"] is None
                    else jnp.asarray(val_ctx["weight"])
                )
                val_ctx["base_dev"] = jnp.asarray(val_ctx["base"])
                val_ctx["scores"] = {
                    c.name: jnp.zeros(n_val, jnp.float32)
                    for c in coordinates
                }

        def eval_fn(it, cname, scores, states):
            if device_metrics:
                # CD scores are already device arrays — sum them there.
                # Device metrics stay 0-d DEVICE scalars in the entry:
                # the CD history flush materializes them in its one
                # batched readback (game/descent.py), so an evaluated
                # update costs no extra host round trip here.
                total = base_dev + sum(scores.values())
                train_metric = (
                    primary_dev(total, resp_dev, w_dev)
                    if primary_dev is not None
                    else primary.evaluate(
                        np.asarray(total), response, w_host
                    )
                )
            else:
                total = base_offsets + np.sum(
                    [np.asarray(s) for s in scores.values()], axis=0
                )
                # With a grouped suite, the train metric is grouped too
                # (else history entries would mix global and per-group
                # semantics); a per-group-only primary without train group
                # ids records None rather than crashing training.
                if suite.group_column is not None and train_group_ids is None:
                    train_metric = None
                else:
                    train_metric = primary.evaluate(
                        total, response, w_host, group_ids=train_group_ids
                    )
            entry = {
                "train_metric": train_metric,
                "evaluator": type(primary).__name__,
            }
            if val_ctx is not None:
                keep = (
                    (lambda a: jnp.asarray(a)) if device_metrics
                    else (lambda a: np.asarray(a))
                )
                if not primed[0]:
                    # First evaluation: warm starts / resumed runs carry
                    # live states for coordinates that haven't updated yet
                    # this run — score them all once.
                    for c in coordinates:
                        if states[c.name] is not None:
                            val_ctx["scores"][c.name] = keep(
                                val_ctx["scorers"][c.name].score(
                                    states[c.name]
                                )
                            )
                    primed[0] = True
                else:
                    val_ctx["scores"][cname] = keep(
                        val_ctx["scorers"][cname].score(states[cname])
                    )
                if device_metrics:
                    v_total = val_ctx["base_dev"] + sum(
                        val_ctx["scores"].values()
                    )
                    metrics = suite.evaluate_device(
                        v_total, val_ctx["resp_dev"], val_ctx["weight_dev"],
                        materialize=False,
                    )
                else:
                    v_total = val_ctx["base"] + np.sum(
                        list(val_ctx["scores"].values()), axis=0
                    )
                    metrics = suite.evaluate(
                        v_total, val_ctx["resp"], val_ctx["weight"],
                        group_ids=val_ctx["groups"],
                    )
                entry["validation"] = metrics
                entry["validation_metric"] = metrics[suite.primary]
            return entry

        locked = tuple(locked_coordinates)
        if locked and initial_model is None:
            raise ValueError(
                "locked_coordinates requires initial_model (partial "
                "retraining holds those coordinates at the prior model)"
            )
        if locked:
            missing = [
                n_ for n_ in locked if n_ not in (initial_model.models or {})
            ]
            if missing:
                raise ValueError(
                    f"locked coordinates {missing} are not in the initial "
                    "model"
                )
        initial_states = (
            self.initial_states_from_model(coordinates, initial_model)
            if initial_model is not None
            else None
        )
        unlockable = [
            n_ for n_ in locked
            if initial_states is None or initial_states.get(n_) is None
        ]
        if unlockable:
            # Accurate up-front rejection: a factored coordinate's saved
            # sub-model holds materialized w_e only, so its (u, V) device
            # state is not reconstructible — descent's generic "supply a
            # prior model" message would gaslight a user who already did.
            raise ValueError(
                f"coordinates {unlockable} cannot be locked: their prior "
                "state is not reconstructible from the initial model "
                "(factored coordinates save materialized coefficients "
                "only)"
            )
        cd = CoordinateDescent(coordinates, pipeline=self.pipeline)
        result = cd.run(
            jnp.asarray(base_offsets),
            n_iterations=self.n_iterations,
            eval_fn=eval_fn,
            logger=self.logger,
            checkpointer=checkpointer,
            initial_states=initial_states,
            locked=locked,
        )
        # Finalize with each coordinate's residual offsets (base + the
        # OTHER coordinates' scores) so coefficient variances — when a
        # coordinate's config asks for them — are evaluated at the full
        # final margins.  Skipped entirely (no device readbacks) when no
        # coordinate wants variances.
        def wants_variances(c):
            cfg = getattr(c, "config", None) or getattr(
                getattr(c, "problem", None), "config", None
            )
            return bool(cfg is not None and cfg.compute_variances)

        total_np = None
        if any(wants_variances(c) for c in coordinates):
            total_np = base_offsets + np.sum(
                [np.asarray(s) for s in result.scores.values()], axis=0
            )
        models = {}
        for c in coordinates:
            if c.name in locked:
                # Partial retraining: the locked sub-model passes through
                # VERBATIM (re-deriving it from the reconstructed device
                # state would drop variances and any stored detail).
                models[c.name] = initial_model.models[c.name]
                continue
            off_c = (
                total_np - np.asarray(result.scores[c.name])
                if total_np is not None
                else None
            )
            # A layer span (docs/telemetry.md): the host works alone here,
            # after the flush's read and before the next fit's first
            # program.
            with layer_span(
                "coordinate.finalize", coordinate=c.name, kind=c.kind
            ) as span:
                sub = c.finalize(result.states[c.name], offsets=off_c)
                if isinstance(sub, RandomEffectModel):
                    span.set(
                        entities=sub.n_entities,
                        table="arrays"
                        if isinstance(sub.coefficients, EntityTable)
                        else "dict",
                    )
            models[c.name] = sub
        return GameModel(models=models, task=self.task), result.history

    def fit_grid(
        self,
        grid_configs: Sequence[dict],
        shards: dict,
        ids: dict,
        response: np.ndarray,
        weight: Optional[np.ndarray] = None,
        offset: Optional[np.ndarray] = None,
        validation=None,
        suite=None,
        initial_model: Optional[GameModel] = None,
        grid_checkpointer=None,
    ) -> tuple[GameModel, list[dict]]:
        """Fit EVERY coordinate-config combination, select best (SURVEY.md
        §3.2: "for each coordinate-config combination ... select best model
        by validation metric").

        ``grid_configs`` is a list of name→config mappings (one grid point
        each, same coordinate names).  Datasets and validation scorers are
        built once per distinct :meth:`dataset_key` and shared across
        points.  Selection: final validation primary metric when
        ``validation`` is given, else final train metric.  Returns
        ``(best_model, point_results)`` where each point result dict carries
        ``configs / model / history / metric``.

        ``grid_checkpointer`` (io.checkpoint.GameGridCheckpointer):
        completed points persist as saved models and are SKIPPED on
        re-entry (retry / --resume), so an interrupted grid resumes at the
        completed-point boundary instead of restarting.
        """
        from photon_ml_tpu.evaluation.suite import EvaluationSuite

        if not grid_configs:
            raise ValueError("empty coordinate-config grid")
        if suite is None:
            suite = EvaluationSuite.for_task(self.task)
        dataset_cache: dict = {}
        scorer_cache: dict = {}
        results: list[dict] = []
        best_idx, best_metric = None, None
        metric_key = (
            "validation_metric" if validation is not None else "train_metric"
        )
        for gi, configs in enumerate(grid_configs):
            loaded = (
                grid_checkpointer.load_point(gi, configs, metric_key)
                if grid_checkpointer is not None else None
            )
            if loaded is not None:
                model, metric, history = loaded
                results.append({
                    "grid_index": gi,
                    "configs": configs,
                    "model": model,
                    "history": history,
                    "metric": metric,
                    "selected_by": metric_key,
                    "resumed": True,
                })
                if best_idx is None or suite.better_than(metric, best_metric):
                    best_idx, best_metric = gi, metric
                if self.logger is not None:
                    self.logger.info(
                        "grid point %d/%d resumed from checkpoint "
                        "(%s = %s)",
                        gi + 1, len(grid_configs), metric_key, metric,
                    )
                continue
            coordinates = self._build_coordinates(
                configs, shards, ids, response, weight, offset,
                dataset_cache=dataset_cache,
            )
            scorers = None
            if validation is not None:
                scorers = {}
                for name, cfg in configs.items():
                    # Fixed-effect scorers depend only on the feature shard
                    # (not on down-sampling, which is train-side only).
                    # Random-effect scorer keys carry the config TYPE:
                    # factored and plain share dataset_key (same dataset)
                    # but their scorers consume different state shapes.
                    key = (
                        ("fixed_scorer", cfg.feature_shard)
                        if isinstance(cfg, FixedEffectCoordinateConfig)
                        else (type(cfg).__name__,) + self.dataset_key(cfg)
                    )
                    if key not in scorer_cache:
                        coord = next(c for c in coordinates if c.name == name)
                        scorer_cache[key] = coord.make_validation_scorer(
                            validation[0], validation[1]
                        )
                    scorers[name] = scorer_cache[key]
            train_groups = None
            if suite.group_column is not None:
                train_groups = np.asarray(ids[suite.group_column])
            model, history = self.fit_coordinates(
                coordinates, response, weight, offset,
                validation=validation, suite=suite,
                validation_scorers=scorers, initial_model=initial_model,
                train_group_ids=train_groups,
            )
            metric = history[-1].get(metric_key) if history else None
            if grid_checkpointer is not None:
                grid_checkpointer.save_point(
                    gi, configs, model, metric, metric_key, history
                )
            results.append(
                {
                    "grid_index": gi,
                    "configs": configs,
                    "model": model,
                    "history": history,
                    "metric": metric,
                    "selected_by": metric_key,
                }
            )
            if best_idx is None or suite.better_than(metric, best_metric):
                best_idx, best_metric = gi, metric
            if self.logger is not None:
                self.logger.info(
                    "grid point %d/%d: %s = %s",
                    gi + 1, len(grid_configs), metric_key, metric,
                )
        for r in results:
            r["best"] = r["grid_index"] == best_idx
        return results[best_idx]["model"], results


@dataclasses.dataclass
class PreparedScoringSet:
    """Grouped block structures for scoring ONE dataset many times.

    Building the per-entity block grouping is the dominant host cost of
    random-effect scoring; ``GameTransformer.prepare`` pays it once and
    every subsequent ``transform`` over the same data reuses it (the
    reference persists its joined scoring RDDs the same way)."""

    n_rows: int
    re_datasets: dict  # coordinate name -> host-side RandomEffectDataset


class GameTransformer:
    """Reference: ``GameTransformer`` — batch scoring with a GameModel
    (SURVEY.md §3.3): fixed effect = one matvec; each random effect = block
    gather of per-entity coefficients; total = sum + offset.

    The scoring math itself lives in ``serving/kernels.py`` — ONE
    implementation shared with the online serving runtime, so batch jobs
    (``game_scoring_driver``) and the request path score through the same
    fixed-effect matvec + random-effect gather + offset sum.

    Scoring is pure host compute (scipy matvec + packed-table gathers):
    uploading scoring shards to the accelerator just to pull scores back
    would waste PCIe/HBM.  Repeated calls on the SAME (shards, ids) objects
    reuse the entity grouping automatically; for explicit control, call
    :meth:`prepare` once and pass ``prepared=`` to every transform."""

    def __init__(self, model: GameModel, logger=None):
        self.model = model
        self.logger = logger
        # (value-identity key, [weakrefs to source arrays], prepared); the
        # weakref callbacks clear the slot when any source array dies, so a
        # long-lived transformer never pins a dead scoring set's blocks.
        self._cache: Optional[tuple] = None

    def prepare(self, shards: dict, ids: dict) -> PreparedScoringSet:
        """Group scoring rows by entity for every random-effect coordinate
        (build once, score many times)."""
        n = next(iter(shards.values())).shape[0]
        re_datasets = {}
        for name, sub in self.model.models.items():
            if isinstance(sub, RandomEffectModel):
                # A file with NO rows carrying this id column yields no
                # ids entry at all — same join-miss semantics as rows
                # individually missing it: zero contribution, not a crash.
                entity_col = ids.get(sub.entity_key)
                if entity_col is None:
                    entity_col = np.full(n, None, object)
                re_datasets[name] = build_random_effect_dataset(
                    np.asarray(entity_col),
                    shards[sub.feature_shard],
                    np.zeros(n, np.float32),
                    np.ones(n, np.float32),
                    device=False,
                    # Scoring join semantics: rows without this entity id
                    # get zero contribution, they are not a data error.
                    allow_missing=True,
                )
        return PreparedScoringSet(n_rows=n, re_datasets=re_datasets)

    @staticmethod
    def _cache_key(shards: dict, ids: dict) -> tuple:
        """Identity of the VALUE objects (not the dicts): replacing a matrix
        or id column inside the same dict objects must miss the cache."""
        return (
            tuple(sorted((name, id(m)) for name, m in shards.items())),
            tuple(sorted((name, id(a)) for name, a in ids.items())),
        )

    def _prepared_for(self, shards: dict, ids: dict) -> PreparedScoringSet:
        import weakref

        key = self._cache_key(shards, ids)
        if self._cache is not None and self._cache[0] == key:
            return self._cache[2]
        prepared = self.prepare(shards, ids)

        def _clear(_ref, _self=weakref.ref(self)):
            t = _self()
            if t is not None:
                t._cache = None

        refs = []
        for obj in list(shards.values()) + list(ids.values()):
            try:
                refs.append(weakref.ref(obj, _clear))
            except TypeError:
                pass  # un-weakref-able value: fall back to identity check
        self._cache = (key, refs, prepared)
        return prepared

    def transform(
        self,
        shards: dict,
        ids: dict,
        offset: Optional[np.ndarray] = None,
        prepared: Optional[PreparedScoringSet] = None,
    ) -> np.ndarray:
        some_shard = next(iter(shards.values()))
        n = some_shard.shape[0]
        if prepared is not None and prepared.n_rows != n:
            raise ValueError(
                f"prepared scoring set covers {prepared.n_rows} rows but "
                f"the shards have {n}; prepare() must be called on the same "
                "data being transformed"
            )
        from photon_ml_tpu.serving import kernels as serving_kernels

        parts = []
        for name, sub in self.model.models.items():
            if isinstance(sub, FixedEffectModel):
                parts.append(serving_kernels.fixed_effect_matvec(
                    shards[sub.feature_shard], sub.model.coefficients.means
                ))
            else:
                if prepared is None:
                    prepared = self._prepared_for(shards, ids)
                parts.append(serving_kernels.random_effect_block_scores(
                    sub, prepared.re_datasets[name]
                ))
        return serving_kernels.sum_margins(n, offset, parts)

    @staticmethod
    def _score_random_effect(model: RandomEffectModel, dataset) -> np.ndarray:
        """Back-compat shim; the implementation moved to
        ``serving.kernels.random_effect_block_scores`` (shared with the
        online runtime)."""
        from photon_ml_tpu.serving import kernels as serving_kernels

        return serving_kernels.random_effect_block_scores(model, dataset)

    def transform_with_mean(self, shards, ids, offset=None) -> np.ndarray:
        """Scores passed through the task's inverse link (probabilities for
        logistic, rates for Poisson)."""
        from photon_ml_tpu.ops import losses as losses_lib

        margins = self.transform(shards, ids, offset)
        return np.asarray(losses_lib.get(self.model.task).mean_fn(jnp.asarray(margins)))
