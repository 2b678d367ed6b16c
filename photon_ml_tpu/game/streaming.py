"""Out-of-core GAME: a fixed-effect coordinate over a streamed dataset.

At BASELINE's north-star scale the GAME fixed-effect dataset alone
exceeds one chip's HBM, exactly like the legacy-GLM case
(SURVEY.md §7 "Host→device ingest bandwidth").  This coordinate plugs the
host-RAM chunk store (data/streaming.py) into the block coordinate
descent loop: training is the host-loop L-BFGS over double-buffered
chunk passes with the OTHER coordinates' scores entering as per-chunk
offset slices, and scoring streams ``X @ w`` back per chunk.  The rest
of the descent (random effects, factored effects, validation hooks,
checkpointing) is unchanged — coordinates compose through per-row score
arrays, which stay device-resident and small.

The streamed chunks must be built with ZERO data offsets: in GAME, the
base offsets ride the coordinate-descent total (the estimator seeds it),
so chunk-held offsets would double-count.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.streaming import StreamingGlmData
from photon_ml_tpu.game.coordinates import Coordinate
from photon_ml_tpu.game.model import FixedEffectModel
from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.ops import losses as losses_lib
from photon_ml_tpu.optim.lbfgs import LBFGSConfig
from photon_ml_tpu.optim.owlqn import OWLQNConfig
from photon_ml_tpu.optim.problem import GlmOptimizationConfig, choose_solver
from photon_ml_tpu.optim.streaming import (
    STREAMED_SOLVERS,
    StreamingObjective,
    ensure_streamable,
    streaming_lbfgs_solve,
    streaming_owlqn_solve,
    streaming_tron_solve,
)

Array = jax.Array


class StreamingFixedEffectCoordinate(Coordinate):
    """FixedEffectCoordinate for datasets larger than HBM.

    Drop-in for the resident coordinate inside ``CoordinateDescent``:
    same ``train(offsets, warm) → w`` / ``score(w)`` / ``finalize``
    surface, with every objective evaluation a streamed pass.  All three
    optimizers stream: L-BFGS, OWL-QN (L1/elastic-net), and smooth TRON
    (each CG step one streamed HVP pass).
    """

    def __init__(
        self,
        name: str,
        stream: StreamingGlmData,
        task: str,
        config: GlmOptimizationConfig,
        reg_weight: float = 0.0,
        feature_shard: str = "global",
        accumulate: str = "f32",
        mesh=None,
        prefetch_depth: int = 2,
        chunk_fuse: int = 1,
        batch_linesearch: bool = True,
        compress: str = "off",
        hot_budget_bytes: int = 0,
    ):
        """``chunk_fuse``: chunks folded per device dispatch via
        ``lax.scan`` (single-device only) — amortizes per-dispatch
        overhead when chunks are small.  ``batch_linesearch``: evaluate
        a bracket of line-search candidates per streamed pass (identical
        trial sequence, ~half the passes per solve).

        ``compress`` / ``hot_budget_bytes``: the transfer-avoidance
        knobs — compressed chunk wire formats with on-device dequant,
        and the importance-aware HBM working-set cache (hot chunks skip
        pack + transfer across CD iterations; single-device only).
        Lossless compression and the cache leave every coordinate solve
        bitwise unchanged (see optim/streaming.py).

        ``mesh``: streams each chunk SHARDED over the mesh's first axis
        (chunks must be built with ``n_shards == mesh size``) — streamed
        data parallelism composed with GAME: the per-chunk reduction runs
        under shard_map with one fused psum, and the coordinate-descent
        offsets ride per-chunk as sharded row slices.

        On a multi-process POD, per-row CD state is PROCESS-LOCAL: this
        coordinate's ``train`` offsets and ``score`` output cover THIS
        process's rows (the rows its chunk store holds, built with
        ``n_shards == jax.local_device_count()``), the reference's layout
        of score RDDs partitioned next to the data.  The solve itself is
        global — every objective pass psums over the whole pod — so all
        processes converge on one identical model; compose only with
        coordinates whose per-row surface is also process-local (e.g.
        per-entity random effects whose entities are partitioned to the
        process holding their rows, the reference's hash-partitioner
        invariant), and reduce metrics with a psum or allgather."""
        ensure_streamable(config)
        self._solver = choose_solver(
            config.optimizer, l1_frac=config.regularization.l1_weight(1.0)
        )
        if self._solver not in STREAMED_SOLVERS:
            raise ValueError(
                f"solver {self._solver!r} has no streamed implementation; "
                f"a streamed fixed effect runs one of {STREAMED_SOLVERS}"
            )
        if mesh is None and stream.n_shards != 1:
            raise ValueError(
                f"stream has n_shards={stream.n_shards}; pass the mesh it "
                "was built for"
            )
        if stream.has_nonzero_offsets():  # cached: free per grid point
            raise ValueError(
                "streamed GAME chunks must carry zero offsets — base "
                "offsets ride the coordinate-descent total"
            )
        self.name = name
        self.stream = stream
        self.task = losses_lib.get(task).name
        self.config = config
        self.reg_weight = reg_weight
        self.feature_shard = feature_shard
        self.batch_linesearch = bool(batch_linesearch)
        self._sobj = StreamingObjective(
            self.task, stream, accumulate=accumulate, mesh=mesh,
            prefetch_depth=prefetch_depth, chunk_fuse=chunk_fuse,
            compress=compress, hot_budget_bytes=hot_budget_bytes,
        )
        opt = config.optimizer
        self._lbfgs = LBFGSConfig(
            max_iters=opt.max_iters,
            tolerance=opt.tolerance,
            history=opt.history,
        )
        self._owlqn = OWLQNConfig(
            max_iters=opt.max_iters,
            tolerance=opt.tolerance,
            history=opt.history,
        )

    @property
    def transfer_stats(self):
        """The underlying stream's h2d observability (data/prefetch.py's
        TransferStats) — per-chunk timing, GB/s, stall counters."""
        return self._sobj.transfer_stats

    @property
    def _l1_frac(self) -> float:
        return self.config.regularization.l1_weight(1.0)

    @property
    def _l2(self) -> float:
        return self.config.regularization.l2_weight(1.0) * self.reg_weight

    @property
    def feature_layout(self) -> str:
        from photon_ml_tpu.utils.device_report import describe_layout

        return f"streamed {describe_layout(self.stream.chunks[0].features)}"

    def train(self, offsets: Array, warm_state: Optional[Array] = None):
        w0 = (
            jnp.zeros((self.stream.n_features,), jnp.float32)
            if warm_state is None else warm_state
        )
        # Offsets are fixed for the whole solve: slice them per chunk ONCE
        # (value_and_grad accepts the pre-sliced list), not per line-search
        # probe.
        slices = self._sobj.offset_slices(offsets)
        vg = lambda w: self._sobj.value_and_grad(w, self._l2, offsets=slices)
        # Batched line-search trials: one streamed pass evaluates the
        # whole candidate bracket (same trial sequence, fewer passes).
        vgb = (
            (lambda ws: self._sobj.value_and_grad_batch(
                ws, self._l2, offsets=slices
            ))
            if self.batch_linesearch else None
        )
        if self._solver == "owlqn":
            res = streaming_owlqn_solve(
                vg, w0, self._l1_frac * self.reg_weight, self._owlqn,
                value_and_grad_batch=vgb,
            )
        elif self._solver == "tron":
            from photon_ml_tpu.optim.tron import TRONConfig

            opt = self.config.optimizer
            res = streaming_tron_solve(
                vg,
                lambda w, v: self._sobj.hvp(
                    w, v, self._l2, offsets=slices
                ),
                w0,
                TRONConfig(
                    max_iters=opt.max_iters, tolerance=opt.tolerance
                ),
            )
        else:
            res = streaming_lbfgs_solve(
                vg, w0, self._lbfgs, value_and_grad_batch=vgb
            )
        return res.w

    def score(self, state: Array) -> Array:
        # Margin WITHOUT offsets: coordinate scores are additive pieces
        # (chunks carry zero offsets by the constructor's contract).
        return jnp.asarray(self._sobj.scores(state))

    def finalize(self, state: Array, offsets=None) -> FixedEffectModel:
        variances = None
        if self.config.compute_variances and offsets is None:
            # Same contract (and warning) as the distributed sibling: the
            # variance Hessian needs the FULL final margins.
            import logging

            logging.getLogger(__name__).warning(
                "compute_variances requested but finalize() got no "
                "offsets; variances omitted for coordinate %r", self.name,
            )
        if self.config.compute_variances and offsets is not None:
            diag = self._sobj.hessian_diagonal(state, offsets=offsets)
            variances = 1.0 / jnp.maximum(diag + self._l2, 1e-12)
        return FixedEffectModel(
            GeneralizedLinearModel(Coefficients(state, variances), self.task),
            self.feature_shard,
        )

    def make_validation_scorer(self, shards: dict, ids: dict):
        from photon_ml_tpu.game.validation import FixedEffectValidationScorer

        return FixedEffectValidationScorer(shards[self.feature_shard])
