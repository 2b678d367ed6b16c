"""Out-of-core FACTORED random effects: w_e = V u_e at beyond-HBM scale.

Completes the out-of-core coordinate matrix (game/ooc_random.py covers
plain random effects): the factored coordinate's entity blocks stream
through HBM in the same budget-bounded pass groups, while the two
alternation sub-problems restructure exactly the way the fixed-effect
solvers did when their data went out of core (optim/streaming.py):

1. **latent step** — per-entity solves are independent, so each pass
   group projects its slices through the (device-resident, replicated)
   ``V`` and runs the memoized batched solver at dimension ``rank``;
   latent vectors live in host numpy between passes.
2. **projection step** — the shared-``V`` fit becomes a HOST-LOOP
   L-BFGS (``streaming_lbfgs_solve``, the same outer loop the streamed
   GLM uses) whose every value/gradient evaluation is one streamed pass
   over the groups, accumulating the ``(n_features+1, rank)`` gradient
   on device.

``V`` and its gradient are the only whole-pass-resident device state;
their bytes are carved out of the budget before groups are sized
(``_budget_overhead_bytes``).  State is ``(u_list, V)`` with ``u_list``
host numpy — the factored analogue of the plain OOC coordinate's
host-resident coefficients.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.game.coordinates import _gather_block_offsets
from photon_ml_tpu.game.data import EntityBlock, RandomEffectDataset
from photon_ml_tpu.game.factored import _gather_v, _project_block
from photon_ml_tpu.game.model import RandomEffectModel
from photon_ml_tpu.game.ooc_random import (
    OutOfCoreRandomEffectCoordinate,
    _cut,
    _slice_block,
)
from photon_ml_tpu.ops import losses as losses_lib
from photon_ml_tpu.optim.lbfgs import LBFGSConfig
from photon_ml_tpu.optim.problem import GlmOptimizationConfig

Array = jax.Array


class OutOfCoreFactoredRandomEffectCoordinate(OutOfCoreRandomEffectCoordinate):
    """FactoredRandomEffectCoordinate for datasets larger than HBM.

    Same ``train(offsets, warm) → (u_list, V)`` / ``score(state)``
    surface as the resident factored coordinate; the same pass-plan,
    double-buffer, and budget machinery as the plain OOC coordinate.
    """

    # The projection step threads ONE device-resident (V, gradient)
    # accumulator through every slice's program — a slice committed to
    # device k would drag that accumulator across devices mid-pass, so
    # this coordinate keeps the legacy everything-split mesh layout.
    _supports_packed = False
    # train/score here stream PROJECTED payloads with their own pack
    # functions — the base class's cached raw-block trees would never
    # be consumed, so the hot working-set cache stays off.
    _supports_hot_cache = False

    def prestage(self, warm_state=None) -> None:
        # The factored train packs PROJECTED latent payloads, not the
        # base class's (block, w0) slices — inherited prestage buffers
        # would never be consumed, so opt out of the hint entirely.
        return None

    def __init__(
        self,
        name: str,
        dataset: RandomEffectDataset,
        task: str,
        config: GlmOptimizationConfig,
        rank: int,
        reg_weight: float = 0.0,
        projection_reg_weight: Optional[float] = None,
        alternations: int = 2,
        feature_shard: str = "global",
        entity_key: str = "",
        device_budget_bytes: int = 256 * 2**20,
        mesh=None,
        seed: int = 0,
        prefetch_depth: int = 2,
    ):
        if rank < 1:
            raise ValueError(f"rank must be >= 1, got {rank}")
        # The plan hooks below read these during super().__init__.
        self.rank = int(rank)
        self._n_features = dataset.n_features
        super().__init__(
            name, dataset, task, config, reg_weight=reg_weight,
            feature_shard=feature_shard, entity_key=entity_key,
            device_budget_bytes=device_budget_bytes, mesh=mesh,
            prefetch_depth=prefetch_depth,
        )
        self.projection_reg_weight = (
            reg_weight if projection_reg_weight is None
            else projection_reg_weight
        )
        self.alternations = int(alternations)
        loss = losses_lib.get(self.task)
        rank = self.rank
        n_features = dataset.n_features
        solver = self._solver

        # Same deterministic non-zero V init as the resident coordinate.
        self._v0 = jnp.asarray(
            (
                np.random.default_rng(seed).normal(size=(n_features, rank))
                / np.sqrt(max(rank, 1))
            ).astype(np.float32)
        )

        def _latent_slice(block, V, offsets, u0, l1, l2):
            return solver(
                _project_block(block, V, rank),
                _gather_block_offsets(offsets, block),
                u0, l1, l2,
            )

        def _proj_slice(acc_val, acc_g, block, u, offsets, vflat):
            """One slice's (value, gradient-scatter) contribution to the
            projection objective — accumulated on device."""
            V = vflat.reshape(n_features, rank)
            vsub = _gather_v(V, block.col_map)
            off = _gather_block_offsets(offsets, block)
            m = (
                jnp.einsum("erd,edk,ek->er", block.x_erd, vsub, u)
                + off.astype(jnp.float32)
            )
            acc_val = acc_val + jnp.sum(
                block.weights * loss.value(m, block.labels)
            )
            dm = block.weights * loss.d1(m, block.labels)
            g_local = jnp.einsum("er,erd,ek->edk", dm, block.x_erd, u)
            idx = jnp.where(block.col_map >= 0, block.col_map, n_features)
            acc_g = acc_g.at[idx.reshape(-1)].add(g_local.reshape(-1, rank))
            return acc_val, acc_g

        def _proj_finish(val, g, vflat, l2v):
            V = vflat.reshape(n_features, rank)
            return (
                val + 0.5 * l2v * jnp.vdot(vflat, vflat),
                (g[:n_features] + l2v * V).reshape(-1),
            )

        def _score_slice_f(total, X, col_map, row_index, u, V):
            s = jnp.einsum(
                "erd,edk,ek->er", X, _gather_v(V, col_map), u
            )
            return total.at[row_index.ravel()].add(s.ravel())

        def _materialize_slice(block_cmap, u, V):
            return jnp.einsum("edk,ek->ed", _gather_v(V, block_cmap), u)

        self._latent_jit = jax.jit(_latent_slice)
        self._proj_jit = jax.jit(_proj_slice, donate_argnums=(0, 1))
        self._proj_finish_jit = jax.jit(_proj_finish)
        self._score_f_jit = jax.jit(_score_slice_f, donate_argnums=0)
        self._materialize_jit = jax.jit(_materialize_slice)
        self._lbfgs_cfg = LBFGSConfig(
            max_iters=config.optimizer.max_iters,
            tolerance=config.optimizer.tolerance,
            history=config.optimizer.history,
        )

    # -- plan hooks ---------------------------------------------------------

    def _extra_lane_bytes(self, block: EntityBlock) -> int:
        # Projected features Z (E, R, rank) live next to X during the
        # latent step; latent vectors ride in and out.
        return 4 * (block.rows_per_entity * self.rank + 2 * self.rank)

    def _budget_overhead_bytes(self) -> int:
        # V + its gradient accumulator, replicated and whole-pass-resident.
        return 2 * 4 * (self._n_features + 1) * self.rank

    # -- coordinate surface -------------------------------------------------

    def train(self, offsets: Array, warm_state=None):
        l1 = jnp.asarray(
            self.config.regularization.l1_weight(1.0) * self.reg_weight,
            jnp.float32,
        )
        l2 = jnp.asarray(
            self.config.regularization.l2_weight(1.0) * self.reg_weight,
            jnp.float32,
        )
        l2v = jnp.asarray(self.projection_reg_weight, jnp.float32)
        offsets = jnp.asarray(offsets, jnp.float32)
        sentinel = self.dataset.n_global_rows
        if warm_state is None:
            u_list = [
                np.zeros((b.n_entities, self.rank), np.float32)
                for b in self.dataset.blocks
            ]
            V = self._v0
        else:
            u_warm, V = warm_state
            u_list = [np.array(u, np.float32) for u in u_warm]
            V = jnp.asarray(V, jnp.float32)

        def host_group(group):
            # One slicer for BOTH passes: the latent step reads u as its
            # warm start, the projection step as the fixed latents.
            out = []
            for s in group:
                out.append((
                    _slice_block(
                        self.dataset.blocks[s.block_idx],
                        s.lane_lo, s.lane_hi, s.padded_e, sentinel,
                    ),
                    _cut(
                        u_list[s.block_idx], s.lane_lo, s.lane_hi,
                        s.padded_e, 0,
                    ),
                ))
            return out

        from photon_ml_tpu.optim.streaming import streaming_lbfgs_solve

        for _ in range(self.alternations):
            # (1) latent step: one streamed pass, u host-resident between.
            V_dev = V

            def consume_latent(group, dev):
                results = [
                    self._latent_jit(blk, V_dev, offsets, u0, l1, l2)
                    for blk, u0 in dev
                ]
                for s, res in zip(group, results):
                    u_list[s.block_idx][s.lane_lo:s.lane_hi] = np.asarray(
                        res
                    )[: s.lane_hi - s.lane_lo]

            self._run_groups(host_group, consume_latent)

            # (2) projection step: host-loop L-BFGS; every evaluation is
            # one streamed pass accumulating (val, grad) on device.
            def vg(vflat):
                import collections

                acc = [
                    jnp.zeros((), jnp.float32),
                    jnp.zeros(
                        (self._n_features + 1, self.rank), jnp.float32
                    ),
                ]
                # Windowed carry sync (optim/streaming.py's discipline):
                # run up to prefetch_depth dispatched-but-unexecuted
                # group programs ahead, then block on the value scalar a
                # window behind — keeps the device fed through each
                # group's Python dispatch while bounding live group
                # buffers (the device_budget contract) instead of
                # letting the dispatch queue pin arbitrarily many.
                window = 0 if self.prefetch_depth == 1 else (
                    self.prefetch_depth
                )
                ring: collections.deque = collections.deque()

                def consume(group, dev):
                    for blk, u in dev:
                        acc[0], acc[1] = self._proj_jit(
                            acc[0], acc[1], blk, u, offsets, vflat
                        )
                    ring.append(acc[0])
                    if len(ring) > window:
                        jax.block_until_ready(ring.popleft())

                self._run_groups(host_group, consume)
                ring.clear()
                return self._proj_finish_jit(acc[0], acc[1], vflat, l2v)

            V = streaming_lbfgs_solve(
                vg, V.reshape(-1), self._lbfgs_cfg
            ).w.reshape(self._n_features, self.rank)
        return u_list, V

    def score(self, state) -> Array:
        u_list, V = state
        V = jnp.asarray(V, jnp.float32)
        sentinel = self.dataset.n_global_rows
        total = self._zeros_jit()

        def host_group(group):
            out = []
            for s in group:
                u = _cut(
                    np.asarray(u_list[s.block_idx], np.float32),
                    s.lane_lo, s.lane_hi, s.padded_e, 0,
                )
                block = self.dataset.blocks[s.block_idx]
                active = (
                    _cut(block.X, s.lane_lo, s.lane_hi, s.padded_e, 0),
                    _cut(block.col_map, s.lane_lo, s.lane_hi,
                         s.padded_e, -1),
                    _cut(block.row_index, s.lane_lo, s.lane_hi,
                         s.padded_e, sentinel),
                )
                passive = None
                if self._passive_blocks:
                    pb = self._passive_blocks[s.block_idx]
                    if pb is not None:
                        passive = (
                            _cut(pb.X, s.lane_lo, s.lane_hi, s.padded_e, 0),
                            _cut(pb.col_map, s.lane_lo, s.lane_hi,
                                 s.padded_e, -1),
                            _cut(pb.row_index, s.lane_lo, s.lane_hi,
                                 s.padded_e, sentinel),
                        )
                out.append((active, passive, u))
            return out

        def consume(_group, dev):
            nonlocal total
            for active, passive, u in dev:
                total = self._score_f_jit(total, *active, u, V)
                if passive is not None:
                    total = self._score_f_jit(total, *passive, u, V)

        self._run_groups(host_group, consume)
        return total[: self.dataset.n_global_rows]

    def materialize(self, state) -> list[np.ndarray]:
        """Per-bucket dense local coefficients, computed slice-wise so
        no whole block rides to the device (validation scorers and
        finalize share this)."""
        u_list, V = state
        V = jnp.asarray(V, jnp.float32)
        out = [
            np.zeros((b.n_entities, b.block_dim), np.float32)
            for b in self.dataset.blocks
        ]
        for group in self.pass_plan:
            for s in group:
                block = self.dataset.blocks[s.block_idx]
                cmap = self._put(_cut(
                    block.col_map, s.lane_lo, s.lane_hi, s.padded_e, -1
                ))
                u = self._put(_cut(
                    np.asarray(u_list[s.block_idx], np.float32),
                    s.lane_lo, s.lane_hi, s.padded_e, 0,
                ))
                w = self._materialize_jit(cmap, u, V)
                out[s.block_idx][s.lane_lo:s.lane_hi] = np.asarray(
                    w
                )[: s.lane_hi - s.lane_lo]
        return out

    def finalize(self, state, offsets=None) -> RandomEffectModel:
        from photon_ml_tpu.game.factored import finalize_factored_model

        return finalize_factored_model(self, state)

    def make_validation_scorer(self, shards: dict, ids: dict):
        from photon_ml_tpu.game.factored import _FactoredValidationScorer
        from photon_ml_tpu.game.validation import RandomEffectValidationScorer

        inner = RandomEffectValidationScorer(
            self.dataset, ids[self.entity_key], shards[self.feature_shard]
        )
        # The resident adapter only needs coord.materialize(state).
        return _FactoredValidationScorer(self, inner)
