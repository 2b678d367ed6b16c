"""Factored random effects: per-entity coefficients through a shared
low-rank projection.

The reference's ``FactoredRandomEffectCoordinate`` (SURVEY.md §2, GAME
coordinates row — the older-photon-ml variant, tagged [LOW]; modeled here
from the GLMix matrix-factorization formulation since the reference mount
is unreadable): entity e's coefficient vector is constrained to

    w_e = V u_e        V: (n_features, rank) shared, u_e: (rank,) per entity

so sparse entities borrow statistical strength through V (classic
factorization regularization), and per-entity state is ``rank`` floats
instead of ``n_features``.

Training alternates two convex sub-problems (block coordinate descent
INSIDE this coordinate, mirroring the reference's alternation between the
per-entity problems and the projection fit):

1. **latent step** (V fixed): per-entity GLMs over the projected features
   ``Z = X V`` — exactly the batched bucketed solver used by
   ``RandomEffectCoordinate``, at dimension ``rank``;
2. **projection step** (all u_e fixed): one global GLM over vec(V) with
   margin ``x_rᵀ V u_e`` — value/gradient assembled per bucket with
   einsums (no (n_rows × d·rank) design matrix is ever materialized),
   solved by the on-device L-BFGS.

Both steps run inside ONE jitted program per call (static alternation
count), so a factored coordinate costs one device dispatch per CD update,
like the other coordinates.

``finalize`` materializes ``w_e = V u_e`` into the standard
``RandomEffectModel`` table, so model storage, scoring drivers, and the
transformer treat factored and plain random effects identically.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.game.coordinates import (
    Coordinate,
    _gather_block_offsets,
    _make_block_solver,
)
from photon_ml_tpu.game.data import EntityBlock, RandomEffectDataset
from photon_ml_tpu.game.model import EntityLanes, RandomEffectModel
from photon_ml_tpu.ops import losses as losses_lib
from photon_ml_tpu.optim.lbfgs import LBFGSConfig, lbfgs_solve
from photon_ml_tpu.optim.problem import GlmOptimizationConfig

Array = jax.Array


def _gather_v(V: Array, cmap: Array) -> Array:
    """Per-lane rows of V in the block's LOCAL column space: (E, D, rank).
    Padding columns (cmap == -1) read as zero rows."""
    safe = jnp.maximum(cmap, 0)
    vsub = jnp.take(V, safe, axis=0)
    return jnp.where((cmap >= 0)[:, :, None], vsub, 0.0)


def _project_block(block: EntityBlock, V: Array, rank: int) -> EntityBlock:
    """The block with features projected through V: X (E,R,D) → Z (E,R,k),
    stored as computed (``x_minor == "d"``) whatever order the block keeps
    its own X in."""
    vsub = _gather_v(V, block.col_map)
    z = jnp.einsum("erd,edk->erk", block.x_erd, vsub)
    # col_map is meaningless in latent space; the solver never reads it.
    return dataclasses.replace(
        block,
        X=z,
        col_map=jnp.zeros((block.n_entities, rank), jnp.int32),
        block_dim=rank,
        x_minor="d",
    )


class FactoredRandomEffectCoordinate(Coordinate):
    """Reference: ``FactoredRandomEffectCoordinate`` — see module docstring.

    State is ``(u_list, V)``: per-bucket latent arrays ``(E, rank)`` plus
    the shared projection ``(n_features, rank)``.
    """

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
        seed: int = 0,
    ):
        if rank < 1:
            raise ValueError(f"rank must be >= 1, got {rank}")
        self.name = name
        self.dataset = dataset
        self.task = losses_lib.get(task).name
        self.config = config
        self.rank = int(rank)
        self.reg_weight = reg_weight
        self.projection_reg_weight = (
            reg_weight if projection_reg_weight is None
            else projection_reg_weight
        )
        self.alternations = int(alternations)
        self.feature_shard = feature_shard
        self.entity_key = entity_key or name
        self._solver = _make_block_solver(task, config)
        loss = losses_lib.get(task)
        n_rows = dataset.n_global_rows
        n_features = dataset.n_features
        rank = self.rank
        opt = config.optimizer
        solver = self._solver
        alternations_n = self.alternations

        # Deterministic non-zero init for V: with U = 0 the projection
        # gradient vanishes (dm ⊗ u = 0), so V must start non-degenerate;
        # the first latent step then populates U against this basis.
        self._v0 = jnp.asarray(
            (
                np.random.default_rng(seed).normal(size=(n_features, rank))
                / np.sqrt(max(rank, 1))
            ).astype(np.float32)
        )

        def projection_value_grad(vflat, blocks, u_list, offsets, l2v):
            """Objective in V with all latents fixed (margins via einsum —
            the (n_rows, d·rank) design matrix is never materialized)."""
            V = vflat.reshape(n_features, rank)
            val = 0.5 * l2v * jnp.vdot(vflat, vflat)
            g = jnp.zeros((n_features + 1, rank), jnp.float32)
            for block, u in zip(blocks, u_list):
                vsub = _gather_v(V, block.col_map)
                off = _gather_block_offsets(offsets, block)
                m = (
                    jnp.einsum("erd,edk,ek->er", block.x_erd, vsub, u)
                    + off.astype(jnp.float32)
                )
                val = val + jnp.sum(
                    block.weights * loss.value(m, block.labels)
                )
                dm = block.weights * loss.d1(m, block.labels)  # (E, R)
                g_local = jnp.einsum(
                    "er,erd,ek->edk", dm, block.x_erd, u
                )  # (E, D, rank)
                idx = jnp.where(
                    block.col_map >= 0, block.col_map, n_features
                )
                g = g.at[idx.reshape(-1)].add(
                    g_local.reshape(-1, rank)
                )
            g = g[:n_features] + l2v * V
            return val, g.reshape(-1)

        def _train_impl(blocks, offsets, u_list, V, l1, l2, l2v):
            offsets = offsets.astype(jnp.float32)
            for _ in range(alternations_n):
                # (1) latent step: bucketed per-entity solves at dim=rank.
                u_list = [
                    solver(
                        _project_block(b, V, rank),
                        _gather_block_offsets(offsets, b),
                        u, l1, l2,
                    )
                    for b, u in zip(blocks, u_list)
                ]
                # (2) projection step: global L-BFGS over vec(V).
                def vg(vflat, u_list=u_list):
                    return projection_value_grad(
                        vflat, blocks, u_list, offsets, l2v
                    )

                V = lbfgs_solve(
                    vg,
                    V.reshape(-1),
                    LBFGSConfig(
                        max_iters=opt.max_iters,
                        tolerance=opt.tolerance,
                        history=opt.history,
                    ),
                ).w.reshape(n_features, rank)
            return u_list, V

        def _score_impl(blocks, passive_blocks, u_list, V):
            total = jnp.zeros((n_rows + 1,), jnp.float32)
            passive = passive_blocks or [None] * len(blocks)
            for block, pblock, u in zip(blocks, passive, u_list):
                s = jnp.einsum(
                    "erd,edk,ek->er",
                    block.x_erd, _gather_v(V, block.col_map), u,
                )
                total = total.at[block.row_index.ravel()].add(s.ravel())
                if pblock is not None:
                    w = jnp.einsum(
                        "edk,ek->ed", _gather_v(V, block.col_map), u)
                    total = total.at[pblock.row_index].add(pblock.scores(w))
            return total[:n_rows]

        def _materialize_impl(blocks, u_list, V):
            """Dense per-bucket local coefficients w_e = V_sub u_e: the
            shape RandomEffectCoordinate state has, for shared scorers."""
            return [
                jnp.einsum("edk,ek->ed", _gather_v(V, b.col_map), u)
                for b, u in zip(blocks, u_list)
            ]

        self._train_jit = jax.jit(_train_impl)
        self._score_jit = jax.jit(_score_impl)
        self._materialize_jit = jax.jit(_materialize_impl)

    # -- Coordinate protocol ------------------------------------------------
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
        if warm_state is None:
            u_list = [
                jnp.zeros((b.n_entities, self.rank), jnp.float32)
                for b in self.dataset.blocks
            ]
            V = self._v0
        else:
            u_list, V = warm_state
        return self._train_jit(
            self.dataset.blocks, jnp.asarray(offsets), u_list, V,
            l1, l2, l2v,
        )

    def score(self, state) -> Array:
        u_list, V = state
        return self._score_jit(
            self.dataset.blocks, self.dataset.passive_blocks, u_list, V
        )

    def materialize(self, state) -> list[Array]:
        """Per-bucket dense local coefficients (RandomEffectCoordinate's
        state shape) — used by validation scorers and finalize."""
        u_list, V = state
        return self._materialize_jit(self.dataset.blocks, u_list, V)

    def finalize(self, state, offsets=None) -> RandomEffectModel:
        return finalize_factored_model(self, state)

    def make_validation_scorer(self, shards: dict, ids: dict):
        from photon_ml_tpu.game.validation import RandomEffectValidationScorer

        inner = RandomEffectValidationScorer(
            self.dataset, ids[self.entity_key], shards[self.feature_shard]
        )
        return _FactoredValidationScorer(self, inner)


def finalize_factored_model(coord, state) -> RandomEffectModel:
    """The one materialized-table builder both the resident and the
    out-of-core factored coordinates share.  Identical storage shape to a
    plain random effect: scoring driver, transformer, and Avro store need
    no factored-specific handling.  Coefficient variances are not defined
    through the factorization (w_e is a deterministic function of the
    joint (U, V) fit), so none are produced — matching the reference,
    which computes variances only for unfactored coordinates."""
    table = EntityLanes(
        coord.dataset.entity_ids,
        [b.col_map for b in coord.dataset.blocks],
    ).table(jax.device_get(list(coord.materialize(state))))
    return RandomEffectModel(
        coefficients=table,
        feature_shard=coord.feature_shard,
        entity_key=coord.entity_key,
        task=coord.task,
        n_features=coord.dataset.n_features,
        variances=None,
    )


class _FactoredValidationScorer:
    """Adapts factored (u_list, V) state to the dense-coefficient scorer."""

    def __init__(self, coord: FactoredRandomEffectCoordinate, inner):
        self._coord = coord
        self._inner = inner

    @property
    def n_rows(self) -> int:
        return self._inner.n_rows

    def score(self, state) -> Array:
        return self._inner.score(self._coord.materialize(state))
