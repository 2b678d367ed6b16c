"""Distributed GAME coordinates: multi-chip fixed and random effects.

The reference scales GAME with Spark (SURVEY.md §2 "Parallelism strategies"):
rows sharded across executors for the fixed effect (`treeAggregate`
reductions), entities hash-partitioned across executors for random effects
(communication-free per-entity solves).  The TPU mapping
[CONFIRMED-BASELINE north star]:

- ``DistributedFixedEffectCoordinate`` — rows sharded over the mesh's
  ``DATA_AXIS``; the whole L-BFGS/OWL-QN/TRON loop runs inside ``shard_map``
  with one fused ``psum`` per objective evaluation over ICI.
- ``EntityShardedRandomEffectCoordinate`` — the "expert parallelism"
  analogue: each block's ENTITY axis is sharded over the mesh
  (``NamedSharding``), and because the vmap'd batched solver is elementwise
  across entities, XLA partitions it with zero communication in the solve —
  exactly the reference's communication-free ``mapPartitions`` property.
  Only the per-row score scatter crosses shards.

Both run multi-host unchanged: mesh devices may span hosts; XLA routes
collectives over ICI within a slice and DCN across slices.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu.game.coordinates import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.data import EntityBlock, RandomEffectDataset
from photon_ml_tpu.game.model import FixedEffectModel
from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.ops import losses as losses_lib
from photon_ml_tpu.optim.problem import GlmOptimizationConfig
from photon_ml_tpu.parallel.distributed import (
    DATA_AXIS,
    DistributedGlmData,
    shard_glm_data,
)

Array = jax.Array


class DistributedFixedEffectCoordinate(FixedEffectCoordinate):
    """Row-sharded fixed-effect coordinate (SURVEY.md §3.1 hot loop on a
    mesh).  Constructed from HOST data; sharding happens once here, like the
    reference persisting its row-partitioned RDD."""

    def __init__(
        self,
        name: str,
        X_host,
        labels: np.ndarray,
        mesh,
        task: str,
        config: GlmOptimizationConfig,
        reg_weight: float = 0.0,
        feature_shard: str = "global",
        weights: Optional[np.ndarray] = None,
        dist: Optional[DistributedGlmData] = None,
    ):
        from photon_ml_tpu.optim.problem import GlmOptimizationProblem

        # Deliberately NOT calling super().__init__: the dataset lives as
        # DistributedGlmData and train/score are shard_map programs.
        self.name = name
        self.task = losses_lib.get(task).name
        self.problem = GlmOptimizationProblem(task, config)
        self.reg_weight = reg_weight
        self.feature_shard = feature_shard
        self.mesh = mesh
        self.n_rows = X_host.shape[0]
        self.n_features = X_host.shape[1]
        # A prebuilt sharded dataset (grid points differing only in the
        # optimizer config reuse it — re-sharding/re-uploading the training
        # matrix per point is the expensive part).
        self.dist = (
            dist if dist is not None
            else shard_glm_data(X_host, labels, mesh, weights=weights)
        )
        self._rows_per_shard = self.dist.data.labels.shape[1]
        self._n_shards = self.dist.n_shards

        def _train(
            dd: DistributedGlmData,
            offsets_blocked: Array,
            w0: Array,
            reg_weight: Array,
        ):
            local = dd.local()
            local = dataclasses.replace(local, offsets=offsets_blocked[0])
            return self.problem.solve(
                local, reg_weight, w0, axis_name=DATA_AXIS
            ).w

        def _score(dd: DistributedGlmData, w: Array) -> Array:
            return dd.local().features.matvec(w)[None, :]

        self._train_sm = jax.jit(
            shard_map(
                _train,
                mesh=mesh,
                in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(), P()),
                out_specs=P(),
                check_vma=False,
            )
        )
        self._score_sm = jax.jit(
            shard_map(
                _score,
                mesh=mesh,
                in_specs=(P(DATA_AXIS), P()),
                out_specs=P(DATA_AXIS),
                check_vma=False,
            )
        )

        def _variances(
            dd: DistributedGlmData,
            offsets_blocked: Array,
            w: Array,
            reg_weight: Array,
        ):
            local = dd.local()
            local = dataclasses.replace(local, offsets=offsets_blocked[0])
            return self.problem.coefficient_variances(
                w, local, reg_weight, axis_name=DATA_AXIS
            )

        self._var_sm = jax.jit(
            shard_map(
                _variances,
                mesh=mesh,
                in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(), P()),
                out_specs=P(),
                check_vma=False,
            )
        )

    @property
    def feature_layout(self) -> str:
        from photon_ml_tpu.utils.device_report import describe_layout

        return describe_layout(self.dist.data.features, self._n_shards)

    def _block_offsets(self, offsets: Array) -> Array:
        total = self._n_shards * self._rows_per_shard
        padded = jnp.concatenate(
            [offsets, jnp.zeros((total - self.n_rows,), offsets.dtype)]
        )
        blocked = padded.reshape(self._n_shards, self._rows_per_shard)
        return jax.device_put(blocked, NamedSharding(self.mesh, P(DATA_AXIS)))

    def train(self, offsets: Array, warm_state: Optional[Array] = None) -> Array:
        w0 = (
            jnp.zeros((self.n_features,), jnp.float32)
            if warm_state is None
            else warm_state
        )
        # reg_weight is traced (not closed over) so hyperparameter tuning can
        # mutate self.reg_weight between runs without a stale compiled value.
        return self._train_sm(
            self.dist,
            self._block_offsets(offsets),
            w0,
            jnp.asarray(self.reg_weight, jnp.float32),
        )

    def score(self, state: Array) -> Array:
        blocked = self._score_sm(self.dist, state)
        return blocked.reshape(-1)[: self.n_rows]

    def finalize(self, state: Array, offsets=None) -> FixedEffectModel:
        variances = None
        if self.problem.config.compute_variances and offsets is None:
            import logging

            logging.getLogger(__name__).warning(
                "coordinate %s: compute_variances requires finalize(...,"
                " offsets=...) (the estimator passes residual offsets); "
                "the model will carry no variances",
                self.name,
            )
        if self.problem.config.compute_variances and offsets is not None:
            # One psum'd squared-column reduction over the mesh, with the
            # Hessian evaluated at the full final margins (residual offsets
            # included) — same semantics as the single-device path.
            variances = self._var_sm(
                self.dist,
                self._block_offsets(jnp.asarray(offsets, jnp.float32)),
                state,
                jnp.asarray(self.reg_weight, jnp.float32),
            )
        return FixedEffectModel(
            GeneralizedLinearModel(Coefficients(state, variances), self.task),
            self.feature_shard,
        )


def _pad_block_entities(block: EntityBlock, multiple: int, sentinel: int):
    """Pad the entity axis to a multiple of the mesh size.  Padding lanes
    carry zero weights (solve to 0 under L2) and sentinel row indices
    (scatter into the discarded trailing slot)."""
    E = block.n_entities
    target = ((E + multiple - 1) // multiple) * multiple
    pad = target - E
    if pad == 0:
        return block
    return EntityBlock(
        X=jnp.pad(block.X, ((0, pad), (0, 0), (0, 0))),
        labels=jnp.pad(block.labels, ((0, pad), (0, 0))),
        weights=jnp.pad(block.weights, ((0, pad), (0, 0))),
        col_map=jnp.pad(block.col_map, ((0, pad), (0, 0)), constant_values=-1),
        row_index=jnp.pad(
            block.row_index, ((0, pad), (0, 0)), constant_values=sentinel
        ),
        n_entities=target,
        rows_per_entity=block.rows_per_entity,
        block_dim=block.block_dim,
        x_minor=block.x_minor,
    )


def shard_dataset_entities(
    dataset: RandomEffectDataset, mesh
) -> RandomEffectDataset:
    """The dataset with every block's ENTITY axis padded to the mesh size
    and placed sharded over it — the one placement both the plain and the
    factored entity-sharded coordinates build on."""
    n_dev = mesh.devices.size
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    sentinel = dataset.n_global_rows

    def place(block):
        if block is None:
            return None
        padded = _pad_block_entities(block, n_dev, sentinel)
        return jax.tree.map(
            lambda x: jax.device_put(x, sharding), padded
        )

    # Passive rows index lanes of the whole block: replicated, each device
    # scores them against the (sharded) coefficients it is handed.
    replicated = NamedSharding(mesh, P())
    return dataclasses.replace(
        dataset,
        blocks=[place(b) for b in dataset.blocks],
        passive_blocks=[
            None if p is None
            else jax.tree.map(lambda x: jax.device_put(x, replicated), p)
            for p in dataset.passive_blocks
        ],
    )


class EntityShardedRandomEffectCoordinate(RandomEffectCoordinate):
    """Random-effect coordinate with entity-axis sharding over a mesh."""

    def __init__(
        self,
        name: str,
        dataset: RandomEffectDataset,
        mesh,
        task: str,
        config: GlmOptimizationConfig,
        reg_weight: float = 0.0,
        feature_shard: str = "global",
        entity_key: str = "",
    ):
        dataset = shard_dataset_entities(dataset, mesh)
        super().__init__(
            name, dataset, task, config, reg_weight,
            feature_shard=feature_shard, entity_key=entity_key,
        )
        self.mesh = mesh

    def finalize(self, state, offsets=None):
        # Drop padding lanes (entity_ids lists are shorter than padded E);
        # the base implementation iterates entity_ids, so padding lanes are
        # skipped naturally.
        return super().finalize(state, offsets=offsets)


def entity_sharded_factored_coordinate(
    name: str,
    dataset: RandomEffectDataset,
    mesh,
    task: str,
    config: GlmOptimizationConfig,
    rank: int,
    **kwargs,
):
    """Factored random effect with entity-axis sharding over a mesh.

    The factored coordinate's training program is ONE jitted alternation
    over block pytrees, so sharded placement is all the distribution it
    needs: the latent step's vmapped per-entity solves are elementwise
    across lanes (XLA partitions them with zero communication — the
    ``mapPartitions`` property), and the projection step's gradient
    scatter from sharded ``(E, D, rank)`` contributions into the
    REPLICATED ``V`` gradient is exactly the cross-shard psum the shared
    projection fit needs — GSPMD inserts it; no hand-written collective.
    A factory (placement + delegation), not a subclass: the factored
    constructor's jit closures must see only ready-sharded blocks.
    """
    from photon_ml_tpu.game.factored import FactoredRandomEffectCoordinate

    coord = FactoredRandomEffectCoordinate(
        name, shard_dataset_entities(dataset, mesh), task, config,
        rank, **kwargs,
    )
    coord.mesh = mesh
    return coord
