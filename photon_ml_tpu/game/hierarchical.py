"""Hierarchical random-effect execution: the bucket ladder sharded
across mesh devices.

The bucket ladder (game/data.py) turns one random effect into a list of
independent dense blocks; ``RandomEffectCoordinate`` runs them all on one
device, so per-coordinate seconds stay flat no matter how many devices
the mesh has.  Per-entity
solves are embarrassingly parallel — Snap ML's nested node/accelerator
hierarchy (PAPERS.md) — so this module distributes the ladder itself:

- **Large buckets split** along the entity axis with the existing
  ``NamedSharding(mesh, P(DATA_AXIS))`` placement
  (game/distributed.py): the vmapped solver is elementwise across
  lanes, so GSPMD partitions it with zero communication.
- **Small buckets pack whole** onto single devices by greedy
  cost-balanced assignment (LPT over padded-FLOP costs): a 4-entity
  bucket sharded 8 ways would pad 2× and pay collective overhead for
  nothing — it runs where it lands, concurrently with its neighbours
  (per-device program dispatch is async, so devices overlap).

Parity contract: the plan only changes WHERE each block's program runs,
never the block shapes or the per-bucket math, and the score scatter
re-runs on one device in exactly ``_re_score_all_jit``'s block order —
so a PACKED block's results are bit-for-bit the single-device
coordinate's.  A SPLIT block runs the same vmapped solve at another
batch width (its lanes divided over the mesh), which is another compiled
program: a lane's f32 sums may be ordered by that width, and results
agree to rounding, not to the bit (tests/test_game_hierarchical.py holds
packed blocks to the bit and split blocks to 4 ulp of the block's
largest entry; XLA:CPU shows up to 1).  Contrast the repacker
(game/data.py), which changes realized shapes and moves results within
float tolerance vs the geometric ladder.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import telemetry as telemetry_mod
from photon_ml_tpu.chaos import core as chaos_mod
from photon_ml_tpu.game.coordinates import (
    RandomEffectCoordinate,
    _layout_sig,
    _re_train_all_jit,
)
from photon_ml_tpu.game.data import EntityBlock, RandomEffectDataset
from photon_ml_tpu.game.distributed import (
    DATA_AXIS,
    NamedSharding,
    P,
    _pad_block_entities,
)
from photon_ml_tpu.optim.problem import GlmOptimizationConfig

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class BucketShardPlan:
    """Where each bucket of one random-effect ladder executes.

    ``placements[b]`` is ``("split",)`` — block b's entity axis sharded
    over the whole mesh — or ``("pack", k)`` — block b resident whole on
    device k.  ``imbalance_ratio`` is max/mean padded-FLOP load across
    devices (1.0 = perfectly balanced; the ``game_shard_imbalance_ratio``
    gauge).
    """

    placements: tuple
    n_devices: int
    imbalance_ratio: float

    @property
    def n_split(self) -> int:
        return sum(1 for p in self.placements if p[0] == "split")

    @property
    def n_packed(self) -> int:
        return len(self.placements) - self.n_split


def plan_bucket_shards(
    blocks: list[EntityBlock],
    n_devices: int,
    split_factor: float = 0.5,
) -> BucketShardPlan:
    """Greedy cost-balanced placement of a bucket ladder on ``n_devices``.

    Cost model: padded FLOPs ``E·R·D`` per block (the same objective the
    repacker minimizes).  A block SPLITS across the mesh when its cost
    is at least ``split_factor`` of the ideal per-device share AND it
    has at least one entity lane per device (splitting smaller blocks
    pads more than it parallelizes).  Remaining blocks pack via longest
    processing time: sorted by descending cost (ascending index on
    ties), each onto the currently least-loaded device — deterministic,
    within 4/3 of optimal makespan.  Split blocks load every device
    with cost/n_devices.
    """
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    costs = [
        b.n_entities * b.rows_per_entity * b.block_dim for b in blocks
    ]
    total = sum(costs)
    if not blocks or n_devices == 1 or total == 0:
        return BucketShardPlan(
            placements=tuple(("pack", 0) for _ in blocks),
            n_devices=n_devices,
            imbalance_ratio=1.0,
        )
    ideal = total / n_devices
    loads = np.zeros(n_devices)
    placements: list = [None] * len(blocks)
    packable = []
    for bi, (block, cost) in enumerate(zip(blocks, costs)):
        if cost >= split_factor * ideal and block.n_entities >= n_devices:
            placements[bi] = ("split",)
            loads += cost / n_devices
        else:
            packable.append((cost, bi))
    for cost, bi in sorted(packable, key=lambda t: (-t[0], t[1])):
        k = int(np.argmin(loads))
        placements[bi] = ("pack", k)
        loads[k] += cost
    mean = float(loads.mean())
    imbalance = float(loads.max() / mean) if mean > 0 else 1.0
    return BucketShardPlan(
        placements=tuple(placements),
        n_devices=n_devices,
        imbalance_ratio=imbalance,
    )


@functools.lru_cache(maxsize=64)
def _re_block_scores_jit(layout_sig: tuple):
    """Per-block raw score vectors ``(E, R)`` for a placement group —
    the einsum half of ``_re_score_all_jit``, dispatched on the group's
    home device; the scatter half runs later on ONE device in global
    block order so the accumulation order (and the f32 bits) match the
    single-device program.  Memoized on layout like every other block
    program cache (eviction granule, see ``_layout_sig``)."""

    def _scores(blocks, coefs_list):
        return [
            jnp.einsum("erd,ed->er", b.x_erd, c)
            for b, c in zip(blocks, coefs_list)
        ]

    return jax.jit(_scores)


@jax.jit
def _passive_scores_jit(rows, coefs):
    return rows.scores(coefs)


@functools.lru_cache(maxsize=64)
def _re_scatter_jit(n_rows: int, layout_sig: tuple):
    """The scatter half: per-block (row_index, scores) pairs accumulate
    into one row vector in block order — active then passive per block,
    exactly ``_re_score_all_jit``'s order, so the result is bitwise the
    single-device score."""

    def _scatter(row_indexes, scores):
        total = jnp.zeros((n_rows + 1,), jnp.float32)
        for ri, s in zip(row_indexes, scores):
            total = total.at[ri.ravel()].add(s.ravel())
        return total[:n_rows]

    return jax.jit(_scatter)


class ShardedBucketRandomEffectCoordinate(RandomEffectCoordinate):
    """Random-effect coordinate whose bucket ladder is distributed over a
    mesh by a :class:`BucketShardPlan`.

    Supersedes ``EntityShardedRandomEffectCoordinate`` (which shards
    EVERY block over the whole mesh): the hierarchical plan splits only
    the blocks big enough to amortize it and packs the long tail whole
    onto devices, so small buckets stop paying mesh-wide padding.  State
    layout, ``finalize`` and variances are inherited — the state is
    still one ``(E, D)`` array per block in global block order.
    """

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
        split_factor: float = 0.5,
    ):
        devices = list(mesh.devices.flat)
        self.plan = plan_bucket_shards(
            dataset.blocks, len(devices), split_factor=split_factor
        )
        telemetry_mod.current().gauge("game_shard_imbalance_ratio").set(
            self.plan.imbalance_ratio
        )
        sharding = NamedSharding(mesh, P(DATA_AXIS))
        sentinel = dataset.n_global_rows

        def place(block, placement):
            if block is None:
                return None
            if placement[0] == "split":
                padded = _pad_block_entities(
                    block, len(devices), sentinel
                )
                return jax.tree.map(
                    lambda x: jax.device_put(x, sharding), padded
                )
            return jax.tree.map(
                lambda x: jax.device_put(x, devices[placement[1]]), block
            )

        placed = dataclasses.replace(
            dataset,
            blocks=[
                place(b, p)
                for b, p in zip(dataset.blocks, self.plan.placements)
            ],
            # Passive rows are scored where the score scatter runs.
            passive_blocks=[
                None if p is None
                else jax.tree.map(
                    lambda x: jax.device_put(jnp.asarray(x), devices[0]), p)
                for p in dataset.passive_blocks
            ],
        )
        super().__init__(
            name, placed, task, config, reg_weight,
            feature_shard=feature_shard, entity_key=entity_key,
        )
        self.mesh = mesh
        # Dispatch groups: the split group (one SPMD program over the
        # mesh) plus one group per device holding packed blocks.  Group
        # order is deterministic (split first, then device index) but
        # does not affect results — only the score scatter's BLOCK
        # order matters, and that is global.
        groups: dict = {}
        for bi, p in enumerate(self.plan.placements):
            groups.setdefault(p, []).append(bi)
        self._groups = sorted(
            groups.items(), key=lambda kv: (kv[0][0] != "split", kv[0])
        )
        self._group_train_jits = {
            key: _re_train_all_jit(
                self.task, config,
                _layout_sig([placed.blocks[i] for i in idxs]),
            )
            for key, idxs in self._groups
        }
        # The score scatter is ONE program on a home device, so its
        # inputs must be colocated there.  Row indexes are static —
        # stage them once; per-call score vectors (small: (E, R) f32 vs
        # the (E, R, D) blocks) move at score time.
        self._devices = devices
        self._home = devices[0]

        def home(x):
            return jax.device_put(jnp.asarray(x), devices[0])

        self._home_row_index = [home(b.row_index) for b in placed.blocks]

    def train(self, offsets: Array, warm_state=None) -> list[Array]:
        l1 = jnp.asarray(
            self.config.regularization.l1_weight(1.0) * self.reg_weight,
            jnp.float32,
        )
        l2 = jnp.asarray(
            self.config.regularization.l2_weight(1.0) * self.reg_weight,
            jnp.float32,
        )
        offsets = jnp.asarray(offsets, jnp.float32)
        # Each dispatch group needs offsets on ITS device set — a
        # committed input pinned elsewhere (the descent's running score
        # array) would clash inside the group jit.  Split groups take a
        # mesh-replicated copy, each packed device its own committed
        # copy; identical bits everywhere, so results never move.
        off_split = jax.device_put(
            offsets, NamedSharding(self.mesh, P())
        )
        off_for = {
            key: (
                off_split
                if key[0] == "split"
                else jax.device_put(offsets, self._devices[key[1]])
            )
            for key, _ in self._groups
        }
        state: list = [None] * len(self.dataset.blocks)
        for key, idxs in self._groups:
            # The per-device dispatch seam: a fault here aborts the
            # update with some groups already in flight; device programs
            # are pure functions of (blocks, offsets, w0), so the
            # retried update is bitwise the uninterrupted one.
            chaos_mod.maybe_fail(
                "game.bucket_shard", placement=key, blocks=len(idxs)
            )
            blocks = [self.dataset.blocks[i] for i in idxs]
            w0s = [
                (
                    warm_state[i]
                    if warm_state is not None
                    else jnp.zeros(
                        (b.n_entities, b.block_dim), jnp.float32
                    )
                )
                for i, b in zip(idxs, blocks)
            ]
            outs, _counts = self._group_train_jits[key](
                blocks, off_for[key], w0s, l1, l2
            )
            for i, out in zip(idxs, outs):
                state[i] = out
        return state

    def score(self, state: list[Array]) -> Array:
        # Einsums run on each block's home device (async, concurrent);
        # the scatter-accumulate runs as ONE program in global block
        # order — active then passive per block — matching the
        # single-device ``_re_score_all_jit`` bit for bit.
        per_block_scores: list = [None] * len(self.dataset.blocks)
        per_block_passive: list = [None] * len(self.dataset.blocks)
        for key, idxs in self._groups:
            blocks = [self.dataset.blocks[i] for i in idxs]
            coefs = [state[i] for i in idxs]
            outs = _re_block_scores_jit(_layout_sig(blocks))(
                blocks, coefs
            )
            for i, out in zip(idxs, outs):
                per_block_scores[i] = out
        passive = self.dataset.passive_blocks or [None] * len(state)
        for i, rows in enumerate(passive):
            if rows is not None:
                per_block_passive[i] = _passive_scores_jit(
                    rows, jax.device_put(state[i], self._home))
        row_indexes: list = []
        scores: list = []
        for bi in range(len(self.dataset.blocks)):
            row_indexes.append(self._home_row_index[bi])
            scores.append(jax.device_put(per_block_scores[bi], self._home))
            if per_block_passive[bi] is not None:
                row_indexes.append(passive[bi].row_index)
                scores.append(per_block_passive[bi])
        out = _re_scatter_jit(
            self.dataset.n_global_rows,
            _layout_sig(row_indexes),
        )(row_indexes, scores)
        # Hand the score back mesh-replicated: the descent sums it with
        # mesh-placed fixed-effect scores, and a home-device-committed
        # array would clash there.  Pure transfer — bits unchanged.
        return jax.device_put(out, NamedSharding(self.mesh, P()))
