"""Out-of-core random effects: entity blocks larger than device memory.

The reference's ``RandomEffectDataset`` is an RDD — *cluster-memory*-scaled:
entities hash-partitioned across executors, each executor training its
partition's per-entity GLMs locally (SURVEY.md §2 RandomEffectDataset row,
§3.2).  At BASELINE config 5's scale (1B rows, user+item+context random
effects) the per-entity datasets collectively dwarf one chip's HBM, and
entity-sharding only divides by ``n_devices`` — it never bounds the
PER-DEVICE footprint.

This module bounds it.  The per-entity solves are embarrassingly
independent (no cross-block state beyond the shared per-row offsets), so
the blocks stream the way the row-chunk store streams fixed-effect data:

1. the dataset is built HOST-resident (``device=False``);
2. oversized blocks are split along the ENTITY axis into uniform-shape
   sub-slices (one compiled program per original block shape — the last
   slice pads with zero-weight lanes, which solve to w=0 under any L2);
3. slices are packed into PASS GROUPS whose device footprint fits half the
   budget — half, because the next group's transfer is enqueued while the
   current group solves (double buffering, the chunk-store discipline);
4. per-entity coefficients live in host numpy between passes; only the
   global offset/score row arrays stay device-resident.

With a mesh, each slice's entity axis is additionally sharded over the
mesh (the ``EntityShardedRandomEffectCoordinate`` layout) — the budget
then bounds the PER-DEVICE bytes, and the vmap'd solver still partitions
with zero communication.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu import telemetry as telemetry_mod
from photon_ml_tpu.chaos import core as chaos_mod
from photon_ml_tpu.data.prefetch import TransferStats, run_prefetched
from photon_ml_tpu.game.coordinates import (
    RandomEffectCoordinate,
    _gather_block_offsets,
    _make_block_solver,
)
from photon_ml_tpu.game.data import EntityBlock, RandomEffectDataset
from photon_ml_tpu.game.hierarchical import plan_bucket_shards
from photon_ml_tpu.ops import losses as losses_lib
from photon_ml_tpu.optim.problem import GlmOptimizationConfig
from photon_ml_tpu.optim.streaming import HotChunkCache
from photon_ml_tpu.parallel.distributed import DATA_AXIS

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class _Slice:
    """One schedulable unit: lanes [lane_lo, lane_hi) of block ``block_idx``,
    padded to ``padded_e`` entities (uniform across the block's slices so
    every slice of a block shares ONE compiled program).  ``placement``
    follows the block's :class:`~photon_ml_tpu.game.hierarchical
    .BucketShardPlan` entry — ``("split",)`` shards the slice's entity
    axis over the whole mesh, ``("pack", k)`` lands it whole on device k
    (ignored when there is no mesh)."""

    block_idx: int
    lane_lo: int
    lane_hi: int
    padded_e: int
    bytes: int
    placement: tuple = ("split",)


def _lane_bytes(block: EntityBlock, passive: Optional[EntityBlock]) -> int:
    """Device bytes one entity lane costs in a pass: active leaves + the
    gathered offsets + coefficients in and out, plus the lane's score-only
    passive companion (score passes carry both; one conservative number
    keeps train and score on a single plan)."""
    r, d = block.rows_per_entity, block.block_dim
    active = 4 * (r * d + 4 * r + 2 * d)  # X, labels/weights/row_index/off, cmap+w
    out = 4 * d
    psv = 0
    if passive is not None:
        rp = passive.rows_per_entity
        psv = 4 * (rp * d + 3 * rp)  # Xp, labels/weights/row_index
    return active + out + psv


@functools.lru_cache(maxsize=64)
def _ooc_slice_jits(
    task: str, config: GlmOptimizationConfig, slice_sig: tuple
):
    # slice_sig is unused inside — it is the cache's eviction granule
    # (see coordinates._layout_sig): slice shapes vary per dataset/plan,
    # and one shared wrapper would otherwise pin an executable per
    # distinct layout for process lifetime.
    solver = _make_block_solver(task, config)
    loss = losses_lib.get(task)

    def _solve_slice(block, offsets, w0, l1, l2):
        return solver(
            block, _gather_block_offsets(offsets, block), w0, l1, l2
        )

    def _var_slice(block, coefs, offsets, l2):
        off_b = _gather_block_offsets(offsets, block)
        m = jnp.einsum("erd,ed->er", block.x_erd, coefs) + off_b
        d2w = block.weights * loss.d2(m, block.labels)
        diag = jnp.einsum("er,erd->ed", d2w, block.x_erd * block.x_erd) + l2
        return 1.0 / jnp.maximum(diag, 1e-12)

    return jax.jit(_solve_slice), jax.jit(_var_slice)


@functools.lru_cache(maxsize=None)
def _ooc_score_jit():
    def _score_slice(total, X, row_index, coefs):
        s = jnp.einsum("erd,ed->er", X, coefs)
        return total.at[row_index.ravel()].add(s.ravel())

    # total is donated: each pass group's scatter reuses the buffer
    # instead of allocating a second (n_rows+1) array per step.
    return jax.jit(_score_slice, donate_argnums=0)


@functools.lru_cache(maxsize=32)  # size-keyed: bounded (see coordinates.py)
def _ooc_zeros_jit(n_rows: int):
    return jax.jit(lambda: jnp.zeros((n_rows + 1,), jnp.float32))


def _host_leaf(x) -> np.ndarray:
    if isinstance(x, jax.Array):
        raise ValueError(
            "out-of-core random effects need a HOST-resident dataset — "
            "build it with build_random_effect_dataset(..., device=False)"
        )
    return np.asarray(x)


def _cut(x, lo: int, hi: int, padded_e: int, fill):
    """Entity-axis slice [lo, hi) padded to ``padded_e`` lanes with
    ``fill`` — the one pad-and-slice implementation for both the full
    block slicer and the score path's slimmed (X, row_index) slices."""
    x = x[lo:hi]
    pad = padded_e - x.shape[0]
    if pad == 0:
        return x
    width = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return np.pad(x, width, constant_values=fill)


def _slice_block(
    block: EntityBlock, lo: int, hi: int, padded_e: int, sentinel: int
) -> EntityBlock:
    """Host-side entity-axis slice [lo, hi), padded to ``padded_e`` lanes.
    Padding lanes carry zero weights (solve to 0), col_map -1, and sentinel
    row indices (scatter into the discarded trailing slot)."""
    return EntityBlock(
        X=_cut(block.X, lo, hi, padded_e, 0),
        labels=_cut(block.labels, lo, hi, padded_e, 0),
        weights=_cut(block.weights, lo, hi, padded_e, 0),
        col_map=_cut(block.col_map, lo, hi, padded_e, -1),
        row_index=_cut(block.row_index, lo, hi, padded_e, sentinel),
        n_entities=padded_e,
        rows_per_entity=block.rows_per_entity,
        block_dim=block.block_dim,
        x_minor=block.x_minor,
    )


class OutOfCoreRandomEffectCoordinate(RandomEffectCoordinate):
    """RandomEffectCoordinate whose dataset exceeds device memory.

    Same ``train(offsets, warm) → state`` / ``score(state)`` surface as the
    resident coordinate; identical numerics (the very same memoized block
    solver runs on each slice, and entity-axis slicing/padding never changes
    a lane's math).  State is a list of HOST (E, D) numpy arrays.
    """

    #: Subclasses whose jitted programs mix slice payloads with
    #: whole-pass device state (the factored projection accumulator)
    #: cannot commit slices to individual devices — they disable the
    #: hierarchical plan and keep the legacy everything-split layout.
    _supports_packed = True
    #: Subclasses with their own payload formats (the factored variant
    #: streams projected features, not raw blocks) opt out of the hot
    #: working-set cache — the base-class train/score are the only
    #: consumers of the cached slice trees.
    _supports_hot_cache = True

    def __init__(
        self,
        name: str,
        dataset: RandomEffectDataset,
        task: str,
        config: GlmOptimizationConfig,
        reg_weight: float = 0.0,
        feature_shard: str = "global",
        entity_key: str = "",
        device_budget_bytes: int = 256 * 2**20,
        mesh=None,
        prefetch_depth: int = 2,
        split_factor: float = 0.5,
        hot_budget_bytes: int = 0,
    ):
        # Deliberately NOT calling super().__init__: the resident
        # constructor jits one whole-dataset program, which is exactly what
        # a larger-than-HBM dataset cannot do.
        self.name = name
        self.dataset = dataset
        # Slices cut blocks by lanes, so the passive rows (stored flat)
        # are read through their lane-aligned view, on the host.
        self._passive_blocks = dataset.lane_aligned_passive()
        self.task = losses_lib.get(task).name
        self.config = config
        self.reg_weight = reg_weight
        self.feature_shard = feature_shard
        self.entity_key = entity_key or name
        self.device_budget_bytes = int(device_budget_bytes)
        if prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {prefetch_depth}"
            )
        self.prefetch_depth = int(prefetch_depth)
        #: h2d observability for this coordinate's group transfers — the
        #: same TransferStats the streamed fixed effect exposes.
        self.transfer_stats = TransferStats()
        if mesh is not None and jax.process_count() > 1:
            # Same early rejection as StreamingFixedEffectCoordinate:
            # _put would device_put per-process host numpy onto a
            # pod-spanning sharding — unsupported/undefined — and only
            # deep inside the first train pass.
            raise NotImplementedError(
                "out-of-core random effects are single-host for now: "
                "entity blocks live in one process's RAM, and slicing "
                "them onto a multi-process pod mesh is not wired up"
            )
        self.mesh = mesh
        self._solver = _make_block_solver(task, config)
        self._sharding = (
            None if mesh is None else NamedSharding(mesh, P(DATA_AXIS))
        )
        self._devices = (
            None if mesh is None else list(mesh.devices.flat)
        )
        # Hierarchical placement (game/hierarchical.py): big blocks split
        # over the mesh, the long tail packs whole onto devices — the
        # slices inherit their block's placement, so small buckets stop
        # paying mesh-quantum padding and the devices' async dispatch
        # overlaps their solves.
        self.bucket_plan = (
            None
            if mesh is None or not self._supports_packed
            else plan_bucket_shards(
                dataset.blocks, len(self._devices),
                split_factor=split_factor,
            )
        )
        if self.bucket_plan is not None:
            telemetry_mod.current().gauge(
                "game_shard_imbalance_ratio"
            ).set(self.bucket_plan.imbalance_ratio)

        for b in dataset.blocks:
            jax.tree.map(_host_leaf, b)
        for b in dataset.passive_blocks:
            if b is not None:
                jax.tree.map(_host_leaf, b)

        self.pass_plan = self._build_plan()
        #: high-water mark of pass groups with live device buffers —
        #: the structural "bounded memory" witness the tests pin
        #: (≤ prefetch_depth; 2 by default: the solving group plus the
        #: prefetched next one).
        self.live_groups_high_water = 0

        # Process-wide memoized programs (per-instance jits re-compiled
        # identical HLO for every new coordinate — each fit, grid point,
        # or fresh estimator).
        slice_sig = tuple(sorted({
            (s.padded_e,
             dataset.blocks[s.block_idx].rows_per_entity,
             dataset.blocks[s.block_idx].block_dim)
            for group in self.pass_plan for s in group
        }))
        self._solve_jit, self._var_jit = _ooc_slice_jits(
            self.task, config, slice_sig
        )
        self._score_jit = _ooc_score_jit()
        self._zeros_jit = _ooc_zeros_jit(dataset.n_global_rows)
        # Pipelined-descent prestage state: one background packer at a
        # time, single-producer/single-consumer handed off via an Event
        # (no shared mutable state beyond the record, so no lock).
        self._plan_index = {
            id(g): gi for gi, g in enumerate(self.pass_plan)
        }
        self._prestage_rec = None
        # Hot working-set cache (optim/streaming.py HotChunkCache,
        # generalized to per-device hot sets): a hot pass group's STATIC
        # slice payloads — the already-placed block/score trees, sharded
        # or device-committed per the bucket plan — stay resident, so
        # repeat passes skip their host pack AND h2d transfer and stream
        # only the dynamic part (warm starts / coefficients).  The same
        # compiled programs serve hot and cold groups in the same order,
        # so results are bitwise identical either way.  Blocks are
        # immutable for the coordinate's lifetime, so entries never go
        # stale; the wanted set is picked ONCE here, biggest transfers
        # first (the importance of a static payload IS its wire bytes).
        if hot_budget_bytes < 0:
            raise ValueError(
                f"hot_budget_bytes must be >= 0, got {hot_budget_bytes}"
            )
        self.hot_budget_bytes = int(hot_budget_bytes)
        self._hot_cache = None
        self._hot_bytes: dict = {}
        if self.hot_budget_bytes and self._supports_hot_cache:
            self._hot_cache = HotChunkCache(self.hot_budget_bytes)
            for gi, group in enumerate(self.pass_plan):
                for kind in ("train", "score"):
                    self._hot_bytes[(kind, gi)] = (
                        self._group_static_bytes(kind, group)
                    )
            self._hot_cache.replan(
                self._hot_bytes, self._hot_bytes.__getitem__
            )

    # -- pass planning -----------------------------------------------------

    def _build_plan(self) -> list[list[_Slice]]:
        """Split blocks along the entity axis and pack slices into groups.

        Each original block is cut into ``n_parts`` uniform sub-slices
        (ceil division, padded to the mesh quantum) so the whole block
        contributes ONE compiled shape; groups then fill greedily to the
        per-pass budget (= budget/prefetch_depth — the pipeline keeps up
        to that many groups live on the device; depth 2 is the classic
        double-buffering reserve).
        """
        budget = (
            self.device_budget_bytes - self._budget_overhead_bytes()
        ) // self.prefetch_depth
        if budget <= 0:
            raise ValueError(
                f"random-effect coordinate {self.name!r}: "
                f"device_budget_bytes={self.device_budget_bytes} does not "
                f"cover the {self._budget_overhead_bytes()}-byte "
                "whole-pass-resident overhead"
            )
        plan: list[list[_Slice]] = []
        group: list[_Slice] = []
        group_bytes = 0
        for bi, block in enumerate(self.dataset.blocks):
            passive = (
                self._passive_blocks[bi]
                if self._passive_blocks else None
            )
            # Placement sets the lane quantum: split slices need one
            # shardable lane per mesh device, packed (and unmeshed)
            # slices run whole on one device and pad nothing extra.
            placement = (
                ("split",)
                if self.bucket_plan is None
                else self.bucket_plan.placements[bi]
            )
            q = (
                len(self._devices)
                if self.mesh is not None and placement[0] == "split"
                else 1
            )
            per_lane = _lane_bytes(block, passive) + self._extra_lane_bytes(
                block
            )
            e = block.n_entities
            if per_lane * q > budget:
                raise ValueError(
                    f"random-effect coordinate {self.name!r}: one "
                    f"{q}-entity slice of block {bi} "
                    f"(R={block.rows_per_entity}, D={block.block_dim}) "
                    f"needs {per_lane * q} bytes, over the "
                    f"per-pass budget {budget} (= (device_budget_bytes "
                    f"- {self._budget_overhead_bytes()} overhead) / "
                    f"prefetch_depth={self.prefetch_depth}). "
                    "Raise device_budget_bytes or lower "
                    "max_rows_per_entity / bucket_growth"
                )
            # Quantum-multiple lane cap, so the final round-up below can
            # never push a slice past the budget.
            lanes_per_pass = max(q, (budget // per_lane) // q * q)
            n_parts = max(1, -(-e // lanes_per_pass))  # ceil
            sub_e = -(-e // n_parts)
            sub_e = ((sub_e + q - 1) // q) * q  # quantum-aligned
            for lo in range(0, e, sub_e):
                hi = min(lo + sub_e, e)
                s = _Slice(
                    bi, lo, hi, sub_e, per_lane * sub_e, placement
                )
                if group and group_bytes + s.bytes > budget:
                    plan.append(group)
                    group, group_bytes = [], 0
                group.append(s)
                group_bytes += s.bytes
        if group:
            plan.append(group)
        return plan

    def _group_static_bytes(self, kind: str, group) -> int:
        """Wire bytes of one pass group's pass-invariant payloads — the
        train path's sliced blocks, or the score path's (X, row_index)
        active/passive pairs.  Budget arithmetic for the hot cache; the
        dynamic leaves (w0, coefs) stream every pass and don't count."""
        total = 0
        for s in group:
            b = self.dataset.blocks[s.block_idx]
            r, d = b.rows_per_entity, b.block_dim
            if kind == "train":
                # X, labels, weights, row_index (E,R) + col_map (E,D)
                per = 4 * (r * d + 3 * r + d)
            else:
                per = 4 * (r * d + r)  # X + row_index
                if self._passive_blocks:
                    pb = self._passive_blocks[s.block_idx]
                    if pb is not None:
                        rp = pb.rows_per_entity
                        per += 4 * (rp * d + rp)
            total += per * s.padded_e
        return total

    def _probe_hot(self, kind: str) -> dict:
        """Resident static trees by group index for this pass — one
        locked cache probe per group, before any pipeline thread
        starts (the streaming objective's hot/cold-split discipline)."""
        hot: dict = {}
        if self._hot_cache is not None:
            for gi in range(len(self.pass_plan)):
                d = self._hot_cache.get((kind, gi))
                if d is not None:
                    hot[gi] = d
        return hot

    def _extra_lane_bytes(self, block: EntityBlock) -> int:
        """Subclass hook: additional device bytes one lane costs beyond
        the raw block leaves (e.g. the factored variant's projected
        features and latent vectors)."""
        return 0

    def _budget_overhead_bytes(self) -> int:
        """Subclass hook: device bytes resident for the WHOLE pass
        (shared state like the factored projection + its gradient),
        carved out of the budget before groups are sized."""
        return 0

    def _put(self, tree):
        if self._sharding is None:
            return jax.device_put(tree)
        return jax.tree.map(
            lambda x: jax.device_put(x, self._sharding), tree
        )

    def _put_group(self, group, payloads, pack_to_default=False):
        """One pass group's transfer — one call per group on the
        transfer thread (the bounded-memory tests hook this to count
        dispatched-but-unconsumed groups)."""
        return [
            self._put_one(s.placement, p, pack_to_default)
            for s, p in zip(group, payloads)
        ]

    def _put_one(self, placement, tree, pack_to_default=False):
        """Placement-aware transfer for one slice payload.  Split slices
        shard over the mesh; packed slices land whole on their assigned
        device — except when ``pack_to_default`` (the score path: every
        scatter folds into ONE accumulator, and a packed slice committed
        to device k would force that accumulator to bounce devices)."""
        if self._sharding is None:
            return jax.device_put(tree)
        if placement[0] == "pack":
            if pack_to_default:
                return jax.device_put(tree)
            dev = self._devices[placement[1]]
            return jax.tree.map(
                lambda x: jax.device_put(x, dev), tree
            )
        return jax.tree.map(
            lambda x: jax.device_put(x, self._sharding), tree
        )

    def _run_groups(self, make_host_group, consume, pack_to_default=False):
        """Prefetch-pipelined group runner (the chunk store's ingest
        pipeline, data/prefetch.py): a PACK thread slices the next
        groups on the host, a TRANSFER thread dispatches them and waits
        out their h2d completion, and the caller thread consumes the
        current one — host slicing, the link, and device compute all
        overlap, with at most ``prefetch_depth`` groups admitted by the
        permit accounting (which replaced the old hand-rolled double
        buffer — and its reference-lifetime subtleties — outright).
        ``make_host_group(group) → host pytree list``; per-stage wall
        attribution lands in ``self.transfer_stats``."""
        plan = self.pass_plan
        self.live_groups_high_water = 0
        if not plan:
            return

        self.live_groups_high_water = run_prefetched(
            len(plan),
            lambda gi: (plan[gi], make_host_group(plan[gi])),
            lambda item: self._put_group(*item, pack_to_default),
            lambda gi, dev: consume(plan[gi], dev),
            depth=self.prefetch_depth,
            stats=self.transfer_stats,
        )

    # -- pipelined-descent prestage ----------------------------------------

    def _train_state_init(self, warm_state) -> list[np.ndarray]:
        return [
            (
                np.zeros((b.n_entities, b.block_dim), np.float32)
                if warm_state is None
                # copy: np.asarray of a jax array (checkpoint resume) is
                # a read-only zero-copy view, and this buffer is written
                # into.
                else np.array(warm_state[bi], np.float32)
            )
            for bi, b in enumerate(self.dataset.blocks)
        ]

    def _train_host_group(self, group, state, with_blocks=True) -> list:
        # with_blocks=False builds only the dynamic half (warm-start
        # lanes) — the hot-cache path, where the sliced block already
        # sits on device and packing it again would waste the savings.
        sentinel = self.dataset.n_global_rows
        out = []
        for s in group:
            block = self.dataset.blocks[s.block_idx]
            w0 = state[s.block_idx][s.lane_lo:s.lane_hi]
            pad = s.padded_e - w0.shape[0]
            if pad:
                w0 = np.pad(w0, ((0, pad), (0, 0)))
            out.append((
                _slice_block(
                    block, s.lane_lo, s.lane_hi, s.padded_e, sentinel
                ) if with_blocks else None,
                w0,
            ))
        return out

    def prestage(self, warm_state=None) -> None:
        """Background-pack the first ``prefetch_depth`` pass groups' host
        payloads while ANOTHER coordinate's solve owns the foreground
        (the pipelined descent schedule, game/descent.py).

        Packing is offset-independent — slices and warm-start lanes are
        pure functions of (dataset, plan, warm_state) — so the staged
        payloads are byte-identical to what ``train``'s pack thread
        would build, and results stay bitwise the unpipelined run's.
        The buffers are keyed to this exact ``warm_state`` object; a
        train call with any other warm state discards them.  Host RAM
        held is at most one pass budget (depth groups of budget/depth
        bytes).  The overlap actually achieved lands on the
        ``game_coordinate_overlap_seconds`` counter at take time."""
        self._drop_prestage()
        if not self.pass_plan:
            return
        n = min(self.prefetch_depth, len(self.pass_plan))
        rec = {
            "warm": warm_state,
            "buf": {},
            "t0": time.perf_counter(),
            "t_end": None,
        }

        def work():
            try:
                state = self._train_state_init(warm_state)
                for gi in range(n):
                    rec["buf"][gi] = self._train_host_group(
                        self.pass_plan[gi], state
                    )
            finally:
                rec["t_end"] = time.perf_counter()

        rec["thread"] = threading.Thread(
            target=work, name="game-ooc-prestage", daemon=True
        )
        self._prestage_rec = rec
        rec["thread"].start()

    def _drop_prestage(self) -> None:
        rec, self._prestage_rec = self._prestage_rec, None
        if rec is not None:
            rec["thread"].join()

    def _take_prestage(self, warm_state) -> dict:
        rec, self._prestage_rec = self._prestage_rec, None
        if rec is None:
            return {}
        t_take = time.perf_counter()
        rec["thread"].join()
        if rec["warm"] is not warm_state:
            # Stale hint (different warm start than announced): the
            # payloads would carry the WRONG w0 lanes — drop them.
            return {}
        overlap = max(0.0, min(rec["t_end"], t_take) - rec["t0"])
        telemetry_mod.current().counter(
            "game_coordinate_overlap_seconds"
        ).inc(overlap)
        return rec["buf"]

    # -- coordinate surface ------------------------------------------------

    def train(self, offsets: Array, warm_state=None) -> list[np.ndarray]:
        l1 = jnp.asarray(
            self.config.regularization.l1_weight(1.0) * self.reg_weight,
            jnp.float32,
        )
        l2 = jnp.asarray(
            self.config.regularization.l2_weight(1.0) * self.reg_weight,
            jnp.float32,
        )
        offsets = jnp.asarray(offsets, jnp.float32)
        # Each placement needs offsets on ITS device set — a committed
        # input pinned elsewhere (e.g. the caller's score array on
        # device 0) would clash inside the jit.  Split slices take a
        # mesh-replicated copy; each packed device gets its own
        # committed copy.  Staged once per train pass; identical bits
        # everywhere, so this never perturbs results.
        off_split = offsets
        off_by_dev = {}
        if self.mesh is not None:
            off_split = jax.device_put(
                offsets, NamedSharding(self.mesh, P())
            )
        if self.bucket_plan is not None:
            packed_devs = {
                s.placement[1]
                for group in self.pass_plan
                for s in group
                if s.placement[0] == "pack"
            }
            off_by_dev = {
                k: jax.device_put(offsets, self._devices[k])
                for k in sorted(packed_devs)
            }
        prestaged = self._take_prestage(warm_state)
        state = self._train_state_init(warm_state)
        hot = self._probe_hot("train")

        def host_group(group):
            gi = self._plan_index[id(group)]
            if gi in prestaged:
                payload = prestaged.pop(gi)
                if gi in hot:
                    # Prestage packed full payloads before this pass
                    # knew its hot set — keep just the dynamic half.
                    payload = [(None, w0) for _blk, w0 in payload]
                return payload
            return self._train_host_group(
                group, state, with_blocks=gi not in hot
            )

        def consume(group, dev):
            gi = self._plan_index[id(group)]
            # The per-device dispatch seam (mirrors the resident
            # hierarchical coordinate): a fault here aborts the update
            # mid-pass; per-bucket solves are pure functions of
            # (block, offsets, w0), so the retried update is bitwise
            # the uninterrupted one.
            chaos_mod.maybe_fail(
                "game.bucket_shard",
                coordinate=self.name,
                slices=len(group),
            )
            resident = hot.get(gi)
            blks = [
                blk if blk is not None else resident[si]
                for si, (blk, _w0) in enumerate(dev)
            ]
            # Dispatch every solve in the group first (async), then pull —
            # the pulls overlap the NEXT group's host slicing + transfer,
            # and packed slices' programs run concurrently on their
            # assigned devices.
            results = [
                self._solve_jit(
                    blk,
                    (
                        off_by_dev[s.placement[1]]
                        if s.placement[0] == "pack" and off_by_dev
                        else off_split
                    ),
                    w0, l1, l2,
                )
                for s, blk, (_b, w0) in zip(group, blks, dev)
            ]
            for s, res in zip(group, results):
                state[s.block_idx][s.lane_lo:s.lane_hi] = np.asarray(
                    res
                )[: s.lane_hi - s.lane_lo]
            if self._hot_cache is not None and resident is None:
                self._hot_cache.maybe_admit(
                    ("train", gi), blks, self._hot_bytes[("train", gi)]
                )

        self._run_groups(host_group, consume)
        return state

    def score(self, state) -> Array:
        sentinel = self.dataset.n_global_rows
        total = self._zeros_jit()
        hot = self._probe_hot("score")

        def host_group(group):
            # Score-only slices: just X + row_index (+ coefs) cross the
            # wire — labels/weights/col_map are ~30% of the lane bytes
            # and the score einsum/scatter never reads them.  A hot
            # group's static pair is already resident; only coefs cross.
            gi = self._plan_index[id(group)]
            resident = gi in hot
            out = []
            for s in group:
                coefs = _cut(
                    np.asarray(state[s.block_idx], np.float32),
                    s.lane_lo, s.lane_hi, s.padded_e, 0,
                )
                if resident:
                    out.append((None, None, coefs))
                    continue
                block = self.dataset.blocks[s.block_idx]
                active = (
                    _cut(block.X, s.lane_lo, s.lane_hi, s.padded_e, 0),
                    _cut(block.row_index, s.lane_lo, s.lane_hi,
                        s.padded_e, sentinel),
                )
                passive = None
                if self._passive_blocks:
                    pb = self._passive_blocks[s.block_idx]
                    if pb is not None:
                        passive = (
                            _cut(pb.X, s.lane_lo, s.lane_hi, s.padded_e, 0),
                            _cut(pb.row_index, s.lane_lo, s.lane_hi,
                                s.padded_e, sentinel),
                        )
                out.append((active, passive, coefs))
            return out

        def consume(group, dev):
            nonlocal total
            gi = self._plan_index[id(group)]
            resident = hot.get(gi)
            statics = []
            for si, (active, passive, coefs) in enumerate(dev):
                if active is None and resident is not None:
                    active, passive = resident[si]
                statics.append((active, passive))
                total = self._score_jit(total, *active, coefs)
                if passive is not None:
                    # Active/passive split: capped-out rows are never
                    # trained on but MUST be scored (coordinates train
                    # against each other's full contributions).
                    total = self._score_jit(total, *passive, coefs)
            if self._hot_cache is not None and resident is None:
                self._hot_cache.maybe_admit(
                    ("score", gi), statics, self._hot_bytes[("score", gi)]
                )

        # pack_to_default: the donated ``total`` accumulator lives on the
        # default device; a payload committed to device k would drag it
        # there and clash with the next slice.  Scatter order (slice
        # order, active then passive) is placement-independent, so the
        # score stays bitwise the unpacked one.
        self._run_groups(host_group, consume, pack_to_default=True)
        return total[: self.dataset.n_global_rows]

    def _block_variances(self, block: EntityBlock, coefs, offsets):
        """Budget-bounded override: the inherited version moves the WHOLE
        block to device for the variance Hessian — exactly the transfer
        this coordinate exists to avoid.  Reuse the pass plan's slice
        shape for this block instead."""
        bi = next(
            i for i, b in enumerate(self.dataset.blocks) if b is block
        )
        sub_e = next(
            s.padded_e
            for group in self.pass_plan
            for s in group
            if s.block_idx == bi
        )
        sentinel = self.dataset.n_global_rows
        placement = (
            ("split",)
            if self.bucket_plan is None
            else self.bucket_plan.placements[bi]
        )
        offsets = jnp.asarray(offsets, jnp.float32)
        if self.mesh is not None and placement[0] == "split":
            # Same device-set normalization as train: sharded slice
            # inputs need mesh-replicated offsets.
            offsets = jax.device_put(
                offsets, NamedSharding(self.mesh, P())
            )
        l2 = jnp.asarray(
            self.config.regularization.l2_weight(1.0) * self.reg_weight,
            jnp.float32,
        )
        coefs = np.asarray(coefs, np.float32)
        out = np.empty((block.n_entities, block.block_dim), np.float32)
        for lo in range(0, block.n_entities, sub_e):
            hi = min(lo + sub_e, block.n_entities)
            c = coefs[lo:hi]
            pad = sub_e - c.shape[0]
            if pad:
                c = np.pad(c, ((0, pad), (0, 0)))
            v = self._var_jit(
                self._put_one(
                    placement,
                    _slice_block(block, lo, hi, sub_e, sentinel),
                    pack_to_default=True,
                ),
                self._put_one(placement, c, pack_to_default=True),
                offsets, l2,
            )
            out[lo:hi] = np.asarray(v)[: hi - lo]
        return out
