"""GAME data layer: fixed-effect and random-effect datasets.

The analogue of the reference's ``...ml.data`` GAME layer (SURVEY.md §2):
``GameDatum`` (per-row response/weight/offset + features-by-shard + entity
ids), ``FixedEffectDataset`` (all rows, one feature shard), and
``RandomEffectDataset`` — in the reference an RDD keyed by entity id with a
custom partitioner colocating each entity's rows, so per-entity GLMs solve
locally inside ``mapPartitions``.

TPU-first reshape: instead of per-entity JVM objects, entities are

1. **grouped** (all rows of an entity gathered together),
2. **projected** — each entity's rows only reference the feature columns that
   entity actually observes, so tiny per-entity problems don't carry the
   global dimensionality (the reference's ``LinearSubspaceProjector``), and
3. **bucketed by size** — entities with similar row counts / active-feature
   counts share one dense padded block ``(E, R, D)`` that a ``vmap``'d
   solver minimizes in one jitted program (SURVEY.md §7 step 6).

Padding discipline matches the rest of the framework: padding rows carry
weight 0; padding columns map to global column -1 and carry value 0; padding
*entities* (to fill a bucket) have all-zero weights and solve to w=0 under
any L2.

Row bookkeeping: each block row remembers its global row index so coordinate
descent can gather per-row offsets in and scatter per-row scores out
(the analogue of the reference's score joins on unique id).
"""

from __future__ import annotations

import ctypes
import dataclasses
import math
import time
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.dataset import GlmData
from photon_ml_tpu.telemetry import layer_span
from photon_ml_tpu.utils.placement import place_leaves

Array = jax.Array


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["X", "labels", "weights", "col_map", "row_index"],
    meta_fields=["n_entities", "rows_per_entity", "block_dim", "x_minor"],
)
@dataclasses.dataclass
class EntityBlock:
    """One size-bucket of entities as a dense padded batch.

    ``x_erd[e, r, k]`` is the value of local feature k in row r of entity
    e; ``col_map[e, k]`` maps local feature k to its global column (or -1).
    ``row_index[e, r]`` is the row's index in the global dataset (or the
    sentinel ``n_global_rows`` for padding — callers gather from arrays
    padded with one trailing zero slot).

    ``X`` is the STORED array, ``(E, R, D)`` (``x_minor == "d"``) or
    ``(E, D, R)`` (``"r"``): a TPU pads an array's minor axis to 128 lanes,
    so a narrow block (a per-user effect of 21 columns) stored features-
    minor would take six times its bytes in device memory and in every
    pass over it (:func:`_x_minor`).  Arithmetic reads ``x_erd``; code that
    only cuts or pads the entity axis may touch ``X``.
    """

    X: Array  # (E, R, D) float, or (E, D, R) when x_minor == "r"
    labels: Array  # (E, R)
    weights: Array  # (E, R) — 0 for padding rows / entities
    col_map: Array  # (E, D) int32 — global column ids, -1 pad
    row_index: Array  # (E, R) int32 — global row ids, sentinel pad
    n_entities: int
    rows_per_entity: int
    block_dim: int
    x_minor: str = "d"

    @property
    def x_erd(self):
        """The features as ``(E, R, D)`` whatever the storage order (under
        ``jit`` the compiler folds the transpose into the products)."""
        if self.x_minor == "d":
            return self.X
        xp = jnp if isinstance(self.X, jax.Array) else np
        return xp.swapaxes(self.X, 1, 2)


def _device_tile():
    """``(sublanes, lanes)`` to which the default backend pads the two
    minor axes of a 32-bit array in device memory; ``None`` where arrays
    are stored dense (CPU, GPU)."""
    return (8, 128) if jax.default_backend() == "tpu" else None


def _x_minor(rows: int, dim: int, tile) -> str:
    """Which axis of a block's features to store minor: the one that pads
    to fewer bytes under the device's tiling; features (``"d"``) on a tie
    and where nothing is padded."""
    if tile is None:
        return "d"
    sub, lanes = tile

    def pad(n, m):
        return -(-n // m) * m

    rows_minor = pad(dim, sub) * pad(rows, lanes)
    return "r" if rows_minor < pad(rows, sub) * pad(dim, lanes) else "d"


#: Passive rows looked up at a time (:meth:`PassiveRows.scores`).
_PASSIVE_CHUNK = 8192


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["X", "row_index", "slot", "lanes"],
    meta_fields=["n_rows", "block_dim", "chunk", "x_minor"],
)
@dataclasses.dataclass
class PassiveRows:
    """The score-only rows of one bucket's capped entities, FLAT: one entry
    per passive row, whatever the entities' passive counts (a lane-aligned
    ``(E, Rp, D)`` companion pads every lane to the heaviest entity's
    passive rows: 57 k for a movie of 65 k ratings under a cap of 8,192,
    beside lanes that have a handful).

    ``lanes[c]`` is the lane, in the bucket's active block, of the c-th
    entity that has passive rows; ``slot[p]`` is row p's entity's index
    into ``lanes``, ascending; ``row_index[p]`` its global row.  The rows
    are padded to ``P``, a whole number of chunks of ``chunk`` rows:
    padding rows carry the sentinel row index, the last slot and no
    features.  ``X`` is ``(P, D)`` (``x_minor == "d"``) or ``(D, P)``
    (``"r"``) in the ACTIVE block's local columns (a passive row's features
    outside its entity's active subspace drop, as the reference's projected
    scoring does), stored by the same rule as a block's (:func:`_x_minor`).
    """

    X: Array  # (P, D) float, or (D, P) when x_minor == "r"
    row_index: Array  # (P,) int32 — global row ids, sentinel pad
    slot: Array  # (P,) int32 — index into ``lanes``, ascending
    lanes: Array  # (Ec,) int32 — lanes of the entities with passive rows
    n_rows: int  # real rows
    block_dim: int
    chunk: int
    x_minor: str = "d"

    def scores(self, coefs):
        """``(P,)``: each row against its entity's row of ``coefs``, the
        bucket's ``(E, D)`` coefficients; 0 for padding rows.

        A row's coefficients are looked up a chunk of rows at a time with a
        one-hot product: the slots ascend, so a chunk of C rows reads at
        most C consecutive slots, a static window of the table.  On a TPU
        that is the matrix unit's work, exact at ``HIGHEST`` precision (a
        1.0 times the coefficient), and 30 times faster than XLA's gather
        of ``(P, D)`` elements, which also pads its result's 9 columns to
        128 lanes: 5.6 GB at 5.5 M rows.
        """
        D, C = self.block_dim, self.chunk
        n_slots = self.lanes.shape[0]
        W = min(n_slots, C)
        table = jnp.take(coefs, self.lanes, axis=0).T  # (D, n_slots)
        x = self.X if self.x_minor == "r" else self.X.T  # (D, P)
        local = jnp.arange(W, dtype=jnp.int32)[:, None]
        zero = jnp.int32(0)

        def chunk(k):
            at = k * jnp.int32(C)
            s = jax.lax.dynamic_slice(self.slot, (at,), (C,))
            first = jnp.minimum(s[0], jnp.int32(n_slots - W))
            window = jax.lax.dynamic_slice(table, (zero, first), (D, W))
            hot = (local == (s - first)[None, :]).astype(table.dtype)
            rows_coefs = jnp.dot(
                window, hot, precision=jax.lax.Precision.HIGHEST)
            rows = jax.lax.dynamic_slice(x, (zero, at), (D, C))
            return jnp.sum(rows * rows_coefs, axis=0)

        n_chunks = self.row_index.shape[0] // C
        return jax.lax.map(
            chunk, jnp.arange(n_chunks, dtype=jnp.int32)).reshape(-1)

    def lane_aligned(self, block: EntityBlock, sentinel: int) -> EntityBlock:
        """The same rows as a HOST block lane-aligned with ``block`` and
        padded to its heaviest lane, ``(E, Rp, D)``, for the coordinates
        that cut their blocks by lanes (out-of-core slices)."""
        n = self.n_rows
        lane = np.asarray(self.lanes, np.int64)[np.asarray(self.slot)[:n]]
        E, D = block.n_entities, self.block_dim
        counts = np.bincount(lane, minlength=E)
        Rp = int(counts.max()) if n else 0
        local = np.arange(n) - np.repeat(np.cumsum(counts) - counts, counts)
        x = np.asarray(self.X)
        X = np.zeros((E, Rp, D), x.dtype)
        X[lane, local] = (x.T if self.x_minor == "r" else x)[:n]
        row_index = np.full((E, Rp), sentinel, np.int32)
        row_index[lane, local] = np.asarray(self.row_index)[:n]
        return EntityBlock(
            X=X, labels=np.zeros((E, Rp), np.float32),
            weights=np.zeros((E, Rp), np.float32),
            col_map=np.asarray(block.col_map), row_index=row_index,
            n_entities=E, rows_per_entity=Rp, block_dim=D,
        )


@dataclasses.dataclass
class RandomEffectDataset:
    """All buckets for one random-effect coordinate + host-side id maps.

    ``entity_ids[b][e]`` is the entity key of lane e in bucket b;
    ``entity_to_slot`` maps entity key → (bucket, lane).

    ``passive_blocks[b]`` (None when no entity in bucket b exceeds the
    active-set cap) holds the rows beyond ``max_rows_per_entity`` — the
    reference's active/passive split: passive rows are never TRAINED on, but
    they must still be SCORED during coordinate descent or the other
    coordinates would train against offsets missing this coordinate's
    contribution for those rows.  They are stored flat (:class:`PassiveRows`:
    one entry a row, each with its entity's lane in block b), so the
    trained (E, D) coefficients apply through ``lanes``; passive-row
    features outside the entity's active subspace drop, as the reference's
    projector-based scoring does.
    """

    blocks: list[EntityBlock]
    entity_ids: list[list]
    entity_to_slot: dict
    n_global_rows: int
    n_features: int  # global feature-space width of this coordinate's shard
    passive_blocks: list[Optional[PassiveRows]] = dataclasses.field(
        default_factory=list
    )
    # Padding accounting from build time (docs/performance.md
    # "Hierarchical execution"): padded = Σ_blocks E·R·D over the
    # realized block shapes, exact = Σ_entities r·max(d, 1).  Their
    # ratio is the `game_bucket_padding_ratio` gauge and the repacker
    # A/B's objective; 0 means the dataset predates the accounting
    # (host-rebuilt scoring paths).
    padded_flops: int = 0
    exact_flops: int = 0
    #: Per block, the rows that are some entity's (trained on: weighted or
    #: not, but not padding); empty on datasets built before the count.
    block_rows_real: list = dataclasses.field(default_factory=list)

    @property
    def n_entities(self) -> int:
        return len(self.entity_to_slot)

    @property
    def rows_active(self) -> int:
        """Rows some entity trains on (0 on a dataset from before
        ``block_rows_real``)."""
        return int(sum(self.block_rows_real))

    @property
    def rows_passive(self) -> int:
        """Rows that are scored and never trained on."""
        return int(sum(p.n_rows for p in self.passive_blocks if p is not None))

    def lane_aligned_passive(self) -> list[Optional[EntityBlock]]:
        """Each bucket's passive rows as a host block lane-aligned with its
        active block (:meth:`PassiveRows.lane_aligned`), or ``None``."""
        passive = self.passive_blocks or [None] * len(self.blocks)
        return [
            None if p is None else p.lane_aligned(b, self.n_global_rows)
            for p, b in zip(passive, self.blocks)
        ]

    @property
    def padding_ratio(self) -> float:
        """Padded/exact FLOPs of the realized bucket ladder (>= 1.0)."""
        return (
            self.padded_flops / self.exact_flops if self.exact_flops else 1.0
        )


@dataclasses.dataclass
class FixedEffectDataset:
    """All rows against one feature shard (reference: FixedEffectDataset)."""

    data: GlmData
    n_global_rows: int


@dataclasses.dataclass
class GameData:
    """Per-coordinate datasets over one global row space (the analogue of the
    reference's per-coordinate dataset map inside GameEstimator).

    labels/weights are global row arrays shared by every coordinate;
    ``base_offsets`` are the user-supplied per-row offsets (GameDatum.offset).
    """

    coordinates: dict  # name -> FixedEffectDataset | RandomEffectDataset
    labels: np.ndarray
    weights: np.ndarray
    base_offsets: np.ndarray

    @property
    def n_rows(self) -> int:
        return len(self.labels)


def _round_up_geometric(n: int, growth: float, floor: int = 1) -> int:
    """Smallest bucket size >= n on the geometric grid floor·growth^k.

    growth=2.0 reproduces the pow2 grid; larger growth consolidates the
    long tail into fewer buckets — fewer compiled block programs and fewer
    per-pass dispatches, at the cost of more padding FLOPs (the
    shape-consolidation policy knob; VERDICT round 1, weak #6)."""
    if growth <= 1.0:
        raise ValueError(f"bucket growth must be > 1, got {growth}")
    n = max(n, floor)
    v = floor
    while v < n:
        v = max(v + 1, int(math.ceil(v * growth)))
    return v


@dataclasses.dataclass(frozen=True)
class RepackPlan:
    """A cost-model bucket plan: K bucket shapes + the entity→bucket map.

    ``shapes`` is ``(K, 2)`` int64 ``(rows, dims)`` sorted ascending;
    ``assignment[e]`` is entity e's bucket.  ``padded_flops`` is the
    plan's Σ n·R·D cost over PLAN shapes (realized blocks pad tighter —
    to member maxima — so the realized ratio only improves on this).
    """

    shapes: np.ndarray  # (K, 2) int64
    assignment: np.ndarray  # (n_entities,) int64
    padded_flops: int
    exact_flops: int


#: (entity, column) cells up to which the grouping finds each entity's
#: active columns with a presence table (one byte a cell) and not a sort.
_PAIR_TABLE_CELLS = 1 << 28

#: Distinct (rows, dims) shapes above which the repacker pre-quantizes
#: on a fine geometric grid before the O(K²)-per-merge greedy runs.
_REPACK_MAX_DISTINCT = 256


def plan_entity_buckets(
    row_counts,
    col_counts,
    program_budget: int = 16,
    seed: int = 0,
) -> RepackPlan:
    """Cost-model entity repacker: pick ≤ ``program_budget`` bucket
    shapes minimizing padded FLOPs for the observed per-entity sizes.

    Replaces the static geometric ladder with a plan driven by the
    actual (row count, active-feature count) distribution
    (data/stats.py ``entity_shape_histogram``).  Greedy agglomeration:
    start from every distinct shape as its own bucket (zero padding,
    too many compiled programs), then repeatedly merge the pair whose
    merged bucket (elementwise-max shape) adds the fewest padded FLOPs,
    until the compiled-program-count budget holds.  Fully
    deterministic: shapes are processed in sorted order, ties break on
    the first (lexicographically smallest) pair, and ``seed`` only
    feeds the optional entity subsample for very large populations
    (``entity_shape_histogram``).
    """
    from photon_ml_tpu.data.stats import entity_shape_histogram

    if program_budget < 1:
        raise ValueError(
            f"program_budget must be >= 1, got {program_budget}"
        )
    shapes, counts, inverse = entity_shape_histogram(
        row_counts, col_counts, seed=seed
    )
    exact = int(
        np.sum(
            np.asarray(row_counts, np.int64)
            * np.maximum(np.asarray(col_counts, np.int64), 1)
        )
    )
    if len(shapes) == 0:
        return RepackPlan(
            shapes=np.zeros((0, 2), np.int64),
            assignment=np.zeros(0, np.int64),
            padded_flops=0, exact_flops=0,
        )

    # Pre-quantize a pathologically diverse shape population so each
    # greedy step stays a small dense matrix: snap to a fine geometric
    # grid (far finer than the ladder this replaces) and re-unique.
    shape_to_slot = np.arange(len(shapes))
    if len(shapes) > _REPACK_MAX_DISTINCT:
        growth = 1.05
        while True:
            q = np.stack(
                [
                    [_round_up_geometric(int(r), growth) for r in shapes[:, 0]],
                    [_round_up_geometric(int(c), growth) for c in shapes[:, 1]],
                ],
                axis=1,
            )
            qshapes, qinv = np.unique(q, axis=0, return_inverse=True)
            if len(qshapes) <= _REPACK_MAX_DISTINCT:
                break
            growth *= 1.1
        qcounts = np.bincount(
            qinv, weights=counts.astype(np.float64), minlength=len(qshapes)
        ).astype(np.int64)
        shape_to_slot = qinv
        shapes, counts = qshapes.astype(np.int64), qcounts

    # Greedy agglomeration over (R, D, n, cost) bucket rows.  `members`
    # tracks which initial slots each surviving bucket absorbed.
    R = shapes[:, 0].astype(np.int64)
    D = shapes[:, 1].astype(np.int64)
    N = counts.astype(np.int64)
    C = N * R * D
    members: list[list[int]] = [[i] for i in range(len(shapes))]
    alive = np.ones(len(shapes), bool)

    def _merge_pass(free_only: bool) -> None:
        nonlocal R, D, N, C
        while True:
            idx = np.flatnonzero(alive)
            if len(idx) <= 1 or (
                not free_only and len(idx) <= program_budget
            ):
                break
            Ra, Da, Na, Ca = R[idx], D[idx], N[idx], C[idx]
            Rm = np.maximum(Ra[:, None], Ra[None, :])
            Dm = np.maximum(Da[:, None], Da[None, :])
            delta = (Na[:, None] + Na[None, :]) * Rm * Dm \
                - Ca[:, None] - Ca[None, :]
            iu = np.triu_indices(len(idx), k=1)
            flat = delta[iu]
            if free_only and flat.min() > 0:
                break
            # argmin over the upper triangle is (i, j)-lexicographic on
            # ties — buckets were built from SORTED shapes, so the
            # winner is deterministic.
            k = int(np.argmin(flat))
            a, b = idx[iu[0][k]], idx[iu[1][k]]
            R[a] = max(R[a], R[b])
            D[a] = max(D[a], D[b])
            N[a] += N[b]
            C[a] = N[a] * R[a] * D[a]
            members[a].extend(members[b])
            alive[b] = False

    # Paid merges down to the program budget, then a free coalesce:
    # merging can leave two buckets with IDENTICAL shapes (distinct
    # ancestors growing to the same maxima) — folding those costs zero
    # padding and saves a compiled program, so always take them.
    _merge_pass(free_only=False)
    _merge_pass(free_only=True)

    kept = np.flatnonzero(alive)
    order = np.lexsort((D[kept], R[kept]))
    kept = kept[order]
    plan_shapes = np.stack([R[kept], D[kept]], axis=1)
    slot_to_bucket = np.empty(
        int(shape_to_slot.max()) + 1 if len(shape_to_slot) else 0, np.int64
    )
    for bi, ki in enumerate(kept):
        for slot in members[ki]:
            slot_to_bucket[slot] = bi
    assignment = slot_to_bucket[shape_to_slot[inverse]]
    padded = int(np.sum(C[kept]))
    return RepackPlan(
        shapes=plan_shapes,
        assignment=assignment,
        padded_flops=padded,
        exact_flops=exact,
    )


def build_random_effect_dataset(
    entity_keys: Sequence,
    rows_csr,  # scipy CSR (n_rows, d) — this coordinate's feature shard
    labels: np.ndarray,
    weights: np.ndarray,
    max_rows_per_entity: Optional[int] = None,
    dtype=jnp.float32,
    device: bool = True,
    bucket_growth: float = 2.0,
    allow_missing: bool = False,
    repack: str = "geometric",
    program_budget: int = 16,
    repack_seed: int = 0,
    name: str = "",
) -> RandomEffectDataset:
    """Group rows by entity, project to per-entity subspaces, bucket by size.

    ``max_rows_per_entity`` is the reference's active-set cap: entities with
    more rows train on a uniformly-spaced subset; the remaining (passive)
    rows land in score-only ``passive_blocks``.

    ``bucket_growth`` sets the geometric bucket grid (2.0 = pow2; larger
    values consolidate long-tailed size distributions into fewer buckets —
    fewer compiled programs / dispatches per CD pass, more padding).

    Entity keys are canonicalized to STRINGS — the on-disk model format
    (Avro entityId) is string-keyed, so training with int keys and scoring
    after reload must agree.  ``device=False`` keeps blocks as host numpy
    arrays (pure-host scoring paths avoid the device round trip).  ``name``
    is the coordinate's, for the ``game.group`` / ``game.place`` spans.
    """
    import scipy.sparse as sp

    rows_csr = sp.csr_matrix(rows_csr)
    rows_csr.sum_duplicates()
    n_rows, d = rows_csr.shape
    entity_keys = np.asarray(entity_keys)
    assert entity_keys.shape[0] == n_rows
    if entity_keys.dtype == object:
        missing = sum(1 for k in entity_keys if k is None)
        if missing and not allow_missing:
            # TRAINING: a row with no entity id is a data error (it would
            # silently train some entity on foreign rows).
            raise ValueError(
                f"{missing} of {n_rows} rows have no entity id for this "
                "random effect (records missing the id column?)"
            )
        if missing:
            # SCORING (allow_missing): id-less rows simply get no
            # contribution from this coordinate — the reference's
            # join-miss semantics.  Drop them from the grouping; the
            # score scatter covers only grouped rows, everything else
            # stays 0.
            keep = np.array([k is not None for k in entity_keys])
            rows_kept = np.flatnonzero(keep)
            if rows_kept.size == 0:
                # Every row id-less (e.g. one streamed scoring block):
                # this coordinate contributes nothing to any row.
                return RandomEffectDataset(
                    blocks=[],
                    entity_ids=[],
                    entity_to_slot={},
                    n_global_rows=n_rows,
                    n_features=d,
                    passive_blocks=[],
                )
            ds = build_random_effect_dataset(
                entity_keys[keep], rows_csr[rows_kept], labels[keep],
                weights[keep], max_rows_per_entity=max_rows_per_entity,
                dtype=dtype, device=device, bucket_growth=bucket_growth,
                repack=repack, program_budget=program_budget,
                repack_seed=repack_seed, name=name,
            )
            # Re-point every block's row indices at the ORIGINAL row
            # space (scatter targets), keeping the sentinel padding slot.
            remap = np.concatenate([rows_kept, [n_rows]]).astype(np.int64)
            kept_n = int(keep.sum())

            def _repoint(block):
                if block is None:  # bucket with no passive rows
                    return None
                ri = np.asarray(block.row_index)
                ri = np.where(ri >= kept_n, kept_n, ri)  # sentinel slot
                new_ri = (
                    jnp.asarray(remap[ri])
                    if isinstance(block.row_index, jax.Array)
                    else remap[ri]
                )
                return dataclasses.replace(block, row_index=new_ri)

            return dataclasses.replace(
                ds,
                blocks=[_repoint(b) for b in ds.blocks],
                passive_blocks=(
                    [_repoint(b) for b in ds.passive_blocks]
                    if ds.passive_blocks else ds.passive_blocks
                ),
                n_global_rows=n_rows,
            )
    with layer_span(
        "game.group", coordinate=name, rows=int(n_rows)
    ) as group_span:
        host = _group_entities(
            entity_keys, rows_csr, labels, weights, max_rows_per_entity,
            bucket_growth, repack, program_budget, repack_seed,
            _device_tile() if device else None,
        )
        if host is not None:
            rows_active = int(sum(host["block_rows_real"]))
            counted = dict(
                entities=len(host["entity_to_slot"]),
                rows_active=rows_active,
                rows_passive=int(n_rows) - rows_active,
            )
            group_span.set(buckets=len(host["blocks"]), **counted)
    if host is None:
        return RandomEffectDataset(
            blocks=[], entity_ids=[], entity_to_slot={},
            n_global_rows=n_rows, n_features=d, passive_blocks=[],
        )

    leaves: list[dict] = []

    def place(cls, fields, path):
        """One block's (or one bucket's passive rows') host fields as
        ``cls``; the fields pop as they go, so a host array is freed once
        its device copy is made.  What each copy cost the host joins
        ``leaves`` (the cast of ``X`` counts to its ``dispatch_s``)."""
        arrays = {}
        for key in [k for k, v in fields.items() if isinstance(v, np.ndarray)]:
            value = fields.pop(key)
            src_dtype = str(value.dtype)
            t0 = time.perf_counter()
            if key == "X":
                value = value.astype(dtype, copy=False)
            if device:
                value, (leaf,) = place_leaves(value, f"{path}.{key}")
                leaf.update(src_dtype=src_dtype,
                            dispatch_s=time.perf_counter() - t0)
                leaves.append(leaf)
            arrays[key] = value
        return cls(**arrays, **fields)

    with layer_span("game.place", coordinate=name, **counted) as place_span:
        blocks = [place(EntityBlock, f, f"blocks[{i}]")
                  for i, f in enumerate(host["blocks"])]
        passive_blocks = [
            None if f is None else place(PassiveRows, f, f"passive[{i}]")
            for i, f in enumerate(host["passive_blocks"])
        ]
        t0 = time.perf_counter()
        if device:
            jax.block_until_ready((blocks, passive_blocks))  # the one sync
        place_span.set(
            wait_s=time.perf_counter() - t0, leaves=leaves,
            bytes=sum(
                x.nbytes for x in jax.tree.leaves((blocks, passive_blocks))))

    padded_flops = int(
        sum(b.n_entities * b.rows_per_entity * b.block_dim for b in blocks)
    )
    ds = RandomEffectDataset(
        blocks=blocks,
        entity_ids=host["entity_ids"],
        entity_to_slot=host["entity_to_slot"],
        n_global_rows=n_rows,
        n_features=d,
        passive_blocks=passive_blocks,
        padded_flops=padded_flops,
        exact_flops=host["exact_flops"],
        block_rows_real=host["block_rows_real"],
    )
    from photon_ml_tpu import telemetry as telemetry_mod

    telemetry_mod.current().gauge("game_bucket_padding_ratio").set(
        ds.padding_ratio
    )
    return ds


def _sort_by_entity(entity_keys: np.ndarray):
    """``(order, starts, ent_keys)``: the stable order of the rows by their
    entity's STRING key, where each entity starts in it, and the entities'
    string keys, ascending.

    Keys of an integer or string dtype are ranked through their distinct
    values, so the string conversion and the string sort touch one value
    per entity and the rows sort as integers: at 20 M rows the row-wise
    ``astype(str)`` + string argsort this replaces took most of a minute.
    The order is the same either way (a stable sort by the key's rank is a
    stable sort by the key)."""
    if entity_keys.dtype.kind not in "iubSU":
        keys = entity_keys.astype(str)
        order = np.argsort(keys, kind="stable")
        sorted_keys = keys[order]
        starts = np.flatnonzero(
            np.concatenate([[True], sorted_keys[1:] != sorted_keys[:-1]])
        )
        return order, starts, sorted_keys[starts]
    uniq, inverse = np.unique(entity_keys, return_inverse=True)
    ukeys = uniq.astype(str)
    by_string = np.argsort(ukeys, kind="stable")
    rank = np.empty(len(uniq), np.int64)
    rank[by_string] = np.arange(len(uniq))
    row_rank = rank[inverse.reshape(-1)]
    order = np.argsort(row_rank, kind="stable")
    counts = np.bincount(row_rank, minlength=len(uniq))
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    return order, starts, ukeys[by_string]


def _group_entities(
    entity_keys, rows_csr, labels, weights, max_rows_per_entity,
    bucket_growth, repack, program_budget, repack_seed, tile,
):
    """The host half of :func:`build_random_effect_dataset`: numpy fields
    of every block, or ``None`` without rows.  ``tile`` is the device's
    tiling (:func:`_device_tile`), or ``None`` for blocks that stay on the
    host.

    Six layer spans tile the call, children of the caller's ``game.group``
    in the order of the code (docs/telemetry.md "Layer spans"):
    ``game.group.sort``, ``.cap``, ``.gather``, ``.columns``, ``.plan`` and
    ``.fill``; outside them there are only a few assignments."""
    n_rows, d = rows_csr.shape

    # Group rows by entity — FLAT-ARRAY pipeline throughout.  A previous
    # version sliced scipy CSR per entity (rows_csr[ridx] then
    # sub[:, active]); at 100k entities those 200k __getitem__ calls
    # spent ~26 s in scipy index validation for ~2 s of real work.
    # Everything below runs on the raw indptr/indices/data arrays of ONE
    # bulk row gather; the fill walks each entity's run of it once
    # (native/group_fill.cpp), or scatters it a bucket at a time (numpy).
    if len(entity_keys) == 0:
        return None
    with layer_span("game.group.sort"):
        order, starts, ent_keys = _sort_by_entity(entity_keys)
        n_sorted = len(order)
        ends = np.append(starts[1:], n_sorted)
        span_sizes = ends - starts
        n_ent = len(starts)

    with layer_span("game.group.cap"):
        # Active-set cap (the reference's split): capped entities keep a
        # uniformly-spaced row subset, the rest become score-only passive
        # rows.  keep is over SORTED positions; only capped entities loop.
        keep = np.ones(n_sorted, bool)
        if max_rows_per_entity is not None:
            for g in np.flatnonzero(span_sizes > max_rows_per_entity):
                m = np.zeros(span_sizes[g], bool)
                m[np.linspace(
                    0, span_sizes[g] - 1, max_rows_per_entity
                ).astype(int)] = True
                keep[starts[g]:ends[g]] = m

        # Index arrays of the rows' and the entries' size are the grouping's
        # memory: 4 bytes each wherever the counts allow.
        index_t = np.int32 if n_sorted < (1 << 31) else np.int64
        ent_of_pos = np.repeat(np.arange(n_ent, dtype=index_t), span_sizes)
        # Local row index within the entity's kept (resp. passive) rows.
        kept_counts = np.bincount(ent_of_pos, weights=keep, minlength=n_ent
                                  ).astype(np.int64)
        kept_before = np.concatenate([[0], np.cumsum(kept_counts)[:-1]])
        local_kept = (
            (np.cumsum(keep) - 1) - kept_before[ent_of_pos]).astype(index_t)
        psv_counts = span_sizes - kept_counts
        n_passive = int(psv_counts.sum())
        local_psv = None
        if n_passive:
            psv_before = np.concatenate([[0], np.cumsum(psv_counts)[:-1]])
            local_psv = ((np.cumsum(~keep) - 1)
                         - psv_before[ent_of_pos]).astype(index_t)

    with layer_span("game.group.gather"):
        sorted_csr = rows_csr[order]  # one bulk row gather
        indptr = sorted_csr.indptr.astype(np.int64)
        nnz_per_row = np.diff(indptr)

    with layer_span("game.group.columns"):
        # Per-entity ACTIVE columns (from kept rows only, as the reference's
        # projector sees them): the distinct (entity, column) pairs of the
        # kept entries, entity-major, so each entity's active columns come out
        # ascending — the same order np.unique(sub.indices) produced.
        # ``col_rank[k]`` is entry k's pair's index among them, and
        # ``col_hit[k]`` whether it is one (every kept entry's is; a passive
        # entry's only where its entity trained on that column).
        pair = np.repeat(ent_of_pos, nnz_per_row).astype(np.int64)
        pair *= d
        pair += sorted_csr.indices
        nnz_keep = np.repeat(keep, nnz_per_row)
        if n_ent * d <= _PAIR_TABLE_CELLS:
            # Few enough (entity, column) cells for a presence table: one
            # pass in place of the sort inside np.unique (8 s at 62 M pairs).
            present = np.zeros(n_ent * d, bool)
            present[pair[nnz_keep]] = True
            upair = np.flatnonzero(present)
            col_rank = (np.cumsum(present, dtype=np.int32) - 1)[pair]
            col_hit = present[pair] if n_passive else None
            del present
        else:
            upair = np.unique(pair[nnz_keep])
            col_rank = np.searchsorted(upair, pair)
            col_hit = None
            if n_passive:  # no active pair at all: every passive entry drops
                col_hit = (
                    upair[np.minimum(col_rank, len(upair) - 1)] == pair
                    if len(upair) else np.zeros(len(pair), bool))
        del pair
        act_ent = (upair // d).astype(np.int64)
        act_col = (upair % d).astype(np.int32)
        act_counts = np.bincount(act_ent, minlength=n_ent).astype(np.int64)
        act_before = np.concatenate([[0], np.cumsum(act_counts)[:-1]])

    with layer_span("game.group.plan"):
        # GROUP entities into buckets, PADDING each block only to its
        # members' actual maxima: the grouping key bounds the bucket COUNT
        # (compile count per dataset), while the per-bucket entity count E
        # already makes every block shape unique — so tight padding costs
        # no extra compiles and cuts the padded bytes every objective
        # evaluation touches.
        #
        # Two grouping policies (docs/performance.md "Hierarchical
        # execution"):
        #  - "geometric" (default): the static ladder — key by
        #    (geo(rows), geo(dims)) on the floor·growth^k grid.
        #  - "cost_model": plan_entity_buckets fits ≤ program_budget bucket
        #    shapes to the OBSERVED size distribution, minimizing padded
        #    FLOPs.  Same downstream machinery; only the membership map
        #    changes.  NOTE: regrouping changes realized block shapes, and
        #    XLA reduction tiling varies with padded length — repacked
        #    coefficients are the same math but not bit-for-bit the
        #    ladder's (unlike sharding/pipelining, which preserve the plan
        #    and are bitwise; measured in docs/performance.md).
        if repack == "cost_model":
            from photon_ml_tpu.chaos import core as chaos_mod

            chaos_mod.maybe_fail(
                "game.repack", n_entities=n_ent, budget=program_budget
            )
            plan = plan_entity_buckets(
                kept_counts, act_counts, program_budget=program_budget,
                seed=repack_seed,
            )
            buckets: dict[tuple[int, int], list[int]] = {}
            for g in range(n_ent):
                bi = int(plan.assignment[g])
                key = (int(plan.shapes[bi, 0]), int(plan.shapes[bi, 1]))
                buckets.setdefault(key, []).append(g)
        elif repack == "geometric":
            geo = {}

            def _geo(v: int) -> int:
                if v not in geo:
                    geo[v] = _round_up_geometric(v, bucket_growth)
                return geo[v]

            buckets = {}
            for g in range(n_ent):
                key = (_geo(int(kept_counts[g])), _geo(int(act_counts[g])))
                buckets.setdefault(key, []).append(g)
        else:
            raise ValueError(
                f"repack must be 'geometric' or 'cost_model', got {repack!r}"
            )

        # lane_of_ent/block_of_ent drive every flat scatter below.
        lane_of_ent = np.empty(n_ent, np.int64)
        block_of_ent = np.full(n_ent, -1, np.int64)
        ordered_buckets = []
        for bi, (_key, members) in enumerate(sorted(buckets.items())):
            m = np.asarray(members, np.int64)
            ordered_buckets.append(m)
            lane_of_ent[m] = np.arange(len(m))
            block_of_ent[m] = bi

        # Each bucket's sorted positions and active pairs as index lists
        # (the numpy fill's: the native one walks the entities' runs),
        # ascending, from ONE stable sort by bucket each: a boolean mask over
        # all rows per bucket cost a pass over the whole data for every
        # bucket.  A bucket's stored entries are its rows' ranges of the CSR.
        def by_block(block_ids):
            small = block_ids.astype(
                np.int16 if len(ordered_buckets) < (1 << 15) else np.int64)
            idx = np.argsort(small, kind="stable")
            bounds = np.concatenate([[0], np.cumsum(np.bincount(
                block_ids, minlength=len(ordered_buckets)))])
            return idx, bounds

        pos_idx, pos_bounds = by_block(block_of_ent[ent_of_pos])
        act_idx, act_bounds = by_block(block_of_ent[act_ent])

    def entries_of(positions):
        """``(entry indices, each entry's sorted position)`` of the rows at
        ``positions``, in their order."""
        lens = nnz_per_row[positions]
        first = np.cumsum(lens) - lens
        pos_of = np.repeat(positions, lens)
        return (np.repeat(indptr[positions] - first, lens)
                + np.arange(int(lens.sum()))), pos_of

    def stored_zeros(minor, lead, rows_, D):
        """Zeroed features in their storage order: the ``lead`` axes, then
        ``(rows_, D)``, the last two swapped rows-minor."""
        return np.zeros(
            (*lead, D, rows_) if minor == "r" else (*lead, rows_, D),
            np.float32)

    def scatter(X, minor, lane_or_row, row, col, values):
        X[(*lane_or_row, col, row) if minor == "r"
          else (*lane_or_row, row, col)] = values

    labels = np.asarray(labels)
    weights = np.asarray(weights)
    row_of_pos = order  # global row id of each sorted position
    blocks: list[dict] = []
    passive_blocks: list[Optional[dict]] = []
    ids_per_block: list[list] = []
    entity_to_slot: dict = {}
    block_rows_real: list[int] = []
    # Each entity's first flat passive row and its slot among its bucket's
    # lanes with passive rows (the native fill's; 0 without passive rows).
    first_of_ent = np.zeros(n_ent, np.int64)
    slot_of_ent = np.zeros(n_ent, np.int64)
    with layer_span(
            "game.group.fill", buckets=len(ordered_buckets)) as fill_span:
        # The library walks each entity's run of sorted positions once,
        # after the buckets' arrays are allocated below (bit-identical
        # arrays); the numpy chain of gathers and scatters fills each
        # bucket in the loop otherwise, many times slower.
        from photon_ml_tpu.native import load_group_fill

        lib = load_group_fill()
        fill_span.set(method="numpy" if lib is None else "native")
        for bi, m in enumerate(ordered_buckets):
            E = len(m)
            R = int(kept_counts[m].max())
            D = max(1, int(act_counts[m].max()))
            minor = _x_minor(R, D, tile)
            lab = np.zeros((E, R), np.float32)
            wts = np.zeros((E, R), np.float32)
            rindex = np.full((E, R), n_rows, np.int32)  # sentinel
            cmap = np.full((E, D), -1, np.int32)
            X = stored_zeros(minor, (E,), R, D)
            block_rows_real.append(int(kept_counts[m].sum()))
            if lib is None:
                # Row-level fills: labels/weights/row_index at
                # (lane, local_row).
                in_bucket = pos_idx[pos_bounds[bi]:pos_bounds[bi + 1]]
                sel = in_bucket[keep[in_bucket]]
                lane_r = lane_of_ent[ent_of_pos[sel]]
                lrow = local_kept[sel]
                rows_sel = row_of_pos[sel]
                lab[lane_r, lrow] = labels[rows_sel]
                wts[lane_r, lrow] = weights[rows_sel]
                rindex[lane_r, lrow] = rows_sel

                # col_map: each unique active (entity, col) lands at its
                # rank within the entity's active list.
                a_sel = act_idx[act_bounds[bi]:act_bounds[bi + 1]]
                local_c = a_sel - act_before[act_ent[a_sel]]
                cmap[lane_of_ent[act_ent[a_sel]], local_c] = act_col[a_sel]

                # X: every kept nnz of the bucket scatters to
                # (lane, local_row, local_col); duplicates were pre-summed.
                n_sel, pos_n = entries_of(sel)
                e_n = ent_of_pos[pos_n]
                scatter(
                    X, minor, (lane_of_ent[e_n],), local_kept[pos_n],
                    col_rank[n_sel] - act_before[e_n],
                    sorted_csr.data[n_sel],
                )
                del n_sel, pos_n, e_n

            ids = list(ent_keys[m])
            for lane, key in enumerate(ids):
                entity_to_slot[key] = (bi, lane)
            blocks.append(dict(
                X=X, labels=lab, weights=wts, col_map=cmap, row_index=rindex,
                n_entities=E, rows_per_entity=R, block_dim=D, x_minor=minor,
            ))
            ids_per_block.append(ids)

            # The bucket's score-only rows, flat and in lane order (sorted
            # positions are entity-major and lanes ascend with the entity).
            if not n_passive or not psv_counts[m].any():
                passive_blocks.append(None)
                continue
            Np = int(psv_counts[m].sum())
            chunk = min(_PASSIVE_CHUNK, -(-Np // 128) * 128)
            P = -(-Np // chunk) * chunk
            has = psv_counts[m] > 0
            slot_of_lane = np.cumsum(has) - 1
            first_of_lane = np.cumsum(psv_counts[m]) - psv_counts[m]
            first_of_ent[m] = first_of_lane
            slot_of_ent[m] = slot_of_lane
            rindexp = np.full(P, n_rows, np.int32)  # sentinel
            slot = np.full(P, slot_of_lane[-1], np.int32)
            passive_minor = _x_minor(P, D, tile)
            Xp = stored_zeros(passive_minor, (), P, D)
            if lib is None:
                selp = in_bucket[~keep[in_bucket]]
                rindexp[:Np] = row_of_pos[selp]
                slot[:Np] = slot_of_lane[lane_of_ent[ent_of_pos[selp]]]
                # Passive features project onto the ACTIVE subspace
                # (features the entity never trained on drop, as in the
                # reference's projected scoring): entries whose
                # (entity, col) pair is not active drop.
                np_sel, pos_p = entries_of(selp)
                hit = col_hit[np_sel]
                np_sel, pos_p = np_sel[hit], pos_p[hit]
                e_p = ent_of_pos[pos_p]
                scatter(
                    Xp, passive_minor, (),
                    first_of_lane[lane_of_ent[e_p]] + local_psv[pos_p],
                    col_rank[np_sel] - act_before[e_p],
                    sorted_csr.data[np_sel],
                )
            passive_blocks.append(dict(
                X=Xp, row_index=rindexp, slot=slot,
                lanes=np.flatnonzero(has).astype(np.int32), n_rows=Np,
                block_dim=D, chunk=chunk, x_minor=passive_minor,
            ))
        if lib is not None:
            _fill_native(
                lib, blocks, passive_blocks, block_of_ent, lane_of_ent,
                first_of_ent, slot_of_ent, starts, span_sizes, keep, order,
                labels, weights, indptr, sorted_csr.data, col_rank, col_hit,
                act_before, act_counts, act_col)

    return {
        "blocks": blocks,
        "passive_blocks": passive_blocks,
        "entity_ids": ids_per_block,
        "entity_to_slot": entity_to_slot,
        "exact_flops": int(np.sum(kept_counts * np.maximum(act_counts, 1))),
        "block_rows_real": block_rows_real,
    }


def _fill_native(lib, blocks, passive_blocks, block_of_ent, lane_of_ent,
                 first_of_ent, slot_of_ent, starts, span_sizes, keep, order,
                 labels, weights, indptr, data, col_rank, col_hit,
                 act_before, act_counts, act_col):
    """Every bucket's arrays of ``blocks`` and ``passive_blocks`` (the
    fill's dicts, allocated and sentinel-filled) filled in place by
    ``native/group_fill.cpp``'s one walk over the entities' runs.  Labels,
    weights and values go to float32 here, as the numpy path's scatters
    cast them."""
    from photon_ml_tpu.native import GfBucket, GfRows

    def i64(a):
        return np.ascontiguousarray(a, np.int64)

    def addr(a):
        return None if a is None else a.ctypes.data

    rank_t = np.int32 if col_rank.dtype == np.int32 else np.int64
    held = dict(  # every buffer the call reads, alive until it returns
        starts=i64(starts), span_sizes=i64(span_sizes),
        keep=np.ascontiguousarray(keep).view(np.uint8), order=i64(order),
        labels=np.ascontiguousarray(labels, np.float32),
        weights=np.ascontiguousarray(weights, np.float32),
        indptr=i64(indptr), data=np.ascontiguousarray(data, np.float32),
        col_rank=np.ascontiguousarray(col_rank, rank_t),
        col_hit=(None if col_hit is None
                 else np.ascontiguousarray(col_hit).view(np.uint8)),
        act_before=i64(act_before), act_counts=i64(act_counts),
        act_col=np.ascontiguousarray(act_col, np.int32),
        block_of=i64(block_of_ent), lane_of=i64(lane_of_ent),
        first_of=i64(first_of_ent), slot_of=i64(slot_of_ent),
    )
    rows = GfRows(rank_i64=int(rank_t == np.int64), **{
        k: addr(held[k]) for k in (
            "starts", "span_sizes", "keep", "order", "labels", "weights",
            "indptr", "data", "col_rank", "col_hit", "act_before",
            "act_counts", "act_col")})
    table = (GfBucket * len(blocks))()
    for t, b, pb in zip(table, blocks, passive_blocks):
        t.E, t.R, t.D = b["n_entities"], b["rows_per_entity"], b["block_dim"]
        t.minor_r = int(b["x_minor"] == "r")
        t.lab, t.wts = addr(b["labels"]), addr(b["weights"])
        t.rindex, t.X, t.cmap = (
            addr(b["row_index"]), addr(b["X"]), addr(b["col_map"]))
        if pb is not None:
            t.P = pb["row_index"].shape[0]
            t.p_minor_r = int(pb["x_minor"] == "r")
            t.rindexp, t.slot, t.Xp = (
                addr(pb["row_index"]), addr(pb["slot"]), addr(pb["X"]))
    p_i64 = ctypes.POINTER(ctypes.c_int64)
    if lib.gf_fill(
        ctypes.byref(rows), table, len(table), len(held["block_of"]),
        len(held["data"]),
        *(held[k].ctypes.data_as(p_i64)
          for k in ("block_of", "lane_of", "first_of", "slot_of")),
    ) != 0:
        raise RuntimeError(
            "native group fill: an index fell outside its block")
