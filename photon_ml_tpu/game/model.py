"""GAME model containers.

The analogue of the reference's ``...ml.model`` GAME classes (SURVEY.md §2):
``GameModel`` (container of per-coordinate models; scoring = sum of
coordinate scores), ``FixedEffectModel`` (one coefficient vector, broadcast
in the reference — replicated here), and ``RandomEffectModel`` (per-entity
coefficients, an RDD in the reference — a host-side entity→sparse-coefficient
table here, materialized into dense device blocks when scoring).
"""

from __future__ import annotations

import collections.abc
import dataclasses
from typing import Mapping, Optional

import numpy as np

from photon_ml_tpu.models.glm import GeneralizedLinearModel


@dataclasses.dataclass
class FixedEffectModel:
    """Reference: ``FixedEffectModel(model, featureShardId)``."""

    model: GeneralizedLinearModel
    feature_shard: str


def _frozen(a) -> np.ndarray:
    """A read-only view of ``a`` (the caller's own array stays as it was)."""
    view = np.asarray(a).view()
    view.flags.writeable = False
    return view


class EntityTable(collections.abc.Mapping):
    """Read-only entity key → ``(cols, vals)`` over flat arrays: the table
    a trained random effect holds, with no Python object per entity.

    ``ids`` are the entity keys, sorted and distinct; entity ``i`` owns
    ``cols[starts[i]:starts[i + 1]]`` (int32, ascending) and the ``vals``
    (float32) and optional ``variances`` (float32) beside them.  A lookup
    is one binary search and returns views; iteration is in ``ids``' order.
    """

    __slots__ = ("ids", "starts", "cols", "vals", "variances")

    def __init__(self, ids, starts, cols, vals, variances=None):
        if len(starts) != len(ids) + 1 or len(cols) != len(vals):
            raise ValueError(
                f"entity table of {len(ids)} keys, {len(starts)} starts, "
                f"{len(cols)} columns and {len(vals)} values")
        self.ids, self.starts = _frozen(ids), _frozen(starts)
        self.cols, self.vals = _frozen(cols), _frozen(vals)
        self.variances = None if variances is None else _frozen(variances)

    def bounds(self, key) -> Optional[tuple[int, int]]:
        """``(lo, hi)`` of ``key``'s entries in the flat arrays, or None."""
        if np.ndim(key) != 0 or not len(self.ids):
            return None
        try:
            i = int(np.searchsorted(self.ids, key))
        except (TypeError, ValueError):
            return None
        if i == len(self.ids) or not self.ids[i] == key:
            return None
        return int(self.starts[i]), int(self.starts[i + 1])

    def __getitem__(self, key):
        found = self.bounds(key)
        if found is None:
            raise KeyError(key)
        return self.cols[found[0]:found[1]], self.vals[found[0]:found[1]]

    def __contains__(self, key) -> bool:
        return self.bounds(key) is not None

    def __len__(self) -> int:
        return len(self.ids)

    def __iter__(self):
        return iter(self.ids)

    def items(self):
        return _EntityItems(self)


class EntityLanes:
    """What the table of a bucket ladder needs that no fit changes: lane
    ``e`` of bucket ``b`` is entity ``entity_ids[b][e]`` and holds a
    coefficient for column ``col_maps[b][e, k]`` where that is ``>= 0``;
    lanes past a bucket's ids are padding.  Built once (one stable sort of
    the keys across buckets; a key met twice keeps its last lane, as a
    dict filled in lane order would), it holds the sorted keys, every real
    column in the table's order and where its coefficient lies in the
    buckets' concatenated ``(E, D)`` arrays.  :meth:`table` is then one
    gather of the values, whole-array operations only."""

    def __init__(self, entity_ids, col_maps):
        self._lane_counts = [len(ids) for ids in entity_ids]
        real = [b for b, n in enumerate(self._lane_counts) if n]
        if not real:
            self._table = (np.empty(0, object), np.zeros(1, np.int64),
                           np.empty(0, np.int32))
            self._source = np.empty(0, np.int64)
            return
        ids = np.concatenate([np.asarray(entity_ids[b]) for b in real])
        cmaps = [np.asarray(col_maps[b])[:self._lane_counts[b]]
                 for b in real]
        flat_cols = np.concatenate([c.ravel() for c in cmaps])
        counts = np.concatenate([(c >= 0).sum(axis=1) for c in cmaps])
        order = np.argsort(ids, kind="stable")
        sorted_ids = ids[order]
        order = order[np.append(sorted_ids[1:] != sorted_ids[:-1], True)]
        # Every real column, lane by lane; then the lanes in key order.
        by_lane = np.flatnonzero(flat_cols >= 0)
        lane_starts = np.cumsum(counts) - counts
        counts = counts[order]
        starts = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        gather = np.repeat(lane_starts[order] - starts[:-1], counts)
        gather += np.arange(starts[-1])
        self._source = by_lane[gather]
        self._table = (ids[order], starts,
                       flat_cols[self._source].astype(np.int32, copy=False))

    def _values(self, blocks) -> np.ndarray:
        """``blocks[b][e, k]`` beside each of the table's columns."""
        flat = [np.asarray(blocks[b], np.float32)[:n].ravel()
                for b, n in enumerate(self._lane_counts) if n]
        return np.concatenate(
            [np.empty(0, np.float32)] + flat).take(self._source)

    def table(self, coefs, variances=None) -> "EntityTable":
        """The :class:`EntityTable` of ``coefs[b][e, k]`` (and
        ``variances``, shaped alike): real columns with a nonzero
        coefficient."""
        ids, starts, cols = self._table
        vals = self._values(coefs)
        var = None if variances is None else self._values(variances)
        nonzero = vals != 0
        if not nonzero.all():
            kept = np.concatenate([[0], np.cumsum(nonzero)])
            starts, cols, vals = kept[starts], cols[nonzero], vals[nonzero]
            var = None if var is None else var[nonzero]
        return EntityTable(ids, starts, cols, vals, var)


class _EntityItems(collections.abc.ItemsView):
    """``EntityTable.items()`` in one pass over ``starts`` (the mixin's
    would search for every key it has just been handed)."""

    def __iter__(self):
        t = self._mapping
        bounds = t.starts.tolist()
        for i, key in enumerate(t.ids):
            lo, hi = bounds[i], bounds[i + 1]
            yield key, (t.cols[lo:hi], t.vals[lo:hi])


class EntityVariances(collections.abc.Mapping):
    """Read-only entity key → float32 variances beside that entity's
    ``cols``: a view of an :class:`EntityTable`'s ``variances``."""

    __slots__ = ("table",)

    def __init__(self, table: EntityTable):
        self.table = table

    def __getitem__(self, key):
        found = self.table.bounds(key)
        if found is None:
            raise KeyError(key)
        return self.table.variances[found[0]:found[1]]

    def __len__(self) -> int:
        return len(self.table)

    def __iter__(self):
        return iter(self.table)


@dataclasses.dataclass
class RandomEffectModel:
    """Per-entity GLMs over one feature shard.

    ``coefficients`` is a mapping: entity key → (global_cols int32[],
    values float32[]) with columns sorted ascending — the sparse
    original-space coefficient vector of that entity (the
    reference stores per-entity ``Coefficients`` in projected space and
    carries the projector; storing sparse global-space pairs is equivalent
    and projector-free).  It is one of two things: an :class:`EntityTable`
    (flat arrays; what training's ``finalize`` returns) or a plain mapping
    such as a ``dict`` (a loaded model, a hand-built one, serving's
    ``SharedEntityTable``).  Either way it is READ-ONLY once training or
    load has returned it: readers use ``get`` / ``[]`` / ``in`` / ``len``
    / iteration / ``items()``, and one that needs to change entries copies
    first (``dict(model.coefficients)``).  Entities never seen at training
    time score 0, as in the reference.
    """

    coefficients: Mapping
    feature_shard: str
    entity_key: str
    task: str
    n_features: int
    #: optional per-entity coefficient variances (reference: Bayesian model
    #: output) — a mapping, entity key → float32[] aligned with that
    #: entity's ``cols``; read-only like ``coefficients``.
    variances: Optional[Mapping] = None
    #: lazily-built packed view for vectorized lookup; the coefficient table
    #: is immutable after training/load, so this never needs invalidation.
    _packed: object = dataclasses.field(
        default=None, repr=False, compare=False
    )

    @property
    def n_entities(self) -> int:
        return len(self.coefficients)

    def _ensure_packed(self):
        """CSR-like packing of the entity→(cols, vals) table enabling ONE
        vectorized lookup across all lanes of a block: entity keys sorted,
        per-entity column segments concatenated, and a combined
        ``entity_rank * (n_features + 1) + col`` key that is GLOBALLY sorted
        (segments are rank-ordered, columns sorted within each segment), so
        a single ``searchsorted`` resolves every (lane, local column) pair."""
        if self._packed is not None:
            return self._packed
        stride = self.n_features + 1
        table = self.coefficients
        if isinstance(table, EntityTable):
            # Already this packing, but for the combined key.
            ranks = np.repeat(
                np.arange(len(table), dtype=np.int64), np.diff(table.starts)
            )
            self._packed = (
                table.ids.astype(object), ranks * stride + table.cols,
                table.vals, stride,
            )
            return self._packed
        keys = np.asarray(sorted(self.coefficients), dtype=object)
        sizes = np.array(
            [len(self.coefficients[k][0]) for k in keys], np.int64
        )
        starts = np.concatenate([[0], np.cumsum(sizes)])
        total = int(starts[-1])
        cols = np.empty(total, np.int64)
        vals = np.empty(total, np.float32)
        for i, k in enumerate(keys):
            c, v = self.coefficients[k]
            cols[starts[i] : starts[i + 1]] = c
            vals[starts[i] : starts[i + 1]] = v
        ranks = np.repeat(np.arange(len(keys), dtype=np.int64), sizes)
        combined = ranks * stride + cols
        self._packed = (keys, combined, vals, stride)
        return self._packed

    def coefficient_matrix_for(
        self, col_map: np.ndarray, entity_ids: list
    ) -> np.ndarray:
        """Project stored coefficients into a block's local column layout:
        returns (E, D) with w_local[e, k] = w_e[col_map[e, k]].  Used when
        scoring new data through the block pipeline.  Fully vectorized: one
        ``searchsorted`` over the packed combined-key array covers every
        lane and column at once (no per-entity Python loop)."""
        keys, combined, vals, stride = self._ensure_packed()
        E, D = col_map.shape
        out = np.zeros((E, D), np.float32)
        if len(keys) == 0:
            return out
        lane_keys = np.asarray(entity_ids, dtype=object)
        rank = np.searchsorted(keys, lane_keys)
        rank_c = np.minimum(rank, len(keys) - 1)
        known = keys[rank_c] == lane_keys  # (E,) entity seen at training
        cm = np.asarray(col_map, np.int64)
        q = rank_c[:, None] * stride + cm  # (E, D) combined query keys
        pos = np.searchsorted(combined, q)
        pos_c = np.minimum(pos, len(combined) - 1)
        hit = (
            known[:, None]
            & (cm >= 0)
            & (pos < len(combined))
            & (combined[pos_c] == q)
        )
        out[hit] = vals[pos_c[hit]]
        return out


@dataclasses.dataclass
class GameModel:
    """Reference: ``GameModel`` — ordered per-coordinate models; the overall
    score of a row is the sum of its coordinate scores (plus offset)."""

    models: dict  # coordinate name -> FixedEffectModel | RandomEffectModel
    task: str

    def __getitem__(self, name: str):
        return self.models[name]

    @property
    def coordinate_names(self) -> list[str]:
        return list(self.models)
