"""Per-coordinate validation scoring for coordinate descent.

The reference's ``CoordinateDescent`` evaluates its validation
``EvaluationSuite`` after every coordinate update (SURVEY.md §2
CoordinateDescent, §3.2 loop).  Doing that cheaply requires scoring the
validation set against a coordinate's CURRENT device state without
finalizing a host-side model each step.  These scorers are built ONCE per
(training dataset, validation data) pair:

- ``FixedEffectValidationScorer`` — the validation shard as device
  ``GlmData``; one matvec per evaluation.
- ``RandomEffectValidationScorer`` — the validation rows grouped into entity
  blocks once, plus a host-precomputed STATIC gather map from every
  (validation lane, local column) into a flattened view of the training
  state (the per-bucket ``(E, D)`` coefficient arrays).  Each evaluation is
  then pure device work: flatten state → one ``take`` per validation block →
  batched einsum → scatter-add into the validation row space.  Entities
  unseen at training time (and column misses outside a training entity's
  active subspace) gather from a zero slot, so they score 0 exactly like the
  reference's projector-based scoring of unseen entities/features.

Both scorers are reused verbatim across a config grid when grid points share
the underlying training dataset (the gather map depends only on the training
dataset's entity layout, not on the coefficients).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.game.data import (
    RandomEffectDataset,
    build_random_effect_dataset,
)

Array = jax.Array


@jax.jit
def _fixed_matvec(features, w):
    return features.matvec(w)


@functools.lru_cache(maxsize=64)
def _re_val_score_jit(n_val: int, layout_sig: tuple):
    """Jitted static-gather validation scorer, memoized on the
    validation row count plus the (val blocks, train state) layout
    signature — the eviction granule (see coordinates._layout_sig) —
    where per-instance jits re-compiled identical programs for every
    scorer (one per coordinate per fit)."""

    def _score(state, blocks, gidxs):
        flat = jnp.concatenate(
            [s.ravel() for s in state] + [jnp.zeros((1,), jnp.float32)]
        )
        total_scores = jnp.zeros((n_val + 1,), jnp.float32)
        for vb, gidx in zip(blocks, gidxs):
            coefs = jnp.take(flat, gidx, axis=0)  # (E_v, D_v)
            s = jnp.einsum("erd,ed->er", vb.x_erd, coefs)
            total_scores = total_scores.at[vb.row_index.ravel()].add(
                s.ravel()
            )
        return total_scores[:n_val]

    return jax.jit(_score)


class FixedEffectValidationScorer:
    """score(w) = X_val @ w on device; built once per validation shard.

    Holds ONLY the feature matrix (scoring never reads labels/weights, and
    only the matvec orientation is needed — no Pallas dual-orientation
    layout, no dummy row arrays)."""

    def __init__(self, val_shard):
        import scipy.sparse as sp

        from photon_ml_tpu.ops.sparse import DenseMatrix, from_scipy_csr

        self.n_rows = val_shard.shape[0]
        if sp.issparse(val_shard):
            self._features = from_scipy_csr(sp.csr_matrix(val_shard))
        else:
            self._features = DenseMatrix(
                jnp.asarray(np.asarray(val_shard), jnp.float32)
            )

    def score(self, state: Array) -> Array:
        return _fixed_matvec(self._features, state)


def _flat_layout(state_shapes: Sequence[tuple[int, int]]):
    """Bucket (E, D) shapes → per-bucket offsets into the flattened state."""
    sizes = [e * d for e, d in state_shapes]
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    return offsets, int(offsets[-1])


class RandomEffectValidationScorer:
    """Static-gather scoring of validation rows against training RE state.

    ``train_dataset`` fixes the entity→(bucket, lane) layout and per-lane
    column maps; ``entity_col``/``val_shard`` are the validation rows.  The
    expensive grouping + gather-map construction happens here, once.
    """

    def __init__(
        self,
        train_dataset: RandomEffectDataset,
        entity_col,
        val_shard,
    ):
        n_val = val_shard.shape[0]
        self.n_rows = n_val
        # Group validation rows by entity (no active-set cap: scoring covers
        # every row).  Labels/weights are irrelevant for scoring.
        val_ds = build_random_effect_dataset(
            entity_col,
            val_shard,
            np.zeros(n_val, np.float32),
            np.ones(n_val, np.float32),
        )
        state_shapes = [
            (b.n_entities, b.block_dim) for b in train_dataset.blocks
        ]
        offsets, total = _flat_layout(state_shapes)
        self._miss = total  # index of the appended zero slot
        d = train_dataset.n_features

        # Flatten every training lane's active columns into ONE globally
        # sorted key table (global_lane_id * d + col — ascending because
        # lanes flatten in order and each lane's cmap holds its sorted
        # active cols first), so each validation block resolves with a
        # single searchsorted instead of a per-lane Python loop (the
        # loop was ~2 s per scorer at 100k entities).
        lane_gid0 = np.concatenate(
            [[0], np.cumsum([e for e, _d in state_shapes])]
        ).astype(np.int64)
        key_parts, pos_parts = [], []
        for tb, b in enumerate(train_dataset.blocks):
            tcmap = np.asarray(b.col_map)  # (E, D) active cols then -1 pad
            lanes, cols = np.nonzero(tcmap >= 0)
            key_parts.append(
                (lane_gid0[tb] + lanes).astype(np.int64) * d + tcmap[lanes, cols]
            )
            # cmap packs actives first, so the column position IS the
            # coefficient's rank in the lane's local space.
            pos_parts.append(
                offsets[tb] + lanes.astype(np.int64) * state_shapes[tb][1]
                + cols
            )
        train_keys = (
            np.concatenate(key_parts) if key_parts
            else np.empty(0, np.int64)
        )
        train_pos = (
            np.concatenate(pos_parts) if pos_parts
            else np.empty(0, np.int64)
        )

        gather_idxs = []
        for vb, vids in zip(val_ds.blocks, val_ds.entity_ids):
            vcmap = np.asarray(vb.col_map)  # (E_v, D_v) global cols, -1 pad
            gid = np.fromiter(
                (
                    -1 if (s := train_dataset.entity_to_slot.get(k)) is None
                    else lane_gid0[s[0]] + s[1]
                    for k in vids
                ),
                np.int64, count=len(vids),
            )
            gidx = np.full(vcmap.shape, self._miss, np.int64)
            valid = (vcmap >= 0) & (gid[:, None] >= 0)
            keys = gid[:, None] * d + vcmap
            if len(train_keys) and valid.any():
                kv = keys[valid]
                ss = np.searchsorted(train_keys, kv)
                hit = (ss < len(train_keys)) & (
                    train_keys[np.minimum(ss, len(train_keys) - 1)] == kv
                )
                flat = gidx[valid]
                flat[hit] = train_pos[ss[hit]]
                gidx[valid] = flat
            gather_idxs.append(jnp.asarray(gidx))

        self._val_blocks = val_ds.blocks
        self._gather_idxs = gather_idxs
        from photon_ml_tpu.game.coordinates import _layout_sig

        self._score_jit = _re_val_score_jit(
            n_val,
            _layout_sig((val_ds.blocks, gather_idxs))
            + tuple(state_shapes),
        )

    def score(self, state: list[Array]) -> Array:
        # A mesh-sharded coordinate leaves blocks committed to different
        # devices (packed vs split placements); jit rejects mixed committed
        # inputs, so stage to one device first.  Transfers preserve bits.
        shardings = {getattr(b, "sharding", None) for b in state}
        if len(shardings) > 1:
            dev = jax.devices()[0]
            state = [jax.device_put(b, dev) for b in state]
        return self._score_jit(state, self._val_blocks, self._gather_idxs)
