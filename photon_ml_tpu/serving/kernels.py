"""The ONE implementation of GAME/GLM scoring math.

Both scoring surfaces route through this module so batch and online
results come from the same formulas:

- **Batch** (``GameTransformer`` → ``game_scoring_driver``): host compute
  over whole datasets — :func:`fixed_effect_matvec` (scipy CSR matvec),
  :func:`random_effect_block_scores` (pre-grouped block gather + einsum),
  summed into the offset column.
- **Online** (``serving.runtime.ScoringRuntime``): :func:`build_bucket_kernel`
  returns the jit'd padded-batch program — per-row multiply+reduce for
  every coordinate plus the hot-table gather — and
  :func:`dense_coefficient_rows` materializes the cold tail's per-entity
  coefficients host-side for it.

Numerical contract the online path relies on: the bucket kernel computes
each row's margin as ``offset + Σ_coord sum(x_row * w, axis=-1)`` — a
per-row reduction whose result is INDEPENDENT of the padded batch size
(XLA row reductions don't re-associate across rows), so scores are
bit-identical across the bucket ladder and between batched and
single-request scoring.  A plain matmul does NOT have this property on
CPU (verified: ``X @ w`` re-blocks by batch shape), which is why the
kernels spell the reduction out.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import numpy as np

from photon_ml_tpu.game.model import RandomEffectModel


# ---------------------------------------------------------------------------
# Host batch path (GameTransformer / game_scoring_driver)
# ---------------------------------------------------------------------------

def fixed_effect_matvec(shard_matrix, means: np.ndarray) -> np.ndarray:
    """Fixed-effect margins of a whole scoring shard: one CSR matvec."""
    w = np.asarray(means, np.float32)
    return np.asarray(shard_matrix @ w, np.float32).ravel()


def random_effect_block_scores(
    model: RandomEffectModel, dataset
) -> np.ndarray:
    """Score a pre-grouped random-effect dataset through the block
    pipeline; entities without trained coefficients (and padding lanes)
    contribute zero.  ``dataset`` is a host-side RandomEffectDataset."""
    n = dataset.n_global_rows
    out = np.zeros(n + 1, np.float32)
    for block, block_ids in zip(dataset.blocks, dataset.entity_ids):
        coefs = model.coefficient_matrix_for(block.col_map, block_ids)
        scores = np.einsum("erd,ed->er", block.x_erd, coefs)
        np.add.at(out, block.row_index.ravel(), scores.ravel())
    return out[:n]


def sum_margins(
    n_rows: int,
    offset: Optional[np.ndarray],
    parts: Sequence[np.ndarray],
) -> np.ndarray:
    """Offset + per-coordinate margin sum (the GAME score definition)."""
    total = (
        np.zeros(n_rows, np.float32)
        if offset is None
        else np.asarray(offset, np.float32).copy()
    )
    for p in parts:
        total += p
    return total


# ---------------------------------------------------------------------------
# Shared gather: sparse per-entity table -> dense coefficient rows
# ---------------------------------------------------------------------------

def dense_coefficient_rows(
    model: RandomEffectModel, entity_ids: Sequence
) -> np.ndarray:
    """Materialize ``(B, n_features)`` dense coefficient rows from the
    entity→(cols, vals) table — the host-side gather behind the online
    cold tail and hot-set fills.  Unknown entities (and ``None``) get the
    zero row, the same join-miss semantics as batch scoring."""
    out = np.zeros((len(entity_ids), model.n_features), np.float32)
    table = model.coefficients
    for i, key in enumerate(entity_ids):
        entry = table.get(key) if key is not None else None
        if entry is not None:
            cols, vals = entry
            out[i, cols] = vals
    return out


# ---------------------------------------------------------------------------
# Online bucket kernel (ScoringRuntime)
# ---------------------------------------------------------------------------

def build_bucket_kernel(mean_fn: Callable):
    """Jit'd padded-batch scoring program for one model structure.

    Called as ``kernel(offsets, fixed_x, fixed_w, re_x, re_tables,
    re_slots, re_cold)`` where the tuples are per-coordinate:

    - ``fixed_x[i]``: ``(B, D_i)`` dense request features,
      ``fixed_w[i]``: ``(D_i,)`` coefficients;
    - ``re_x[j]``: ``(B, D_j)`` request features,
      ``re_tables[j]``: ``(H+1, D_j)`` device-resident hot set (row 0 is
      the reserved zero row), ``re_slots[j]``: ``(B,)`` int32 hot slots
      (0 = cold/unknown/padding), ``re_cold[j]``: ``(B, D_j)`` host-side
      fallback gathers (zero on hot rows).

    ``table[slot] + cold`` is exact — one side is always the zero row —
    so a row scores bit-identically whether its entity is hot or cold.
    Returns ``(margins, means)``; one jitted callable serves every
    bucket size (jit re-specializes per shape, the runtime warms each
    bucket ahead of the request path).
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def kernel(offsets, fixed_x, fixed_w, re_x, re_tables, re_slots, re_cold):
        total = offsets
        for x, w in zip(fixed_x, fixed_w):
            total = total + jnp.sum(x * w[None, :], axis=1)
        for x, table, slots, cold in zip(re_x, re_tables, re_slots, re_cold):
            coefs = table[slots] + cold
            total = total + jnp.sum(x * coefs, axis=1)
        return total, mean_fn(total)

    return kernel


def build_fused_bucket_kernel(mean_fn: Callable):
    """Single-round-trip variant of :func:`build_bucket_kernel`.

    The composed kernel takes ``1 + n_fixed + 3·n_random`` request-side
    arrays, so every batch pays that many host→device transfers plus two
    device→host readbacks.  This kernel takes exactly TWO request-side
    arguments — one packed float32 buffer and one int32 slot matrix —
    and returns margins and means STACKED into one ``(2, B)`` array, so
    a batch costs two uploads and one readback regardless of model
    structure.

    ``packed`` is ``(B, 1 + Σ fixed_dims + Σ 2·re_dims)``, laid out as
    the offset column, then each fixed coordinate's request features,
    then per random coordinate its request features followed by its
    host-gathered cold rows.  ``slots`` is ``(n_random, B)`` int32 hot
    slots.  ``fixed_w`` / ``re_tables`` are the device-resident model
    arrays, unchanged from the composed signature.

    Bit-parity contract: the margin arithmetic is the SAME expression
    sequence as the composed kernel — per-row multiply+reduce per
    coordinate, accumulated in the same order, with ``table[slot] +
    cold`` exactness — over contiguous column slices of the packed
    buffer, so fused and composed scores are bitwise identical (pinned
    by tests/test_serving_wire.py).
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def kernel(packed, slots, fixed_w, re_tables):
        total = packed[:, 0]
        off = 1
        for w in fixed_w:
            d = w.shape[0]
            total = total + jnp.sum(
                packed[:, off:off + d] * w[None, :], axis=1
            )
            off += d
        for j, table in enumerate(re_tables):
            d = table.shape[1]
            x = packed[:, off:off + d]
            cold = packed[:, off + d:off + 2 * d]
            off += 2 * d
            coefs = table[slots[j]] + cold
            total = total + jnp.sum(x * coefs, axis=1)
        return jnp.stack([total, mean_fn(total)])

    return kernel
