"""Feature-matrix representations: dense and sparse, TPU-first.

The reference keeps examples as Breeze ``SparseVector``s inside RDD
partitions and runs BLAS dot/axpy per row inside its aggregators
(SURVEY.md §2, "Gradient/HVP aggregators").  TPUs want the opposite layout:
one large, statically-shaped, padded structure per shard that XLA can tile
onto the MXU / VPU.  Two interchangeable representations:

- ``DenseMatrix``: a plain ``(n_rows, n_cols)`` array; margins are a single
  matmul on the MXU.  Right for narrow feature spaces (a1a has 123 features)
  and for the padded per-entity blocks of random-effect solves.

- ``SparseMatrix``: flat COO with a static nnz budget (padding entries carry
  ``value = 0`` and point at row 0 / col 0, so they contribute nothing).
  ``matvec`` is gather + ``segment_sum`` over row ids; ``rmatvec`` (the Xᵀu
  needed for gradients) is gather + ``segment_sum`` over column ids.  Row ids
  are kept sorted so ``indices_are_sorted`` lets XLA lower the row reduction
  efficiently.

Both are registered as pytrees, so they can live inside ``jit``/``shard_map``
programs and be device-put once and reused across optimizer iterations
(the analogue of the reference persisting its RDDs).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Union

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["data"],
    meta_fields=[],
)
@dataclasses.dataclass
class DenseMatrix:
    """Dense feature matrix of shape (n_rows, n_cols)."""

    data: Array

    @property
    def shape(self) -> tuple[int, int]:
        return self.data.shape

    @property
    def n_rows(self) -> int:
        return self.data.shape[0]

    @property
    def n_cols(self) -> int:
        return self.data.shape[1]

    def matvec(self, w: Array) -> Array:
        """X @ w → (n_rows,) margins."""
        return self.data @ w

    def rmatvec(self, u: Array) -> Array:
        """Xᵀ @ u → (n_cols,) — the gradient-side reduction."""
        return self.data.T @ u

    def row_sq_matvec(self, v: Array) -> Array:
        """(X ⊙ X) @ v — used for diagonal-Hessian preconditioners."""
        return (self.data * self.data) @ v

    def sq_rmatvec(self, u: Array) -> Array:
        """(X ⊙ X)ᵀ @ u — per-feature squared reductions (Hessian diagonal
        ``diag(XᵀDX) = (X⊙X)ᵀ d``, second moments for summary stats)."""
        return (self.data * self.data).T @ u

    def col_nnz(self, row_mask: Array | None = None) -> Array:
        """Per-feature nonzero counts (summary stats).  ``row_mask`` excludes
        padding / zero-weight rows."""
        nz = self.data != 0
        if row_mask is not None:
            nz = jnp.logical_and(nz, row_mask[:, None])
        return jnp.sum(nz, axis=0)

    def col_min_max(self, row_mask: Array | None = None) -> tuple[Array, Array]:
        """Per-feature (min, max); rows excluded by ``row_mask`` (padding,
        zero-weight) contribute nothing."""
        if row_mask is None:
            return jnp.min(self.data, axis=0), jnp.max(self.data, axis=0)
        m = row_mask[:, None]
        mins = jnp.min(jnp.where(m, self.data, jnp.inf), axis=0)
        maxs = jnp.max(jnp.where(m, self.data, -jnp.inf), axis=0)
        return mins, maxs


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["row_ids", "col_ids", "values"],
    meta_fields=["n_rows", "n_cols"],
)
@dataclasses.dataclass
class SparseMatrix:
    """Flat COO sparse matrix with a static (padded) nnz budget.

    Invariants: ``row_ids`` sorted ascending; padding entries have
    ``values == 0`` (their row/col ids are arbitrary but in-range).
    """

    row_ids: Array  # (nnz,) int32, sorted
    col_ids: Array  # (nnz,) int32
    values: Array  # (nnz,) float
    n_rows: int
    n_cols: int

    @property
    def shape(self) -> tuple[int, int]:
        return (self.n_rows, self.n_cols)

    @property
    def nnz(self) -> int:
        return self.values.shape[0]

    def matvec(self, w: Array) -> Array:
        contrib = self.values * jnp.take(w, self.col_ids)
        return jax.ops.segment_sum(
            contrib, self.row_ids, num_segments=self.n_rows, indices_are_sorted=True
        )

    def rmatvec(self, u: Array) -> Array:
        contrib = self.values * jnp.take(u, self.row_ids)
        return jax.ops.segment_sum(contrib, self.col_ids, num_segments=self.n_cols)

    def row_sq_matvec(self, v: Array) -> Array:
        contrib = self.values * self.values * jnp.take(v, self.col_ids)
        return jax.ops.segment_sum(
            contrib, self.row_ids, num_segments=self.n_rows, indices_are_sorted=True
        )

    def sq_rmatvec(self, u: Array) -> Array:
        """(X ⊙ X)ᵀ @ u — per-feature squared reductions."""
        contrib = self.values * self.values * jnp.take(u, self.row_ids)
        return jax.ops.segment_sum(contrib, self.col_ids, num_segments=self.n_cols)

    def _live_entries(self, row_mask: Array | None) -> Array:
        """Entries that represent a real stored value: nonzero (padding
        entries carry value 0) and, with ``row_mask``, in a live row."""
        live = self.values != 0
        if row_mask is not None:
            live = jnp.logical_and(live, jnp.take(row_mask, self.row_ids))
        return live

    def col_nnz(self, row_mask: Array | None = None) -> Array:
        """Per-feature nonzero counts.  ``row_mask`` excludes padding /
        zero-weight rows."""
        return jax.ops.segment_sum(
            self._live_entries(row_mask).astype(jnp.int32),
            self.col_ids,
            num_segments=self.n_cols,
        )

    def col_min_max(self, row_mask: Array | None = None) -> tuple[Array, Array]:
        """Per-feature (min, max) over stored entries of live rows, folded
        with the implicit zeros of unstored entries (a column with fewer
        stored values than live rows necessarily contains a zero)."""
        live = self._live_entries(row_mask)
        nnz = jax.ops.segment_sum(
            live.astype(jnp.int32), self.col_ids, num_segments=self.n_cols
        )
        n_live_rows = (
            self.n_rows
            if row_mask is None
            else jnp.sum(row_mask.astype(jnp.int32))
        )
        has_zero = nnz < n_live_rows
        # Non-live entries are neutralized to ±inf so they can't pollute the
        # column they point at; the has_zero fold restores the 0 that
        # zero-valued entries represent (and repairs empty segments).
        vals_min = jnp.where(live, self.values, jnp.inf)
        vals_max = jnp.where(live, self.values, -jnp.inf)
        mins = jax.ops.segment_min(vals_min, self.col_ids, num_segments=self.n_cols)
        maxs = jax.ops.segment_max(vals_max, self.col_ids, num_segments=self.n_cols)
        mins = jnp.where(has_zero, jnp.minimum(mins, 0.0), mins)
        maxs = jnp.where(has_zero, jnp.maximum(maxs, 0.0), maxs)
        return mins, maxs

    def to_dense(self) -> DenseMatrix:
        dense = jnp.zeros(self.shape, dtype=self.values.dtype)
        dense = dense.at[self.row_ids, self.col_ids].add(self.values)
        return DenseMatrix(dense)


FeatureMatrix = Union[DenseMatrix, SparseMatrix]


def from_scipy_csr(csr, pad_nnz: int | None = None, dtype=jnp.float32) -> SparseMatrix:
    """Build a SparseMatrix from a scipy CSR matrix, padding nnz to a static budget."""
    csr = csr.tocsr()
    csr.sum_duplicates()
    coo = csr.tocoo()
    return from_coo(
        coo.row, coo.col, coo.data, csr.shape[0], csr.shape[1], pad_nnz, dtype
    )


def canonicalize_coo(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    n_cols: int,
    pad_nnz: int | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side COO canonicalization shared by the device and Pallas
    builders: dedup duplicate (row, col) entries by summing, sort by row,
    pad nnz to the requested budget.  Returns numpy (rows i32, cols i32,
    vals)."""
    rows = np.asarray(rows)
    cols = np.asarray(cols)
    vals = np.asarray(vals)
    # Canonicalize: duplicate coordinates must be summed, or row_sq_matvec
    # (which squares per-entry values) diverges from the dense equivalent.
    if _is_canonical(rows, cols):
        # A CSR with sorted, distinct columns comes out of ``tocoo`` this
        # way: the sort below would be the identity and its four gathers
        # copies.  At 4e8 entries they were two minutes.
        return pad_coo_triples(
            rows.astype(np.int32, copy=False),
            cols.astype(np.int32, copy=False), vals,
            pad_nnz if pad_nnz is not None else rows.shape[0])
    # One stable (radix) argsort of the combined key orders by (row, col);
    # the np.unique(return_inverse) + scatter-add formulation this
    # replaces cost ~2x at 33M entries, paid even with zero duplicates.
    keys = rows.astype(np.int64) * np.int64(n_cols) + cols.astype(np.int64)
    order = np.argsort(keys, kind="stable")
    keys = keys[order]
    rows = rows[order].astype(np.int32)
    cols = cols[order].astype(np.int32)
    vals = vals[order]
    if keys.size > 1 and bool(np.any(keys[1:] == keys[:-1])):
        change = np.empty(keys.size, dtype=bool)
        change[0] = True
        np.not_equal(keys[1:], keys[:-1], out=change[1:])
        starts = np.flatnonzero(change)
        vals = np.add.reduceat(vals, starts)
        rows = rows[starts]
        cols = cols[starts]
    budget = pad_nnz if pad_nnz is not None else rows.shape[0]
    return pad_coo_triples(rows, cols, vals, budget)


def _is_canonical(rows: np.ndarray, cols: np.ndarray,
                  chunk: int = 1 << 24) -> bool:
    """Whether the entries are already sorted by (row, col) and distinct,
    read in chunks (a few bytes of scratch an entry of the chunk, and no
    further than the first entry out of order)."""
    for lo in range(0, len(rows) - 1, chunk):
        r = rows[lo:lo + chunk + 1]
        c = cols[lo:lo + chunk + 1]
        same_row = r[1:] == r[:-1]
        ahead = (r[1:] > r[:-1]) | (same_row & (c[1:] > c[:-1]))
        if not ahead.all():
            return False
    return True


def pad_coo_triples(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, budget: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad already-canonical (row-sorted) COO triples to a static nnz
    budget.  THE padding invariant, shared by every builder (device COO,
    Pallas spill, streaming chunk stores): pad entries carry value 0 and
    the LAST row id, so the sorted-rows invariant holds and the entries
    are numerically inert."""
    nnz = rows.shape[0]
    if budget < nnz:
        raise ValueError(f"pad_nnz={budget} < actual nnz={nnz}")
    pad = budget - nnz
    if pad:
        pad_row = rows[-1] if nnz else 0
        rows = np.concatenate([rows, np.full(pad, pad_row, np.int32)])
        cols = np.concatenate([cols, np.zeros(pad, np.int32)])
        vals = np.concatenate([vals, np.zeros(pad, vals.dtype)])
    return rows, cols, vals


def from_coo(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    n_cols: int,
    pad_nnz: int | None = None,
    dtype=jnp.float32,
) -> SparseMatrix:
    """Build a SparseMatrix from host COO triples (dedups duplicate (row, col)
    entries by summing, sorts by row, pads nnz)."""
    rows, cols, vals = canonicalize_coo(
        rows, cols, vals, n_rows, n_cols, pad_nnz
    )
    return SparseMatrix(
        row_ids=jnp.asarray(rows),
        col_ids=jnp.asarray(cols),
        values=jnp.asarray(vals, dtype=dtype),
        n_rows=int(n_rows),
        n_cols=int(n_cols),
    )
