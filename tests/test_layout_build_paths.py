"""The layout build's faster host paths give the arrays the slower ones
gave: canonical input skips the sort, the stripe split runs in chunks."""

import jax
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.ops import sparse_pallas
from photon_ml_tpu.ops.sparse import _is_canonical, canonicalize_coo


def _triples(seed, n_rows=300, n_cols=70, nnz=4000):
    rng = np.random.default_rng(seed)
    keys = np.sort(rng.choice(n_rows * n_cols, nnz, replace=False))
    return (keys // n_cols, keys % n_cols,
            rng.standard_normal(nnz).astype(np.float32), n_rows, n_cols)


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("pad_nnz", [None, 4100])
def test_canonical_input_skips_the_sort(dtype, pad_nnz):
    rows, cols, vals, n_rows, n_cols = _triples(0)
    fast = canonicalize_coo(rows.astype(dtype), cols.astype(dtype), vals,
                            n_rows, n_cols, pad_nnz)
    shuffle = np.random.default_rng(1).permutation(len(rows))
    slow = canonicalize_coo(rows[shuffle], cols[shuffle], vals[shuffle],
                            n_rows, n_cols, pad_nnz)
    for a, b in zip(fast, slow):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_duplicates_still_sum():
    rows = np.array([0, 0, 1, 1]); cols = np.array([1, 1, 0, 2])
    vals = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
    r, c, v = canonicalize_coo(rows, cols, vals, 2, 3)
    assert r.tolist() == [0, 1, 1] and c.tolist() == [1, 0, 2]
    assert v.tolist() == [3.0, 3.0, 4.0]


@pytest.mark.parametrize("fault", ["none", "swap", "duplicate"])
@pytest.mark.parametrize("at", [0, 63, 64, 65, 3998])
def test_canonical_order_is_read_in_chunks(fault, at):
    """One pair out of order, or one pair equal, anywhere -- at a chunk's
    first, last and straddling pair too -- is not canonical."""
    rows, cols, _vals, _n_rows, _n_cols = _triples(4)
    rows, cols = rows.copy(), cols.copy()
    if fault == "swap":
        rows[[at, at + 1]] = rows[[at + 1, at]]
        cols[[at, at + 1]] = cols[[at + 1, at]]
    elif fault == "duplicate":
        rows[at + 1], cols[at + 1] = rows[at], cols[at]
    assert _is_canonical(rows, cols, chunk=64) == (fault == "none")
    assert _is_canonical(rows, cols) == (fault == "none")


@pytest.mark.parametrize("chunk", [64, 1 << 24])
def test_stripe_split_in_chunks_builds_the_same_layout(chunk, monkeypatch):
    """A matrix with dense columns (stripes) and a sparse rest, built with
    the entry list cut into many chunks and into one."""
    rng = np.random.default_rng(2)
    n_rows, n_cols = 5000, 300
    sparse_part = sp.random(n_rows, n_cols, density=0.01, random_state=3,
                            dtype=np.float32, format="csr")
    dense_part = sp.csr_matrix(
        (rng.standard_normal(n_rows * 3).astype(np.float32),
         (np.repeat(np.arange(n_rows), 3), np.tile([5, 100, 299], n_rows))),
        shape=(n_rows, n_cols))
    csr = (sparse_part + dense_part).tocsr()
    monkeypatch.setattr(sparse_pallas, "_SPLIT_CHUNK", 1 << 24)
    want = sparse_pallas.host_layout_from_scipy_csr(csr)
    monkeypatch.setattr(sparse_pallas, "_SPLIT_CHUNK", chunk)
    got = sparse_pallas.host_layout_from_scipy_csr(csr)
    assert got.has_dense_cols and set(got.dense_col_ids) >= {5, 100, 299}
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
