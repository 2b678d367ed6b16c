"""What one GAME fit needs, from shapes alone, and the least time a chip
could take for it (``roofline.py`` has the rule and the peaks).  Nothing
here reads the program's layout, its padding or its launch counts.

Fixed effect.  One product with the ``global`` shard (``X beta`` or
``X^T u``) reads, per row, the index of its movie and of each genre tag (2
bytes each: indicators have no value to read) and its summary features (4
bytes each: a dense column has no index), and one vector element per row
and per column; a multiply and an add per stored entry.  One L-BFGS
iteration needs two products (a value and a gradient); the starting point
of a solve two more.  Further line-search trials are the solver's choice,
not needed work.

Random effect.  One Newton iteration of one user reads each of the user's
rows once -- its features (4 bytes each), label, weight and offset -- and
makes the margin, the gradient and the Hessian from them: ``2 d`` + ``2 d``
+ ``2 d^2`` operations a row.  An update also gathers each row's offset and
scatters each row's score (4 + 4 bytes, and the features once more for the
score).  Padding rows and padding lanes are not needed work.
"""

from __future__ import annotations

from benchmarks import roofline


def fixed_product(shape: dict) -> tuple[float, float]:
    """(operations, bytes) of one product with the ``global`` shard."""
    n, cols = shape["n_rows"], (
        shape["n_movies"] + shape["n_genres"] + shape["n_dense"] + 1)
    indicators = n + shape["genre_tags"]
    nbytes = 2 * indicators + 4 * n * shape["n_dense"] + 4 * (n + cols)
    return 2.0 * shape["fixed_nnz"], float(nbytes)


def fixed_solve_seconds(shape, iterations, peak) -> float:
    ops, nbytes = fixed_product(shape)
    least, _bound = roofline.min_seconds(ops, nbytes, peak)
    return (2 * iterations + 2) * least


def tiled_product_seconds(shape, tiled_entries: float, peak) -> float:
    """One product over the entries the tile kernel is handed: indicators
    (an index of 2 bytes each), one vector element per row and column."""
    cols = shape["n_movies"] + shape["n_genres"] + shape["n_dense"] + 1
    nbytes = 2.0 * tiled_entries + 4.0 * (shape["n_rows"] + cols)
    return roofline.min_seconds(2.0 * tiled_entries, nbytes, peak)[0]


def random_iteration(rows: float, dim: int) -> tuple[float, float]:
    """(operations, bytes) of one Newton iteration over ``rows`` rows."""
    return rows * (4.0 * dim + 2.0 * dim * dim), rows * (4.0 * dim + 12.0)


def random_update_seconds(shape, row_iterations, peak) -> float:
    """``row_iterations``: the sum over users of rows times iterations."""
    dim, n = shape["n_genres"] + 1, shape["n_rows"]
    ops, nbytes = random_iteration(row_iterations, dim)
    # the offsets in, the scores out
    ops += 2.0 * dim * n
    nbytes += n * (4.0 * dim + 8.0)
    return roofline.min_seconds(ops, nbytes, peak)[0]
