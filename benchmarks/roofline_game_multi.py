"""What one GAME fit with several random effects needs, from shapes alone,
and the least time a chip could take for it (``roofline.py`` has the rule
and the peaks; ``roofline_game.py`` the fixed effect's products and one
Newton iteration's operations and bytes).  Nothing here reads the program's
layout, its padding or its launch counts.

A random effect's update.  One Newton iteration of one entity reads each of
its ACTIVE rows once (features at 4 bytes, label, weight, offset) and makes
margin, gradient and Hessian from them.  The update also gathers the
offset of each active row (4 bytes) and scores EVERY row, active or passive:
its features once more, and 4 bytes of score out.  Padding rows and lanes,
and whatever a layout stores beside the features, are not needed work.
"""

from __future__ import annotations

from benchmarks import roofline, roofline_game


def effect_update(n_rows: float, rows_active: float, dim: int,
                  row_iterations: float) -> tuple[float, float]:
    """(operations, bytes) of one update of one random effect of ``dim``
    columns: ``row_iterations`` is the sum over its entities of active rows
    times Newton iterations."""
    ops, nbytes = roofline_game.random_iteration(row_iterations, dim)
    ops += 2.0 * dim * n_rows
    nbytes += 4.0 * rows_active + n_rows * (4.0 * dim + 4.0)
    return ops, nbytes


def effect_update_seconds(n_rows, rows_active, dim, row_iterations, peak):
    return roofline.min_seconds(
        *effect_update(n_rows, rows_active, dim, row_iterations), peak)[0]
