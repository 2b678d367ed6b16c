"""``passes_per_solve`` on this cell: tile-kernel launches / 2 / solves.  An
orthant-wise solve needs ``fn_evals`` passes by its own counter (every
line-search trial is a whole value+gradient, though a refused trial's
gradient is never read); a reading above that is a product nobody asked
for."""

from benchmarks.metrics.passes_per_solve import read  # noqa: F401
