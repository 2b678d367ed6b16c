"""``passes_per_solve`` on this cell: tile-kernel launches / 2 / solves.  A
trust-region Newton solve needs ``fn_evals + cg_iterations`` passes by its
own counters, and on the chip this reads exactly that (PERF.md section 6,
PR 33): the forward product that ``tron_solve`` writes a second time for the
curvature at every trial point is merged with the value+gradient's by the
compiler.  A reading above the counters' sum is a product nobody asked
for."""

from benchmarks.metrics.passes_per_solve import read  # noqa: F401
