"""``tile_kernel_share_pct`` on this cell; what is left is XLA (the loss, the
stripes' fusions, and the loop's own work over the coefficient vector: the
pseudo-gradient, the two-loop recursion, the projection, the history)."""

from benchmarks.metrics.tile_kernel_share_pct import read  # noqa: F401
