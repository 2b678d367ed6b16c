"""``tile_kernel_share_pct`` on this cell; what is left is XLA (the loss and
its curvature, the CG's vectors, the stripes)."""

from benchmarks.metrics.tile_kernel_share_pct import read  # noqa: F401
