"""``tile_kernel_roofline`` on this cell (the same kernel on the same layout,
under a mix of Hessian-vector products)."""

from benchmarks.metrics.tile_kernel_roofline import read  # noqa: F401
