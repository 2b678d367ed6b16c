"""``tile_kernel_roofline`` on this cell (the same kernel on the same layout:
``benchmarks/roofline.py``'s count of its work, no new one)."""

from benchmarks.metrics.tile_kernel_roofline import read  # noqa: F401
