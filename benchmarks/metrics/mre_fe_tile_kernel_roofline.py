"""``fe_tile_kernel_roofline`` on this cell (the same ``global`` shard)."""

from benchmarks.metrics.fe_tile_kernel_roofline import read  # noqa: F401
