"""The tile kernel's device time (the fixed effect's products) over all
device busy time in the window."""

from benchmarks.metrics.tile_kernel_share_pct import read  # noqa: F401
