"""``tile_kernel_share_pct`` on this cell: the tile kernel's device time over
all busy time in the window."""

from benchmarks.metrics.tile_kernel_share_pct import read  # noqa: F401
