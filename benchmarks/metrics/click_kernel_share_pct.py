"""``tile_kernel_share_pct`` on this cell: the warm band's tile kernel
(``_tiled_apply``) over busy time; the cold band's kernel is
``click_cold_share_pct``."""

from benchmarks.metrics.tile_kernel_share_pct import read  # noqa: F401
