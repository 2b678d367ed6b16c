"""``tile_bwd_share_pct`` on this cell: the warm band's backward products'
share of its tile kernel's device time."""

from benchmarks.metrics.tile_bwd_share_pct import read  # noqa: F401
