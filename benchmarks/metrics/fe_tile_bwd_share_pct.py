"""The backward products' share of the tile kernel's device time on the
GAME cell (``_tiled_apply_bwd*`` over ``_tiled_apply*``)."""

from benchmarks.metrics.tile_bwd_share_pct import read  # noqa: F401
