"""``tile_bwd_share_pct`` on this cell: every trial is a forward and a
backward product, so the share stands where ``glm_lbfgs_fit`` has it; it is
what a value-only trial in the line search would take off a refused trial."""

from benchmarks.metrics.tile_bwd_share_pct import read  # noqa: F401
