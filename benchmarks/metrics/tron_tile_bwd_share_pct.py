"""``tile_bwd_share_pct`` on this cell: a Hessian-vector product is a forward
and a backward product, as a value+gradient is, so the mix leaves the share
where ``glm_lbfgs_fit`` has it."""

from benchmarks.metrics.tile_bwd_share_pct import read  # noqa: F401
