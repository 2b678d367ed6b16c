"""``fe_solve_share_pct`` on this cell: the fixed effect's share of the
device's busy time (``fixed_effect_train`` and ``fixed_effect_score``)."""

from benchmarks.metrics.fe_solve_share_pct import read  # noqa: F401
