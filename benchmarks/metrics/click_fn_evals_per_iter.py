"""``fn_evals_per_iter`` on this cell: the same L-BFGS and Wolfe search over
the wide layout; a reading apart from ``glm_lbfgs_fit``'s is the log's
curvature, not the layout."""

from benchmarks.metrics.fn_evals_per_iter import read  # noqa: F401
