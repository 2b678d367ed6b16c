"""CG iterations (one Hessian-vector product each) per outer iteration,
counted by the solver itself: the sum of ``cg_iterations`` over the sum of
``iterations`` of the window's ``solver`` spans."""

from benchmarks.metrics import _tron_spans


def read(run):
    return _tron_spans.per_outer_iteration(run, "cg_iterations")
