"""Coordinates the projection onto the chosen orthant set to zero, per
iteration: the sum of ``orthant_clamps`` (counted over accepted steps in
``owlqn_solve``'s loop state) over the sum of ``iterations`` of the window's
``solver`` spans.  Zero where the orthant logic does nothing."""

from benchmarks.metrics import _owlqn_spans


def read(run):
    return _owlqn_spans.per_iteration(run, "orthant_clamps")
