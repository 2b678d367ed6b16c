"""The share of outer iterations whose step the trust region refused (a
value+gradient and a CG spent on no progress): the sum of ``rejected_steps``
over the sum of ``iterations`` of the window's ``solver`` spans."""

from benchmarks.metrics import _tron_spans


def read(run):
    share = _tron_spans.per_outer_iteration(run, "rejected_steps")
    return None if share is None else 100.0 * share
