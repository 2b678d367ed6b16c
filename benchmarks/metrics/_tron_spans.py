"""The window's ``solver`` spans of a trust-region Newton cell, for the
readers beside this file.  A program whose spans carry no CG counts (a
commit before they were added) gives ``None``."""

from benchmarks.metrics import _layer_spans


def window_solves(run):
    """The attributes of the window's ``solver`` spans, or ``None`` where
    there are none or any lacks the trust-region counts."""
    solves = [s.get("attrs", {}) for s in _layer_spans.between(
        run, "solver", "window_start", "window_end")]
    if not solves or any("cg_iterations" not in a for a in solves):
        return None
    return solves


def per_outer_iteration(run, count):
    """The sum of the attribute ``count`` over the sum of ``iterations`` of
    the window's ``solver`` spans."""
    solves = window_solves(run)
    iters = sum(a["iterations"] for a in solves) if solves else 0
    return sum(a[count] for a in solves) / iters if iters else None
