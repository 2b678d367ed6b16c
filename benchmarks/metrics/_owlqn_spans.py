"""The window's ``solver`` spans of an orthant-wise (OWL-QN) cell, for the
readers beside this file.  A program whose spans carry no orthant counts (a
commit before they were added) gives ``None``."""

from benchmarks.metrics import _layer_spans


def window_solves(run):
    """The attributes of the window's ``solver`` spans, or ``None`` where
    there are none or any lacks the orthant-wise counts."""
    solves = [s.get("attrs", {}) for s in _layer_spans.between(
        run, "solver", "window_start", "window_end")]
    if not solves or any("orthant_clamps" not in a for a in solves):
        return None
    return solves


def per_iteration(run, count, less=0):
    """The sum of the attribute ``count`` (less ``less`` a solve) over the
    sum of ``iterations`` of the window's ``solver`` spans."""
    solves = window_solves(run)
    iters = sum(a["iterations"] for a in solves) if solves else 0
    return sum(a[count] - less for a in solves) / iters if iters else None
