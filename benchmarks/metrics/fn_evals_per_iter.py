"""Objective evaluations per iteration, counted by the solver itself:
the sum of (``fn_evals`` - 1) over the sum of ``iterations`` of the
window's ``solver`` spans (the one taken off is each solve's starting
value+gradient).  ``passes_per_solve`` infers the same from kernel launches
in the device trace."""

from benchmarks.metrics import _layer_spans


def read(run):
    solves = [s.get("attrs", {}) for s in _layer_spans.between(
        run, "solver", "window_start", "window_end")]
    if not solves or any("fn_evals" not in a for a in solves):
        return None
    iters = sum(a["iterations"] for a in solves)
    return sum(a["fn_evals"] - 1 for a in solves) / iters if iters else None
