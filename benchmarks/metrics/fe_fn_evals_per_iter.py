"""Objective evaluations per L-BFGS iteration of the fixed effect, counted
by the solver itself: the sum of (``fn_evals`` - 1) over the sum of
``iterations`` of the window's fixed-effect ``coordinate.train`` spans (the
one taken off is each solve's starting value+gradient)."""

from benchmarks.metrics import _game


def read(run):
    solves = [s["attrs"] for s in _game.window_spans(run, "coordinate.train")
              if s.get("attrs", {}).get("kind") == "fixed"]
    if not solves or any("fn_evals" not in a for a in solves):
        return None
    iters = sum(a["iterations"] for a in solves)
    return sum(a["fn_evals"] - 1 for a in solves) / iters if iters else None
