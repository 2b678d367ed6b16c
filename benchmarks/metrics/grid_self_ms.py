"""What a grid costs beside its solves: each ``grid`` span of the window
less its ``solver`` children (warm-start hand-over, the scalar read-back,
the model objects, the harness's ``on_solved``), in milliseconds per
solve."""

from benchmarks.metrics import _layer_spans


def read(run):
    self_s, solves = 0.0, 0
    for grid in _layer_spans.between(run, "grid", "window_start",
                                     "window_end"):
        kids = _layer_spans.children(grid, "solver")
        self_s += grid["dur"] - sum(k["dur"] for k in kids)
        solves += len(kids)
    return 1e3 * self_s / solves if solves else None
