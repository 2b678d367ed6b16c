"""Non-zeros of the answers as a share of the penalised coefficients: the
mean of the ``nonzeros`` the solver counted (``w != 0`` under the penalty's
mask, on the device) over the window's ``solver`` spans, over the
configuration's ``n_features`` (the intercept is not penalised)."""

from benchmarks.metrics import _owlqn_spans


def read(run):
    solves = _owlqn_spans.window_solves(run)
    if not solves:
        return None
    mean = sum(a["nonzeros"] for a in solves) / len(solves)
    return 100.0 * mean / run.state["shape"]["n_features"]
