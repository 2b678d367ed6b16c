"""The whole window's share of the chip's peak for an orthant-wise sweep: the
least time the chip could take for the products the solves NEEDED, over the
traced window on the trace's clock (``trace.Reduced.window_s``, what
``device_idle_pct`` is a share of).

Needed products, from the solver's own counts on the window's ``solver``
spans and the shapes alone: 2 for a solve's starting value+gradient and 2 for
each iteration's (the accepted trial's forward and backward product).  A
refused trial is the line search's choice, not needed work, and lowers the
share (``owlqn_fn_evals_per_iter`` shows them); so does the loop's own vector
work.  Each product is the larger of its operations over the peak FLOP/s and
its bytes over the peak bytes/s: bytes."""

from benchmarks import roofline
from benchmarks.metrics import _owlqn_spans


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:  # a CPU rehearsal has no device plane
        return None
    solves = _owlqn_spans.window_solves(run)
    if not solves:
        return None
    host = run.state["shape"]
    least, _bound = roofline.product_min_seconds(
        host["nnz"], host["n_rows"], host["n_features"] + 1,
        roofline.peaks(run.device_kind))
    products = sum(2 + 2 * a["iterations"] for a in solves)
    return 100.0 * products * least / t.window_s
