"""The whole window's share of the chip's peak on the click log: the least
time the chip could take for the products the solves NEEDED, over the
traced window on the trace's clock (``trace.Reduced.window_s``).

Needed products, from the window's ``solver`` spans and the shapes alone: 2
for a solve's starting value+gradient and 2 for each iteration.  Each is
the larger of its operations over the peak FLOP/s and its bytes over the
peak bytes/s (bytes: every valued entry's index at unit values, and the
vectors), whatever band or stripe holds the entry."""

from benchmarks import roofline
from benchmarks.metrics import _layer_spans


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:  # a CPU rehearsal has no device plane
        return None
    solves = [s.get("attrs", {}) for s in _layer_spans.between(
        run, "solver", "window_start", "window_end")]
    if not solves:
        return None
    host = run.state["shape"]
    least, _bound = roofline.product_min_seconds(
        host["nnz"], host["n_rows"], host["n_features"] + 1,
        roofline.peaks(run.device_kind), unit_values=True)
    products = sum(2 + 2 * a["iterations"] for a in solves)
    return 100.0 * products * least / t.window_s
