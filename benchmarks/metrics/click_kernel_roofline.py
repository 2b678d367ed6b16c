"""The cold band's kernel's share of its roofline: the bytes one cold
product needs (``roofline.sparse_product_bytes`` of the cold entries, unit
values, every row and column), at the peak bytes/s, over its mean event.
Its events are one forward and one backward product an evaluation: twice
the ``fn_evals`` of the window's ``solver`` spans."""

from benchmarks import roofline
from benchmarks.metrics import _click, _layer_spans


def read(run):
    seconds = _click.cold_seconds(run)
    attrs = _click.wide_build(run)
    solves = [s.get("attrs", {}) for s in _layer_spans.between(
        run, "solver", "window_start", "window_end")]
    if not seconds or attrs is None or not solves or any(
            "fn_evals" not in a for a in solves):
        return None
    host = run.state["shape"]
    least, _bound = roofline.product_min_seconds(
        attrs["cold_nnz"], host["n_rows"], host["n_features"] + 1,
        roofline.peaks(run.device_kind), unit_values=True)
    events = 2 * sum(a["fn_evals"] for a in solves)
    return 100.0 * least / (seconds / events)
