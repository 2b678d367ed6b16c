"""The tile kernel's share of its roofline: needed bytes of one forward or
backward product over the peak bytes/s, over the mean device duration of
the kernel's events in the trace (bound by bytes: 2 operations per 6)."""

from benchmarks import roofline


def read(run):
    t = run.trace
    if t is None or not t.kernel_durations_s:
        return None
    host = run.state["shape"]
    # The kernel computes the tiled part: the valued entries, without the
    # dense intercept column.
    least, _bound = roofline.product_min_seconds(
        host["nnz"], host["n_rows"], host["n_features"],
        roofline.peaks(run.device_kind))
    mean = sum(t.kernel_durations_s) / len(t.kernel_durations_s)
    return 100.0 * least / mean
