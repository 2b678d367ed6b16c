"""The warm band's tile kernel's share of its roofline: the bytes one
product of the warm band's tiles needs (``roofline.sparse_product_bytes`` of
the entries the build placed in its tiles, ``warm_tiled_nnz``, unit values,
every row and the band's columns), at the peak bytes/s, over the mean
device duration of the kernel's events (``_tiled_apply*``)."""

from benchmarks import roofline
from benchmarks.metrics import _click


def read(run):
    t = run.trace
    attrs = _click.wide_build(run)
    if t is None or not t.kernel_durations_s or attrs is None:
        return None
    least, _bound = roofline.product_min_seconds(
        attrs["warm_tiled_nnz"], run.state["shape"]["n_rows"],
        attrs["warm_cols"], roofline.peaks(run.device_kind),
        unit_values=True)
    mean = sum(t.kernel_durations_s) / len(t.kernel_durations_s)
    return 100.0 * least / mean
