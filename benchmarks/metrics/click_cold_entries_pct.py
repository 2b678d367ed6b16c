"""The cold band's share of the valued entries: the ``layout.build``
span's ``cold_nnz`` over the entries of all storage classes (stripes, warm
tiles, spill, cold)."""

from benchmarks.metrics import _click


def read(run):
    attrs = _click.wide_build(run)
    if attrs is None:
        return None
    valued = sum(attrs[k] for k in (
        "stripe_nnz", "warm_tiled_nnz", "spilled", "cold_nnz"))
    return 100.0 * attrs["cold_nnz"] / valued if valued else None
