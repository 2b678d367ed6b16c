"""``cd_host_gap_pct`` on this cell: share of the traced window in which no
program ran on the device (six updates and six AUCs a fit to dispatch)."""

from benchmarks.metrics.cd_host_gap_pct import read  # noqa: F401
