"""``cd_metric_share_pct`` on this cell: the per-update train AUC's programs
(``jit_device_*``) over busy seconds, six calls a fit."""

from benchmarks.metrics.cd_metric_share_pct import read  # noqa: F401
