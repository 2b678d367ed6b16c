"""Data passes per fixed-effect solve: tile-kernel launches in the trace
/ 2 / the window's fixed-effect solves; the line search's trials and each
update's scoring product included."""

from benchmarks.metrics.passes_per_solve import read  # noqa: F401
