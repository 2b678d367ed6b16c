"""``passes_per_solve`` on this cell: the warm band's tile-kernel launches /
2 / solves (every evaluation runs the warm band's forward and backward
product once, beside the cold band's)."""

from benchmarks.metrics.passes_per_solve import read  # noqa: F401
