"""``grid_self_ms`` on this cell: what a grid costs beside its solves, where
``w`` and the 10-pair history are 10^6 floats."""

from benchmarks.metrics.grid_self_ms import read  # noqa: F401
