"""``grid_self_ms`` on this cell: what a grid costs beside its solves.  An
orthant-wise solve's one batched read-back carries six scalars where an
L-BFGS solve's carries three (``optim/problem.grid_loop``), and the ``solver``
span three more attributes: this is where that would show."""

from benchmarks.metrics.grid_self_ms import read  # noqa: F401
