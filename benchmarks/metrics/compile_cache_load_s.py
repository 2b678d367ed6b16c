"""Seconds spent loading executables from the persistent cache before the
window opened: the ``retrieval_s`` of the set-up's compile records whose
``cache`` is ``hit``."""

from benchmarks.metrics import _setup


def read(run):
    return _setup.total(_setup.setup_compiles(run), "retrieval_s", ("hit",))
