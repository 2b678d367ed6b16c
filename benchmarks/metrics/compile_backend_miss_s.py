"""Seconds of real backend compiles before the window opened: the
``backend_s`` of the set-up's compile records whose ``cache`` is ``stored``,
``unstored`` or ``off`` (everything but a hit of the persistent cache)."""

from benchmarks.metrics import _setup


def read(run):
    return _setup.total(_setup.setup_compiles(run), "backend_s", _setup.MISSES)
