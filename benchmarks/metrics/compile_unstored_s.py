"""Seconds of the backend compiles that the persistent cache will never
save: the ``backend_s`` of the set-up's compile records whose ``cache`` is
``unstored`` (a miss under the cache's thresholds, compiled anew on every
start, warm cache or not)."""

from benchmarks.metrics import _setup


def read(run):
    return _setup.total(
        _setup.setup_compiles(run), "backend_s", ("unstored",))
