"""Buckets of the per-movie ladder (``re_buckets`` for one ladder)."""

from benchmarks.metrics import _multi


def read(run):
    ups = _multi.updates(run, "movie")
    return float(len(ups[0])) if ups else None
