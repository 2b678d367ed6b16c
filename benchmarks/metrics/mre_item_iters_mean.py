"""Newton iterations a movie's solve makes, the mean over real movies and
over the window's per-movie updates (``re_iters_mean`` for one ladder)."""

from benchmarks.metrics import _multi


def read(run):
    buckets = [b for up in _multi.updates(run, "movie") for b in up]
    lanes = sum(b["lanes"] for b in buckets)
    return sum(b["iterations_sum"] for b in buckets) / lanes if lanes else None
