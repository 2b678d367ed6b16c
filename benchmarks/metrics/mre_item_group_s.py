"""Host seconds of grouping the rows by movie (keys scattered over the whole
file; an active-row cap that splits 432 movies into active and passive
rows): the per-movie coordinate's ``game.group`` span."""

from benchmarks.metrics import _multi


def read(run):
    return _multi.setup_seconds(run, "game.group", "movie")
