"""Host seconds of grouping the rows by user (keys the rows are sorted by):
the per-user coordinate's ``game.group`` span under ``game.build``."""

from benchmarks.metrics import _multi


def read(run):
    return _multi.setup_seconds(run, "game.group", "user")
