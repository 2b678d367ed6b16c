"""Host seconds of grouping the rows into per-entity blocks: the program's
``game.group`` spans under the run's ``game.build`` (sort by entity, active
columns, the bucket ladder, the blocks' arrays; no device work)."""

from benchmarks.metrics import _game


def read(run):
    return _game.setup_seconds(run, "game.group")
