"""Seconds from the first host-to-device copy of the per-entity blocks
until every one is resident: the program's ``game.place`` spans under the
run's ``game.build``."""

from benchmarks.metrics import _game


def read(run):
    return _game.setup_seconds(run, "game.place")
