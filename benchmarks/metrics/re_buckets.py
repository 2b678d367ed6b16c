"""Buckets of the random effect's ladder (each a block shape of its own
inside the one compiled program): the entries of a random-effect
``coordinate.train`` span's ``buckets``."""

from benchmarks.metrics import _game


def read(run):
    updates = _game.random_updates(run)
    return float(len(updates[0])) if updates else None
