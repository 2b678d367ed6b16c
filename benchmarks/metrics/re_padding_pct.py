"""Rows of the per-entity blocks that are padding, as a share of all their
rows: from the ``rows_padded`` and ``rows_real`` of a random-effect
``coordinate.train`` span's ``buckets`` (one update's blocks; every update
has the same)."""

from benchmarks.metrics import _game


def read(run):
    updates = _game.random_updates(run)
    if not updates:
        return None
    padded = sum(b["rows_padded"] for b in updates[0])
    real = sum(b["rows_real"] or 0 for b in updates[0])
    return 100.0 * (padded - real) / padded if padded and real else None
