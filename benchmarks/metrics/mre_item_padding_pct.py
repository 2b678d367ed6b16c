"""Rows of the per-movie blocks that are padding, as a share of all their
rows (``re_padding_pct`` for one ladder; passive rows are stored flat and
have none)."""

from benchmarks.metrics import _multi


def read(run):
    ups = _multi.updates(run, "movie")
    if not ups:
        return None
    padded = sum(b["rows_padded"] for b in ups[0])
    real = sum(b["rows_real"] or 0 for b in ups[0])
    return 100.0 * (padded - real) / padded if padded and real else None
