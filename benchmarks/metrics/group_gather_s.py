"""Host seconds of the one bulk gather of a shard's rows into entity order
(``rows_csr[order]``): the ``game.group.gather`` children of every
``game.group`` of the set-up, summed over the coordinates."""

from benchmarks.metrics import _setup


def read(run):
    return _setup.group_phase_seconds(run, "gather")
