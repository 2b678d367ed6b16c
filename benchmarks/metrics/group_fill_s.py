"""Host seconds of the per-bucket scatters that fill the blocks' arrays and
the flat passive rows: the ``game.group.fill`` children of every
``game.group`` of the set-up, summed over the coordinates."""

from benchmarks.metrics import _setup


def read(run):
    return _setup.group_phase_seconds(run, "fill")
