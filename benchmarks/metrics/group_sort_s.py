"""Host seconds of sorting the rows by their entity's key
(``_sort_by_entity``): the ``game.group.sort`` children of every
``game.group`` of the set-up, summed over the coordinates.  All six phases
of each coordinate go to the result line as ``group_phases``."""

from benchmarks.metrics import _setup


def read(run):
    return _setup.group_phase_seconds(run, "sort")
