"""Host seconds of handing both ladders' blocks and the passive rows to the
device, until every one is resident: the ``game.place`` spans under the
run's ``game.build``."""

from benchmarks.metrics import _multi


def read(run):
    return _multi.setup_seconds(run, "game.place")
