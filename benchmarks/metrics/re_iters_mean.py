"""Newton iterations an entity's solve makes, the mean over real entities
and over the window's random-effect updates: each bucket's
``iterations_sum`` (counted on the device, per lane) over its ``lanes``,
from the random-effect ``coordinate.train`` spans' ``buckets``."""

from benchmarks.metrics import _game


def read(run):
    buckets = [b for update in _game.random_updates(run) for b in update]
    lanes = sum(b["lanes"] for b in buckets)
    if not lanes:
        return None
    return sum(b["iterations_sum"] for b in buckets) / lanes
