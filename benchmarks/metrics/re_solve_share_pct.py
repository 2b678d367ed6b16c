"""The random effect's share of the device's busy time: device seconds of
the ``random_effect_train`` and ``random_effect_score`` programs over the
traced window's busy seconds."""

from benchmarks.metrics import _game


def read(run, mark="random_effect_"):
    t = run.trace
    seconds = _game.program_seconds(run, mark)
    if t is None or t.busy_s <= 0 or seconds is None:
        return None
    return 100.0 * seconds / t.busy_s
