"""The per-user ladder's share of the device's busy time: device seconds of
``random_effect_train_per_user`` and ``random_effect_score_per_user``."""

from benchmarks.metrics import _multi


def read(run):
    return _multi.share_of_busy(run, _multi.program_seconds(run, "user"))
