"""The per-movie ladder's share of the device's busy time: device seconds of
``random_effect_train_per_movie`` and ``random_effect_score_per_movie``."""

from benchmarks.metrics import _multi


def read(run):
    return _multi.share_of_busy(run, _multi.program_seconds(run, "movie"))
