"""The per-movie scoring program's share of the device's busy time
(``random_effect_score_per_movie``): the active blocks' products and scatter
and the 5.5 M passive rows' gather, product and scatter."""

from benchmarks.metrics import _multi


def read(run):
    return _multi.share_of_busy(run, _multi.program_seconds(
        run, "movie", "random_effect_score_"))
