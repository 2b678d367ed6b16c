"""The batched per-entity solve's share of its roofline: the least time
the chip could take for the window's random-effect updates (per-user value,
gradient and Hessian over real rows by their iterations, the offsets in:
``roofline_game.py``; bound by bytes) over the device seconds of the
``random_effect_train`` and ``random_effect_score`` programs in the trace."""

from benchmarks.metrics import _game, cd_mfu


def read(run):
    seconds = _game.program_seconds(run, "random_effect_")
    if seconds is None:  # no trace, or a program without these names
        return None
    needed = cd_mfu.needed_seconds(run)
    return 100.0 * needed[1] / seconds if needed else None
