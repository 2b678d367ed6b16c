"""The per-movie ladder's share of its roofline: its needed bytes at its own
width (9), active rows by their iterations and every row's score, over its
own programs' device seconds."""

from benchmarks.metrics import _multi


def read(run):
    return _multi.block_roofline(run, "movie")
