"""The per-user ladder's share of its roofline: its needed bytes at its own
width (21) over its own programs' device seconds."""

from benchmarks.metrics import _multi


def read(run):
    return _multi.block_roofline(run, "user")
