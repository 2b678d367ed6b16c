"""Entries placed in slots over the slots allocated, both orientations of
both bands (the warm band's tiles, the cold band's blocks): the
``layout.build`` span's ``slot_entries`` over its ``slots``."""

from benchmarks.metrics import _click


def read(run):
    return _click.share(run, "slot_entries", "slots")
