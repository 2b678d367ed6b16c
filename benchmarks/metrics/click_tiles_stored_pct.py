"""Tiles the warm band stores over the tiles of the whole grid the tiled
layout would have allocated (``⌈n/2048⌉ x ⌈d/2048⌉``): the ``layout.build``
span's ``tiles_stored`` over its ``grid_tiles``."""

from benchmarks.metrics import _click


def read(run):
    return _click.share(run, "tiles_stored", "grid_tiles")
