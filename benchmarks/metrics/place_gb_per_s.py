"""Bytes a second of the set-up's ``layout.place`` (the fixed effect's in a
GAME cell): the span's ``bytes`` over its duration, first host-to-device
copy until every leaf is ready."""

from benchmarks.metrics import _setup


def read(run):
    place = _setup.setup_place(run)
    if place is None or not place["dur"] or "bytes" not in place["attrs"]:
        return None
    return place["attrs"]["bytes"] / place["dur"] / 1e9
