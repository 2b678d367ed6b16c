"""``layout_build_s`` on the GAME cell: host seconds of the tiled layout's
build for the fixed effect's shard, the ``layout.build`` span inside the
run's one ``data.make_glm_data`` (itself inside ``game.build``)."""

from benchmarks.metrics.layout_build_s import read  # noqa: F401
