"""Seconds from the first host-to-device copy of the built layout until
every leaf of the ``GlmData`` is ready: the program's ``layout.place`` span
inside the run's one ``make_glm_data``."""

from benchmarks.metrics import _layer_spans


def read(run):
    return _layer_spans.setup_child_seconds(run, "layout.place")
