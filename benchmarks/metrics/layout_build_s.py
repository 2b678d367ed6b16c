"""Host seconds of the tiled layout's build inside the run's one
``make_glm_data``: the program's ``layout.build`` span (canonicalise, dense
stripes, column permutation, both orientations; no device work)."""

from benchmarks.metrics import _layer_spans


def read(run):
    return _layer_spans.setup_child_seconds(run, "layout.build")
