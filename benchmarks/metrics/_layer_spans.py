"""The program's own layer spans (``photon_ml_tpu.telemetry.layer_span``),
for the readers beside this file.

The program keeps them in memory, on ``time.perf_counter()``: the clock of
``run.marks``.  A record is ``{"name", "ts", "dur", "id", "parent",
"attrs"}``.  Spans are selected by the harness's marks and by their
parents, never by position.  A program without layer spans (a commit before
they were added) gives empty lists, and the readers then return ``None``.
"""


def records():
    try:
        from photon_ml_tpu.telemetry import layer_spans
    except ImportError:
        return []
    return layer_spans()


def between(run, name, lo_mark, hi_mark):
    """The spans called ``name`` that lie wholly between two marks."""
    lo, hi = run.marks[lo_mark], run.marks[hi_mark]
    return [r for r in records()
            if r["name"] == name and lo <= r["ts"]
            and r["ts"] + r["dur"] <= hi]


def children(parent, name):
    return [r for r in records()
            if r["parent"] == parent["id"] and r["name"] == name]


def setup_child_seconds(run, name):
    """Seconds of the ``name`` children of the run's one
    ``data.make_glm_data`` span (set-up: before the window opened)."""
    made = between(run, "data.make_glm_data", "process_start", "window_start")
    if len(made) != 1:
        return None
    kids = children(made[0], name)
    return sum(k["dur"] for k in kids) if kids else None
