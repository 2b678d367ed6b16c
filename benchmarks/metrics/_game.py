"""What the GAME cell's readers share: the program's layer spans of one
run (``_layer_spans``), and the device seconds of each executed program,
which the window kind ``cd_fit`` reads off the trace's ``XLA Modules``
line (``run.state["module_seconds"]``: ``{name: [(start_s, dur_s)]}``, or
``None`` in a run without a trace).

The program names its jitted functions for this: ``fixed_effect_train``,
``fixed_effect_score``, ``random_effect_train``, ``random_effect_score``.
A program without them (a commit before they were named) gives no match,
and the readers return ``None``.
"""

from benchmarks.metrics import _layer_spans


def program_seconds(run, mark):
    """Device seconds of every execution of the programs whose name
    contains ``mark``; ``None`` where the trace has none."""
    modules = run.state.get("module_seconds")
    if not modules:
        return None
    spans = [d for name, evs in modules.items() if mark in name
             for _s, d in evs]
    return sum(spans) if spans else None


def all_program_intervals(run):
    modules = run.state.get("module_seconds")
    if not modules:
        return None
    return [(s, s + d) for evs in modules.values() for s, d in evs]


def window_spans(run, name):
    return _layer_spans.between(run, name, "window_start", "window_end")


def setup_seconds(run, name):
    """Seconds of the ``name`` spans under the run's ``game.build``."""
    built = _layer_spans.between(
        run, "game.build", "process_start", "window_start")
    if not built:
        return None
    kids = [k for b in built for k in _layer_spans.children(b, name)]
    return sum(k["dur"] for k in kids) if kids else None


def random_updates(run):
    """The window's random-effect updates: for each ``coordinate.train``
    span of kind ``random`` that carries them, the list of its buckets
    (shape, and what each bucket's solve counted on the device)."""
    return [s["attrs"]["buckets"]
            for s in window_spans(run, "coordinate.train")
            if s.get("attrs", {}).get("kind") == "random"
            and "buckets" in s["attrs"]]
