"""Compilations inside the timed window; has to read 0."""


def read(run):
    lo, hi = run.marks["window_start"], run.marks["window_end"]
    return sum(1 for event, _secs, at in run.compile_events
               if lo < at <= hi and event.endswith("backend_compile_duration"))
