"""Seconds JAX spent tracing, lowering and compiling (or loading from the
persistent cache) before the window opened."""


def read(run):
    start = run.marks["window_start"]
    return sum(secs for event, secs, at in run.compile_events
               if at <= start and ("/compile/" in event
                                   or "cache_retrieval" in event))
