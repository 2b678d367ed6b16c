"""The whole window's share of the chip's peak, three coordinates: the least
time the chip could take for the updates the window made
(``roofline_game_multi.py``: the fixed effect's products by its iterations;
each random effect's value, gradient and Hessian over its ACTIVE rows by
their iterations, its offsets in, and EVERY row's score out, the passive
rows' too) over the traced window (``trace.Reduced.window_s``).  Bound by
bytes throughout."""

from benchmarks.metrics import _multi


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:  # a CPU rehearsal has no device plane
        return None
    parts = [_multi.fixed_needed_seconds(run)] + [
        _multi.needed_seconds(run, e["role"])
        for e in run.state.get("shape", {}).get("effects", {}).values()]
    if len(parts) < 2 or any(p is None for p in parts):
        return None
    return 100.0 * sum(parts) / t.window_s
