"""Share of the traced window in which no program ran on the device: the
gaps between one coordinate's program and the next (the host dispatching,
reading a history, building a model), as against ``device_idle_pct``, which
also counts the gaps between operations inside a program."""

from benchmarks import trace as trace_mod
from benchmarks.metrics import _game


def read(run):
    t = run.trace
    intervals = _game.all_program_intervals(run)
    if t is None or t.window_s <= 0 or not intervals:
        return None
    lo = min(s for s, _e in intervals)
    covered = sum(e - s for s, e in trace_mod.union(intervals))
    # The window on the trace's clock starts with its first program.
    window = max(t.window_s, max(e for _s, e in intervals) - lo)
    return 100.0 * max(0.0, 1.0 - covered / window)
