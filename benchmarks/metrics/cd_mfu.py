"""The whole window's share of the chip's peak: the least time the chip
could take for the updates the window made (``roofline_game.py``: the
fixed effect's products by its iterations, the per-user value, gradient
and Hessian over real rows by their iterations, the offsets in and the
scores out) over the traced window (``trace.Reduced.window_s``, the time
``device_idle_pct`` is a share of: the harness's ``grid`` annotations where
the trace has them, else from the first to the last device operation; the
harness's own wall of the window is ``solve_s`` times the updates).  Bound
by bytes throughout."""

from benchmarks import roofline, roofline_game
from benchmarks.metrics import _game


def needed_seconds(run):
    """(fixed effect's, random effect's) least seconds of the window."""
    shape, peak = run.state["shape"], roofline.peaks(run.device_kind)
    fixed = [s for s in _game.window_spans(run, "coordinate.train")
             if s["attrs"].get("kind") == "fixed"
             and "iterations" in s["attrs"]]
    updates = _game.random_updates(run)
    if not fixed or not updates:
        return None
    fixed_s = sum(roofline_game.fixed_solve_seconds(
        shape, s["attrs"]["iterations"], peak) for s in fixed)
    # rows x iterations, a block's rows at its lanes' mean iterations
    random_s = sum(roofline_game.random_update_seconds(
        shape, sum(b["rows_real"] * b["iterations_sum"] / b["lanes"]
                   for b in update), peak) for update in updates)
    return fixed_s, random_s


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:  # a CPU rehearsal has no device plane
        return None
    needed = needed_seconds(run)
    return 100.0 * sum(needed) / t.window_s if needed else None
