"""What the readers of the cell ``game_cd_fit_user_item`` share: a GAME fit
with several random effects, each told apart by its coordinate's name -- on
the program's layer spans (``coordinate`` on ``game.group``, ``game.place``,
``coordinate.train``, ``coordinate.score``) and in the names of its jitted
programs (``jit_random_effect_train_<coordinate>``, ``..._score_...``).

The window kind ``cd_fit_multi`` leaves ``run.state["shape"]["effects"]``:
per coordinate its ``role`` (``user``, ``movie``: the part of a metric's
name), width, entities and active / passive rows by the generator's counts.
A run without them (another window kind, a program without the spans) makes
every reader here return ``None``.
"""

from benchmarks import roofline, roofline_game, roofline_game_multi
from benchmarks.metrics import _game, _layer_spans


def coordinate_of(run, role):
    """The name of the coordinate whose role is ``role``, or ``None``."""
    effects = run.state.get("shape", {}).get("effects", {})
    return next((n for n, e in effects.items() if e["role"] == role), None)


def setup_seconds(run, name, role=None):
    """Seconds of the ``name`` spans under the run's ``game.build``; of the
    coordinate with ``role`` alone where one is given."""
    built = _layer_spans.between(
        run, "game.build", "process_start", "window_start")
    want = coordinate_of(run, role) if role else None
    if not built or (role and want is None):
        return None
    kids = [k for b in built for k in _layer_spans.children(b, name)
            if not role or k.get("attrs", {}).get("coordinate") == want]
    return sum(k["dur"] for k in kids) if kids else None


def updates(run, role):
    """The window's updates of the coordinate with ``role``: each one's
    ``buckets`` (``_game.random_updates`` for one coordinate)."""
    want = coordinate_of(run, role)
    if want is None:
        return []
    return [s["attrs"]["buckets"]
            for s in _game.window_spans(run, "coordinate.train")
            if s.get("attrs", {}).get("coordinate") == want
            and "buckets" in s["attrs"]]


def program_seconds(run, role, stem="random_effect_"):
    """Device seconds of the coordinate's programs whose name has ``stem``
    (``random_effect_`` for both, ``random_effect_score_`` for one)."""
    want = coordinate_of(run, role)
    modules = run.state.get("module_seconds")
    if want is None or not modules:
        return None
    spans = [d for name, evs in modules.items()
             if stem in name and ("_" + want) in name
             for _s, d in evs]
    return sum(spans) if spans else None


def share_of_busy(run, seconds):
    t = run.trace
    if t is None or t.busy_s <= 0 or seconds is None:
        return None
    return 100.0 * seconds / t.busy_s


def needed_seconds(run, role):
    """The least seconds the chip could take for the window's updates of
    one random effect: its active rows by their Newton iterations (a
    block's rows at its lanes' mean), its offsets in, every row's score
    out; shapes from the generator, iterations as the program counted."""
    ups = updates(run, role)
    if not ups:
        return None
    shape = run.state["shape"]
    eff = shape["effects"][coordinate_of(run, role)]
    peak = roofline.peaks(run.device_kind)
    return sum(roofline_game_multi.effect_update_seconds(
        shape["n_rows"], eff["rows_active"], eff["dim"],
        sum(b["rows_real"] * b["iterations_sum"] / b["lanes"] for b in up),
        peak) for up in ups)


def fixed_needed_seconds(run):
    fixed = [s for s in _game.window_spans(run, "coordinate.train")
             if s["attrs"].get("kind") == "fixed"
             and "iterations" in s["attrs"]]
    if not fixed:
        return None
    peak = roofline.peaks(run.device_kind)
    return sum(roofline_game.fixed_solve_seconds(
        run.state["shape"], s["attrs"]["iterations"], peak) for s in fixed)


def block_roofline(run, role):
    seconds = program_seconds(run, role)
    if seconds is None:  # no trace, or a program without these names
        return None
    needed = needed_seconds(run, role)
    return 100.0 * needed / seconds if needed else None
