"""The set-up's wide layout build, for the readers beside this file: the
attributes of the one ``layout.build`` under the run's one
``data.make_glm_data`` whose ``layout`` is ``wide`` (what
``ops/sparse_pallas.build_wide_host`` counts), or ``None`` on a program
that builds no wide layout (a commit before PR 39)."""

from benchmarks.metrics import _setup


def wide_build(run):
    built = _setup._only_child(run, "layout.build")[1]
    attrs = (built or {}).get("attrs") or {}
    return attrs if attrs.get("layout") == "wide" else None


def share(run, part, whole):
    """100 x one of the build's counts over another's, or ``None``."""
    attrs = wide_build(run)
    if attrs is None or not attrs.get(whole):
        return None
    return 100.0 * attrs[part] / attrs[whole]


def cold_seconds(run):
    """Device seconds of the cold band's kernel in the traced window
    (``_cold_apply_fwd.N`` / ``_cold_apply_bwd.N``), or ``None``."""
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    return sum(s for name, s in t.device_ops if name.startswith("_cold_apply"))
