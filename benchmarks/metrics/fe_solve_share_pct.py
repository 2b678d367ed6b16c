"""The fixed effect's share of the device's busy time: device seconds of
the ``fixed_effect_train`` and ``fixed_effect_score`` programs over the
traced window's busy seconds."""

from benchmarks.metrics import re_solve_share_pct


def read(run):
    return re_solve_share_pct.read(run, mark="fixed_effect_")
