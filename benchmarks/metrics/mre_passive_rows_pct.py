"""Rows that some random effect scores and never trains on, as a share of
the data's rows: the ``rows_passive`` the program counted while grouping
(its ``game.group`` spans under ``game.build``)."""

from benchmarks.metrics import _layer_spans


def read(run):
    built = _layer_spans.between(
        run, "game.build", "process_start", "window_start")
    groups = [k for b in built for k in _layer_spans.children(b, "game.group")
              if "rows_passive" in k.get("attrs", {})]
    rows = run.state.get("shape", {}).get("n_rows")
    if not groups or not rows:
        return None
    return 100.0 * sum(g["attrs"]["rows_passive"] for g in groups) / rows
