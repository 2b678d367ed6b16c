"""Data passes per solve: tile-kernel launches in the trace / 2 (a pass is
a forward and a backward product) / solves in the traced window; line
search and CG included."""


def read(run):
    t = run.trace
    solves = run.window["solves"]
    if t is None or not t.kernel_durations_s or not solves:
        return None
    return len(t.kernel_durations_s) / 2.0 / len(solves)
