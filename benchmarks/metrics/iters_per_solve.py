"""Sum of SolveResult.iterations over the window's solves, per solve."""


def read(run):
    solves = run.window["solves"]
    return sum(s.iterations for s in solves) / len(solves) if solves else None
