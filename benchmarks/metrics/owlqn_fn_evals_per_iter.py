"""Value+gradient evaluations per iteration, counted by the solver itself:
the sum of (``fn_evals`` - 1) over the sum of ``iterations`` of the window's
``solver`` spans (the one taken off is each solve's starting evaluation).
Every trial of the orthant-wise line search is one full evaluation, a
refused trial's too; ``owlqn_passes_per_solve`` infers the same from kernel
launches in the device trace."""

from benchmarks.metrics import _owlqn_spans


def read(run):
    return _owlqn_spans.per_iteration(run, "fn_evals", less=1)
