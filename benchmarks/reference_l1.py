"""The plain reference of the L1 cells: the L1-regularised GLM objective

    F(w) = sum_i loss(x_i . w, y_i) + l2/2 |w|^2 + lambda sum_j mask_j |w_j|

its minimum-norm subgradient (Andrew & Gao, "Scalable Training of
L1-Regularized Log-Linear Models", ICML 2007, eq. 4: the pseudo-gradient),
and the orthant-wise limited-memory quasi-Newton method over them, in float64
NumPy on the host over the ELL arrays the generator made.  The smooth part is
``reference.GlmReference``'s; it imports nothing of the program and takes
nothing the program made.

``owlqn`` is the method of the paper as the program (``optim/owlqn.py``,
after Breeze's ``OWLQN``, which linkedin/photon-ml runs) makes it, so that
the two can be held against each other iteration by iteration.  Where that
is not the paper:

* the first step.  From an empty history the direction is the negated
  pseudo-gradient and the first trial's step is ``min(1, 1/|pg|)`` (Breeze's
  scaling; the paper starts at 1 with ``H0 = I``); every later search starts
  at 1;
* the line search.  Backtracking by 0.5, at most 30 trials, Armijo on the
  PROJECTED step with the non-strict inequality: a trial is refused while
  ``F(trial) >= F(w) + c1 <pg, trial - w>`` (``c1`` 1e-4; the paper tests the
  unprojected direction, strictly), so a trial the projection clamps back
  onto ``w`` is refused and a shorter step tried;
* the projection holds EVERY coordinate to the chosen orthant, the
  unpenalised ones (the intercept) too, as Breeze's ``takeStep`` does: an
  intercept cannot cross zero within one step;
* the stall rule.  A search whose last trial did not lower ``F`` ends the
  solve at the point it started from: ``converged`` only if the
  pseudo-gradient test holds there, else ``stalled``;
* the stops.  ``|pg| <= tolerance * max(1, |pg(w0)|)`` with the 2-norm and
  the scale taken at the solve's OWN start (LIBLINEAR's ``-s 6`` tests a
  1-norm of the violation against the one at zero), or an accepted step whose
  relative decrease ``|F - F_new| / max(|F|, 1e-12)`` is ``<= tolerance *
  1e-2``.  The second test is taken only on a step whose direction came from
  a history of two kept pairs or more: the normalised steepest-descent step
  above, and the same direction rescaled by one pair's ``<s,y>/<y,y>``, are
  cut down by the search where the curvature along ``pg`` is large, and
  their decrease says how short the step was, not how near the answer is.
  ``rel_test_from_pairs=0`` is the rule before that repair (PR 37), kept to
  show what it did (a warm-started solve that returns after one iteration);
* a curvature pair is kept only when ``<s, y> > 1e-10 |s| |y|``; pairs use
  the smooth gradient; ``H0 = <s, y> / <y, y>`` of the newest kept pair.

``precision="bf16"`` on the smooth part is the CONTROL, as in
``reference.py``.  The three faults a window plants (``windows/fit_l1.py``)
are switches of ``owlqn``: ``one_sided=False`` (no one-sided derivative at
zero: the support never grows), ``project=False`` (trial points not held to
the orthant: no exact zeros), and a mask of ones (the intercept penalised).
"""

from __future__ import annotations

import numpy as np

from benchmarks.reference import GlmReference


class GlmL1Reference(GlmReference):
    """``GlmReference`` with each float64 block built once and kept: an
    orthant-wise path asks for the same blocks a hundred times (float64
    CSR: 12 bytes a stored element)."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self._made = {}

    def _block(self, lo, hi, bf16):
        key = (lo, hi, bf16)
        if key not in self._made:
            self._made[key] = super()._block(lo, hi, bf16)
        return self._made[key]


def penalty(w, lam, mask):
    return float(lam) * float(mask @ np.abs(w))


def pseudo_gradient(w, grad, lam, mask, one_sided=True):
    """The minimum-norm subgradient of ``f + lam |mask * w|_1`` at ``w`` from
    the smooth gradient there (Andrew & Gao eq. 4).  ``one_sided=False`` is
    the planted fault: zero wherever ``w`` is."""
    lam = float(lam) * mask
    right, left = grad + lam, grad - lam  # one-sided derivatives at w_j = 0
    at_zero = np.where(left > 0, left, np.where(right < 0, right, 0.0))
    if not one_sided:
        at_zero = np.zeros_like(at_zero)
    return np.where(w != 0, grad + lam * np.sign(w), at_zero)


class L1Objective:
    """``F``, its smooth part and its pseudo-gradient for one reference, one
    mask and one ridge (elastic net: ``l2`` > 0)."""

    def __init__(self, ref, mask, l2=0.0, precision="f64"):
        self.ref, self.mask = ref, np.asarray(mask, np.float64)
        self.l2, self.precision = float(l2), precision

    def smooth(self, w):
        return self.ref.value_and_grad(w, self.l2, precision=self.precision)

    def value_and_pgrad(self, w, lam):
        f, g = self.smooth(w)
        return (f + penalty(w, lam, self.mask),
                pseudo_gradient(w, g, lam, self.mask))


def _two_loop(q, pairs, gamma):
    """``H q`` over the kept pairs, oldest first in ``pairs``."""
    alphas = []
    for s, y, rho in reversed(pairs):
        a = rho * float(s @ q)
        q = q - a * y
        alphas.append(a)
    r = gamma * q
    for (s, y, rho), a in zip(pairs, reversed(alphas)):
        r = r + (a - rho * float(y @ r)) * s
    return r


def owlqn(objective, lam, w0, *, max_iters, tolerance, history=10,
          max_line_search_evals=30, armijo_c1=1e-4, backtrack=0.5,
          one_sided=True, project=True, rel_test_from_pairs=2):
    """OWL-QN for ``objective.smooth(w) + lam |mask * w|_1`` from ``w0``.

    Returns a dict: ``w``, ``value``, ``pgrad``, ``iterations``,
    ``fn_evals`` (the starting evaluation and every trial), ``converged``,
    ``stalled``, ``stopped_by`` (``start``, ``pgrad``, ``improvement``,
    ``stall`` or ``cap``), ``values`` and ``pg_norms`` (the start first, one
    per iteration), ``trials`` (per iteration), ``clamps`` (coordinates the
    projection zeroed, over accepted steps), ``nonzeros`` (of the penalised
    coefficients)."""
    mask = objective.mask
    lam = float(lam)

    def evaluate(w):
        f, g = objective.smooth(w)
        return f + penalty(w, lam, mask), g

    def pgrad(w, g):
        return pseudo_gradient(w, g, lam, mask, one_sided)

    w = np.asarray(w0, np.float64).copy()
    value, grad = evaluate(w)
    pg = pgrad(w, grad)
    pg_norm = float(np.linalg.norm(pg))
    threshold = tolerance * max(1.0, pg_norm)
    out = {"values": [value], "pg_norms": [pg_norm], "trials": []}
    converged = pg_norm <= threshold
    stalled = False
    stopped_by = "start" if converged else "cap"
    pairs, gamma, kept = [], 1.0, 0
    k, fn_evals, clamps = 0, 1, 0
    while not converged and k < max_iters:
        direction = -_two_loop(pg, pairs, gamma)
        direction = np.where(direction * -pg > 0, direction, 0.0)
        if not direction.any():
            direction = -pg
        xi = np.where(w != 0, np.sign(w), np.sign(-pg))
        kept_before = kept
        t = min(1.0, 1.0 / pg_norm) if not pairs else 1.0

        trials = 0
        while True:
            raw = w + t * direction
            w_try = np.where(raw * xi >= 0, raw, 0.0) if project else raw
            f_try, g_try = evaluate(w_try)
            trials += 1
            refused = f_try >= value + armijo_c1 * float(pg @ (w_try - w))
            if not refused or trials >= max_line_search_evals:
                break
            t *= backtrack
        fn_evals += trials
        out["trials"].append(trials)

        s_vec, y_vec = w_try - w, g_try - grad
        sy = float(s_vec @ y_vec)
        if sy > 1e-10 * np.linalg.norm(s_vec) * np.linalg.norm(y_vec):
            pairs = (pairs + [(s_vec, y_vec, 1.0 / sy)])[-history:]
            gamma = sy / float(y_vec @ y_vec)
            kept += 1

        k += 1
        if f_try >= value:  # no decrease: the solve ends where it stood
            converged = pg_norm <= threshold
            stalled = not converged
            stopped_by = "pgrad" if converged else "stall"
            out["values"].append(value)
            out["pg_norms"].append(pg_norm)
            break
        improvement = abs(value - f_try) / max(abs(value), 1e-12)
        if project:
            clamps += int(np.count_nonzero(raw * xi < 0))
        w, value, grad = w_try, f_try, g_try
        pg = pgrad(w, grad)
        pg_norm = float(np.linalg.norm(pg))
        out["values"].append(value)
        out["pg_norms"].append(pg_norm)
        if pg_norm <= threshold:
            converged, stopped_by = True, "pgrad"
        elif (improvement <= tolerance * 1e-2
              and kept_before >= rel_test_from_pairs):
            converged, stopped_by = True, "improvement"
    out.update(
        w=w, value=value, pgrad=pg, iterations=k, fn_evals=fn_evals,
        converged=bool(converged), stalled=bool(stalled),
        stopped_by=stopped_by, clamps=clamps,
        nonzeros=int(np.count_nonzero((w != 0) & (mask != 0))))
    return out
