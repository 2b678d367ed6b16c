"""The plain reference of the Hessian-vector cells: beside ``reference.py``'s
value and gradient, the Hessian-vector product of the L2-regularised GLM
objective

    H(w) v = X^T (d2(X w, y) * (X v)) + lambda v

and a plain trust-region Newton method over it, in float64 on the host over
the ELL arrays the generator made.  It imports nothing of the program and
takes nothing the program made.

``precision="bf16"`` is the CONTROL of the product: the matrix values, the
direction and the per-row curvature rounded to bfloat16 before each product
(accumulation stays wide).  ``windows/fit_hv.py`` has to call it not correct.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.reference import GlmReference, round_bf16


def _logistic_d2(z: np.ndarray) -> np.ndarray:
    """sigmoid(z) (1 - sigmoid(z)), the same for either label."""
    t = np.tanh(0.5 * z)
    return 0.25 * (1.0 - t * t)


_CURVATURES = {"logistic": _logistic_d2}


class GlmHvReference(GlmReference):
    """``GlmReference`` with the per-row curvature and the Hessian-vector
    product, blocked and threaded as ``value_and_grad`` is."""

    def __init__(self, cols, vals, labels, n_features, loss="logistic", **kw):
        super().__init__(cols, vals, labels, n_features, loss=loss, **kw)
        if loss not in _CURVATURES:
            raise ValueError(f"the reference has no curvature for {loss!r}: "
                             f"{sorted(_CURVATURES)}")
        self.d2 = _CURVATURES[loss]
        self._made = {}

    def _block(self, lo, hi, bf16):
        # A CG asks for the same blocks hundreds of times: each is built once
        # and kept (float64 CSR: 12 bytes a stored element).
        key = (lo, hi, bf16)
        if key not in self._made:
            self._made[key] = super()._block(lo, hi, bf16)
        return self._made[key]

    def _blocks(self, fn):
        with ThreadPoolExecutor(self.threads) as pool:
            return list(pool.map(
                lambda lo: fn(lo, min(self.n, lo + self.block_rows)),
                range(0, self.n, self.block_rows)))

    def curvature(self, w, precision="f64"):
        """Per-row ``d2(x_i . w)`` (times the row's scale), float64."""
        bf16 = _is_bf16(precision)
        w = np.asarray(w, np.float64)
        wq = round_bf16(w).astype(np.float64) if bf16 else w

        def part(lo, hi):
            c = self.d2(self._block(lo, hi, bf16) @ wq)
            return c if self.row_scale is None else c * self.row_scale[lo:hi]

        return np.concatenate(self._blocks(part))

    def hvp_from(self, curvature, v, lam, precision="f64"):
        """``X^T (curvature * (X v)) + lam v`` for a curvature in hand (a
        CG reuses one over all its steps)."""
        bf16 = _is_bf16(precision)
        v = np.asarray(v, np.float64)
        vq = round_bf16(v).astype(np.float64) if bf16 else v
        if bf16:
            curvature = round_bf16(curvature).astype(np.float64)

        def part(lo, hi):
            X = self._block(lo, hi, bf16)
            u = curvature[lo:hi] * (X @ vq)
            if bf16:
                u = round_bf16(u).astype(np.float64)
            return u @ X

        return np.sum(self._blocks(part), axis=0) + lam * v

    def hvp(self, w, v, lam, precision="f64"):
        """``H(w) v`` as float64."""
        return self.hvp_from(self.curvature(w, precision), v, lam, precision)


def _is_bf16(precision):
    if precision not in ("f64", "bf16"):
        raise ValueError(f"precision {precision!r}: f64 or bf16")
    return precision == "bf16"


def tron(ref, lam, w0, *, max_iters, tolerance, max_cg_iters=50, cg_tol=0.1,
         eta0=1e-4, eta1=0.25, eta2=0.75, sigma1=0.25, sigma2=0.5,
         sigma3=4.0):
    """Trust-region Newton for ``f(w) = ref.value_and_grad(w, lam)`` in
    plain float64 NumPy: the method of Lin, Weng & Keerthi (JMLR 9, 2008) as
    LIBLINEAR's ``tron.cpp`` runs it, with the constants of the program's
    ``TRONConfig`` as defaults.  ``ref`` gives ``value_and_grad(w, lam)``,
    ``curvature(w)`` and ``hvp_from(curvature, v, lam)``.

    Returns a dict: ``w``, ``value``, ``grad``, ``iterations`` (outer,
    accepted or not), ``converged``, ``stopped_by`` (``gradient``,
    ``improvement``, ``cap``, ``radius`` or ``start``), ``values`` and
    ``grad_norms`` (one per outer iteration, the start first), ``accepted``
    (one bool per outer iteration), ``cg_iterations`` (one count per outer
    iteration: Hessian-vector products), ``boundary`` (one bool each).

    Where it follows the program (photon-ml's port) and not the paper, so
    that the two can be held against each other step by step:

    * the stop.  LIBLINEAR: ``|g| <= eps |g(0)|``, the gradient at ZERO,
      eps scaled by ``min(pos, neg) / l``.  Here: ``|g| <= tolerance *
      max(1, |g(w0)|)`` at the solve's own start (a warm start then has a
      smaller yardstick), or an accepted step whose relative decrease
      ``|f - f_new| / max(|f|, 1e-12)`` is ``<= tolerance * 1e-2``;
    * the radius.  The paper interpolates a step length ``alpha`` from the
      actual decrease and picks among four cases of ``min`` / ``max`` with
      it; here three cases with no interpolation: ``rho < eta1`` shrinks to
      ``max(sigma1 |s|, sigma2 delta)`` (halved again when the step is
      refused), ``rho > eta2`` grows to ``max(delta, sigma3 |s|)``, else
      unchanged.  LIBLINEAR also clips the first radius to the first
      step's length; not here;
    * acceptance asks ``rho > eta0`` AND a positive predicted decrease;
    * CG: at most ``max_cg_iters`` steps (LIBLINEAR: the dimension), stopped
      at ``|r| <= cg_tol |g|`` tested AFTER a step (so a CG always makes one
      step unless ``|g|`` itself passes), with a move to the boundary on
      ``p.Hp <= 0`` as well as on crossing it (the paper's Hessian is
      positive definite and has no such branch).  ``s.Hs`` comes from the
      residual, as in LIBLINEAR: ``r = -g - H s`` gives
      ``s.Hs = -s.r - s.g``;
    * the solve gives up when the radius falls to 1e-18 (LIBLINEAR: when
      actual and predicted decrease both vanish).
    """
    w = np.asarray(w0, np.float64).copy()
    f, g = ref.value_and_grad(w, lam)
    g_norm = float(np.linalg.norm(g))
    threshold = tolerance * max(1.0, g_norm)
    delta = g_norm
    out = {"values": [f], "grad_norms": [g_norm], "accepted": [],
           "cg_iterations": [], "boundary": []}
    converged = g_norm <= threshold
    stopped_by = "start" if converged else "cap"
    k = 0
    curvature = None if converged else ref.curvature(w)
    while not converged and k < max_iters:
        s, r, on_boundary, steps = _steihaug_cg(
            lambda p: ref.hvp_from(curvature, p, lam), g, delta,
            max_cg_iters, cg_tol * g_norm)
        w_try = w + s
        f_try, g_try = ref.value_and_grad(w_try, lam)
        gs = float(g @ s)
        sHs = -float(s @ r) - gs
        predicted = -(gs + 0.5 * sHs)
        actual = f - f_try
        rho = actual / (predicted if predicted > 0 else 1e-30)
        accept = rho > eta0 and predicted > 0

        s_norm = float(np.linalg.norm(s))
        if rho < eta1:
            delta = max(sigma1 * s_norm, sigma2 * delta) * (
                sigma2 if rho < eta0 else 1.0)
        elif rho > eta2:
            delta = max(delta, sigma3 * s_norm)
        delta = max(delta, 1e-20)

        k += 1
        improvement = np.inf
        if accept:
            improvement = abs(actual) / max(abs(f), 1e-12)
            w, f, g = w_try, f_try, g_try
            curvature = ref.curvature(w)
            g_norm = float(np.linalg.norm(g))
        out["values"].append(f)
        out["grad_norms"].append(g_norm)
        out["accepted"].append(bool(accept))
        out["cg_iterations"].append(steps)
        out["boundary"].append(bool(on_boundary))
        if g_norm <= threshold:
            converged, stopped_by = True, "gradient"
        elif improvement <= tolerance * 1e-2:
            converged, stopped_by = True, "improvement"
        elif delta <= 1e-18:
            stopped_by = "radius"
            break
    out.update(w=w, value=f, grad=g, iterations=k, converged=bool(converged),
               stopped_by=stopped_by)
    return out


def _steihaug_cg(hv, g, delta, max_steps, tol):
    """Steihaug's CG for ``min g.s + s.Hs / 2`` within ``|s| <= delta``:
    ``(s, r, on_boundary, steps)`` with ``r = -g - H s``."""
    s = np.zeros_like(g)
    r = -g
    p = r.copy()
    rr = float(r @ r)
    steps, on_boundary = 0, False
    if np.sqrt(rr) <= tol:
        return s, r, on_boundary, steps
    while steps < max_steps:
        Hp = hv(p)
        pHp = float(p @ Hp)
        alpha = rr / pHp if pHp > 0 else 0.0
        steps += 1
        if pHp <= 0 or np.linalg.norm(s + alpha * p) >= delta:
            # to the boundary along p: the root tau >= 0 of |s + tau p| = delta
            pp, sp_, ss = float(p @ p), float(s @ p), float(s @ s)
            disc = max(sp_ * sp_ + pp * (delta * delta - ss), 0.0)
            tau = (-sp_ + np.sqrt(disc)) / max(pp, 1e-30)
            return s + tau * p, r - tau * Hp, True, steps
        s = s + alpha * p
        r = r - alpha * Hp
        rr_new = float(r @ r)
        if np.sqrt(rr_new) <= tol:
            break
        p = r + (rr_new / max(rr, 1e-30)) * p
        rr = rr_new
    return s, r, on_boundary, steps
