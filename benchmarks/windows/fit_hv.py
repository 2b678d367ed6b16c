"""Window kind ``fit_hv``: ``fit``'s regularisation sweep for a solver that
makes Hessian-vector products (trust-region Newton: ``optim/tron.py``).

As ``fit`` (which it imports and does not edit), and:

* each ``Solve`` also keeps what the solve counted (CG iterations = Hessian-
  vector products, rejected steps, boundary exits, value+gradient
  evaluations) and its trackers of value and gradient norm: the PATH the
  timed solve took, one entry per outer iteration;
* the window never closes before its ``min_grids``-th grid (traffic file);
* ``check`` holds every timed solve of the grid it draws to
  ``reference_hv.tron``, the same method in plain float64 NumPy started where
  the solve started (zero, then the program's own answer before it): the
  outer iterations and refused steps it made, its CG iterations, and its
  value at every outer iteration (``path_*``).  The CG's Hessian-vector
  products reach ``correct`` through these: a product one precision down,
  without its ridge or on a stale curvature still converges, by another
  path;
* once the window has closed (at the start of ``free``, before the program's
  state is dropped; the trace has stopped by then) it calls the program's OWN
  Hessian-vector product -- the ``GlmObjective.d2_weights`` + ``hvp`` pair
  the CG calls, jitted once in set-up, on the same resident ``GlmData`` -- at
  the ``w`` and ``lambda`` of every solve of the grid that ``check`` draws, on
  one direction ``v``.  ``v`` is fixed by the corpus: a unit vector with the
  signs of the planted model, which ``--seed`` mirrors with the columns, and
  magnitudes drawn from ``data_seed``, so ``H v`` mirrors too and every
  compared number reads the same on every seed;
* ``check`` = ``fit``'s numbers, the three ``path_*`` numbers, ``hv_gap``
  (that one product against ``reference_hv.py``'s in float64: a diagnostic
  of the product alone, beside the paths), ``unconverged`` and
  ``cg_over_cap`` (exact).

A program whose ``SolveResult`` does not carry the counts (a commit before
they were added) is refused at the top of ``setup``, in seconds.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import reference_hv
from benchmarks.datagen import glm_sparse
from benchmarks.windows import fit

COUNTS = ("fn_evals", "cg_iterations", "rejected_steps", "boundary_exits")

end_to_end = fit.end_to_end
attempted_failed = fit.attempted_failed
sampled_grid = fit.sampled_grid


class Solve(fit.Solve):
    """``fit.Solve`` with the solve's counts, its trackers, and (for the
    checked grid) the program's ``H v`` at its answer."""

    __slots__ = COUNTS + ("values", "grad_norms", "hv")

    def __init__(self, grid, lam, res, end):
        super().__init__(grid, lam, res, end)
        for name in COUNTS:
            setattr(self, name, getattr(res, name))
        self.values, self.grad_norms, self.hv = res.values, res.grad_norms, None

    def to_host(self):
        super().to_host()
        for name in COUNTS:
            setattr(self, name, int(getattr(self, name)))
        self.values = np.asarray(self.values, np.float64)
        self.grad_norms = np.asarray(self.grad_norms, np.float64)

    def with_hv(self, hv, path=None):
        """A copy with another ``H v`` and, where given, the path of another
        solve (what ``reference_hv.tron`` returns) in the place of its own."""
        other = self.with_answer()
        other.hv = hv
        if path is not None:
            other.iterations = path["iterations"]
            other.converged = path["converged"]
            other.fn_evals = path["iterations"] + 1
            other.cg_iterations = sum(path["cg_iterations"])
            other.rejected_steps = path["accepted"].count(False)
            other.boundary_exits = sum(path["boundary"])
            other.values = np.asarray(path["values"])
            other.grad_norms = np.asarray(path["grad_norms"])
        return other


def direction(w_true, data_seed):
    """The unit direction ``v`` of the Hv comparison: the signs of the
    generator's planted model (``w_true`` carries the seed's mirror),
    magnitudes from the corpus's own seed."""
    law = np.random.default_rng([int(data_seed), 21])
    v = np.sign(w_true) * np.abs(law.standard_normal(w_true.shape[0]))
    return v / np.linalg.norm(v)


def setup(run):
    from photon_ml_tpu.optim.lbfgs import SolveResult

    missing = [c for c in COUNTS if c not in SolveResult._fields]
    if missing:
        raise SystemExit(
            "benchmarks/windows/fit_hv.py: this program's SolveResult has no "
            f"{missing}: its trust-region Newton does not count what this "
            "window checks. No result.")
    import jax
    import jax.numpy as jnp

    fit.setup(run)
    st = run.state
    objective = st["problem"].objective
    # The pair tron_solve's CG runs on: the curvature once per iterate, the
    # product with it cached.
    st["hv"] = jax.jit(lambda data, w, v, lam: objective.hvp(
        w, v, data, l2_weight=lam, d2w=objective.d2_weights(w, data)))
    st["v"] = direction(st["shape"]["w_true"], run.cfg["data_seed"])
    with run.span("warm_hv"):
        v = jnp.asarray(st["v"], jnp.float32)
        jax.block_until_ready(st["hv"](st["data"], jnp.zeros_like(v), v,
                                       jnp.float32(1.0)))


def _one_grid(run, index, solves):
    st = run.state
    ends = []
    results = st["problem"].run_grid(
        st["data"], st["grid"],
        on_solved=lambda lam, w: ends.append(time.perf_counter()))
    for (lam, _model, res), end in zip(results, ends):
        solves.append(Solve(index, lam, res, end))


def window(run, seconds):
    """``fit.window`` with ``min_grids`` and this module's ``Solve``."""
    import jax

    min_grids = int(run.traffic["min_grids"])
    solves = []
    start = time.perf_counter()
    index = 0
    while time.perf_counter() - start < seconds or index < min_grids:
        with jax.profiler.TraceAnnotation("grid"):
            _one_grid(run, index, solves)
        index += 1
    end = solves[-1].end
    with run.span("read_answers"):
        for s in solves:
            s.to_host()
    run.info["solve_ends_s"] = [round(s.end - start, 4) for s in solves]
    return {"solves": solves, "start": start, "end": end, "grids": index}


def free(run):
    """The program's own Hv at the checked grid's answers, then ``fit.free``."""
    import jax
    import jax.numpy as jnp

    st = run.state
    with run.span("program_hv"):
        v = jnp.asarray(st["v"], jnp.float32)
        for s in sampled_grid(run, run.window):
            s.hv = np.asarray(st["hv"](
                st["data"], jnp.asarray(s.w, jnp.float32), v,
                jnp.float32(s.lam)), np.float64)
    st.pop("hv", None)
    fit.free(run)


def make_reference(run, **kw):
    host = run.state.get("host")
    if host is None:
        host = run.state["host"] = glm_sparse.generate(run.cfg, run.seed)
    return reference_hv.GlmHvReference(
        host["cols"], host["vals"], host["labels"], host["n_features"],
        loss=run.cfg["task"], **kw)


def stopped_by(s, cfg):
    """Which of the program's tests ended a solve, from its trackers
    (``optim/tron.py``: the gradient's norm against ``tolerance x max(1,
    |g0|)`` at the solve's own start, or an accepted step's relative decrease
    against ``tolerance x 1e-2``), and the two thresholds."""
    tol = float(cfg["tolerance"])
    k = s.iterations
    g0, gk = s.grad_norms[0], s.grad_norms[k]
    threshold = tol * max(1.0, g0)
    out = {"g0_norm": float(g0), "g_end_norm": float(gk),
           "grad_threshold": threshold}
    if k == 0:
        return {**out, "stopped_by": "start" if s.converged else "cap"}
    before, after = s.values[k - 1], s.values[k]
    decrease = abs(before - after) / max(abs(before), 1e-12)
    out["last_rel_decrease"] = float(decrease)
    if gk <= threshold:
        why = "gradient"
    elif s.converged:
        why = "improvement"
    else:
        why = "cap" if k >= int(cfg["max_iters"]) else "radius"
    return {**out, "stopped_by": why}


class HvFault:
    """The reference with its Hessian-vector product one precision down,
    without its ridge, or on the curvature of ``w = 0``; value and gradient
    sound.  ``reference_hv.tron`` runs on it as on the reference."""

    def __init__(self, ref, precision="f64", ridge=True, stale=False):
        self.ref, self.precision = ref, precision
        self.ridge, self.stale = ridge, stale
        self.value_and_grad = ref.value_and_grad

    def curvature(self, w):
        return self.ref.curvature(np.zeros_like(w) if self.stale else w,
                                  self.precision)

    def hvp_from(self, curvature, v, lam):
        return self.ref.hvp_from(curvature, v, lam if self.ridge else 0.0,
                                 self.precision)

    def hvp(self, w, v, lam):
        return self.hvp_from(self.curvature(w), v, lam)


def paths(ref, answers, cfg):
    """``reference_hv.tron`` for every solve of a grid, each from the point
    the timed solve started at: zero, then the program's answer before it."""
    start = np.zeros_like(answers[0].w)
    out = []
    for s in answers:
        out.append(reference_hv.tron(
            ref, s.lam, start, max_iters=int(cfg["max_iters"]),
            tolerance=float(cfg["tolerance"]),
            max_cg_iters=int(cfg["max_cg_iters"]),
            cg_tol=float(cfg["cg_tol"])))
        start = s.w
    return out


def path_gaps(s, want):
    """One timed solve against the reference's from the same start.

    path_iters_gap: outer iterations, or refused steps, apart (the larger).
    path_cg_gap:    CG iterations (Hessian-vector products) apart, as a
        share of the reference's.
    path_value_gap: the value after each outer iteration both made, apart,
        as a share of the reference's (the largest).
    path_grad_gap:  the gradient's norm after each such iteration, apart, as
        a share of the norm at the solve's start (the largest).  Written
        per solve and given no limit: a CG that ends on its cap or on the
        boundary ends wherever its residual, which does not fall steadily,
        happens to be, and the sound float32 solve at the weakest ridge
        reads further from the reference than any fault (PERF.md section 2).
    """
    both = slice(1, min(s.iterations, want["iterations"]) + 1)
    values, norms = (np.asarray(want[k], np.float64)
                     for k in ("values", "grad_norms"))
    cg = sum(want["cg_iterations"])
    return {
        "path_iters_gap": max(
            abs(s.iterations - want["iterations"]),
            abs(s.rejected_steps - want["accepted"].count(False))),
        "path_cg_gap": abs(s.cg_iterations - cg) / max(cg, 1),
        "path_value_gap": float(np.max(
            np.abs(s.values[both] - values[both]) / np.abs(values[both]),
            initial=0.0)),
        "path_grad_gap": float(np.max(
            np.abs(s.grad_norms[both] - norms[both]) / norms[0],
            initial=0.0)),
    }


def judge(ref, answers, cfg, v, want_paths):
    """``(correct, numbers, details)`` of one grid's answers: ``fit.judge``'s
    numbers at this configuration's limits, then each solve's path against
    the reference's (``want_paths``, from ``paths``), the Hessian-vector
    product at its answer, and the exact counts."""
    limits = dict(cfg["limits"])
    mine = {k: limits.pop(k) for k in list(limits)
            if k.startswith("path_") or k == "hv_gap"}
    _ok, numbers, (per_solve, scale, at_answers) = fit.judge(
        ref, answers, limits, cfg["max_iters"])
    for s, want, row in zip(answers, want_paths, per_solve):
        hv = ref.hvp(s.w, v, s.lam)
        row.update(
            path_gaps(s, want),
            hv_gap=float(np.linalg.norm(s.hv - hv) / np.linalg.norm(hv)),
            hv_norm=float(np.linalg.norm(hv)),
            reference={"iterations": want["iterations"],
                       "cg_iterations": sum(want["cg_iterations"]),
                       "stopped_by": want["stopped_by"]},
            **{c: getattr(s, c) for c in COUNTS})
    for name, limit in mine.items():
        numbers[name] = {"value": max(r[name] for r in per_solve),
                         "limit": limit}
    # Exact: every solve of the grid met one of its tests ...
    numbers["unconverged"] = {
        "value": sum(1 for s in answers if not s.converged), "limit": 0}
    # ... and no CG ran past its own cap.
    cg_cap = int(cfg["max_cg_iters"])
    numbers["cg_over_cap"] = {"value": max(
        max(s.cg_iterations - cg_cap * s.iterations, 0) for s in answers),
        "limit": 0}
    correct = all(np.isfinite(n["value"]) and n["value"] <= n["limit"]
                  for n in numbers.values())
    return correct, numbers, (per_solve, scale, at_answers)


def check(run, win):
    """``(correct, numbers)`` of the grid drawn from the seed."""
    answers = sampled_grid(run, win)
    ref = make_reference(run)
    cfg, v = run.cfg, run.state["v"]
    with run.span("reference_paths"):
        want_paths = paths(ref, answers, cfg)
    correct, numbers, (per_solve, scale, at_answers) = judge(
        ref, answers, cfg, v, want_paths)
    # The stop's honesty (the configuration's ``assumed``): which test ended
    # each solve, and how far its threshold lies above what float32 resolves
    # there (the gradient's own error; the value's).
    for s, row in zip(answers, per_solve):
        row.update(stopped_by(s, cfg))
        row["grad_margin"] = row["grad_threshold"] / max(
            row["grad_gap"] * scale["g_zero_norm"], 1e-300)
        row["value_margin"] = float(cfg["tolerance"]) * 1e-2 / max(
            row["value_gap"], 1e-300)
    run.info["check"] = {"grid": answers[0].grid, "per_solve": per_solve,
                         **scale}
    if run.control:
        run.info["control"] = {
            name: dict(zip(("correct", "numbers"),
                           judge(ref, wrong, cfg, v, want_paths)[:2]))
            for name, wrong in wrong_answers(run, ref, answers, at_answers, v)}
    return correct, numbers


def wrong_answers(run, ref, answers, at_answers, v):
    """``fit``'s control and faults with the run's own path and Hv kept, then
    the control and the faults of the Hessian-vector product with the run's
    own value and gradient kept: each in the place of the CG's product, so
    in the path of every solve (``reference_hv.tron`` on an ``HvFault`` from
    the solve's own start) and in the product at its answer.  ``judge`` has
    to call each not correct."""

    def planted(like, fault):
        return [a.with_hv(fault.hvp(s.w, v, s.lam), path)
                for a, s, path in zip(like, answers,
                                      paths(fault, answers, run.cfg))]

    for name, wrong in fit.wrong_answers(run, ref, answers, at_answers):
        if name == "bf16":  # the control is one: value, gradient, Hv, path
            wrong = planted(wrong, HvFault(ref, precision="bf16"))
        yield name, wrong
    # Fault: the ridge's term left out of the product.
    yield "hv_no_ridge", planted(answers, HvFault(ref, ridge=False))
    # Fault: a stale curvature, taken at w = 0 and not at the iterate.
    yield "hv_stale_curvature", planted(answers, HvFault(ref, stale=True))
