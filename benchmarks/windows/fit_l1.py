"""Window kind ``fit_l1``: ``fit``'s regularisation sweep under an L1 penalty,
solved by the orthant-wise method (``optim/owlqn.py``).

As ``fit`` (which it imports and does not edit), and:

* ``fit``'s problem refuses every penalty but L2 and its grid call passes no
  mask, so this module builds its own: ``RegularizationContext.l1()`` and
  ``run_grid(..., l1_mask=)`` with the intercept (the last column) exempt, as
  ``glm_driver --reg-type l1`` does.  Set-up is ``fit``'s otherwise: the same
  spans under the same names, the same keys of ``run.state``;
* each ``Solve`` also keeps what the solve counted (value+gradient
  evaluations, coordinates the projection clamped, non-zeros of the answer,
  whether it stalled) and its trackers of value and pseudo-gradient norm: the
  PATH the timed solve took, one entry per iteration;
* the window never closes before its ``min_grids``-th grid (traffic file);
* ``check`` holds every timed solve of the grid it draws to
  ``reference_l1.owlqn``, the same method in plain float64 NumPy started where
  the solve started (zero, then the program's own answer before it): its
  iterations, its evaluations, its value at every iteration (``path_*``) and
  the non-zeros of its answer (``nnz_gap``), beside the value and the
  pseudo-gradient the solve reported against the reference's at its answer and
  the descent it made.  ``unconverged`` (exact) counts the solves that ended
  stalled or on the cap, ``one_step_endings`` those that ended after one
  iteration with their pseudo-gradient test unmet, ``nnz_miscounted`` the
  solves whose own count of non-zeros (made on the device) is not the count
  of ``w != 0`` in the coefficients the host read: a zero that is not exact.

A program whose ``SolveResult`` does not carry the counts (a commit before
they were added) is refused at the top of ``setup``, in seconds.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import reference_l1
from benchmarks.datagen import glm_sparse
from benchmarks.windows import fit

COUNTS = ("fn_evals", "stalled", "orthant_clamps", "nonzeros")

end_to_end = fit.end_to_end
attempted_failed = fit.attempted_failed
sampled_grid = fit.sampled_grid
free = fit.free


class Solve(fit.Solve):
    """``fit.Solve`` with the solve's counts and its trackers; ``grad`` is
    the pseudo-gradient ``owlqn_solve`` returns."""

    __slots__ = COUNTS + ("values", "pg_norms")

    def __init__(self, grid, lam, res, end):
        super().__init__(grid, lam, res, end)
        for name in COUNTS:
            setattr(self, name, getattr(res, name))
        self.values, self.pg_norms = res.values, res.grad_norms

    def to_host(self):
        super().to_host()
        for name in COUNTS:
            setattr(self, name, int(getattr(self, name)))
        self.stalled = bool(self.stalled)
        self.values = np.asarray(self.values, np.float64)
        self.pg_norms = np.asarray(self.pg_norms, np.float64)

    def with_path(self, path):
        """A copy that says what another solve did (what
        ``reference_l1.owlqn`` returns) in the place of its own."""
        other = self.with_answer(path["w"], path["value"], path["pgrad"])
        other.iterations = path["iterations"]
        other.converged, other.stalled = path["converged"], path["stalled"]
        other.fn_evals, other.nonzeros = path["fn_evals"], path["nonzeros"]
        other.orthant_clamps = path["clamps"]
        other.values = np.asarray(path["values"])
        other.pg_norms = np.asarray(path["pg_norms"])
        return other


def _problem(cfg):
    from photon_ml_tpu.optim.problem import (
        GlmOptimizationConfig, GlmOptimizationProblem, OptimizerConfig,
        OptimizerType)
    from photon_ml_tpu.optim.regularization import RegularizationContext

    if cfg["regularization"] != "l1":
        raise ValueError("window 'fit_l1' drives L1 grids only; got "
                         f"{cfg['regularization']!r}")
    return GlmOptimizationProblem(
        cfg["task"],
        GlmOptimizationConfig(
            optimizer=OptimizerConfig(
                optimizer=OptimizerType(cfg["optimizer"]),
                max_iters=int(cfg["max_iters"]),
                tolerance=float(cfg["tolerance"]),
                history=int(cfg["history"]),
            ),
            regularization=RegularizationContext.l1(),
        ),
    )


def host_mask(n_features):
    """The penalty's mask on the host: every term, not the intercept (the
    generator's last column)."""
    mask = np.ones(int(n_features) + 1)
    mask[-1] = 0.0
    return mask


def setup(run):
    """``fit.setup`` with this module's problem, mask and grid call."""
    from photon_ml_tpu.optim.lbfgs import SolveResult

    missing = [c for c in COUNTS if c not in SolveResult._fields]
    if missing:
        raise SystemExit(
            "benchmarks/windows/fit_l1.py: this program's SolveResult has no "
            f"{missing}: its orthant-wise solver does not count what this "
            "window checks. No result.")
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.dataset import make_glm_data

    cfg = run.cfg
    with run.span("datagen"):
        host = glm_sparse.generate(cfg, run.seed)
        csr = glm_sparse.as_csr(host)
        labels = host.pop("labels")
        # The reference makes its own copy from the seed once the window
        # has closed; the layout build needs the host's memory now.
        del host["cols"], host["vals"]
    layout = True if run.dry else cfg["layout"]
    with run.span("data_ready"):
        data = make_glm_data(csr, labels, use_pallas=layout)
        jax.block_until_ready(jax.tree.leaves(data))
    del csr, labels
    features = data.features
    run.state.update(
        shape=host, data=data, problem=_problem(cfg),
        grid=[float(x) for x in cfg["reg_weights"]],
        # glm_driver's mask: ones, the intercept's column zero
        l1_mask=jnp.asarray(host_mask(host["n_features"]), jnp.float32),
        feature_bytes=sum(x.nbytes for x in jax.tree.leaves(features)),
    )
    run.info["layout"] = {
        "type": type(features).__name__,
        **{k: getattr(features, k) for k in (
            "a_f", "a_b", "depth_f", "depth_b", "has_dense_cols",
            "has_col_perm", "unit_vals") if hasattr(features, k)},
    }
    if not run.dry and cfg.get("expect_layout") not in (
            None, type(features).__name__):
        raise RuntimeError(
            f"the cell names the {cfg['expect_layout']} path but "
            f"make_glm_data built a {type(features).__name__}")
    with run.span("warm_pass"):
        _one_grid(run, 0, [])


def _one_grid(run, index, solves):
    st = run.state
    ends = []
    results = st["problem"].run_grid(
        st["data"], st["grid"], l1_mask=st["l1_mask"],
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


def make_objective(run, mask=None, **kw):
    """The float64 objective over the run's corpus: every term penalised,
    the intercept not (or ``mask``)."""
    host = run.state.get("host")
    if host is None:
        host = run.state["host"] = glm_sparse.generate(run.cfg, run.seed)
    ref = run.state.get("reference")
    if ref is None:
        ref = run.state["reference"] = reference_l1.GlmL1Reference(
            host["cols"], host["vals"], host["labels"], host["n_features"],
            loss=run.cfg["task"])
    if mask is None:
        mask = host_mask(host["n_features"])
    return reference_l1.L1Objective(ref, mask, **kw)


def starts_of(answers):
    """Where each solve of a grid started: zero, then the answer before it."""
    return [np.zeros_like(answers[0].w)] + [s.w for s in answers[:-1]]


def paths(objective, answers, starts, cfg, **rules):
    """``reference_l1.owlqn`` for every solve of a grid, each from the point
    the timed solve started at."""
    return [reference_l1.owlqn(
        objective, s.lam, start, max_iters=int(cfg["max_iters"]),
        tolerance=float(cfg["tolerance"]), history=int(cfg["history"]),
        **rules) for s, start in zip(answers, starts)]


def stopped_by(s, cfg):
    """Which of the program's tests ended a solve, from its trackers
    (``optim/owlqn.py``: the pseudo-gradient's norm against ``tolerance x
    max(1, |pg0|)`` at the solve's own start, or an accepted step's relative
    decrease against ``tolerance x 1e-2``), and the thresholds."""
    tol = float(cfg["tolerance"])
    k = s.iterations
    pg0, pgk = s.pg_norms[0], s.pg_norms[k]
    threshold = tol * max(1.0, pg0)
    out = {"pg0_norm": float(pg0), "pg_end_norm": float(pgk),
           "pg_threshold": threshold}
    if k == 0:
        return {**out, "stopped_by": "start" if s.converged else "cap"}
    before, after = s.values[k - 1], s.values[k]
    out["last_rel_decrease"] = float(
        abs(before - after) / max(abs(before), 1e-12))
    if pgk <= threshold:
        why = "pgrad"
    elif s.converged:
        why = "improvement"
    else:
        why = "stall" if s.stalled else "cap"
    return {**out, "stopped_by": why}


def path_gaps(s, want):
    """One timed solve against the reference's from the same start.

    path_iters_gap: iterations apart, as a share of the reference's.
    path_evals_gap: value+gradient evaluations apart, as a share of the
        reference's.
    path_value_gap: the value after each iteration both made, apart, as a
        share of the reference's (the largest).

    float32 and float64 make the same first iterations and then part: the
    relative-decrease stop fires when one step happens to fall under
    ``tolerance x 1e-2`` of the value, so the count of iterations is
    chaotic at a loose tolerance while the answers agree (PERF.md
    section 2).
    """
    both = slice(1, min(s.iterations, want["iterations"]) + 1)
    values = np.asarray(want["values"], np.float64)
    return {
        "path_iters_gap": abs(s.iterations - want["iterations"]) / max(
            want["iterations"], 1),
        "path_evals_gap": abs(s.fn_evals - want["fn_evals"]) / max(
            want["fn_evals"], 1),
        "path_value_gap": float(np.max(
            np.abs(s.values[both] - values[both]) / np.abs(values[both]),
            initial=0.0)),
    }


def judge(objective, answers, starts, cfg, want_paths):
    """``(correct, numbers, per_solve)`` of one grid's answers, each number
    the grid's largest beside its limit.  The window's own answers, the
    control's and each planted fault's all come through here."""
    limits = cfg["limits"]
    mask = objective.mask
    g_zero_norm = float(np.linalg.norm(objective.smooth(starts[0])[1]))
    per_solve = []
    for s, want in zip(answers, want_paths):
        # the reference's path set out from the solve's own start
        f_start, pg_start_norm = want["values"][0], want["pg_norms"][0]
        f, pg = objective.value_and_pgrad(s.w, s.lam)
        nnz = int(np.count_nonzero((s.w != 0) & (mask != 0)))
        row = {
            "lam": s.lam, "iterations": s.iterations,
            "converged": s.converged,
            # the value a solve reported against F at the w it returned
            "value_gap": abs(s.value - f) / abs(f),
            # the pseudo-gradient it reported against the reference's at w
            "pgrad_gap": float(np.linalg.norm(s.grad - pg)) / g_zero_norm,
            # F at the start over the descent made from there, by the
            # reference alone: a solve that returns its start reads 1e30
            "inv_descent": f_start / max(f_start - f, 1e-30 * f_start),
            # written, not judged: every solve ends by its relative decrease,
            # so the subgradient stands wherever that leaves it
            "subgrad_ratio": float(np.linalg.norm(pg)) / max(
                pg_start_norm, 1e-300),
            **path_gaps(s, want),
            "nnz_gap": abs(nnz - want["nonzeros"]) / max(want["nonzeros"], 1),
            "nonzeros_host": nnz,
            "reference": {k: want[k] for k in (
                "iterations", "fn_evals", "stopped_by", "nonzeros",
                "clamps")},
            **{c: getattr(s, c) for c in COUNTS},
        }
        row.update(stopped_by(s, cfg))
        per_solve.append(row)
    numbers = {k: {"value": max(r[k] for r in per_solve), "limit": limits[k]}
               for k in limits}
    # Exact: every solve of the grid met one of its tests ...
    numbers["unconverged"] = {
        "value": sum(1 for s in answers if not s.converged or s.stalled),
        "limit": 0}
    # ... none after one iteration with its pseudo-gradient test unmet ...
    numbers["one_step_endings"] = {
        "value": sum(1 for r in per_solve if r["iterations"] == 1
                     and r["stopped_by"] != "pgrad"), "limit": 0}
    # ... and the zeros the device counted are zeros on the host.
    numbers["nnz_miscounted"] = {
        "value": sum(1 for r in per_solve
                     if r["nonzeros"] != r["nonzeros_host"]), "limit": 0}
    correct = all(np.isfinite(n["value"]) and n["value"] <= n["limit"]
                  for n in numbers.values())
    return correct, numbers, (per_solve, {"g_zero_norm": g_zero_norm})


def check(run, win):
    """``(correct, numbers)`` of the grid drawn from the seed."""
    answers = sampled_grid(run, win)
    objective = make_objective(run)
    cfg, starts = run.cfg, starts_of(answers)
    with run.span("reference_paths"):
        want_paths = paths(objective, answers, starts, cfg)
    correct, numbers, (per_solve, scale) = judge(
        objective, answers, starts, cfg, want_paths)
    # The stop's honesty (the configuration's ``assumed``): how far the
    # threshold of the test that ended each solve lies above what float32
    # resolves there: the pseudo-gradient's own error; for the value both
    # the spacing of float32 at the value (what a decrease can fall under)
    # and the value's own error against float64 (a bias that consecutive
    # iterates share, so an upper reading).
    for s, row in zip(answers, per_solve):
        decrease = float(cfg["tolerance"]) * 1e-2
        row["pgrad_margin"] = row["pg_threshold"] / max(
            row["pgrad_gap"] * scale["g_zero_norm"], 1e-300)
        row["value_margin"] = decrease / max(row["value_gap"], 1e-300)
        row["value_ulp_margin"] = decrease * abs(s.value) / float(
            np.spacing(np.float32(abs(s.value))))
    run.info["check"] = {"grid": answers[0].grid, "per_solve": per_solve,
                         **scale}
    if run.control:
        run.info["control"] = {
            name: dict(zip(("correct", "numbers"), judge(
                objective, wrong, starts, cfg, want_paths)[:2]))
            for name, wrong in wrong_answers(run, objective, answers, starts)}
    return correct, numbers


def wrong_answers(run, objective, answers, starts):
    """The control and the planted faults, as answers in the place of the
    run's own (``--control 1``): each is ``reference_l1.owlqn`` with one
    thing wrong, run for every solve of the grid from the point the timed
    solve started at, and its path, answer, value and pseudo-gradient put in
    the solve's place.  ``judge`` has to call each not correct."""
    cfg = run.cfg

    def planted(wrong_objective, **rules):
        return [s.with_path(p) for s, p in zip(answers, paths(
            wrong_objective, answers, starts, cfg, **rules))]

    # The control: the smooth part one precision down (matrix values,
    # coefficients and per-row derivative rounded to bfloat16).
    yield "bf16", planted(make_objective(run, precision="bf16"))
    # Fault: a pseudo-gradient with no one-sided rule at zero, so a
    # coefficient at zero stays there and the support never grows.
    yield "no_one_sided_rule", planted(objective, one_sided=False)
    # Fault: trial points not projected onto the orthant: no exact zeros.
    yield "not_projected", planted(objective, project=False)
    # Fault: the intercept penalised with the terms.
    yield "intercept_penalised", planted(
        make_objective(run, mask=np.ones_like(objective.mask)))
