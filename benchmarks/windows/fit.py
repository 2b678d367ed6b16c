"""Window kind ``fit``: a trainer's regularisation sweep with the data
resident, closed loop, one client.

Set-up makes the data from the seed, hands host CSR arrays to the program's
own ``make_glm_data`` (timed: the span ``data_ready``), builds ONE
``GlmOptimizationProblem`` and drives it once through the grid untimed
(compile or cache load).  The window then calls the same object's
``run_grid`` again and again on the same ``GlmData`` -- each call walks the
whole lambda grid from ``w0 = 0`` as ``glm_driver`` does, from the largest
weight down, each solve warm-started from the one before -- and closes with
the first grid that ends after ``--seconds``.  Every solve ends in the
program's own blocking read of its coefficients.

``check`` decides ``correct`` from one grid of the window, drawn from the
seed: what each of its solves returned against the float64 reference in
``reference.py`` (see PERF.md, "How correct is decided here").
"""

from __future__ import annotations

import copy
import time

import numpy as np

from benchmarks import reference as reference_mod
from benchmarks.datagen import glm_sparse


class Solve:
    """One answer of the window, on the host."""

    __slots__ = ("grid", "lam", "w", "value", "grad", "iterations",
                 "converged", "end")

    def __init__(self, grid, lam, res, end):
        self.grid, self.lam, self.end = grid, float(lam), end
        self.w, self.value, self.grad = res.w, res.value, res.grad
        self.iterations, self.converged = res.iterations, res.converged

    def to_host(self):
        self.w = np.asarray(self.w, np.float64)
        self.grad = np.asarray(self.grad, np.float64)
        self.value = float(self.value)
        self.iterations = int(self.iterations)
        self.converged = bool(self.converged)

    def with_answer(self, w=None, value=None, grad=None):
        """A copy that says something else (the control and the faults)."""
        other = copy.copy(self)
        other.w = self.w if w is None else w
        other.value = self.value if value is None else value
        other.grad = self.grad if grad is None else grad
        return other


def _problem(cfg):
    from photon_ml_tpu.optim.problem import (
        GlmOptimizationConfig, GlmOptimizationProblem, OptimizerConfig,
        OptimizerType)
    from photon_ml_tpu.optim.regularization import RegularizationContext

    if cfg["regularization"] != "l2":
        raise ValueError("window 'fit' drives L2 grids only; got "
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
            regularization=RegularizationContext.l2(),
        ),
    )


def setup(run):
    """Everything before the window.  Fills ``run.state`` and the set-up
    spans in ``run.spans``."""
    import jax

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
        st["data"], st["grid"],
        on_solved=lambda lam, w: ends.append(time.perf_counter()))
    for (lam, _model, res), end in zip(results, ends):
        solves.append(Solve(index, lam, res, end))


def window(run, seconds):
    """The timed window; returns its solves and its two ends."""
    import jax

    solves = []
    start = time.perf_counter()
    index = 0
    while time.perf_counter() - start < seconds:
        with jax.profiler.TraceAnnotation("grid"):
            _one_grid(run, index, solves)
        index += 1
    end = solves[-1].end
    with run.span("read_answers"):
        for s in solves:
            s.to_host()
    # Each solve's end, in seconds of the window: where a far-off run lost
    # its time shows here.
    run.info["solve_ends_s"] = [round(s.end - start, 4) for s in solves]
    return {"solves": solves, "start": start, "end": end, "grids": index}


def end_to_end(run, win):
    """The cell's end-to-end metrics other than ``setup_s`` and the peak,
    which the harness takes itself."""
    return {"solve_s": (win["end"] - win["start"]) / len(win["solves"])}


def attempted_failed(win):
    bad = sum(1 for s in win["solves"]
              if not (np.isfinite(s.value) and np.isfinite(s.w).all()))
    return len(win["solves"]), bad


def free(run):
    """Drop the program's state before the reference runs."""
    import jax

    for key in ("data", "problem"):
        run.state.pop(key, None)
    jax.clear_caches()


def sampled_grid(run, win):
    pick = int(np.random.default_rng([run.seed % (1 << 63), 20]).integers(
        win["grids"]))
    return [s for s in win["solves"] if s.grid == pick]


def make_reference(run, **kw):
    host = run.state.get("host")
    if host is None:
        host = run.state["host"] = glm_sparse.generate(run.cfg, run.seed)
    return reference_mod.GlmReference(
        host["cols"], host["vals"], host["labels"], host["n_features"],
        loss=run.cfg["task"], **kw)


def compare(ref, answers):
    """The numbers of one grid's answers against the reference, each the
    largest over the grid's solves.

    value_gap:   the value a solve reported against f(w) at the w it
        returned, as a share of f(w).
    grad_gap:    the gradient it reported against grad f(w): the norm of the
        difference over the norm of the gradient at w = 0.
    inv_descent: f at the solve's starting point (w = 0, then the answer
        before it in the warm-started chain) over the descent the solve
        made from there, by the reference alone.  L-BFGS never accepts a
        step that raises f, so every solve descends; one that returns its
        state unchanged makes no descent and reads 1e30.  (The gradient's
        norm says nothing here: ten iterations into an ill-conditioned
        problem it is still swinging, above its starting norm on some
        seeds.)
    """
    start = np.zeros_like(answers[0].w)
    g_zero_norm = float(np.linalg.norm(
        ref.value_and_grad(start, answers[0].lam)[1]))
    per_solve, at_answers = [], []
    for s in answers:
        f_start = ref.value_and_grad(start, s.lam)[0]
        f, g = ref.value_and_grad(s.w, s.lam)
        at_answers.append((f, g))
        per_solve.append({
            "lam": s.lam, "iterations": s.iterations,
            "converged": s.converged,
            "value_gap": abs(s.value - f) / abs(f),
            "grad_gap": float(np.linalg.norm(s.grad - g)) / g_zero_norm,
            "inv_descent": f_start / max(f_start - f, 1e-30 * f_start),
            "grad_norm": float(np.linalg.norm(g)),
        })
        start = s.w
    out = {k: max(r[k] for r in per_solve)
           for k in ("value_gap", "grad_gap", "inv_descent")}
    return out, per_solve, {"g_zero_norm": g_zero_norm}, at_answers


def judge(ref, answers, limits, cap):
    """``(correct, numbers, details)`` of one grid's answers: each number
    beside its limit.  The window's own answers, the control's and each
    planted fault's all come through here."""
    got, per_solve, scale, at_answers = compare(ref, answers)
    numbers = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    # Exact: a solve never runs past its cap.
    over = max(s.iterations for s in answers) - int(cap)
    numbers["iters_over_cap"] = {"value": max(over, 0), "limit": 0}
    correct = all(np.isfinite(n["value"]) and n["value"] <= n["limit"]
                  for n in numbers.values())
    return correct, numbers, (per_solve, scale, at_answers)


def check(run, win):
    """``(correct, numbers)`` of the grid drawn from the seed."""
    answers = sampled_grid(run, win)
    ref = make_reference(run)
    limits, cap = run.cfg["limits"], run.cfg["max_iters"]
    correct, numbers, (per_solve, scale, at_answers) = judge(
        ref, answers, limits, cap)
    run.info["check"] = {"grid": answers[0].grid, "per_solve": per_solve,
                         **scale}
    if run.control:
        run.info["control"] = {
            name: dict(zip(("correct", "numbers"), judge(
                ref, wrong, limits, cap)[:2]))
            for name, wrong in wrong_answers(run, ref, answers, at_answers)}
    return correct, numbers


def wrong_answers(run, ref, answers, at_answers):
    """The control and the planted faults, as answers in the place of the
    run's own (``--control 1``; the benchmark's own runs do not call this).
    ``judge`` has to call each of them not correct."""
    # The control: the reference in the program's place, one precision down,
    # at the program's own coefficients.
    yield "bf16", [
        s.with_answer(None, *ref.value_and_grad(s.w, s.lam, precision="bf16"))
        for s in answers]
    # Fault: half of the batch left out, the rest counted double.
    half = np.zeros(ref.n)
    half[::2] = 2.0
    ref_half = make_reference(run, row_scale=half)
    yield "half_batch", [
        s.with_answer(None, *ref_half.value_and_grad(s.w, s.lam))
        for s in answers]
    # Fault: every solve returns its state unchanged, so the warm-started
    # chain stays at w = 0 with the value and gradient of that point.
    zero = np.zeros_like(answers[0].w)
    yield "state_unchanged", [
        s.with_answer(zero, *ref.value_and_grad(zero, s.lam)) for s in answers]
    # Fault: an answer altered where it is produced (every coefficient moved
    # by a thousandth of itself; value and gradient as reported).
    yield "answer_altered", [s.with_answer(s.w * 1.001) for s in answers]
