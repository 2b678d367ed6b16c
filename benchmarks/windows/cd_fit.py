"""Window kind ``cd_fit``: a trainer refitting a GAME mixed-effects model with
the data resident, closed loop, one client.

Set-up makes the data from the seed, hands the two feature shards and the
entity ids to the program's own ``GameEstimator.build_coordinates`` (timed:
the span ``data_ready``; the program's ``game.build`` layer span lies inside
it) and runs ONE whole fit untimed (compile or cache load).  The window then
calls ``GameEstimator.fit_coordinates`` on the same coordinates again and
again -- each call is a whole fit from zero coefficients: the configuration's
coordinate-descent iterations over (fixed effect, per-user random effect),
warm-started within the fit, metrics on the device as ``game_training_driver
--device-metrics`` has them, ended by the program's blocking read of its
history and its model -- and closes with the first fit that ends after
``--seconds``.  The unit of work is one coordinate UPDATE (one coordinate's
solve inside one iteration).

Between the descent and each coordinate stands a ``Recorder``: it passes
every call through and keeps what crossed the boundary (the offsets handed
in, the state and the scores handed back, what the solve reported).
``check`` decides ``correct`` from one fit of the window, drawn from the
seed, every update of it against the float64 reference in
``reference_game.py`` (PERF.md section 2).
"""

from __future__ import annotations

import copy
import glob
import os
import time
import types

import numpy as np

from benchmarks import reference_game
from benchmarks.datagen import game_ml20m

#: Where ``run.py`` has the profiler write (its ``TRACE_DIR``).
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".bench_trace")
#: The device plane's line with one event per executed program.
MODULES_LINE = "XLA Modules"


class Recorder:
    """Stands in for one coordinate in the descent's list."""

    def __init__(self, inner, log: list):
        self._inner, self._log = inner, log

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def train(self, offsets, warm_state=None):
        state = self._inner.train(offsets, warm_state=warm_state)
        self._log.append({
            "coordinate": self._inner.name, "kind": self._inner.kind,
            "offsets": offsets, "state": state,
            "solve": getattr(self._inner, "last_solve", None),
            "counts": self._inner.train_counts(),
        })
        return state

    def score(self, state):
        scores = self._inner.score(state)
        self._log[-1]["scores"] = scores
        return scores


class Update:
    """One coordinate update of the checked fit, on the host."""

    def __init__(self, rec, gamma_of):
        self.coordinate, self.kind = rec["coordinate"], rec["kind"]
        self.offsets = np.asarray(rec["offsets"], np.float64)
        self.scores = np.asarray(rec["scores"], np.float64)
        self.value = self.grad = None
        if self.kind == "fixed":
            self.coef = np.asarray(rec["state"], np.float64)
            solve = rec["solve"]
            self.value = float(solve.value)
            self.grad = np.asarray(solve.grad, np.float64)
            self.iterations = int(solve.iterations)
        else:
            self.coef = gamma_of(rec["state"])
            self.iterations = max(
                int(b["iterations_max"]) for b in rec["counts"]["buckets"])

    def with_answer(self, **changed):
        """A copy that says something else (the control and the faults)."""
        other = copy.copy(self)
        for key, value in changed.items():
            setattr(other, key, value)
        return other


def _coordinate_configs(cfg):
    from photon_ml_tpu.game.estimator import (
        FixedEffectCoordinateConfig, RandomEffectCoordinateConfig)
    from photon_ml_tpu.optim.problem import (
        GlmOptimizationConfig, OptimizerConfig, OptimizerType)
    from photon_ml_tpu.optim.regularization import RegularizationContext

    def optimization(spec, max_iters):
        if spec["regularization"] != "l2":
            raise ValueError("window 'cd_fit' drives L2 coordinates only; "
                             f"got {spec['regularization']!r}")
        return GlmOptimizationConfig(
            optimizer=OptimizerConfig(
                optimizer=OptimizerType(spec["optimizer"]),
                max_iters=int(max_iters),
                tolerance=float(spec["tolerance"]),
                history=int(spec["history"])),
            regularization=RegularizationContext.l2())

    fixed, random = cfg["fixed_effect"], cfg["random_effect"]
    return {
        fixed["name"]: FixedEffectCoordinateConfig(
            feature_shard=fixed["feature_shard"],
            optimization=optimization(fixed, cfg["max_iters"]),
            reg_weight=float(fixed["reg_weight"])),
        random["name"]: RandomEffectCoordinateConfig(
            feature_shard=random["feature_shard"],
            entity_key=random["entity_key"],
            optimization=optimization(random, random["max_iters"]),
            reg_weight=float(random["reg_weight"])),
    }


def _require_counts():
    """A program from before its coordinates counted their solves cannot be
    checked here (the reported value, gradient and iterations are what
    ``check`` compares): say so at once, before any data is made."""
    from photon_ml_tpu.game import coordinates

    if not hasattr(coordinates.Coordinate, "train_counts"):
        raise SystemExit(
            "benchmarks/windows/cd_fit.py: this program's GAME coordinates "
            "do not report what their solves counted "
            "(Coordinate.train_counts, FixedEffectCoordinate.last_solve); "
            "the cell cannot be run or checked on it. No result.")


def setup(run):
    """Everything before the window.  Fills ``run.state`` and the set-up
    spans in ``run.spans``."""
    _require_counts()
    import jax

    from photon_ml_tpu.game.estimator import GameEstimator

    cfg = run.cfg
    with run.span("datagen"):
        host = game_ml20m.generate(cfg, run.seed)
        shards, ids = game_ml20m.shards(host)
    labels = host["labels"]
    estimator = GameEstimator(
        cfg["task"], _coordinate_configs(cfg),
        n_iterations=int(cfg["cd_iterations"]),
        device_metrics=bool(cfg["device_metrics"]))
    with run.span("data_ready"):
        coordinates = estimator.build_coordinates(shards, ids, labels)
        jax.block_until_ready([
            jax.tree.leaves(getattr(c.dataset, "data", None)
                            or c.dataset.blocks) for c in coordinates])
    del shards
    log: list = []
    shape = {k: host[k] for k in (
        "n_rows", "n_users", "n_movies", "n_genres", "n_dense",
        "fixed_nnz", "random_nnz")}
    shape["genre_tags"] = host["random_nnz"] - host["n_rows"]
    shape["nnz"] = shape["fixed_nnz"]  # as the layout readers call it
    fixed = next(c for c in coordinates if c.kind == "fixed")
    # The reference makes its own copy from the seed once the window has
    # closed; the program's data needs the host's memory now.
    del host
    run.state.update(
        shape=shape, labels=labels, ids=ids, estimator=estimator,
        coordinates=coordinates, log=log,
        recorders=[Recorder(c, log) for c in coordinates],
        updates_per_fit=int(cfg["cd_iterations"]) * len(coordinates),
        feature_bytes=sum(
            x.nbytes for x in jax.tree.leaves(fixed.dataset.data.features)),
    )
    layouts = {c.name: c.feature_layout for c in coordinates}
    run.info["layout"] = layouts
    expect = cfg.get("expect_layout")
    if not run.dry and expect and not layouts[
            cfg["fixed_effect"]["name"]].startswith(expect):
        raise RuntimeError(
            f"the cell names the {expect} path but the fixed effect holds "
            f"its features as {layouts[cfg['fixed_effect']['name']]}")
    random = _random_coordinate(run)
    run.info["random_effect_blocks"] = [
        {"lanes": b.n_entities, "rows": b.rows_per_entity,
         "dim": b.block_dim, "x_minor": getattr(b, "x_minor", "d"),
         "rows_real": real}
        for b, real in zip(random.dataset.blocks,
                           random.dataset.block_rows_real)]
    with run.span("warm_pass"):
        _one_fit(run)
        log.clear()


def _random_coordinate(run):
    return next(c for c in run.state["coordinates"] if c.kind == "random")


def _one_fit(run):
    """One whole fit through the estimator; returns its history."""
    st = run.state
    _model, history = st["estimator"].fit_coordinates(
        st["recorders"], st["labels"])
    return history


def window(run, seconds):
    """The timed window; returns its fits' ends and the fit kept for the
    check (a reservoir of one, drawn by the seed: every fit of the window
    is as likely, and only one fit's arrays stay on the device)."""
    import jax

    st = run.state
    log = st["log"]
    draw = np.random.default_rng([run.seed % (1 << 63), 20])
    ends, finite, kept, kept_index, fixed_solves = [], [], None, None, []
    st["window_wall_start"] = time.time()
    start = time.perf_counter()
    while time.perf_counter() - start < seconds:
        with jax.profiler.TraceAnnotation("grid"):
            history = _one_fit(run)
        ends.append(time.perf_counter())
        finite.append(all(np.isfinite(h["score_norm"]) for h in history))
        if draw.random() * len(ends) < 1.0:
            kept, kept_index = list(log), len(ends) - 1
        fixed_solves += [rec["solve"] for rec in log if rec["kind"] == "fixed"]
        log.clear()
    with run.span("read_answers"):
        gamma_of = _gamma_reader(run)
        kept = [Update(rec, gamma_of) for rec in kept]
    run.info["fit_ends_s"] = [round(e - start, 4) for e in ends]
    # The window's L-BFGS solves (the fixed effect's), as the readers of
    # the solvers' layer read them.
    solves = [types.SimpleNamespace(iterations=int(i)) for i in
              jax.device_get([s.iterations for s in fixed_solves])]
    return {"start": start, "end": ends[-1], "fits": len(ends),
            "solves": solves,
            "updates": len(ends) * st["updates_per_fit"],
            "finite": finite, "checked_fit": kept_index, "answers": kept}


def _gamma_reader(run):
    """From the random effect's state (one ``(E, D)`` array a block, in the
    block's own columns) to ``(n_users, n_random)`` in the shard's."""
    ds = _random_coordinate(run).dataset
    shape = run.state["shape"]
    users = [np.asarray(ids, dtype=np.int64) for ids in ds.entity_ids]
    col_maps = [np.asarray(b.col_map) for b in ds.blocks]

    def gamma_of(state):
        gamma = np.zeros((shape["n_users"], shape["n_genres"] + 1))
        for user, cmap, coefs in zip(users, col_maps, state):
            coefs = np.asarray(coefs, np.float64)
            lane, k = np.nonzero(cmap >= 0)
            gamma[user[lane], cmap[lane, k]] = coefs[lane, k]
        return gamma

    return gamma_of


def end_to_end(run, win):
    """The cell's end-to-end metrics other than ``setup_s`` and the peak,
    which the harness takes itself."""
    return {"solve_s": (win["end"] - win["start"]) / win["updates"]}


def attempted_failed(win):
    per_fit = win["updates"] // win["fits"]
    return win["updates"], per_fit * sum(1 for ok in win["finite"] if not ok)


def free(run):
    """Read the programs' device time off the trace, if this run was traced
    (``run.py`` deletes the trace before the readers run), then drop the
    program's state before the reference runs."""
    import jax

    with run.span("read_programs"):
        modules = run.state["module_seconds"] = _module_seconds(
            run.state.get("window_wall_start", float("inf")))
    if modules:
        # the window's device seconds by program, most first
        run.info["programs_s"] = dict(sorted(
            ((name, round(sum(d for _s, d in evs), 4))
             for name, evs in modules.items()), key=lambda kv: -kv[1])[:12])
    for key in ("coordinates", "recorders", "estimator", "log", "labels",
                "ids"):
        run.state.pop(key, None)
    jax.clear_caches()


def _module_seconds(not_before: float):
    """``{program name: [device seconds of each execution]}`` from the
    newest trace under ``TRACE_DIR`` written since ``not_before`` (a wall
    time), or ``None``: one event per executed program on the device
    plane's ``XLA Modules`` line."""
    paths = [p for p in glob.glob(os.path.join(
        TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb"))
        if os.path.getmtime(p) >= not_before]
    if not paths:
        return None
    from jax.profiler import ProfileData

    out: dict = {}
    data = ProfileData.from_file(sorted(paths)[-1])
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return out or None


def make_reference(run, **kw):
    host = run.state.get("host")
    if host is None:
        host = run.state["host"] = game_ml20m.generate(run.cfg, run.seed)
    cfg = run.cfg
    return reference_game.GameReference(
        host, cfg["fixed_effect"]["reg_weight"],
        cfg["random_effect"]["reg_weight"], loss=cfg["task"], **kw)


def _rel(diff, scale):
    return float(np.linalg.norm(diff)) / max(float(np.linalg.norm(scale)), 1.0)


def compare(ref, answers):
    """The numbers of one fit's updates against the reference, each the
    largest over the updates it applies to.

    value_gap:      the objective a fixed-effect solve reported against the
        reference's at the coefficients it returned and the offsets it was
        handed, as a share of the reference's.
    fixed_grad_gap: the gradient it reported against the reference's there:
        the norm of the difference over the norm of the gradient at zero
        coefficients and zero offsets.
    user_grad_gap_max, user_grad_gap_mean: a random-effect update solves
        every user to its tolerance, so each user's gradient at what was
        returned, at the offsets handed in, is small beside the same
        user's gradient at zero coefficients (at least 1, the solver's own
        floor): the worst user and the mean over users.
    offsets_gap:    the offsets a coordinate was handed against the other
        coordinate's scores by the reference, at the coefficients the
        program had then: the norm of the difference over the norm.
    scores_gap:     the scores a coordinate handed back against the
        reference's at the coefficients it returned.
    inv_descent:    the full objective before the update over the descent
        the update made, by the reference alone.  Every update minimises
        the full objective in its own block, so every update descends; one
        that returns its state unchanged reads 1e30.
    """
    n_users = ref.host["n_users"]
    width = ref.host["n_genres"] + 1
    beta = np.zeros(ref.cols["n_fixed"])
    gamma = np.zeros((n_users, width))
    fixed = np.zeros(ref.n)
    random = np.zeros(ref.n)
    zero = np.zeros(ref.n)
    g_zero_norm = float(np.linalg.norm(
        ref.fixed_value_and_grad(beta, zero)[1]))

    f_before = ref.full_objective(beta, gamma, fixed, random)
    per_update = []
    for up in answers:
        row = {"coordinate": up.coordinate, "iterations": up.iterations}
        if up.kind == "fixed":
            others = random
            beta = up.coef
            fixed = ref.scores(beta, None)[0]
            mine = fixed
            f, g = ref.fixed_value_and_grad(beta, up.offsets, scores=fixed)
            row["value_gap"] = abs(up.value - f) / abs(f)
            row["fixed_grad_gap"] = float(
                np.linalg.norm(up.grad - g)) / g_zero_norm
        else:
            others = fixed
            gamma = up.coef
            random = ref.scores(None, gamma)[1]
            mine = random
            at_zero = np.linalg.norm(ref.random_grad(
                np.zeros_like(gamma), up.offsets, scores=zero), axis=1)
            gap = np.linalg.norm(
                ref.random_grad(gamma, up.offsets, scores=random), axis=1
            ) / np.maximum(at_zero, 1.0)
            row["user_grad_gap_max"] = float(gap.max())
            row["user_grad_gap_mean"] = float(gap.mean())
        row["offsets_gap"] = _rel(up.offsets - others, others)
        row["scores_gap"] = _rel(up.scores - mine, mine)
        f_after = ref.full_objective(beta, gamma, fixed, random)
        row["inv_descent"] = f_before / max(
            f_before - f_after, 1e-30 * f_before)
        row["objective"] = f_after
        f_before = f_after
        per_update.append(row)
    names = ("value_gap", "fixed_grad_gap", "user_grad_gap_max",
             "user_grad_gap_mean", "offsets_gap", "scores_gap",
             "inv_descent")
    out = {k: max(r[k] for r in per_update if k in r) for k in names}
    return out, per_update, {"g_zero_norm": g_zero_norm}


def judge(ref, answers, limits, caps):
    """``(correct, numbers, details)`` of one fit's updates: each number
    beside its limit.  The window's own answers, the control's and each
    planted fault's all come through here."""
    got, per_update, scale = compare(ref, answers)
    numbers = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    # Exact: no solve runs past its cap.
    over = max(up.iterations - caps[up.kind] for up in answers)
    numbers["iters_over_cap"] = {"value": max(over, 0), "limit": 0}
    correct = all(np.isfinite(n["value"]) and n["value"] <= n["limit"]
                  for n in numbers.values())
    return correct, numbers, (per_update, scale)


def check(run, win):
    """``(correct, numbers)`` of the fit drawn from the seed."""
    answers = win["answers"]
    limits = run.cfg["limits"]
    caps = {"fixed": int(run.cfg["max_iters"]),
            "random": int(run.cfg["random_effect"]["max_iters"])}
    with run.span("reference"):
        ref = make_reference(run)
        correct, numbers, (per_update, scale) = judge(
            ref, answers, limits, caps)
    run.info["check"] = {"fit": win["checked_fit"], "per_update": per_update,
                         **scale}
    if run.control:
        with run.span("control"):
            run.info["control"] = {
                name: dict(zip(("correct", "numbers"), judge(
                    ref, wrong, limits, caps)[:2]))
                for name, wrong in wrong_answers(run, ref, answers)}
    return correct, numbers


def wrong_answers(run, ref, answers):
    """The control and the planted faults, as answers in the place of the
    run's own (``--control 1``; the benchmark's own runs do not call this).
    ``judge`` has to call each of them not correct."""
    def reported(reference, precision="f64"):
        """Every fixed-effect update says what ``reference`` computes at
        the program's own coefficients and offsets."""
        return [
            up.with_answer(**dict(zip(("value", "grad"), (
                reference.fixed_value_and_grad(
                    up.coef, up.offsets, precision)))))
            if up.kind == "fixed" else up for up in answers]

    # The control: the reference in the program's place, one precision
    # down: what the fixed effect reports, and every score handed on.
    control, beta, gamma = [], None, None
    for up in reported(ref, "bf16"):
        if up.kind == "fixed":
            beta = up.coef
            scores = ref.scores(beta, None, "bf16")[0]
        else:
            gamma = up.coef
            scores = ref.scores(None, gamma, "bf16")[1]
        control.append(up.with_answer(scores=scores))
    for i, up in enumerate(control[1:], 1):
        control[i] = up.with_answer(offsets=control[i - 1].scores)
    yield "bf16", control
    # Fault: half of the batch left out, the rest counted double.
    half = np.zeros(ref.n)
    half[::2] = 2.0
    yield "half_batch", reported(make_reference(run, row_scale=half))
    # Fault: the rows that pad the data to whole tiles counted as rows (a
    # padding row has margin 0 and label 0: log 2 of loss, 1/2 of
    # derivative, on the intercept alone).
    pad = -ref.n % 2048
    grad_pad = np.zeros(ref.cols["n_fixed"])
    grad_pad[ref.cols["intercept"]] = 0.5 * pad
    yield "padding_rows_counted", [
        up.with_answer(value=up.value + pad * np.log(2.0),
                       grad=up.grad + grad_pad)
        if up.kind == "fixed" else up for up in answers]
    # Fault: one user's block dropped (its coefficients never solved).
    worst = int(np.argmax(ref.host["counts"]))

    def without(up):
        coef = up.coef.copy()
        coef[worst] = 0.0
        return up.with_answer(coef=coef)

    yield "user_block_dropped", [
        without(up) if up.kind == "random" else up for up in answers]
    # Fault: offsets not refreshed between coordinates: every update after
    # the first trains against what the first was handed.
    yield "offsets_not_refreshed", [
        up.with_answer(offsets=answers[0].offsets) for up in answers]
    # Fault: every update returns its state unchanged (zero coefficients,
    # zero scores, the value and gradient of that point).
    def unchanged(up):
        zero = np.zeros_like(up.coef)
        if up.kind != "fixed":
            return up.with_answer(coef=zero, scores=np.zeros(ref.n))
        value, grad = ref.fixed_value_and_grad(zero, np.zeros(ref.n))
        return up.with_answer(coef=zero, scores=np.zeros(ref.n),
                              offsets=np.zeros(ref.n), value=value, grad=grad)

    yield "state_unchanged", [unchanged(up) for up in answers]
