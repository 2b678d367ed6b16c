"""Window kind ``fit_click``: ``fit``'s regularisation sweep on a hashed click
log (``datagen/click_hashed.py``), whose matrix is wide and sparse enough
that the program lays it out in its wide form (``WideSparseMatrix``: a warm
band of tiles and a cold band of mixed blocks).

As ``fit`` (which it imports and does not edit), and:

* ``setup`` makes the log with ``click_hashed`` and hands it to
  ``make_glm_data`` with the configuration's ``layout`` on the chip and in a
  CPU rehearsal alike (``"auto"``: the program's own rule has to choose the
  wide layout); before anything else it refuses, in seconds, a program that
  has no class named ``expect_layout`` (a commit before the wide layout);
* ``make_reference`` builds ``reference.GlmReference`` over the log's ELL
  arrays (a column merged into the one before it has value 0: nothing);
* the window never closes before its ``min_grids``-th grid (traffic file);
* ``--control 1`` adds one fault of this layout: the cold band's entries
  left out (the reference over the warm columns and the intercept alone).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import reference as reference_mod
from benchmarks.datagen import click_hashed
from benchmarks.windows import fit

Solve = fit.Solve
end_to_end = fit.end_to_end
attempted_failed = fit.attempted_failed
free = fit.free
sampled_grid = fit.sampled_grid
judge = fit.judge
_problem = fit._problem
_one_grid = fit._one_grid


def setup(run):
    """``fit.setup`` with this module's generator and layout choice."""
    from photon_ml_tpu.ops import sparse_pallas

    want = run.cfg["expect_layout"]
    if not hasattr(sparse_pallas, want):
        raise SystemExit(
            f"benchmarks/windows/fit_click.py: this program has no {want}: "
            "it cannot lay out a matrix of this width. No result.")
    import jax

    from photon_ml_tpu.data.dataset import make_glm_data

    cfg = run.cfg
    with run.span("datagen"):
        host = click_hashed.generate(cfg, run.seed)
        csr = click_hashed.as_csr(host)
        labels = host.pop("labels")
        # The reference makes its own copy from the seed once the window
        # has closed; the layout build needs the host's memory now.
        del host["cols"], host["vals"]
    with run.span("data_ready"):
        data = make_glm_data(csr, labels, use_pallas=cfg["layout"])
        jax.block_until_ready(jax.tree.leaves(data))
    del csr, labels
    features = data.features
    run.state.update(
        shape=host, data=data, problem=_problem(cfg),
        grid=[float(x) for x in cfg["reg_weights"]],
        feature_bytes=sum(x.nbytes for x in jax.tree.leaves(features)),
    )
    layout = {"type": type(features).__name__}
    if layout["type"] == "WideSparseMatrix":
        run.state["warm_cols"] = np.asarray(features.warm_cols)
        layout.update(
            warm_cols=int(features.warm_cols.shape[0]),
            cold_a_f=features.cold_a_f, cold_a_b=features.cold_a_b,
            cold_blocks=features.cold_nbr * features.cold_nbc)
        if features.has_warm:
            warm = features.warm
            layout.update(
                a_f=warm.a_f, a_b=warm.a_b, depth_f=warm.depth_f,
                depth_b=warm.depth_b,
                stripes=int(warm.dense_col_ids.shape[0]),
                has_col_perm=warm.has_col_perm, unit_vals=warm.unit_vals)
    run.info["layout"] = layout
    if layout["type"] != cfg["expect_layout"]:
        raise RuntimeError(
            f"the cell names the {cfg['expect_layout']} path but "
            f"make_glm_data built a {layout['type']}")
    with run.span("warm_pass"):
        _one_grid(run, 0, [])


def window(run, seconds):
    """``fit.window`` that never closes before ``min_grids`` grids."""
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


def make_reference(run, cold=True, **kw):
    """The float64 reference over the log; without ``cold``, over the warm
    columns and the intercept alone (the fault)."""
    host = run.state.get("host")
    if host is None:
        host = run.state["host"] = click_hashed.generate(run.cfg, run.seed)
    vals = host["vals"]
    if not cold:
        keep = np.zeros(host["n_features"] + 1, bool)
        keep[run.state["warm_cols"]] = True
        keep[host["n_features"]] = True
        vals = np.where(keep[host["cols"]], vals, np.float32(0.0))
    return reference_mod.GlmReference(
        host["cols"], vals, host["labels"], host["n_features"],
        loss=run.cfg["task"], **kw)


def check(run, win):
    """``fit.check`` with this module's reference and faults."""
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
    """``fit.wrong_answers``'s control and faults over this module's
    reference, then the cold band's entries left out: the value and
    gradient of the log without them, at the program's own coefficients.
    ``judge`` has to call each not correct."""
    def at_own_w(wrong_ref, **kw):
        return [s.with_answer(None, *wrong_ref.value_and_grad(
            s.w, s.lam, **kw)) for s in answers]

    yield "bf16", at_own_w(ref, precision="bf16")
    half = np.zeros(ref.n)
    half[::2] = 2.0
    yield "half_batch", at_own_w(make_reference(run, row_scale=half))
    zero = np.zeros_like(answers[0].w)
    yield "state_unchanged", [
        s.with_answer(zero, *ref.value_and_grad(zero, s.lam)) for s in answers]
    yield "answer_altered", [s.with_answer(s.w * 1.001) for s in answers]
    yield "cold_dropped", at_own_w(make_reference(run, cold=False))
