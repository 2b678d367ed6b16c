"""Layer spans of the training path (telemetry.layer_span): recorded with no
hub installed, the same interval as the hub's span under a driver's hub,
mirrored into the JAX profiler; and the solver's own count of objective
evaluations (``SolveResult.fn_evals``).  CPU, Pallas in interpret mode,
small sizes."""

import glob
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu import telemetry
from photon_ml_tpu.data.dataset import make_glm_data
from photon_ml_tpu.optim.lbfgs import LBFGSConfig, SolveResult, lbfgs_solve
from photon_ml_tpu.optim.problem import (
    GlmOptimizationConfig,
    GlmOptimizationProblem,
    OptimizerConfig,
)
from photon_ml_tpu.optim.regularization import RegularizationContext
from photon_ml_tpu.telemetry import core as telemetry_core

GRID = [1.0, 0.1]
LAYOUT_CHILDREN = ["layout.canonicalize", "layout.dense_split",
                   "layout.col_perm", "layout.orient", "layout.orient"]


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("PHOTON_PALLAS_INTERPRET", "1")


def _corpus(n=2048, d=1000):
    rng = np.random.default_rng(0)
    X = sp.random(n, d, density=0.01, random_state=1, format="csr",
                  dtype=np.float32)
    return X, (rng.uniform(size=n) < 0.5).astype(np.float32)


def _problem(max_iters=4):
    return GlmOptimizationProblem(
        "logistic",
        GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=max_iters),
            regularization=RegularizationContext.l2(),
        ),
    )


def _mark():
    """The newest layer span so far (the ring may be full, and a parent is
    filed after its children: ids, not positions, say what is new)."""
    return max((r["id"] for r in telemetry.layer_spans()), default=0)


def _since(mark):
    return [r for r in telemetry.layer_spans() if r["id"] > mark]


def _fit():
    """A tiny make_glm_data + run_grid; returns the layer spans it left."""
    mark = _mark()
    X, y = _corpus()
    data = make_glm_data(X, y, use_pallas=True)
    results = _problem().run_grid(data, GRID)
    return _since(mark), data, results


@pytest.fixture
def fit(interpret):
    assert telemetry.current() is telemetry.NULL
    return _fit()


def _named(spans, name):
    return [r for r in spans if r["name"] == name]


def _inside(child, parent):
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


class TestNoHub:
    def test_layout_spans_and_parents(self, fit):
        spans, _data, _results = fit
        (made,) = _named(spans, "data.make_glm_data")
        (build,) = _named(spans, "layout.build")
        (place,) = _named(spans, "layout.place")
        assert made["parent"] is None
        assert build["parent"] == made["id"] == place["parent"]
        kids = sorted((r for r in spans if r["parent"] == build["id"]),
                      key=lambda r: r["ts"])
        assert [k["name"] for k in kids] == LAYOUT_CHILDREN
        assert [k["attrs"]["side"] for k in kids[-2:]] == ["f", "b"]
        for child, parent in [(build, made), (place, made),
                              *((k, build) for k in kids)]:
            assert _inside(child, parent), (child["name"], parent["name"])
        # the build ends before the first copy to the device starts
        assert build["ts"] + build["dur"] <= place["ts"]

    def test_layout_attributes(self, fit):
        spans, data, _results = fit
        (made,) = _named(spans, "data.make_glm_data")
        (build,) = _named(spans, "layout.build")
        (place,) = _named(spans, "layout.place")
        P = data.features
        assert made["attrs"] == {"rows": 2048, "nnz": P.nnz,
                                 "layout": "PallasSparseMatrix"}
        predicted = {k: build["attrs"].pop(k)
                     for k in ("a_f_predicted", "a_b_predicted")}
        assert build["attrs"] == {
            "nnz": P.nnz, "a_f": P.a_f, "a_b": P.a_b,
            "stripes": len(P.dense_col_ids) + len(P.dense_row_ids),
            "stripe_nnz_share": 0.0, "stripe_bytes": 0,
            "has_col_perm": P.has_col_perm, "spilled": 0,
        }
        # the stripe chooser's forecast of the depths, from the histogram
        assert abs(predicted["a_f_predicted"] - P.a_f) <= 16
        assert abs(predicted["a_b_predicted"] - P.a_b) <= 16
        assert place["attrs"]["bytes"] == sum(
            x.nbytes for x in jax.tree.leaves(data))

    def test_col_perm_says_how_it_decided(self, fit):
        spans, data, _results = fit
        (perm,) = _named(spans, "layout.col_perm")
        attrs = perm["attrs"]
        assert set(attrs) == {"method", "a_identity", "a_permuted", "engaged"}
        # 20 k entries: too few to load the native library for
        assert attrs["method"] == "sort"
        assert attrs["a_identity"] > 0 and attrs["a_permuted"] > 0
        assert attrs["engaged"] is data.features.has_col_perm

    def test_make_glm_data_returns_resident_data(self, fit):
        _spans, data, _results = fit
        assert all(x.is_fully_addressable and x.is_ready()
                   for x in jax.tree.leaves(data))

    def test_grid_and_solver_spans(self, fit):
        spans, _data, results = fit
        (grid,) = _named(spans, "grid")
        solvers = _named(spans, "solver")
        assert grid["parent"] is None and len(solvers) == len(GRID)
        for s, (lam, _model, res) in zip(solvers, results):
            assert s["parent"] == grid["id"] and _inside(s, grid)
            assert s["attrs"] == {
                "reg_weight": lam, "optimizer": "lbfgs",
                "iterations": int(res.iterations),
                "fn_evals": int(res.fn_evals),
                "converged": bool(res.converged),
                "wall_seconds": s["dur"],
            }
            assert s["attrs"]["fn_evals"] > s["attrs"]["iterations"] > 0

    def test_a_solve_is_measured_once(self, interpret):
        problem = _problem()
        X, y = _corpus()
        mark = _mark()
        problem.run_grid(make_glm_data(X, y, use_pallas=True), GRID)
        solvers = _named(_since(mark), "solver")
        assert problem.grid_wall_seconds == {
            s["attrs"]["reg_weight"]: s["dur"] for s in solvers}

    def test_a_spill_rebuilds_both_orientations(self, interpret):
        from photon_ml_tpu.ops.sparse_pallas import build_pallas_host

        rng = np.random.default_rng(3)
        # one cell of the slot grid far over the depth cap
        rows = np.concatenate([np.full(40, 5), rng.integers(0, 512, 300)])
        cols = np.concatenate([np.arange(40) * 128,
                               rng.integers(0, 6000, 300)])
        mark = _mark()
        P = build_pallas_host(rows, cols, np.ones(340, np.float32), 512,
                              6000, depth_cap=2, col_permutation=False)
        spans = _since(mark)
        (build,) = _named(spans, "layout.build")
        assert P.spill.has_spill and build["attrs"]["spilled"] > 0
        assert [r["attrs"]["side"] for r in _named(spans, "layout.orient")
                ] == ["f", "b", "f", "b"]
        assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(P))


class TestCostWhenOff:
    def test_no_hub_no_sink_no_io(self, monkeypatch):
        """Under the NULL hub a layer span touches no sink and no hub lock:
        either would raise here."""
        def refuse(*a, **k):
            raise AssertionError("a layer span reached a hub's sinks")

        monkeypatch.setattr(telemetry.Telemetry, "_emit", refuse)
        monkeypatch.setattr("builtins.open", refuse)
        assert telemetry.current() is telemetry.NULL
        with telemetry.layer_span("layout.build", nnz=3) as sp_:
            sp_.set(a_f=8)
        last = telemetry.layer_spans()[-1]
        assert last["name"] == "layout.build"
        assert last["attrs"] == {"nnz": 3, "a_f": 8}

    def test_ring_is_bounded(self):
        capacity = telemetry_core._LAYER_RING.capacity
        for _ in range(capacity + 10):
            with telemetry.layer_span("grid"):
                pass
        assert len(telemetry.layer_spans()) == capacity

    def test_other_call_sites_stay_one_branch(self):
        """Every ``tel.span`` call site under the NULL hub is still the
        shared no-op: nothing allocated, nothing recorded."""
        mark = _mark()
        tel = telemetry.current()
        assert tel is telemetry.NULL
        assert tel.span("solver", reg_weight=1.0) is telemetry_core._NULL_SPAN
        with tel.span("chunk") as sp_:
            sp_.set(rows=1)
        assert _since(mark) == []

    def test_spans_on_threads_are_roots_of_their_own(self):
        seen = []

        def work():
            with telemetry.layer_span("grid") as sp_:
                seen.append(sp_.parent_id)

        with telemetry.layer_span("data.make_glm_data"):
            t = threading.Thread(target=work)
            t.start()
            t.join(timeout=30)
        assert not t.is_alive() and seen == [None]

    def test_an_error_is_recorded_and_raised(self):
        with pytest.raises(ValueError):
            with telemetry.layer_span("layout.place"):
                raise ValueError("boom")
        last = telemetry.layer_spans()[-1]
        assert last["name"] == "layout.place"
        assert last["error"] == "ValueError: boom"
        with telemetry.layer_span("grid") as sp_:
            assert sp_.parent_id is None  # the failed span left the stack


class TestUnderADriverHub:
    def test_solver_record_in_events_jsonl(self, interpret, tmp_path):
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            with tel.span("train"):
                spans, _data, results = _fit()
        with open(os.path.join(tmp_path, "events.jsonl")) as f:
            records = [json.loads(line) for line in f]
        by_id = {r["id"]: r for r in records if r.get("type") == "span"}
        solvers = [r for r in by_id.values() if r["name"] == "solver"]
        assert len(solvers) == len(GRID)
        ring = _named(spans, "solver")
        for rec, mine, (lam, _m, res) in zip(solvers, ring, results):
            assert set(rec) == {"type", "name", "ts", "dur", "id", "parent",
                                "tid", "attrs"}
            assert rec["attrs"] == mine["attrs"]
            assert rec["attrs"]["wall_seconds"] == rec["dur"] == mine["dur"]
            assert rec["ts"] == pytest.approx(
                mine["ts"] - tel._epoch_perf, abs=1e-9)
            assert by_id[rec["parent"]]["name"] == "grid"
            assert by_id[by_id[rec["parent"]]["parent"]]["name"] == "train"
        names = {r["name"] for r in by_id.values()}
        assert {"data.make_glm_data", "layout.build", "layout.place",
                "layout.orient", "grid"} <= names
        snap = records[-1]["snapshot"]
        assert snap["counters"]["solver_iterations"] == sum(
            int(res.iterations) for _l, _m, res in results)
        assert snap["counters"]["solver_fn_evals"] == sum(
            int(res.fn_evals) for _l, _m, res in results)
        hist = snap["histograms"]["solver_wall_seconds"]
        assert hist["count"] == len(GRID)
        assert hist["sum"] == pytest.approx(sum(s["dur"] for s in ring))


class TestFnEvals:
    @staticmethod
    def _counted(value_and_grad):
        calls = []

        def counted(w):
            jax.debug.callback(lambda: calls.append(1))
            return value_and_grad(w)

        return counted, calls

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_equals_an_independent_count(self, seed):
        rng = np.random.default_rng(seed)
        A = jnp.asarray(rng.normal(size=(40, 12)), jnp.float32)
        y = jnp.asarray(rng.uniform(size=40) < 0.5, jnp.float32)

        def f(w):  # logistic loss: the line search has to work for its step
            m = A @ w
            return jnp.sum(jnp.logaddexp(0.0, m) - y * m) + 0.05 * w @ w

        counted, calls = self._counted(jax.value_and_grad(f))
        res = jax.jit(lambda w0: lbfgs_solve(
            counted, w0, LBFGSConfig(max_iters=25)))(
                jnp.asarray(rng.normal(size=12) * 3, jnp.float32))
        jax.block_until_ready(res)
        jax.effects_barrier()
        assert int(res.fn_evals) == len(calls)
        assert int(res.fn_evals) > int(res.iterations) + 1  # extra trials

    def test_quadratic_that_accepts_every_first_step(self):
        rng = np.random.default_rng(5)
        # float64: in float32 the last iterations' Armijo test is lost in
        # rounding and the search makes trials that no algebra asks for
        diag = jnp.asarray(rng.uniform(1.0, 1.5, size=16), jnp.float64)
        b = jnp.asarray(rng.normal(size=16), jnp.float64)
        b = 0.5 * b / jnp.linalg.norm(b)  # ||g0|| < 1: the first step is 1

        def vg(w):
            return 0.5 * w @ (diag * w) - b @ w, diag * w - b

        counted, calls = self._counted(vg)
        res = jax.jit(lambda w0: lbfgs_solve(
            counted, w0, LBFGSConfig(max_iters=30)))(jnp.zeros(16, jnp.float64))
        jax.block_until_ready(res)
        jax.effects_barrier()
        assert int(res.iterations) > 2
        assert int(res.fn_evals) == int(res.iterations) + 1 == len(calls)

    def test_solvers_that_do_not_count_say_none(self, interpret):
        # the box-constrained path (bounds route to SPG) counts nothing
        X, y = _corpus(512, 40)
        problem = _problem(max_iters=3)
        mark = _mark()
        box = (jnp.full((40,), -1.0), jnp.full((40,), 1.0))
        ((_lam, _model, res),) = problem.run_grid(
            make_glm_data(X, y, use_pallas=False), [1.0], bounds=box)
        assert res.fn_evals is None and res.stalled is not None
        (solver,) = _named(_since(mark), "solver")
        assert solver["attrs"]["optimizer"] == "lbfgs"  # as configured
        assert "fn_evals" not in solver["attrs"]
        assert "stalled" not in solver["attrs"]
        assert solver["attrs"]["iterations"] == int(res.iterations)


TRON_COUNTS = ("cg_iterations", "rejected_steps", "boundary_exits")


def _tron_problem(max_iters=6, tolerance=1e-4):
    from photon_ml_tpu.optim.problem import OptimizerType

    return GlmOptimizationProblem(
        "logistic",
        GlmOptimizationConfig(
            optimizer=OptimizerConfig(optimizer=OptimizerType.TRON,
                                      max_iters=max_iters,
                                      tolerance=tolerance),
            regularization=RegularizationContext.l2(),
        ),
    )


class TestTronCounts:
    """What a trust-region Newton solve counts in its loops' states
    (``SolveResult.cg_iterations`` and the fields beside it), and where
    ``grid_loop`` puts it."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_equal_independent_counts(self, seed):
        from photon_ml_tpu.optim.tron import TRONConfig, tron_solve

        rng = np.random.default_rng(seed)
        A = jnp.asarray(rng.normal(size=(60, 12)), jnp.float32)
        y = jnp.asarray(rng.uniform(size=60) < 0.5, jnp.float32)
        vg_calls, hv_calls = [], []

        def f(w):
            m = A @ w
            return jnp.sum(jnp.logaddexp(0.0, m) - y * m) + 0.05 * w @ w

        def vg(w):
            jax.debug.callback(lambda: vg_calls.append(1))
            return jax.value_and_grad(f)(w)

        def hvp(w, v, aux):
            jax.debug.callback(lambda: hv_calls.append(1))
            return A.T @ (aux * (A @ v)) + 0.1 * v

        def d2(w):
            p = jax.nn.sigmoid(A @ w)
            return p * (1.0 - p)

        res = jax.jit(lambda w0: tron_solve(
            vg, hvp, w0, TRONConfig(max_iters=25, tolerance=1e-5),
            d2_fn=d2))(jnp.asarray(rng.normal(size=12) * 3, jnp.float32))
        jax.block_until_ready(res)
        jax.effects_barrier()
        # (at 1e-5 a float32 solve reaches the plateau where rho is noise
        # on some seeds and refuses steps: the count below sees them)
        assert int(res.iterations) >= 2
        assert int(res.cg_iterations) == len(hv_calls)
        assert int(res.cg_iterations) >= int(res.iterations)
        assert int(res.fn_evals) == int(res.iterations) + 1 == len(vg_calls)
        values = np.asarray(res.values)[:int(res.iterations) + 1]
        assert int(res.rejected_steps) == int(
            np.sum(values[1:] == values[:-1]))
        assert 0 <= int(res.boundary_exits) <= int(res.iterations)

    def test_an_lbfgs_result_has_none_of_them(self):
        res = lbfgs_solve(lambda w: (w @ w, 2 * w), jnp.ones(4),
                          LBFGSConfig(max_iters=3))
        assert res.fn_evals is not None
        assert [getattr(res, c) for c in TRON_COUNTS] == [None] * 3

    def test_on_the_solver_span_without_a_hub(self, interpret):
        assert telemetry.current() is telemetry.NULL
        X, y = _corpus()
        data = make_glm_data(X, y, use_pallas=True)
        mark = _mark()
        results = _tron_problem().run_grid(data, GRID)
        solvers = _named(_since(mark), "solver")
        assert len(solvers) == len(GRID)
        for s, (lam, _model, res) in zip(solvers, results):
            assert s["attrs"] == {
                "reg_weight": lam, "optimizer": "tron",
                "iterations": int(res.iterations),
                "fn_evals": int(res.iterations) + 1,
                "converged": bool(res.converged),
                "wall_seconds": s["dur"],
                **{c: int(getattr(res, c)) for c in TRON_COUNTS},
            }
            assert s["attrs"]["cg_iterations"] >= s["attrs"]["iterations"] > 0

    def test_under_a_hub_with_their_counters(self, interpret, tmp_path):
        X, y = _corpus()
        data = make_glm_data(X, y, use_pallas=True)
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            with tel.span("train"):
                results = _tron_problem().run_grid(data, GRID)
        with open(os.path.join(tmp_path, "events.jsonl")) as f:
            records = [json.loads(line) for line in f]
        solvers = [r for r in records
                   if r.get("type") == "span" and r["name"] == "solver"]
        for rec, (_lam, _model, res) in zip(solvers, results):
            for c in TRON_COUNTS:
                assert rec["attrs"][c] == int(getattr(res, c))
        counters = records[-1]["snapshot"]["counters"]
        assert counters["solver_cg_iterations"] == sum(
            int(res.cg_iterations) for _l, _m, res in results) > 0
        assert counters["solver_fn_evals"] == sum(
            int(res.iterations) + 1 for _l, _m, res in results)

    @pytest.mark.parametrize("optimizer", ["lbfgs", "tron"])
    def test_one_batched_read_a_solve(self, interpret, monkeypatch,
                                      optimizer):
        """The counts ride the one tuple that is queued behind the solve
        and read once; an L-BFGS solve queues what it always did."""
        problem = _problem() if optimizer == "lbfgs" else _tron_problem()
        X, y = _corpus()
        data = make_glm_data(X, y, use_pallas=True)
        problem.run_grid(data, GRID)  # compile outside the count
        queued, read = [], []
        copy, get = jax.copy_to_host_async, jax.device_get
        monkeypatch.setattr(jax, "copy_to_host_async",
                            lambda x: (queued.append(x), copy(x))[1])
        monkeypatch.setattr(jax, "device_get",
                            lambda x: (read.append(x), get(x))[1])
        problem.run_grid(data, GRID)
        assert len(queued) == len(read) == len(GRID)
        leaves = 3 if optimizer == "lbfgs" else 3 + len(TRON_COUNTS)
        assert all(len(jax.tree.leaves(q)) == leaves for q in queued)


OWLQN_COUNTS = ("stalled", "orthant_clamps", "nonzeros")


def _l1_problem(max_iters=12, tolerance=1e-3):
    return GlmOptimizationProblem(
        "logistic",
        GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=max_iters,
                                      tolerance=tolerance),
            regularization=RegularizationContext.l1(),
        ),
    )


class TestOwlqnCounts:
    """What an orthant-wise solve counts in its loop's state
    (``SolveResult.orthant_clamps``, ``nonzeros``, and ``fn_evals`` and
    ``stalled`` for OWL-QN too), and where ``grid_loop`` puts it."""

    def test_an_lbfgs_or_tron_result_has_none_of_them(self):
        res = lbfgs_solve(lambda w: (w @ w, 2 * w), jnp.ones(4),
                          LBFGSConfig(max_iters=3))
        assert [getattr(res, c) for c in OWLQN_COUNTS] == [None] * 3

    def test_on_the_solver_span_without_a_hub(self, interpret):
        assert telemetry.current() is telemetry.NULL
        X, y = _corpus()
        data = make_glm_data(X, y, use_pallas=True)
        mask = jnp.ones((data.n_features,), jnp.float32).at[-1].set(0.0)
        mark = _mark()
        results = _l1_problem().run_grid(data, GRID, l1_mask=mask)
        solvers = _named(_since(mark), "solver")
        assert len(solvers) == len(GRID)
        for s, (lam, _model, res) in zip(solvers, results):
            assert s["attrs"] == {
                # any L1 component routes to OWL-QN; the span says what was
                # configured
                "reg_weight": lam, "optimizer": "lbfgs",
                "iterations": int(res.iterations),
                "fn_evals": int(res.fn_evals),
                "converged": bool(res.converged),
                "wall_seconds": s["dur"],
                "stalled": bool(res.stalled),
                "orthant_clamps": int(res.orthant_clamps),
                "nonzeros": int(res.nonzeros),
            }
            assert s["attrs"]["fn_evals"] > s["attrs"]["iterations"] > 0
            w = np.asarray(res.w)
            assert s["attrs"]["nonzeros"] == np.count_nonzero(w[:-1])
        # the weaker penalty keeps more coefficients
        assert solvers[0]["attrs"]["nonzeros"] < solvers[1]["attrs"][
            "nonzeros"]

    def test_under_a_hub_with_their_counters(self, interpret, tmp_path):
        X, y = _corpus()
        data = make_glm_data(X, y, use_pallas=True)
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            with tel.span("train"):
                results = _l1_problem().run_grid(data, GRID)
        with open(os.path.join(tmp_path, "events.jsonl")) as f:
            records = [json.loads(line) for line in f]
        solvers = [r for r in records
                   if r.get("type") == "span" and r["name"] == "solver"]
        for rec, (_lam, _model, res) in zip(solvers, results):
            for c in OWLQN_COUNTS:
                assert rec["attrs"][c] == int(getattr(res, c))
        counters = records[-1]["snapshot"]["counters"]
        assert counters["solver_orthant_clamps_total"] == sum(
            int(res.orthant_clamps) for _l, _m, res in results)
        assert counters["solver_stalled_total"] == sum(
            int(res.stalled) for _l, _m, res in results)
        assert counters["solver_fn_evals"] == sum(
            int(res.fn_evals) for _l, _m, res in results) > 0
        assert "solver_cg_iterations" not in counters

    def test_one_batched_read_a_solve(self, interpret, monkeypatch):
        problem = _l1_problem()
        X, y = _corpus()
        data = make_glm_data(X, y, use_pallas=True)
        problem.run_grid(data, GRID)  # compile outside the count
        queued, read = [], []
        copy, get = jax.copy_to_host_async, jax.device_get
        monkeypatch.setattr(jax, "copy_to_host_async",
                            lambda x: (queued.append(x), copy(x))[1])
        monkeypatch.setattr(jax, "device_get",
                            lambda x: (read.append(x), get(x))[1])
        problem.run_grid(data, GRID)
        assert len(queued) == len(read) == len(GRID)
        assert all(len(jax.tree.leaves(q)) == 3 + len(OWLQN_COUNTS)
                   for q in queued)

    @pytest.mark.parametrize("reg_type", ["l1", "elastic_net"])
    def test_through_glm_driver(self, tmp_path, reg_type):
        """``glm_driver --reg-type l1`` (whatever ``--optimizer`` says) leaves
        the counts on the ``solver`` spans of the telemetry ring."""
        from photon_ml_tpu.data import libsvm
        from photon_ml_tpu.drivers import glm_driver

        rng = np.random.default_rng(3)
        n, d = 600, 40
        X = sp.random(n, d, density=0.15, random_state=3, format="csr")
        X.data[:] = 1.0
        w_true = rng.normal(size=d) * (rng.uniform(size=d) < 0.4)
        y = np.where(
            rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w_true))), 1.0, -1.0)
        train = str(tmp_path / "t.libsvm")
        libsvm.write_libsvm(train, X, y)
        mark = _mark()
        result = glm_driver.run([
            "--train-data", train, "--output-dir", str(tmp_path / "out"),
            "--task", "logistic", "--reg-type", reg_type,
            "--optimizer", "lbfgs", "--reg-weights", "20,2",
            "--n-features", str(d), "--tolerance", "1e-4",
        ])
        assert result
        solvers = _named(_since(mark), "solver")
        assert [s["attrs"]["reg_weight"] for s in solvers] == [20.0, 2.0]
        for s in solvers:
            attrs = s["attrs"]
            assert attrs["stalled"] is False and attrs["converged"] is True
            assert attrs["fn_evals"] > attrs["iterations"] >= 3
            assert attrs["orthant_clamps"] >= 0
            assert 0 < attrs["nonzeros"] <= d
        assert solvers[0]["attrs"]["nonzeros"] < solvers[1]["attrs"][
            "nonzeros"]


# What each solver family fills in SolveResult beyond the base fields, and
# what grid_loop must make of it: the ``solver`` span's own attributes past
# the five every solve has, and every counter it moves (per solve).
FAMILY_FIELDS = {
    "lbfgs": (dict(fn_evals=9),
              {"fn_evals": 9},
              {"solver_iterations": 7, "solver_fn_evals": 9}),
    "tron": (dict(fn_evals=8, cg_iterations=31, rejected_steps=2,
                  boundary_exits=1),
             {"fn_evals": 8, "cg_iterations": 31, "rejected_steps": 2,
              "boundary_exits": 1},
             {"solver_iterations": 7, "solver_fn_evals": 8,
              "solver_cg_iterations": 31}),
    "owlqn": (dict(fn_evals=11, stalled=True, orthant_clamps=5, nonzeros=2),
              {"fn_evals": 11, "stalled": True, "orthant_clamps": 5,
               "nonzeros": 2},
              {"solver_iterations": 7, "solver_fn_evals": 11,
               "solver_orthant_clamps_total": 5, "solver_stalled_total": 1}),
    # SPG fills ``stalled`` and nothing else; the grid loop does not read it
    "spg": (dict(stalled=True),
            {},
            {"solver_iterations": 7}),
}


class TestSolverCountsRule:
    """``grid_loop``'s contract with the benchmark's span readers, pinned on
    a fake result per solver family: which fields land on the ``solver``
    span, under which names and types, and which counters they feed."""

    @pytest.mark.parametrize("family", sorted(FAMILY_FIELDS))
    def test_span_attributes_and_counters(self, family):
        filled, attrs, counters = FAMILY_FIELDS[family]
        res = SolveResult(
            w=jnp.arange(3, dtype=jnp.float32), value=jnp.float32(1.5),
            grad=jnp.zeros(3), iterations=jnp.int32(7),
            converged=jnp.bool_(True), values=jnp.zeros(8),
            grad_norms=jnp.zeros(8),
            **{k: (jnp.bool_(v) if isinstance(v, bool) else jnp.int32(v))
               for k, v in filled.items()},
        )
        tel = telemetry.Telemetry(enabled=True, sinks=[])
        prev = telemetry.set_current(tel)
        mark = _mark()
        try:
            results = _problem().grid_loop(lambda lam, w: res, GRID)
        finally:
            telemetry.set_current(prev)
        solvers = _named(_since(mark), "solver")
        assert len(solvers) == len(results) == len(GRID)
        for s, lam in zip(solvers, sorted(GRID, reverse=True)):
            assert s["attrs"] == {
                "reg_weight": lam, "optimizer": "lbfgs", "iterations": 7,
                "converged": True, "wall_seconds": s["dur"], **attrs,
            }
            assert all(type(s["attrs"][k]) is type(v)
                       for k, v in attrs.items())
        snap = tel.snapshot()
        assert {k: v for k, v in snap["counters"].items()
                if k.startswith("solver_")} == {
            k: v * len(GRID) for k, v in counters.items()}
        assert snap["histograms"]["solver_wall_seconds"]["count"] == len(GRID)


class TestProfiler:
    def test_annotations_on_the_host_line(self, interpret, tmp_path):
        """Under a profiler session the layer spans lie on the host's
        ``python`` line (where the device plane's clock is the trace's)."""
        from jax.profiler import ProfileData

        _fit()  # compile first: the trace then holds the spans, not XLA
        jax.profiler.start_trace(str(tmp_path))
        try:
            spans, _data, _results = _fit()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        counts = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    if line.name == "python":
                        for ev in line.events:
                            counts[ev.name] = counts.get(ev.name, 0) + 1
        for name in {r["name"] for r in spans}:
            assert counts.get(name) == len(_named(spans, name)), name

    def test_nothing_fails_without_a_session(self, fit):
        spans, _data, _results = fit
        assert {"data.make_glm_data", "grid", "solver"} <= {
            r["name"] for r in spans}
