"""Distributed solver subsystem tests (ISSUE 18).

Three pillars:

1. **Solver choice** — ``optim.problem.choose_solver``'s legacy routing
   (an unset ``OptimizerConfig.solver`` must be BITWISE identical to the
   explicit name on the resident, streamed, and distributed paths), its
   static compatibility guards, and ``solvers.HOST_SOLVERS``.
2. **Host-kind solvers** — consensus-ADMM (L-BFGS and cached-eigh ridge
   x-updates, logical shards AND the 8-virtual-device mesh) and
   drift-corrected distributed block CD converge to the same optimum as
   the resident reference solvers.
3. **Chaos** — a kill at ``admm.consensus`` (the outer-iteration
   boundary) or ``distributed.allreduce`` (the reduce seam) resumes
   BITWISE through the GridCheckpointer + watchdog, mirroring
   test_chaos's crash-at-every-boundary bar.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import chaos, solvers
from photon_ml_tpu import telemetry as telemetry_mod
from photon_ml_tpu.data.dataset import make_glm_data
from photon_ml_tpu.io.checkpoint import GridCheckpointer
from photon_ml_tpu.optim.problem import (
    DEVICE_SOLVERS,
    HOST_LOOP_SOLVERS,
    GlmOptimizationConfig,
    GlmOptimizationProblem,
    OptimizerConfig,
    OptimizerType,
    choose_solver,
)
from photon_ml_tpu.optim.regularization import RegularizationContext
from photon_ml_tpu.parallel.distributed import (
    data_mesh,
    run_grid_distributed,
    shard_glm_data,
)
from photon_ml_tpu.solvers import sharded as solvers_sharded
from photon_ml_tpu.utils.watchdog import RetryPolicy, run_with_retries


def _bitwise_equal(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return a.shape == b.shape and a.tobytes() == b.tobytes()


def _make_xy(rng, n=240, d=10, task="logistic"):
    X = (rng.normal(size=(n, d)) / np.sqrt(d)).astype(np.float32)
    w_true = (rng.normal(size=d) * (rng.uniform(size=d) < 0.5)).astype(
        np.float32
    )
    if task == "logistic":
        p = 1.0 / (1.0 + np.exp(-3.0 * (X @ w_true)))
        y = (rng.uniform(size=n) < p).astype(np.float32)
    else:
        y = (X @ w_true + 0.1 * rng.normal(size=n)).astype(np.float32)
    return X, y


def _make_problem(
    task="logistic",
    reg=None,
    solver=None,
    solver_options=(),
    optimizer=OptimizerType.LBFGS,
    max_iters=150,
):
    return GlmOptimizationProblem(task, GlmOptimizationConfig(
        optimizer=OptimizerConfig(
            optimizer=optimizer, max_iters=max_iters, tolerance=1e-8,
            solver=solver, solver_options=solver_options,
        ),
        regularization=(
            reg if reg is not None else RegularizationContext.l2()
        ),
    ))


def _objective_value(problem, data, w, lam):
    cfg = problem.config
    l1 = cfg.regularization.l1_weight(lam)
    l2 = cfg.regularization.l2_weight(lam)
    m = data.features.matvec(jnp.asarray(w, jnp.float32)) + data.offsets
    loss = jnp.sum(
        data.weights * problem.objective.loss.value(m, data.labels)
    )
    return float(
        loss + l1 * jnp.sum(jnp.abs(jnp.asarray(w)))
        + 0.5 * l2 * jnp.vdot(jnp.asarray(w), jnp.asarray(w))
    )


# ---------------------------------------------------------------------------
# The solver choice
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_builtins_registered(self):
        assert set(DEVICE_SOLVERS + HOST_LOOP_SOLVERS) == {
            "lbfgs", "owlqn", "tron", "spg", "admm", "block_cd"}
        assert set(solvers.HOST_SOLVERS) == set(HOST_LOOP_SOLVERS)
        assert all(map(callable, solvers.HOST_SOLVERS.values()))

    def test_unknown_name(self):
        with pytest.raises(KeyError, match="unknown solver"):
            choose_solver(OptimizerConfig(solver="levenberg"), l1_frac=0.0)

    def test_legacy_routing(self):
        opt = OptimizerConfig(optimizer=OptimizerType.TRON)
        assert choose_solver(opt, l1_frac=0.0) == "tron"
        assert choose_solver(opt, l1_frac=0.5) == "owlqn"
        assert choose_solver(opt, l1_frac=0.0, has_bounds=True) == "spg"
        assert choose_solver(OptimizerConfig(), l1_frac=0.0) == "lbfgs"
        assert choose_solver(
            OptimizerConfig(optimizer=OptimizerType.OWLQN), l1_frac=0.0
        ) == "owlqn"

    def test_explicit_name_guards(self):
        lbfgs = OptimizerConfig(solver="lbfgs")
        with pytest.raises(ValueError, match="no L1 subgradient"):
            choose_solver(lbfgs, l1_frac=0.5)
        with pytest.raises(ValueError, match="box constraints"):
            choose_solver(lbfgs, l1_frac=0.0, has_bounds=True)
        with pytest.raises(ValueError, match="needs box constraints"):
            choose_solver(OptimizerConfig(solver="spg"), l1_frac=0.0)
        admm = OptimizerConfig(solver="admm")
        assert choose_solver(admm, l1_frac=0.5) == "admm"
        with pytest.raises(ValueError, match="box constraints"):
            choose_solver(admm, l1_frac=0.0, has_bounds=True)
        assert choose_solver(
            OptimizerConfig(solver="tron"), l1_frac=0.0) == "tron"

    def test_solver_options_dict(self):
        opt = OptimizerConfig(
            solver="admm", solver_options=(("rho", "0.5"), ("shards", "4"))
        )
        assert opt.solver_options_dict() == {"rho": "0.5", "shards": "4"}
        assert OptimizerConfig().solver_options_dict() == {}

    def test_host_kind_rejected_in_traced_solve(self, rng):
        X, y = _make_xy(rng)
        data = make_glm_data(X, y)
        problem = _make_problem(
            reg=RegularizationContext.elastic_net(0.5), solver="admm"
        )
        with pytest.raises(ValueError, match="host-side outer loop"):
            problem.solve(data, 0.1)


# ---------------------------------------------------------------------------
# An explicit name = the legacy routing, bitwise
# ---------------------------------------------------------------------------

def _run_driver(tmp_path, X, y, *flags):
    """glm_driver over a LIBSVM copy of (X, y); returns (result, the saved
    coefficients by λ, the run's metrics.json counters)."""
    import json
    import os

    import scipy.sparse as sp

    from photon_ml_tpu.data import libsvm
    from photon_ml_tpu.drivers import glm_driver
    from photon_ml_tpu.io.model_store import load_glm_model

    train = str(tmp_path / "train.libsvm")
    if not os.path.exists(train):
        libsvm.write_libsvm(train, sp.csr_matrix(X), np.where(y > 0, 1.0, -1.0))
    out = str(tmp_path / "_".join(f.strip("-") for f in flags) or "plain")
    result = glm_driver.run([
        "--train-data", train, "--output-dir", out, "--task", "logistic",
        "--n-features", str(X.shape[1]), "--output-mode", "all", *flags,
    ])
    coefs = {
        lam: np.asarray(load_glm_model(os.path.join(
            out, f"model_lambda_{float(lam):g}.avro"))[0].coefficients.means)
        for lam in result["objective_values"]
    }
    with open(os.path.join(out, "metrics.json")) as f:
        counters = json.load(f)["counters"]
    return result, coefs, counters


class TestDispatchParity:
    """An EXPLICIT solver name must be bitwise identical to the implicit
    legacy routing on every execution path (``choose_solver`` names what
    the routing would have picked, and the same solve runs)."""

    @pytest.mark.parametrize("name,optimizer,reg", [
        ("lbfgs", OptimizerType.LBFGS, RegularizationContext.l2()),
        ("tron", OptimizerType.TRON, RegularizationContext.l2()),
        ("owlqn", OptimizerType.LBFGS,
         RegularizationContext.elastic_net(0.5)),
    ])
    def test_resident_bitwise(self, rng, name, optimizer, reg):
        X, y = _make_xy(rng)
        data = make_glm_data(X, y)
        implicit = _make_problem(reg=reg, optimizer=optimizer)
        explicit = _make_problem(reg=reg, optimizer=optimizer, solver=name)
        res_i = implicit.solve_single_device(data, 0.3)
        res_e = explicit.solve_single_device(data, 0.3)
        assert _bitwise_equal(res_i.w, res_e.w)
        assert int(res_i.iterations) == int(res_e.iterations)

    @pytest.mark.parametrize("name,reg", [
        ("lbfgs", RegularizationContext.l2()),
        ("owlqn", RegularizationContext.elastic_net(0.5)),
    ])
    def test_streamed_bitwise(self, rng, name, reg):
        from photon_ml_tpu.data.streaming import make_streaming_glm_data
        from photon_ml_tpu.optim.streaming import streaming_run_grid

        X, y = _make_xy(rng)
        stream = make_streaming_glm_data(X, y, chunk_rows=64)
        grid = [1.0, 0.1]
        imp = streaming_run_grid(_make_problem(reg=reg), stream, grid)
        exp = streaming_run_grid(
            _make_problem(reg=reg, solver=name), stream, grid
        )
        for (lam_i, m_i, _), (lam_e, m_e, _) in zip(imp, exp):
            assert lam_i == lam_e
            assert _bitwise_equal(
                m_i.coefficients.means, m_e.coefficients.means
            )

    def test_distributed_bitwise(self, rng, tmp_path, eight_devices):
        """Through glm_driver, which chooses the grid: on the mesh the
        implicit routing and ``--solver owlqn`` run the same shard_map
        solve."""
        X, y = _make_xy(rng)
        flags = ("--data-parallel", "auto", "--reg-type", "elastic_net",
                 "--reg-weights", "0.1")
        imp, coefs_i, _ = _run_driver(tmp_path, X, y, *flags)
        exp, coefs_e, counters = _run_driver(
            tmp_path, X, y, *flags, "--solver", "owlqn")
        assert imp["objective_values"] == exp["objective_values"]
        assert coefs_i.keys() == coefs_e.keys()
        for lam in coefs_i:
            assert _bitwise_equal(coefs_i[lam], coefs_e[lam])
        assert "solvers_sharded_solves_total" not in counters

    def test_driver_runs_host_loop_on_the_mesh(self, rng, tmp_path,
                                               eight_devices):
        """``--solver admm --data-parallel auto``: the driver hands the
        mesh to the host-loop grid, which lands on OWL-QN's optimum."""
        X, y = _make_xy(rng, n=256, d=6)
        flags = ("--data-parallel", "auto", "--reg-type", "elastic_net",
                 "--reg-weights", "0.2", "--max-iters", "150",
                 "--tolerance", "1e-8")
        ref, _, _ = _run_driver(tmp_path, X, y, *flags)
        res, _, counters = _run_driver(
            tmp_path, X, y, *flags, "--solver", "admm",
            "--solver-option", "reltol=1e-6")
        assert counters["solvers_sharded_solves_total"] == 1
        (f_ref,) = ref["objective_values"].values()
        (f_admm,) = res["objective_values"].values()
        assert abs(f_admm - f_ref) / max(1.0, abs(f_ref)) <= 1e-4

    def test_distributed_grid_refuses_host_loop(self, rng, eight_devices):
        X, y = _make_xy(rng, n=80, d=4)
        mesh = data_mesh(eight_devices)
        dist = shard_glm_data(X, y, mesh)
        problem = _make_problem(
            reg=RegularizationContext.elastic_net(0.5), solver="admm")
        with pytest.raises(ValueError, match="host-side outer loop"):
            run_grid_distributed(problem, dist, mesh, [0.1])


# ---------------------------------------------------------------------------
# Consensus ADMM
# ---------------------------------------------------------------------------

class TestADMM:
    def test_logical_shards_match_owlqn(self, rng):
        """ADMM over 4 logical shards lands within 1e-5 relative objective
        of the resident OWL-QN optimum on an elastic-net logistic fit."""
        X, y = _make_xy(rng, n=256, d=10)
        reg = RegularizationContext.elastic_net(0.5)
        data = make_glm_data(X, y)
        ref_problem = _make_problem(reg=reg)
        grid = [0.3, 0.1]
        ref = {
            lam: np.asarray(m.coefficients.means)
            for lam, m, _ in ref_problem.run_grid(data, grid)
        }
        admm_problem = _make_problem(
            reg=reg, solver="admm",
            solver_options=(("reltol", "1e-6"), ("shards", "4")),
        )
        dist = shard_glm_data(X, y, None, n_shards=4)
        results = solvers_sharded.run_grid_sharded(
            admm_problem, dist, None, grid
        )
        for lam, model, res in results:
            w = np.asarray(model.coefficients.means)
            f_ref = _objective_value(ref_problem, data, ref[lam], lam)
            f_admm = _objective_value(ref_problem, data, w, lam)
            gap = abs(f_admm - f_ref) / max(1.0, abs(f_ref))
            assert gap <= 1e-5, f"λ={lam}: relative gap {gap:.2e}"
            assert bool(res.converged)

    def test_ridge_closed_form_path(self, rng):
        """Squared-loss task takes the cached-eigendecomposition x-update;
        the local L-BFGS path must agree with it (same consensus optimum)."""
        X, y = _make_xy(rng, n=200, d=6, task="linear")
        reg = RegularizationContext.elastic_net(0.5)
        data = make_glm_data(X, y)
        dist = shard_glm_data(X, y, None, n_shards=4)
        ws = {}
        for local in ("ridge", "lbfgs"):
            problem = _make_problem(
                task="linear", reg=reg, solver="admm",
                solver_options=(
                    ("reltol", "1e-6"), ("local_solver", local),
                    ("max_outer", "400"),
                ),
            )
            [(_, model, res)] = solvers_sharded.run_grid_sharded(
                problem, dist, None, [0.2]
            )
            assert bool(res.converged)
            ws[local] = np.asarray(model.coefficients.means)
        f_r = _objective_value(problem, data, ws["ridge"], 0.2)
        f_l = _objective_value(problem, data, ws["lbfgs"], 0.2)
        assert abs(f_r - f_l) / max(1.0, abs(f_l)) < 1e-5

    def test_mesh_matches_logical(self, rng, eight_devices):
        """The shard_map/psum step and the vmap/axis-sum step are the same
        math: an 8-device mesh solve must agree with 8 logical shards."""
        X, y = _make_xy(rng, n=256, d=6)
        reg = RegularizationContext.elastic_net(0.5)
        opts = (("reltol", "1e-6"),)
        mesh = data_mesh(eight_devices)
        problem = _make_problem(reg=reg, solver="admm", solver_options=opts)
        dist_mesh = shard_glm_data(X, y, mesh)
        [(_, m_mesh, _)] = solvers_sharded.run_grid_sharded(
            problem, dist_mesh, mesh, [0.2]
        )
        dist_log = shard_glm_data(X, y, None, n_shards=8)
        [(_, m_log, _)] = solvers_sharded.run_grid_sharded(
            problem, dist_log, None, [0.2]
        )
        # psum vs axis-0 sum reduce in different orders, so the runs are
        # close-not-bitwise; both must land on the same consensus optimum.
        np.testing.assert_allclose(
            np.asarray(m_mesh.coefficients.means),
            np.asarray(m_log.coefficients.means),
            rtol=0, atol=5e-4,
        )
        f_mesh = _objective_value(
            problem, make_glm_data(X, y), m_mesh.coefficients.means, 0.2
        )
        f_log = _objective_value(
            problem, make_glm_data(X, y), m_log.coefficients.means, 0.2
        )
        assert abs(f_mesh - f_log) / max(1.0, abs(f_log)) < 1e-5

    def test_option_validation(self):
        from photon_ml_tpu.solvers.admm import ADMMOptions

        with pytest.raises(ValueError, match="unknown admm solver_options"):
            ADMMOptions.from_options({"momentum": "0.9"})
        with pytest.raises(ValueError, match="over_relaxation"):
            ADMMOptions.from_options({"over_relaxation": "2.5"})
        with pytest.raises(ValueError, match="local_solver"):
            ADMMOptions.from_options({"local_solver": "newton"})

    def test_telemetry_counters(self, rng):
        X, y = _make_xy(rng, n=128, d=5)
        problem = _make_problem(
            reg=RegularizationContext.elastic_net(0.5), solver="admm"
        )
        dist = shard_glm_data(X, y, None, n_shards=2)
        tel = telemetry_mod.Telemetry(enabled=True, sinks=[])
        prev = telemetry_mod.set_current(tel)
        try:
            [(_, _, res)] = solvers_sharded.run_grid_sharded(
                problem, dist, None, [0.1]
            )
        finally:
            telemetry_mod.set_current(prev)
        rounds = int(res.iterations)
        assert rounds > 0
        assert tel.counter(
            "solver_outer_iterations_total"
        ).value == rounds
        # One reduce per outer round + the final exact evaluation.
        assert tel.counter("solver_allreduce_count").value == rounds + 1
        d = X.shape[1]
        assert tel.counter("solver_allreduce_bytes_total").value == (
            rounds * (2 * d + 4) * 4 + (d + 1) * 4
        )
        assert tel.counter("solvers_sharded_solves_total").value == 1
        assert tel.gauge("solver_consensus_residual").value >= 0.0


# ---------------------------------------------------------------------------
# Distributed block coordinate descent
# ---------------------------------------------------------------------------

class TestBlockCD:
    @pytest.mark.parametrize("reg", [
        RegularizationContext.l2(),
        RegularizationContext.elastic_net(0.5),
    ])
    def test_matches_resident_reference(self, rng, reg):
        """Drift-corrected block CD over 4 shards reaches the resident
        reference optimum (the correction's fixed point is EXACT global
        prox-stationarity, not the biased delta-averaging one)."""
        X, y = _make_xy(rng, n=240, d=9)
        data = make_glm_data(X, y)
        ref_problem = _make_problem(reg=reg)
        [(lam, ref_model, _)] = ref_problem.run_grid(data, [0.1])
        problem = _make_problem(
            reg=reg, solver="block_cd",
            solver_options=(
                ("n_blocks", "3"), ("sweeps", "2"),
                ("tolerance", "1e-10"), ("max_rounds", "400"),
            ),
        )
        dist = shard_glm_data(X, y, None, n_shards=4)
        [(_, model, res)] = solvers_sharded.run_grid_sharded(
            problem, dist, None, [0.1]
        )
        f_ref = _objective_value(
            ref_problem, data, ref_model.coefficients.means, lam
        )
        f_cd = _objective_value(
            ref_problem, data, model.coefficients.means, lam
        )
        gap = abs(f_cd - f_ref) / max(1.0, abs(f_ref))
        assert gap <= 1e-5, f"relative gap {gap:.2e}"

    def test_mesh_matches_logical(self, rng, eight_devices):
        X, y = _make_xy(rng, n=256, d=6)
        reg = RegularizationContext.elastic_net(0.5)
        opts = (("n_blocks", "2"), ("max_rounds", "50"))
        mesh = data_mesh(eight_devices)
        problem = _make_problem(
            reg=reg, solver="block_cd", solver_options=opts
        )
        dist_mesh = shard_glm_data(X, y, mesh)
        [(_, m_mesh, _)] = solvers_sharded.run_grid_sharded(
            problem, dist_mesh, mesh, [0.2]
        )
        dist_log = shard_glm_data(X, y, None, n_shards=8)
        [(_, m_log, _)] = solvers_sharded.run_grid_sharded(
            problem, dist_log, None, [0.2]
        )
        np.testing.assert_allclose(
            np.asarray(m_mesh.coefficients.means),
            np.asarray(m_log.coefficients.means),
            rtol=0, atol=5e-5,
        )

    def test_option_validation(self):
        from photon_ml_tpu.solvers.block_cd import BlockCDOptions

        with pytest.raises(ValueError, match="unknown block_cd"):
            BlockCDOptions.from_options({"rho": "1.0"})

    def test_dense_features_required(self, rng):
        import scipy.sparse as sp

        X, y = _make_xy(rng, n=100, d=6)
        problem = _make_problem(
            reg=RegularizationContext.l2(), solver="block_cd"
        )
        dist = shard_glm_data(sp.csr_matrix(X), y, None, n_shards=2)
        with pytest.raises(ValueError, match="[Dd]ense"):
            solvers_sharded.run_grid_sharded(problem, dist, None, [0.1])


# ---------------------------------------------------------------------------
# Sharded-data builders + grid runner guards
# ---------------------------------------------------------------------------

class TestShardedRunner:
    def test_jit_kind_rejected(self, rng):
        X, y = _make_xy(rng, n=80, d=4)
        dist = shard_glm_data(X, y, None, n_shards=2)
        with pytest.raises(ValueError, match="jit-kind"):
            solvers_sharded.run_grid_sharded(
                _make_problem(solver="lbfgs"), dist, None, [0.1]
            )

    def test_variances_rejected(self, rng):
        X, y = _make_xy(rng, n=80, d=4)
        dist = shard_glm_data(X, y, None, n_shards=2)
        problem = GlmOptimizationProblem("logistic", GlmOptimizationConfig(
            optimizer=OptimizerConfig(solver="admm"),
            regularization=RegularizationContext.l2(),
            compute_variances=True,
        ))
        with pytest.raises(ValueError, match="compute_variances"):
            solvers_sharded.run_grid_sharded(problem, dist, None, [0.1])

    def test_stack_resident_pads_with_zero_weight(self, rng):
        X, y = _make_xy(rng, n=103, d=5)  # 103 % 4 != 0 → padding
        data = make_glm_data(X, y)
        dist = solvers_sharded.stack_resident(data, 4)
        assert dist.n_shards == 4
        assert dist.data.labels.shape[0] == 4
        total = dist.data.labels.shape[0] * dist.data.labels.shape[1]
        pad = total - 103
        assert pad > 0
        flat_w = np.asarray(dist.data.weights).reshape(-1)
        assert np.all(flat_w[103:] == 0.0)

    def test_resolve_shard_count(self):
        opt = OptimizerConfig(solver="admm", solver_options=(("shards", "6"),))
        assert solvers_sharded.resolve_shard_count(opt) == 6
        assert solvers_sharded.resolve_shard_count(OptimizerConfig()) == 2


# ---------------------------------------------------------------------------
# Chaos: kill + bitwise resume at the new sites
# ---------------------------------------------------------------------------

class TestChaosKillResume:
    def _admm_setup(self, rng):
        X, y = _make_xy(rng, n=160, d=6)
        problem = _make_problem(
            reg=RegularizationContext.elastic_net(0.5), solver="admm",
            solver_options=(("reltol", "1e-4"),),
        )
        dist = shard_glm_data(X, y, None, n_shards=2)
        lams = [0.3, 0.1]
        return problem, dist, lams

    def test_consensus_kill_resumes_bitwise(self, rng, tmp_path):
        """Kill at the admm.consensus boundary mid-λ; the watchdog
        re-enters the grid through the GridCheckpointer and the resumed
        result must be bitwise identical to the uninterrupted run (the
        warm dual + every update is deterministic in the checkpointed
        warm start)."""
        problem, dist, lams = self._admm_setup(rng)
        full = solvers_sharded.run_grid_sharded(problem, dist, None, lams)
        ref = {lam: np.asarray(m.coefficients.means) for lam, m, _ in full}

        ckpt = GridCheckpointer(str(tmp_path / "admm"))
        plan = chaos.FaultPlan([
            chaos.FaultSpec(site="admm.consensus", at=3),
        ])

        def train(attempt):
            solved = ckpt.load() if attempt else {}
            acc = dict(solved)

            def on_solved(lam, w):
                acc[lam] = np.asarray(w)
                ckpt.save(acc)

            return solvers_sharded.run_grid_sharded(
                problem, dist, None, lams,
                solved=solved, on_solved=on_solved,
            )

        with plan:
            resumed = run_with_retries(
                train, RetryPolicy(max_retries=1), sleep=lambda s: None
            )
        assert len(plan.fired_at("admm.consensus")) == 1
        for lam, model, _ in resumed:
            assert _bitwise_equal(ref[lam], model.coefficients.means), (
                f"λ={lam}: resumed ADMM grid diverged"
            )

    def test_allreduce_kill_resumes_bitwise(self, rng, tmp_path):
        """Same bar at the distributed.allreduce seam (fires BEFORE the
        round's step program dispatches)."""
        problem, dist, lams = self._admm_setup(rng)
        full = solvers_sharded.run_grid_sharded(problem, dist, None, lams)
        ref = {lam: np.asarray(m.coefficients.means) for lam, m, _ in full}

        ckpt = GridCheckpointer(str(tmp_path / "ar"))
        plan = chaos.FaultPlan([
            chaos.FaultSpec(site="distributed.allreduce", at=5),
        ])

        def train(attempt):
            solved = ckpt.load() if attempt else {}
            acc = dict(solved)

            def on_solved(lam, w):
                acc[lam] = np.asarray(w)
                ckpt.save(acc)

            return solvers_sharded.run_grid_sharded(
                problem, dist, None, lams,
                solved=solved, on_solved=on_solved,
            )

        with plan:
            resumed = run_with_retries(
                train, RetryPolicy(max_retries=1), sleep=lambda s: None
            )
        assert len(plan.fired_at("distributed.allreduce")) == 1
        for lam, model, _ in resumed:
            assert _bitwise_equal(ref[lam], model.coefficients.means)

    def test_block_cd_allreduce_kill_resumes_bitwise(self, rng, tmp_path):
        X, y = _make_xy(rng, n=128, d=6)
        problem = _make_problem(
            reg=RegularizationContext.l2(), solver="block_cd",
            solver_options=(("n_blocks", "2"), ("max_rounds", "30")),
        )
        dist = shard_glm_data(X, y, None, n_shards=2)
        full = solvers_sharded.run_grid_sharded(problem, dist, None, [0.1])
        ref = np.asarray(full[0][1].coefficients.means)

        ckpt = GridCheckpointer(str(tmp_path / "cd"))
        plan = chaos.FaultPlan([
            chaos.FaultSpec(site="distributed.allreduce", at=2),
        ])

        def train(attempt):
            solved = ckpt.load() if attempt else {}
            return solvers_sharded.run_grid_sharded(
                problem, dist, None, [0.1],
                solved=solved,
                on_solved=lambda lam, w: ckpt.save({lam: np.asarray(w)}),
            )

        with plan:
            resumed = run_with_retries(
                train, RetryPolicy(max_retries=1), sleep=lambda s: None
            )
        assert _bitwise_equal(ref, resumed[0][1].coefficients.means)


# ---------------------------------------------------------------------------
# Streamed pass counters (satellite: existing solvers publish reduces)
# ---------------------------------------------------------------------------

class TestStreamedReduceCounter:
    def test_streamed_passes_counted(self, rng):
        """Every streamed objective pass is one logical all-reduce; the
        counter puts OWL-QN/L-BFGS on the same instrument as the
        distributed solvers."""
        from photon_ml_tpu.data.streaming import make_streaming_glm_data
        from photon_ml_tpu.optim.streaming import streaming_run_grid

        X, y = _make_xy(rng, n=128, d=6)
        stream = make_streaming_glm_data(X, y, chunk_rows=32)
        problem = _make_problem(reg=RegularizationContext.l2())
        tel = telemetry_mod.Telemetry(enabled=True, sinks=[])
        prev = telemetry_mod.set_current(tel)
        try:
            streaming_run_grid(problem, stream, [0.1])
        finally:
            telemetry_mod.set_current(prev)
        count = tel.counter("solver_allreduce_count").value
        assert count > 0
        # Each logical reduce moves (d+1) f32 partials per chunk batch.
        assert tel.counter("solver_allreduce_bytes_total").value >= (
            count * (X.shape[1] + 1) * 4
        )


# ---------------------------------------------------------------------------
# GAME integration: spec keys + host-kind fixed-effect trainer
# ---------------------------------------------------------------------------

class TestGameIntegration:
    def test_spec_solver_keys_parse(self):
        from photon_ml_tpu.drivers.game_training_driver import (
            parse_coordinate_config,
        )

        name, cfg = parse_coordinate_config({
            "name": "global",
            "type": "fixed",
            "feature_shard": "global",
            "solver": "admm",
            "solver_options": {"rho": "0.5", "shards": "2"},
            "reg_type": "elastic_net",
            "elastic_net_alpha": 0.5,
            "reg_weight": 0.1,
        })
        assert name == "global"
        assert cfg.optimization.optimizer.solver == "admm"
        assert dict(cfg.optimization.optimizer.solver_options) == {
            "rho": "0.5", "shards": "2"
        }

    def test_fixed_effect_trainer_matches_reference(self, rng):
        """make_fixed_effect_trainer (the GAME fixed-effect coordinate's
        host-kind path) reaches the resident optimum with re-slotted
        offsets."""
        X, y = _make_xy(rng, n=160, d=6)
        offsets = rng.normal(scale=0.3, size=160).astype(np.float32)
        reg = RegularizationContext.elastic_net(0.5)
        data = make_glm_data(X, y)
        problem = _make_problem(
            reg=reg, solver="admm", solver_options=(("reltol", "1e-6"),)
        )
        trainer = solvers_sharded.make_fixed_effect_trainer(
            problem, data, n_shards=2
        )
        w = trainer(offsets, jnp.zeros(6, jnp.float32), 0.1)

        ref_problem = _make_problem(reg=reg)
        data_off = dataclasses.replace(
            data, offsets=jnp.asarray(offsets)
        )
        ref = ref_problem.solve_single_device(data_off, 0.1)
        f_ref = _objective_value(ref_problem, data_off, ref.w, 0.1)
        f_admm = _objective_value(ref_problem, data_off, w, 0.1)
        assert abs(f_admm - f_ref) / max(1.0, abs(f_ref)) <= 1e-5
