"""Out-of-core streaming trainer vs the resident solvers.

The contract (VERDICT round 2, item 1): a chunked dataset must train to the
SAME solution as the resident path — the streamed pass is the reference's
``treeAggregate`` full-data scan rebuilt as a double-buffered device_put
stream (SURVEY.md §3.1, §7 "Host→device ingest bandwidth").
"""

import os

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

os.environ.setdefault("PHOTON_PALLAS_INTERPRET", "1")

from photon_ml_tpu.data.dataset import make_glm_data
from photon_ml_tpu.data.streaming import (
    StreamingGlmData,
    make_streaming_glm_data,
    streaming_from_blocks,
)
from photon_ml_tpu.optim.lbfgs import LBFGSConfig, lbfgs_solve
from photon_ml_tpu.optim.objective import GlmObjective
from photon_ml_tpu.optim.problem import (
    GlmOptimizationConfig,
    GlmOptimizationProblem,
    OptimizerConfig,
    OptimizerType,
)
from photon_ml_tpu.optim.regularization import RegularizationContext
from photon_ml_tpu.optim.streaming import (
    StreamingObjective,
    streaming_lbfgs_solve,
    streaming_run_grid,
)
from photon_ml_tpu.ops import losses


def _logistic_problem(rng, n, d, density=0.01, seed=3):
    X = sp.random(n, d, density=density, random_state=seed, format="csr",
                  dtype=np.float32)
    X = sp.hstack(
        [sp.csr_matrix(np.ones((n, 1), np.float32)), X]
    ).tocsr()
    w_true = (rng.normal(size=d + 1) *
              (rng.uniform(size=d + 1) < 0.3)).astype(np.float32)
    logits = X @ w_true
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-logits))).astype(
        np.float32
    )
    return X, y


class TestStreamingObjective:
    @pytest.mark.parametrize("accumulate", ["f32", "kahan"])
    def test_value_and_grad_matches_resident(self, rng, accumulate):
        n, d = 900, 40
        X, y = _logistic_problem(rng, n, d - 1, density=0.1)
        stream = make_streaming_glm_data(
            X, y, chunk_rows=256, use_pallas=False
        )
        assert stream.n_chunks == 4  # last chunk row-padded
        sobj = StreamingObjective("logistic", stream, accumulate=accumulate)
        data = make_glm_data(X, y)
        obj = GlmObjective(losses.logistic)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v_s, g_s = sobj.value_and_grad(w, l2_weight=0.5)
        v_r, g_r = obj.value_and_grad(w, data, l2_weight=0.5)
        assert float(jnp.abs(v_s - v_r)) < 1e-3 * max(1.0, abs(float(v_r)))
        assert float(jnp.abs(g_s - g_r).max()) < 1e-3

    def test_dense_features(self, rng):
        n, d = 300, 12
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        stream = make_streaming_glm_data(X, y, chunk_rows=128)
        sobj = StreamingObjective("logistic", stream)
        obj = GlmObjective(losses.logistic)
        data = make_glm_data(X, y)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v_s, g_s = sobj.value_and_grad(w)
        v_r, g_r = obj.value_and_grad(w, data)
        np.testing.assert_allclose(float(v_s), float(v_r), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(g_s), np.asarray(g_r), atol=1e-4
        )

    def test_scores_match_resident(self, rng):
        n, d = 500, 30
        X, y = _logistic_problem(rng, n, d - 1, density=0.1)
        stream = make_streaming_glm_data(
            X, y, chunk_rows=200, use_pallas=False
        )
        sobj = StreamingObjective("logistic", stream)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        scores = sobj.scores(w)
        assert scores.shape == (n,)
        np.testing.assert_allclose(
            scores, np.asarray(X @ np.asarray(w)).ravel(), atol=1e-4
        )

    def test_kahan_beats_f32_on_adversarial_stream(self, rng):
        """Many chunks of alternating huge/tiny contributions: compensated
        accumulation must track the f64 oracle much more tightly."""
        n, d = 4096, 4
        X = np.zeros((n, d), np.float32)
        X[:, 0] = 1.0
        y = np.zeros(n, np.float32)
        # Weights spanning 7 orders of magnitude force f32 cancellation
        # across the 32-chunk stream.
        w_rows = np.where(
            np.arange(n) % 2 == 0, 1e7, 1.0
        ).astype(np.float32)
        sq = make_streaming_glm_data(
            X, y, weights=w_rows, chunk_rows=128
        )
        w = jnp.asarray(np.array([1e-3, 0, 0, 0], np.float32))
        v32, _ = StreamingObjective(
            "linear", sq, accumulate="f32"
        ).value_and_grad(w)
        vk, _ = StreamingObjective(
            "linear", sq, accumulate="kahan"
        ).value_and_grad(w)
        # f64 oracle on host
        margins = (X @ np.asarray(w, np.float64))
        oracle = float(np.sum(
            w_rows.astype(np.float64) * 0.5 * margins**2
        ))
        err32 = abs(float(v32) - oracle)
        errk = abs(float(vk) - oracle)
        assert errk <= err32
        assert errk <= 1e-6 * abs(oracle) + 1e-6


class TestStreamingLBFGS:
    def test_matches_resident_solver(self, rng):
        n, d = 1200, 50
        X, y = _logistic_problem(rng, n, d - 1, density=0.1)
        data = make_glm_data(X, y)
        obj = GlmObjective(losses.logistic)
        cfg = LBFGSConfig(max_iters=200, tolerance=1e-9)
        res_r = lbfgs_solve(
            lambda w: obj.value_and_grad(w, data, l2_weight=1.0),
            jnp.zeros(d, jnp.float32), cfg,
        )
        stream = make_streaming_glm_data(
            X, y, chunk_rows=400, use_pallas=False
        )
        sobj = StreamingObjective("logistic", stream)
        res_s = streaming_lbfgs_solve(
            lambda w: sobj.value_and_grad(w, 1.0),
            jnp.zeros(d, jnp.float32), cfg,
        )
        # Same optimum to optimizer tolerance (summation order differs; the
        # converged FLAG may differ by one stalled step — host f64 vs device
        # f32 Armijo arithmetic — so the contract is the solution itself).
        np.testing.assert_allclose(
            float(res_s.value), float(res_r.value), rtol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(res_s.w), np.asarray(res_r.w), atol=5e-3
        )

    def test_single_chunk_mirrors_resident_trajectory(self, rng):
        """With ONE chunk the streamed solver runs the identical math; the
        per-iteration objective trace must match the resident solver
        closely, not just the endpoint."""
        n, d = 400, 20
        X, y = _logistic_problem(rng, n, d - 1, density=0.15)
        data = make_glm_data(X, y)
        obj = GlmObjective(losses.logistic)
        cfg = LBFGSConfig(max_iters=40, tolerance=1e-9)
        res_r = lbfgs_solve(
            lambda w: obj.value_and_grad(w, data, l2_weight=0.3),
            jnp.zeros(d, jnp.float32), cfg,
        )
        stream = make_streaming_glm_data(X, y, chunk_rows=n, use_pallas=False)
        sobj = StreamingObjective("logistic", stream)
        res_s = streaming_lbfgs_solve(
            lambda w: sobj.value_and_grad(w, 0.3),
            jnp.zeros(d, jnp.float32), cfg,
        )
        vr = np.asarray(res_r.values)
        vs = np.asarray(res_s.values)
        k = min(5, int(res_r.iterations), int(res_s.iterations))
        np.testing.assert_allclose(vs[: k + 1], vr[: k + 1], rtol=1e-4)


class TestStreamingPallasChunks:
    def test_pallas_chunks_match_coo_stream(self, rng):
        """Uniformized tiled layouts as chunk features: same objective as
        the COO chunk store (kernel parity through the streaming path)."""
        n, d = 700, 300
        X, y = _logistic_problem(rng, n, d - 1, density=0.05)
        s_coo = make_streaming_glm_data(
            X, y, chunk_rows=256, use_pallas=False
        )
        s_pal = make_streaming_glm_data(
            X, y, chunk_rows=256, use_pallas=True, depth_cap=16
        )
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v1, g1 = StreamingObjective("logistic", s_coo).value_and_grad(w)
        v2, g2 = StreamingObjective("logistic", s_pal).value_and_grad(w)
        np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(g1), np.asarray(g2), atol=1e-4
        )

    def test_sharded_pallas_chunks_match_coo_stream(self, rng):
        """Tiled Pallas layouts on SHARDED streams (VERDICT r3 #4): one
        per-shard layout each, uniformized across chunks × shards, stacked
        on the shard axis — the streamed-DP shard_map program must match
        the COO-layout stream bit-for-tolerance, offsets included."""
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
        n_dev = mesh.devices.size
        n, d = 700, 300
        X, y = _logistic_problem(rng, n, d - 1, density=0.05)
        offs = rng.normal(size=n).astype(np.float32)
        s_coo = make_streaming_glm_data(
            X, y, chunk_rows=256, use_pallas=False, n_shards=n_dev
        )
        s_pal = make_streaming_glm_data(
            X, y, chunk_rows=256, use_pallas=True, n_shards=n_dev,
            depth_cap=16,
        )
        assert s_pal.n_shards == n_dev
        o_coo = StreamingObjective("logistic", s_coo, mesh=mesh)
        o_pal = StreamingObjective("logistic", s_pal, mesh=mesh)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v1, g1 = o_coo.value_and_grad(w, 0.5, offsets=offs)
        v2, g2 = o_pal.value_and_grad(w, 0.5, offsets=offs)
        np.testing.assert_allclose(float(v2), float(v1), rtol=1e-4)
        np.testing.assert_allclose(np.asarray(g2), np.asarray(g1), atol=1e-3)
        v = jnp.asarray(rng.normal(size=d).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(o_pal.hvp(w, v, 0.5, offsets=offs)),
            np.asarray(o_coo.hvp(w, v, 0.5, offsets=offs)),
            atol=1e-3,
        )

    def test_dropped_host_coo_fails_loudly(self, rng):
        n, d = 300, 200
        X, y = _logistic_problem(rng, n, d - 1, density=0.05)
        s = make_streaming_glm_data(X, y, chunk_rows=128, use_pallas=True)
        with pytest.raises(RuntimeError, match="dropped"):
            s.chunks[0].features.host_coo.col_nnz()


class TestStreamingGrid:
    def test_grid_matches_resident_grid(self, rng):
        n, d = 800, 30
        X, y = _logistic_problem(rng, n, d - 1, density=0.1)
        problem = GlmOptimizationProblem(
            "logistic",
            GlmOptimizationConfig(
                optimizer=OptimizerConfig(max_iters=150, tolerance=1e-9),
                regularization=RegularizationContext.l2(),
            ),
        )
        lams = [0.5, 2.0]
        data = make_glm_data(X, y)
        grid_r = problem.run_grid(data, lams)
        stream = make_streaming_glm_data(
            X, y, chunk_rows=256, use_pallas=False
        )
        grid_s = streaming_run_grid(problem, stream, lams)
        for (lam_r, model_r, _), (lam_s, model_s, _) in zip(grid_r, grid_s):
            assert lam_r == lam_s
            np.testing.assert_allclose(
                np.asarray(model_s.coefficients.means),
                np.asarray(model_r.coefficients.means),
                atol=5e-3,
            )

    def test_variances_match_resident(self, rng):
        n, d = 400, 15
        X, y = _logistic_problem(rng, n, d - 1, density=0.2)
        problem = GlmOptimizationProblem(
            "logistic",
            GlmOptimizationConfig(
                optimizer=OptimizerConfig(max_iters=100, tolerance=1e-8),
                regularization=RegularizationContext.l2(),
                compute_variances=True,
            ),
        )
        data = make_glm_data(X, y)
        grid_r = problem.run_grid(data, [1.0])
        stream = make_streaming_glm_data(
            X, y, chunk_rows=128, use_pallas=False
        )
        grid_s = streaming_run_grid(problem, stream, [1.0])
        v_r = np.asarray(grid_r[0][1].coefficients.variances)
        v_s = np.asarray(grid_s[0][1].coefficients.variances)
        np.testing.assert_allclose(v_s, v_r, rtol=2e-2)

    def test_l1_grid_matches_resident(self, rng):
        """Streamed OWL-QN: L1 grid lands on the resident solution with
        the same sparsity pattern."""
        n, d = 700, 30
        X, y = _logistic_problem(rng, n, d - 1, density=0.15)
        problem = GlmOptimizationProblem(
            "logistic",
            GlmOptimizationConfig(
                optimizer=OptimizerConfig(max_iters=200, tolerance=1e-9),
                regularization=RegularizationContext.l1(),
            ),
        )
        data = make_glm_data(X, y)
        grid_r = problem.run_grid(data, [2.0])
        stream = make_streaming_glm_data(
            X, y, chunk_rows=256, use_pallas=False
        )
        grid_s = streaming_run_grid(problem, stream, [2.0])
        w_r = np.asarray(grid_r[0][1].coefficients.means)
        w_s = np.asarray(grid_s[0][1].coefficients.means)
        np.testing.assert_allclose(w_s, w_r, atol=5e-3)
        # L1 must actually sparsify, identically on both paths.
        assert np.sum(w_r == 0.0) > d // 4
        np.testing.assert_array_equal(w_s == 0.0, w_r == 0.0)

    def test_tron_grid_matches_resident(self, rng):
        """Smooth TRON streams (VERDICT r3 #2: the last optimizer ×
        residency cell): the streamed grid lands on the resident TRON
        solution."""
        n, d = 800, 30
        X, y = _logistic_problem(rng, n, d - 1, density=0.1)
        problem = GlmOptimizationProblem(
            "logistic",
            GlmOptimizationConfig(
                optimizer=OptimizerConfig(
                    optimizer=OptimizerType.TRON,
                    max_iters=100,
                    tolerance=1e-8,
                ),
                regularization=RegularizationContext.l2(),
            ),
        )
        lams = [0.5, 2.0]
        data = make_glm_data(X, y)
        grid_r = problem.run_grid(data, lams)
        stream = make_streaming_glm_data(
            X, y, chunk_rows=256, use_pallas=False
        )
        grid_s = streaming_run_grid(problem, stream, lams)
        for (lam_r, model_r, _), (lam_s, model_s, _) in zip(grid_r, grid_s):
            assert lam_r == lam_s
            np.testing.assert_allclose(
                np.asarray(model_s.coefficients.means),
                np.asarray(model_r.coefficients.means),
                atol=5e-3,
            )


class TestStreamingNormalization:
    def test_normalized_objective_matches_resident(self, rng):
        """NormalizationContext composes with the streamed objective the
        same way it does resident: value/grad/HVP parity under a
        standardization context (the reference applies normalization
        inside the optimizer against unscaled data — SURVEY.md §2)."""
        from photon_ml_tpu.data.normalization import (
            NormalizationContext,
            NormalizationType,
            build_normalization,
        )
        from photon_ml_tpu.data.stats import summarize

        n, d = 600, 20
        X, y = _logistic_problem(rng, n, d - 1, density=0.2)
        data = make_glm_data(X, y)
        norm = build_normalization(
            NormalizationType.STANDARDIZATION, summarize(data),
            intercept_index=0,
        )
        obj = GlmObjective(losses.logistic, norm)
        stream = make_streaming_glm_data(
            X, y, chunk_rows=200, use_pallas=False
        )
        sobj = StreamingObjective(obj, stream)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v_r, g_r = obj.value_and_grad(w, data, l2_weight=0.5)
        v_s, g_s = sobj.value_and_grad(w, 0.5)
        np.testing.assert_allclose(float(v_s), float(v_r), rtol=1e-4)
        np.testing.assert_allclose(np.asarray(g_s), np.asarray(g_r),
                                   atol=1e-3)
        vv = jnp.asarray(rng.normal(size=d).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(sobj.hvp(w, vv, 0.5)),
            np.asarray(obj.hvp(w, vv, data, l2_weight=0.5)),
            atol=1e-3,
        )


class TestStreamingTRON:
    def test_hvp_matches_resident(self, rng):
        """One streamed HVP pass == the resident Hessian-vector product
        (the HessianVectorAggregator treeAggregate analogue)."""
        n, d = 900, 40
        X, y = _logistic_problem(rng, n, d - 1, density=0.1)
        data = make_glm_data(X, y)
        obj = GlmObjective(losses.logistic)
        stream = make_streaming_glm_data(
            X, y, chunk_rows=256, use_pallas=False
        )
        sobj = StreamingObjective("logistic", stream)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v = jnp.asarray(rng.normal(size=d).astype(np.float32))
        h_r = obj.hvp(w, v, data, l2_weight=0.7)
        h_s = sobj.hvp(w, v, l2_weight=0.7)
        np.testing.assert_allclose(
            np.asarray(h_s), np.asarray(h_r), atol=1e-3
        )
        # The kahan accumulator must carry through the HVP pass too (its
        # compensation pair changes the carry structure, not the result).
        h_k = StreamingObjective(
            "logistic", stream, accumulate="kahan"
        ).hvp(w, v, l2_weight=0.7)
        np.testing.assert_allclose(
            np.asarray(h_k), np.asarray(h_r), atol=1e-3
        )

    def test_single_chunk_mirrors_resident_trajectory(self, rng):
        """With ONE chunk the streamed trust-region solver runs identical
        math (same radius updates, same CG, same acceptance): the
        per-iteration objective trace must track the resident solver."""
        from photon_ml_tpu.optim.streaming import streaming_tron_solve
        from photon_ml_tpu.optim.tron import TRONConfig, tron_solve

        n, d = 400, 20
        X, y = _logistic_problem(rng, n, d - 1, density=0.15)
        data = make_glm_data(X, y)
        obj = GlmObjective(losses.logistic)
        cfg = TRONConfig(max_iters=40, tolerance=1e-9)
        res_r = tron_solve(
            lambda w: obj.value_and_grad(w, data, l2_weight=0.3),
            lambda w, v, aux: obj.hvp(w, v, data, l2_weight=0.3, d2w=aux),
            jnp.zeros(d, jnp.float32),
            cfg,
            d2_fn=lambda w: obj.d2_weights(w, data),
        )
        stream = make_streaming_glm_data(X, y, chunk_rows=n, use_pallas=False)
        sobj = StreamingObjective("logistic", stream)
        res_s = streaming_tron_solve(
            lambda w: sobj.value_and_grad(w, 0.3),
            lambda w, v: sobj.hvp(w, v, 0.3),
            jnp.zeros(d, jnp.float32),
            cfg,
        )
        vr = np.asarray(res_r.values)
        vs = np.asarray(res_s.values)
        k = min(5, int(res_r.iterations), int(res_s.iterations))
        np.testing.assert_allclose(vs[: k + 1], vr[: k + 1], rtol=1e-4)
        np.testing.assert_allclose(
            float(res_s.value), float(res_r.value), rtol=1e-5
        )

    def test_multi_chunk_matches_resident_solution(self, rng):
        from photon_ml_tpu.optim.streaming import streaming_tron_solve
        from photon_ml_tpu.optim.tron import TRONConfig, tron_solve

        n, d = 1200, 50
        X, y = _logistic_problem(rng, n, d - 1, density=0.1)
        data = make_glm_data(X, y)
        obj = GlmObjective(losses.logistic)
        cfg = TRONConfig(max_iters=100, tolerance=1e-9)
        res_r = tron_solve(
            lambda w: obj.value_and_grad(w, data, l2_weight=1.0),
            lambda w, v, aux: obj.hvp(w, v, data, l2_weight=1.0, d2w=aux),
            jnp.zeros(d, jnp.float32),
            cfg,
            d2_fn=lambda w: obj.d2_weights(w, data),
        )
        stream = make_streaming_glm_data(
            X, y, chunk_rows=400, use_pallas=False
        )
        sobj = StreamingObjective("logistic", stream)
        res_s = streaming_tron_solve(
            lambda w: sobj.value_and_grad(w, 1.0),
            lambda w, v: sobj.hvp(w, v, 1.0),
            jnp.zeros(d, jnp.float32),
            cfg,
        )
        np.testing.assert_allclose(
            float(res_s.value), float(res_r.value), rtol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(res_s.w), np.asarray(res_r.w), atol=5e-3
        )

    def test_tron_l1_still_routes_to_owlqn(self, rng):
        """A TRON config carrying L1 routes to streamed OWL-QN (static
        routing parity with the resident problem.solve)."""
        n, d = 400, 20
        X, y = _logistic_problem(rng, n, d - 1, density=0.15)
        problem = GlmOptimizationProblem(
            "logistic",
            GlmOptimizationConfig(
                optimizer=OptimizerConfig(
                    optimizer=OptimizerType.TRON,
                    max_iters=150,
                    tolerance=1e-9,
                ),
                regularization=RegularizationContext.elastic_net(0.5),
            ),
        )
        data = make_glm_data(X, y)
        grid_r = problem.run_grid(data, [1.0])
        stream = make_streaming_glm_data(
            X, y, chunk_rows=128, use_pallas=False
        )
        grid_s = streaming_run_grid(problem, stream, [1.0])
        w_r = np.asarray(grid_r[0][1].coefficients.means)
        w_s = np.asarray(grid_s[0][1].coefficients.means)
        np.testing.assert_allclose(w_s, w_r, atol=5e-3)
        np.testing.assert_array_equal(w_s == 0.0, w_r == 0.0)


class TestStreamingDataParallel:
    def test_sharded_stream_matches_single_device(self, rng):
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
        n_dev = mesh.devices.size
        n, d = 960, 25
        X, y = _logistic_problem(rng, n, d - 1, density=0.1)
        stream1 = make_streaming_glm_data(
            X, y, chunk_rows=320, use_pallas=False
        )
        streamN = make_streaming_glm_data(
            X, y, chunk_rows=320, use_pallas=False, n_shards=n_dev
        )
        sobj1 = StreamingObjective("logistic", stream1)
        sobjN = StreamingObjective("logistic", streamN, mesh=mesh)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v1, g1 = sobj1.value_and_grad(w, 0.7)
        vN, gN = sobjN.value_and_grad(w, 0.7)
        np.testing.assert_allclose(float(vN), float(v1), rtol=1e-4)
        np.testing.assert_allclose(
            np.asarray(gN), np.asarray(g1), atol=1e-3
        )
        # HVP parity under the mesh too (streamed-DP TRON's inner pass).
        v = jnp.asarray(rng.normal(size=d).astype(np.float32))
        h1 = sobj1.hvp(w, v, 0.7)
        hN = sobjN.hvp(w, v, 0.7)
        np.testing.assert_allclose(
            np.asarray(hN), np.asarray(h1), atol=1e-3
        )

    def test_sharded_grid_fit(self, rng):
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
        n, d = 640, 20
        X, y = _logistic_problem(rng, n, d - 1, density=0.15)
        problem = GlmOptimizationProblem(
            "logistic",
            GlmOptimizationConfig(
                optimizer=OptimizerConfig(max_iters=120, tolerance=1e-9),
                regularization=RegularizationContext.l2(),
            ),
        )
        data = make_glm_data(X, y)
        grid_r = problem.run_grid(data, [1.0])
        streamN = make_streaming_glm_data(
            X, y, chunk_rows=160, use_pallas=False,
            n_shards=mesh.devices.size,
        )
        grid_s = streaming_run_grid(problem, streamN, [1.0], mesh=mesh)
        np.testing.assert_allclose(
            np.asarray(grid_s[0][1].coefficients.means),
            np.asarray(grid_r[0][1].coefficients.means),
            atol=5e-3,
        )

    def test_sharded_row_offsets_match_single_device(self, rng):
        """Per-row CD offsets under the mesh (VERDICT r3 #3): each chunk's
        offset slice rides SHARDED next to the chunk, and value/grad/HVP/
        hessian-diagonal all match the single-device streamed pass."""
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
        n_dev = mesh.devices.size
        n, d = 960, 25
        X, y = _logistic_problem(rng, n, d - 1, density=0.1)
        offs = rng.normal(size=n).astype(np.float32)
        stream1 = make_streaming_glm_data(
            X, y, chunk_rows=320, use_pallas=False
        )
        streamN = make_streaming_glm_data(
            X, y, chunk_rows=320, use_pallas=False, n_shards=n_dev
        )
        sobj1 = StreamingObjective("logistic", stream1)
        sobjN = StreamingObjective("logistic", streamN, mesh=mesh)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v1, g1 = sobj1.value_and_grad(w, 0.5, offsets=offs)
        vN, gN = sobjN.value_and_grad(w, 0.5, offsets=offs)
        np.testing.assert_allclose(float(vN), float(v1), rtol=1e-4)
        np.testing.assert_allclose(np.asarray(gN), np.asarray(g1), atol=1e-3)
        v = jnp.asarray(rng.normal(size=d).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(sobjN.hvp(w, v, 0.5, offsets=offs)),
            np.asarray(sobj1.hvp(w, v, 0.5, offsets=offs)),
            atol=1e-3,
        )
        np.testing.assert_allclose(
            np.asarray(sobjN.hessian_diagonal(w, offsets=offs)),
            np.asarray(sobj1.hessian_diagonal(w, offsets=offs)),
            atol=1e-3,
        )

    def test_streamed_game_cd_on_mesh(self, rng):
        """BASELINE config 5's minimum viable shape: streaming AND
        multi-device AND GAME simultaneously — a mesh-sharded streamed
        fixed effect composed with a resident random effect in one
        coordinate descent, matching the single-device streamed run."""
        from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
        from photon_ml_tpu.game.data import build_random_effect_dataset
        from photon_ml_tpu.game.descent import CoordinateDescent
        from photon_ml_tpu.game.streaming import (
            StreamingFixedEffectCoordinate,
        )

        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
        n_dev = mesh.devices.size
        n, d, n_users = 640, 16, 12
        X = sp.random(n, d, density=0.15, random_state=9, format="csr",
                      dtype=np.float32)
        users = np.array(
            [f"u{rng.integers(n_users)}" for _ in range(n)], dtype=object
        )
        margin = X @ rng.normal(size=d).astype(np.float32)
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(
            np.float32
        )
        bias = sp.csr_matrix(np.ones((n, 1), np.float32))
        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=50, tolerance=1e-8),
            regularization=RegularizationContext.l2(),
        )

        def run_cd(fixed_coord):
            re = RandomEffectCoordinate(
                "per_user",
                build_random_effect_dataset(
                    users, bias, y, np.ones(n, np.float32)
                ),
                "logistic", opt, reg_weight=1.0, entity_key="userId",
            )
            return CoordinateDescent([fixed_coord, re]).run(
                jnp.zeros(n, jnp.float32), n_iterations=2
            )

        stream1 = make_streaming_glm_data(
            X, y, chunk_rows=160, use_pallas=False
        )
        streamN = make_streaming_glm_data(
            X, y, chunk_rows=160, use_pallas=False, n_shards=n_dev
        )
        single = run_cd(StreamingFixedEffectCoordinate(
            "fixed", stream1, "logistic", opt, reg_weight=0.5,
        ))
        meshed = run_cd(StreamingFixedEffectCoordinate(
            "fixed", streamN, "logistic", opt, reg_weight=0.5, mesh=mesh,
        ))
        np.testing.assert_allclose(
            np.asarray(meshed.states["fixed"]),
            np.asarray(single.states["fixed"]),
            atol=5e-3,
        )
        np.testing.assert_allclose(
            np.asarray(meshed.scores["fixed"]),
            np.asarray(single.scores["fixed"]),
            atol=5e-3,
        )
        # Downstream coordinate: trained against the streamed-DP scores,
        # so psum-order f32 drift compounds once more — slightly looser.
        for b_m, b_s in zip(
            meshed.states["per_user"], single.states["per_user"]
        ):
            np.testing.assert_allclose(
                np.asarray(b_m), np.asarray(b_s), atol=1e-2
            )

    def test_estimator_mesh_plus_streaming(self, rng):
        """GameEstimator accepts mesh + streaming_chunk_rows together now
        (the round-3 rejection at game/estimator.py:198 is lifted)."""
        from photon_ml_tpu.game.estimator import (
            FixedEffectCoordinateConfig,
            GameEstimator,
            RandomEffectCoordinateConfig,
        )

        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
        n, d, n_users = 512, 12, 10
        X = sp.random(n, d, density=0.2, random_state=3, format="csr",
                      dtype=np.float32)
        users = np.array(
            [f"u{rng.integers(n_users)}" for _ in range(n)], dtype=object
        )
        margin = X @ rng.normal(size=d).astype(np.float32)
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(
            np.float32
        )
        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=40, tolerance=1e-7),
            regularization=RegularizationContext.l2(),
        )
        configs = {
            "fixed": FixedEffectCoordinateConfig(
                feature_shard="global", optimization=opt, reg_weight=0.5,
                streaming_chunk_rows=128,
            ),
            "per_user": RandomEffectCoordinateConfig(
                feature_shard="global", entity_key="userId",
                optimization=opt, reg_weight=1.0,
            ),
        }
        shards = {"global": X}
        ids = {"userId": users}

        fit_m = GameEstimator(
            "logistic", configs, n_iterations=2, mesh=mesh
        ).fit(shards, ids, y)
        fit_1 = GameEstimator(
            "logistic", configs, n_iterations=2
        ).fit(shards, ids, y)
        w_m = np.asarray(
            fit_m[0].models["fixed"].model.coefficients.means
        )
        w_1 = np.asarray(
            fit_1[0].models["fixed"].model.coefficients.means
        )
        np.testing.assert_allclose(w_m, w_1, atol=5e-3)


class TestStreamingMeshGuards:
    def test_one_device_mesh_rejected(self, rng):
        """Single-shard chunks carry no shard axis; the mesh path's x[0]
        unstack would strip a DATA axis and silently return wrong
        values/gradients — construction must refuse loudly instead."""
        mesh1 = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
        X, y = _logistic_problem(rng, 100, 10)
        stream = make_streaming_glm_data(
            X, y, chunk_rows=64, use_pallas=False
        )
        with pytest.raises(ValueError, match="no shard axis"):
            StreamingObjective("logistic", stream, mesh=mesh1)


class TestChunkStoreShapes:
    def test_uniform_chunk_shapes(self, rng):
        X, y = _logistic_problem(rng, 1000, 64)
        stream = make_streaming_glm_data(
            X, y, chunk_rows=300, use_pallas=False
        )
        assert stream.n_chunks == 4
        shapes = [
            [leaf.shape for leaf in jax.tree.leaves(c)]
            for c in stream.chunks
        ]
        assert all(s == shapes[0] for s in shapes)
        # weight padding: total weight equals real row count
        assert stream.weight_sum == pytest.approx(1000.0)
        assert stream.nbytes() > 0

    def test_from_blocks(self, rng):
        X, y = _logistic_problem(rng, 500, 32)
        blocks = [
            (X[i * 100:(i + 1) * 100], y[i * 100:(i + 1) * 100])
            for i in range(5)
        ]
        stream = streaming_from_blocks(
            blocks, n_features=X.shape[1], chunk_rows=150, use_pallas=False
        )
        assert stream.n_rows == 500
        sobj = StreamingObjective("logistic", stream)
        data = make_glm_data(X, y)
        obj = GlmObjective(losses.logistic)
        w = jnp.asarray(rng.normal(size=X.shape[1]).astype(np.float32))
        v_s, _ = sobj.value_and_grad(w)
        v_r, _ = obj.value_and_grad(w, data)
        np.testing.assert_allclose(float(v_s), float(v_r), rtol=1e-5)


class TestStreamingGameCoordinate:
    """StreamingFixedEffectCoordinate inside coordinate descent: same
    result as the resident fixed effect, composed with a random effect."""

    def _game_problem(self, rng, n=600, d=20, n_users=15):
        X = sp.random(n, d, density=0.15, random_state=7, format="csr",
                      dtype=np.float32)
        users = np.array(
            [f"u{rng.integers(n_users)}" for _ in range(n)], dtype=object
        )
        user_eff = {f"u{u}": rng.normal() for u in range(n_users)}
        w_true = rng.normal(size=d).astype(np.float32)
        margin = X @ w_true + np.array([user_eff[u] for u in users])
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(
            np.float32
        )
        return X, users, y

    def test_cd_matches_resident_fixed_effect(self, rng):
        import jax.numpy as jnp

        from photon_ml_tpu.game.coordinates import (
            FixedEffectCoordinate,
            RandomEffectCoordinate,
        )
        from photon_ml_tpu.game.data import (
            FixedEffectDataset,
            build_random_effect_dataset,
        )
        from photon_ml_tpu.game.descent import CoordinateDescent
        from photon_ml_tpu.game.streaming import (
            StreamingFixedEffectCoordinate,
        )
        from photon_ml_tpu.optim.problem import (
            GlmOptimizationConfig,
            OptimizerConfig,
        )
        from photon_ml_tpu.optim.regularization import RegularizationContext

        X, users, y = self._game_problem(rng)
        n, d = X.shape
        bias = sp.csr_matrix(np.ones((n, 1), np.float32))
        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=50, tolerance=1e-8),
            regularization=RegularizationContext.l2(),
        )

        def run_cd(fixed_coord):
            re = RandomEffectCoordinate(
                "per_user",
                build_random_effect_dataset(
                    users, bias, y, np.ones(n, np.float32)
                ),
                "logistic", opt, reg_weight=1.0, entity_key="userId",
            )
            return CoordinateDescent([fixed_coord, re]).run(
                jnp.zeros(n, jnp.float32), n_iterations=2
            )

        resident = run_cd(FixedEffectCoordinate(
            "fixed",
            FixedEffectDataset(data=make_glm_data(X, y), n_global_rows=n),
            "logistic", opt, reg_weight=0.5,
        ))
        stream = make_streaming_glm_data(
            X, y, chunk_rows=200, use_pallas=False
        )
        streamed = run_cd(StreamingFixedEffectCoordinate(
            "fixed", stream, "logistic", opt, reg_weight=0.5,
        ))

        np.testing.assert_allclose(
            np.asarray(streamed.states["fixed"]),
            np.asarray(resident.states["fixed"]),
            atol=5e-3,
        )
        np.testing.assert_allclose(
            np.asarray(streamed.scores["fixed"]),
            np.asarray(resident.scores["fixed"]),
            atol=5e-3,
        )
        # The OTHER coordinate's solution must agree too (it trains
        # against the streamed coordinate's scores).
        for b_s, b_r in zip(
            streamed.states["per_user"], resident.states["per_user"]
        ):
            np.testing.assert_allclose(
                np.asarray(b_s), np.asarray(b_r), atol=5e-3
            )

    def test_finalize_variances_and_model(self, rng):
        import jax.numpy as jnp

        from photon_ml_tpu.game.streaming import (
            StreamingFixedEffectCoordinate,
        )
        from photon_ml_tpu.optim.problem import (
            GlmOptimizationConfig,
            OptimizerConfig,
        )
        from photon_ml_tpu.optim.regularization import RegularizationContext

        X, _, y = self._game_problem(rng, n=300, d=10)
        stream = make_streaming_glm_data(
            X, y, chunk_rows=128, use_pallas=False
        )
        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=40, tolerance=1e-7),
            regularization=RegularizationContext.l2(),
            compute_variances=True,
        )
        coord = StreamingFixedEffectCoordinate(
            "fixed", stream, "logistic", opt, reg_weight=1.0,
        )
        offsets = jnp.zeros(stream.n_rows, jnp.float32)
        w = coord.train(offsets)
        model = coord.finalize(w, offsets=offsets)
        assert model.model.task == "logistic"
        v = np.asarray(model.model.coefficients.variances)
        assert v.shape == (10,) and np.all(v > 0)

    def test_nonzero_chunk_offsets_rejected(self, rng):
        from photon_ml_tpu.game.streaming import (
            StreamingFixedEffectCoordinate,
        )
        from photon_ml_tpu.optim.problem import GlmOptimizationConfig

        X, _, y = self._game_problem(rng, n=200, d=8)
        stream = make_streaming_glm_data(
            X, y, offsets=np.ones(X.shape[0], np.float32),
            chunk_rows=100, use_pallas=False,
        )
        with pytest.raises(ValueError, match="zero offsets"):
            StreamingFixedEffectCoordinate(
                "fixed", stream, "logistic", GlmOptimizationConfig(),
            )

    def test_streamed_game_l1_fixed_effect(self, rng):
        """L1 on the STREAMED GAME fixed effect inside coordinate descent:
        same solution and sparsity pattern as the resident coordinate
        (exercises OWL-QN's orthant-projected trials against per-chunk
        CD offsets and the l1 = l1_frac * reg_weight scaling)."""
        import jax.numpy as jnp

        from photon_ml_tpu.game.coordinates import (
            FixedEffectCoordinate,
            RandomEffectCoordinate,
        )
        from photon_ml_tpu.game.data import (
            FixedEffectDataset,
            build_random_effect_dataset,
        )
        from photon_ml_tpu.game.descent import CoordinateDescent
        from photon_ml_tpu.game.streaming import (
            StreamingFixedEffectCoordinate,
        )
        from photon_ml_tpu.optim.problem import (
            GlmOptimizationConfig,
            OptimizerConfig,
        )
        from photon_ml_tpu.optim.regularization import RegularizationContext

        X, users, y = self._game_problem(rng, n=500, d=30)
        n, d = X.shape
        bias = sp.csr_matrix(np.ones((n, 1), np.float32))
        l1_opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=60, tolerance=1e-8),
            regularization=RegularizationContext.l1(),
        )
        re_opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=30, tolerance=1e-7),
            regularization=RegularizationContext.l2(),
        )

        def run_cd(fixed_coord):
            re = RandomEffectCoordinate(
                "per_user",
                build_random_effect_dataset(
                    users, bias, y, np.ones(n, np.float32)
                ),
                "logistic", re_opt, reg_weight=1.0, entity_key="userId",
            )
            return CoordinateDescent([fixed_coord, re]).run(
                jnp.zeros(n, jnp.float32), n_iterations=2
            )

        resident = run_cd(FixedEffectCoordinate(
            "fixed",
            FixedEffectDataset(data=make_glm_data(X, y), n_global_rows=n),
            "logistic", l1_opt, reg_weight=2.0,
        ))
        stream = make_streaming_glm_data(
            X, y, chunk_rows=180, use_pallas=False
        )
        streamed = run_cd(StreamingFixedEffectCoordinate(
            "fixed", stream, "logistic", l1_opt, reg_weight=2.0,
        ))
        w_r = np.asarray(resident.states["fixed"])
        w_s = np.asarray(streamed.states["fixed"])
        assert np.sum(w_r == 0.0) > 0  # the penalty actually pruned
        np.testing.assert_allclose(w_s, w_r, atol=5e-3)
        np.testing.assert_array_equal(w_s == 0.0, w_r == 0.0)

    def test_streamed_game_tron_fixed_effect(self, rng):
        """Smooth TRON on the STREAMED GAME fixed effect: exercises the
        streamed HVP against per-chunk CD offsets (the d2 weights depend
        on the other coordinates' scores through the margin)."""
        import jax.numpy as jnp

        from photon_ml_tpu.game.coordinates import (
            FixedEffectCoordinate,
            RandomEffectCoordinate,
        )
        from photon_ml_tpu.game.data import (
            FixedEffectDataset,
            build_random_effect_dataset,
        )
        from photon_ml_tpu.game.descent import CoordinateDescent
        from photon_ml_tpu.game.streaming import (
            StreamingFixedEffectCoordinate,
        )
        from photon_ml_tpu.optim.problem import (
            GlmOptimizationConfig,
            OptimizerConfig,
        )
        from photon_ml_tpu.optim.regularization import RegularizationContext

        X, users, y = self._game_problem(rng, n=500, d=20)
        n, d = X.shape
        bias = sp.csr_matrix(np.ones((n, 1), np.float32))
        tron_opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(
                optimizer=OptimizerType.TRON, max_iters=50, tolerance=1e-8
            ),
            regularization=RegularizationContext.l2(),
        )
        re_opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=30, tolerance=1e-7),
            regularization=RegularizationContext.l2(),
        )

        def run_cd(fixed_coord):
            re = RandomEffectCoordinate(
                "per_user",
                build_random_effect_dataset(
                    users, bias, y, np.ones(n, np.float32)
                ),
                "logistic", re_opt, reg_weight=1.0, entity_key="userId",
            )
            return CoordinateDescent([fixed_coord, re]).run(
                jnp.zeros(n, jnp.float32), n_iterations=2
            )

        resident = run_cd(FixedEffectCoordinate(
            "fixed",
            FixedEffectDataset(data=make_glm_data(X, y), n_global_rows=n),
            "logistic", tron_opt, reg_weight=0.5,
        ))
        stream = make_streaming_glm_data(
            X, y, chunk_rows=180, use_pallas=False
        )
        streamed = run_cd(StreamingFixedEffectCoordinate(
            "fixed", stream, "logistic", tron_opt, reg_weight=0.5,
        ))
        np.testing.assert_allclose(
            np.asarray(streamed.states["fixed"]),
            np.asarray(resident.states["fixed"]),
            atol=5e-3,
        )


class TestDoubleBufferStructure:
    """VERDICT r3 weak #3: the overlap claim, pinned by structure instead
    of arithmetic.  Rewritten for the windowed-async pipeline: the
    consumer dispatches chunk k's program and blocks only on the carry a
    ``prefetch_depth``-deep WINDOW behind, so (a) the number of blocking
    syncs per pass is ``n_chunks - window + 1`` (each carry synced once,
    plus the drain), (b) transfer k+1 is never gated on compute k's sync
    (the pin is a handshake: every sync WAITS for the next transfer to
    have been dispatched — deadlock-free exactly when the transfer
    thread is not gated on that sync), and (c) HBM liveness stays
    bounded by ``2·prefetch_depth`` chunks (``prefetch_depth``
    transferred-not-consumed by permit accounting + the window of
    dispatched-not-synced programs pinning their buffers)."""

    def test_transfer_overlaps_compute_and_hbm_bound(
        self, rng, monkeypatch
    ):
        import gc
        import threading
        import weakref

        n, d = 600, 10
        X, y = _logistic_problem(rng, n, d - 1, density=0.2)
        stream = make_streaming_glm_data(
            X, y, chunk_rows=100, use_pallas=False
        )
        assert stream.n_chunks == 6
        n_chunks = stream.n_chunks
        depth = 2
        sobj = StreamingObjective("logistic", stream, prefetch_depth=depth)

        put_done = [threading.Event() for _ in range(n_chunks)]
        live_refs = []
        hbm_violations = []
        orig_put = sobj._put
        put_idx = [0]

        def tracked_put(chunk):
            k = put_idx[0]
            put_idx[0] += 1
            dev = orig_put(chunk)
            live_refs.append(weakref.ref(jax.tree.leaves(dev)[0]))
            # HBM-residency bound: at the moment chunk k lands, the
            # permit-held transfers (≤ depth) plus the window of
            # dispatched-but-unsynced programs (≤ depth) may pin chunk
            # buffers.  (Recorded, not asserted: this runs on the
            # transfer thread.)
            gc.collect()
            alive = sum(1 for r in live_refs if r() is not None)
            if alive > 2 * depth:
                hbm_violations.append((k, alive))
            put_done[k].set()
            return dev

        monkeypatch.setattr(sobj, "_put", tracked_put)

        orig_block = jax.block_until_ready
        block_count = [0]

        def tracked_block(x):
            block_count[0] += 1
            # Syncs run a window of ``depth`` carries behind dispatch, so
            # by the time ANY sync runs, the transfer thread must have
            # been able to dispatch at least the next chunk without it —
            # if the pipeline ever serialized transfer k+1 behind
            # compute k's sync, this wait could only time out.
            k_ahead = min(
                block_count[0] - 1 + depth + 1, n_chunks - 1
            )
            assert put_done[k_ahead].wait(timeout=60.0), (
                f"transfer {k_ahead} was not dispatched while an "
                f"earlier compute sync was still pending — no overlap"
            )
            return orig_block(x)

        monkeypatch.setattr(jax, "block_until_ready", tracked_block)

        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        v, g = sobj.value_and_grad(w, 0.3)
        monkeypatch.undo()
        assert np.isfinite(float(v))
        assert put_idx[0] == n_chunks
        # Windowed backpressure: one blocking sync per chunk beyond the
        # window, plus the end-of-pass drain.
        assert block_count[0] == n_chunks - depth + 1
        assert not hbm_violations, (
            f"chunks alive in device memory beyond the pipeline bound: "
            f"{hbm_violations}"
        )
        assert sobj.transfer_stats.max_live <= depth

    def test_depth_one_syncs_every_chunk(self, rng, monkeypatch):
        """prefetch_depth=1 is the fully-serial measurement baseline:
        window 0, one blocking sync per chunk."""
        n, d = 400, 8
        X, y = _logistic_problem(rng, n, d - 1, density=0.2)
        stream = make_streaming_glm_data(
            X, y, chunk_rows=100, use_pallas=False
        )
        sobj = StreamingObjective("logistic", stream, prefetch_depth=1)
        orig_block = jax.block_until_ready
        count = [0]

        def tracked(x):
            count[0] += 1
            return orig_block(x)

        monkeypatch.setattr(jax, "block_until_ready", tracked)
        sobj.value_and_grad(jnp.zeros(d, jnp.float32))
        monkeypatch.undo()
        assert count[0] == stream.n_chunks


class TestDiskBackedStore:
    """storage_dir: the chunk store spills to .npy and trains from
    memmap leaves — the MEMORY_AND_DISK rung of the residency ladder
    (host RAM stops bounding trainable size, disk does).  Parity is
    bit-for-bit: the spill is a pure re-residency of the same arrays."""

    @staticmethod
    def _data(seed=0, n=700, d=12):
        rng = np.random.default_rng(seed)
        X = sp.random(n, d, density=0.4, random_state=seed, format="csr",
                      dtype=np.float32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        return X, y

    @pytest.mark.parametrize("mode,n_shards", [
        ("coo", 1), ("coo", 4), ("pallas", 1), ("dense", 1), ("dense", 4),
    ])
    def test_bit_identical_to_ram_store(self, tmp_path, mode, n_shards):
        X, y = self._data()
        if mode == "dense":
            X = np.asarray(X.toarray(), np.float32)
        kw = dict(
            chunk_rows=256, use_pallas=(mode == "pallas"),
            n_shards=n_shards,
        )
        ram = make_streaming_glm_data(X, y, **kw)
        disk = make_streaming_glm_data(
            X, y, storage_dir=str(tmp_path / "store"), **kw
        )
        assert disk.n_chunks == ram.n_chunks
        for cr, cd in zip(ram.chunks, disk.chunks):
            leaves_r = jax.tree_util.tree_leaves(cr)
            leaves_d = jax.tree_util.tree_leaves(cd)
            assert any(
                isinstance(l, np.memmap) for l in leaves_d
            ), "no leaf is disk-backed"
            for a, b in zip(leaves_r, leaves_d):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_training_from_disk_matches_ram(self, tmp_path):
        X, y = self._data(seed=3)
        ram = make_streaming_glm_data(X, y, chunk_rows=256, use_pallas=False)
        disk = make_streaming_glm_data(
            X, y, chunk_rows=256, use_pallas=False,
            storage_dir=str(tmp_path / "store"),
        )
        cfg = LBFGSConfig(max_iters=30, tolerance=1e-9)
        w_ram = streaming_lbfgs_solve(
            lambda w: StreamingObjective("logistic", ram).value_and_grad(
                w, 1.0
            ),
            jnp.zeros(X.shape[1], jnp.float32), cfg,
        ).w
        w_disk = streaming_lbfgs_solve(
            lambda w: StreamingObjective("logistic", disk).value_and_grad(
                w, 1.0
            ),
            jnp.zeros(X.shape[1], jnp.float32), cfg,
        ).w
        np.testing.assert_array_equal(np.asarray(w_ram), np.asarray(w_disk))

    def test_spilled_random_effect_dataset_trains(self, tmp_path):
        from photon_ml_tpu.data.streaming import spill_random_effect_dataset
        from photon_ml_tpu.game.data import build_random_effect_dataset
        from photon_ml_tpu.game.ooc_random import (
            OutOfCoreRandomEffectCoordinate,
        )
        from photon_ml_tpu.optim.problem import (
            GlmOptimizationConfig, OptimizerConfig,
        )
        from photon_ml_tpu.optim.regularization import RegularizationContext

        rng = np.random.default_rng(5)
        n_ent, rows, d = 40, 4, 5
        n = n_ent * rows
        users = np.repeat([f"u{i}" for i in range(n_ent)], rows)
        Xe = sp.csr_matrix(rng.normal(size=(n, d)).astype(np.float32))
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        w = np.ones(n, np.float32)
        host = build_random_effect_dataset(users, Xe, y, w, device=False)
        spilled = spill_random_effect_dataset(
            build_random_effect_dataset(users, Xe, y, w, device=False),
            str(tmp_path / "re"),
        )
        assert any(
            isinstance(l, np.memmap)
            for b in spilled.blocks
            for l in jax.tree_util.tree_leaves(b)
        )
        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=20, tolerance=1e-7),
            regularization=RegularizationContext.l2(),
        )
        offsets = jnp.zeros(n, jnp.float32)
        st_ram = OutOfCoreRandomEffectCoordinate(
            "re", host, "logistic", opt, reg_weight=0.5,
            device_budget_bytes=20_000,
        ).train(offsets)
        st_disk = OutOfCoreRandomEffectCoordinate(
            "re", spilled, "logistic", opt, reg_weight=0.5,
            device_budget_bytes=20_000,
        ).train(offsets)
        for a, b in zip(st_ram, st_disk):
            np.testing.assert_array_equal(a, b)

    def test_nonempty_storage_dir_refused(self, tmp_path):
        X, y = self._data()
        store = tmp_path / "store"
        store.mkdir()
        (store / "stale.npy").write_bytes(b"x")
        with pytest.raises(ValueError, match="not empty"):
            make_streaming_glm_data(
                X, y, chunk_rows=256, use_pallas=False,
                storage_dir=str(store),
            )


class TestPipelineParity:
    """ISSUE 5 parity pins for the windowed-async pipeline: depth>1
    (windowed carry sync + donated accumulators) must be BIT-IDENTICAL
    on f32 to the ``prefetch_depth=1`` serial baseline for value/grad,
    HVP and scores (float-close on kahan — same order, but donation-free
    vs donated buffers may round identically anyway); chunk fusion must
    reproduce the unfused pass to 1e-6, the ragged tail included
    (asserted close, not bitwise: the scan body is another program);
    batched line-search trials must match the one-trial pass BITWISE in
    value and gradient (each candidate folds into its own accumulator
    row: the single-w program per candidate), hence the same iteration
    count and solution as the unbatched solver; a failed pass must leave the objective reusable (no use-after-donate);
    and the stall counters must stay monotone across passes."""

    @staticmethod
    def _stream4(rng, n=640, d=24, chunk_rows=160):
        X, y = _logistic_problem(rng, n, d - 1, density=0.15)
        stream = make_streaming_glm_data(
            X, y, chunk_rows=chunk_rows, use_pallas=False
        )
        return X, y, stream

    def test_async_window_bit_identical_to_sync_f32(self, rng):
        """The check.sh --fast parity smoke: tiny 4-chunk store,
        async (depth 3) == sync (depth 1), bitwise."""
        _, _, stream = self._stream4(rng)
        assert stream.n_chunks == 4
        w = jnp.asarray(rng.normal(size=stream.n_features), jnp.float32)
        v = jnp.asarray(rng.normal(size=stream.n_features), jnp.float32)
        sync = StreamingObjective("logistic", stream, prefetch_depth=1)
        asyn = StreamingObjective("logistic", stream, prefetch_depth=3)
        vs, gs = sync.value_and_grad(w, 0.5)
        va, ga = asyn.value_and_grad(w, 0.5)
        np.testing.assert_array_equal(np.asarray(vs), np.asarray(va))
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(ga))
        np.testing.assert_array_equal(
            np.asarray(sync.hvp(w, v, 0.5)), np.asarray(asyn.hvp(w, v, 0.5))
        )
        np.testing.assert_array_equal(sync.scores(w), asyn.scores(w))
        np.testing.assert_array_equal(
            np.asarray(sync.hessian_diagonal(w)),
            np.asarray(asyn.hessian_diagonal(w)),
        )

    def test_async_window_kahan_close_to_sync(self, rng):
        _, _, stream = self._stream4(rng)
        w = jnp.asarray(rng.normal(size=stream.n_features), jnp.float32)
        sync = StreamingObjective(
            "logistic", stream, prefetch_depth=1, accumulate="kahan"
        )
        asyn = StreamingObjective(
            "logistic", stream, prefetch_depth=3, accumulate="kahan"
        )
        vs, gs = sync.value_and_grad(w, 0.5)
        va, ga = asyn.value_and_grad(w, 0.5)
        np.testing.assert_allclose(float(vs), float(va), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(gs), np.asarray(ga), rtol=1e-6, atol=1e-7
        )

    @pytest.mark.parametrize("fuse", [2, 3, 99])
    def test_fused_chunks_match_unfused(self, rng, fuse):
        """chunk_fuse folds chunks into one lax.scan dispatch; the
        accumulation order is unchanged, including the RAGGED TAIL group
        (4 chunks at fuse=3 → groups of 3 and 1; fuse=99 → one group)."""
        _, _, stream = self._stream4(rng)
        w = jnp.asarray(rng.normal(size=stream.n_features), jnp.float32)
        v = jnp.asarray(rng.normal(size=stream.n_features), jnp.float32)
        ref = StreamingObjective("logistic", stream, chunk_fuse=1)
        fused = StreamingObjective("logistic", stream, chunk_fuse=fuse)
        if fuse == 3:
            assert [len(g) for g in fused._groups] == [3, 1]
        vr, gr = ref.value_and_grad(w, 0.5)
        vf, gf = fused.value_and_grad(w, 0.5)
        np.testing.assert_allclose(float(vr), float(vf), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(gr), np.asarray(gf), rtol=1e-6, atol=1e-7
        )
        np.testing.assert_allclose(
            np.asarray(ref.hvp(w, v, 0.5)),
            np.asarray(fused.hvp(w, v, 0.5)),
            rtol=1e-6, atol=1e-7,
        )
        np.testing.assert_allclose(
            ref.scores(w), fused.scores(w), rtol=1e-6, atol=1e-7
        )

    def test_fused_solve_matches_unfused(self, rng):
        _, _, stream = self._stream4(rng)
        cfg = LBFGSConfig(max_iters=30, tolerance=1e-8)
        w0 = jnp.zeros(stream.n_features, jnp.float32)
        ref = StreamingObjective("logistic", stream, chunk_fuse=1)
        fused = StreamingObjective("logistic", stream, chunk_fuse=3)
        res_r = streaming_lbfgs_solve(
            lambda w: ref.value_and_grad(w, 0.5), w0, cfg
        )
        res_f = streaming_lbfgs_solve(
            lambda w: fused.value_and_grad(w, 0.5), w0, cfg
        )
        np.testing.assert_allclose(
            np.asarray(res_r.w), np.asarray(res_f.w), atol=1e-4
        )

    def test_fuse_rejects_mesh_and_invalid(self, rng):
        _, _, stream = self._stream4(rng)
        with pytest.raises(ValueError, match="chunk_fuse"):
            StreamingObjective("logistic", stream, chunk_fuse=0)
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
        with pytest.raises(ValueError, match="single-device"):
            StreamingObjective(
                "logistic", stream, mesh=mesh, chunk_fuse=2
            )

    def test_batched_vg_rows_match_single(self, rng):
        """value_and_grad_batch unrolls the exact single-w graph per
        candidate — each row must equal the separate pass BITWISE (the
        property the batched line search's trajectory pin rests on)."""
        _, _, stream = self._stream4(rng)
        sobj = StreamingObjective("logistic", stream)
        ws = jnp.asarray(
            rng.normal(size=(3, stream.n_features)).astype(np.float32)
        )
        vb, gb = sobj.value_and_grad_batch(ws, 0.7)
        for i in range(3):
            vi, gi = sobj.value_and_grad(ws[i], 0.7)
            np.testing.assert_array_equal(
                np.asarray(vb[i]), np.asarray(vi)
            )
            np.testing.assert_array_equal(
                np.asarray(gb[i]), np.asarray(gi)
            )

    def test_batched_linesearch_same_trajectory(self, rng):
        """The speculative batched Wolfe search examines the identical
        candidate sequence, so iteration count AND solution must match
        the unbatched solver."""
        _, _, stream = self._stream4(rng)
        cfg = LBFGSConfig(max_iters=40, tolerance=1e-8)
        w0 = jnp.zeros(stream.n_features, jnp.float32)
        sobj = StreamingObjective("logistic", stream)
        res_seq = streaming_lbfgs_solve(
            lambda w: sobj.value_and_grad(w, 0.3), w0, cfg
        )
        passes_before = sobj.transfer_stats.passes
        res_bat = streaming_lbfgs_solve(
            lambda w: sobj.value_and_grad(w, 0.3), w0, cfg,
            value_and_grad_batch=lambda ws: sobj.value_and_grad_batch(
                ws, 0.3
            ),
        )
        passes_batched = sobj.transfer_stats.passes - passes_before
        assert int(res_bat.iterations) == int(res_seq.iterations)
        np.testing.assert_array_equal(
            np.asarray(res_bat.w), np.asarray(res_seq.w)
        )
        # The batched solver must not stream MORE passes than the
        # sequential one (one pass per cache miss, each covering the
        # trial plus its successors).
        assert passes_batched <= passes_before

    def test_batched_linesearch_owlqn_same_trajectory(self, rng):
        from photon_ml_tpu.optim.owlqn import OWLQNConfig
        from photon_ml_tpu.optim.streaming import streaming_owlqn_solve

        _, _, stream = self._stream4(rng)
        cfg = OWLQNConfig(max_iters=30, tolerance=1e-8)
        w0 = jnp.zeros(stream.n_features, jnp.float32)
        sobj = StreamingObjective("logistic", stream)
        res_seq = streaming_owlqn_solve(
            lambda w: sobj.value_and_grad(w, 0.1), w0, 0.05, cfg
        )
        res_bat = streaming_owlqn_solve(
            lambda w: sobj.value_and_grad(w, 0.1), w0, 0.05, cfg,
            value_and_grad_batch=lambda ws: sobj.value_and_grad_batch(
                ws, 0.1
            ),
        )
        assert int(res_bat.iterations) == int(res_seq.iterations)
        np.testing.assert_array_equal(
            np.asarray(res_bat.w), np.asarray(res_seq.w)
        )

    def test_donation_safety_after_failed_pass(self, rng, monkeypatch):
        """A pass that dies mid-stream (producer failure) must not leave
        the objective poisoned: the next pass starts from fresh carries
        and produces the same answer as an undisturbed objective — no
        use-after-donate, no stale ring state."""
        _, _, stream = self._stream4(rng)
        sobj = StreamingObjective("logistic", stream, prefetch_depth=2)
        w = jnp.asarray(rng.normal(size=stream.n_features), jnp.float32)
        ref_v, ref_g = StreamingObjective(
            "logistic", stream
        ).value_and_grad(w, 0.5)

        orig = sobj._host_item

        def exploding(k):
            if k == 2:
                raise RuntimeError("ingest exploded mid-pass")
            return orig(k)

        monkeypatch.setattr(sobj, "_host_item", exploding)
        with pytest.raises(RuntimeError, match="ingest exploded"):
            sobj.value_and_grad(w, 0.5)
        monkeypatch.undo()
        v2, g2 = sobj.value_and_grad(w, 0.5)
        np.testing.assert_array_equal(np.asarray(v2), np.asarray(ref_v))
        np.testing.assert_array_equal(np.asarray(g2), np.asarray(ref_g))

    def test_stall_counters_monotone(self, rng):
        """Counters only ever accumulate across passes (callers reset
        around measurement windows; a decrement would corrupt deltas)."""
        _, _, stream = self._stream4(rng)
        sobj = StreamingObjective("logistic", stream)
        w = jnp.zeros(stream.n_features, jnp.float32)
        prev = (0, 0, 0.0, 0.0, 0.0, 0.0, 0)
        for _ in range(3):
            sobj.value_and_grad(w, 0.5)
            st = sobj.transfer_stats
            cur = (
                st.consumer_stalls, st.producer_stalls,
                st.consumer_stall_seconds, st.producer_stall_seconds,
                st.pack_seconds, st.h2d_seconds, st.chunks,
            )
            assert all(c >= p for c, p in zip(cur, prev))
            prev = cur
        assert st.passes == 3
        assert st.chunks == 3 * stream.n_chunks


class TestTransferAvoidance:
    """ISSUE 14 pins: compressed wire formats + the importance-aware hot
    working-set cache must be BITWISE NEUTRAL on the f32 path — across
    prefetch depth, chunk fusion and hot-budget settings, over multiple
    passes (the cache admits on pass 2 and hits from pass 3) — while
    actually moving fewer wire bytes; cache admission must be
    deterministic under tied importance scores."""

    @staticmethod
    def _problem(rng, n=640, d=24):
        return _logistic_problem(rng, n, d - 1, density=0.15)

    @staticmethod
    def _stream4(X, y, chunk_rows=160):
        return make_streaming_glm_data(
            X, y, chunk_rows=chunk_rows, use_pallas=False
        )

    def test_fast_lane_compressed_cached_parity(self, rng):
        """The check.sh --fast transfer-avoidance smoke: a 4-chunk
        store streamed compressed (lossless) + cached is bitwise the
        raw uncached stream — value/grad, batched trials, HVP, diag and
        scores — and the wire actually shrank."""
        X, y = self._problem(rng)
        stream = self._stream4(X, y)
        assert stream.n_chunks == 4
        w = jnp.asarray(rng.normal(size=stream.n_features), jnp.float32)
        v = jnp.asarray(rng.normal(size=stream.n_features), jnp.float32)
        ws = jnp.stack([w, 0.5 * w, 2.0 * w])
        raw = StreamingObjective("logistic", stream)
        ta = StreamingObjective(
            "logistic", self._stream4(X, y), compress="lossless",
            hot_budget_bytes=1 << 30,
        )
        assert ta._codec is not None and ta._codec.ratio > 1.0
        v0, g0 = raw.value_and_grad(w, 0.5)
        vb0, gb0 = raw.value_and_grad_batch(ws, 0.5)
        for _ in range(3):  # pass 2 admits, pass 3 hits
            v1, g1 = ta.value_and_grad(w, 0.5)
        assert ta._hot_cache.hits > 0
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
        np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))
        vb1, gb1 = ta.value_and_grad_batch(ws, 0.5)
        np.testing.assert_array_equal(np.asarray(vb0), np.asarray(vb1))
        np.testing.assert_array_equal(np.asarray(gb0), np.asarray(gb1))
        np.testing.assert_array_equal(
            np.asarray(raw.hvp(w, v, 0.5)), np.asarray(ta.hvp(w, v, 0.5))
        )
        np.testing.assert_array_equal(
            np.asarray(raw.hessian_diagonal(w)),
            np.asarray(ta.hessian_diagonal(w)),
        )
        np.testing.assert_array_equal(raw.scores(w), ta.scores(w))
        # Wire vs logical accounting: the compressed stream recorded
        # fewer wire bytes than the decoded bytes it stood for.
        s = ta.transfer_stats
        assert s.logical_bytes > s.bytes > 0
        assert s.compression_ratio > 1.0

    @pytest.mark.parametrize("depth", [1, 2])
    @pytest.mark.parametrize("fuse", [1, 2])
    @pytest.mark.parametrize("budget", ["zero", "half", "huge"])
    def test_cached_vs_uncached_bitwise_grid(self, rng, depth, fuse,
                                             budget):
        """The full knob grid: hot-budget {0, ~half the store, huge} ×
        prefetch_depth × chunk_fuse, three passes each — every cell
        bitwise the uncached raw baseline."""
        X, y = self._problem(rng)
        stream = self._stream4(X, y)
        w = jnp.asarray(rng.normal(size=stream.n_features), jnp.float32)
        raw = StreamingObjective("logistic", stream)
        v0, g0 = raw.value_and_grad(w, 0.5)
        codec_bytes = StreamingObjective(
            "logistic", self._stream4(X, y), compress="lossless"
        )._codec.wire_nbytes
        budget_bytes = {
            "zero": 0,
            # room for 2 of the 4 chunks (×fuse items per group)
            "half": 2 * codec_bytes * fuse + 1,
            "huge": 1 << 30,
        }[budget]
        ta = StreamingObjective(
            "logistic", self._stream4(X, y), compress="lossless",
            hot_budget_bytes=budget_bytes, prefetch_depth=depth,
            chunk_fuse=fuse,
        )
        for _ in range(3):
            v1, g1 = ta.value_and_grad(w, 0.5)
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
        np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))
        if budget == "half":
            cache = ta._hot_cache
            assert 0 < cache.resident_bytes <= budget_bytes
            assert cache.hits > 0
        if budget == "huge":
            # Everything fits: from pass 3 on, zero wire transfers.
            chunks_before = ta.transfer_stats.chunks
            v1, g1 = ta.value_and_grad(w, 0.5)
            assert ta.transfer_stats.chunks == chunks_before
            np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
            np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))

    def test_admission_determinism_under_tie(self):
        """Tied importance scores break by ascending item index, so the
        wanted set — and therefore admission — is deterministic."""
        from photon_ml_tpu.optim.streaming import HotChunkCache

        nbytes = 100
        cache = HotChunkCache(budget_bytes=250)  # fits exactly 2 items
        scores = {i: 1.0 for i in range(6)}  # fully tied
        cache.replan(scores, lambda i: nbytes)
        admitted = [
            i for i in range(6)
            if cache.maybe_admit(i, object(), nbytes)
        ]
        assert admitted == [0, 1]
        # A strictly-higher score displaces the highest tied index on
        # the next replan (and evicts its resident entry).
        scores[5] = 2.0
        cache.replan(scores, lambda i: nbytes)
        assert cache.maybe_admit(5, object(), nbytes)
        assert not cache.maybe_admit(2, object(), nbytes)
        assert cache.evictions == 1
        assert len(cache) == 2 and cache.resident_bytes == 200

    def test_compress_requires_staged_and_single_host(self, rng):
        """Pointed construction errors: unknown mode, negative budget."""
        X, y = self._problem(rng)
        stream = self._stream4(X, y)
        with pytest.raises(ValueError, match="compress must be one of"):
            StreamingObjective("logistic", stream, compress="zstd")
        with pytest.raises(ValueError, match="hot_budget_bytes"):
            StreamingObjective(
                "logistic", stream, hot_budget_bytes=-1
            )
