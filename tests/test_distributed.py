"""Distributed (multi-device) tests on the 8-virtual-CPU-device mesh.

The analogue of the reference's `local[*]`-Spark integration tests
(SURVEY.md §4): real psum/sharding semantics, fake devices.  The key parity
property mirrors the reference's distributed-vs-single-node objective test:
the sharded objective and solver must agree with the single-device ones.
"""

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
import pytest
import scipy.sparse as sp

from photon_ml_tpu.data.dataset import make_glm_data
from photon_ml_tpu.ops import losses
from photon_ml_tpu.optim.lbfgs import LBFGSConfig, lbfgs_solve
from photon_ml_tpu.optim.objective import GlmObjective
from photon_ml_tpu.parallel.distributed import (
    DATA_AXIS,
    data_mesh,
    distributed_solve,
    shard_glm_data,
)


def _problem(rng, n=173, d=12, sparse=False):
    X = rng.normal(size=(n, d)).astype(np.float32)
    if sparse:
        X = X * (rng.uniform(size=(n, d)) < 0.4)
    w_true = rng.normal(size=d)
    p = 1.0 / (1.0 + np.exp(-(X @ w_true)))
    y = (rng.uniform(size=n) < p).astype(np.float32)
    weights = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    return (sp.csr_matrix(X) if sparse else X), y, weights


class TestShardedObjectiveParity:
    def test_dense_value_and_grad_matches_single_device(self, rng, eight_devices):
        X, y, w_row = _problem(rng)
        mesh = data_mesh(eight_devices)
        dist = shard_glm_data(X, y, mesh, weights=w_row)
        local_data = make_glm_data(X, y, weights=w_row)
        obj = GlmObjective(losses.logistic)
        w = jnp.asarray(rng.normal(size=X.shape[1]), jnp.float32)

        val_1, grad_1 = obj.value_and_grad(w, local_data, l2_weight=0.3)

        def spmd(dd, w):
            return obj.value_and_grad(
                w, dd.local(), l2_weight=0.3, axis_name=DATA_AXIS
            )

        val_8, grad_8 = jax.jit(
            shard_map(
                spmd,
                mesh=mesh,
                in_specs=(jax.sharding.PartitionSpec(DATA_AXIS),
                          jax.sharding.PartitionSpec()),
                out_specs=jax.sharding.PartitionSpec(),
                check_vma=False,
            )
        )(dist, w)
        np.testing.assert_allclose(float(val_8), float(val_1), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(grad_8), np.asarray(grad_1), rtol=1e-4, atol=1e-5
        )

    def test_sparse_shards_match_dense(self, rng, eight_devices):
        Xs, y, w_row = _problem(rng, n=90, d=7, sparse=True)
        mesh = data_mesh(eight_devices)
        dist_sparse = shard_glm_data(Xs, y, mesh, weights=w_row)
        dist_dense = shard_glm_data(Xs.toarray(), y, mesh, weights=w_row)
        obj = GlmObjective(losses.logistic)
        w = jnp.asarray(rng.normal(size=7), jnp.float32)

        def run(dd):
            def spmd(dd, w):
                return obj.value_and_grad(w, dd.local(), axis_name=DATA_AXIS)

            return jax.jit(
                shard_map(
                    spmd,
                    mesh=mesh,
                    in_specs=(jax.sharding.PartitionSpec(DATA_AXIS),
                              jax.sharding.PartitionSpec()),
                    out_specs=jax.sharding.PartitionSpec(),
                    check_vma=False,
                )
            )(dd, w)

        v_s, g_s = run(dist_sparse)
        v_d, g_d = run(dist_dense)
        np.testing.assert_allclose(float(v_s), float(v_d), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g_s), np.asarray(g_d), rtol=1e-4,
                                   atol=1e-5)


class TestDistributedSolve:
    def test_lbfgs_inside_shard_map_matches_single_device(self, rng, eight_devices):
        X, y, w_row = _problem(rng, n=240, d=10)
        mesh = data_mesh(eight_devices)
        dist = shard_glm_data(X, y, mesh, weights=w_row)
        obj = GlmObjective(losses.logistic)
        l2 = 0.5
        cfg = LBFGSConfig(max_iters=100, tolerance=1e-7)

        def solve_fn(local_data, w0):
            return lbfgs_solve(
                lambda w: obj.value_and_grad(
                    w, local_data, l2_weight=l2, axis_name=DATA_AXIS
                ),
                w0,
                cfg,
            )

        res = distributed_solve(solve_fn, dist, jnp.zeros(10, jnp.float32), mesh)

        local_data = make_glm_data(X, y, weights=w_row)
        res_1 = lbfgs_solve(
            lambda w: obj.value_and_grad(w, local_data, l2_weight=l2),
            jnp.zeros(10, jnp.float32),
            cfg,
        )
        assert bool(res.converged)
        np.testing.assert_allclose(float(res.value), float(res_1.value), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(res.w), np.asarray(res_1.w), rtol=1e-3, atol=1e-4
        )


class TestDistributedGrid:
    def test_run_grid_distributed_matches_single_device(self, rng):
        """The sharded λ-grid warm-start chain reproduces the single-device
        grid (same λs, same coefficients to solver tolerance)."""
        import scipy.sparse as sp

        from photon_ml_tpu.data.dataset import make_glm_data
        from photon_ml_tpu.optim.problem import (
            GlmOptimizationConfig,
            GlmOptimizationProblem,
            OptimizerConfig,
        )
        from photon_ml_tpu.optim.regularization import RegularizationContext
        from photon_ml_tpu.parallel.distributed import (
            data_mesh,
            run_grid_distributed,
            shard_glm_data,
        )

        n, d = 400, 30
        X = sp.random(n, d, density=0.3, random_state=2, format="csr")
        w_true = rng.normal(size=d)
        y = (np.asarray(X @ w_true).ravel() > 0).astype(np.float32)
        problem = GlmOptimizationProblem(
            "logistic",
            GlmOptimizationConfig(
                optimizer=OptimizerConfig(max_iters=60),
                regularization=RegularizationContext.l2(),
            ),
        )
        lams = [5.0, 0.5]
        single = problem.run_grid(make_glm_data(X, y), lams)
        mesh = data_mesh()
        dist = shard_glm_data(X, y, mesh)
        multi = run_grid_distributed(problem, dist, mesh, lams)
        for (l1_, m1, _), (l2_, m2, _) in zip(single, multi):
            assert l1_ == l2_
            np.testing.assert_allclose(
                np.asarray(m1.coefficients.means),
                np.asarray(m2.coefficients.means),
                atol=2e-3,
            )

    def test_glm_driver_data_parallel_flag(self, rng, tmp_path):
        import scipy.sparse as sp

        from photon_ml_tpu.data import libsvm
        from photon_ml_tpu.drivers import glm_driver

        n, d = 300, 25
        X = sp.random(n, d, density=0.25, random_state=3, format="csr")
        w_true = rng.normal(size=d)
        y = np.where(np.asarray(X @ w_true).ravel() > 0, 1.0, -1.0)
        train = str(tmp_path / "t.libsvm")
        libsvm.write_libsvm(train, X, y)
        args = [
            "--train-data", train, "--task", "logistic", "--reg-type", "l2",
            "--reg-weights", "0.5,5.0", "--n-features", str(d),
            "--max-iters", "40", "--output-dir",
        ]
        r_dp = glm_driver.run(
            args + [str(tmp_path / "dp"), "--data-parallel", "auto"]
        )
        r_sd = glm_driver.run(args + [str(tmp_path / "sd")])
        assert r_dp["best_lambda"] == r_sd["best_lambda"]
        for k in r_sd["metrics"]:
            assert r_dp["metrics"][k] == pytest.approx(
                r_sd["metrics"][k], abs=1e-3
            )


class TestDriverStreamedDataParallel:
    def test_glm_driver_streaming_composes_with_data_parallel(
        self, rng, tmp_path
    ):
        """--stream-chunk-rows + --data-parallel auto: out-of-core chunks
        sharded over the 8-device mesh, same selection and metrics as the
        plain single-device run (the streamed treeAggregate shape)."""
        import scipy.sparse as sp

        from photon_ml_tpu.data import libsvm
        from photon_ml_tpu.drivers import glm_driver

        n, d = 320, 20
        X = sp.random(n, d, density=0.25, random_state=5, format="csr")
        w_true = rng.normal(size=d)
        y = np.where(np.asarray(X @ w_true).ravel() > 0, 1.0, -1.0)
        train = str(tmp_path / "t.libsvm")
        libsvm.write_libsvm(train, X, y)
        args = [
            "--train-data", train, "--task", "logistic", "--reg-type", "l2",
            "--reg-weights", "0.5,5.0", "--n-features", str(d),
            "--max-iters", "40", "--output-dir",
        ]
        r_sdp = glm_driver.run(args + [
            str(tmp_path / "sdp"),
            "--stream-chunk-rows", "80", "--data-parallel", "auto",
        ])
        r_ref = glm_driver.run(args + [str(tmp_path / "ref")])
        assert r_sdp["best_lambda"] == r_ref["best_lambda"]
        for k in r_ref["metrics"]:
            assert r_sdp["metrics"][k] == pytest.approx(
                r_ref["metrics"][k], abs=2e-3
            )

    def test_game_driver_streaming_composes_with_data_parallel(
        self, rng, tmp_path
    ):
        """GAME JSON config 'streaming_chunk_rows' + --data-parallel auto:
        mesh-sharded streamed fixed effect + entity-sharded random effect
        through the CLI, matching the plain run's validation metric."""
        import json

        from photon_ml_tpu.data.game_reader import write_game_avro
        from photon_ml_tpu.drivers import game_training_driver

        n, n_users = 400, 15
        user_eff = {f"u{u}": rng.normal() for u in range(n_users)}
        rows = []
        for i in range(n):
            u = f"u{rng.integers(n_users)}"
            xg = rng.normal(size=3)
            m = 1.2 * xg[0] - 0.9 * xg[1] + user_eff[u]
            rows.append({
                "uid": f"r{i}",
                "response": float(rng.uniform() < 1 / (1 + np.exp(-m))),
                "weight": None, "offset": None, "ids": {"userId": u},
                "features": {
                    "global": [
                        {"name": f"g{j}", "term": "", "value": float(xg[j])}
                        for j in range(3)
                    ],
                    "userFeatures": [
                        {"name": "bias", "term": "", "value": 1.0}
                    ],
                },
            })
        train = str(tmp_path / "g.avro")
        val = str(tmp_path / "v.avro")
        write_game_avro(train, rows[:320])
        write_game_avro(val, rows[320:])
        cfg = {
            "task": "logistic", "iterations": 2, "evaluator": "auc",
            "coordinates": [
                {"name": "fixed", "type": "fixed", "feature_shard": "global",
                 "reg_type": "l2", "reg_weight": 0.5, "max_iters": 40,
                 "streaming_chunk_rows": 100},
                {"name": "per_user", "type": "random",
                 "feature_shard": "userFeatures", "entity_key": "userId",
                 "reg_type": "l2", "reg_weight": 1.0, "max_iters": 30},
            ],
        }
        cfg_path = str(tmp_path / "cfg.json")
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        base = [
            "--train-data", train, "--validate-data", val,
            "--config", cfg_path, "--output-dir",
        ]
        r_dp = game_training_driver.run(base + [
            str(tmp_path / "dp"), "--data-parallel", "auto",
        ])
        r_sd = game_training_driver.run(base + [str(tmp_path / "sd")])
        assert r_dp["validation_metric"] == pytest.approx(
            r_sd["validation_metric"], abs=2e-3
        )
