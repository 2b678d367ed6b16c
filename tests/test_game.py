"""GAME layer tests.

Mirrors the reference's GAME integration-test strategy (SURVEY.md §4): mini
GAME datasets with known per-entity structure; assertions that coordinate
descent recovers it and that mixed-effects beat fixed-effects alone."""

import dataclasses

import numpy as np
import pytest
import scipy.sparse as sp

import jax.numpy as jnp

from photon_ml_tpu.evaluation.evaluators import AreaUnderROCCurveEvaluator
from photon_ml_tpu.game.data import build_random_effect_dataset
from photon_ml_tpu.game.estimator import (
    FactoredRandomEffectCoordinateConfig,
    FixedEffectCoordinateConfig,
    GameEstimator,
    GameTransformer,
    RandomEffectCoordinateConfig,
)
from photon_ml_tpu.optim.problem import GlmOptimizationConfig, OptimizerConfig
from photon_ml_tpu.optim.regularization import RegularizationContext


def _mixed_effects_problem(rng, n_users=30, rows_per_user=(5, 60), d_global=8,
                           d_user=4):
    """y ~ sigmoid(x_g·w_g + x_u·w_user[u]): global + per-user effects."""
    rows, user_ids = [], []
    for u in range(n_users):
        k = rng.integers(*rows_per_user)
        rows.append(k)
        user_ids.extend([f"user_{u}"] * k)
    n = sum(rows)
    Xg = rng.normal(size=(n, d_global)).astype(np.float32)
    Xu = rng.normal(size=(n, d_user)).astype(np.float32)
    wg = rng.normal(size=d_global)
    w_users = {f"user_{u}": 2.0 * rng.normal(size=d_user) for u in range(n_users)}
    margins = Xg @ wg + np.array(
        [Xu[i] @ w_users[user_ids[i]] for i in range(n)]
    )
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margins))).astype(np.float32)
    return {
        "shards": {"global": sp.csr_matrix(Xg), "per_user": sp.csr_matrix(Xu)},
        "ids": {"userId": np.array(user_ids)},
        "response": y,
        "margins": margins,
    }


class TestRandomEffectDataset:
    def test_grouping_projection_bucketing(self, rng):
        keys = np.array(["b", "a", "b", "c", "a", "b"])
        X = sp.csr_matrix(np.array([
            [1.0, 0.0, 0.0, 2.0],
            [0.0, 3.0, 0.0, 0.0],
            [4.0, 0.0, 0.0, 0.0],
            [0.0, 0.0, 5.0, 0.0],
            [0.0, 6.0, 0.0, 0.0],
            [0.0, 0.0, 0.0, 7.0],
        ], np.float32))
        y = np.arange(6, dtype=np.float32)
        ds = build_random_effect_dataset(keys, X, y, np.ones(6, np.float32))
        assert ds.n_entities == 3
        assert set(ds.entity_to_slot) == {"a", "b", "c"}
        # Every row index appears exactly once across blocks (minus sentinels).
        seen = []
        for block in ds.blocks:
            ri = np.asarray(block.row_index).ravel()
            seen.extend(ri[ri < 6].tolist())
        assert sorted(seen) == list(range(6))
        # Projection: entity "b" touches global cols {0, 3} only.
        b_block, b_lane = ds.entity_to_slot["b"]
        cmap = np.asarray(ds.blocks[b_block].col_map)[b_lane]
        assert set(cmap[cmap >= 0].tolist()) == {0, 3}
        # Block reconstruction matches the original rows.
        blk = ds.blocks[b_block]
        Xb = np.asarray(blk.X)[b_lane]
        rix = np.asarray(blk.row_index)[b_lane]
        for r, gr in enumerate(rix):
            if gr >= 6:
                continue
            dense_row = X[int(gr)].toarray().ravel()
            for k, g in enumerate(cmap):
                if g >= 0:
                    assert Xb[r, k] == dense_row[g]

    def test_max_rows_cap_creates_passive_blocks(self, rng):
        keys = np.array(["u"] * 100)
        X = sp.csr_matrix(rng.normal(size=(100, 3)).astype(np.float32))
        ds = build_random_effect_dataset(
            keys, X, np.zeros(100, np.float32), np.ones(100, np.float32),
            max_rows_per_entity=16,
        )
        assert ds.blocks[0].rows_per_entity == 16
        # The 84 capped-out rows land in a score-only passive block; every
        # global row appears exactly once across active+passive.
        pb = ds.passive_blocks[0]
        assert pb is not None
        seen = []
        for block in (ds.blocks[0], pb):
            ri = np.asarray(block.row_index).ravel()
            seen.extend(ri[ri < 100].tolist())
        assert sorted(seen) == list(range(100))

    def test_capped_coordinate_scores_all_rows(self, rng):
        # Same data trained with and without a cap: the capped coordinate
        # must still produce nonzero scores for EVERY row of a capped entity.
        n = 80
        keys = np.array(["big"] * n)
        X = sp.csr_matrix(
            (rng.normal(size=(n, 3)) + 1.0).astype(np.float32)
        )
        y = (rng.uniform(size=n) < 0.7).astype(np.float32)
        from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
        from photon_ml_tpu.optim.problem import (
            GlmOptimizationConfig, OptimizerConfig)
        from photon_ml_tpu.optim.regularization import RegularizationContext

        ds = build_random_effect_dataset(
            keys, X, y, np.ones(n, np.float32), max_rows_per_entity=16
        )
        coord = RandomEffectCoordinate(
            "re", ds, "logistic",
            GlmOptimizationConfig(
                optimizer=OptimizerConfig(max_iters=30),
                regularization=RegularizationContext.l2(),
            ),
            reg_weight=1.0,
        )
        state = coord.train(jnp.zeros(n, jnp.float32))
        scores = np.asarray(coord.score(state))
        assert np.all(scores != 0.0), "passive rows must be scored too"


class TestGameTraining:
    def test_mixed_effects_beat_fixed_only(self, rng):
        prob = _mixed_effects_problem(rng)
        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=60),
            regularization=RegularizationContext.l2(),
        )
        auc = AreaUnderROCCurveEvaluator()

        fixed_only = GameEstimator(
            "logistic",
            {"fixed": FixedEffectCoordinateConfig("global", opt, reg_weight=1.0)},
            n_iterations=1,
        )
        model_f, hist_f = fixed_only.fit(
            prob["shards"], prob["ids"], prob["response"]
        )
        scores_f = GameTransformer(model_f).transform(prob["shards"], prob["ids"])
        auc_f = auc.evaluate(scores_f, prob["response"])

        game = GameEstimator(
            "logistic",
            {
                "fixed": FixedEffectCoordinateConfig("global", opt, reg_weight=1.0),
                "per_user": RandomEffectCoordinateConfig(
                    "per_user", "userId", opt, reg_weight=1.0
                ),
            },
            n_iterations=3,
        )
        model_g, hist_g = game.fit(prob["shards"], prob["ids"], prob["response"])
        scores_g = GameTransformer(model_g).transform(prob["shards"], prob["ids"])
        auc_g = auc.evaluate(scores_g, prob["response"])

        assert auc_g > auc_f + 0.05, (auc_g, auc_f)
        assert auc_g > 0.85
        # History records training metric per coordinate update.
        assert len(hist_g) == 3 * 2
        assert hist_g[-1]["train_metric"] == pytest.approx(
            auc.evaluate(
                prob["margins"] * 0 + np.asarray(scores_g), prob["response"]
            ),
            abs=0.02,
        )

    def test_coordinate_descent_improves_monotonically(self, rng):
        prob = _mixed_effects_problem(rng, n_users=15)
        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=40),
            regularization=RegularizationContext.l2(),
        )
        game = GameEstimator(
            "logistic",
            {
                "fixed": FixedEffectCoordinateConfig("global", opt, reg_weight=1.0),
                "per_user": RandomEffectCoordinateConfig(
                    "per_user", "userId", opt, reg_weight=1.0
                ),
            },
            n_iterations=3,
        )
        _, hist = game.fit(prob["shards"], prob["ids"], prob["response"])
        metrics = [h["train_metric"] for h in hist]
        # AUC after the final update should be >= after the first update.
        assert metrics[-1] >= metrics[0] - 1e-6

    def test_unseen_entities_score_zero_random_effect(self, rng):
        prob = _mixed_effects_problem(rng, n_users=10)
        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=30),
            regularization=RegularizationContext.l2(),
        )
        game = GameEstimator(
            "logistic",
            {
                "fixed": FixedEffectCoordinateConfig("global", opt, reg_weight=1.0),
                "per_user": RandomEffectCoordinateConfig(
                    "per_user", "userId", opt, reg_weight=1.0
                ),
            },
            n_iterations=2,
        )
        model, _ = game.fit(prob["shards"], prob["ids"], prob["response"])

        # Score 5 rows with a brand-new user: RE contributes 0, so the total
        # must equal the fixed-effect score alone.
        n_new = 5
        shards_new = {
            "global": prob["shards"]["global"][:n_new],
            "per_user": prob["shards"]["per_user"][:n_new],
        }
        ids_new = {"userId": np.array(["never_seen"] * n_new)}
        total = GameTransformer(model).transform(shards_new, ids_new)
        from photon_ml_tpu.data.dataset import make_glm_data

        fixed_scores = np.asarray(
            model["fixed"].model.compute_score(
                make_glm_data(shards_new["global"], np.zeros(n_new))
            )
        )
        np.testing.assert_allclose(total, fixed_scores, rtol=1e-5, atol=1e-6)

    def test_multi_random_effect_user_item_context(self, rng):
        """BASELINE config 5's shape: fixed + user + item + context effects."""
        n = 900
        n_users, n_items, n_ctx = 20, 15, 4
        users = np.array([f"u{rng.integers(n_users)}" for _ in range(n)])
        items = np.array([f"i{rng.integers(n_items)}" for _ in range(n)])
        ctxs = np.array([f"c{rng.integers(n_ctx)}" for _ in range(n)])
        ue = {f"u{k}": rng.normal(scale=1.5) for k in range(n_users)}
        ie = {f"i{k}": rng.normal(scale=1.5) for k in range(n_items)}
        ce = {f"c{k}": rng.normal(scale=1.0) for k in range(n_ctx)}
        Xg = rng.normal(size=(n, 5)).astype(np.float32)
        wg = rng.normal(size=5)
        margins = (
            Xg @ wg
            + np.array([ue[u] for u in users])
            + np.array([ie[i] for i in items])
            + np.array([ce[c] for c in ctxs])
        )
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margins))).astype(np.float32)
        bias = sp.csr_matrix(np.ones((n, 1), np.float32))
        shards = {"global": sp.csr_matrix(Xg), "bias": bias}
        ids = {"userId": users, "itemId": items, "contextId": ctxs}

        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=40),
            regularization=RegularizationContext.l2(),
        )
        est = GameEstimator(
            "logistic",
            {
                "fixed": FixedEffectCoordinateConfig("global", opt, reg_weight=0.5),
                "per_user": RandomEffectCoordinateConfig(
                    "bias", "userId", opt, reg_weight=0.5),
                "per_item": RandomEffectCoordinateConfig(
                    "bias", "itemId", opt, reg_weight=0.5),
                "per_context": RandomEffectCoordinateConfig(
                    "bias", "contextId", opt, reg_weight=0.5),
            },
            n_iterations=3,
        )
        model, hist = est.fit(shards, ids, y)
        scores = GameTransformer(model).transform(shards, ids)
        auc = AreaUnderROCCurveEvaluator().evaluate(scores, y)
        assert auc > 0.85
        assert model["per_user"].n_entities == n_users
        assert model["per_item"].n_entities == n_items
        assert model["per_context"].n_entities == n_ctx
        # Each coordinate update improved (or held) the training metric.
        metrics = [h["train_metric"] for h in hist]
        assert metrics[-1] > metrics[0]

    def test_int_entity_ids_survive_save_load(self, rng, tmp_path):
        # Regression: int-keyed ids must score identically after the
        # string-keyed Avro round trip.
        from photon_ml_tpu.io.game_store import load_game_model, save_game_model
        from photon_ml_tpu.data.index_map import IndexMap

        prob = _mixed_effects_problem(rng, n_users=6)
        int_ids = {"userId": np.array(
            [int(u.split("_")[1]) for u in prob["ids"]["userId"]]
        )}
        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=30),
            regularization=RegularizationContext.l2(),
        )
        est = GameEstimator(
            "logistic",
            {"per_user": RandomEffectCoordinateConfig(
                "per_user", "userId", opt, reg_weight=1.0)},
            n_iterations=1,
        )
        model, _ = est.fit(prob["shards"], int_ids, prob["response"])
        s_before = GameTransformer(model).transform(prob["shards"], int_ids)
        assert np.any(s_before != 0)

        imaps = {"per_user": IndexMap.build(
            [f"f{j}" for j in range(prob["shards"]["per_user"].shape[1])]
        )}
        save_game_model(model, imaps, str(tmp_path / "m"))
        model2, _ = load_game_model(str(tmp_path / "m"))
        s_after = GameTransformer(model2).transform(prob["shards"], int_ids)
        np.testing.assert_allclose(s_after, s_before, rtol=1e-5, atol=1e-6)

    def test_missing_entity_ids_rejected(self, rng):
        keys = np.array(["a", None, "b"], dtype=object)
        X = sp.csr_matrix(np.ones((3, 2), np.float32))
        with pytest.raises(ValueError, match="no entity id"):
            build_random_effect_dataset(
                keys, X, np.zeros(3, np.float32), np.ones(3, np.float32)
            )

    def test_fixed_effect_down_sampling(self, rng):
        prob = _mixed_effects_problem(rng, n_users=10)
        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=40),
            regularization=RegularizationContext.l2(),
        )
        est = GameEstimator(
            "logistic",
            {"fixed": FixedEffectCoordinateConfig(
                "global", opt, reg_weight=1.0, down_sampling_rate=0.5)},
            n_iterations=1,
        )
        model, _ = est.fit(prob["shards"], prob["ids"], prob["response"])
        scores = GameTransformer(model).transform(prob["shards"], prob["ids"])
        # Down-sampled training still yields a usable model.
        auc = AreaUnderROCCurveEvaluator().evaluate(scores, prob["response"])
        assert auc > 0.6

    def test_warm_start_states_reused(self, rng):
        # Two CD iterations with max_iters=0 on the second coordinate pass
        # would keep state; here we just check states have block shapes.
        prob = _mixed_effects_problem(rng, n_users=8)
        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=20),
            regularization=RegularizationContext.l2(),
        )
        est = GameEstimator(
            "logistic",
            {"per_user": RandomEffectCoordinateConfig(
                "per_user", "userId", opt, reg_weight=1.0)},
            n_iterations=2,
        )
        model, hist = est.fit(prob["shards"], prob["ids"], prob["response"])
        re = model["per_user"]
        assert re.n_entities == 8
        # Every trained user has some nonzero coefficients.
        nonzero = sum(1 for c, v in re.coefficients.values() if len(v))
        assert nonzero == 8


class TestBucketConsolidation:
    def test_growth_reduces_buckets_same_model(self, rng):
        """bucket_growth=4 consolidates the long tail into fewer blocks and
        trains per-entity models identical to the pow2 grid (padding rows
        carry weight 0, so bucket shape never changes the math)."""
        import scipy.sparse as sp

        from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
        from photon_ml_tpu.game.data import build_random_effect_dataset
        from photon_ml_tpu.optim.problem import (
            GlmOptimizationConfig,
            OptimizerConfig,
        )
        from photon_ml_tpu.optim.regularization import RegularizationContext

        sizes = np.minimum(rng.zipf(1.7, 300), 64)
        n = int(sizes.sum())
        users = np.repeat(
            np.array([f"u{i}" for i in range(300)], dtype=object), sizes
        )
        X = sp.csr_matrix(rng.normal(size=(n, 5)).astype(np.float32))
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        w = np.ones(n, np.float32)

        ds2 = build_random_effect_dataset(users, X, y, w)
        ds4 = build_random_effect_dataset(users, X, y, w, bucket_growth=4.0)
        assert len(ds4.blocks) < len(ds2.blocks)

        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=25),
            regularization=RegularizationContext.l2(),
        )
        import jax.numpy as jnp

        offs = jnp.zeros(n, jnp.float32)
        models = []
        for ds in (ds2, ds4):
            coord = RandomEffectCoordinate(
                "re", ds, "logistic", opt, reg_weight=0.5,
                entity_key="userId",
            )
            models.append(coord.finalize(coord.train(offs)))
        t2, t4 = models[0].coefficients, models[1].coefficients
        assert set(t2) == set(t4)
        for k in t2:
            np.testing.assert_array_equal(t2[k][0], t4[k][0])
            # Padded shapes change f32 reduction order inside the iterative
            # solver; solutions agree to optimization tolerance, not ulps.
            np.testing.assert_allclose(t2[k][1], t4[k][1], atol=2e-3)


class TestRank1FastPath:
    @pytest.mark.parametrize("task", ["logistic", "squared", "poisson"])
    def test_single_row_bucket_matches_generic_solver(self, rng, task):
        """R == 1 buckets take the rank-1 Newton path; it must agree with
        the generic vmapped L-BFGS solve to optimization tolerance."""
        import jax
        import jax.numpy as jnp
        import scipy.sparse as sp

        from photon_ml_tpu.game.coordinates import _make_block_solver
        from photon_ml_tpu.game.data import build_random_effect_dataset
        from photon_ml_tpu.optim.problem import (
            GlmOptimizationConfig,
            OptimizerConfig,
        )
        from photon_ml_tpu.optim.regularization import RegularizationContext

        n_entities = 80
        users = np.array([f"u{i}" for i in range(n_entities)], dtype=object)
        X = sp.csr_matrix(rng.normal(size=(n_entities, 4)).astype(np.float32))
        if task == "poisson":
            y = rng.poisson(1.5, size=n_entities).astype(np.float32)
        else:
            y = (rng.uniform(size=n_entities) < 0.5).astype(np.float32)
        ds = build_random_effect_dataset(
            users, X, y, np.ones(n_entities, np.float32)
        )
        assert len(ds.blocks) == 1 and ds.blocks[0].rows_per_entity == 1

        cfg = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=60, tolerance=1e-9),
            regularization=RegularizationContext.l2(),
        )
        solver = _make_block_solver(task, cfg)
        block = ds.blocks[0]
        off = jnp.asarray(
            rng.normal(size=(block.n_entities, 1)).astype(np.float32) * 0.3
        )
        w0 = jnp.zeros((block.n_entities, block.block_dim), jnp.float32)
        l1 = jnp.asarray(0.0)
        l2 = jnp.asarray(0.7)
        fast = np.asarray(solver(block, off, w0, l1, l2))

        # Force the generic path by faking R=2 (duplicate the row with the
        # second copy zero-weighted — mathematically identical problem).
        from photon_ml_tpu.game.data import EntityBlock

        block2 = EntityBlock(
            X=jnp.concatenate([block.X, jnp.zeros_like(block.X)], axis=1),
            labels=jnp.concatenate(
                [block.labels, jnp.zeros_like(block.labels)], axis=1
            ),
            weights=jnp.concatenate(
                [block.weights, jnp.zeros_like(block.weights)], axis=1
            ),
            col_map=block.col_map,
            row_index=jnp.concatenate(
                [block.row_index, jnp.full_like(block.row_index, n_entities)],
                axis=1,
            ),
            n_entities=block.n_entities,
            rows_per_entity=2,
            block_dim=block.block_dim,
        )
        off2 = jnp.concatenate([off, jnp.zeros_like(off)], axis=1)
        generic = np.asarray(solver(block2, off2, w0, l1, l2))
        np.testing.assert_allclose(fast, generic, atol=5e-4)

    def test_rank1_large_norm_poisson_no_nan(self, rng):
        """Regression: a large-norm feature row with a huge Poisson count
        must not blow the Newton step into inf/NaN (margin-change clamp)."""
        import jax.numpy as jnp
        import scipy.sparse as sp

        from photon_ml_tpu.game.coordinates import _make_block_solver
        from photon_ml_tpu.game.data import build_random_effect_dataset
        from photon_ml_tpu.optim.problem import (
            GlmOptimizationConfig,
            OptimizerConfig,
        )
        from photon_ml_tpu.optim.regularization import RegularizationContext

        users = np.array(["a", "b", "c"], dtype=object)
        X = sp.csr_matrix(np.array([
            [20.0, 0.0],      # ||x|| = 20 (s = 400)
            [1e-2, 0.0],      # tiny norm
            [1.0, 1.0],
        ], np.float32))
        y = np.array([1000.0, 100.0, 2.0], np.float32)
        ds = build_random_effect_dataset(users, X, y, np.ones(3, np.float32))
        cfg = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=40),
            regularization=RegularizationContext.l2(),
        )
        solver = _make_block_solver("poisson", cfg)
        for block in ds.blocks:
            w0 = jnp.zeros((block.n_entities, block.block_dim), jnp.float32)
            out = np.asarray(solver(
                block,
                jnp.zeros(
                    (block.n_entities, block.rows_per_entity), jnp.float32
                ),
                w0, jnp.asarray(0.0, jnp.float32),
                jnp.asarray(1e-3, jnp.float32),
            ))
            assert np.all(np.isfinite(out)), out
        # The s=400/y=1000 entity must actually converge: optimal margin is
        # close to log(1000) ≈ 6.9 (weak L2), so exp(m) ≈ y.
        blk, lane = ds.entity_to_slot["a"]
        block = ds.blocks[blk]
        w0 = jnp.zeros((block.n_entities, block.block_dim), jnp.float32)
        w = np.asarray(solver(
            block,
            jnp.zeros(
                (block.n_entities, block.rows_per_entity), jnp.float32
            ),
            w0, jnp.asarray(0.0, jnp.float32),
            jnp.asarray(1e-3, jnp.float32),
        ))
        m = float((np.asarray(block.X)[lane, 0] * w[lane]).sum())
        assert abs(np.exp(m) - 1000.0) / 1000.0 < 0.05, m


class TestTightBucketPadding:
    def test_blocks_pad_to_member_maxima_not_grid(self, rng):
        """Round 4: the geometric grid only GROUPS; block dims are the
        members' actual maxima (the zipf row cap used to pad to the next
        grid point — 2x pure waste on the largest block)."""
        import scipy.sparse as sp

        from photon_ml_tpu.game.data import build_random_effect_dataset

        # One entity with 100 rows: growth=2 grid point is 128, tight is
        # 100.  A second entity with 3 rows lands in a different bucket.
        users = np.array(["a"] * 100 + ["b"] * 3, dtype=object)
        n = len(users)
        X = sp.csr_matrix(rng.normal(size=(n, 5)).astype(np.float32))
        ds = build_random_effect_dataset(
            users, X, np.zeros(n, np.float32), np.ones(n, np.float32),
            bucket_growth=2.0,
        )
        dims = sorted(
            (b.rows_per_entity, b.block_dim) for b in ds.blocks
        )
        assert dims == [(3, 5), (100, 5)], dims  # tight, not (4,8)/(128,8)


class TestDim1Newton:
    def test_bias_random_effect_matches_scalar_oracle(self, rng):
        """D == 1 blocks (per-entity bias — the MovieLens shape) take the
        scalar-Newton path; each entity's solution must match an
        independent 1-D scipy solve of its own regularized objective."""
        import scipy.optimize
        import scipy.sparse as sp

        from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
        from photon_ml_tpu.game.data import build_random_effect_dataset
        from photon_ml_tpu.optim.problem import (
            GlmOptimizationConfig,
            OptimizerConfig,
        )
        from photon_ml_tpu.optim.regularization import RegularizationContext

        n_users, rows_each = 12, 7  # R > 1 so rank1 does NOT shadow dim1
        n = n_users * rows_each
        users = np.repeat(
            np.array([f"u{i}" for i in range(n_users)], dtype=object),
            rows_each,
        )
        x = rng.normal(size=n).astype(np.float32)  # single feature
        offs = rng.normal(size=n).astype(np.float32) * 0.5
        margins = 1.3 * x + offs
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margins))).astype(
            np.float32
        )
        X = sp.csr_matrix(x[:, None])
        ds = build_random_effect_dataset(
            users, X, y, np.ones(n, np.float32)
        )
        assert all(b.block_dim == 1 for b in ds.blocks)
        assert all(b.rows_per_entity > 1 for b in ds.blocks)
        coord = RandomEffectCoordinate(
            "per_user", ds, "logistic",
            GlmOptimizationConfig(
                optimizer=OptimizerConfig(max_iters=50, tolerance=1e-9),
                regularization=RegularizationContext.l2(),
            ),
            reg_weight=0.7, entity_key="userId",
        )
        state = coord.train(jnp.asarray(offs))

        def entity_obj(w, rows):
            m = w * x[rows] + offs[rows]
            return float(
                np.sum(np.log1p(np.exp(-m)) * y[rows]
                       + np.log1p(np.exp(m)) * (1 - y[rows]))
                + 0.35 * w * w  # 0.5 * l2, l2 = 0.7
            )

        for bi, (block_ids, coefs) in enumerate(
            zip(ds.entity_ids, state)
        ):
            for lane, key in enumerate(block_ids):
                rows = np.flatnonzero(users == key)
                res = scipy.optimize.minimize_scalar(
                    lambda w: entity_obj(w, rows), bounds=(-20, 20),
                    method="bounded",
                    options={"xatol": 1e-10},
                )
                np.testing.assert_allclose(
                    float(np.asarray(coefs)[lane, 0]), res.x, atol=2e-4,
                    err_msg=f"entity {key}",
                )


def _dense_block(rng, n_entities, rows, dim, pad_lanes=0, minor="d"):
    """One EntityBlock of dense features, ``pad_lanes`` trailing padding
    lanes (no features, no weight), and its float64 arrays."""
    from photon_ml_tpu.game.data import EntityBlock

    lanes = n_entities + pad_lanes
    X = rng.normal(size=(lanes, rows, dim)).astype(np.float32)
    y = (rng.uniform(size=(lanes, rows)) < 0.5).astype(np.float32)
    wt = (rng.uniform(size=(lanes, rows)) < 0.8).astype(np.float32)
    wt[:, 0] = 1.0
    off = (0.3 * rng.normal(size=(lanes, rows))).astype(np.float32)
    X[n_entities:], wt[n_entities:], off[n_entities:] = 0.0, 0.0, 0.0
    block = EntityBlock(
        X=jnp.asarray(X if minor == "d" else X.swapaxes(1, 2)),
        labels=jnp.asarray(y), weights=jnp.asarray(wt),
        col_map=jnp.zeros((lanes, dim), jnp.int32),
        row_index=jnp.zeros((lanes, rows), jnp.int32),
        n_entities=lanes, rows_per_entity=rows, block_dim=dim,
        x_minor=minor,
    )
    return block, X.astype(np.float64), y, wt, off


def _logistic_grad64(X, y, wt, off, l2, w):
    p = 1.0 / (1.0 + np.exp(-(np.einsum("erd,ed->er", X, w) + off)))
    return p, np.einsum("er,erd->ed", wt * (p - y), X) + l2 * w


def _newton64(X, y, wt, off, l2, iters=60):
    """Per-entity damped Newton in float64, run far past convergence."""
    w = np.zeros((X.shape[0], X.shape[2]))
    for _ in range(iters):
        p, g = _logistic_grad64(X, y, wt, off, l2, w)
        H = np.einsum("erd,er,erk->edk", X, wt * p * (1 - p), X)
        H = H + l2 * np.eye(X.shape[2])
        step = np.linalg.solve(H, g[:, :, None])[:, :, 0]
        dm = np.abs(np.einsum("erd,ed->er", X, step)).max(axis=1)
        w = w - np.minimum(1.0, 20.0 / np.maximum(dm, 1e-12))[:, None] * step
    return w


def _smooth_solver(max_iters=30, tolerance=1e-7, **kw):
    from photon_ml_tpu.game.coordinates import _make_block_solver

    return _make_block_solver("logistic", GlmOptimizationConfig(
        optimizer=OptimizerConfig(
            max_iters=max_iters, tolerance=tolerance, **kw),
        regularization=RegularizationContext.l2(),
    ))


class TestNewtonDirect:
    """The small-D block path: damped Newton whose D x D system is solved
    once, directly, with the entity on the minor axis."""

    @pytest.mark.parametrize("rows", [2, 37, 130])
    @pytest.mark.parametrize("dim", [2, 9, 21, 32])
    def test_matches_float64_newton(self, dim, rows):
        rng = np.random.default_rng(1000 * dim + rows)
        block, X, y, wt, off = _dense_block(rng, 48, rows, dim)
        solver = _smooth_solver()
        assert solver.path(block) == "newton_direct"
        w0 = jnp.zeros((48, dim), jnp.float32)
        w = np.asarray(solver(
            block, jnp.asarray(off), w0, jnp.asarray(0.0), jnp.asarray(1.0)))
        ref = _newton64(X, y, wt, off, 1.0)
        assert np.max(np.abs(w - ref)) <= 1e-4 * np.max(np.abs(ref))
        _, g0 = _logistic_grad64(X, y, wt, off, 1.0, np.zeros_like(ref))
        _, g = _logistic_grad64(X, y, wt, off, 1.0, w.astype(np.float64))
        bound = 1e-4 * np.maximum(1.0, np.linalg.norm(g0, axis=1))
        assert np.all(np.linalg.norm(g, axis=1) <= bound)

    @pytest.mark.parametrize("minor", ["d", "r"])
    def test_padding_lanes_zero_and_uncounted(self, minor):
        rng = np.random.default_rng(7)
        block, *_rest, off = _dense_block(
            rng, 20, 12, 5, pad_lanes=12, minor=minor)
        w, n = _smooth_solver().counted(
            block, jnp.asarray(off), jnp.zeros((32, 5), jnp.float32),
            jnp.asarray(0.0), jnp.asarray(1.0))
        w, n = np.asarray(w), np.asarray(n)
        assert w.shape == (32, 5) and n.shape == (32,)
        assert n.dtype == np.int32
        assert np.all(w[20:] == 0.0) and np.all(n[20:] == 0)
        assert np.all(n[:20] >= 1) and np.all(np.abs(w[:20]).max(axis=1) > 0)

    @pytest.mark.parametrize("fault", ["zero_column", "duplicated_column"])
    def test_no_l2_degenerate_column_stays_finite(self, fault):
        rng = np.random.default_rng(11)
        block, _, y, wt, off = _dense_block(rng, 24, 40, 6)
        Xf = np.asarray(block.X).copy()
        if fault == "zero_column":
            Xf[:, :, 2] = 0.0
        else:
            Xf[:, :, 4] = Xf[:, :, 1]
        block = dataclasses.replace(block, X=jnp.asarray(Xf))
        w = np.asarray(_smooth_solver()(
            block, jnp.asarray(off), jnp.zeros((24, 6), jnp.float32),
            jnp.asarray(0.0), jnp.asarray(0.0)))
        assert np.all(np.isfinite(w))
        # ... and still a stationary point of the unregularised objective
        # (the degenerate direction carries no gradient).
        _, g = _logistic_grad64(
            Xf.astype(np.float64), y, wt, off, 0.0, w.astype(np.float64))
        _, g0 = _logistic_grad64(
            Xf.astype(np.float64), y, wt, off, 0.0, np.zeros_like(g))
        assert np.all(np.linalg.norm(g, axis=1)
                      <= 1e-3 * np.maximum(1.0, np.linalg.norm(g0, axis=1)))

    @pytest.mark.parametrize("l2,floored", [(1e-6, True), (1e-4, False)])
    def test_small_l2_duplicated_column(self, l2, floored):
        """A duplicated column under an L2 so small that the second twin's
        pivot (~2 l2 of a diagonal entry of ~8) is at the floor: that
        component keeps its start value on every trip, the others reach
        their stationary point, and the lane freezes or counts to the cap
        by what is left of the twin's gradient.  A hundred times the L2
        and the pivot is sound: the twins share the weight evenly, as the
        penalty asks."""
        rng = np.random.default_rng(13)
        block, _, y, wt, off = _dense_block(rng, 24, 40, 6)
        Xf = np.asarray(block.X).copy()
        Xf[:, :, 4] = Xf[:, :, 1]
        block = dataclasses.replace(block, X=jnp.asarray(Xf))
        w0 = np.zeros((24, 6), np.float32)
        w0[:, 4] = 0.5
        w, n = _smooth_solver().counted(
            block, jnp.asarray(off), jnp.asarray(w0), jnp.asarray(0.0),
            jnp.asarray(l2, jnp.float32))
        w, n = np.asarray(w), np.asarray(n)
        assert np.all(np.isfinite(w)) and np.all((1 <= n) & (n <= 30))
        X64 = Xf.astype(np.float64)
        _, g = _logistic_grad64(X64, y, wt, off, l2, w.astype(np.float64))
        _, g0 = _logistic_grad64(X64, y, wt, off, l2, w0.astype(np.float64))
        scale = np.maximum(1.0, np.linalg.norm(g0, axis=1))
        others = [0, 1, 2, 3, 5]
        assert np.all(np.linalg.norm(g[:, others], axis=1) <= 1e-5 * scale)
        if floored:
            assert np.all(w[:, 4] == 0.5)
            assert np.max(np.abs(w[:, 1] - w[:, 4])) > 0.1
            assert n.max() == 30        # some lane's test stays open
        else:
            assert np.max(np.abs(w[:, 1] - w[:, 4])) <= 1e-5
            assert np.all(np.abs(g[:, 4]) <= 1e-5 * scale)
            assert n.max() < 30

    @pytest.mark.parametrize("dim", [2, 9, 21, 32])
    def test_direct_solve_matches_numpy(self, dim):
        from photon_ml_tpu.game.coordinates import _spd_solve_direct

        rng = np.random.default_rng(dim)
        A = rng.normal(size=(300, dim, 2 * dim))
        H = A @ A.transpose(0, 2, 1) / (2 * dim) + np.eye(dim)
        g = rng.normal(size=(300, dim))
        ref = np.linalg.solve(H, g[:, :, None])[:, :, 0]
        got = np.asarray(_spd_solve_direct(
            jnp.asarray(H.transpose(1, 2, 0), jnp.float32),
            jnp.asarray(g.T, jnp.float32))).T
        assert got.shape == (300, dim)
        assert np.max(np.abs(got - ref)) <= 1e-5 * np.max(np.abs(ref))

    def test_direct_solve_padding_lane_is_exact_zero(self):
        from photon_ml_tpu.game.coordinates import _spd_solve_direct

        for l2 in (0.0, 1.0, 3.7):
            H = jnp.broadcast_to(
                l2 * jnp.eye(5, dtype=jnp.float32)[:, :, None], (5, 5, 9))
            x = np.asarray(_spd_solve_direct(H, jnp.zeros((5, 9))))
            assert np.all(x == 0.0)

    @pytest.mark.parametrize("rows,dim,l1,optimizer,expected", [
        (1, 4, False, "LBFGS", "rank1"),
        (6, 1, False, "LBFGS", "dim1"),
        (6, 4, False, "LBFGS", "newton_direct"),
        (6, 32, False, "TRON", "newton_direct"),
        (6, 33, False, "LBFGS", "lbfgs"),
        (6, 33, False, "TRON", "tron"),
        (6, 4, True, "LBFGS", "owlqn"),
        (1, 4, True, "LBFGS", "owlqn"),
    ])
    def test_solver_path_by_shape(self, rows, dim, l1, optimizer, expected):
        from photon_ml_tpu.game.coordinates import _make_block_solver
        from photon_ml_tpu.optim.problem import OptimizerType

        solver = _make_block_solver("logistic", GlmOptimizationConfig(
            optimizer=OptimizerConfig(
                optimizer=OptimizerType[optimizer], max_iters=5),
            regularization=(RegularizationContext.l1() if l1
                            else RegularizationContext.l2()),
        ))
        block, *_ = _dense_block(np.random.default_rng(0), 4, rows, dim)
        assert solver.path(block) == expected
        w, n = solver.counted(
            block, jnp.zeros((4, rows), jnp.float32),
            jnp.zeros((4, dim), jnp.float32), jnp.asarray(0.1 * l1),
            jnp.asarray(1.0))
        assert w.shape == (4, dim) and n.shape == (4,)

    def test_buckets_name_their_solver(self, rng):
        """Every entry of the train span's ``buckets`` says which path its
        block took, from the shape test ``solve_block`` makes."""
        from photon_ml_tpu.game.coordinates import RandomEffectCoordinate

        users, rows = [], []
        for u, k in enumerate([1] * 6 + [3] * 5 + [9] * 4):
            users += [f"u{u}"] * k
            rows.append(k)
        n = len(users)
        X = sp.csr_matrix(rng.normal(size=(n, 3)).astype(np.float32))
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        ds = build_random_effect_dataset(
            np.array(users, dtype=object), X, y, np.ones(n, np.float32))
        coord = RandomEffectCoordinate(
            "per_user", ds, "logistic", GlmOptimizationConfig(
                optimizer=OptimizerConfig(max_iters=10),
                regularization=RegularizationContext.l2()),
            reg_weight=1.0)
        coord.train(jnp.zeros((n,), jnp.float32))
        buckets = coord.train_counts()["buckets"]
        assert len(buckets) == len(ds.blocks) >= 2
        for entry, block in zip(buckets, ds.blocks):
            want = "rank1" if block.rows_per_entity == 1 else "newton_direct"
            assert entry["solver"] == want
            assert entry["dim"] == block.block_dim

    @pytest.mark.parametrize("R,n_products", [(32, 3), (256, 4)])
    def test_body_makes_no_pass_over_the_hessian(self, R, n_products):
        """Structural guard: in one Newton body at (E, D, R) = (256, 21, 32)
        the only products are the ones with X (margin, gradient, damp; at
        256 rows an entity also the Hessian build, which short-row blocks
        make as elementwise pairs); none has the (., D, D) Hessian for an
        operand, so the solve makes no matvec pass."""
        import jax

        E, D = 256, 21
        block, *_rest, off = _dense_block(np.random.default_rng(0), E, R, D)
        solver = _smooth_solver()
        jaxpr = jax.make_jaxpr(solver.counted)(
            block, jnp.asarray(off), jnp.zeros((E, D), jnp.float32),
            jnp.asarray(0.0), jnp.asarray(1.0))

        def subjaxprs(eqn):
            for v in eqn.params.values():
                for c in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(c, "jaxpr", c)
                    if hasattr(inner, "eqns"):
                        yield inner

        def walk(jp, inside_while, found):
            for eqn in jp.eqns:
                if eqn.primitive.name == "dot_general" and inside_while:
                    found.append([tuple(v.aval.shape) for v in eqn.invars])
                if eqn.primitive.name == "while":
                    walk(eqn.params["body_jaxpr"].jaxpr, True, found)
                    continue
                for inner in subjaxprs(eqn):
                    walk(inner, inside_while, found)
            return found

        products = walk(jaxpr.jaxpr, False, [])
        # the outer Newton loop's own; the solve's loops add none
        assert len(products) == n_products, products
        for shapes in products:
            for shape in shapes:
                assert sorted(shape) != sorted((E, D, D)), products
            assert any(sorted(s) == sorted((E, R, D)) for s in shapes)


class TestDeferredNormFlush:
    """The CD loop defers score_norm readbacks to ONE end-of-run sync when
    nothing needs per-iteration values (game/descent.py flush) — history
    must come out identical to the logger-driven per-iteration path."""

    def _cd(self, rng):
        from photon_ml_tpu.data.dataset import make_glm_data
        from photon_ml_tpu.game.coordinates import (
            FixedEffectCoordinate,
            RandomEffectCoordinate,
        )
        from photon_ml_tpu.game.data import FixedEffectDataset
        from photon_ml_tpu.game.descent import CoordinateDescent

        prob = _mixed_effects_problem(rng, n_users=12)
        n = len(prob["response"])
        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=15),
            regularization=RegularizationContext.l2(),
        )
        fixed = FixedEffectCoordinate(
            "fixed",
            FixedEffectDataset(
                data=make_glm_data(
                    prob["shards"]["global"], prob["response"]
                ),
                n_global_rows=n,
            ),
            "logistic", opt, reg_weight=1.0,
        )
        re = RandomEffectCoordinate(
            "per_user",
            build_random_effect_dataset(
                prob["ids"]["userId"], prob["shards"]["per_user"],
                prob["response"], np.ones(n, np.float32),
            ),
            "logistic", opt, reg_weight=1.0, entity_key="userId",
        )
        return CoordinateDescent([fixed, re]), n

    def test_history_matches_logger_path(self, rng, tmp_path):
        from photon_ml_tpu.utils.logging import PhotonLogger

        cd, n = self._cd(rng)
        base = jnp.zeros(n, jnp.float32)
        quiet = cd.run(base, n_iterations=3)
        logged = cd.run(
            base, n_iterations=3, logger=PhotonLogger(str(tmp_path))
        )
        assert len(quiet.history) == len(logged.history) == 6
        for a, b in zip(quiet.history, logged.history):
            assert (a["iteration"], a["coordinate"]) == (
                b["iteration"], b["coordinate"],
            )
            assert a["score_norm"] == pytest.approx(
                b["score_norm"], rel=1e-6
            )
            assert np.isfinite(a["score_norm"])
        # The logger path logged one line per coordinate update.
        log_text = (tmp_path / "photon.log").read_text()
        assert log_text.count("score_norm") == 6

    def test_history_ordered_per_update(self, rng):
        cd, n = self._cd(rng)
        result = cd.run(jnp.zeros(n, jnp.float32), n_iterations=2)
        assert [
            (h["iteration"], h["coordinate"]) for h in result.history
        ] == [
            (0, "fixed"), (0, "per_user"), (1, "fixed"), (1, "per_user"),
        ]

    def test_empty_coordinate_list(self):
        from photon_ml_tpu.game.descent import CoordinateDescent

        result = CoordinateDescent([]).run(
            jnp.zeros(7, jnp.float32), n_iterations=2
        )
        assert result.history == [] and result.scores == {}


class TestBuilderDegenerateInputs:
    def test_all_zero_kept_rows_with_passive_features(self):
        """Capped entity whose KEPT (linspace) rows are all-zero while its
        passive rows carry features: the active-pair table is empty, every
        passive feature drops (projection onto an empty active subspace),
        and the build must not crash."""
        import scipy.sparse as sp

        X = np.zeros((5, 3), np.float32)
        X[1:4] = 1.0  # rows 0 and 4 (the linspace keeps for cap=2) empty
        ds = build_random_effect_dataset(
            np.array(["e"] * 5, dtype=object), sp.csr_matrix(X),
            np.zeros(5, np.float32), np.ones(5, np.float32),
            max_rows_per_entity=2, device=False,
        )
        assert len(ds.blocks) == 1
        assert np.all(np.asarray(ds.blocks[0].col_map) == -1)
        pb = ds.passive_blocks[0]
        assert pb is not None
        # Passive rows are present (scored) but their features dropped.
        assert np.all(np.asarray(pb.X) == 0)
        assert sorted(np.asarray(pb.row_index).ravel()[:3].tolist()) == [1, 2, 3]

    def test_task_alias_shares_solver_cache(self):
        from photon_ml_tpu.game.coordinates import _make_block_solver

        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=5),
            regularization=RegularizationContext.l2(),
        )
        assert _make_block_solver("logistic_regression", opt) is (
            _make_block_solver("logistic", opt)
        )


def _reference_group_build(entity_keys, rows_csr, labels, weights,
                           max_rows_per_entity=None):
    """Obviously-correct per-entity reference of the flat-array builder:
    the pre-vectorization algorithm (scipy slice per entity), kept as the
    differential oracle for the grouping/projection/bucket-fill pipeline."""
    from photon_ml_tpu.game.data import _round_up_geometric

    rows_csr = sp.csr_matrix(rows_csr)
    rows_csr.sum_duplicates()
    n_rows = rows_csr.shape[0]
    keys = np.asarray(entity_keys).astype(str)
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    starts = np.flatnonzero(np.concatenate([[True], sk[1:] != sk[:-1]]))
    groups = []
    for gi, start in enumerate(starts):
        end = starts[gi + 1] if gi + 1 < len(starts) else len(order)
        ridx = order[start:end]
        passive = np.empty(0, ridx.dtype)
        if max_rows_per_entity is not None and len(ridx) > max_rows_per_entity:
            keep = np.linspace(0, len(ridx) - 1, max_rows_per_entity).astype(int)
            mask = np.zeros(len(ridx), bool)
            mask[keep] = True
            passive = ridx[~mask]
            ridx = ridx[mask]
        sub = rows_csr[ridx]
        groups.append((sk[start], ridx, passive, np.unique(sub.indices), sub))
    buckets = {}
    for i, (_, ridx, _p, active, _s) in enumerate(groups):
        key = (_round_up_geometric(len(ridx), 2.0),
               _round_up_geometric(len(active), 2.0))
        buckets.setdefault(key, []).append(i)
    out = []
    for _key, members in sorted(buckets.items()):
        E = len(members)
        R = max(len(groups[gi][1]) for gi in members)
        D = max(1, max(len(groups[gi][3]) for gi in members))
        X = np.zeros((E, R, D), np.float32)
        lab = np.zeros((E, R), np.float32)
        wts = np.zeros((E, R), np.float32)
        cmap = np.full((E, D), -1, np.int32)
        rindex = np.full((E, R), n_rows, np.int32)
        ids = []
        maxp = max(len(groups[gi][2]) for gi in members)
        Xp = np.zeros((E, maxp, D), np.float32) if maxp else None
        rindexp = np.full((E, maxp), n_rows, np.int32) if maxp else None
        for lane, gi in enumerate(members):
            key, ridx, passive, active, sub = groups[gi]
            ids.append(key)
            cmap[lane, : len(active)] = active
            X[lane, : len(ridx), : len(active)] = sub[:, active].toarray()
            lab[lane, : len(ridx)] = labels[ridx]
            wts[lane, : len(ridx)] = weights[ridx]
            rindex[lane, : len(ridx)] = ridx
            if maxp and len(passive):
                Xp[lane, : len(passive), : len(active)] = (
                    rows_csr[passive][:, active].toarray()
                )
                rindexp[lane, : len(passive)] = passive
        out.append((ids, X, lab, wts, cmap, rindex, Xp, rindexp))
    return out


class TestBuilderDifferential:
    """Randomized differential test of the flat-array dataset builder
    against the per-entity reference algorithm it replaced."""

    @pytest.mark.parametrize("trial", range(6))
    def test_matches_per_entity_reference(self, trial):
        rng = np.random.default_rng(100 + trial)
        n = int(rng.integers(30, 400))
        d = int(rng.integers(1, 12))
        n_ent = int(rng.integers(1, 40))
        density = float(rng.uniform(0.05, 0.9))
        X = sp.random(n, d, density, "csr", dtype=np.float32,
                      random_state=int(rng.integers(1 << 30)))
        keys = np.array(
            [f"e{rng.integers(n_ent)}" for _ in range(n)], dtype=object
        )
        labels = rng.normal(size=n).astype(np.float32)
        weights = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
        cap = (
            None if trial % 2 == 0
            else int(rng.integers(1, max(2, n // max(1, n_ent))))
        )
        ds = build_random_effect_dataset(
            keys, X, labels, weights, max_rows_per_entity=cap, device=False,
        )
        ref = _reference_group_build(
            keys, X, labels, weights, max_rows_per_entity=cap
        )
        assert len(ds.blocks) == len(ref)
        for b, pb, ids, (rids, rX, rlab, rwts, rcmap, rrindex, rXp,
                         rrindexp) in zip(
            ds.blocks, ds.passive_blocks, ds.entity_ids, ref
        ):
            assert list(ids) == list(rids)
            np.testing.assert_array_equal(np.asarray(b.col_map), rcmap)
            np.testing.assert_array_equal(np.asarray(b.row_index), rrindex)
            np.testing.assert_array_equal(np.asarray(b.X), rX)
            np.testing.assert_array_equal(np.asarray(b.labels), rlab)
            np.testing.assert_array_equal(np.asarray(b.weights), rwts)
            if rXp is None:
                assert pb is None
            else:
                # the flat passive rows, through their lane-aligned view
                pb = pb.lane_aligned(b, len(keys))
                np.testing.assert_array_equal(np.asarray(pb.X), rXp)
                np.testing.assert_array_equal(
                    np.asarray(pb.row_index), rrindexp
                )


def _fill_problem(case):
    """Inputs of one grouping for the fill's parity test: skewed entities
    (some of one row, one alone in its bucket), duplicate entries summed
    before the fill, and per case a cap, the width, dtypes and a passive
    entry set that leaves some (entity, column) pairs inactive."""
    rng = np.random.default_rng(3800 + sorted(FILL_CASES).index(case))
    d, cap = FILL_CASES[case]
    # "threads": over 2^21 entries, so the native fill runs a team
    n_mid = 6000 if case == "threads" else 30
    sizes = np.concatenate([
        np.ones(5, np.int64), rng.integers(2, 400 if n_mid > 30 else 40,
                                           size=n_mid), [300]])
    n = int(sizes.sum())
    keys = np.repeat(np.arange(len(sizes)), sizes)
    rng.shuffle(keys)
    nnz = 3 * n
    r = rng.integers(0, n, size=nnz)
    c = rng.integers(0, d, size=nnz)
    if case == "inactive_passive":
        # the heavy entity's rows all on column 0 but one passive row,
        # which alone holds columns 1 and 2: its entries there must drop
        heavy = np.flatnonzero(keys == len(sizes) - 1)
        c[np.isin(r, heavy)] = 0
        r = np.concatenate([r, [heavy[1], heavy[1]]])
        c = np.concatenate([c, [1, 2]])
    dtype = np.float64 if case == "float64" else np.float32
    v = rng.normal(size=len(r)).astype(dtype)
    X = sp.csr_matrix(sp.coo_matrix((v, (r, c)), shape=(n, d)))
    if case == "string_keys":
        keys = np.array([f"e{k}" for k in keys], dtype=object)
    labels = rng.normal(size=n).astype(dtype)
    weights = rng.uniform(0.5, 2.0, size=n).astype(dtype)
    return keys, X, labels, weights, cap


#: case -> (feature width, active-row cap)
FILL_CASES = {
    "no_cap": (9, None),
    "capped": (9, 6),
    "dim_1": (1, 3),
    "inactive_passive": (3, 4),
    "float64": (5, 5),
    "string_keys": (6, 8),
    "pair_sort": (7, 5),
    "threads": (9, 64),
}


def _fill_blocks(monkeypatch, case, tile, native):
    """``_group_entities``' output for ``case`` by the native fill or the
    numpy chain, and the method its ``game.group.fill`` span read."""
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.game import data as game_data

    if native:
        monkeypatch.delenv("PHOTON_NO_NATIVE", raising=False)
    else:
        monkeypatch.setenv("PHOTON_NO_NATIVE", "1")
    if case == "pair_sort":  # the (entity, column) pairs by np.unique
        monkeypatch.setattr(game_data, "_PAIR_TABLE_CELLS", 0)
    keys, X, labels, weights, cap = _fill_problem(case)
    X.sum_duplicates()
    before = {r["id"] for r in telemetry.layer_spans()}
    out = game_data._group_entities(
        keys, X, labels, weights, cap, 2.0, "geometric", 16, 0, tile)
    (fill,) = [r for r in telemetry.layer_spans()
               if r["id"] not in before and r["name"] == "game.group.fill"]
    return out, fill["attrs"]["method"]


def _assert_bitwise(a, b):
    assert a.keys() == b.keys()
    for k in a:
        if isinstance(a[k], np.ndarray):
            assert (a[k].dtype, a[k].shape) == (b[k].dtype, b[k].shape), k
            assert a[k].tobytes() == b[k].tobytes(), k
        else:
            assert a[k] == b[k], k


class TestNativeGroupFill:
    """The native fill (native/group_fill.cpp) against the numpy chain it
    replaces, which stays as the fall-back: every array of every block and
    passive block bit for bit, both storage orders."""

    @pytest.mark.parametrize("tile", [None, (8, 128)], ids=["dense", "tpu"])
    @pytest.mark.parametrize("case", sorted(FILL_CASES))
    def test_matches_numpy_fill(self, monkeypatch, case, tile):
        got, method = _fill_blocks(monkeypatch, case, tile, native=True)
        want, ref_method = _fill_blocks(monkeypatch, case, tile, native=False)
        assert (method, ref_method) == ("native", "numpy")
        assert got["entity_to_slot"] == want["entity_to_slot"]
        assert got["entity_ids"] == want["entity_ids"]
        assert got["block_rows_real"] == want["block_rows_real"]
        assert len(got["blocks"]) == len(want["blocks"])
        for a, b in zip(got["blocks"], want["blocks"]):
            _assert_bitwise(a, b)
        assert [p is None for p in got["passive_blocks"]] == [
            p is None for p in want["passive_blocks"]]
        for a, b in zip(got["passive_blocks"], want["passive_blocks"]):
            if a is not None:
                _assert_bitwise(a, b)
        # what the case is there to reach was reached
        passive = [p for p in got["passive_blocks"] if p is not None]
        cap = FILL_CASES[case][1]
        assert bool(passive) == (cap is not None)
        assert any(b["rows_per_entity"] == 1 for b in got["blocks"])
        if cap is None:  # the 300-row entity is a bucket of its own
            assert any(b["n_entities"] == 1 for b in got["blocks"])
        if case == "dim_1":
            assert {b["block_dim"] for b in got["blocks"]} == {1}
        if tile is not None:  # rows-minor storage, by blocks or passive rows
            assert "r" in {b["x_minor"] for b in got["blocks"] + passive}
        if case == "inactive_passive":  # some passive entries dropped
            _keys, X, *_ = _fill_problem(case)
            rows = np.concatenate([p["row_index"][:p["n_rows"]]
                                   for p in passive])
            held = sum(np.count_nonzero(p["X"]) for p in passive)
            assert 0 < held < X[rows].nnz

    def test_no_native_reads_numpy(self, monkeypatch):
        out, method = _fill_blocks(monkeypatch, "capped", None, native=False)
        assert method == "numpy" and out["blocks"]


class TestPartialRetraining:
    """Locked coordinates (the reference's partial retraining): held at
    the prior model, contributing scores but never retrained."""

    def _fit(self, prob, **kw):
        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=40),
            regularization=RegularizationContext.l2(),
        )
        est = GameEstimator(
            "logistic",
            {
                "fixed": FixedEffectCoordinateConfig(
                    "global", opt, reg_weight=1.0
                ),
                "per_user": RandomEffectCoordinateConfig(
                    "per_user", "userId", opt, reg_weight=1.0
                ),
            },
            n_iterations=2,
        )
        model, hist = est.fit(
            prob["shards"], prob["ids"], prob["response"], **kw
        )
        return est, model, hist

    def test_locked_submodel_passes_through_verbatim(self, rng):
        prob = _mixed_effects_problem(rng, n_users=15)
        est, base_model, _ = self._fit(prob)
        _, model2, hist2 = self._fit(
            prob, initial_model=base_model,
            locked_coordinates=("per_user",),
        )
        # Identical per-entity tables, the SAME object carried through.
        assert model2.models["per_user"] is base_model.models["per_user"]
        # Only the fixed coordinate produced history entries.
        assert {h["coordinate"] for h in hist2} == {"fixed"}
        assert len(hist2) == 2

    @pytest.mark.parametrize("seed", [20260729, 2, 4])
    def test_locked_matches_manual_offsets(self, seed):
        """Training fixed against a locked per_user must equal training
        fixed alone with per_user's scores as base offsets: both reach the
        float64 optimum of that one problem as nearly as float32 can."""
        import scipy.optimize

        prob = _mixed_effects_problem(
            np.random.default_rng(seed), n_users=15)
        est, base_model, _ = self._fit(prob)
        _, model_locked, _ = self._fit(
            prob, initial_model=base_model,
            locked_coordinates=("per_user",),
        )
        from photon_ml_tpu.game.model import GameModel

        user_scores = np.asarray(
            GameTransformer(
                GameModel(
                    models={"per_user": base_model.models["per_user"]},
                    task="logistic",
                )
            ).transform(prob["shards"], prob["ids"])
        )
        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=40),
            regularization=RegularizationContext.l2(),
        )
        fixed_only = GameEstimator(
            "logistic",
            {"fixed": FixedEffectCoordinateConfig("global", opt, reg_weight=1.0)},
            n_iterations=2,
        )
        model_manual, _ = fixed_only.fit(
            prob["shards"], prob["ids"], prob["response"],
            offset=user_scores,
        )
        w_locked = np.asarray(
            model_locked.models["fixed"].model.coefficients.means
        )
        w_manual = np.asarray(
            model_manual.models["fixed"].model.coefficients.means
        )

        X = prob["shards"]["global"].toarray().astype(np.float64)
        y = prob["response"].astype(np.float64)
        off = user_scores.astype(np.float64)

        def objective(w):
            m = X @ w + off
            value = np.sum(np.logaddexp(0.0, m) - y * m) + 0.5 * w @ w
            return value, X.T @ (1.0 / (1.0 + np.exp(-m)) - y) + w

        best = scipy.optimize.minimize(
            objective, np.zeros(X.shape[1]), jac=True, method="L-BFGS-B",
            options=dict(gtol=1e-12, ftol=1e-16, maxiter=1000))
        # Each side is a float32 L-BFGS solve that stops where its value
        # repeats, so each is held to the float64 optimum's VALUE within 4
        # float32 ulps (measured: at most 1.1, over these seeds and 1, 3,
        # 5, on the parent and with the direct Newton solve).  Offsets
        # wired wrongly move the value by 1e-3 of itself.
        for w in (w_locked, w_manual):
            gap = objective(w.astype(np.float64))[0] - best.fun
            assert abs(gap) <= 4 * np.finfo(np.float32).eps * best.fun
        # A value that flat leaves the coefficients 1e-4 to 7e-4 apart,
        # from one seed to the next and from one start to another: the
        # old bound here (rtol 2e-4, atol 2e-5) was inside that.  The
        # parent commit, with this test as it was, passes at the fixture's
        # seed (largest excess over the bound -5.1e-5) and at 1 and 5,
        # and fails at 2 (+1.3e-6), 3 (+3.2e-5) and 4 (+5.4e-4, the sides
        # 6.7e-4 apart); a stop at 1e-9 or 1e-12 and TRON land no nearer.
        # Offsets wired wrongly move the coefficients by 1e-1.
        np.testing.assert_allclose(w_locked, w_manual, rtol=1e-3, atol=1e-3)

    def test_locked_requires_initial_model(self, rng):
        prob = _mixed_effects_problem(rng, n_users=15)
        with pytest.raises(ValueError, match="initial_model"):
            self._fit(prob, locked_coordinates=("per_user",))

    def test_locked_unknown_coordinate_rejected(self, rng):
        prob = _mixed_effects_problem(rng, n_users=15)
        _, base_model, _ = self._fit(prob)
        with pytest.raises(ValueError, match="not in the initial model"):
            self._fit(
                prob, initial_model=base_model,
                locked_coordinates=("nope",),
            )

    def test_resume_with_changed_locked_set_rejected(self, rng, tmp_path):
        from photon_ml_tpu.io.checkpoint import CoordinateDescentCheckpointer

        prob = _mixed_effects_problem(rng, n_users=15)
        est, base_model, _ = self._fit(prob)
        ckpt = CoordinateDescentCheckpointer(str(tmp_path / "cd"))
        # Checkpoint a run that trained everything...
        self._fit(prob, checkpointer=ckpt)
        # ...then resuming with a locked coordinate must refuse.
        with pytest.raises(ValueError, match="locked coordinates"):
            self._fit(
                prob, initial_model=base_model,
                locked_coordinates=("per_user",), checkpointer=ckpt,
            )

    def test_all_locked_rejected(self, rng):
        prob = _mixed_effects_problem(rng, n_users=15)
        _, base_model, _ = self._fit(prob)
        with pytest.raises(ValueError, match="nothing to train"):
            self._fit(
                prob, initial_model=base_model,
                locked_coordinates=("fixed", "per_user"),
            )

    def test_locked_factored_rejected_up_front(self, rng):
        """A factored coordinate's saved sub-model can't be locked (its
        (u, V) state is not reconstructible) — the estimator must say so
        accurately instead of descent's generic message."""
        prob = _mixed_effects_problem(rng, n_users=15)
        opt = GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=20),
            regularization=RegularizationContext.l2(),
        )
        est = GameEstimator(
            "logistic",
            {
                "fixed": FixedEffectCoordinateConfig(
                    "global", opt, reg_weight=1.0
                ),
                "per_user": FactoredRandomEffectCoordinateConfig(
                    "per_user", "userId", rank=2, optimization=opt,
                    reg_weight=1.0,
                ),
            },
            n_iterations=1,
        )
        model, _ = est.fit(prob["shards"], prob["ids"], prob["response"])
        with pytest.raises(ValueError, match="not reconstructible"):
            est.fit(
                prob["shards"], prob["ids"], prob["response"],
                initial_model=model, locked_coordinates=("per_user",),
            )
