"""Auxiliary subsystem tests: down-sampling, hyperparameter search, tracker."""

import os

import numpy as np
import pytest

from photon_ml_tpu.data.sampling import (
    BinaryClassificationDownSampler,
    DefaultDownSampler,
)
from photon_ml_tpu.hyperparameter.search import (
    GaussianProcessModel,
    GaussianProcessSearch,
    RandomSearch,
    expected_improvement,
)


class TestDownSampling:
    def test_default_unbiased_weight_sum(self, rng):
        n = 20000
        labels = (rng.uniform(size=n) < 0.5).astype(float)
        weights = np.ones(n)
        idx, w = DefaultDownSampler(0.25, seed=1).downsample(labels, weights)
        # Survivor weight sum ≈ original weight sum (unbiased).
        assert abs(w.sum() - n) / n < 0.05
        assert len(idx) == pytest.approx(n * 0.25, rel=0.1)

    def test_binary_keeps_all_positives(self, rng):
        n = 10000
        labels = (rng.uniform(size=n) < 0.05).astype(float)  # 5% positive
        weights = np.ones(n)
        idx, w = BinaryClassificationDownSampler(0.1, seed=2).downsample(
            labels, weights
        )
        kept = labels[idx]
        assert kept.sum() == labels.sum()  # every positive kept, weight 1
        np.testing.assert_allclose(w[kept > 0], 1.0)
        # Kept negatives re-weighted to preserve total negative mass.
        neg_mass = w[kept == 0].sum()
        assert abs(neg_mass - (n - labels.sum())) / n < 0.05

    def test_invalid_rate_rejected(self):
        with pytest.raises(ValueError):
            DefaultDownSampler(0.0)
        with pytest.raises(ValueError):
            BinaryClassificationDownSampler(1.5)


class TestHyperparameterSearch:
    def test_random_search_finds_decent_point(self):
        def f(x):
            return float((x[0] - 3.0) ** 2 + (x[1] + 1.0) ** 2)

        res = RandomSearch([(0, 5), (-3, 3)], seed=4).find(f, 60)
        assert res.best_value < 0.5
        assert len(res.history) == 60

    def test_gp_posterior_interpolates(self):
        X = np.array([[0.0], [0.5], [1.0]])
        y = np.array([1.0, 0.0, 1.0])
        gp = GaussianProcessModel().fit(X, y)
        mean, std = gp.predict(X)
        np.testing.assert_allclose(mean, y, atol=1e-2)
        assert np.all(std < 0.1)
        # Uncertainty grows away from data.
        _, std_far = gp.predict(np.array([[0.25]]))
        assert std_far[0] > std[0]

    def test_ei_prefers_low_mean_and_high_std(self):
        ei = expected_improvement(
            np.array([0.0, 1.0]), np.array([0.1, 0.1]), best=0.5
        )
        assert ei[0] > ei[1]
        ei2 = expected_improvement(
            np.array([1.0, 1.0]), np.array([1.0, 0.01]), best=0.5
        )
        assert ei2[0] > ei2[1]

    def test_gp_search_beats_random_on_smooth_objective(self):
        def f(x):
            return float(np.sin(3 * x[0]) + 0.3 * (x[0] - 4.0) ** 2)

        budget = 18
        gp = GaussianProcessSearch([(0.0, 8.0)], seed=5).find(f, budget)
        assert gp.best_value < 0.1  # true min ≈ -0.04 near x≈4.5
        assert len(gp.history) == budget

    def test_gp_search_log_scale_and_priors(self):
        # Optimum at lambda = 1e-2 on a log-scaled axis.
        def f(x):
            return float((np.log10(x[0]) + 2.0) ** 2)

        priors = [(np.array([1.0]), f(np.array([1.0])))]
        res = GaussianProcessSearch(
            [(1e-4, 1e2)], log_scale=True, seed=6
        ).find(f, 15, priors=priors)
        assert res.best_value < 0.1
        # History includes the prior.
        assert len(res.history) == 16

    def test_maximize_mode(self):
        def f(x):
            return float(-((x[0] - 2.0) ** 2))  # max at x=2

        res = GaussianProcessSearch([(0.0, 5.0)], seed=7).find(
            f, 15, maximize=True
        )
        assert abs(res.best_params[0] - 2.0) < 0.3


class TestCompileCache:
    """The one compile-cache resolver (utils/compile_cache.py): the
    directory is $JAX_COMPILATION_CACHE_DIR when set — and then nothing
    is set in code — else the fixed <checkout>/.jax_cache."""

    @pytest.fixture(autouse=True)
    def _restore_jax_cache_config(self):
        """These tests mutate process-global JAX config; restore it so
        later tests keep the suite's cache settings (conftest.py)."""
        import jax
        from jax._src import compilation_cache as _cc

        prev_dir = jax.config.jax_compilation_cache_dir
        prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
        yield
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prev_min
        )
        _cc.reset_cache()

    def test_env_set_uses_that_dir_and_sets_no_other(
        self, monkeypatch, tmp_path
    ):
        import jax
        from jax._src import compilation_cache as _cc

        from photon_ml_tpu.utils import compile_cache

        target = str(tmp_path / "envcache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", target)
        # The state a process STARTED with the variable is in: JAX reads
        # it into its config and opens the cache there at first compile.
        jax.config.update("jax_compilation_cache_dir", target)
        _cc.reset_cache()
        dir_updates = []
        real_update = jax.config.update

        def spy(name, value):
            if name == "jax_compilation_cache_dir":
                dir_updates.append(value)
            return real_update(name, value)

        monkeypatch.setattr(jax.config, "update", spy)
        assert compile_cache.cache_dir() == target
        got = compile_cache.enable_compile_cache("auto", min_compile_secs=0.0)
        assert got == target and os.path.isdir(target)
        assert dir_updates == []  # the directory was never set in code
        assert jax.config.jax_compilation_cache_dir == target
        # A jitted computation lands an entry there.  The baked-in
        # constant makes the HLO unique so an in-memory executable from an
        # earlier test can't satisfy it without a fresh compile.
        const = float(np.random.default_rng().uniform(1.0, 2.0))
        jax.jit(lambda x: x * 2.0 + const)(
            jax.numpy.ones((8, 8))
        ).block_until_ready()
        assert len(os.listdir(target)) >= 1

    def test_env_unset_uses_fixed_path_in_checkout(self, monkeypatch):
        import jax

        from photon_ml_tpu.utils import compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert compile_cache.cache_dir() == want
        first = compile_cache.enable_compile_cache("auto")
        second = compile_cache.enable_compile_cache()
        assert first == second == want  # never a temp name, pid or clock
        assert jax.config.jax_compilation_cache_dir == want

    def test_off_disables_and_paths_are_refused(self, tmp_path):
        import jax

        from photon_ml_tpu.utils import compile_cache

        assert compile_cache.enable_compile_cache("auto") is not None
        assert compile_cache.enable_compile_cache("off") is None
        assert jax.config.jax_compilation_cache_dir is None
        # The directory is not a per-call argument any more.
        with pytest.raises(ValueError, match="'auto' or 'off'"):
            compile_cache.enable_compile_cache(str(tmp_path / "elsewhere"))

    def test_uncreatable_dir_degrades_to_off(self, monkeypatch, tmp_path):
        from photon_ml_tpu.utils import compile_cache

        blocker = tmp_path / "file"
        blocker.write_text("not a directory")
        monkeypatch.setenv(
            "JAX_COMPILATION_CACHE_DIR", str(blocker / "sub")
        )
        assert compile_cache.enable_compile_cache("auto") is None


class TestMarginalLikelihoodFit:
    """length_scale='fit': type-II ML over a log grid (VERDICT r2 weak #6)."""

    def test_recovers_scale_ordering(self):
        """Smooth data must select a longer length scale than jagged data."""
        rng = np.random.default_rng(0)
        X = rng.uniform(size=(25, 1))
        y_smooth = np.sin(2.0 * np.pi * X[:, 0] * 0.5)
        y_jagged = np.sin(2.0 * np.pi * X[:, 0] * 6.0)
        ls_smooth = GaussianProcessModel("fit").fit(
            X, y_smooth
        ).fitted_length_scale
        ls_jagged = GaussianProcessModel("fit").fit(
            X, y_jagged
        ).fitted_length_scale
        assert ls_smooth > ls_jagged

    def test_fit_improves_interpolation_vs_bad_fixed_scale(self):
        rng = np.random.default_rng(1)
        X = rng.uniform(size=(30, 2))
        y = np.sin(3 * X[:, 0]) + np.cos(5 * X[:, 1])
        Xq = rng.uniform(size=(50, 2))
        yq = np.sin(3 * Xq[:, 0]) + np.cos(5 * Xq[:, 1])
        mean_fit, _ = GaussianProcessModel("fit").fit(X, y).predict(Xq)
        mean_bad, _ = GaussianProcessModel(5.0).fit(X, y).predict(Xq)
        assert np.mean((mean_fit - yq) ** 2) < np.mean((mean_bad - yq) ** 2)

    def test_invalid_length_scale_rejected(self):
        with pytest.raises(ValueError):
            GaussianProcessModel("auto")

    def test_gp_fit_beats_random_in_fewer_evals(self):
        """The VERDICT acceptance bar: the fitted-GP search reaches a
        better optimum on a known 2-D response surface than random search
        gets with MORE evaluations."""

        def branin_like(x):
            # Smooth 2-D bowl with a unique optimum at (0.65, 0.35).
            return (
                (x[0] - 0.65) ** 2 + (x[1] - 0.35) ** 2
                + 0.3 * np.sin(4 * x[0]) * np.sin(4 * x[1])
            )

        bounds = [(0.0, 1.0), (0.0, 1.0)]
        gp = GaussianProcessSearch(
            bounds, seed=7, n_seed_points=4, length_scale="fit"
        ).find(branin_like, n_iterations=15)
        rnd = RandomSearch(bounds, seed=7).find(branin_like, n_iterations=30)
        assert gp.best_value < rnd.best_value
        assert len(gp.history) == 15 and len(rnd.history) == 30
