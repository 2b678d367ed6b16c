"""Tuning subsystem tests: proposers, ASHA, executor, journal resume.

Also the first dedicated coverage of hyperparameter/search.py's GP
internals (previously only exercised incidentally via test_aux.py):
Cholesky jitter escalation and duplicate-point handling.
"""

import json
import os
import threading

import numpy as np
import pytest

from photon_ml_tpu.hyperparameter.search import (
    GaussianProcessModel,
    GaussianProcessSearch,
    _chol_with_jitter,
)
from photon_ml_tpu.tuning.executor import (
    TrialReport,
    TuningConfig,
    TuningOrchestrator,
)
from photon_ml_tpu.tuning.scheduler import (
    AshaConfig,
    AshaScheduler,
    GPProposer,
    GridProposer,
    RandomProposer,
    SearchSpace,
    make_proposer,
)
from photon_ml_tpu.tuning.state import (
    STATE_RECORD_TYPES,
    ResumeMismatch,
    SearchAborted,
    TrialStore,
    TuningJournal,
    replay_journal,
)
from photon_ml_tpu.utils.watchdog import RetryPolicy


def _cfg(**kw):
    kw.setdefault("max_trials", 8)
    kw.setdefault("workers", 2)
    kw.setdefault("retry", RetryPolicy())
    kw.setdefault("sleep", lambda s: None)
    return TuningConfig(**kw)


def _decisions(journal):
    """State-bearing journal records minus run-local noise."""
    out = []
    for rec in journal.read():
        if rec.get("type") in STATE_RECORD_TYPES:
            rec = {
                k: v for k, v in rec.items()
                if k not in ("wall", "wall_epoch")
            }
            out.append(rec)
    return out


class TestSearchSpace:
    def test_validation(self):
        with pytest.raises(ValueError):
            SearchSpace.create([(1.0, 1.0)])
        with pytest.raises(ValueError):
            SearchSpace.create([(0.0, 1.0)], log_scale=True)
        with pytest.raises(ValueError):
            SearchSpace.create([(0.0, 1.0)], names=["a", "b"])

    def test_fingerprint_tracks_geometry(self):
        a = SearchSpace.create([(1e-3, 1e3)], log_scale=True, names=["lam"])
        b = SearchSpace.create([(1e-3, 1e3)], log_scale=True, names=["lam"])
        c = SearchSpace.create([(1e-3, 1e2)], log_scale=True, names=["lam"])
        d = SearchSpace.create([(1e-3, 1e3)], log_scale=False, names=["lam"])
        assert a.fingerprint() == b.fingerprint()
        assert a.fingerprint() != c.fingerprint()
        assert a.fingerprint() != d.fingerprint()
        assert SearchSpace.from_config(a.to_config()) == a

    def test_sample_and_normalize(self):
        sp = SearchSpace.create(
            [(1e-2, 1e2), (-1.0, 3.0)], log_scale=[True, False]
        )
        X = sp.sample(np.random.default_rng(0), 200)
        assert X.shape == (200, 2)
        assert np.all(X[:, 0] >= 1e-2) and np.all(X[:, 0] <= 1e2)
        assert np.all(X[:, 1] >= -1.0) and np.all(X[:, 1] <= 3.0)
        Z = sp.normalize(X)
        assert np.all(Z >= 0.0) and np.all(Z <= 1.0)
        # Log dimension: the geometric midpoint maps to 0.5.
        z = sp.normalize(np.array([[1.0, 1.0]]))
        assert z[0, 0] == pytest.approx(0.5)
        assert z[0, 1] == pytest.approx(0.5)


class TestProposers:
    def test_random_deterministic_and_rng_roundtrip(self):
        sp = SearchSpace.create([(0.0, 1.0)] * 2)
        a, b = RandomProposer(sp, seed=3), RandomProposer(sp, seed=3)
        np.testing.assert_array_equal(a.ask(), b.ask())
        state = a.rng_state
        x1 = a.ask()
        a.set_rng_state(state)
        np.testing.assert_array_equal(a.ask(), x1)

    def test_pending_bookkeeping(self):
        sp = SearchSpace.create([(0.0, 1.0)])
        p = RandomProposer(sp, seed=0)
        x1, x2 = p.ask(), p.ask()
        assert len(p.pending) == 2
        p.tell(x1, 0.5)
        assert len(p.pending) == 1 and len(p.observations) == 1
        p.resolve(x2)
        assert not p.pending and len(p.observations) == 1

    def test_grid_order_exhaustion_restore(self):
        sp = SearchSpace.create([(0.0, 10.0)])
        g = GridProposer(sp, [[1.0], [2.0], [3.0]])
        assert g.ask()[0] == 1.0 and g.ask()[0] == 2.0
        assert not g.exhausted()
        assert g.ask()[0] == 3.0
        assert g.exhausted()
        g2 = GridProposer(sp, [[1.0], [2.0], [3.0]])
        g2.restore_ask(np.array([1.0]))
        assert g2.ask()[0] == 2.0

    def test_gp_constant_liar_batch_is_diverse(self):
        """With pending asks imputed at the incumbent, a batch of asks
        must not collapse onto one EI argmax."""
        sp = SearchSpace.create([(0.0, 1.0)])
        p = GPProposer(sp, seed=2, n_seed_points=2, n_candidates=128)
        # Two observations bracketing a clear minimum at 0.4.
        p.tell(np.array([0.2]), 0.04)
        p.tell(np.array([0.8]), 0.16)
        batch = [p.ask() for _ in range(4)]
        assert len(p.pending) == 4
        flat = [float(x[0]) for x in batch]
        assert len({round(v, 6) for v in flat}) == 4, flat
        assert all(0.0 <= v <= 1.0 for v in flat)

    def test_gp_cold_start_is_random_then_model_based(self):
        sp = SearchSpace.create([(0.0, 1.0)])
        p = GPProposer(sp, seed=5, n_seed_points=3)
        xs = [p.ask() for _ in range(3)]  # all cold-start samples
        for x, y in zip(xs, [0.5, 0.2, 0.9]):
            p.tell(x, y)
        x_gp = p.ask()  # surrogate path
        assert 0.0 <= float(x_gp[0]) <= 1.0

    def test_make_proposer_rejects_unknown(self):
        sp = SearchSpace.create([(0.0, 1.0)])
        with pytest.raises(ValueError):
            make_proposer("annealing", sp)


class TestGPRobustness:
    """Satellite: escalating Cholesky jitter + duplicate de-duplication
    in hyperparameter/search.py."""

    def test_duplicate_observations_do_not_crash_fit(self):
        X = np.array([[0.3], [0.3], [0.7], [0.7], [0.7]])
        y = np.array([1.0, 3.0, 2.0, 2.0, 2.0])
        gp = GaussianProcessModel().fit(X, y)
        mean, std = gp.predict(np.array([[0.3], [0.7]]))
        # Duplicates average: posterior interpolates the merged targets.
        assert mean[0] == pytest.approx(2.0, abs=0.1)
        assert mean[1] == pytest.approx(2.0, abs=0.1)
        assert np.all(np.isfinite(std))

    def test_near_duplicates_merge(self):
        X = np.array([[0.5], [0.5 + 1e-12], [0.9]])
        gp = GaussianProcessModel().fit(X, np.array([1.0, 2.0, 0.0]))
        assert gp._X.shape[0] == 2

    def test_jitter_ladder_recovers_psd(self):
        # Rank-1 PSD matrix: exact Cholesky fails, jitter succeeds.
        K = np.ones((6, 6))
        L = _chol_with_jitter(K)
        assert np.all(np.isfinite(L))

    def test_jitter_ladder_gives_up_loudly(self):
        with pytest.raises(np.linalg.LinAlgError, match="jitter"):
            _chol_with_jitter(-np.eye(3))

    def test_search_survives_duplicate_priors(self):
        def f(x):
            return float((x[0] - 2.0) ** 2)

        prior = (np.array([1.0]), f(np.array([1.0])))
        res = GaussianProcessSearch([(0.0, 5.0)], seed=1).find(
            f, 8, priors=[prior, prior, prior]
        )
        assert np.isfinite(res.best_value)


class TestAsha:
    def test_resource_geometry(self):
        cfg = AshaConfig(min_resource=2, reduction_factor=3, num_rungs=3)
        assert [cfg.resource(r) for r in range(3)] == [2, 6, 18]
        assert cfg.top_rung == 2

    def test_promote_kill_sequence(self):
        s = AshaScheduler(AshaConfig(1, 2, 3))
        # First report at a rung is trivially top — promoted.
        assert s.report(0, 0, 0.5) == "promote"
        # Worse than the incumbent with keep=max(1, 2//2)=1 — killed.
        assert s.report(1, 0, 0.9) == "stop"
        # n=3, keep=1: only the best of {0.5, 0.9, 0.1} promotes.
        assert s.report(2, 0, 0.1) == "promote"
        assert s.decide(0, 0) == "stop"
        # Top rung always completes.
        assert s.report(2, 2, 0.1) == "complete"

    def test_ties_break_by_trial_id(self):
        s = AshaScheduler(AshaConfig(1, 2, 2))
        s.record(0, 0, 0.5)
        s.record(1, 0, 0.5)
        assert s.decide(0, 0) == "promote"
        assert s.decide(1, 0) == "stop"

    def test_record_then_decide_matches_report(self):
        a = AshaScheduler(AshaConfig(1, 3, 2))
        b = AshaScheduler(AshaConfig(1, 3, 2))
        rng = np.random.default_rng(0)
        for i in range(9):
            y = float(rng.uniform())
            da = a.report(i, 0, y)
            b.record(i, 0, y)
            assert da == b.decide(i, 0)

    def test_config_validation(self):
        with pytest.raises(ValueError):
            AshaConfig(min_resource=0)
        with pytest.raises(ValueError):
            AshaConfig(reduction_factor=1)


class TestExecutor:
    def test_simple_search_finds_minimum(self, tmp_path):
        sp = SearchSpace.create([(0.0, 1.0)])
        journal = TuningJournal(str(tmp_path))
        res = TuningOrchestrator(
            sp, lambda p, r, w: float((p[0] - 0.37) ** 2),
            RandomProposer(sp, seed=4),
            _cfg(max_trials=12, workers=3), journal,
        ).run()
        journal.close()
        assert res.completed == 12 and res.failed == 0
        assert abs(res.best_params[0] - 0.37) < 0.2
        assert len(res.trials) == 12
        assert {t["status"] for t in res.trials} == {"completed"}

    def test_warm_start_chains_from_nearest_completed(self, tmp_path):
        sp = SearchSpace.create([(0.0, 10.0)])
        seen = {}
        lock = threading.Lock()

        def fn(p, r, w):
            x = float(p[0])
            with lock:
                seen[x] = None if w is None else float(np.asarray(w)[0])
            return TrialReport(
                metric=x, coefficients=np.array([x], np.float32)
            )

        journal = TuningJournal(str(tmp_path))
        TuningOrchestrator(
            sp, fn, GridProposer(sp, [[1.0], [2.0], [9.0]]),
            _cfg(max_trials=3, workers=1), journal,
        ).run()
        journal.close()
        assert seen[1.0] is None  # nothing completed yet
        assert seen[2.0] == 1.0  # nearest completed is 1.0
        assert seen[9.0] == 2.0  # 2.0 is nearer than 1.0

    def test_warm_start_disabled(self, tmp_path):
        sp = SearchSpace.create([(0.0, 10.0)])
        warm = []

        def fn(p, r, w):
            warm.append(w)
            return TrialReport(0.0, coefficients=np.zeros(1))

        journal = TuningJournal(str(tmp_path))
        TuningOrchestrator(
            sp, fn, GridProposer(sp, [[1.0], [2.0]]),
            _cfg(max_trials=2, workers=1, warm_start=False), journal,
        ).run()
        journal.close()
        assert warm == [None, None]

    def test_fatal_failure_marks_trial_and_continues(self, tmp_path):
        sp = SearchSpace.create([(0.0, 1.0)])

        def fn(p, r, w):
            if p[0] > 0.55 and p[0] < 0.65:
                raise ValueError("bad hyperparameters")
            return float(p[0])

        journal = TuningJournal(str(tmp_path))
        res = TuningOrchestrator(
            sp, fn, GridProposer(sp, [[0.1], [0.6], [0.9]]),
            _cfg(max_trials=3), journal,
        ).run()
        journal.close()
        assert res.failed == 1 and res.completed == 2
        failed = [t for t in res.trials if t["status"] == "failed"]
        assert len(failed) == 1
        assert "bad hyperparameters" in failed[0]["error"]
        assert res.best_metric == 0.1  # minimize; search continued

    def test_transient_failure_retries_in_place(self, tmp_path):
        sp = SearchSpace.create([(0.0, 1.0)])
        attempts = []
        sleeps = []

        def fn(p, r, w):
            attempts.append(float(p[0]))
            if len(attempts) == 1:
                raise RuntimeError("UNAVAILABLE: Socket closed")
            return 0.0

        journal = TuningJournal(str(tmp_path))
        res = TuningOrchestrator(
            sp, fn, GridProposer(sp, [[0.5]]),
            _cfg(
                max_trials=1, workers=1,
                retry=RetryPolicy(max_retries=2, backoff_seconds=7.0),
                sleep=sleeps.append,
            ),
            journal,
        ).run()
        journal.close()
        assert len(attempts) == 2 and res.completed == 1
        assert sleeps == [7.0]
        assert res.trials[0]["retries"] == 1
        kinds = [r["type"] for r in journal.read()]
        assert "retry" in kinds and "fail" not in kinds

    def test_transient_budget_exhausted_fails(self, tmp_path):
        sp = SearchSpace.create([(0.0, 1.0)])

        def fn(p, r, w):
            raise RuntimeError("UNAVAILABLE: device lost")

        journal = TuningJournal(str(tmp_path))
        res = TuningOrchestrator(
            sp, fn, GridProposer(sp, [[0.5]]),
            _cfg(max_trials=1, retry=RetryPolicy(max_retries=1)),
            journal,
        ).run()
        journal.close()
        assert res.failed == 1
        fail = [r for r in journal.read() if r["type"] == "fail"][0]
        assert fail["transient"] is True and fail["retries"] == 1

    def test_asha_prunes_and_promotes(self, tmp_path):
        sp = SearchSpace.create([(0.0, 1.0)])
        resources = {}
        lock = threading.Lock()

        def fn(p, r, w):
            with lock:
                resources.setdefault(float(p[0]), []).append(r)
            return float((p[0] - 0.3) ** 2)

        journal = TuningJournal(str(tmp_path))
        res = TuningOrchestrator(
            sp, fn,
            GridProposer(sp, [[0.3], [0.9], [0.35], [0.8]]),
            _cfg(
                max_trials=4, workers=2,
                asha=AshaConfig(
                    min_resource=5, reduction_factor=2, num_rungs=2
                ),
            ),
            journal,
        ).run()
        journal.close()
        assert res.pruned >= 1 and res.completed >= 1
        assert res.best_params == [0.3]
        # Rung resources follow the geometry: 5 then 10.
        assert resources[0.3] == [5, 10]
        assert all(rs[0] == 5 for rs in resources.values())

    def test_parallel_matches_sequential_on_pure_function(self, tmp_path):
        sp = SearchSpace.create([(1e-2, 1e2)], log_scale=True)
        fn = lambda p, r, w: float(np.log10(p[0]) ** 2)  # noqa: E731

        def sweep(workers, sub):
            journal = TuningJournal(str(tmp_path / sub))
            res = TuningOrchestrator(
                sp, fn, GPProposer(sp, seed=9),
                _cfg(
                    max_trials=8, workers=workers,
                    asha=AshaConfig(1, 2, 2),
                ),
                journal,
            ).run()
            journal.close()
            return res

        seq = sweep(1, "seq")
        par = sweep(4, "par")
        # Wave structure differs with worker count, so the histories may
        # differ — but both must land a valid search; the deterministic
        # contract within one worker count is exercised by resume tests.
        assert seq.n_trials == par.n_trials == 8
        assert seq.best_metric is not None and par.best_metric is not None


class TestGlmSweepParity:
    """The acceptance bar: parallel-4 vs sequential best-metric
    parity (±1e-6) on a real GLM λ sweep with warm starts ON."""

    def test_parity(self, tmp_path, rng):
        from photon_ml_tpu.drivers.glm_driver import make_fit_once
        from photon_ml_tpu.tuning.scheduler import GridProposer

        n, d = 600, 16
        X = rng.normal(size=(n, d)).astype(np.float32)
        w = rng.normal(size=d).astype(np.float32)
        y = (
            rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w)))
        ).astype(np.float32)
        fit_once = make_fit_once(
            X[:400], y[:400], X[400:], y[400:],
            task="logistic", reg_type="l2", max_iters=50, tolerance=1e-9,
        )
        sp = SearchSpace.create([(1e-4, 1e2)], log_scale=True)
        lambdas = [[lam] for lam in np.geomspace(1e-3, 10.0, 6)]

        def sweep(workers, sub):
            journal = TuningJournal(str(tmp_path / sub))
            res = TuningOrchestrator(
                sp, fit_once, GridProposer(sp, lambdas),
                _cfg(
                    max_trials=6, workers=workers,
                    maximize=fit_once.larger_is_better,
                ),
                journal,
            ).run()
            journal.close()
            return res

        seq = sweep(1, "seq")
        par = sweep(4, "par")
        assert seq.best_params == par.best_params
        assert abs(seq.best_metric - par.best_metric) <= 1e-6


class TestJournal:
    def test_fsync_append_and_read(self, tmp_path):
        j = TuningJournal(str(tmp_path))
        j.append({"type": "header", "x": 1})
        j.append({"type": "ask", "trial": 0})
        j.close()
        assert [r["type"] for r in j.read()] == ["header", "ask"]

    def test_torn_tail_dropped(self, tmp_path):
        j = TuningJournal(str(tmp_path))
        j.append({"type": "header"})
        j.append({"type": "ask", "trial": 0, "params": [1.0]})
        j.close()
        with open(j.path, "a") as f:
            f.write('{"type": "report", "trial": 0, "met')  # torn write
        assert [r["type"] for r in j.read()] == ["header", "ask"]

    def test_mid_file_corruption_raises(self, tmp_path):
        j = TuningJournal(str(tmp_path))
        j.append({"type": "header"})
        j.close()
        with open(j.path, "a") as f:
            f.write("garbage\n")
            f.write('{"type": "ask", "trial": 0}\n')
        with pytest.raises(ValueError, match="corrupt journal"):
            j.read()

    def test_abort_hook_fires_at_boundary(self, tmp_path):
        j = TuningJournal(str(tmp_path), abort_after=2)
        j.append({"type": "header"})
        j.append({"type": "ask"})
        with pytest.raises(SearchAborted):
            j.append({"type": "ask"})
        j.close()
        assert len(j.read()) == 2

    def test_replay_requires_header(self):
        with pytest.raises(ValueError, match="header"):
            replay_journal([{"type": "ask", "trial": 0}])

    def test_trial_store_roundtrip_and_clear(self, tmp_path):
        store = TrialStore(str(tmp_path))
        store.save(3, np.array([0.5]), np.arange(4, dtype=np.float32))
        params, coefs = store.load(3)
        assert params[0] == 0.5
        np.testing.assert_array_equal(coefs, np.arange(4, dtype=np.float32))
        assert store.load(7) is None
        store.clear()
        assert store.load(3) is None


class TestResume:
    """Kill the search at journal record boundaries, resume, and demand
    the identical trial history + decision sequence — the crash-safe
    reproducibility contract."""

    @staticmethod
    def _search(directory, abort=None, resume=False, seed=5):
        sp = SearchSpace.create([(1e-2, 1e2)], log_scale=True)
        journal = TuningJournal(directory, abort_after=abort)
        orch = TuningOrchestrator(
            sp,
            lambda p, r, w: float(np.log10(p[0]) ** 2 + 0.01 * r),
            GPProposer(sp, seed=seed),
            _cfg(
                max_trials=6, workers=3,
                asha=AshaConfig(1, 2, 2),
                maximize=False,
            ),
            journal,
        )
        try:
            return orch.run(resume=resume), journal
        finally:
            journal.close()

    def test_kill_resume_bit_parity(self, tmp_path):
        ref, ref_journal = self._search(str(tmp_path / "ref"))
        n = len(ref_journal.read())
        assert n > 10
        for abort_at in range(2, n, 5):
            d = str(tmp_path / f"killed_{abort_at}")
            with pytest.raises(SearchAborted):
                self._search(d, abort=abort_at)
            resumed, journal = self._search(d, resume=True)
            assert resumed.trials == ref.trials, f"abort@{abort_at}"
            assert _decisions(journal) == _decisions(ref_journal), (
                f"abort@{abort_at}"
            )
            assert resumed.best_metric == ref.best_metric

    def test_resume_refuses_changed_space(self, tmp_path):
        d = str(tmp_path)
        with pytest.raises(SearchAborted):
            self._search(d, abort=4)
        sp = SearchSpace.create([(1e-3, 1e2)], log_scale=True)  # changed
        journal = TuningJournal(d)
        orch = TuningOrchestrator(
            sp, lambda p, r, w: 0.0, GPProposer(sp, seed=5),
            _cfg(max_trials=6, workers=3, asha=AshaConfig(1, 2, 2)),
            journal,
        )
        with pytest.raises(ResumeMismatch, match="search space"):
            orch.run(resume=True)
        journal.close()

    def test_resume_refuses_changed_config(self, tmp_path):
        d = str(tmp_path)
        with pytest.raises(SearchAborted):
            self._search(d, abort=4)
        sp = SearchSpace.create([(1e-2, 1e2)], log_scale=True)
        journal = TuningJournal(d)
        orch = TuningOrchestrator(
            sp, lambda p, r, w: 0.0, GPProposer(sp, seed=5),
            _cfg(max_trials=6, workers=4, asha=AshaConfig(1, 2, 2)),
            journal,  # workers 3 -> 4
        )
        with pytest.raises(ResumeMismatch, match="workers"):
            orch.run(resume=True)
        journal.close()

    def test_resume_without_journal_fails(self, tmp_path):
        sp = SearchSpace.create([(0.0, 1.0)])
        journal = TuningJournal(str(tmp_path))
        orch = TuningOrchestrator(
            sp, lambda p, r, w: 0.0, RandomProposer(sp),
            _cfg(max_trials=2), journal,
        )
        with pytest.raises(ResumeMismatch, match="no journal"):
            orch.run(resume=True)
        journal.close()

    def test_resume_after_torn_tail(self, tmp_path):
        d = str(tmp_path)
        with pytest.raises(SearchAborted):
            self._search(d, abort=6)
        path = os.path.join(d, TuningJournal.FILENAME)
        with open(path, "a") as f:
            f.write('{"type": "report", "tri')  # torn mid-write record
        resumed, journal = self._search(d, resume=True)
        journal.close()
        ref, ref_journal = self._search(str(tmp_path / "ref"))
        ref_journal.close()
        assert resumed.trials == ref.trials


class TestFitOnceEntries:
    def test_glm_fit_once(self, rng):
        from photon_ml_tpu.drivers.glm_driver import make_fit_once

        n, d = 300, 8
        X = rng.normal(size=(n, d)).astype(np.float32)
        w = rng.normal(size=d).astype(np.float32)
        y = (
            rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w)))
        ).astype(np.float32)
        fit_once = make_fit_once(
            X[:200], y[:200], X[200:], y[200:],
            task="logistic", reg_type="l2",
        )
        assert fit_once.larger_is_better  # AUC
        metric, metrics, coefs = fit_once(np.array([0.1]), 0, None)
        assert 0.0 <= metric <= 1.0
        assert fit_once.suite.primary in metrics
        assert coefs.shape == (d,)
        # resource caps iterations: 1 iteration from zero is a worse fit.
        weak, _, weak_coefs = fit_once(np.array([0.1]), 1, None)
        assert not np.allclose(weak_coefs, coefs)
        # warm start at the converged solution reproduces it.
        again, _, coefs2 = fit_once(np.array([0.1]), 0, coefs)
        assert again == pytest.approx(metric, abs=1e-6)

    def test_game_fit_once(self):
        from photon_ml_tpu.tuning.__main__ import synthetic_game_fit_once

        fit_once = synthetic_game_fit_once(seed=1)
        m1, metrics, coefs = fit_once(np.array([1.0, 1.0]), 1, None)
        assert 0.0 <= m1 <= 1.0 and coefs is None
        assert fit_once.suite.primary in metrics
        # A wildly different regularization changes the fit.
        m2, _, _ = fit_once(np.array([100.0, 100.0]), 1, None)
        assert m1 != m2
        # Deterministic: same params, same metric, any call order.
        m1b, _, _ = fit_once(np.array([1.0, 1.0]), 1, None)
        assert m1b == m1

    def test_suite_evaluate_primary(self):
        from photon_ml_tpu.evaluation.suite import EvaluationSuite

        suite = EvaluationSuite.from_specs(["auc", "logistic_loss"])
        scores = np.array([-2.0, -1.0, 1.0, 2.0])
        labels = np.array([0.0, 0.0, 1.0, 1.0])
        primary, values = suite.evaluate_primary(scores, labels)
        assert primary == values["auc"] == 1.0
        assert "logistic_loss" in values


class TestSelfcheck:
    def test_selfcheck_passes(self, tmp_path):
        from photon_ml_tpu.tuning.__main__ import run_selfcheck

        failures = run_selfcheck(str(tmp_path))
        assert failures == []
        # The journal + telemetry artifacts exist where documented.
        assert os.path.exists(
            tmp_path / "search_a" / TuningJournal.FILENAME
        )
        assert os.path.exists(tmp_path / "metrics.json")
        with open(tmp_path / "metrics.json") as f:
            snap = json.load(f)
        assert snap["counters"]["tuning_trials_pruned"] >= 1
        assert snap["counters"]["tuning_trials_failed"] == 1
