"""Failure/elastic recovery (SURVEY.md §5.3): the transient-failure
watchdog and its driver integration.

The reference's elastic recovery is Spark's cluster manager re-running
failed tasks; the TPU analogue is checkpoint + automatic resume.  The
driver tests here kill training MID-GRID with a transport-shaped error
and assert the retry completes from the checkpoint without repeating
finished λs."""

import os

import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.utils.watchdog import (
    RetryPolicy,
    RetryStats,
    run_with_retries,
)


class _FakeLogger:
    def __init__(self):
        self.warnings = []

    def warning(self, msg, *args):
        self.warnings.append(msg % args if args else msg)

    def info(self, *a, **k):
        pass


class TestRetryPolicy:
    def test_transient_classification(self):
        p = RetryPolicy(max_retries=3)
        assert p.is_transient(RuntimeError("UNAVAILABLE: Socket closed"))
        assert p.is_transient(RuntimeError("DEADLINE_EXCEEDED: timed out"))
        assert p.is_transient(OSError("connection reset by peer"))
        assert not p.is_transient(ValueError("bad shape"))
        assert not p.is_transient(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        )

    def test_type_name_alone_is_not_transient(self):
        XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
        assert not RetryPolicy().is_transient(XlaRuntimeError("whatever"))

    def test_extra_patterns(self):
        p = RetryPolicy(extra_patterns=("my-cluster-oops",))
        assert p.is_transient(RuntimeError("MY-CLUSTER-OOPS happened"))

    def test_backoff_exponential_capped(self):
        p = RetryPolicy(backoff_seconds=2.0, backoff_multiplier=3.0,
                        max_backoff_seconds=10.0)
        assert p.backoff(0) == 2.0
        assert p.backoff(1) == 6.0
        assert p.backoff(2) == 10.0  # capped

    def test_interrupts_never_retryable(self):
        """KeyboardInterrupt/SystemExit are refused as transient even
        when their message screams the transient vocabulary — a user
        interrupt must never put the process back to work."""
        p = RetryPolicy(extra_patterns=("UNAVAILABLE",))
        c = p.classify(KeyboardInterrupt("UNAVAILABLE: device lost"))
        assert not c.transient and c.source == "interrupt"
        c = p.classify(SystemExit("UNAVAILABLE: bye"))
        assert not c.transient and c.source == "interrupt"

    def test_jitter_validation(self):
        with pytest.raises(ValueError, match="jitter"):
            RetryPolicy(jitter="full")

    def test_decorrelated_jitter_bounds_and_determinism(self):
        import random

        p = RetryPolicy(
            backoff_seconds=1.0, max_backoff_seconds=30.0,
            jitter="decorrelated",
        )
        # Seeded RNG -> the whole jittered schedule is reproducible.
        seq = []
        rng = random.Random(7)
        prev = None
        for attempt in range(6):
            d = p.backoff(attempt, rng=rng, previous=prev)
            lo, hi = 1.0, max(1.0, 3.0 * (prev if prev is not None else 1.0))
            assert lo <= d <= min(30.0, hi)
            seq.append(d)
            prev = d
        rng2 = random.Random(7)
        prev = None
        for attempt, want in enumerate(seq):
            got = p.backoff(attempt, rng=rng2, previous=prev)
            assert got == want
            prev = got

    def test_jitter_none_ignores_rng(self):
        import random

        p = RetryPolicy(backoff_seconds=2.0)
        assert p.backoff(1, rng=random.Random(0)) == 4.0


class TestRunWithRetries:
    def test_retries_then_succeeds(self):
        calls = []
        slept = []

        def fn(attempt):
            calls.append(attempt)
            if attempt < 2:
                raise RuntimeError("UNAVAILABLE: transport lost")
            return "ok"

        log = _FakeLogger()
        out = run_with_retries(
            fn, RetryPolicy(max_retries=3, backoff_seconds=0.01),
            log, sleep=slept.append,
        )
        assert out == "ok"
        assert calls == [0, 1, 2]
        assert len(slept) == 2
        assert len(log.warnings) == 2

    def test_budget_exhausted_raises(self):
        def fn(attempt):
            raise RuntimeError("UNAVAILABLE: still down")

        with pytest.raises(RuntimeError, match="still down"):
            run_with_retries(
                fn, RetryPolicy(max_retries=2, backoff_seconds=0),
                sleep=lambda s: None,
            )

    def test_non_transient_propagates_immediately(self):
        calls = []

        def fn(attempt):
            calls.append(attempt)
            raise ValueError("programming error")

        with pytest.raises(ValueError):
            run_with_retries(
                fn, RetryPolicy(max_retries=5), sleep=lambda s: None
            )
        assert calls == [0]

    def test_disabled_by_default(self):
        def fn(attempt):
            raise RuntimeError("UNAVAILABLE")

        with pytest.raises(RuntimeError):
            run_with_retries(fn, RetryPolicy(), sleep=lambda s: None)

    def test_decorrelated_jitter_schedule_is_seeded(self):
        """Two runs with the same seeded RNG sleep the identical jittered
        schedule; the recorded delays stay inside the decorrelated
        envelope ([base, 3·previous], capped)."""
        import random

        def fn(attempt):
            if attempt < 3:
                raise RuntimeError("UNAVAILABLE: flaky")
            return attempt

        policy = RetryPolicy(
            max_retries=5, backoff_seconds=1.0, max_backoff_seconds=5.0,
            jitter="decorrelated",
        )

        def delays(seed):
            slept = []
            run_with_retries(
                fn, policy, sleep=slept.append, rng=random.Random(seed)
            )
            return slept

        a, b = delays(42), delays(42)
        assert a == b and len(a) == 3
        assert a != delays(43)  # a different seed decorrelates
        prev = 1.0
        for d in a:
            assert 1.0 <= d <= min(5.0, max(1.0, 3.0 * prev))
            prev = d


class TestClassification:
    def test_classify_reports_matched_pattern(self):
        p = RetryPolicy()
        c = p.classify(RuntimeError("UNAVAILABLE: Socket closed"))
        assert c.transient and c.matched == "UNAVAILABLE"
        assert c.source == "transient_pattern"
        c = p.classify(RuntimeError("RESOURCE_EXHAUSTED: oom"))
        assert not c.transient and c.matched == "RESOURCE_EXHAUSTED"
        assert c.source == "non_transient_pattern"
        XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
        c = p.classify(XlaRuntimeError("mystery"))
        assert not c.transient and c.matched is None and c.source == "none"
        c = p.classify(XlaRuntimeError(
            "INTERNAL: Mosaic failed to compile TPU kernel: scoped vmem"
        ))
        assert not c.transient and c.matched == "mosaic"
        assert c.source == "non_transient_pattern"
        c = p.classify(ValueError("bad shape"))
        assert not c.transient and c.matched is None and c.source == "none"


class TestRetryStats:
    def test_stats_record_each_attempt_without_sleeping(self):
        """The retry-behavior assertion surface: verdicts, matched
        patterns, and backoffs observable on RetryStats — no timing."""
        slept = []

        def fn(attempt):
            if attempt < 2:
                raise RuntimeError("UNAVAILABLE: transport lost")
            return "ok"

        stats = RetryStats()
        out = run_with_retries(
            fn, RetryPolicy(max_retries=3, backoff_seconds=2.0),
            sleep=slept.append, stats=stats,
        )
        assert out == "ok"
        assert stats.succeeded and not stats.gave_up
        assert stats.attempts == 3 and stats.retries == 2
        assert stats.sleep_seconds == pytest.approx(2.0 + 4.0)
        assert [f["attempt"] for f in stats.failures] == [0, 1]
        assert all(f["matched"] == "UNAVAILABLE" for f in stats.failures)
        assert [f["backoff_seconds"] for f in stats.failures] == [2.0, 4.0]
        # snapshot() is JSON-able driver-result material.
        import json

        json.dumps(stats.snapshot())

    def test_stats_mark_gave_up_on_budget_exhaustion(self):
        stats = RetryStats()

        def fn(attempt):
            raise RuntimeError("UNAVAILABLE: still down")

        with pytest.raises(RuntimeError):
            run_with_retries(
                fn, RetryPolicy(max_retries=1, backoff_seconds=0),
                sleep=lambda s: None, stats=stats,
            )
        assert stats.gave_up and not stats.succeeded
        assert stats.attempts == 2 and stats.retries == 1
        assert stats.failures[-1]["backoff_seconds"] is None

    def test_stats_non_transient_single_failure(self):
        stats = RetryStats()

        def fn(attempt):
            raise ValueError("broken")

        with pytest.raises(ValueError):
            run_with_retries(
                fn, RetryPolicy(max_retries=5), sleep=lambda s: None,
                stats=stats,
            )
        assert not stats.gave_up  # non-transient, not a budget give-up
        assert stats.attempts == 1 and stats.retries == 0
        assert stats.failures[0]["transient"] is False

    def test_telemetry_events_per_attempt(self, tmp_path):
        """Every classify/backoff decision is emitted as a
        watchdog.attempt event; retries increment the counter."""
        import json

        from photon_ml_tpu import telemetry

        def fn(attempt):
            if attempt == 0:
                raise RuntimeError("DEADLINE_EXCEEDED: slow transport")
            return 42

        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            out = run_with_retries(
                fn, RetryPolicy(max_retries=2, backoff_seconds=0.5),
                sleep=lambda s: None,
            )
            snap = tel.snapshot()
        assert out == 42
        assert snap["counters"]["watchdog_retries"] == 1
        with open(tmp_path / "events.jsonl") as f:
            records = [json.loads(line) for line in f]
        attempts = [
            r for r in records
            if r.get("type") == "event" and r["name"] == "watchdog.attempt"
        ]
        assert len(attempts) == 1
        a = attempts[0]["attrs"]
        assert a["outcome"] == "retry"
        assert a["matched"] == "DEADLINE_EXCEEDED"
        assert a["backoff_seconds"] == 0.5
        assert any(
            r.get("type") == "event" and r["name"] == "watchdog.recovered"
            for r in records
        )


class TestGlmDriverRecovery:
    def test_mid_grid_crash_resumes_from_checkpoint(
        self, tmp_path, monkeypatch, rng
    ):
        """Kill the run after the FIRST λ checkpoints; --max-retries must
        finish the grid with the first λ restored, matching an
        uninterrupted run's models."""
        from photon_ml_tpu.data import libsvm
        from photon_ml_tpu.drivers import glm_driver
        from photon_ml_tpu.optim.problem import GlmOptimizationProblem

        n, d = 400, 60
        X = sp.random(n, d, density=0.1, random_state=1, format="csr")
        X.data[:] = 1.0
        w_true = rng.normal(size=d) * (rng.uniform(size=d) < 0.4)
        y = np.where(
            rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w_true))), 1.0, -1.0
        )
        train = str(tmp_path / "t.libsvm")
        libsvm.write_libsvm(train, X, y)
        common = [
            "--train-data", train,
            "--task", "logistic",
            "--reg-type", "l2",
            "--reg-weights", "0.5,5.0",
            "--n-features", str(d),
        ]

        out_ok = str(tmp_path / "ok")
        res_ok = glm_driver.run(common + ["--output-dir", out_ok])

        orig = GlmOptimizationProblem.run_grid
        state = {"attempts": 0, "solves_before_crash": []}

        def flaky_run_grid(self, data, reg_weights, **kw):
            state["attempts"] += 1
            if state["attempts"] == 1:
                inner = kw.get("on_solved")

                def dying_on_solved(lam, w):
                    inner(lam, w)  # persist the checkpoint FIRST
                    state["solves_before_crash"].append(lam)
                    raise RuntimeError(
                        "UNAVAILABLE: TPU transport lost (induced)"
                    )

                kw["on_solved"] = dying_on_solved
            return orig(self, data, reg_weights, **kw)

        monkeypatch.setattr(
            GlmOptimizationProblem, "run_grid", flaky_run_grid
        )
        out = str(tmp_path / "recovered")
        res = glm_driver.run(common + [
            "--output-dir", out, "--max-retries", "2",
            "--retry-backoff", "0.01",
        ])
        # Crashed once after λ=5.0 (grid solves big-to-small), retried,
        # and did NOT re-solve the checkpointed λ.
        assert state["attempts"] == 2
        assert state["solves_before_crash"] == [5.0]
        assert res["best_lambda"] == res_ok["best_lambda"]
        for lam in ("0.5", "5.0"):
            assert res["metrics"][lam] == pytest.approx(
                res_ok["metrics"][lam], abs=1e-6
            )

    def test_non_transient_failure_still_fatal(
        self, tmp_path, monkeypatch, rng
    ):
        from photon_ml_tpu.data import libsvm
        from photon_ml_tpu.drivers import glm_driver
        from photon_ml_tpu.optim.problem import GlmOptimizationProblem

        n, d = 100, 10
        X = sp.random(n, d, density=0.3, random_state=2, format="csr")
        y = np.where(rng.uniform(size=n) < 0.5, 1.0, -1.0)
        train = str(tmp_path / "t.libsvm")
        libsvm.write_libsvm(train, X, y)

        def broken(self, *a, **k):
            raise ValueError("genuinely broken config")

        monkeypatch.setattr(GlmOptimizationProblem, "run_grid", broken)
        with pytest.raises(ValueError, match="genuinely broken"):
            glm_driver.run([
                "--train-data", train,
                "--output-dir", str(tmp_path / "out"),
                "--task", "logistic",
                "--n-features", str(d),
                "--max-retries", "5",
                "--retry-backoff", "0.01",
            ])


class TestGameDriverRecovery:
    def test_cd_crash_resumes_per_iteration(self, tmp_path, monkeypatch):
        """Crash the GAME fit after iteration 0 checkpoints; the retry must
        resume at iteration 1 (not restart) and produce a model."""
        import json

        from photon_ml_tpu.drivers import game_training_driver
        from photon_ml_tpu.game import descent as descent_mod
        from photon_ml_tpu.data.game_reader import write_game_avro

        rng = np.random.default_rng(5)
        n = 300
        records = [
            {
                "uid": f"row{i}",
                "response": float(rng.integers(2)),
                "weight": None,
                "offset": None,
                "ids": {"userId": f"u{rng.integers(20)}"},
                "features": {
                    "global": [
                        {"name": f"g{j}", "term": "",
                         "value": float(rng.normal())}
                        for j in range(3)
                    ],
                    "userFeatures": [
                        {"name": "bias", "term": "", "value": 1.0}
                    ],
                },
            }
            for i in range(n)
        ]
        train = str(tmp_path / "game.avro")
        write_game_avro(train, records)
        config = {
            "task": "logistic",
            "iterations": 2,
            "coordinates": [
                {"name": "fixed", "type": "fixed",
                 "feature_shard": "global", "reg_type": "l2",
                 "reg_weight": 1.0, "max_iters": 5},
                {"name": "per_user", "type": "random",
                 "feature_shard": "userFeatures", "entity_key": "userId",
                 "reg_type": "l2", "reg_weight": 1.0, "max_iters": 5},
            ],
        }
        cfg_path = str(tmp_path / "cfg.json")
        with open(cfg_path, "w") as f:
            json.dump(config, f)

        orig_run = descent_mod.CoordinateDescent.run
        state = {"calls": 0, "resumed_from": None}

        def flaky_run(self, base_offsets, n_iterations=1, checkpointer=None,
                      **kw):
            state["calls"] += 1
            if state["calls"] == 1:
                # First attempt: run ONE iteration (checkpointing), then
                # die as the transport would.
                orig_run(
                    self, base_offsets, n_iterations=1,
                    checkpointer=checkpointer, **kw
                )
                raise RuntimeError("UNAVAILABLE: device lost (induced)")
            saved = checkpointer.load() if checkpointer else None
            state["resumed_from"] = (
                saved["iteration"] if saved is not None else None
            )
            return orig_run(
                self, base_offsets, n_iterations=n_iterations,
                checkpointer=checkpointer, **kw
            )

        monkeypatch.setattr(
            descent_mod.CoordinateDescent, "run", flaky_run
        )
        out = str(tmp_path / "out")
        result = game_training_driver.run([
            "--train-data", train,
            "--config", cfg_path,
            "--output-dir", out,
            "--max-retries", "1",
            "--retry-backoff", "0.01",
        ])
        assert state["calls"] == 2
        assert state["resumed_from"] == 0  # resumed AFTER iteration 0
        assert os.path.isdir(os.path.join(out, "models"))
        assert result["history"]


class TestDeterministicStatusVeto:
    def test_xla_error_with_oom_status_not_retried(self):
        """RESOURCE_EXHAUSTED inside an XlaRuntimeError is never
        transient — a retry re-runs the same allocation."""
        XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
        p = RetryPolicy(max_retries=3)
        assert not p.is_transient(
            XlaRuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating")
        )
        assert not p.is_transient(
            XlaRuntimeError("INVALID_ARGUMENT: shape mismatch")
        )
        # ...but a genuinely transient status still retries.
        assert p.is_transient(XlaRuntimeError("UNAVAILABLE: Socket closed"))
        assert p.is_transient(XlaRuntimeError("INTERNAL: device halted"))
        # A compile or lowering failure repeats on every retry.
        for msg in (
            "INTERNAL: Mosaic failed to compile TPU kernel",
            "INTERNAL: during XLA compilation: ...",
            "INTERNAL: scoped vmem limit exceeded",
        ):
            assert not p.is_transient(XlaRuntimeError(msg)), msg


class TestGameGridRecovery:
    def test_grid_crash_resumes_at_point_boundary(self, tmp_path, monkeypatch):
        """Kill the GAME fit between grid points; the retry must SKIP the
        completed point (loading its checkpointed model) and fit only the
        rest (VERDICT r3 weak #6 / next-round #8)."""
        import json

        from photon_ml_tpu.data.game_reader import write_game_avro
        from photon_ml_tpu.drivers import game_training_driver
        from photon_ml_tpu.game import estimator as est_mod

        rng = np.random.default_rng(7)
        n = 300
        records = [
            {
                "uid": f"row{i}",
                "response": float(rng.integers(2)),
                "weight": None,
                "offset": None,
                "ids": {"userId": f"u{rng.integers(15)}"},
                "features": {
                    "global": [
                        {"name": f"g{j}", "term": "",
                         "value": float(rng.normal())}
                        for j in range(3)
                    ],
                    "userFeatures": [
                        {"name": "bias", "term": "", "value": 1.0}
                    ],
                },
            }
            for i in range(n)
        ]
        train = str(tmp_path / "game.avro")
        val = str(tmp_path / "val.avro")
        write_game_avro(train, records[: n - 60])
        write_game_avro(val, records[n - 60:])
        config = {
            "task": "logistic",
            "iterations": 1,
            "evaluator": "auc",
            "coordinates": [
                {"name": "fixed", "type": "fixed",
                 "feature_shard": "global", "reg_type": "l2",
                 "reg_weights": [0.1, 1.0, 10.0], "max_iters": 5},
                {"name": "per_user", "type": "random",
                 "feature_shard": "userFeatures", "entity_key": "userId",
                 "reg_type": "l2", "reg_weight": 1.0, "max_iters": 5},
            ],
        }
        cfg_path = str(tmp_path / "cfg.json")
        with open(cfg_path, "w") as f:
            json.dump(config, f)

        orig_fit = est_mod.GameEstimator.fit_coordinates
        state = {"fits": []}

        def flaky_fit(self, *a, **kw):
            # fit_coordinates runs once per NON-RESUMED grid point; die
            # right after the second point's fit returns (its checkpoint
            # has NOT been written yet -> it must re-fit on retry).
            out = orig_fit(self, *a, **kw)
            state["fits"].append(len(state["fits"]))
            if len(state["fits"]) == 2:
                raise RuntimeError("UNAVAILABLE: device lost (induced)")
            return out

        monkeypatch.setattr(
            est_mod.GameEstimator, "fit_coordinates", flaky_fit
        )
        out = str(tmp_path / "out")
        result = game_training_driver.run([
            "--train-data", train,
            "--validate-data", val,
            "--config", cfg_path,
            "--output-dir", out,
            "--max-retries", "1",
            "--retry-backoff", "0.01",
        ])
        # Attempt 1: fits point 0 (checkpointed) + point 1 (killed before
        # checkpoint).  Attempt 2: skips point 0, re-fits points 1 and 2.
        # Total real fits = 4, not 6 — the completed point never re-ran.
        assert len(state["fits"]) == 4
        assert len(result["grid"]) == 3
        assert sum(1 for g in result["grid"] if g["best"]) == 1
        assert os.path.isdir(os.path.join(out, "models"))
        # The checkpointed point 0 still contributed a real metric.
        assert all(g["metric"] is not None for g in result["grid"])
