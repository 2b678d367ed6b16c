"""Telemetry subsystem: spans, metrics registry, sinks, driver wiring.

Covers the PR-2 acceptance surface: span nesting (incl. across threads),
registry snapshot round-trip, JSONL/Chrome-trace output validity (every
event parses; the trace is a valid trace-event array), the one-branch
disabled path, the selfcheck entry point, PhotonLogger lifecycle, and
end-to-end driver runs producing events.jsonl + trace.json +
metrics.json with nested run/coordinate/solver spans.
"""

import json
import logging
import os
import threading
import time

import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry.__main__ import selfcheck, validate_outputs


def read_events(out_dir):
    path = os.path.join(out_dir, "events.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f]


def span_records(records):
    return [r for r in records if r.get("type") == "span"]


class TestSpans:
    def test_nesting_parent_links(self, tmp_path):
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            with tel.span("run"):
                with tel.span("outer", k=1):
                    with tel.span("inner"):
                        pass
                with tel.span("sibling"):
                    pass
        spans = {r["name"]: r for r in span_records(read_events(tmp_path))}
        assert spans["run"]["parent"] is None
        assert spans["outer"]["parent"] == spans["run"]["id"]
        assert spans["inner"]["parent"] == spans["outer"]["id"]
        assert spans["sibling"]["parent"] == spans["run"]["id"]
        assert spans["outer"]["attrs"] == {"k": 1}
        # Children close before parents; durations nest.
        assert spans["inner"]["dur"] <= spans["outer"]["dur"]
        assert spans["outer"]["ts"] >= spans["run"]["ts"]

    def test_set_attaches_mid_span_attrs(self, tmp_path):
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            with tel.span("solver") as sp_:
                sp_.set(iterations=12, converged=True)
        (rec,) = span_records(read_events(tmp_path))
        assert rec["attrs"] == {"iterations": 12, "converged": True}

    def test_events_carry_enclosing_span(self, tmp_path):
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            with tel.span("run") as run_span:
                tel.event("checkpoint.save", path="x")
                run_id = run_span.span_id
        records = read_events(tmp_path)
        (ev,) = [r for r in records if r.get("type") == "event"]
        assert ev["name"] == "checkpoint.save"
        assert ev["parent"] == run_id
        assert ev["attrs"]["path"] == "x"

    def test_threads_get_independent_stacks(self, tmp_path):
        """A span opened on another thread must not nest under the main
        thread's current span (each thread owns its stack), and
        concurrent emission must not corrupt the JSONL."""
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            with tel.span("run"):
                def worker(i):
                    for k in range(20):
                        with tel.span("chunk", worker=i, k=k):
                            pass

                threads = [
                    threading.Thread(target=worker, args=(i,))
                    for i in range(4)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
        records = read_events(tmp_path)  # every line parses
        chunks = [r for r in span_records(records) if r["name"] == "chunk"]
        assert len(chunks) == 80
        assert all(c["parent"] is None for c in chunks)
        # ids unique across threads
        ids = [c["id"] for c in chunks]
        assert len(set(ids)) == len(ids)

    def test_mismatched_exit_does_not_corrupt_stack(self, tmp_path):
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            with tel.span("run") as run_span:
                inner = tel.span("inner")
                inner.__enter__()
                # Caller error: exits the OUTER before the inner...
                run_span.__exit__(None, None, None)
                # ...later spans must still be recordable as roots.
                with tel.span("after"):
                    pass
        names = {r["name"] for r in span_records(read_events(tmp_path))}
        assert "after" in names

    def test_exception_recorded_on_span(self, tmp_path):
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            with pytest.raises(ValueError):
                with tel.span("boom"):
                    raise ValueError("induced")
        (rec,) = span_records(read_events(tmp_path))
        assert "ValueError" in rec["error"]


class TestMetricsRegistry:
    def test_counter_gauge_histogram_snapshot(self):
        reg = telemetry.MetricsRegistry()
        reg.counter("retries").inc()
        reg.counter("retries").inc(2)
        reg.gauge("gbps").set(3.5)
        for v in (1.0, 3.0, 2.0):
            reg.histogram("lat").observe(v)
        snap = reg.snapshot()
        assert snap["counters"] == {"retries": 3}
        assert snap["gauges"] == {"gbps": 3.5}
        h = snap["histograms"]["lat"]
        assert h["count"] == 3 and h["min"] == 1.0 and h["max"] == 3.0
        assert h["mean"] == pytest.approx(2.0) and h["last"] == 2.0

    def test_snapshot_json_round_trip(self, tmp_path):
        tel = telemetry.Telemetry(
            output_dir=str(tmp_path), sinks=[], enabled=True
        )
        tel.counter("c").inc(7)
        tel.gauge("g").set(1.5)
        tel.histogram("h").observe(0.25)
        path = tel.write_snapshot()
        loaded = json.load(open(path))
        live = tel.snapshot()
        for kind in ("counters", "gauges", "histograms"):
            assert loaded[kind] == live[kind]

    def test_threaded_counters_do_not_lose_increments(self):
        reg = telemetry.MetricsRegistry()

        def bump():
            for _ in range(1000):
                reg.counter("n").inc()

        threads = [threading.Thread(target=bump) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert reg.snapshot()["counters"]["n"] == 8000

    def test_disabled_registry_returns_noop(self):
        reg = telemetry.MetricsRegistry(enabled=False)
        reg.counter("x").inc()
        reg.gauge("y").set(1)
        reg.histogram("z").observe(2.0)
        snap = reg.snapshot()
        assert snap == {"counters": {}, "gauges": {}, "histograms": {}}


class TestSinkOutputs:
    def test_selfcheck_passes(self):
        assert selfcheck() == 0

    def test_validate_outputs_catches_corruption(self, tmp_path):
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            with tel.span("run"):
                pass
            snap = tel.snapshot()
        assert validate_outputs(str(tmp_path), snap) == []
        with open(os.path.join(tmp_path, "trace.json"), "w") as f:
            f.write("{not json")
        assert any(
            "trace.json" in msg
            for msg in validate_outputs(str(tmp_path), snap)
        )

    def test_chrome_trace_is_valid_event_array(self, tmp_path):
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            with tel.span("run"):
                with tel.span("coordinate", coordinate="fixed"):
                    pass
                tel.event("marker")
            tel.counter("n_things").inc(3)
        trace = json.load(open(os.path.join(tmp_path, "trace.json")))
        assert isinstance(trace, list)
        by_ph = {}
        for ev in trace:
            assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)
            by_ph.setdefault(ev["ph"], []).append(ev)
        assert all("dur" in ev for ev in by_ph["X"])
        # Counter sample rides the trace.
        assert any(
            ev["name"] == "n_things" and ev["args"]["value"] == 3
            for ev in by_ph.get("C", [])
        )
        # Microsecond timestamps: the span ts/dur must be finite floats.
        for ev in by_ph["X"]:
            assert ev["dur"] >= 0.0

    def test_device_arrays_never_materialized_in_attrs(self, tmp_path):
        """Attribute sanitization must not pull device arrays to host —
        a large jax array attribute records as a placeholder."""
        import jax.numpy as jnp

        big = jnp.zeros((4096,), jnp.float32)
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            tel.event("e", arr=big)
        records = read_events(tmp_path)
        (ev,) = [r for r in records if r.get("type") == "event"]
        assert isinstance(ev["attrs"]["arr"], str)
        assert "4096" in ev["attrs"]["arr"]

    def test_logger_summary_sink_logs_through_photon_logger(self, tmp_path):
        from photon_ml_tpu.utils.logging import PhotonLogger

        with PhotonLogger(str(tmp_path / "log")) as logger:
            with telemetry.Telemetry(
                output_dir=str(tmp_path / "tel"), logger=logger
            ) as tel:
                with tel.span("run"):
                    pass
        text = open(tmp_path / "log" / "photon.log").read()
        assert "telemetry summary" in text


class TestDisabledPath:
    def test_disabled_hub_is_noop_and_writes_nothing(self, tmp_path):
        tel = telemetry.Telemetry(
            output_dir=str(tmp_path / "off"), enabled=False
        )
        with tel:
            with tel.span("run") as sp_:
                sp_.set(x=1)
                tel.event("e")
            tel.counter("c").inc()
        assert not os.path.exists(tmp_path / "off" / "events.jsonl")
        assert not os.path.exists(tmp_path / "off" / "trace.json")

    def test_disabled_span_is_shared_singleton(self):
        tel = telemetry.Telemetry(enabled=False, sinks=[])
        assert tel.span("a") is tel.span("b")

    def test_disabled_overhead_smoke(self):
        """The disabled path must stay branch-cheap: 100k span+event+metric
        calls well under a second (~µs each)."""
        tel = telemetry.Telemetry(enabled=False, sinks=[])
        t0 = time.perf_counter()
        for _ in range(100_000):
            with tel.span("s"):
                pass
            tel.event("e", k=1)
            tel.counter("c").inc()
        elapsed = time.perf_counter() - t0
        assert elapsed < 2.0, f"disabled path too slow: {elapsed:.3f}s"

    def test_current_defaults_to_disabled_null(self):
        assert telemetry.current() is telemetry.NULL or not (
            telemetry.current().active
        )

    def test_install_restore_nesting(self, tmp_path):
        before = telemetry.current()
        with telemetry.Telemetry(output_dir=str(tmp_path / "a")) as a:
            assert telemetry.current() is a
            with telemetry.Telemetry(output_dir=str(tmp_path / "b")) as b:
                assert telemetry.current() is b
            assert telemetry.current() is a
        assert telemetry.current() is before


class TestPhotonLoggerLifecycle:
    def test_close_detaches_handlers_and_unregisters(self, tmp_path):
        from photon_ml_tpu.utils.logging import PhotonLogger

        logger = PhotonLogger(str(tmp_path))
        inner = logger._logger
        name = logger._name
        assert len(inner.handlers) == 2  # console + file
        logger.info("hello")
        logger.close()
        assert inner.handlers == []
        assert name not in logging.Logger.manager.loggerDict
        logger.close()  # idempotent

    def test_repeated_instances_leak_no_handles(self, tmp_path):
        """100 context-managed loggers leave zero registered photon
        loggers and zero open handlers behind — the repeated-driver
        (hyperparameter search) shape that used to leak file handles."""
        from photon_ml_tpu.utils.logging import PhotonLogger

        before = {
            n for n in logging.Logger.manager.loggerDict
            if n.startswith("photon_ml_tpu.")
        }
        for i in range(100):
            with PhotonLogger(str(tmp_path / f"d{i}")) as logger:
                logger.info("run %d", i)
        after = {
            n for n in logging.Logger.manager.loggerDict
            if n.startswith("photon_ml_tpu.")
        }
        assert after == before

    def test_unique_names_across_instances(self, tmp_path):
        from photon_ml_tpu.utils.logging import PhotonLogger

        a = PhotonLogger(str(tmp_path / "a"))
        b = PhotonLogger(str(tmp_path / "b"))
        try:
            assert a._name != b._name
            assert a._logger is not b._logger
        finally:
            a.close()
            b.close()

    def test_exception_path_closes_logger(self, tmp_path):
        from photon_ml_tpu.utils.logging import PhotonLogger

        with pytest.raises(RuntimeError):
            with PhotonLogger(str(tmp_path)) as logger:
                raise RuntimeError("induced")
        assert logger.closed


class TestDriverTelemetry:
    @pytest.fixture
    def glm_files(self, tmp_path, rng):
        from photon_ml_tpu.data import libsvm

        n, d = 200, 30
        X = sp.random(n, d, density=0.2, random_state=3, format="csr")
        X.data[:] = 1.0
        y = np.where(rng.uniform(size=n) < 0.5, 1.0, -1.0)
        train = str(tmp_path / "t.libsvm")
        libsvm.write_libsvm(train, X, y)
        return train, d

    def test_glm_driver_produces_valid_telemetry(self, tmp_path, glm_files):
        from photon_ml_tpu.drivers import glm_driver

        train, d = glm_files
        out = str(tmp_path / "out")
        res = glm_driver.run([
            "--train-data", train, "--output-dir", out,
            "--task", "logistic", "--reg-type", "l2",
            "--reg-weights", "0.5,5.0", "--n-features", str(d),
        ])
        for fname in ("events.jsonl", "trace.json", "metrics.json"):
            assert os.path.exists(os.path.join(out, fname)), fname
        records = read_events(out)
        names = {r["name"] for r in span_records(records)}
        assert {"run", "read", "summarize", "train", "solver",
                "validate", "write"} <= names
        # solver spans nest under the grid's span, under train under run
        spans = {r["id"]: r for r in span_records(records)}
        solver = [r for r in span_records(records) if r["name"] == "solver"]
        assert solver
        for s in solver:
            chain = []
            cur = s
            while cur["parent"] is not None:
                cur = spans[cur["parent"]]
                chain.append(cur["name"])
            assert chain == ["grid", "train", "run"]
            assert s["attrs"]["iterations"] > 0
        trace = json.load(open(os.path.join(out, "trace.json")))
        assert isinstance(trace, list) and any(
            e.get("ph") == "X" for e in trace
        )
        snap = json.load(open(os.path.join(out, "metrics.json")))
        assert snap["counters"]["solver_iterations"] > 0
        # Wall-clock satellite: per-λ solver walls in the result and real
        # (non-NaN) wall on the solve path.
        assert set(res["solver_wall_seconds"]) == {"0.5", "5.0"}
        assert all(w > 0 for w in res["solver_wall_seconds"].values())

    def test_glm_driver_telemetry_off_writes_nothing(
        self, tmp_path, glm_files
    ):
        from photon_ml_tpu.drivers import glm_driver

        train, d = glm_files
        out = str(tmp_path / "out_off")
        glm_driver.run([
            "--train-data", train, "--output-dir", out,
            "--task", "logistic", "--reg-weights", "0.5",
            "--n-features", str(d), "--telemetry", "off",
        ])
        assert not os.path.exists(os.path.join(out, "events.jsonl"))
        assert not os.path.exists(os.path.join(out, "trace.json"))
        # ...and the run still trains a model.
        assert any(
            f.startswith("model_lambda") for f in os.listdir(out)
        )

    def test_game_driver_produces_nested_coordinate_spans(self, tmp_path):
        from photon_ml_tpu.data.game_reader import write_game_avro
        from photon_ml_tpu.drivers import game_training_driver

        rng = np.random.default_rng(11)
        n = 200
        records = [
            {
                "uid": f"row{i}",
                "response": float(rng.integers(2)),
                "weight": None,
                "offset": None,
                "ids": {"userId": f"u{rng.integers(12)}"},
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
        cfg = str(tmp_path / "cfg.json")
        with open(cfg, "w") as f:
            json.dump(config, f)
        out = str(tmp_path / "out")
        res = game_training_driver.run([
            "--train-data", train, "--config", cfg, "--output-dir", out,
        ])
        records_ = read_events(out)
        spans = {r["id"]: r for r in span_records(records_)}

        def ancestry(rec):
            chain = []
            while rec["parent"] is not None:
                rec = spans[rec["parent"]]
                chain.append(rec["name"])
            return chain

        solver = [
            r for r in span_records(records_)
            if r["name"] == "coordinate.train"
        ]
        # 2 CD iterations x 2 coordinates
        assert len(solver) == 4
        for s in solver:
            assert ancestry(s) == [
                "coordinate", "cd.iteration", "cd.fit", "train", "run"
            ]
        coords = {
            r["attrs"]["coordinate"]
            for r in span_records(records_) if r["name"] == "coordinate"
        }
        assert coords == {"fixed", "per_user"}
        # CD history entries carry wall-clock attribution.
        assert all("wall_seconds" in h for h in res["history"])
        assert all(h["wall_seconds"] > 0 for h in res["history"])
        snap = json.load(open(os.path.join(out, "metrics.json")))
        assert snap["histograms"]["cd_iteration_seconds"]["count"] == 2
        assert snap["counters"]["checkpoint_saves"] == 2
        trace = json.load(open(os.path.join(out, "trace.json")))
        assert isinstance(trace, list) and len(trace) > 0


class TestPrefetchTelemetry:
    def test_prefetch_pass_feeds_gauges_and_counters(self, tmp_path):
        from photon_ml_tpu.data.prefetch import TransferStats, run_prefetched

        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            stats = TransferStats()
            consumed = []
            run_prefetched(
                n_items=5,
                get_item=lambda k: np.full(1024, k, np.float32),
                put=lambda host: host,
                consume=lambda k, dev: consumed.append(k),
                depth=2,
                stats=stats,
            )
            snap = tel.snapshot()
        assert consumed == list(range(5))
        assert snap["counters"]["h2d_chunks_total"] == 5
        assert snap["counters"]["h2d_bytes_total"] == 5 * 1024 * 4
        assert snap["counters"]["prefetch_passes"] == 1
        assert "h2d_gbps" in snap["gauges"]
        assert snap["gauges"]["prefetch_max_live"] <= 2
        # The pass event rode the JSONL sink.
        events = [
            r for r in read_events(tmp_path)
            if r.get("type") == "event" and r["name"] == "prefetch.pass"
        ]
        assert len(events) == 1
        assert events[0]["attrs"]["chunks"] == 5

    def test_prefetch_without_hub_costs_one_branch(self):
        """No installed hub: run_prefetched must not record anything (the
        NULL hub is disabled) and must still stream correctly."""
        from photon_ml_tpu.data.prefetch import TransferStats, run_prefetched

        stats = TransferStats()
        out = []
        run_prefetched(
            n_items=3,
            get_item=lambda k: np.zeros(8, np.float32),
            put=lambda h: h,
            consume=lambda k, d: out.append(k),
            stats=stats,
        )
        assert out == [0, 1, 2]
        assert telemetry.current().snapshot()["counters"] == {}
