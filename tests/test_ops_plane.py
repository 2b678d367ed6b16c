"""Live ops plane tests (ISSUE 7): time-series sampler, Prometheus
exporter, HBM accounting, trace-context propagation, flight recorder,
metric-name lint, and the /stats single-source-of-truth dedupe.

The lifecycle bar: every background piece (sampler thread, exporter
server thread) must JOIN on close — including when a chaos fault tears
a streamed pass down mid-flight — and every fault-injection path must
leave a flight-recorder dump whose last event is the fault site.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu import chaos
from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry.exporter import (
    MetricsExporter,
    parse_prometheus_text,
    prometheus_text,
)
from photon_ml_tpu.telemetry.recorder import FlightRecorder
from photon_ml_tpu.telemetry.timeseries import TimeSeriesSampler, read_series


def _get(port, route):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{route}", timeout=10
    ) as resp:
        return resp.status, resp.read().decode()


def _small_stream(n=160, d=10, chunk_rows=40):
    from photon_ml_tpu.data.streaming import make_streaming_glm_data

    rng = np.random.default_rng(11)
    X = sp.random(n, d, density=0.5, random_state=2, format="csr",
                  dtype=np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    return make_streaming_glm_data(X, y, chunk_rows=chunk_rows,
                                   use_pallas=False)


# ---------------------------------------------------------------------------
# Time-series sampler
# ---------------------------------------------------------------------------

class TestTimeSeriesSampler:
    def test_brackets_run_with_monotone_snapshots(self, tmp_path):
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            tel.counter("solver_iterations").inc(3)
            tel.gauge("hbm_live_bytes").set(4096)
            with TimeSeriesSampler(tel, interval_s=0.02) as sampler:
                time.sleep(0.07)
                tel.counter("solver_iterations").inc(4)
            assert not sampler.alive
        series = read_series(str(tmp_path / "metrics_ts.jsonl"))
        assert len(series) >= 2  # start + interval(s) + stop
        seqs = [r["seq"] for r in series]
        monos = [r["t_mono"] for r in series]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        assert all(b > a for a, b in zip(monos, monos[1:]))
        # Counters are cumulative snapshots; the final record equals the
        # end-of-run state and carries the HBM gauge.
        assert series[-1]["counters"]["solver_iterations"] == 7
        assert series[-1]["gauges"]["hbm_live_bytes"] == 4096

    def test_rotation_bounds_disk(self, tmp_path):
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            for i in range(64):
                tel.gauge(f"hbm_g{i}_bytes").set(i)
            sampler = TimeSeriesSampler(
                tel, interval_s=1e9, max_bytes=2048, keep=2
            )
            sampler.start()
            for _ in range(40):
                sampler.sample()
            sampler.stop()
        path = str(tmp_path / "metrics_ts.jsonl")
        assert os.path.exists(path) and os.path.exists(path + ".1")
        assert not os.path.exists(path + ".3")  # keep=2 bounds the set
        # Every retained generation is bounded by max_bytes + one record.
        for p in (path, path + ".1", path + ".2"):
            if os.path.exists(p):
                assert os.path.getsize(p) < 2048 + 4096
        # The live file still parses after rotation.
        assert read_series(path)

    def test_disabled_hub_is_noop(self, tmp_path):
        tel = telemetry.Telemetry(
            output_dir=str(tmp_path / "off"), enabled=False
        )
        sampler = TimeSeriesSampler(tel, interval_s=0.01)
        sampler.start()
        assert not sampler.enabled and not sampler.alive
        sampler.stop()
        assert not os.path.exists(tmp_path / "off" / "metrics_ts.jsonl")


# ---------------------------------------------------------------------------
# Prometheus exporter
# ---------------------------------------------------------------------------

class TestExporter:
    def test_metrics_exposition_parses_and_matches(self, tmp_path):
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            tel.counter("solver_iterations").inc(12)
            tel.gauge("hbm_live_bytes").set(1 << 20)
            tel.gauge("run_note_count").set("not-numeric")  # skipped
            for v in (0.001, 0.002, 0.004):
                tel.histogram("serving_request_latency_seconds").observe(v)
            exporter = MetricsExporter(tel, port=0).start()
            try:
                status, body = _get(exporter.port, "/metrics")
            finally:
                exporter.close()
        assert status == 200
        parsed = parse_prometheus_text(body)
        assert parsed[("solver_iterations", "")] == 12
        assert parsed[("hbm_live_bytes", "")] == float(1 << 20)
        assert ("run_note_count", "") not in parsed
        assert parsed[
            ("serving_request_latency_seconds_count", "")
        ] == 3
        assert parsed[
            ("serving_request_latency_seconds", '{quantile="0.5"}')
        ] == pytest.approx(0.002, rel=0.3)

    def test_snapshot_and_healthz_endpoints(self, tmp_path):
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            tel.counter("solver_iterations").inc(5)
            exporter = MetricsExporter(tel, port=0).start()
            try:
                _, snap_body = _get(exporter.port, "/snapshot")
                _, hz_body = _get(exporter.port, "/healthz")
                status_404, _ = _get_404(exporter.port)
            finally:
                exporter.close()
        snap = json.loads(snap_body)
        assert snap["counters"]["solver_iterations"] == 5
        assert snap["trace"] == tel.trace_id and snap["pid"] == os.getpid()
        assert json.loads(hz_body)["status"] == "ok"
        assert status_404 == 404

    def test_close_joins_thread_no_leak(self, tmp_path):
        before = {t.name for t in threading.enumerate()}
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            plane = telemetry.mount_ops_plane(
                tel, port=0, interval_s=0.01
            )
            assert plane.exporter.alive and plane.sampler.alive
            plane.close()
            plane.close()  # idempotent
            assert not plane.exporter.alive and not plane.sampler.alive
        leaked = {
            t.name for t in threading.enumerate()
            if t.name.startswith("telemetry-")
        } - before
        assert not leaked

    def test_lifecycle_survives_chaos_mid_pass_teardown(self, tmp_path):
        """The ops plane mounted over a streamed pass that a chaos fault
        kills mid-flight: the fault dumps the flight recorder, the pass
        tears down without leaking prefetch threads, and plane.close()
        still joins both ops threads cleanly."""
        import jax.numpy as jnp

        from photon_ml_tpu.optim.streaming import StreamingObjective

        stream = _small_stream()
        sobj = StreamingObjective("logistic", stream)
        w = jnp.zeros((stream.n_features,), jnp.float32)
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            plane = telemetry.mount_ops_plane(tel, port=0, interval_s=0.02)
            try:
                plan = chaos.FaultPlan([
                    chaos.FaultSpec(site="streaming.carry_sync", at=1),
                ])
                with plan:
                    with pytest.raises(chaos.InjectedFault):
                        sobj.value_and_grad(w, 1.0)
                assert tel.counter("prefetch_thread_leak").value == 0
                # The exporter still answers after the fault.
                status, _ = _get(plane.port, "/metrics")
                assert status == 200
            finally:
                plane.close()
            assert not plane.exporter.alive and not plane.sampler.alive
        dump = json.load(open(tmp_path / "flightrecorder.json"))
        assert dump["events"][-1]["name"] == "chaos.fault"
        assert dump["events"][-1]["attrs"]["site"] == "streaming.carry_sync"


def _get_404(port):
    try:
        return _get(port, "/nope")
    except urllib.error.HTTPError as e:
        return e.code, ""


# ---------------------------------------------------------------------------
# Histogram quantiles (satellite: the one estimator behind loadgen)
# ---------------------------------------------------------------------------

class TestHistogramQuantile:
    def test_quantiles_track_percentiles(self):
        rng = np.random.default_rng(7)
        values = rng.lognormal(mean=0.0, sigma=1.5, size=5000)
        h = telemetry.Histogram(threading.Lock())
        for v in values:
            h.observe(v)
        for q in (0.5, 0.9, 0.99):
            exact = float(np.percentile(values, 100 * q))
            assert h.quantile(q) == pytest.approx(exact, rel=0.15)

    def test_edges(self):
        h = telemetry.Histogram(threading.Lock())
        assert h.quantile(0.5) is None
        h.observe(3.0)
        assert h.quantile(0.0) == 3.0 and h.quantile(1.0) == 3.0
        assert h.quantile(0.5) == pytest.approx(3.0, rel=0.3)
        s = h.summary()
        assert s["p50"] is not None and s["p99"] is not None

    def test_loadgen_report_uses_histogram_quantile(self):
        from photon_ml_tpu.serving.loadgen import LoadReport

        lat = np.sort(np.linspace(1.0, 100.0, 400))
        report = LoadReport(
            mode="test", wall_seconds=1.0, completed=400, rejected=0,
            errors=0, latencies_ms=lat,
        )
        snap = report.snapshot()
        assert snap["latency_p50_ms"] == pytest.approx(50.0, rel=0.15)
        assert snap["latency_p99_ms"] == pytest.approx(99.0, rel=0.15)
        assert snap["latency_max_ms"] == pytest.approx(100.0)
        empty = LoadReport(
            mode="test", wall_seconds=1.0, completed=0, rejected=0,
            errors=0, latencies_ms=np.zeros(0),
        )
        assert empty.percentile_ms(50) is None


# ---------------------------------------------------------------------------
# Metric-name lint
# ---------------------------------------------------------------------------

class TestMetricLint:
    def test_registry_rejects_kind_conflicts(self):
        reg = telemetry.MetricsRegistry()
        reg.counter("serving_requests_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("serving_requests_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.histogram("serving_requests_total")
        reg.counter("serving_requests_total").inc()  # same kind: fine

    def test_lint_name_convention(self):
        from photon_ml_tpu.telemetry.lint import lint_name

        assert lint_name("hbm_live_bytes") == []
        assert lint_name("serving_request_latency_seconds") == []
        assert lint_name("chaos_faults_injected") == []  # grandfathered
        assert any("subsystem" in i for i in lint_name("bogus_thing_total"))
        assert any("unit" in i for i in lint_name("serving_thing_blobs"))
        assert lint_name("CamelCase") != []

    def test_source_tree_is_clean(self):
        from photon_ml_tpu.telemetry.lint import lint_source

        n_names, problems = lint_source()
        assert n_names > 40  # the scan actually found the registrations
        assert problems == []

    def test_lint_cli(self, capsys):
        from photon_ml_tpu.telemetry.__main__ import lint_metrics

        assert lint_metrics() == 0
        assert "metric lint OK" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# Trace-context propagation
# ---------------------------------------------------------------------------

def _read_events(out_dir):
    with open(os.path.join(out_dir, "events.jsonl")) as f:
        return [json.loads(line) for line in f]


class TestTraceContext:
    def test_attach_parents_cross_thread_spans(self, tmp_path):
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            with tel.span("run") as run_span:
                ctx = tel.current_context()
                # (trace_id, span_id, remote_ctx) — remote is None
                # outside an adopted distributed context.
                assert ctx == (tel.trace_id, run_span.span_id, None)

                def worker():
                    with tel.attach(ctx):
                        with tel.span("worker_stage"):
                            tel.event("worker.event")

                t = threading.Thread(target=worker)
                t.start()
                t.join()
        records = _read_events(str(tmp_path))
        spans = {r["name"]: r for r in records if r["type"] == "span"}
        events = {r["name"]: r for r in records if r["type"] == "event"}
        assert spans["worker_stage"]["parent"] == spans["run"]["id"]
        assert events["worker.event"]["parent"] == spans["worker_stage"]["id"]

    def test_prefetch_stage_spans_nest_under_caller(self, tmp_path):
        import jax.numpy as jnp

        from photon_ml_tpu.optim.streaming import StreamingObjective

        stream = _small_stream()
        sobj = StreamingObjective("logistic", stream)
        w = jnp.zeros((stream.n_features,), jnp.float32)
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            with tel.span("solve"):
                sobj.value_and_grad(w, 1.0)
        records = _read_events(str(tmp_path))
        spans = {r["name"]: r for r in records if r["type"] == "span"}
        solve_id = spans["solve"]["id"]
        assert spans["prefetch.pack_stage"]["parent"] == solve_id
        assert spans["prefetch.transfer_stage"]["parent"] == solve_id
        # Different threads, one tree.
        tids = {
            spans[n]["tid"]
            for n in ("solve", "prefetch.pack_stage",
                      "prefetch.transfer_stage")
        }
        assert len(tids) == 3

    def test_serving_batch_span_parents_to_submitter(self, tmp_path):
        from photon_ml_tpu.serving.batcher import BatcherConfig, MicroBatcher
        from photon_ml_tpu.serving.runtime import (
            RuntimeConfig,
            ScoringRuntime,
        )
        from photon_ml_tpu.serving.synthetic import SyntheticWorkload

        workload = SyntheticWorkload(n_entities=16, seed=3)
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            runtime = ScoringRuntime(
                workload.model, workload.index_maps,
                RuntimeConfig(max_batch_size=4, hot_entities=8),
            )
            batcher = MicroBatcher(runtime, BatcherConfig(
                max_batch_size=4, max_wait_us=0, max_queue=16,
            ))
            batcher.start()
            with tel.span("request"):
                fut = batcher.submit(
                    runtime.parse_request(workload.request(0))
                )
                fut.result(timeout=30)
            batcher.stop()
        records = _read_events(str(tmp_path))
        spans = {r["name"]: r for r in records if r["type"] == "span"}
        assert spans["serving.batch"]["parent"] == spans["request"]["id"]
        assert spans["serving.batch"]["tid"] != spans["request"]["tid"]

    def test_wall_anchored_chrome_trace(self, tmp_path):
        """Multi-process merge: trace.json timestamps are wall-anchored
        (microseconds since the epoch), not run-relative."""
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            wall0 = tel._epoch_wall
            with tel.span("run"):
                pass
        trace = json.load(open(tmp_path / "trace.json"))
        xs = [ev for ev in trace if ev.get("ph") == "X"]
        assert xs and all(ev["ts"] >= wall0 * 1e6 for ev in xs)


# ---------------------------------------------------------------------------
# HBM accounting
# ---------------------------------------------------------------------------

class TestHbmAccounting:
    def test_streamed_pass_publishes_hbm_gauges(self):
        import jax.numpy as jnp

        from photon_ml_tpu.optim.streaming import StreamingObjective

        stream = _small_stream()
        sobj = StreamingObjective("logistic", stream, prefetch_depth=2)
        w = jnp.zeros((stream.n_features,), jnp.float32)
        with telemetry.Telemetry(enabled=True, sinks=[]) as tel:
            sobj.value_and_grad(w, 1.0)
            snap = tel.snapshot()
        g = snap["gauges"]
        # All transfers consumed by end of pass: live bytes back to 0,
        # but the pass's peak pinned > 0 and bounded by depth x chunk.
        assert g["hbm_live_bytes"] == 0
        assert g["hbm_live_peak_bytes"] > 0
        chunk_bytes = g["hbm_stream_chunk_bytes"]
        assert chunk_bytes > 0
        assert g["hbm_live_peak_bytes"] <= 2 * chunk_bytes
        # Window residency peak: live + dispatched-unexecuted, in bytes.
        assert g["hbm_stream_window_peak_bytes"] >= g["hbm_live_peak_bytes"]
        assert g["hbm_stream_window_peak_bytes"] <= 4 * chunk_bytes
        assert 0.0 < g["prefetch_ring_occupancy_ratio"] <= 1.0 or (
            g["prefetch_ring_occupancy_ratio"] == 0.0
        )
        assert sobj.transfer_stats.max_live_bytes > 0

    def test_serving_hot_table_bytes_gauge(self):
        from photon_ml_tpu.serving.runtime import (
            RuntimeConfig,
            ScoringRuntime,
        )
        from photon_ml_tpu.serving.synthetic import SyntheticWorkload

        workload = SyntheticWorkload(n_entities=32, seed=5)
        with telemetry.Telemetry(enabled=True, sinks=[]) as tel:
            runtime = ScoringRuntime(
                workload.model, workload.index_maps,
                RuntimeConfig(max_batch_size=4, hot_entities=16),
            )
            rows = [
                runtime.parse_request(workload.request(i)) for i in range(4)
            ]
            runtime.score_rows(rows)
            snap = tel.snapshot()
        expected = sum(
            (c.hot.capacity + 1) * c.hot.dim * 4 for c in runtime.random
        )
        assert expected > 0
        assert snap["gauges"]["hbm_serving_hot_table_bytes"] == expected
        assert runtime.hot_table_bytes == expected
        assert snap["gauges"]["serving_hot_resident_rows"] >= 1


# ---------------------------------------------------------------------------
# Serving /stats dedupe (satellite: single source of truth)
# ---------------------------------------------------------------------------

class TestServingStatsSource:
    def _service(self, workload):
        from photon_ml_tpu.serving.batcher import BatcherConfig
        from photon_ml_tpu.serving.runtime import (
            RuntimeConfig,
            ScoringRuntime,
        )
        from photon_ml_tpu.serving.service import ScoringService

        runtime = ScoringRuntime(
            workload.model, workload.index_maps,
            RuntimeConfig(max_batch_size=4, hot_entities=8),
        )
        return ScoringService(runtime, BatcherConfig(
            max_batch_size=4, max_wait_us=0, max_queue=16,
        ))

    def test_enabled_hub_stats_derive_from_registry(self):
        from photon_ml_tpu.serving.synthetic import SyntheticWorkload

        workload = SyntheticWorkload(n_entities=16, seed=9)
        with telemetry.Telemetry(enabled=True, sinks=[]) as tel:
            service = self._service(workload)
            with service:
                for i in range(5):
                    service.score(workload.request(i))
                stats = service.stats()["batcher"]
            assert stats["source"] == "telemetry"
            assert stats["submitted"] == 5
            assert stats["completed"] == 5
            assert stats["batches"] >= 1
            # Drift is impossible: the internal mirror was never written.
            assert service.batcher._counts["submitted"] == 0
            # The numbers ARE the registry's.
            snap = tel.snapshot()
            assert stats["submitted"] == snap["counters"][
                "serving_requests_total"
            ]

    def test_disabled_hub_keeps_internal_mirror(self):
        from photon_ml_tpu.serving.synthetic import SyntheticWorkload

        workload = SyntheticWorkload(n_entities=16, seed=9)
        assert not telemetry.current().enabled
        service = self._service(workload)
        with service:
            for i in range(3):
                service.score(workload.request(i))
            stats = service.stats()["batcher"]
        assert stats["source"] == "internal"
        assert stats["submitted"] == 3 and stats["completed"] == 3


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_is_bounded(self):
        rec = FlightRecorder(capacity=8)
        for i in range(50):
            rec.emit({"type": "event", "name": f"e{i}", "ts": float(i)})
        events = rec.snapshot()
        assert len(events) == 8
        assert events[0]["name"] == "e42" and events[-1]["name"] == "e49"
        assert rec.records_seen == 50

    def test_serving_batch_fault_dumps_last_events(self, tmp_path):
        """The satellite's required forensics test: an injected
        serving.batch fault leaves flightrecorder.json whose event
        window ENDS at the fault, while the request itself fails
        cleanly through the batcher's classified-error path."""
        from photon_ml_tpu.serving.batcher import BatcherConfig
        from photon_ml_tpu.serving.runtime import (
            RuntimeConfig,
            ScoringRuntime,
        )
        from photon_ml_tpu.serving.service import ScoringService
        from photon_ml_tpu.serving.synthetic import SyntheticWorkload

        workload = SyntheticWorkload(n_entities=16, seed=7)
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            runtime = ScoringRuntime(
                workload.model, workload.index_maps,
                RuntimeConfig(max_batch_size=4, hot_entities=8),
            )
            service = ScoringService(runtime, BatcherConfig(
                max_batch_size=4, max_wait_us=0, max_queue=16,
            ))
            plan = chaos.FaultPlan([
                chaos.FaultSpec(site="serving.batch", at=1),
            ])
            with service, plan:
                ok = service.score(workload.request(0))  # pre-fault traffic
                assert "score" in ok
                fut = service.submit(workload.request(1))
                with pytest.raises(chaos.InjectedFault):
                    fut.result(timeout=10)
            assert tel.counter("chaos_faults_injected").value == 1
        dump = json.load(open(tmp_path / "flightrecorder.json"))
        assert dump["reason"].startswith("chaos:serving.batch")
        events = dump["events"]
        assert events, "flight recorder dumped no events"
        assert events[-1]["name"] == "chaos.fault"
        assert events[-1]["attrs"]["site"] == "serving.batch"
        assert events[-1]["attrs"]["rows"] == 1
        # The pre-fault traffic is in the window too (it's a RECORDER,
        # not just the fault record): the healthy batch span precedes.
        names = [e["name"] for e in events]
        assert "serving.batch" in names
        assert len(events) <= dump["capacity"]

    def test_driver_crash_dump_via_hub_exit(self, tmp_path):
        with pytest.raises(RuntimeError, match="boom"):
            with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
                with tel.span("run"):
                    tel.event("about.to.die", step=3)
                    raise RuntimeError("boom")
        dump = json.load(open(tmp_path / "flightrecorder.json"))
        assert dump["reason"].startswith("crash: RuntimeError: boom")
        names = [e["name"] for e in dump["events"]]
        assert "about.to.die" in names and "run" in names

    def test_watchdog_fatal_dump(self, tmp_path):
        from photon_ml_tpu.utils.watchdog import (
            RetryPolicy,
            run_with_retries,
        )

        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            with tel.span("run"):
                def fn(attempt):
                    raise ValueError("INVALID_ARGUMENT: bad shape")

                with pytest.raises(ValueError):
                    run_with_retries(
                        fn, RetryPolicy(max_retries=2),
                        sleep=lambda s: None,
                    )
            # Dumped at the fatal verdict, not at hub exit.
            dump = json.load(
                open(tmp_path / "flightrecorder.json")
            )
        assert dump["reason"].startswith("watchdog-fatal: ValueError")
        assert dump["events"][-1]["name"] == "watchdog.attempt"
        assert dump["events"][-1]["attrs"]["outcome"] == "non_transient"

    def test_no_dump_on_clean_exit(self, tmp_path):
        with telemetry.Telemetry(output_dir=str(tmp_path)) as tel:
            with tel.span("run"):
                pass
        assert not os.path.exists(tmp_path / "flightrecorder.json")


# ---------------------------------------------------------------------------
# The extended module selfcheck ties it all together
# ---------------------------------------------------------------------------

class TestSelfcheck:
    def test_extended_selfcheck_passes(self, tmp_path):
        from photon_ml_tpu.telemetry.__main__ import selfcheck

        keep = str(tmp_path / "sc")
        assert selfcheck(keep) == 0
        # The acceptance artifacts exist and validate.
        assert os.path.exists(os.path.join(keep, "metrics_ts.jsonl"))
        assert os.path.exists(os.path.join(keep, "flightrecorder.json"))

    def test_prometheus_text_round_trip(self):
        snap = {
            "counters": {"serving_requests_total": 7},
            "gauges": {"hbm_live_bytes": 123, "run_name_count": "x"},
            "histograms": {
                "solver_wall_seconds": {
                    "count": 2, "sum": 3.0, "min": 1.0, "max": 2.0,
                    "mean": 1.5, "last": 2.0, "p50": 1.4, "p90": 1.9,
                    "p99": 2.0,
                },
            },
        }
        parsed = parse_prometheus_text(prometheus_text(snap))
        assert parsed[("serving_requests_total", "")] == 7
        assert parsed[("hbm_live_bytes", "")] == 123
        assert parsed[("solver_wall_seconds_sum", "")] == 3.0
        assert parsed[("solver_wall_seconds", '{quantile="0.99"}')] == 2.0
        with pytest.raises(ValueError, match="unparseable"):
            parse_prometheus_text("this is } not exposition format\n")
