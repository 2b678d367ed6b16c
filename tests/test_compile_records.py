"""Compile records (docs/telemetry.md "Compile records"): one per backend
compile, filed by the listeners ``enable_compile_cache`` installs, with the
program's name, its phases, what the persistent cache did and the layer
span it lay under."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry import core as telemetry_core
from photon_ml_tpu.utils import compile_cache


@pytest.fixture(autouse=True)
def _restore_jax_cache_config():
    """These tests move the process-global cache settings (conftest.py's)."""
    from jax._src import compilation_cache as _cc

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
    _cc.reset_cache()


@pytest.fixture
def cache_in(monkeypatch, tmp_path):
    """Point the one resolver at an empty directory of this test's own."""
    def enable(min_compile_secs):
        target = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", target)
        assert compile_cache.enable_compile_cache(
            "auto", min_compile_secs) == target
        return target
    return enable


def _fresh(name):
    """A jitted function called ``name`` that no cache has seen: the baked
    constant makes its HLO unique."""
    const = float(np.random.default_rng().uniform(1.0, 2.0))

    def fn(x):
        return jnp.where(x > 0, x * const, 0.0).sum()

    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _records(name):
    return [r for r in telemetry.compile_records()
            if r["program"] == f"jit({name})"]


def _run(fn):
    jax.block_until_ready(fn(jnp.arange(8.0)))


class TestOneRecordACompile:
    def test_without_a_cache_directory(self):
        assert compile_cache.enable_compile_cache("off") is None
        with telemetry.layer_span("game.build"):
            with telemetry.layer_span(
                    "coordinate.train", coordinate="per_user") as inner:
                _run(_fresh("known_name_off"))
        (rec,) = _records("known_name_off")
        assert rec["type"] == "compile" and rec["cache"] == "off"
        assert rec["retrieval_s"] == 0.0
        for phase in ("trace_s", "lower_s", "backend_s", "dur"):
            assert rec[phase] >= 0.0, phase
        assert rec["trace_s"] > 0.0 and rec["backend_s"] == rec["dur"] > 0.0
        assert rec["span"] == {"name": "coordinate.train",
                               "id": inner.span_id, "coordinate": "per_user"}
        # on the ring's clock, inside the span it names
        (span,) = [r for r in telemetry.layer_spans()
                   if r["id"] == inner.span_id]
        assert span["ts"] <= rec["ts"]
        assert rec["ts"] + rec["dur"] <= span["ts"] + span["dur"]

    def test_outside_every_layer_span(self):
        compile_cache.enable_compile_cache("off")
        _run(_fresh("known_name_bare"))
        (rec,) = _records("known_name_bare")
        assert rec["span"] is None

    def test_installing_twice_files_one_record(self):
        compile_cache.enable_compile_cache("off")
        compile_cache.enable_compile_cache("off")
        compile_cache.enable_compile_cache("auto")
        _run(_fresh("known_name_twice"))
        assert len(_records("known_name_twice")) == 1

    def test_a_function_traced_into_another_counts_once(self):
        """JAX times the inner function's tracing inside the outer's: a
        record holds the outermost interval's wall seconds, which the
        backend compile's start bounds."""
        compile_cache.enable_compile_cache("off")
        events = []

        def listen(event, secs, **_kw):
            if event.endswith("jaxpr_trace_duration"):
                events.append(secs)

        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            with telemetry.layer_span("grid") as span:
                _run(_fresh("known_name_nested"))
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
        (rec,) = _records("known_name_nested")
        assert len(events) > 1  # jnp.where and the rest, traced into it
        assert rec["trace_s"] < sum(events)
        assert rec["trace_s"] + rec["lower_s"] <= rec["ts"] - span.t0


class TestWhatTheCacheDid:
    def test_stored_above_the_threshold_unstored_below(self, cache_in):
        target = cache_in(0.0)
        _run(_fresh("known_name_stored"))
        (rec,) = _records("known_name_stored")
        assert rec["cache"] == "stored" and os.listdir(target)
        cache_in(3600.0)  # nothing compiles for an hour: never written
        before = set(os.listdir(target))
        _run(_fresh("known_name_unstored"))
        (rec,) = _records("known_name_unstored")
        assert rec["cache"] == "unstored"
        assert set(os.listdir(target)) == before

    def test_a_hit_after_the_programs_are_dropped(self, cache_in):
        target = cache_in(0.0)
        fn = _fresh("known_name_hit")
        _run(fn)
        if not os.listdir(target):
            pytest.skip("this backend does not persist its executables")
        jax.clear_caches()
        _run(fn)
        first, second = _records("known_name_hit")
        assert first["cache"] == "stored" and first["retrieval_s"] == 0.0
        assert second["cache"] == "hit"
        # JAX times the retrieval inside the backend interval
        assert 0.0 < second["retrieval_s"] <= second["backend_s"]


class TestTheStore:
    def test_is_bounded_and_leaves_the_layer_ring_alone(self):
        capacity = telemetry_core._COMPILE_RING.capacity
        assert capacity >= 4096
        assert telemetry_core._LAYER_RING.capacity == 4096
        with telemetry.layer_span("grid", marker="kept"):
            pass
        for i in range(capacity + 10):
            telemetry_core.file_compile_record({"program": f"p{i}"})
        records = telemetry.compile_records()
        assert len(records) == capacity
        assert records[-1] == {"program": f"p{capacity + 9}"}
        assert telemetry.layer_spans()[-1]["attrs"] == {"marker": "kept"}
        telemetry_core._COMPILE_RING._ring.clear()


class TestUnderADriverHub:
    def test_counters_and_one_event_a_slow_record(self, cache_in,
                                                  monkeypatch):
        cache_in(0.0)
        monkeypatch.setattr(compile_cache, "MIN_COMPILE_SECS", 0.0)
        fn = _fresh("known_name_hub")
        with telemetry.Telemetry(enabled=True, sinks=[]) as hub:
            events = []
            monkeypatch.setattr(
                hub, "event", lambda name, **a: events.append((name, a)))
            _run(fn)
            jax.clear_caches()
            _run(fn)
            counters = hub.metrics.snapshot()["counters"]
        miss, hit = _records("known_name_hub")
        assert counters["compile_cache_misses"] >= 1
        assert counters["compile_cache_hits"] >= 1
        assert counters["compile_backend_seconds"] >= miss["backend_s"]
        assert counters["compile_cache_load_seconds"] >= hit["retrieval_s"]
        assert counters["compile_trace_lower_seconds"] >= (
            miss["trace_s"] + miss["lower_s"])
        mine = [a for name, a in events
                if name == "compile" and a["program"] == "jit(known_name_hub)"]
        assert [a["cache"] for a in mine] == ["stored", "hit"]
        assert "ts" not in mine[0] and mine[0]["backend_s"] == miss["backend_s"]

    def test_no_event_under_the_threshold(self, monkeypatch):
        compile_cache.enable_compile_cache("off")
        monkeypatch.setattr(compile_cache, "MIN_COMPILE_SECS", 3600.0)
        with telemetry.Telemetry(enabled=True, sinks=[]) as hub:
            events = []
            monkeypatch.setattr(
                hub, "event", lambda name, **a: events.append(name))
            _run(_fresh("known_name_quick"))
        assert "compile" not in events
        assert len(_records("known_name_quick")) == 1


def test_the_removed_names_are_gone():
    for name in ("publish_cache_metrics", "_ENABLE_COUNTS"):
        assert not hasattr(compile_cache, name)
