"""What ran where: the device and compile accounting every entry point
reports.

A run on the wrong backend looks healthy from the inside — JAX falls back
to the CPU quietly, layouts follow the backend name, Pallas kernels have
an interpret mode.  So each entry point (the three drivers, the serving
CLI) logs :func:`describe_devices` once at start and writes a
:func:`runtime_block` into its result JSON; ``chip_smoke.py`` and the
benchmark read the device from there instead of assuming it.
"""

from __future__ import annotations

import importlib.metadata
from typing import Optional

import jax

from photon_ml_tpu import native
from photon_ml_tpu.utils import compile_cache

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _version(dist: str) -> Optional[str]:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


def describe_devices() -> dict:
    """The backend as JAX reports it, plus the versions that decide what
    compiles on it.  Initializes the backend (claims the chip)."""
    from photon_ml_tpu.ops.sparse_pallas import _interpret

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax": jax.__version__,
        "jaxlib": _version("jaxlib"),
        "libtpu": _version("libtpu"),
        "pallas_interpret": _interpret(),
    }


def describe_layout(features, shards: int = 1) -> str:
    """The feature-matrix class a run trains on, with what shapes its
    kernels: for the tiled Pallas layout the value mode, the packed
    sublane counts of both orientations, dense stripes and spill."""
    name = type(features).__name__
    if name == "PallasSparseMatrix":
        f = features
        spill = f.spill.spill_coo.nnz if f.spill.has_spill else 0
        name += (
            f"[{'unit' if f.unit_vals else 'valued'} A={f.a_f}/{f.a_b}"
            f" dense={f.dense_col_ids.shape[0]}/{f.dense_row_ids.shape[0]}"
            f" spill={spill}{' perm' if f.has_col_perm else ''}]"
        )
    elif name == "WideSparseMatrix":
        f = features
        spill = f.cold_spill.spill_coo.nnz if f.cold_spill.has_spill else 0
        name += (f"[warm={f.warm_cols.shape[0]} cols"
                 + (f" A={f.warm.a_f}/{f.warm.a_b}" if f.has_warm else "")
                 + f" cold A={f.cold_a_f}/{f.cold_a_b} spill={spill}]")
    if shards > 1:
        name += f" x{shards} row shards"
    return name


def bytes_in_use() -> list:
    """Per-device ``memory_stats()["bytes_in_use"]`` (None where the
    backend reports no memory stats — the CPU)."""
    out = []
    for d in jax.devices():
        stats = d.memory_stats()
        out.append(None if stats is None else int(stats["bytes_in_use"]))
    return out


class CompileClock:
    """Seconds JAX spent producing executables while entered: tracing,
    lowering and backend compilation (a persistent-cache hit's retrieval
    time lands in the backend share), from JAX's own monitoring events —
    what separates compile time from run time in an entry point's wall."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.backend_seconds = 0.0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            if event == _COMPILE_EVENTS[-1]:
                self.backend_seconds += duration

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def runtime_block(
    clock: CompileClock,
    cache_dir: Optional[str],
    feature_layout=None,
    placed_bytes: Optional[list] = None,
) -> dict:
    """The ``"runtime"`` entry of an entry point's result JSON.

    ``feature_layout`` names the feature-matrix class(es) the run trained
    or scored on (a string, or coordinate name → string);
    ``placed_bytes`` is :func:`bytes_in_use` taken right after the data
    was placed on the device(s)."""
    entries = compile_cache.cache_entry_count(cache_dir)
    return {
        **describe_devices(),
        "feature_layout": feature_layout,
        "bytes_in_use_after_placement": placed_bytes,
        "compile_seconds": round(clock.seconds, 3),
        "backend_compile_seconds": round(clock.backend_seconds, 3),
        "compile_cache_hits": clock.cache_hits,
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": entries,
        "native": native.status(),
    }
