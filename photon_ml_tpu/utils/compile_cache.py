"""Persistent XLA compilation cache for every entry point.

The reference pays JVM+Spark startup per job but compiles nothing; this
framework's cost shape is inverted — jit compilation dominates short driver
runs and a serving start-up (the whole bucket ladder).  JAX's persistent
compilation cache removes that cost for every repeat invocation with the
same program shapes (λ re-grids, scoring reruns, resumed jobs, restarted
servers), including across processes.

WHERE the cache lives is decided in exactly one place, :func:`cache_dir`:
``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX honours
that variable by itself, so nothing is set in code), else the fixed
``<checkout>/.jax_cache``.  The directory is part of the cache key's
neighbourhood — a path built from a temporary name, a pid or a clock never
hits — so no other path is ever used.

Opt-out rather than opt-in at the entry points (``--compile-cache off``);
library users call :func:`enable_compile_cache` themselves.

:func:`enable_compile_cache` also installs, once a process, listeners on
``jax.monitoring`` that file one COMPILE RECORD per backend compile
(``telemetry.compile_records()``, docs/telemetry.md "Compile records"): the
program's name, its tracing, lowering and backend seconds, what the
persistent cache did with it, and the layer span it lay under.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Optional, Sequence

from photon_ml_tpu import telemetry as telemetry_mod
from photon_ml_tpu.telemetry.core import (
    file_compile_record,
    open_layer_span,
)

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

_log = logging.getLogger(__name__)

#: Compilations faster than this are not persisted by default (they would
#: bloat the cache for no win), and only a compile record of at least this
#: many seconds becomes a hub's ``compile`` event.
MIN_COMPILE_SECS = 0.5

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: JAX's plain events inside one backend compile, in the order it records
#: them: the request went to the persistent cache; it was served from it;
#: it was compiled and WRITTEN (JAX records ``cache_misses`` only when it
#: writes the entry: a compile under the thresholds records nothing).
_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_STORED_EVENT = "/jax/compilation_cache/cache_misses"


class _Pending(threading.local):
    """What one thread's compile events have said since its last record."""

    def __init__(self):
        self.depth = 0        # tracing / lowering intervals now open
        self.inside = 0.0     # backend seconds inside the outermost open one
        self.trace_s = 0.0
        self.lower_s = 0.0
        self.retrieval_s = 0.0
        self.events = set()   # of _REQUEST_EVENT, _HIT_EVENT, _STORED_EVENT


_pending = _Pending()


def _on_start(event: str, _start_time, **_kw) -> None:
    """JAX announces each timed interval when it opens (a scalar event):
    tracing nests (a jitted function traced into its caller) and so may
    lowering, and only the outermost interval's seconds are wall seconds."""
    if event == _TRACE_EVENT or event == _LOWER_EVENT:
        _pending.depth += 1


def _on_event(event: str, **_kw) -> None:
    if event in (_REQUEST_EVENT, _HIT_EVENT, _STORED_EVENT):
        _pending.events.add(event)


def _on_duration(event: str, secs: float, fun_name=None, **_kw) -> None:
    p = _pending
    if event == _TRACE_EVENT or event == _LOWER_EVENT:
        p.depth = max(0, p.depth - 1)
        if p.depth == 0:
            # A program compiled while this interval was open (an eager
            # operation inside a traced function) has its own record.
            own = max(0.0, secs - p.inside)
            p.inside = 0.0
            if event == _TRACE_EVENT:
                p.trace_s += own
            else:
                p.lower_s += own
    elif event == _RETRIEVAL_EVENT:
        p.retrieval_s += secs
    elif event == _BACKEND_EVENT:
        _file_record(p, str(fun_name), secs, time.perf_counter())


def _file_record(p: _Pending, program: str, backend_s: float, end: float):
    """One backend compile has ended on this thread: file its record, feed
    the hub's counters, and start the thread's next record."""
    import jax

    # JAX 0.9.0 asks its cache even where no directory is set (it hashes
    # the module, then finds no cache to read or write): that is off too.
    if (_REQUEST_EVENT not in p.events
            or not jax.config.jax_compilation_cache_dir):
        cache = "off"
    elif _HIT_EVENT in p.events:
        cache = "hit"
    elif _STORED_EVENT in p.events:
        cache = "stored"
    else:
        cache = "unstored"
    span = open_layer_span()
    record = {
        "type": "compile",
        "program": program,
        "ts": end - backend_s,
        "dur": backend_s,
        "trace_s": p.trace_s,
        "lower_s": p.lower_s,
        "backend_s": backend_s,
        "cache": cache,
        "retrieval_s": p.retrieval_s,
        "span": None if span is None else {
            "name": span.name, "id": span.span_id,
            "coordinate": span.attrs.get("coordinate"),
        },
        "tid": threading.get_ident(),
    }
    python_s, retrieval_s = p.trace_s + p.lower_s, p.retrieval_s
    if p.depth:
        p.inside += backend_s
    p.trace_s = p.lower_s = p.retrieval_s = 0.0
    p.events = set()
    file_compile_record(record)
    tel = telemetry_mod.current()
    if not tel.enabled:
        return
    tel.counter("compile_trace_lower_seconds").inc(python_s)
    if cache == "hit":  # JAX times a hit's retrieval inside its backend_s
        tel.counter("compile_cache_hits").inc()
        tel.counter("compile_cache_load_seconds").inc(retrieval_s)
    else:
        tel.counter("compile_backend_seconds").inc(backend_s)
        if cache != "off":
            tel.counter("compile_cache_misses").inc()
    if python_s + backend_s >= MIN_COMPILE_SECS:
        tel.event("compile", **{k: v for k, v in record.items()
                                if k not in ("type", "ts", "dur", "tid")})


def _install_listeners() -> None:
    """Register the three listeners unless this process already has them
    (``jax.monitoring`` keeps plain lists: registering twice would file
    every record twice)."""
    from jax._src import monitoring

    if _on_duration in monitoring.get_event_duration_listeners():
        return
    monitoring.register_scalar_listener(_on_start)
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def cache_entry_count(path: Optional[str]) -> Optional[int]:
    """Number of persisted executables in the cache dir (None when the
    dir is unreadable/absent).  JAX writes one flat file per program."""
    if not path:
        return None
    try:
        return sum(
            1 for e in os.scandir(path) if e.is_file()
        )
    except OSError:
        return None


def warmup(fns: Sequence, shapes: Sequence, logger=None) -> int:
    """Pre-compile jitted functions ahead of a latency-sensitive path.

    ``fns[i]`` is called once with zero-filled arguments materialized
    from ``shapes[i]`` — a tuple (or any pytree) of
    ``jax.ShapeDtypeStruct`` leaves (concrete arrays work too: only
    ``.shape``/``.dtype`` are read).  Calling through the normal jit
    entry populates jit's own executable cache — unlike
    ``fn.lower(...).compile()``, whose result a later direct call would
    not reuse — and routes compilations through the persistent
    compilation cache when one is enabled, so a restarted server warms
    from disk instead of recompiling.

    The serving runtime uses this at startup to compile its whole
    padded-batch bucket ladder off the request path.  Returns the number
    of NEW compilations (per-fn delta of jit's private ``_cache_size()``),
    and reports it through telemetry (``compile_cache_warmup_compiles``
    counter, ``compile_cache.warmup`` event with wall seconds).
    """
    import jax
    import jax.numpy as jnp

    if len(fns) != len(shapes):
        raise ValueError(
            f"warmup needs one shape tree per fn: {len(fns)} fns, "
            f"{len(shapes)} shapes"
        )
    tel = telemetry_mod.current()
    t0 = time.perf_counter()

    compiles = 0
    for fn, args in zip(fns, shapes):
        before = fn._cache_size()
        zeros = jax.tree_util.tree_map(
            lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), args
        )
        out = fn(*zeros)
        jax.block_until_ready(out)
        compiles += max(0, fn._cache_size() - before)
    wall = time.perf_counter() - t0
    if tel.enabled:
        tel.counter("compile_cache_warmup_compiles").inc(compiles)
        tel.gauge("compile_cache_warmup_seconds").set(round(wall, 4))
        tel.event(
            "compile_cache.warmup", fns=len(fns), compiles=compiles,
            seconds=wall,
        )
    if logger is not None:
        logger.info(
            "warmup: %d fn calls, %d compiles in %.2fs",
            len(fns), compiles, wall,
        )
    return compiles


def add_compile_cache_arg(parser) -> None:
    """The shared ``--compile-cache`` flag (one help text for all)."""
    parser.add_argument(
        "--compile-cache",
        choices=["auto", "off"],
        default="auto",
        help="persistent XLA compilation cache: 'auto' = "
        f"${CACHE_DIR_ENV} when set, else <checkout>/.jax_cache; 'off' "
        "disables (repeat runs recompile from scratch)",
    )


def enable_from_args(
    args, logger=None, min_compile_secs: float = MIN_COMPILE_SECS
) -> Optional[str]:
    """Entry-point preamble: enable per ``args.compile_cache`` and log the
    dir."""
    path = enable_compile_cache(args.compile_cache, min_compile_secs)
    if path and logger is not None:
        logger.info(f"compilation cache: {path}")
    if path:
        n = cache_entry_count(path)
        if n is not None:
            telemetry_mod.current().event(
                "compile_cache.enabled", dir=path, entries=n
            )
    return path


def cache_dir() -> str:
    """THE compile-cache directory: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else the fixed ``<checkout>/.jax_cache`` (git-ignored)."""
    return os.environ.get(CACHE_DIR_ENV) or os.path.join(
        _CHECKOUT, ".jax_cache"
    )


def enable_compile_cache(
    mode: Optional[str] = "auto", min_compile_secs: float = MIN_COMPILE_SECS
) -> Optional[str]:
    """Turn JAX's persistent compilation cache on at :func:`cache_dir`
    (``"auto"``/None) or off (``"off"``); returns the directory in use,
    None when off.

    Where the environment already names the directory JAX is using it and
    nothing is set in code.  Compilations faster than ``min_compile_secs``
    are not persisted (they'd bloat the cache for no win).  An uncreatable
    directory (read-only checkout) degrades to an uncached run with a
    warning, never a crashed job.  Either way the process's compiles are
    recorded from here on (``telemetry.compile_records()``).
    """
    import jax
    from jax._src import compilation_cache as _cc

    if mode not in (None, "auto", "off"):
        raise ValueError(
            f"compile cache mode must be 'auto' or 'off', got {mode!r}; "
            f"the directory is ${CACHE_DIR_ENV} or <checkout>/.jax_cache"
        )
    _install_listeners()
    path = None if mode == "off" else cache_dir()
    if path is not None:
        try:
            os.makedirs(path, exist_ok=True)
        except OSError as e:
            _log.warning("compile cache dir %s unusable (%s): off", path, e)
            path = None
        else:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs",
                min_compile_secs,
            )
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # JAX latches its cache handle at the first compile; a process
        # that compiled before this call keeps the old one without a reset.
        _cc.reset_cache()
    return path
