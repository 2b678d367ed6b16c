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
"""

from __future__ import annotations

import logging
import os
import time
from typing import Optional, Sequence

from photon_ml_tpu import telemetry as telemetry_mod

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

_log = logging.getLogger(__name__)

#: cache dir -> entry count at enable time (for end-of-run miss deltas).
_ENABLE_COUNTS: dict[str, int] = {}


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


def publish_cache_metrics(path: Optional[str]) -> Optional[int]:
    """End-of-run compile-cache attribution: entries now vs at enable
    time.  New persisted entries are programs this run compiled (cache
    MISSES at the >= min_compile_secs threshold); a run serving entirely
    from cache adds zero.  Returns the delta (None when unknown)."""
    tel = telemetry_mod.current()
    n = cache_entry_count(path)
    if n is None:
        return None
    start = _ENABLE_COUNTS.get(path)
    delta = None if start is None else max(0, n - start)
    if tel.enabled:
        tel.gauge("compile_cache_entries").set(n)
        if delta is not None:
            tel.counter("compile_cache_new_entries").inc(delta)
            tel.event(
                "compile_cache.summary", dir=path, entries=n,
                new_entries=delta,
            )
    return delta


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
    args, logger=None, min_compile_secs: float = 0.5
) -> Optional[str]:
    """Entry-point preamble: enable per ``args.compile_cache`` and log the
    dir."""
    path = enable_compile_cache(args.compile_cache, min_compile_secs)
    if path and logger is not None:
        logger.info(f"compilation cache: {path}")
    if path:
        n = cache_entry_count(path)
        if n is not None:
            _ENABLE_COUNTS[path] = n
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
    mode: Optional[str] = "auto", min_compile_secs: float = 0.5
) -> Optional[str]:
    """Turn JAX's persistent compilation cache on at :func:`cache_dir`
    (``"auto"``/None) or off (``"off"``); returns the directory in use,
    None when off.

    Where the environment already names the directory JAX is using it and
    nothing is set in code.  Compilations faster than ``min_compile_secs``
    are not persisted (they'd bloat the cache for no win).  An uncreatable
    directory (read-only checkout) degrades to an uncached run with a
    warning, never a crashed job.
    """
    import jax
    from jax._src import compilation_cache as _cc

    if mode not in (None, "auto", "off"):
        raise ValueError(
            f"compile cache mode must be 'auto' or 'off', got {mode!r}; "
            f"the directory is ${CACHE_DIR_ENV} or <checkout>/.jax_cache"
        )
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
