"""Native (C++) ingest components, loaded via ctypes.

The shared library builds lazily from the checked-in source with the
system ``g++`` the first time it is needed (no pybind11 in this
environment; the C ABI + ctypes needs no Python headers).  The build is
cached next to the source and invalidated on source change.  Everything
here degrades gracefully: ``load_game_decoder()`` returns None when a
compiler is unavailable or the build fails, and callers fall back to the
pure-Python decoders.  The fallback is many times slower, so it is never
silent: :func:`status` says, per library, whether this process built it,
loaded a build found on disk, or fell back, and the entry points put that
in their result JSON.

Set ``PHOTON_NO_NATIVE=1`` to force the Python paths (used by parity
tests).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "game_decoder.cpp")
_LOCK = threading.Lock()
_CACHE: dict = {}
#: library prefix -> "built" | "loaded" | "fallback" (absent: never asked)
_STATUS: dict = {}

logger = logging.getLogger(__name__)


def _compile_cached(src: str, prefix: str, what: str) -> Optional[str]:
    """Lazy shared-library build: hash-tagged .so next to the source,
    atomic install (concurrent builders race safely), None + a warning on
    ANY failure (missing source/toolchain, compile error) — callers fall
    back to their pure-Python paths."""
    _STATUS[prefix] = "fallback"
    try:
        with open(src, "rb") as f:
            tag = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError as e:
        logger.warning("native %s source unreadable (%s)", what, e)
        return None
    so_path = os.path.join(_DIR, f"{prefix}_{tag}.so")
    if os.path.exists(so_path):
        _STATUS[prefix] = "loaded"
        return so_path
    tmp = f"{so_path}.build.{os.getpid()}"  # unique per builder: no
    # interleaved writes; the os.replace below is the atomic install
    base_cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o", tmp, src,
    ]
    # Try with OpenMP (the layout sorter parallelizes; sources guard with
    # #ifdef _OPENMP), then without — a toolchain missing libgomp must
    # degrade to a single-threaded native build, not to the Python path.
    last_err = None
    for extra in (["-fopenmp"], []):
        cmd = base_cmd[:-3] + extra + base_cmd[-3:]
        try:
            subprocess.run(
                cmd, check=True, capture_output=True, timeout=240
            )
            os.replace(tmp, so_path)
            _STATUS[prefix] = "built"
            return so_path
        except (OSError, subprocess.SubprocessError) as e:
            last_err = e
    detail = getattr(last_err, "stderr", b"") or b""
    logger.warning(
        "native %s build failed (%s): %s — using the Python path",
        what, last_err, detail.decode(errors="replace")[:500],
    )
    return None


def status() -> dict:
    """Per native library this process asked for: ``"built"`` (compiled in
    this process), ``"loaded"`` (a build for this exact source was on
    disk), ``"fallback"`` (the Python path is running instead — build,
    load or ``PHOTON_NO_NATIVE``).  Libraries never asked for are absent."""
    with _LOCK:
        return {k.lstrip("_"): v for k, v in sorted(_STATUS.items())}


def _load(key: str, src: str, prefix: str, what: str, bind):
    """Build-or-find, load and bind one library (memoized under ``key``);
    None — and a ``"fallback"`` status — on any failure or when
    ``PHOTON_NO_NATIVE=1``."""
    with _LOCK:
        if os.environ.get("PHOTON_NO_NATIVE") == "1":
            _STATUS[prefix] = "fallback"
            return None
        if key in _CACHE:
            return _CACHE[key]
        so_path = _compile_cached(src, prefix, what)
        lib = None
        if so_path is not None:
            try:
                lib = bind(ctypes.CDLL(so_path))
            except OSError as e:
                logger.warning("native %s load failed: %s", what, e)
                _STATUS[prefix] = "fallback"
        _CACHE[key] = lib
        return lib


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c_void = ctypes.c_void_p
    c_i64 = ctypes.c_int64
    c_char_p = ctypes.c_char_p
    sig = {
        "gd_new": ([ctypes.c_int], c_void),
        "gd_free": ([c_void], None),
        "gd_preload_shard": (
            [c_void, c_char_p, ctypes.POINTER(c_char_p), c_i64], None),
        "gd_decode_block": ([c_void, ctypes.c_char_p, c_i64, c_i64], c_i64),
        "gd_error": ([c_void], c_char_p),
        "gd_n_rows": ([c_void], c_i64),
        "gd_copy_row_data": (
            [c_void, ctypes.POINTER(ctypes.c_double),
             ctypes.POINTER(ctypes.c_double),
             ctypes.POINTER(ctypes.c_double)], None),
        "gd_uid_blob_len": ([c_void], c_i64),
        "gd_copy_uids": (
            [c_void, ctypes.c_char_p, ctypes.POINTER(c_i64),
             ctypes.POINTER(c_i64)], None),
        "gd_n_id_cols": ([c_void], c_i64),
        "gd_id_col_name": ([c_void, c_i64], c_char_p),
        "gd_id_col_blob_len": ([c_void, c_i64], c_i64),
        "gd_copy_id_col": (
            [c_void, c_i64, ctypes.c_char_p, ctypes.POINTER(c_i64),
             ctypes.POINTER(c_i64)], None),
        "gd_n_shards": ([c_void], c_i64),
        "gd_shard_name": ([c_void, c_i64], c_char_p),
        "gd_shard_nnz": ([c_void, c_i64], c_i64),
        "gd_shard_dropped": ([c_void, c_i64], c_i64),
        "gd_shard_unknown": ([c_void, c_i64], c_i64),
        "gd_shard_seen": ([c_void, c_i64], c_i64),
        "gd_copy_shard_coo": (
            [c_void, c_i64, ctypes.POINTER(c_i64), ctypes.POINTER(c_i64),
             ctypes.POINTER(ctypes.c_float)], None),
        "gd_shard_nkeys": ([c_void, c_i64], c_i64),
        "gd_shard_keys_blob_len": ([c_void, c_i64], c_i64),
        "gd_copy_shard_keys": (
            [c_void, c_i64, ctypes.c_char_p, ctypes.POINTER(c_i64)], None),
    }
    for name, (argtypes, restype) in sig.items():
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = restype
    return lib


def load_game_decoder() -> Optional[ctypes.CDLL]:
    """The bound shared library, building it if needed; None on failure or
    when ``PHOTON_NO_NATIVE=1``."""
    return _load("lib", _SRC, "_game_decoder", "game decoder", _bind)


# ---------------------------------------------------------------------------
# Layout sorter (the hot passes of the Pallas slot-layout build)
# ---------------------------------------------------------------------------

_SORT_SRC = os.path.join(_DIR, "layout_sort.cpp")


def _bind_sorter(lib: ctypes.CDLL) -> ctypes.CDLL:
    i64 = ctypes.c_int64
    p_i64 = ctypes.POINTER(i64)
    p_i32 = ctypes.POINTER(ctypes.c_int32)
    p_f32 = ctypes.POINTER(ctypes.c_float)
    lib.pl_sort_orientation.argtypes = [
        p_i64, p_i64, i64, i64, i64, i64, p_i32, p_i32, p_i64,
    ]
    lib.pl_sort_orientation.restype = i64
    lib.pl_scatter.argtypes = [
        p_i64, p_i64, p_f32, p_i32, p_i32, p_i32,
        i64, i64, i64, i64, i64, i64, i64,
        ctypes.c_void_p, p_f32, p_i64,
    ]
    lib.pl_scatter.restype = i64
    lib.pl_band_depths.argtypes = [
        p_i64, p_i64, i64, i64, i64, i64, p_i64, i64, p_i64,
    ]
    lib.pl_band_depths.restype = i64
    lib.pl_observed_team.argtypes = []
    lib.pl_observed_team.restype = i64
    return lib


def load_layout_sorter() -> Optional[ctypes.CDLL]:
    """The layout-sorter library, building it if needed; None on failure
    or when ``PHOTON_NO_NATIVE=1`` (numpy fallback — bit-identical
    output, parity-tested)."""
    return _load(
        "sorter", _SORT_SRC, "_layout_sort", "layout sorter", _bind_sorter
    )


# ---------------------------------------------------------------------------
# Scoring-result Avro encoder (the write-side mirror of the decoder)
# ---------------------------------------------------------------------------

_ENC_SRC = os.path.join(_DIR, "score_encoder.cpp")


def _bind_encoder(lib: ctypes.CDLL) -> ctypes.CDLL:
    i64 = ctypes.c_int64
    p_i64 = ctypes.POINTER(i64)
    p_u8 = ctypes.POINTER(ctypes.c_uint8)
    p_f64 = ctypes.POINTER(ctypes.c_double)
    lib.se_encode.argtypes = [
        i64,
        ctypes.c_char_p, p_i64, p_u8,
        p_f64,
        p_f64, p_u8,
        i64,
        ctypes.c_char_p, p_i64, p_u8,
        ctypes.c_char_p, p_i64,
        ctypes.c_char_p, i64,
    ]
    lib.se_encode.restype = i64
    return lib


def load_score_encoder() -> Optional[ctypes.CDLL]:
    """The scoring-result encoder library, building it if needed; None on
    failure or when ``PHOTON_NO_NATIVE=1`` (pure-Python fallback —
    bit-identical output, parity-tested)."""
    return _load(
        "encoder", _ENC_SRC, "_score_encoder", "score encoder",
        _bind_encoder,
    )


# ---------------------------------------------------------------------------
# GAME grouping fill (the per-bucket copies of game/data._group_entities)
# ---------------------------------------------------------------------------

_FILL_SRC = os.path.join(_DIR, "group_fill.cpp")


class GfRows(ctypes.Structure):
    """``GfRows`` of group_fill.cpp: the grouping's arrays over sorted
    positions, entries and entities."""

    _fields_ = [
        ("starts", ctypes.c_void_p), ("span_sizes", ctypes.c_void_p),
        ("keep", ctypes.c_void_p), ("order", ctypes.c_void_p),
        ("labels", ctypes.c_void_p), ("weights", ctypes.c_void_p),
        ("indptr", ctypes.c_void_p), ("data", ctypes.c_void_p),
        ("col_rank", ctypes.c_void_p), ("rank_i64", ctypes.c_int64),
        ("col_hit", ctypes.c_void_p), ("act_before", ctypes.c_void_p),
        ("act_counts", ctypes.c_void_p), ("act_col", ctypes.c_void_p),
    ]


class GfBucket(ctypes.Structure):
    """``GfBucket`` of group_fill.cpp: one bucket's shapes and arrays."""

    _fields_ = [
        ("E", ctypes.c_int64), ("R", ctypes.c_int64), ("D", ctypes.c_int64),
        ("minor_r", ctypes.c_int64), ("P", ctypes.c_int64),
        ("p_minor_r", ctypes.c_int64),
        ("lab", ctypes.c_void_p), ("wts", ctypes.c_void_p),
        ("rindex", ctypes.c_void_p), ("X", ctypes.c_void_p),
        ("cmap", ctypes.c_void_p), ("rindexp", ctypes.c_void_p),
        ("slot", ctypes.c_void_p), ("Xp", ctypes.c_void_p),
    ]


def _bind_group_fill(lib: ctypes.CDLL) -> ctypes.CDLL:
    p_i64 = ctypes.POINTER(ctypes.c_int64)
    lib.gf_fill.argtypes = [
        ctypes.POINTER(GfRows), ctypes.POINTER(GfBucket), ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, p_i64, p_i64, p_i64, p_i64,
    ]
    lib.gf_fill.restype = ctypes.c_int64
    return lib


def load_group_fill() -> Optional[ctypes.CDLL]:
    """The grouping-fill library, building it if needed; None on failure
    or when ``PHOTON_NO_NATIVE=1`` (numpy fallback — bit-identical
    blocks, parity-tested)."""
    return _load(
        "group_fill", _FILL_SRC, "_group_fill", "group fill",
        _bind_group_fill,
    )
