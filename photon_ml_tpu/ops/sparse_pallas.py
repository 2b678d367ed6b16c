"""TPU-native sparse feature matrix: tiled Pallas kernels for the GLM hot loop.

This is the framework's BLAS-layer replacement (SURVEY.md §2: "the
performance-critical kernels to write are Pallas/XLA kernels (sparse matvec,
segment reductions)") — the analogue of the reference's netlib/Breeze BLAS
under its ``ValueAndGradientAggregator`` hot loop.

Why not XLA gather/scatter: on TPU a large-index ``jnp.take`` is
effectively a scalar loop, and ``segment_sum`` lowers to scatter, which is
as bad (the one place the ledger shows it: six gathers and two scatters
over 20 M rows were 7.33 s of a GAME fit's 17.94 device seconds before
PR 28 removed them; ledger, PRs 27 and 28).  Mosaic's only fast
data-movement primitive is
``tpu.dynamic_gather`` on a single 128-lane vreg: each sublane of an
``(A, 128)`` operand is an independent 128-wide lookup table.

The kernel design exploits exactly that:

- :class:`PallasSparseMatrix` cuts the matrix into ``TILE_R x TILE_C =
  2048 x 2048`` tiles and stores its grid whole, each tile at one depth:
  right where every tile holds entries enough to fill a few sublanes (the
  text and GAME shapes).  Each tile's
  entries are placed, ON HOST at build time, into a window-PACKED slot grid
  ``(A, 128)`` where

  * ``lane  = row % 128``                      (matvec orientation "F")
  * an entry's *window* ``(col % 2048) // 128`` decides which sublanes can
    hold it: each (tile, window) owns a contiguous run of
    ``min(max-lane-load, depth)`` sublanes (bin-packed per tile), and every
    sublane needs ONE 128-wide slice of ``w`` as its gather table;
  * extra sublanes per window absorb (window, lane) collisions; overflow
    past the cost-model depth spills to a tiny COO tail.

  Packing beats a uniform ``depth × WINS`` grid on slot padding:
  A = Σ over windows of that window's own worst lane, instead of
  ``WINS ×`` the worst cell anywhere in the matrix.

- matvec per tile: per-sublane gather tables are built from each sublane's
  packed window id — ONE one-hot matmul on the MXU
  (f32-HIGHEST, guarded per grid step: a step whose vector windows carry
  inf/nan falls back to an exact 16-step masked-SELECT sweep so inf/nan
  stay localized) — then ONE ``dynamic_gather`` of the
  whole ``(A, 128)`` block, then a 16-step masked sweep adds each output
  window's slots, sublane group onto sublane group, into a VMEM
  accumulator of 8 partial sums per output element
  (``ohi = (row % 2048) // 128``, packed per slot, selects the window);
  the 8 -> 1 reduce into the ``(16, 128)`` margin block runs once per
  output block.  No scatter anywhere, and nothing serial per tile but the
  tile's own chain, which the loop overlaps with its neighbours'
  (``_tiles_per_block``).

- rmatvec (the gradient side, Xᵀu) is the SAME kernel with roles mirrored
  (orientation "B": lane = col % 128, tables = 128-wide windows of ``u``,
  sweep over column-his).  Both directions therefore run at the same rate —
  the property Spark's treeAggregate had for free and TPUs do not.

Measured on one TPU v5e chip (my chip runs, PR 30, traced; PERF.md §5,
§6): in ``glm_lbfgs_fit`` (804,414 x 47,237, 76 nnz/row) a forward product
over 9,432 tiles 128 sublanes deep takes 2.30 ms and a backward one (160
deep) 2.91 ms (10.3 and 10.8 before PR 30), 17.4% of the bytes-bound
roofline, a 10-iteration solve 0.136 s; in ``game_cd_fit``'s fixed effect
(136.7 k unit tiles 32 / 48 deep) 7.2 and 9.9 ms in the solver's loop (63
and 82), 1.37%.  A product is affine in the depth with almost no constant
left (0.14 ms + 0.0176 ms a sublane forward on the text grid); what it
costs now is the 16-step output sweep over mostly empty slots (PERF.md
§5).

- :class:`WideSparseMatrix` stores a matrix too wide and sparse for that
  grid (a hashed click log: 10^6 columns, 39 entries a row, where the grid
  would be 2 M tiles of ~10 entries a window).  Its columns are parted by
  entry count (:func:`build_wide_host`): a WARM band, the popular prefix,
  is a :class:`PallasSparseMatrix` of its own (stripes, permutation, the
  tile kernel above) over only its own columns; the COLD band, every
  other column's entries, lies in ``COLD_TILE``-square blocks whose slots
  each carry their own gather window (:func:`_cold_kernel`: a lane gather
  and a select a window, then the same output sweep, all 64 windows
  unrolled and several blocks a basic block), so that a block's few entries
  from many windows share sublanes instead of each taking one.  Measured
  on one TPU v5e (PERF.md §6): a product over 1,024 x 123 blocks 16 deep
  takes 60.2 ms and over 123 x 1,024 blocks 24 deep 89.2 ms (153 and 173
  ms one block a loop trip): the cold band's time now follows its
  sweep over 64 output windows a block.  The band's width is chosen by
  the predicted device time of a product pair (:func:`_warm_prefix`).
  The cold band stores every block of its grid at one depth an
  orientation: 4 x 128 x (``A_f`` + ``A_b``)
  bytes a block, so its bytes follow rows x columns / ``COLD_TILE``^2 x
  depth, which is 1/64 of the tile grid's count and proportional to the
  entries only while a block's depth tracks its entries (PERF.md §5
  gives the click cell's fill).  Each depth is chosen by the predicted
  device time of a product pair (:func:`_cold_depths`): a block's cost
  grows faster than its depth, so the few entries of the deepest lanes
  go to a compact spill COO rather than set every block's depth.

- ``make_glm_data(use_pallas="auto")`` takes the wide form when the tile
  grid's predicted fill at its least depth, :func:`grid_fill_bound` =
  entries / (tiles x ``SUBPAD`` x 128), is under ``WIDE_FILL`` (1): the
  text cells read 3.2, the GAME shard 1.44, the click log 0.08.

Precision: everything is f32 — bit-comparable to the COO path (only
summation ORDER differs).  Table construction is pure selection (no
arithmetic).  No bf16 shortcuts in the value path.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.ops.sparse import (
    DenseMatrix,
    SparseMatrix,
    canonicalize_coo,
)
from photon_ml_tpu.telemetry import layer_span
from photon_ml_tpu.utils.placement import place_leaves

Array = jax.Array

# Tile edge (PHOTON_PALLAS_TILE; read once at import, the kernels bake
# it at trace time).  The per-tile output sweep costs WINS = TILE/128
# masked passes over the slot grid, so smaller tiles trade DMA granularity
# for sweep work; 2048 is the largest edge whose packed slot code still
# fits int16 (CODE_DTYPE below: past it the index bytes double).  Both
# benchmark cells run at 2048; no other edge has been timed on the chip
# (ROADMAP S2 sweeps it, then takes the variable out).
TILE_R = int(os.environ.get("PHOTON_PALLAS_TILE", "2048"))
if TILE_R < 128 or TILE_R % 128 or TILE_R > 32768:
    # The packed slot code (win | ohi | lo) switches to int32 automatically
    # past TILE 2048 (CODE_DTYPE below); 32768 is a sanity bound.
    raise ValueError(
        f"PHOTON_PALLAS_TILE must be a multiple of 128 in [128, 32768], "
        f"got {TILE_R}"
    )
TILE_C = TILE_R
WIN = 128           # window width = lanes per vreg
WINS = TILE_R // WIN  # windows per tile side
# Packed per-slot code layout: | win | ohi | lo |, low bits first.
#   lo  (7 bits)      — gather index into the sublane's 128-wide table
#   ohi (OBITS bits)  — output window within the tile
#   win (OBITS bits)  — the SUBLANE's gather window (same value in all 128
#                       slots of a sublane; the kernel reads lane 0)
# int16 when it fits (TILE ≤ 2048 — halves index DMA), else int32.
OBITS = max(1, (WINS - 1).bit_length())
WIN_SHIFT = 7 + OBITS
_CODE_BITS = 7 + 2 * OBITS
CODE_DTYPE = np.int16 if _CODE_BITS <= 15 else np.int32
CODE_BYTES = 2 if _CODE_BITS <= 15 else 4
# Empty slots carry the code dtype's SIGN bit (win bits preserved — the
# kernel still reads lane 0's window id through CODE_MASK).  This lets the
# unit-value layout drop the f32 val stream entirely: validity is
# ``code >= 0``, cutting slot DMA 6 → 2 bytes on binary feature matrices
# (the reference's canonical case — a1a features, one-hot GAME features).
CODE_MASK = (1 << _CODE_BITS) - 1
EMPTY_MARK = np.iinfo(CODE_DTYPE).min
# Sublane-count granularity: the int16 slot arrays tile as (16, 128) on TPU,
# so A is padded to a multiple of 16 (8 would re-pad internally).
SUBPAD = 16
# Per-grid-step DMA budget for the tile kernel (bytes): in MBs, so that the
# stream is not bound by per-step overhead.  A grid step's input blocks are
# double-buffered in VMEM beside the tables, the output block and the
# accumulator; all of it stays within VMEM_BUDGET (``_pick_rect``), under
# the compiler's scoped-VMEM limit (16 MiB on a v5e).  Both benchmark
# cells and tests/test_kernel_names_v5e.py compile with these two values.
DMA_BUDGET = 4 << 20
VMEM_BUDGET = 12 << 20
# Partial sums the kernel keeps per output element until an output block's
# last grid step: one vreg's sublanes.
ACC_SUB = 8


#: Entries the stripe split hands one thread at a time.
_SPLIT_CHUNK = 1 << 24


def _in_threads(fn, items) -> list:
    """``[fn(x) for x in items]`` on a few threads: for numpy passes over
    large arrays, which release the interpreter lock."""
    from concurrent.futures import ThreadPoolExecutor

    items = list(items)
    if len(items) <= 1:
        return [fn(x) for x in items]
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        return list(pool.map(fn, items))


def _cptr(arr: np.ndarray, ct):
    """ctypes pointer to a contiguous numpy array (native build glue)."""
    import ctypes

    return arr.ctypes.data_as(ctypes.POINTER(ct))


def _extract_fields(r32: np.ndarray, c32: np.ndarray, nbc: int):
    """(tile, gwin, lane) in int32, with shifts/masks where the tile edge
    is a power of two (the default) — numpy's int64 floor-division is
    scalar (~0.5 s per pass at 33M entries).  Shared by the layout build
    and the permutation predictor."""
    if TILE_R & (TILE_R - 1) == 0:
        tshift = TILE_R.bit_length() - 1
        tr = r32 >> tshift
        tc = c32 >> tshift
        gwin = (c32 >> 7) & (WINS - 1)
    else:
        tr = (r32 // TILE_R).astype(np.int32)
        tc = (c32 // TILE_C).astype(np.int32)
        gwin = ((c32 % TILE_C) // WIN).astype(np.int32)
    tile = tr * np.int32(nbc) + tc
    lane = r32 & np.int32(WIN - 1)
    return tile, gwin, lane


def _interpret() -> bool:
    """Run kernels in interpreter mode (CPU tests set this env var).

    On a TPU the variable is an error, not a mode: an interpreted kernel
    there returns right answers while Mosaic compiles nothing, which is
    exactly what a run on the chip exists to rule out."""
    on = os.environ.get("PHOTON_PALLAS_INTERPRET", "") == "1"
    if on and jax.default_backend() == "tpu":
        raise RuntimeError(
            "PHOTON_PALLAS_INTERPRET=1 with a TPU backend: interpret mode "
            "is for CPU tests; unset it to run the Mosaic-compiled kernels"
        )
    return on


def pallas_available() -> bool:
    """True when the Pallas sparse path can run here (TPU, or interpret)."""
    return jax.default_backend() == "tpu" or _interpret()


# ---------------------------------------------------------------------------
# Host-side layout build
# ---------------------------------------------------------------------------


def _build_orientation(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    nbr: int,
    nbc: int,
    depth_cap: int,
    spill_cost_ratio: float = 1024.0,
    unit: bool = False,
):
    """Place entries into the window-PACKED (tile, sublane, lane) slot grid.

    Orientation F (matvec): ``rows`` are the lane/output side, ``cols`` the
    gather side.  Call with rows/cols swapped (and nbr/nbc swapped) for
    orientation B.  Returns (code, val, spill_idx, a, depth) where

    code (NBR, NBC, A, 128) — packed ``win<<WIN_SHIFT | ohi<<7 | lo``:
         ``lo`` indexes the sublane's 128-wide gather table, ``ohi`` is the
         output window, ``win`` the SUBLANE's gather window (present in
         every slot, empty or not — the kernel reads lane 0's copy)
    val  (NBR, NBC, A, 128) f32 — entry values (0 in empty slots); with
         ``unit`` a (1,) placeholder, never filled (the unit-value layout
         streams codes alone, and at 10^8 entries the grid of values was
         the build's largest temporary)

    Packing: each (tile, window) pair owns a CONTIGUOUS run of
    ``need = min(max-lane-load, depth)`` sublanes, bin-packed per tile, so
    A = max over tiles of Σ_w need — instead of the old uniform
    ``WINS × global-max-depth`` grid.  On Poisson-spread data this cuts slot
    padding: the old grid paid ``WINS ×`` the WORST cell anywhere,
    the packed layout pays each window's own worst lane, summed.

    Depth (the per-cell slot cap) is still COST-based: covering one more
    collision level costs real slots only where windows actually need it
    (Σ over windows of the increment to ``min(M, d)``, maxed over tiles),
    while each spilled entry costs ~``spill_cost_ratio`` slot-equivalents
    on the XLA gather/segment_sum path (a scalar loop per entry),
    plus a FIXED penalty for any nonzero spill (the XLA scatter's latency
    floor, worth ~16 uniform depth levels).  ``spill_cost_ratio=inf``
    forces full coverage (used for the post-spill rebuild).
    """
    nt = nbr * nbc

    if len(rows) == 0:  # all-zero / empty matrix: one empty sublane group
        return (
            np.full((nbr, nbc, SUBPAD, WIN), EMPTY_MARK, CODE_DTYPE),
            np.zeros((nbr, nbc, SUBPAD, WIN), np.float32),
            np.empty(0, np.intp),
            SUBPAD,
            1,
        )

    # Sort + per-cell depth positions + per-(tile, window) max lane loads:
    # the NATIVE path (native/layout_sort.cpp — stable radix argsort with
    # numpy's exact tie order, one sequential scan) when the library is
    # available and the entry count is worth the ctypes round trip; the
    # numpy formulation below otherwise.  Outputs are BIT-IDENTICAL
    # (parity-tested), so everything downstream is shared.
    rows64 = cols64 = None
    lib = None
    if len(rows) >= (1 << 18):
        from photon_ml_tpu.native import load_layout_sorter

        lib = load_layout_sorter()
    if lib is not None:
        import ctypes

        rows64 = np.ascontiguousarray(rows, np.int64)
        cols64 = np.ascontiguousarray(cols, np.int64)
        nnz = len(rows64)
        order = np.empty(nnz, np.int32)
        depth_pos = np.empty(nnz, np.int32)
        M = np.zeros(nt * WINS, np.int64)

        rc = lib.pl_sort_orientation(
            _cptr(rows64, ctypes.c_int64), _cptr(cols64, ctypes.c_int64),
            nnz, nbc, TILE_R, nt,
            _cptr(order, ctypes.c_int32), _cptr(depth_pos, ctypes.c_int32),
            _cptr(M, ctypes.c_int64),
        )
        if rc != 0:  # nnz beyond int32 indexing: numpy handles it
            lib = None
    if lib is None:
        r32 = rows.astype(np.int32, copy=False)
        c32 = cols.astype(np.int32, copy=False)
        tile, gwin, lane = _extract_fields(r32, c32, nbc)

        # One combined sort key (≈2-3x faster than a 3-key lexsort at 33M
        # entries), in int32 when it fits; kind="stable" selects numpy's
        # radix sort for integer keys (~2x quicksort at this size).
        kmax = nt * WINS * WIN
        kdtype = np.int32 if kmax < 2**31 else np.int64
        key = (
            (tile.astype(kdtype) * WINS + gwin) * WIN + lane
        )
        order = np.argsort(key, kind="stable")
        cell = key[order]
        # run-length position within equal consecutive cells
        change = np.empty(len(cell), dtype=bool)
        change[0] = True
        np.not_equal(cell[1:], cell[:-1], out=change[1:])
        run_starts = np.flatnonzero(change)
        run_ids = np.cumsum(change) - 1
        depth_pos = np.arange(len(cell)) - run_starts[run_ids]

        # Per-(tile, window) max lane load M — the sublanes window w needs
        # at depth cap d is min(M[t, w], d) (max of min = min of max per
        # lane).  cell ids are sorted, so grouped reduceat beats the
        # ufunc.at path (~10x at 33M entries).
        counts = np.diff(np.append(run_starts, len(cell)))
        cell_tw = (cell[run_starts] // WIN).astype(np.int64)
        tw_change = np.empty(len(cell_tw), dtype=bool)
        tw_change[0] = True
        np.not_equal(cell_tw[1:], cell_tw[:-1], out=tw_change[1:])
        tw_starts = np.flatnonzero(tw_change)
        M = np.zeros(nt * WINS, np.int64)
        M[cell_tw[tw_starts]] = np.maximum.reduceat(counts, tw_starts)
    M = M.reshape(nt, WINS)

    hist = np.bincount(depth_pos)
    cum = np.cumsum(hist)
    spilled_at = len(depth_pos) - cum  # spilled(d) for d = 1..len(hist)
    if np.isinf(spill_cost_ratio):
        depth = len(hist)
    else:
        max_d = min(len(hist), depth_cap)
        # cost(d) = slots(d) + ratio·spilled(d) + fixed·(spilled(d) > 0)
        a_at = np.array(
            [np.minimum(M, d).sum(axis=1).max() for d in range(1, max_d + 1)],
            np.float64,
        )
        cost = (
            a_at * float(nt * WIN)
            + spill_cost_ratio * spilled_at[:max_d]
            + 16.0 * float(nt * WINS * WIN) * (spilled_at[:max_d] > 0)
        )
        depth = int(np.argmin(cost)) + 1
    depth = min(max(depth, 1), depth_cap)
    keep = depth_pos < depth

    # Bin-pack: window w of tile t owns sublanes [base[t,w], base[t,w]+need).
    need = np.minimum(M, depth)             # (nt, WINS)
    base = np.cumsum(need, axis=1) - need   # exclusive per-tile cumsum
    a_t = need.sum(axis=1)
    a = max(SUBPAD, int(-(-a_t.max() // SUBPAD) * SUBPAD))

    # Every slot of a sublane carries the sublane's window id in its high
    # bits (so empty slots still tell the kernel which table to build).
    winid = np.zeros((nt, a), CODE_DTYPE)
    total = int(a_t.sum())
    tile_of = np.repeat(np.arange(nt), a_t)
    pos = np.arange(total) - np.repeat(np.cumsum(a_t) - a_t, a_t)
    winid[tile_of, pos] = np.repeat(
        np.tile(np.arange(WINS, dtype=CODE_DTYPE), nt), need.ravel()
    )
    code = np.empty((nt, a, WIN), CODE_DTYPE)
    # Empty slots: window id in the high FIELD bits + the EMPTY sign bit.
    code[:] = (
        (winid << np.array(WIN_SHIFT, CODE_DTYPE))
        | np.array(EMPTY_MARK, CODE_DTYPE)
    )[:, :, None]
    val = np.zeros((1,) if unit else (nt, a, WIN), np.float32)

    def shaped(v):
        return v if unit else v.reshape(nbr, nbc, a, WIN)

    if lib is not None:
        import ctypes

        vals32 = np.ascontiguousarray(vals, np.float32)
        base32 = np.ascontiguousarray(base, np.int32)
        n_spill_expected = int(len(rows64) - hist[:depth].sum())
        spill_idx = np.empty(max(n_spill_expected, 1), np.int64)

        n_sp = lib.pl_scatter(
            _cptr(rows64, ctypes.c_int64), _cptr(cols64, ctypes.c_int64),
            _cptr(vals32, ctypes.c_float),
            _cptr(order, ctypes.c_int32), _cptr(depth_pos, ctypes.c_int32),
            _cptr(base32, ctypes.c_int32),
            len(rows64), nbc, TILE_R, depth, a, WIN_SHIFT, CODE_BYTES,
            code.ctypes.data_as(ctypes.c_void_p),
            None if unit else _cptr(val, ctypes.c_float),
            _cptr(spill_idx, ctypes.c_int64),
        )
        assert n_sp == n_spill_expected, (n_sp, n_spill_expected)
        spill_idx = spill_idx[:n_sp]
        return code.reshape(nbr, nbc, a, WIN), shaped(val), spill_idx, a, depth

    # Decompose sorted keys with shifts (WIN is always 2^7; WINS is a
    # power of two for power-of-two tile edges), and gather per-entry
    # payloads through ONE index array instead of gather-then-mask — the
    # div/mod + double-gather formulation cost ~18 s at 33M entries.
    if TILE_R & (TILE_R - 1) == 0:
        ohi = (r32 >> 7) & (WINS - 1)
    else:
        ohi = ((r32 % TILE_R) // WIN).astype(np.int32)
    glo = c32 & np.int32(WIN - 1)
    if WINS & (WINS - 1) == 0:
        wshift = WINS.bit_length() - 1
        t_s = cell >> np.array(7 + wshift, cell.dtype)
        g_s = (cell >> np.array(7, cell.dtype)) & np.array(
            WINS - 1, cell.dtype
        )
    else:
        t_s = cell // (WINS * WIN)
        g_s = (cell // WIN) % WINS
    l_s = cell & np.array(WIN - 1, cell.dtype)
    kidx = order[keep]                  # original indices of kept entries
    kt = t_s[keep]
    kl = l_s[keep]
    kg = g_s[keep]
    sub = base[kt, kg] + depth_pos[keep]
    # Filled slots: full positive code (sign bit clear).  The window id of
    # slot (kt, sub) is kg by construction (sub lies in window g's run).
    flat = (kt.astype(np.int64) * a + sub) * WIN + kl
    code.reshape(-1)[flat] = (
        (kg.astype(np.int32) << WIN_SHIFT)
        | (ohi[kidx].astype(np.int32) << 7)
        | glo[kidx]
    ).astype(CODE_DTYPE)
    if not unit:
        val.reshape(-1)[flat] = vals[kidx]

    spill_idx = order[~keep]            # indices into original entry arrays
    return code.reshape(nbr, nbc, a, WIN), shaped(val), spill_idx, a, depth


# ---------------------------------------------------------------------------
# The tile kernel (shared by both directions)
# ---------------------------------------------------------------------------


def _tile_kernel(*refs, square, batch, chunk, unit):
    """A (batch x chunk) rectangle of tiles per grid step.

    Batching many tiles per step keeps DMAs large (MBs, not hundreds of KB)
    so the stream is not bound by per-step overhead (a one-tile step's
    fixed cost exceeds the time its data takes).

    code: (batch, chunk, A, 128) packed (win<<WIN_SHIFT | ohi<<7 | lo);
          empty slots carry EMPTY_MARK's sign bit (win bits preserved)
    val:  (batch, chunk, A, 128) f32 — ABSENT in ``unit`` mode: binary
          matrices (every tiled value 1.0) stream codes only, 3x less
          DMA on a bandwidth-bound kernel; validity is ``code >= 0``
    tab:  (chunk, WINS, 128) gather-side vector windows for this chunk
    out:  (batch, WINS, 128), written at the last step of the chunked
          grid dim
    acc:  (batch, WINS*8, 128) VMEM scratch that lives across the chunked
          grid dim: 8 partial sums per output element

    A tile's work is one dependency chain (codes -> tables -> gather ->
    output sweep), and nothing in it is serial beyond that chain: the
    branch on the vector's finiteness is taken once per grid step, around
    the whole tile loop; the output sweep only ADDS vregs (each output
    window's slots summed over their sublane groups, not across
    sublanes) into ``acc``; and ``_tiles_per_block`` tile bodies share a
    basic block, so that the scheduler overlaps their chains.  The 8 -> 1
    cross-sublane reduce runs once per output block.

    Gather tables are built per tile from each sublane's packed window
    id — a one-hot f32 matmul on the MXU.  A bare matmul would leak a
    non-finite vector entry into every sublane's table via 0·inf = NaN,
    so a grid step whose vector windows carry inf/nan builds its tables
    by masked selects instead (exact; see the in-body comment and
    test_nonfinite_vector_entries_stay_localized).
    """
    from jax.experimental import pallas as pl

    if unit:
        code_ref, tab_ref, out_ref, acc_ref = refs
        val_ref = None
    else:
        code_ref, val_ref, tab_ref, out_ref, acc_ref = refs
    a = code_ref.shape[2]

    @pl.when(pl.program_id(1) == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def tile_body(t, mxu):
        # j-major: per output block b the tiles add up in the order of j.
        j, b = t // batch, t % batch
        tab_j = tab_ref[pl.ds(j, 1), :, :][0]                 # (WINS, 128)
        code = code_ref[b, j].astype(jnp.int32)
        # Field bits through CODE_MASK: empty slots are sign-marked, and
        # int16→int32 sign extension would otherwise corrupt the window
        # id read from a lane-0-empty sublane.
        fields = code & CODE_MASK
        lo = fields & (WIN - 1)
        ohi = (fields >> 7) & ((1 << OBITS) - 1)
        win = fields[:, 0:1] >> WIN_SHIFT                     # (A, 1)

        # Per-sublane tables.  The common all-finite case rides ONE
        # (A,WINS)x(WINS,128) one-hot matmul on the MXU; a vector
        # carrying inf/nan takes WINS masked selects instead (exact: a
        # non-finite entry stays localized to sublanes whose window
        # actually holds it, where a bare one-hot matmul would leak it
        # everywhere via 0*inf=NaN).
        if mxu:
            onehot = (
                win == jax.lax.broadcasted_iota(jnp.int32, (a, WINS), 1)
            ).astype(jnp.float32)
            # HIGHEST: default matmul precision feeds the MXU bf16
            # inputs, and bf16(table) != f32 table — the one-hot
            # product must return window entries exactly (the value
            # path is f32 end-to-end; sole exception: -0.0 gathers
            # as +0.0, numerically inert in the product-sum).
            tables = jax.lax.dot_general(
                onehot, tab_j,
                (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
        else:
            tables = jnp.zeros((a, WIN), jnp.float32)
            for wi in range(WINS):
                tables = jnp.where(win == wi, tab_j[wi:wi + 1, :], tables)
        g = jnp.take_along_axis(tables, lo, axis=1)           # (A, 128)
        if unit:
            # Unit values: v = v² = 1 for every real slot; empty slots
            # (sign bit set) must contribute EXACT zero even when their
            # placeholder gather hits a non-finite vector entry.
            contrib = jnp.where(code >= 0, g, 0.0)
        else:
            v = val_ref[b, j]
            if square:
                contrib = v * v * g
            else:
                contrib = v * g
            # Empty slots (v == 0; zero-valued entries are excluded at
            # build time) must contribute EXACT zero even when their
            # placeholder gather (lo = 0) hits a non-finite vector entry
            # — 0 * inf = NaN would otherwise leak into output window 0
            # of unrelated rows.
            contrib = jnp.where(v != 0.0, contrib, 0.0)

        # Output sweep: window h's slots, summed over the tile's A/8
        # sublane groups only (vreg adds; A is a multiple of SUBPAD).
        acc_ref[b] += jnp.concatenate([
            jnp.sum(jnp.where(ohi == h, contrib, 0.0)
                    .reshape(a // ACC_SUB, ACC_SUB, WIN), axis=0)
            for h in range(WINS)
        ], axis=0)

    # One finiteness reduce per grid step chooses the table build for all
    # its tiles (a step with inf/nan anywhere in its windows is exact and
    # slower, tile after tile).
    finite = jnp.all(jnp.isfinite(tab_ref[...]))
    pl.when(finite)(lambda: _body_loop(
        batch * chunk, _tiles_per_block(a), lambda t: tile_body(t, True)))
    pl.when(jnp.logical_not(finite))(lambda: _body_loop(
        batch * chunk, 1, lambda t: tile_body(t, False)))

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        # One output block at a time: the whole accumulator in one
        # expression is a stack value of its size (20 MiB at batch 139).
        def reduce_block(b, _):
            for h in range(WINS):
                out_ref[b, h:h + 1, :] = jnp.sum(
                    acc_ref[b, h * ACC_SUB:(h + 1) * ACC_SUB, :],
                    axis=0, keepdims=True)
            return 0

        jax.lax.fori_loop(0, batch, reduce_block, 0)


def _body_loop(n: int, per_block: int, body) -> None:
    """``body(t)`` for t in ``range(n)``, in order, ``per_block`` bodies a
    basic block (the remainder in one more), so that the scheduler overlaps
    their dependency chains."""

    def block(first, count):
        # Unrolled by the lowering, so that the body is traced once.  The
        # interpreter runs the same bodies in the same order as a loop: the
        # unrolling is for the chip's scheduler alone, and would cost the
        # interpreter's compiler a copy of the body each.
        jax.lax.fori_loop(0, count, lambda k, _: body(first + k), None,
                          unroll=not _interpret())

    if n >= per_block:
        jax.lax.fori_loop(
            0, n // per_block,
            lambda i, _: block(i * per_block, per_block), None)
    if n % per_block:
        block(n - n % per_block, n % per_block)


def _tiles_per_block(a: int) -> int:
    """Tile bodies the kernel's loop puts into one basic block, from the
    depth: about 1,024 sublanes of work in flight, at most 16 bodies.
    Timed on a TPU v5e at both cells' grids, 1 to 16 a block (my chip
    run, PR 30; PERF.md §6): a forward product over 136.7 k unit tiles
    32 deep 43.7 / 24.5 / 15.0 / 10.7 / 8.9 ms at 1 / 2 / 4 / 8 / 16;
    over 9,432 valued tiles 128 deep 4.35 / 3.18 / 2.65 / 2.37 / 2.32."""
    return max(1, min(16, 1024 // a))


def _pick_rect(nbo: int, nbg: int, a: int,
               unit: bool = False) -> tuple[int, int]:
    """(batch, chunk) tiles per grid step: ~DMA_BUDGET input bytes, and
    everything the step holds in VMEM (input blocks, tables and output
    block double-buffered, the accumulator) within VMEM_BUDGET."""
    # packed code (+ f32 val unless the unit-value layout dropped it)
    per_tile = a * WIN * (CODE_BYTES + (0 if unit else 4))
    cap = max(1, DMA_BUDGET // per_tile)
    window_block = WINS * WIN * 4           # a tile's tables; its output
    per_row = ACC_SUB * window_block + 2 * window_block

    def largest_divisor_leq(n, m):
        d = max(1, min(n, m))
        while n % d:
            d -= 1
        return d

    chunk = largest_divisor_leq(nbg, min(
        cap, (VMEM_BUDGET - per_row) // (2 * per_tile + 2 * window_block)))
    batch = largest_divisor_leq(nbo, min(
        cap // chunk,
        (VMEM_BUDGET - 2 * chunk * window_block)
        // (2 * chunk * per_tile + per_row)))
    return batch, chunk


@functools.partial(
    jax.jit, static_argnames=("nbo", "nbg", "square", "side", "unit"))
def _tiled_apply(code, val, vec_padded, *, nbo, nbg, square, side,
                 unit=False):
    """out[i] = sum over entries (i, j, v) of v * vec[j] (+ optional v²).

    ``code``/``val``: (nbo, nbg, A, 128); ``vec_padded``: (nbg * TILE_C,).
    Returns (nbo * TILE_R,) output.  The packed sublane count A comes from
    the array shape (jit already specializes on it).  ``unit``: the
    binary-matrix layout — ``val`` is ignored (pass the placeholder) and
    only codes stream through the kernel.  ``side`` (``"fwd"``: a product
    with X, ``"bwd"``: with its transpose) only names the kernel: the HLO
    instruction, and so its event in a device trace, is
    ``_tiled_apply_fwd.N`` or ``_tiled_apply_bwd.N``.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    a = code.shape[2]
    batch, chunk = _pick_rect(nbo, nbg, a, unit=unit)
    tab = vec_padded.reshape(nbg, WINS, WIN)
    kernel = functools.partial(_tile_kernel, square=square,
                               batch=batch, chunk=chunk, unit=unit)
    slot_spec = pl.BlockSpec(
        (batch, chunk, a, WIN), lambda i, j: (i, j, 0, 0),
        memory_space=pltpu.VMEM,
    )
    in_specs = [slot_spec]
    operands = [code]
    if not unit:
        in_specs.append(slot_spec)
        operands.append(val)
    in_specs.append(
        pl.BlockSpec((chunk, WINS, WIN), lambda i, j: (j, 0, 0),
                     memory_space=pltpu.VMEM)
    )
    operands.append(tab)
    out = pl.pallas_call(
        kernel,
        grid=(nbo // batch, nbg // chunk),
        out_shape=jax.ShapeDtypeStruct((nbo, WINS, WIN), jnp.float32),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((batch, WINS, WIN), lambda i, j: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((batch, WINS * ACC_SUB, WIN), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name=f"_tiled_apply_{side}",
    )(*operands)
    # out[i, h, l] = output element i*TILE_R + h*128 + l
    return out.reshape(nbo * TILE_R)


# ---------------------------------------------------------------------------
# Public matrix type
# ---------------------------------------------------------------------------


class HostCoo:
    """Host-side canonical COO triples for COLD paths (stats, min/max,
    densify) — one-shot per job, so they run in numpy on the host instead of
    keeping a full device COO copy alive (at 33M nnz that copy is ~400 MB
    of HBM — 12 bytes per entry — for ops the hot loop never touches).

    Lives in a pytree META field, never traced, never transferred.
    Equality/hash use the (n_rows, n_cols, nnz) shape class — NOT content —
    so rebuilding a same-shaped matrix (tuning / down-sampling loops) keeps
    hitting existing jit caches exactly as the all-int metadata did.  Two
    consequences, both documented invariants:

    - cold ops must be called EAGERLY (outside jit), as the drivers do —
      under tracing their results would be baked as constants keyed by the
      shape class, which is wrong across different matrices (the main
      consumer, stats.summarize, passes a row_mask whose np.asarray raises
      on tracers, failing loudly);
    - a jit cache entry for a given shape class keeps that first holder's
      host arrays alive until the compiled function is dropped (bounded by
      distinct shape classes, not by rebuild count).
    """

    __slots__ = ("rows", "cols", "vals", "n_rows", "n_cols")

    def __eq__(self, other):
        return (
            isinstance(other, HostCoo)
            and self.n_rows == other.n_rows
            and self.n_cols == other.n_cols
            and self.nnz == other.nnz
        )

    def __hash__(self):
        return hash((self.n_rows, self.n_cols, self.nnz))

    def __init__(self, rows, cols, vals, n_rows, n_cols):
        self.rows = rows
        self.cols = cols
        self.vals = vals
        self.n_rows = n_rows
        self.n_cols = n_cols

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])

    def _live(self, row_mask):
        live = self.vals != 0
        if row_mask is not None:
            live &= np.asarray(row_mask)[self.rows]
        return live

    def col_nnz(self, row_mask=None):
        live = self._live(row_mask)
        return jnp.asarray(
            np.bincount(
                self.cols[live], minlength=self.n_cols
            ).astype(np.int32)
        )

    def col_min_max(self, row_mask=None):
        """Per-feature (min, max) over stored entries of live rows, folded
        with the implicit zeros of unstored entries — same semantics as
        SparseMatrix.col_min_max."""
        live = self._live(row_mask)
        c = self.cols[live]
        v = self.vals[live]
        mins = np.full(self.n_cols, np.inf, np.float32)
        maxs = np.full(self.n_cols, -np.inf, np.float32)
        np.minimum.at(mins, c, v)
        np.maximum.at(maxs, c, v)
        nnz = np.bincount(c, minlength=self.n_cols)
        n_live_rows = (
            self.n_rows if row_mask is None
            else int(np.sum(np.asarray(row_mask)))
        )
        has_zero = nnz < n_live_rows
        mins = np.where(has_zero, np.minimum(mins, 0.0), mins)
        maxs = np.where(has_zero, np.maximum(maxs, 0.0), maxs)
        return jnp.asarray(mins), jnp.asarray(maxs)

    def to_dense(self):
        dense = np.zeros((self.n_rows, self.n_cols), np.float32)
        np.add.at(dense, (self.rows, self.cols), self.vals)
        return DenseMatrix(jnp.asarray(dense))


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "f_code", "f_val",
        "b_code", "b_val",
        "spill",
        "dense_cols", "dense_col_ids",
        "dense_rows", "dense_row_ids",
        "col_perm_fwd", "col_perm_inv",
    ],
    meta_fields=[
        "host_coo",
        "n_rows", "n_cols", "nbr", "nbc", "a_f", "a_b", "depth_f", "depth_b",
        "has_dense_cols", "has_dense_rows", "has_col_perm", "unit_vals",
    ],
)
@dataclasses.dataclass
class PallasSparseMatrix:
    """Sparse feature matrix backed by the tiled Pallas layout.

    Drop-in for :class:`photon_ml_tpu.ops.sparse.SparseMatrix` in the GLM
    hot loop (matvec / rmatvec / squared variants).  Three complementary
    storage classes, split at build time:

    - **tiled slot grids** — the bulk of the entries, Pallas-kernel fast;
    - **dense stripes** — the popular columns/rows (an explicit bias column,
      the hot tail of a power-law vocabulary) extracted into dense blocks
      that are multiplied in f32 at the HBM rate: they would otherwise
      overload their slot cells and drag the whole layout's depth up
      (how many: :func:`_choose_stripes`);
    - **compact spill** — the residual overflow past the cost-model depth,
      a COO matrix holding ONLY the spilled entries (cost scales with
      spill size, not total nnz).

    Statistics and other cold paths run host-side over ``host_coo`` (the
    canonical triples; a META field — see its docstring for the eager-only
    contract).
    """

    # orientation F (matvec): lane = row%128, tables = w windows
    f_code: Array
    f_val: Array
    # orientation B (rmatvec): lane = col%128, tables = u windows
    b_code: Array
    b_val: Array
    # compact spill matrix (hot-path overflow past the chosen depth)
    spill: "SpillData"
    # ultra-dense stripes (minor dim = the long axis, so XLA's physical
    # tiling pads 8 sublanes, not 128 lanes per stripe; placeholder arrays
    # when absent — see has_* flags)
    dense_cols: Array      # (kc, n_rows) f32 — TRANSPOSED stripe storage
    dense_col_ids: Array   # (kc,) int32 — global column of each stripe
    dense_rows: Array      # (kr, n_cols) f32
    dense_row_ids: Array   # (kr,) int32 — global row of each stripe
    # Column permutation (clustered-data balance; identity when absent —
    # placeholders gated by has_col_perm):
    col_perm_fwd: Array    # (n_cols,) int32 — old col → tiled position
    col_perm_inv: Array    # (nbc*TILE_C,) int32 — tiled position → old col
    #                        (n_cols = "reads the appended zero slot")
    host_coo: HostCoo      # META: host triples for cold paths (never traced)
    n_rows: int
    n_cols: int
    nbr: int
    nbc: int
    a_f: int               # packed sublane count per tile, orientation F
    a_b: int               # packed sublane count per tile, orientation B
    depth_f: int           # per-cell collision cap chosen by the cost model
    depth_b: int
    has_dense_cols: bool
    has_dense_rows: bool
    has_col_perm: bool
    # Binary-matrix fast path: every TILED value is 1.0, so the f32 val
    # arrays are 1-element placeholders and the kernels stream codes only
    # (3x less slot DMA); validity rides the codes' EMPTY sign bit.
    # Dense stripes and the spill keep true values either way.
    unit_vals: bool = False

    # -- shape protocol ----------------------------------------------------
    @property
    def shape(self) -> tuple[int, int]:
        return (self.n_rows, self.n_cols)

    @property
    def nnz(self) -> int:
        return self.host_coo.nnz

    def _pad_cols(self, w: Array) -> Array:
        """Column-side vector in TILED position space: zero-pad, or (with
        a column permutation) a d-sized gather through the inverse map."""
        target = self.nbc * TILE_C
        if not self.has_col_perm:
            return jnp.pad(w, (0, target - self.n_cols))
        wp = jnp.concatenate([w, jnp.zeros((1,), w.dtype)])
        return jnp.take(wp, self.col_perm_inv, axis=0)

    def _uncols(self, out_full: Array) -> Array:
        """Column-space tiled output back to original column order."""
        if not self.has_col_perm:
            return out_full[: self.n_cols]
        return jnp.take(out_full, self.col_perm_fwd, axis=0)

    def _pad_rows(self, u: Array) -> Array:
        target = self.nbr * TILE_R
        return jnp.pad(u, (0, target - self.n_rows))

    # -- hot paths ---------------------------------------------------------
    # The dense stripes' share of each product: f32 products and f32 sums,
    # said to the compiler (HIGHEST), so that a stripe block of any height
    # is multiplied as exactly as the slots are.  On the TPU each is one
    # bandwidth-bound multiply-reduce fusion over the block; the squared
    # forms square inside that fusion (no (stripes, long axis) temporary).
    @staticmethod
    def _stripes_t_dot(coef: Array, stripes: Array) -> Array:
        """Σ_k coef[k] · stripes[k, :]."""
        return jnp.einsum(
            "k,kn->n", coef, stripes, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    @staticmethod
    def _stripes_dot(stripes: Array, vec: Array) -> Array:
        """Σ_n stripes[:, n] · vec[n]."""
        return jnp.einsum(
            "kn,n->k", stripes, vec, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def _apply(self, vec: Array, *, transpose: bool, square: bool) -> Array:
        """X·vec, or Xᵀ·vec; with ``square``, of the element-wise X²."""
        sq = (lambda x: x * x) if square else (lambda x: x)
        if transpose:
            out = self._uncols(_tiled_apply(
                self.b_code, self.b_val, self._pad_rows(vec),
                nbo=self.nbc, nbg=self.nbr, square=square, side="bwd",
                unit=self.unit_vals,
            ))
            spill = self.spill.sq_rmatvec if square else self.spill.rmatvec
            out = out + spill(vec)
            if self.has_dense_cols:
                out = out.at[self.dense_col_ids].add(
                    self._stripes_dot(sq(self.dense_cols), vec))
            if self.has_dense_rows:
                out = out + self._stripes_t_dot(
                    vec[self.dense_row_ids], sq(self.dense_rows))
            return out
        out = _tiled_apply(
            self.f_code, self.f_val, self._pad_cols(vec),
            nbo=self.nbr, nbg=self.nbc, square=square, side="fwd",
            unit=self.unit_vals,
        )[: self.n_rows]
        spill = self.spill.row_sq_matvec if square else self.spill.matvec
        out = out + spill(vec)
        if self.has_dense_cols:
            out = out + self._stripes_t_dot(
                vec[self.dense_col_ids], sq(self.dense_cols))
        if self.has_dense_rows:
            out = out.at[self.dense_row_ids].add(
                self._stripes_dot(sq(self.dense_rows), vec))
        return out

    def matvec(self, w: Array) -> Array:
        return self._apply(w, transpose=False, square=False)

    def rmatvec(self, u: Array) -> Array:
        return self._apply(u, transpose=True, square=False)

    def row_sq_matvec(self, v: Array) -> Array:
        return self._apply(v, transpose=False, square=True)

    def sq_rmatvec(self, u: Array) -> Array:
        return self._apply(u, transpose=True, square=True)

    # -- cold paths: host-side over the canonical triples ------------------
    def col_nnz(self, row_mask=None) -> Array:
        return self.host_coo.col_nnz(row_mask)

    def col_min_max(self, row_mask=None):
        return self.host_coo.col_min_max(row_mask)

    def to_dense(self):
        return self.host_coo.to_dense()


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["spill_coo"],
    meta_fields=["has_spill"],
)
@dataclasses.dataclass
class SpillData:
    """COMPACT spill matrix for hot-path depth overflow.

    ``spill_coo`` holds ONLY the depth-overflow entries, so the XLA
    gather/segment_sum cost of a spill scales with the spilled minority,
    never with the total nnz.  When nothing spilled (the common case) the
    whole XLA branch is skipped at trace time via the static ``has_spill``
    flag (``spill_coo`` is then an empty 1-entry placeholder).
    """

    spill_coo: SparseMatrix  # spilled entries only
    has_spill: bool

    def matvec(self, w):
        if not self.has_spill:
            return jnp.zeros((), jnp.float32)
        return self.spill_coo.matvec(w)

    def rmatvec(self, u):
        if not self.has_spill:
            return jnp.zeros((), jnp.float32)
        return self.spill_coo.rmatvec(u)

    def row_sq_matvec(self, v):
        if not self.has_spill:
            return jnp.zeros((), jnp.float32)
        return self.spill_coo.row_sq_matvec(v)

    def sq_rmatvec(self, u):
        if not self.has_spill:
            return jnp.zeros((), jnp.float32)
        return self.spill_coo.sq_rmatvec(u)


def _spill_data(rows, cols, vals, n_rows, n_cols, dtype) -> SpillData:
    """The compact spill of the entries given, sorted by row; with none,
    the empty 1-entry placeholder and ``has_spill`` False."""
    has_spill = bool(len(rows))
    if not has_spill:
        rows, cols = np.zeros(1, np.int64), np.zeros(1, np.int64)
        vals = np.zeros(1, np.float32)
    s_rows, s_cols, s_vals = canonicalize_coo(rows, cols, vals, n_rows, n_cols)
    return SpillData(
        spill_coo=SparseMatrix(
            row_ids=s_rows, col_ids=s_cols, values=np.asarray(s_vals, dtype),
            n_rows=int(n_rows), n_cols=int(n_cols)),
        has_spill=has_spill)


def _predict_a(rows, cols, nbr, nbc):
    """Packed sublane count (max over tiles of Σ_w max-lane-load, uncapped)
    of orientation F of the given entry set; swap the arguments for
    orientation B.  Counts only PRESENT cells (sort + reduceat) — a dense
    bincount over every possible cell is O(tiles · TILE · 128) host memory
    and OOMs at millions of tiles.  The reference that
    :func:`_band_depths`'s counting pass is held to (exact equality,
    tests/test_sparse_pallas.py), and its path for entries whose rows are
    not in order, a missing native library, or a grid too wide for
    per-band counters."""
    t, w, l = _extract_fields(
        rows.astype(np.int32, copy=False),
        cols.astype(np.int32, copy=False), nbc,
    )
    kdtype = np.int32 if nbr * nbc * WINS * WIN < 2**31 else np.int64
    key = np.sort(
        (t.astype(kdtype) * WINS + w) * WIN + l, kind="stable"
    )
    change = np.empty(len(key), dtype=bool)
    change[0] = True
    np.not_equal(key[1:], key[:-1], out=change[1:])
    starts = np.flatnonzero(change)
    counts = np.diff(np.append(starts, len(key)))
    tw = key[starts] // WIN
    tw_change = np.empty(len(tw), dtype=bool)
    tw_change[0] = True
    np.not_equal(tw[1:], tw[:-1], out=tw_change[1:])
    tw_starts = np.flatnonzero(tw_change)
    m = np.maximum.reduceat(counts, tw_starts)     # max lane load per (t,w)
    a_t = np.bincount(
        tw[tw_starts] // WINS, weights=m, minlength=nbr * nbc
    )
    return int(a_t.max())


def _band_depths(r, c, relabelings, nbr, nbc):
    """(orientation F's, orientation B's) packed depth of the tiled
    entries under each column labeling of ``relabelings`` (a table old
    col -> new col, or None for the identity): :func:`_predict_a`'s
    integers from one counting pass a labeling
    (native/layout_sort.cpp ``pl_band_depths``).  The entries arrive
    sorted by row, so a band of TILE_R rows is one contiguous run that
    owns every cell of both orientations its entries fall in: O(entries),
    ``nbc · TILE_R`` counters an orientation.  None where the library is
    absent or the entry set too small to be worth loading it, and where
    the library refuses: rows out of order, or counters that would
    outweigh the entries (a very wide, very sparse grid)."""
    if len(r) < (1 << 18):
        return None
    from photon_ml_tpu.native import load_layout_sorter

    lib = load_layout_sorter()
    if lib is None:
        return None
    import ctypes

    r64 = np.ascontiguousarray(r, np.int64)
    c64 = np.ascontiguousarray(c, np.int64)
    pairs = []
    for m in relabelings:
        table, n_table = None, 0
        if m is not None:
            m64 = np.ascontiguousarray(m, np.int64)
            table, n_table = _cptr(m64, ctypes.c_int64), len(m64)
        pair = np.zeros(2, np.int64)
        if lib.pl_band_depths(
            _cptr(r64, ctypes.c_int64), _cptr(c64, ctypes.c_int64),
            len(r64), nbr, nbc, TILE_R, table, n_table,
            _cptr(pair, ctypes.c_int64),
        ) != 0:
            return None
        pairs.append((int(pair[0]), int(pair[1])))
    return pairs


def _labeling_depths(r, c, relabelings, nbr, nbc):
    """Per labeling of ``relabelings`` the packed depth summed over both
    orientations, and how it was counted: ``"band_count"``
    (:func:`_band_depths`) where that path is open, else ``"sort"``
    (:func:`_predict_a`, a sort of one key an entry per orientation and
    labeling).  The integers are the same either way."""
    pairs = _band_depths(r, c, relabelings, nbr, nbc)
    if pairs is not None:
        return [f + b for f, b in pairs], "band_count"
    sums = []
    for m in relabelings:
        c_m = c if m is None else m[c]
        sums.append(
            _predict_a(r, c_m, nbr, nbc) + _predict_a(c_m, r, nbc, nbr))
    return sums, "sort"


def _round_robin_positions(n_cols, nbc):
    """Tiled position of the column of popularity rank r (r = 0 the most
    entries): ranks are striped across ALL column windows of all tiles, and
    the within-window offset rotates so orientation B's lanes (col % 128)
    spread too.  A bijection of [0, n_cols) into [0, nbc*TILE_C)."""
    n_win_total = nbc * WINS
    r = np.arange(n_cols, dtype=np.int64)
    w = r % n_win_total            # window round-robin (F-side balance)
    k = r // n_win_total           # round within the window
    # Lane (= new_col % 128, orientation B's lane) must ALSO spread: within
    # one column-tile, round k of window w gets lane (w % WINS) + WINS·σ
    # via a transposed-grid bijection σ of the rounds, so the first WIN hot
    # ranks of every tile land on WIN DISTINCT lanes (a plain (k + w) % WIN
    # rotation made hot ranks from consecutive rounds collide on the same
    # (col-tile, lane), blowing up orientation B's packing).
    if WIN % WINS == 0:
        q = WIN // WINS
        # k = q·a + b → lane = w_in + WINS·b + a (mod WIN): bijective in k
        # for fixed w, and the first q rounds of a tile's WINS windows
        # cover all WIN lanes exactly once.
        lane = (w % WINS + WINS * (k % q) + k // q) % WIN
    else:
        # Non-power-of-two tiles (WINS ∤ WIN): the grid transpose is not a
        # bijection, so fall back to the trivially bijective per-window
        # round order (weaker B-lane spreading, never wrong).
        lane = k
    new = w * WIN + lane
    assert len(np.unique(new)) == n_cols, "column relabeling not bijective"
    return new


def _balance_col_perm(cols, n_cols, nbc):
    """Frequency round-robin column relabeling: rank columns by entry count
    (descending) and place rank r at :func:`_round_robin_positions`.
    Returns ``m`` (old col → new col, len n_cols), a bijection into
    [0, nbc*TILE_C).

    Clustered real-world data (ids sorted by popularity, feature shards
    grouped by type) concentrates hot columns in a few windows; each
    window pays its own worst lane in the packed layout, so spreading the
    mass is a direct A reduction.  Uniform data is unaffected — the
    builder compares predicted A and keeps the identity when it wins.
    """
    counts = np.bincount(cols, minlength=n_cols)
    ranks = np.argsort(-counts, kind="stable")
    m = np.empty(n_cols, np.int64)
    m[ranks] = _round_robin_positions(n_cols, nbc)
    return m


# What one product pays on a TPU v5e for the two storage classes the stripe
# chooser trades against each other.  From one sweep on the chip (my chip
# run, PR 26; PERF.md §6): 804,414 x 47,237, 61.9 M entries, the top K
# columns forced into stripes, K = 64 ... 1024.  Kernel time is affine in the
# depth: a forward product 5.35 ms + 0.0411 ms a sublane of a_f, a backward
# 4.1 ms + 0.0436 ms a sublane of a_b, at 9,432 tiles x 128 slots a sublane;
# the stripes' fusions run at 748 GB/s.  Only the ratio moves the choice.
#: device seconds one more slot costs a product (mean of the two slopes)
SLOT_SECONDS = 35e-12
#: device seconds an f32 stripe element costs a product
STRIPE_ELEMENT_SECONDS = 5.4e-12
# Tiles of the striped axis the chooser evaluates (the hottest by worst
# lane and by mass); the others are no worse.
_EVAL_TILES = 32


def _threshold_stripes(sorted_counts, long_axis):
    """How many stripes the rule before PR 26 took: every index in at least
    1/32 of the long axis (and 256 entries), at most 64, within 512 MiB of
    dense storage.  The chooser's candidate of last resort and the
    yardstick of its memory guard (both sides scale with the long axis, so
    a 10⁸-row input stays as safe as it was)."""
    above = int(np.searchsorted(
        -sorted_counts, -max(256, long_axis // 32), side="right"))
    return min(above, 64, (512 << 20) // max(long_axis * 4, 1))


def _capped_max_pmf(mu, copies, cap):
    """pmf over 0..cap of ``min(cap, max)`` of independent Poisson loads:
    ``mu`` (G, L) the means of L lanes, each standing for ``copies``
    lanes alike.  Returns (G, cap + 1)."""
    m = np.arange(cap)
    log_fact = np.concatenate([[0.0], np.cumsum(np.log(np.arange(1, cap)))])
    mu = np.maximum(mu, 1e-300)[..., None]
    cdf = np.cumsum(np.exp(m * np.log(mu) - mu - log_fact), axis=-1)
    log_f = copies * np.log(np.clip(cdf, 1e-300, 1.0)).sum(axis=1)
    below = np.concatenate(
        [np.zeros((len(log_f), 1)), np.exp(log_f),
         np.ones((len(log_f), 1))], axis=1)
    return np.diff(below, axis=1)


def _expected_depth(pmfs, copies, replicas):
    """Expected packed sublane count: each of G tiles sums its W window
    loads (``pmfs`` (G, W, cap + 1), independent), ``copies`` times over;
    each tile stands for ``replicas`` tiles alike; the grid's depth is the
    largest sum."""
    # support that carries any mass (a load is far under the cap as a rule)
    c = int(np.flatnonzero(pmfs.max(axis=(0, 1)) > 1e-15)[-1]) + 1
    n = pmfs.shape[1] * copies * (c - 1) + 1
    nfft = 1 << (n - 1).bit_length()
    total = np.fft.irfft(
        np.fft.rfft(pmfs[:, :, :c], n=nfft, axis=-1).prod(axis=1) ** copies,
        n=nfft)[:, :n]
    cdf = np.clip(np.cumsum(np.maximum(total, 0.0), axis=1), 1e-300, 1.0)
    return float(np.sum(1.0 - np.exp(replicas * np.log(cdf).sum(axis=0))))


def _hottest_tiles(mu):
    """Indices of the tiles of ``mu`` (tiles, ...) worth evaluating."""
    if len(mu) <= _EVAL_TILES:
        return np.arange(len(mu))
    flat = mu.reshape(len(mu), -1)
    half = _EVAL_TILES // 2
    return np.union1d(np.argsort(-flat.max(axis=1))[:half],
                      np.argsort(-flat.sum(axis=1))[:half])


def _predict_depths(density, other_len, cap):
    """(window side, lane side) packed sublane counts predicted from the
    histogram alone, for entries whose index along the striped axis has
    ``density`` (tiled position space, a multiple of the tile edge: the
    share of the other axis's ``other_len`` indices each position meets)
    and whose other index is uniform.  A cell's load is then Poisson.

    Window side (columns: orientation F): a cell is one lane of the other
    axis (``per_lane`` indices) under one window of this one.  Lane side
    (columns: orientation B): a cell is ``win_len`` indices of the other
    axis under the ≤ WINS positions of this axis that share a lane."""
    per_tile = min(other_len, TILE_R)
    replicas = -(-other_len // TILE_R)
    lanes = min(WIN, other_len)
    grid = density.reshape(-1, WINS, WIN)

    mu_w = grid.sum(axis=2) * -(-per_tile // lanes)          # (tiles, WINS)
    mu_w = mu_w[_hottest_tiles(mu_w)]
    a_window = _expected_depth(
        _capped_max_pmf(mu_w.reshape(-1, 1), lanes, cap).reshape(
            len(mu_w), WINS, cap + 1),
        1, replicas)

    mu_l = grid.sum(axis=1) * lanes                          # (tiles, WIN)
    mu_l = mu_l[_hottest_tiles(mu_l)]
    a_lane = _expected_depth(
        _capped_max_pmf(mu_l, 1, cap)[:, None, :], -(-per_tile // WIN),
        replicas)
    return a_window, a_lane


def _pad_depth(a):
    return max(SUBPAD, int(-(-round(a) // SUBPAD) * SUBPAD))


def _choose_stripes(counts, long_axis, n_tiles, slot_bytes, cap,
                    max_stripes, permute):
    """Which indices of one axis become dense stripes: the prefix of the
    indices by descending entry count that minimises the predicted device
    time of one forward plus one backward product,

        tiles · 128 · (a_f + a_b) · SLOT_SECONDS
            + 2 · K · long_axis · STRIPE_ELEMENT_SECONDS,

    among prefixes whose layout (slots at ``slot_bytes`` + stripes) is no
    larger than the one :func:`_threshold_stripes` would have given.  The
    depths come from the histogram (:func:`_predict_depths`), under the
    identity placement and, with ``permute``, under the round-robin one
    the column permutation would give: no sort of the entry set.  Ties go
    to the threshold rule's count, then to the fewest stripes: an axis
    without a hot tail keeps the stripes it had.

    Returns (sorted stripe ids, (window-side, lane-side) predicted padded
    depths at the choice)."""
    n = len(counts)
    order = np.argsort(-counts, kind="stable")
    sorted_counts = counts[order]
    dens = sorted_counts / float(max(long_axis, 1))
    n_pos = -(-n // TILE_R) * TILE_R
    k_top = int(np.count_nonzero(sorted_counts))
    if max_stripes is not None:
        k_top = min(k_top, int(max_stripes))
    k_old = min(_threshold_stripes(sorted_counts, long_axis), k_top)
    # prefix sizes: every count up to 8, then a half-octave ladder in
    # multiples of 8 (the stripes' sublane tiling); the cost is flat around
    # its minimum (PERF.md §6, PR 26: within 2% from 290 to 512 stripes)
    ladder = 8 * np.unique(np.round(2.0 ** np.arange(0, 20, 0.5)))
    cands = np.unique(np.concatenate(
        [np.arange(9), ladder, [k_old]]).astype(np.int64))
    cands = cands[cands <= k_top].tolist()
    positions = _round_robin_positions(n, n_pos // TILE_R) if permute else None

    def depths(k):
        """Padded (window-side, lane-side) depths without the top k, under
        the placement that needs the fewer sublanes."""
        pairs = []
        for place in ([order[k:]] if positions is None
                      else [order[k:], positions[:n - k]]):
            density = np.zeros(n_pos)
            density[place] = dens[k:]
            a_w, a_l = _predict_depths(density, long_axis, cap)
            pairs.append((_pad_depth(a_w), _pad_depth(a_l)))
        return min(pairs, key=sum)

    def seconds_bytes(k, pair):
        slots = n_tiles * WIN * sum(pair)
        return (slots * SLOT_SECONDS
                + 2 * k * long_axis * STRIPE_ELEMENT_SECONDS,
                slots * slot_bytes + 4 * k * long_axis)

    chosen = k_old
    chosen_pair = depths(k_old)
    best_s, limit_bytes = seconds_bytes(k_old, chosen_pair)
    for k in cands:
        if 2 * k * long_axis * STRIPE_ELEMENT_SECONDS >= best_s:
            break               # the stripes alone cost more than the best
        if k == k_old:
            continue
        pair = depths(k)
        s, nbytes = seconds_bytes(k, pair)
        if nbytes <= limit_bytes and s < best_s:
            chosen, chosen_pair, best_s = k, pair, s
    return np.sort(order[:chosen]).astype(np.int64), chosen_pair


def build_pallas_host(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    n_cols: int,
    depth_cap: int = 128,
    pad_nnz: Optional[int] = None,
    dtype=jnp.float32,
    max_dense: Optional[int] = None,
    col_permutation: bool = True,
    unit_values: bool | str = "auto",
) -> PallasSparseMatrix:
    """Build the tiled layout from host COO triples, on the host: every
    leaf of the result is a numpy array (:func:`place_pallas_matrix` puts
    them on the device).  One ``layout.build`` layer span, with a child
    per phase (docs/telemetry.md "Layer spans").

    Storage-class split (see :class:`PallasSparseMatrix`):

    1. the most popular columns (then rows, from what remains) become
       dense stripes, f32 products at the HBM rate: a hot column would
       otherwise put its 128·density entries into one lane of every
       row-window of its tiles, and orientation B pays ~16× that in
       depth, in every tile.  How many is :func:`_choose_stripes`'s
       business: the prefix by popularity that minimises the predicted
       time of a forward plus a backward product, within the bytes the
       threshold rule it replaced would have used (``glm_lbfgs_fit``:
       PERF.md §6, PR 26).  ``max_dense`` caps the count per side; tests
       pass 0 to build without stripes;
    2. the rest lands in the tiled slot grids, at the cost-model depth
       (see ``_build_orientation``; ≤ ``depth_cap``);
    3. the residual overflow becomes a COMPACT spill COO (cost ∝ spill).
    """
    with layer_span("layout.build") as build:
        return _build_tiled(
            [rows, cols, vals], n_rows, n_cols, depth_cap, pad_nnz, dtype,
            max_dense, col_permutation, unit_values, build)


def _build_tiled(triples, n_rows, n_cols, depth_cap, pad_nnz, dtype,
                 max_dense, col_permutation, unit_values, build,
                 keep_coo=True):
    """:func:`build_pallas_host`'s body, its phases children of the open
    ``layout.build`` span ``build`` (the wide layout's warm band is built
    here too, inside the wide build's one span).  ``triples`` is a list
    (rows, cols, vals), emptied here: without ``keep_coo`` (the warm band,
    whose entries the wide matrix's own host triples hold) nothing holds
    them once the stripes are split off -- at 10^8 entries, gigabytes of
    the build's peak."""
    # Canonicalize ON HOST (dedup + sort + nnz-budget pad/validation) —
    # the old path built a full device COO first and read it straight
    # back, paying two transfers of the entire entry set for nothing.
    # Padding entries carry value 0, so the tiled build excludes them via
    # the live filter below; P.nnz still reports the padded budget.
    with layer_span("layout.canonicalize"):
        r_all, c_all, v_all = canonicalize_coo(
            *triples, n_rows, n_cols, pad_nnz
        )
        triples.clear()
        host_coo = (HostCoo(r_all, c_all, v_all, int(n_rows), int(n_cols))
                    if keep_coo else DroppedHostCoo(n_rows, n_cols))
        # Zero-valued entries contribute nothing; excluding them keeps
        # explicit zeros from faking a dense cell.
        if np.all(v_all != 0):
            r, c, v = r_all, c_all, v_all
        else:
            live = np.flatnonzero(v_all != 0)
            r, c, v = r_all[live], c_all[live], v_all[live]
        del r_all, c_all, v_all

    nbr = max(1, -(-n_rows // TILE_R))
    nbc = max(1, -(-n_cols // TILE_C))

    # --- dense stripe extraction (columns first, rows from the rest) --
    with layer_span("layout.dense_split"):
        n_valued = len(v)
        # the slot bytes the memory guard counts: the unit-value layout
        # streams codes alone
        slot_bytes = CODE_BYTES + (
            0 if unit_values is True or (
                unit_values == "auto" and bool(np.all(v == 1.0)))
            else 4)

        def split(idx, other, vals_, n_idx, long_axis, permute):
            """Stripe ids of one axis, their dense block (stripe,
            long axis), which entries stay tiled, predicted depths."""
            ids, predicted = _choose_stripes(
                np.bincount(idx, minlength=n_idx), long_axis, nbr * nbc,
                slot_bytes, depth_cap, max_dense, permute)
            # Zero-SIZE block when absent (never read; has_dense_*
            # gates).
            block = np.zeros((len(ids), long_axis), np.float32)
            if not ids.size:
                return ids, block, np.ones(len(idx), bool), predicted
            # stripe of each index, -1 for the tiled ones: one table
            # lookup per entry (np.isin + np.searchsorted were a sort
            # and a search over every entry)
            stripe_of = np.full(n_idx, -1, np.int32)
            stripe_of[ids] = np.arange(len(ids), dtype=np.int32)
            stay = np.empty(len(idx), bool)

            def fill(lo):
                # Each entry has a (stripe, position) of its own, so
                # chunks of the entry list write disjoint cells.
                hi = min(len(idx), lo + _SPLIT_CHUNK)
                stripe = stripe_of[idx[lo:hi]]
                inside = stripe >= 0
                block[stripe[inside], other[lo:hi][inside]] = (
                    vals_[lo:hi][inside])
                np.logical_not(inside, out=stay[lo:hi])

            _in_threads(fill, range(0, len(idx), _SPLIT_CHUNK))
            return ids, block, stay, predicted

        def tiled(stay):
            return _in_threads(lambda a: a[stay], (r, c, v))

        dense_col_ids, dense_cols, stay, (a_f_pred, a_b_pred) = split(
            c, r, v, n_cols, n_rows, col_permutation and n_cols > WIN)
        if dense_col_ids.size:
            r, c, v = tiled(stay)
        dense_row_ids, dense_rows, stay, _ = split(
            r, c, v, n_rows, n_cols, False)
        if dense_row_ids.size:
            r, c, v = tiled(stay)
        stripe_nnz = n_valued - len(v)

    # --- optional column permutation (clustered-data balance) ---------
    # Relabel columns frequency-round-robin across windows when that
    # predicts fewer packed sublanes (summed over both orientations).
    # Spill/dense/cold paths keep ORIGINAL column ids; only the tiled
    # layouts see permuted ones, at the cost of one d-sized gather of the
    # input vector (matvec side) / output vector (rmatvec side).
    col_perm = None
    c_tiled = c
    if col_permutation and r.size and n_cols > WIN:
        with layer_span("layout.col_perm") as perm_span:
            m = _balance_col_perm(c, n_cols, nbc)
            (a_id, a_pm), method = _labeling_depths(
                r, c, (None, m), nbr, nbc)
            # Engage only when the predicted slot-BYTE saving clearly
            # exceeds the gather traffic the permutation adds (a d-sized
            # take of w per matvec + an unpermute take per rmatvec).  The
            # 8x margin covers jnp.take's per-byte inefficiency vs pure
            # streaming for moderate-sized gathers; marginal predicted
            # wins stay identity.
            saving_bytes = (
                (a_id - a_pm) * (nbr * nbc) * WIN * (CODE_BYTES + 4))
            gather_bytes = 2 * (nbc * TILE_C) * 4
            engaged = a_pm < a_id and saving_bytes >= 8 * gather_bytes
            if engaged:
                col_perm = m
                c_tiled = m[c]
            perm_span.set(method=method, a_identity=a_id,
                          a_permuted=a_pm, engaged=engaged)

    # Known before the orientations are built when every tiled value is 1
    # (a spill only takes entries away): their value grids are then never
    # made.
    unit_known = unit_values is True or (
        unit_values == "auto" and bool(np.all(v == 1.0)))

    def orient(side, rows_, cols_, vals_, **kw):
        with layer_span("layout.orient", side=side):
            if side == "f":
                return _build_orientation(
                    rows_, cols_, vals_, nbr, nbc, depth_cap,
                    unit=unit_known, **kw)
            return _build_orientation(
                cols_, rows_, vals_, nbc, nbr, depth_cap, unit=unit_known,
                **kw)

    f_code, f_val, f_spill, a_f, depth_f = orient("f", r, c_tiled, v)
    b_code, b_val, b_spill, a_b, depth_b = orient("b", r, c_tiled, v)

    # Entries spilled from EITHER orientation go through the COO path for
    # BOTH directions (keeps matvec and rmatvec consistent with one X).
    spilled = np.union1d(f_spill, b_spill)
    spill = _spill_data(r[spilled], c[spilled], v[spilled], n_rows, n_cols,
                        dtype)
    if spilled.size:
        # Rebuild both orientations without the spilled entries so neither
        # tiled layout double-counts them (host-side, one extra pass).
        keep = np.ones(r.shape[0], bool)
        keep[spilled] = False
        f_code, f_val, fs2, a_f, depth_f = orient(
            "f", r[keep], c_tiled[keep], v[keep], spill_cost_ratio=np.inf)
        b_code, b_val, bs2, a_b, depth_b = orient(
            "b", r[keep], c_tiled[keep], v[keep], spill_cost_ratio=np.inf)
        assert fs2.size == 0 and bs2.size == 0, "re-spill after rebuild"

    if col_perm is not None:
        inv = np.full(nbc * TILE_C, n_cols, np.int64)  # default: zero slot
        inv[col_perm] = np.arange(n_cols)
        perm_fwd = col_perm.astype(np.int32)
        perm_inv = inv.astype(np.int32)
    else:
        perm_fwd = np.zeros((1,), np.int32)
        perm_inv = np.zeros((1,), np.int32)

    # Binary-matrix fast path: when every TILED value is 1.0 (dense
    # stripes and spill keep their true values), drop the f32 val
    # stream — the kernels then move 2 bytes/slot instead of 6 ("auto";
    # False forces the valued layout, e.g. for A/B measurement).
    tiled_vals = v[keep] if spilled.size else v
    unit = (
        unit_values == "auto"
        and (tiled_vals.size == 0 or bool(np.all(tiled_vals == 1.0)))
    ) or unit_values is True
    if unit_values is True and tiled_vals.size and not np.all(
        tiled_vals == 1.0
    ):
        raise ValueError(
            "unit_values=True but tiled values are not all 1.0")
    if unit:
        f_val = np.zeros((1,), np.float32)
        b_val = np.zeros((1,), np.float32)

    P = PallasSparseMatrix(
        f_code=f_code, f_val=f_val, b_code=b_code, b_val=b_val,
        spill=spill,
        dense_cols=dense_cols,
        dense_col_ids=dense_col_ids.astype(np.int32),
        dense_rows=dense_rows,
        dense_row_ids=dense_row_ids.astype(np.int32),
        col_perm_fwd=perm_fwd, col_perm_inv=perm_inv,
        host_coo=host_coo,
        n_rows=int(n_rows), n_cols=int(n_cols),
        nbr=nbr, nbc=nbc, a_f=a_f, a_b=a_b,
        depth_f=depth_f, depth_b=depth_b,
        has_dense_cols=bool(dense_col_ids.size),
        has_dense_rows=bool(dense_row_ids.size),
        has_col_perm=col_perm is not None,
        unit_vals=unit,
    )
    build.set(
        nnz=P.nnz, a_f=a_f, a_b=a_b,
        a_f_predicted=a_f_pred, a_b_predicted=a_b_pred,
        stripes=len(dense_col_ids) + len(dense_row_ids),
        stripe_nnz_share=stripe_nnz / max(n_valued, 1),
        stripe_bytes=int(dense_cols.nbytes + dense_rows.nbytes),
        has_col_perm=P.has_col_perm, spilled=int(spilled.size),
    )
    return P


def place_pallas_matrix(P: PallasSparseMatrix) -> PallasSparseMatrix:
    """Put every leaf of a host-built layout on the default device."""
    return place_leaves(P)[0]


def build_pallas_matrix(*args, **kwargs) -> PallasSparseMatrix:
    """:func:`build_pallas_host`, placed (same arguments)."""
    return place_pallas_matrix(build_pallas_host(*args, **kwargs))


def host_layout_from_scipy_csr(csr, depth_cap: int = 128,
                               pad_nnz: Optional[int] = None,
                               dtype=jnp.float32, wide: bool = False):
    """:func:`build_pallas_host` (with ``wide``, :func:`build_wide_host`)
    of a scipy CSR matrix.  The way there (duplicates summed, one row
    index an entry) is a ``layout.to_coo`` layer span of its own: seconds
    at 0.5 G entries, before ``layout.build`` opens."""
    with layer_span("layout.to_coo", nnz=int(csr.nnz)):
        csr = csr.tocsr()
        csr.sum_duplicates()
        coo = csr.tocoo()
    build = build_wide_host if wide else build_pallas_host
    return build(
        coo.row, coo.col, coo.data,
        csr.shape[0], csr.shape[1], depth_cap=depth_cap, pad_nnz=pad_nnz,
        dtype=dtype)


def from_scipy_csr_pallas(csr, depth_cap: int = 128, pad_nnz: Optional[int] = None,
                          dtype=jnp.float32) -> PallasSparseMatrix:
    return place_pallas_matrix(
        host_layout_from_scipy_csr(csr, depth_cap, pad_nnz, dtype))


# ---------------------------------------------------------------------------
# The wide layout: a warm band of tiles and a cold band of mixed blocks
# ---------------------------------------------------------------------------

#: Below this predicted fill of the tile grid at its least depth
#: (:func:`grid_fill_bound`) ``make_glm_data(use_pallas="auto")`` builds
#: the wide layout, and the wide layout's warm band is the longest column
#: prefix (by entry count) whose own grid stays at or above it.
WIDE_FILL = 1.0
#: Rows and columns of one cold block: 64 output windows by 64 gather
#: windows, so that a block meets enough of a hashed tail's entries.
COLD_TILE = 8192
COLD_WINS = COLD_TILE // WIN
COLD_OBITS = (COLD_WINS - 1).bit_length()
COLD_WIN_SHIFT = 7 + COLD_OBITS
#: int32 codes tile as (8, 128): the cold depth's granule.
COLD_SUBPAD = 8
#: Device seconds a cold block costs a product: a constant and a slope a
#: sublane of depth, the line through two depths of the cold kernel as
#: ``_cold_bodies`` runs it.  From ``scripts/cold_kernel_sweep.py`` on one
#: TPU v5e (PERF.md section 6): 1,024 x 123 blocks of synthetic codes took
#: 19.05 ms a product 8 deep (16 blocks a basic block) and 60.22 ms 16 deep
#: (8), where one block a loop trip, the 64-window pick and sweep in loops
#: of 8, took 112.6 and 153.0 ms.  The line's constant is below zero: a
#: block 8 deep, one vreg of codes, costs less than the slope says (the
#: band is never shallower: a pair 8 and 8 deep is 302 ns a block); deeper
#: the line is within 12% (24 deep 722.6 ns a block, the line 805; 32 deep
#: 1,214.0, the line 1,132).
COLD_BLOCK_SECONDS = -175.6e-9
COLD_SUBLANE_SECONDS = 40.86e-9
#: Device seconds of the cold band's spill (:func:`_cold_cheapest`) a
#: product: a constant for a spill that is not empty and a slope an entry.
#: Fitted on a TPU v5e (``scripts/cold_kernel_sweep.py``; PERF.md §6) as
#: the mean of two least-squares lines through the device's busy seconds
#: of ``SparseMatrix.matvec`` and ``rmatvec``, each added into the band's
#: output, on the spills of ``glm_click_fit``'s own log (2^23 rows by
#: 1,000,001 columns) at depths (8, 8), (16, 8), (8, 16) and (16, 16):
#: 246,185 / 222,192 / 24,633 / 133 entries, matvec 3.967 / 3.708 / 0.567
#: / 0.203 ms, rmatvec 3.293 / 3.085 / 0.353 / 0.027 ms (the lines 196 us
#: + 15.5 ns an entry and 26 us + 13.5 ns).  At the click log's counts the
#: depths are (8, 8) while the slope stays under ~90 ns.
COLD_SPILL_FIXED_SECONDS = 111e-6
COLD_SPILL_SECONDS = 14.5e-9
COLD_EMPTY = np.iinfo(np.int32).min


def grid_fill_bound(nnz: int, n_rows: int, n_cols: int) -> float:
    """Entries over the slots the tile grid holds at its least depth
    (every tile of the ``⌈n/2048⌉ × ⌈d/2048⌉`` grid, ``SUBPAD`` sublanes
    of 128 slots, one orientation): an upper bound of its slot fill."""
    nbr = max(1, -(-n_rows // TILE_R))
    nbc = max(1, -(-n_cols // TILE_C))
    return nnz / float(nbr * nbc * SUBPAD * WIN)


def _cold_kernel(*refs, square, batch, chunk, unit, bodies):
    """``_tile_kernel`` for blocks that hold a few entries of many windows
    (a hashed vocabulary's tail): every SLOT names its own gather window,
    where the tile kernel gives a sublane one.

    code: (batch, chunk, A, 128) int32 ``win << COLD_WIN_SHIFT | ohi << 7
          | lo`` -- the slot's gather window within the block, its output
          window, its lane in the gather window; empty slots are negative
    val:  (batch, chunk, A, 128) f32, absent in ``unit`` mode
    tab:  (chunk, COLD_WINS, 128) the gather side's windows
    out:  (batch, COLD_WINS, 128); acc: (batch, COLD_WINS*8, 128)

    A slot's value is picked from the block's windows by one lane gather
    and one select a window (exact: a non-finite vector entry reaches only
    the slots that read it); the output sweep is the tile kernel's.  A
    block's pick and sweep are unrolled over all 64 windows, and
    ``_cold_bodies`` blocks share a basic block (:func:`_body_loop`), so
    that the scheduler overlaps their chains: the kernel waited on latency,
    not on the vector unit.  Each output block still adds its blocks in the
    order of j, so the products are the same to the bit whatever the
    number.  A pick as a tree over the window id's six bits (63 selects,
    six deep) timed the same to 2% and slower at 24 deep and more
    (PERF.md §6).
    """
    from jax.experimental import pallas as pl

    if unit:
        code_ref, tab_ref, out_ref, acc_ref = refs
        val_ref = None
    else:
        code_ref, val_ref, tab_ref, out_ref, acc_ref = refs
    a = code_ref.shape[2]

    @pl.when(pl.program_id(1) == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def block_body(t):
        # j-major: per output block b the blocks add up in the order of j.
        j, b = t // batch, t % batch
        code = code_ref[b, j]
        lo = code & (WIN - 1)
        ohi = (code >> 7) & (COLD_WINS - 1)
        win = (code >> COLD_WIN_SHIFT) & (COLD_WINS - 1)
        g = jnp.zeros((a, WIN), jnp.float32)
        for first in range(0, COLD_WINS, ACC_SUB):
            rows = tab_ref[j, first:first + ACC_SUB, :]
            for k in range(ACC_SUB):
                row = jnp.broadcast_to(rows[k:k + 1, :], (a, WIN))
                g = jnp.where(win == first + k, jnp.take_along_axis(
                    row, lo, axis=1, mode="promise_in_bounds"), g)
        if unit:
            contrib = jnp.where(code >= 0, g, 0.0)
        else:
            v = val_ref[b, j]
            contrib = v * v * g if square else v * g
            contrib = jnp.where(v != 0.0, contrib, 0.0)
        for h in range(COLD_WINS):
            acc_ref[b, h * ACC_SUB:(h + 1) * ACC_SUB, :] += jnp.sum(
                jnp.where(ohi == h, contrib, 0.0)
                .reshape(a // ACC_SUB, ACC_SUB, WIN), axis=0)

    _body_loop(batch * chunk, bodies, block_body)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        def reduce_block(b, _):
            for h in range(COLD_WINS):
                out_ref[b, h:h + 1, :] = jnp.sum(
                    acc_ref[b, h * ACC_SUB:(h + 1) * ACC_SUB, :],
                    axis=0, keepdims=True)
            return 0

        jax.lax.fori_loop(0, batch, reduce_block, 0)


def _divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def _pick_cold_rect(nbo: int, nbg: int, a: int,
                    unit: bool) -> tuple[int, int]:
    """(batch, chunk) blocks a grid step: the most within DMA_BUDGET input
    bytes and VMEM_BUDGET of everything the step holds (inputs, tables and
    output double-buffered, the accumulator)."""
    per_block = a * WIN * (4 + (0 if unit else 4))
    window_block = COLD_WINS * WIN * 4
    best = (1, 1)
    for chunk in _divisors(nbg):
        for batch in _divisors(nbo):
            dma = batch * chunk * per_block
            vmem = (2 * dma + 2 * chunk * window_block
                    + batch * (2 + ACC_SUB) * window_block)
            if (dma <= DMA_BUDGET and vmem <= VMEM_BUDGET
                    and batch * chunk > best[0] * best[1]):
                best = (batch, chunk)
    return best


def _cold_bodies(a: int) -> int:
    """Cold blocks the kernel's loop puts into one basic block, from the
    depth: the most, in powers of two up to 8, that keep 128 sublanes or
    fewer in flight.  Timed on a TPU v5e (``scripts/cold_kernel_sweep.py``;
    PERF.md §6), a product over 1,024 x 123 blocks of synthetic codes at 1
    / 2 / 4 / 8 / 16 blocks a basic block: 8 deep 32.23 / 25.11 / 21.56 /
    19.87 / 19.05 ms; 16 deep 70.70 / 64.89 / 61.72 / 60.22 / 59.44; 24
    deep 101.39 / 92.74 / 91.02 / 89.44 / 89.30; 32 deep 162.15 / 158.17 /
    152.90 / 151.55 / 151.58; over 123 x 1,024 blocks 24 deep 100.91 /
    89.99 / 89.17 / 88.22 / 88.23; and, one sweep earlier, 40 deep 191.88 /
    179.53 / 177.42 / 176.44 / 175.78 and 64 deep 320.30 / 305.67 / 301.16
    / 298.61 / 297.69; and 8 deep at 1 / 8 / 16 again, 32.18 / 19.92 /
    19.09 over 1,024 x 123 and 32.93 / 19.18 / 18.17 over 123 x 1,024.  The
    rule is within 5.6% of the fastest at every depth; every body more is
    one more copy of the 64-window body for each compile of a program to
    lower (at 16 everywhere the click solve's program took 19.4 s to trace
    and lower, 16 deep forward and 24 backward at 8 and 4 bodies 7.8 s, 8
    deep both ways at 8 bodies 8.8 s): 16 bodies 8 deep would be 4-6%
    faster than 8 and as many copies as 16 everywhere."""
    k = 1
    while k < 8 and 2 * k * a <= 128:
        k *= 2
    return k


def _cold_plan(nbo: int, nbg: int, a: int,
               unit: bool) -> tuple[int, int, int]:
    """(batch, chunk, bodies): the grid step's blocks and the blocks a basic
    block that the cold kernel traces with (no more than the step holds)."""
    batch, chunk = _pick_cold_rect(nbo, nbg, a, unit)
    return batch, chunk, min(_cold_bodies(a), batch * chunk)


@functools.partial(
    jax.jit, static_argnames=("nbo", "nbg", "square", "side", "unit"))
def _cold_apply(code, val, vec_padded, *, nbo, nbg, square, side,
                unit=False):
    """The cold band's product: ``code``/``val`` (nbo, nbg, A, 128),
    ``vec_padded`` (nbg * COLD_TILE,) -> (nbo * COLD_TILE,).  ``side``
    names the kernel: ``_cold_apply_fwd.N`` / ``_cold_apply_bwd.N`` in a
    device trace."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    a = code.shape[2]
    batch, chunk, bodies = _cold_plan(nbo, nbg, a, unit)
    tab = vec_padded.reshape(nbg, COLD_WINS, WIN)
    kernel = functools.partial(_cold_kernel, square=square, batch=batch,
                               chunk=chunk, unit=unit, bodies=bodies)
    slot_spec = pl.BlockSpec((batch, chunk, a, WIN),
                             lambda i, j: (i, j, 0, 0),
                             memory_space=pltpu.VMEM)
    in_specs, operands = [slot_spec], [code]
    if not unit:
        in_specs.append(slot_spec)
        operands.append(val)
    in_specs.append(pl.BlockSpec((chunk, COLD_WINS, WIN),
                                 lambda i, j: (j, 0, 0),
                                 memory_space=pltpu.VMEM))
    operands.append(tab)
    out = pl.pallas_call(
        kernel,
        grid=(nbo // batch, nbg // chunk),
        out_shape=jax.ShapeDtypeStruct((nbo, COLD_WINS, WIN), jnp.float32),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((batch, COLD_WINS, WIN),
                               lambda i, j: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((batch, COLD_WINS * ACC_SUB, WIN), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name=f"_cold_apply_{side}",
    )(*operands)
    return out.reshape(nbo * COLD_TILE)


def _cold_key(o, g, nbg):
    """An entry's (block, lane) in a cold orientation: ``block * 128 +
    lane``, from int64 output and gather indices."""
    shift = COLD_TILE.bit_length() - 1
    return ((o >> shift) * nbg + (g >> shift)) * WIN + (o & (WIN - 1))


def _run_positions(key):
    """Each element's place in its run of equal neighbours of ``key``."""
    if not len(key):
        return np.zeros(0, np.int64)
    change = np.empty(len(key), bool)
    change[0] = True
    np.not_equal(key[1:], key[:-1], out=change[1:])
    starts = np.flatnonzero(change)
    return np.arange(len(key)) - np.repeat(
        starts, np.diff(np.append(starts, len(key))))


def _cold_sort(out_idx, gather_idx, nbg):
    """The cold entries of one orientation by (block, lane): the stable
    order that sorts them, and in that order their keys
    (:func:`_cold_key`) and each one's depth in its (block, lane)."""
    key = _cold_key(out_idx.astype(np.int64), gather_idx.astype(np.int64),
                    nbg)
    order = np.argsort(key, kind="stable")
    if len(key) < 1 << 31:
        order = order.astype(np.int32)  # held for the layout: half the bytes
    key = key[order]
    return order, key, _run_positions(key).astype(np.int32)


def _cold_cheapest(blocks, a_f, a_b, spilled):
    """The cold band's cheapest depths: ``(seconds, a_f, a_b)`` among the
    candidate depths ``a_f`` (forward) and ``a_b`` (backward), by the
    predicted device seconds of a forward and a backward product: every
    block at ``COLD_BLOCK_SECONDS`` and ``COLD_SUBLANE_SECONDS`` a sublane
    of each depth, and ``spilled[i, j]``, the entries that depths ``a_f[i]``
    and ``a_b[j]`` leave to the spill (which both products read), at
    ``COLD_SPILL_FIXED_SECONDS`` and ``COLD_SPILL_SECONDS`` an entry a
    product.  The one pricing of the band: the build chooses its depths by
    it (:func:`_cold_depths`), the split its warm band
    (:func:`_warm_prefix`)."""
    seconds = blocks * (2 * COLD_BLOCK_SECONDS + COLD_SUBLANE_SECONDS * (
        a_f[:, None] + a_b[None, :])) + np.where(
            spilled > 0,
            2 * (COLD_SPILL_FIXED_SECONDS + COLD_SPILL_SECONDS * spilled), 0.0)
    i, j = np.unravel_index(np.argmin(seconds), seconds.shape)
    return float(seconds[i, j]), int(a_f[i]), int(a_b[j])


def _cold_depths(f, b, blocks):
    """``(a_f, a_b, spilled)``: the cold band's depth in each orientation, a
    multiple of ``COLD_SUBPAD`` up to what its deepest lane needs, the
    cheapest by :func:`_cold_cheapest`, and the entries (indices,
    ascending) at depth ``a_f`` or deeper forward or ``a_b`` or deeper
    backward, which go to the spill.  ``f``, ``b``: each orientation's
    order and depths (:func:`_cold_sort`).  The depths that spill nothing
    are among the candidates, so a band whose lanes are alike keeps its
    full depth."""
    # Only an entry COLD_SUBPAD or deeper on some side can spill: the rest
    # is kept at every candidate.
    (f_order, f_depth), (b_order, b_depth) = f, b
    deep_f = np.flatnonzero(f_depth >= COLD_SUBPAD)
    deep_b = np.flatnonzero(b_depth >= COLD_SUBPAD)
    idx = np.union1d(f_order[deep_f], b_order[deep_b])
    depths = []
    for order, depth, deep in ((f_order, f_depth, deep_f),
                               (b_order, b_depth, deep_b)):
        d = np.zeros(len(idx), np.int64)
        d[np.searchsorted(idx, order[deep])] = depth[deep]
        top = int(depth.max()) + 1 if len(depth) else 1
        # a candidate per COLD_SUBPAD sublanes up to a block's side, and
        # the no-spill depth (a lane deeper than COLD_TILE is one level)
        lv = np.minimum(d // COLD_SUBPAD, COLD_TILE // COLD_SUBPAD)
        a = COLD_SUBPAD * np.arange(1, (int(lv.max()) if len(lv) else 0) + 2)
        a[-1] = -(-top // COLD_SUBPAD) * COLD_SUBPAD
        depths.append((d, lv, a))
    (d_f, lf, a_f), (d_b, lb, a_b) = depths
    # kept[i, j]: the deep entries under both a_f[i] and a_b[j]
    kept = np.bincount(lf * len(a_b) + lb, minlength=len(a_f) * len(a_b))
    kept = kept.reshape(len(a_f), len(a_b)).cumsum(0).cumsum(1)
    _, a_f, a_b = _cold_cheapest(blocks, a_f, a_b, len(idx) - kept)
    return a_f, a_b, idx[(d_f >= a_f) | (d_b >= a_b)]


def _build_cold_orientation(out_idx, gather_idx, vals, nbo, nbg, unit,
                            order, key, depth, a):
    """Place the cold entries ``order`` names, with their keys and depths
    (:func:`_cold_sort`'s, the spill's left out), into (nbo, nbg, a, 128)
    blocks: lane ``out_idx % 128``, each entry at its depth in its (block,
    lane), every one below ``a``; a spilled entry leaves its slot empty.
    Returns (code, val)."""
    assert not len(depth) or depth.max() < a, (int(depth.max()), a)
    flat = ((key >> 7) * a + depth) * WIN + (key & (WIN - 1))
    o = out_idx[order].astype(np.int64)
    g = gather_idx[order].astype(np.int64)
    code = np.full(nbo * nbg * a * WIN, COLD_EMPTY, np.int32)
    code[flat] = (
        (((g & (COLD_TILE - 1)) >> 7) << COLD_WIN_SHIFT)
        | (((o & (COLD_TILE - 1)) >> 7) << 7)
        | (g & (WIN - 1))).astype(np.int32)
    if unit:
        val = np.zeros((1,), np.float32)
    else:
        val = np.zeros(nbo * nbg * a * WIN, np.float32)
        val[flat] = vals[order]
        val = val.reshape(nbo, nbg, a, WIN)
    return code.reshape(nbo, nbg, a, WIN), val


def _cold_depth(means: np.ndarray, copies: int) -> int:
    """The cold band's predicted depth: the deepest of the lanes whose
    loads are Poisson with ``means``, each lane met ``copies`` times (once
    a block along the other side), at the median, rounded up to
    ``COLD_SUBPAD``."""
    from scipy.special import gammainc

    means = means[means > 0]

    def over(m):
        # expected lanes at m or deeper: P(X >= m) = gammainc(m, mean)
        return copies * gammainc(m, means).sum() >= 0.5

    # the least m not over, by doubling then bisection: a band that holds
    # popular columns has lanes thousands deep
    hi = 1
    while over(hi):
        hi *= 2
    lo = hi // 2 + 1
    while lo < hi:
        mid = (lo + hi) // 2
        if over(mid):
            lo = mid + 1
        else:
            hi = mid
    return -(-lo // COLD_SUBPAD) * COLD_SUBPAD


def _cold_spill_expected(means: np.ndarray, copies: int):
    """The candidate depths of a cold orientation whose lanes' loads are
    Poisson with ``means``, each lane met ``copies`` times, as
    :func:`_cold_depths` takes them from a built band: every multiple of
    ``COLD_SUBPAD`` up to ``COLD_TILE`` below the no-spill depth
    (:func:`_cold_depth`), and that depth.  Returns the depths and the
    entries expected at each depth or deeper, E[(X - a)+] = mean P(X >= a)
    - a P(X >= a + 1) a lane; none at the no-spill depth."""
    from scipy.special import gammainc

    top = _cold_depth(means, copies)
    a = COLD_SUBPAD * np.arange(1, min(top, COLD_TILE + COLD_SUBPAD)
                                // COLD_SUBPAD + 1)
    a[-1] = top
    m = np.sort(means[means > 0])
    spilled = []
    for x in a[:-1]:
        # a lane whose mean lies 12 deviations and more below x holds
        # nothing that deep: a deep band's many candidates skip its
        # shallow lanes
        mx = m[np.searchsorted(m, x - 12 * np.sqrt(x) - 40):]
        spilled.append(copies * float(
            (mx * gammainc(x, mx) - x * gammainc(x + 1, mx)).sum()))
    return a, np.array(spilled + [0.0])


def _wide_max_stripes(n_rows: int) -> int:
    """Stripes the wide layout's warm band may take: 512 MiB of them, the
    threshold rule's bound (:func:`_threshold_stripes`), which the stripe
    chooser's own guard does not keep where the grid is wide."""
    return (512 << 20) // (4 * max(n_rows, 1))


def _warm_prefix(counts: np.ndarray, n_rows: int, slot_bytes: int,
                 depth_cap: int) -> np.ndarray:
    """The warm band's columns, ascending: a prefix of the columns by
    descending entry count, in whole column tiles, chosen by predicted
    device time of a forward plus a backward product.  A warm band of k
    tiles costs its predicted slots (the stripe chooser's depths and
    stripes for those columns, :func:`_choose_stripes`) at
    ``SLOT_SECONDS`` and its stripes at ``STRIPE_ELEMENT_SECONDS``; the
    cold band the rest, every block at ``COLD_BLOCK_SECONDS`` an
    orientation and ``COLD_SUBLANE_SECONDS`` a sublane of each
    orientation's depth by :func:`_cold_cheapest`, as the build prices
    it, with the entries each depth leaves to the spill expected from
    :func:`_cold_spill_expected` (rows are alike; orientation B's lanes
    carry the cold columns' own counts).  Candidates: k a power of two, up to the
    longest prefix whose own grid keeps :func:`grid_fill_bound` at
    ``WIDE_FILL`` or above (past it the warm grid is as empty as the one
    the wide layout replaces)."""
    order = np.argsort(-counts, kind="stable")
    total = np.cumsum(counts[order])
    live = int(np.count_nonzero(counts))
    nbr = max(1, -(-n_rows // TILE_R))
    longest = 0
    for k in range(1, -(-live // TILE_C) + 1):
        if total[min(k * TILE_C, live) - 1] < WIDE_FILL * nbr * k * SUBPAD * WIN:
            break
        longest = k
    n_cols = len(counts)
    cold_nbr = max(1, -(-n_rows // COLD_TILE))
    cold_nbc = max(1, -(-n_cols // COLD_TILE))
    blocks = cold_nbr * cold_nbc
    # orientation B's lanes: the columns of a block that share col % 128
    group = (np.arange(n_cols) // COLD_TILE) * WIN + np.arange(n_cols) % WIN

    def seconds(k):
        width = min(k * TILE_C, live)
        cold = int(total[-1] - total[width - 1]) if width else int(total[-1])
        s = 0.0
        if cold:
            rest = counts.astype(np.float64)
            rest[order[:width]] = 0.0
            a_f, s_f = _cold_spill_expected(
                np.array([cold / (blocks * WIN)]), blocks * WIN)
            a_b, s_b = _cold_spill_expected(
                np.bincount(group, rest) * COLD_TILE / n_rows, cold_nbr)
            s = _cold_cheapest(blocks, a_f, a_b, s_f[:, None] + s_b)[0]
        if width:
            ids, (a_w, a_l) = _choose_stripes(
                counts[np.sort(order[:width])], n_rows, nbr * k, slot_bytes,
                depth_cap, _wide_max_stripes(n_rows), True)
            s += (nbr * k * WIN * (a_w + a_l) * SLOT_SECONDS
                  + 2 * len(ids) * n_rows * STRIPE_ELEMENT_SECONDS)
        return s

    cands = sorted({0, longest} | {
        1 << i for i in range(longest.bit_length()) if 1 << i <= longest})
    best = min(cands, key=seconds)
    return np.sort(order[:min(best * TILE_C, live)]).astype(np.int64)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "warm", "warm_cols", "warm_slot",
        "cold_f_code", "cold_f_val", "cold_b_code", "cold_b_val",
        "cold_spill",
    ],
    meta_fields=[
        "host_coo", "n_rows", "n_cols", "cold_nbr", "cold_nbc",
        "cold_a_f", "cold_a_b", "has_warm", "has_cold", "cold_unit",
    ],
)
@dataclasses.dataclass
class WideSparseMatrix:
    """Sparse feature matrix for wide, sparse inputs (a hashed click log:
    10^6 columns, tens of entries a row), where the tile grid of
    :class:`PallasSparseMatrix` would be mostly empty slots.  Two bands of
    columns, split at build time by entry count (:func:`build_wide_host`):

    - **warm** -- the popular columns, as a :class:`PallasSparseMatrix` of
      their own (stripes, permutation, tiles; ``warm_cols`` are their
      original ids, ascending);
    - **cold** -- every other column's entries, in (``COLD_TILE``)^2
      blocks whose slots each name their own gather window
      (:func:`_cold_kernel`), in the original column order: no gather of
      the vector on the way in;
    - **cold spill** -- the cold entries above each orientation's chosen
      depth (:func:`_cold_depths`), a compact COO (:class:`SpillData`,
      ``has_spill`` False where there are none).

    ``warm_slot`` maps an original column to its warm position, or to the
    appended zero for a cold one (the gradient's way back: a gather).
    Products are float32 and scatter-free but for the warm band's stripes
    and the two spills' segment sums.
    """

    warm: Optional[PallasSparseMatrix]
    warm_cols: Array       # (K,) int32
    warm_slot: Array       # (n_cols,) int32, K for a cold column
    cold_f_code: Array     # (cold_nbr, cold_nbc, A_f, 128) int32
    cold_f_val: Array
    cold_b_code: Array     # (cold_nbc, cold_nbr, A_b, 128) int32
    cold_b_val: Array
    cold_spill: SpillData
    host_coo: HostCoo
    n_rows: int
    n_cols: int
    cold_nbr: int
    cold_nbc: int
    cold_a_f: int
    cold_a_b: int
    has_warm: bool
    has_cold: bool
    cold_unit: bool

    @property
    def shape(self) -> tuple[int, int]:
        return (self.n_rows, self.n_cols)

    @property
    def nnz(self) -> int:
        return self.host_coo.nnz

    def _apply(self, vec: Array, *, transpose: bool, square: bool) -> Array:
        if transpose:
            out = jnp.zeros((self.n_cols,), jnp.float32)
            if self.has_cold:
                out = _cold_apply(
                    self.cold_b_code, self.cold_b_val,
                    jnp.pad(vec, (0, self.cold_nbr * COLD_TILE - self.n_rows)),
                    nbo=self.cold_nbc, nbg=self.cold_nbr, square=square,
                    side="bwd", unit=self.cold_unit)[: self.n_cols]
            if self.cold_spill.has_spill:
                spill = self.cold_spill
                out = out + (spill.sq_rmatvec if square else spill.rmatvec)(
                    vec)
            if self.has_warm:
                w = self.warm._apply(vec, transpose=True, square=square)
                out = out + jnp.take(
                    jnp.concatenate([w, jnp.zeros((1,), w.dtype)]),
                    self.warm_slot, axis=0)
            return out
        out = jnp.zeros((self.n_rows,), jnp.float32)
        if self.has_cold:
            out = _cold_apply(
                self.cold_f_code, self.cold_f_val,
                jnp.pad(vec, (0, self.cold_nbc * COLD_TILE - self.n_cols)),
                nbo=self.cold_nbr, nbg=self.cold_nbc, square=square,
                side="fwd", unit=self.cold_unit)[: self.n_rows]
        if self.cold_spill.has_spill:
            spill = self.cold_spill
            out = out + (spill.row_sq_matvec if square else spill.matvec)(vec)
        if self.has_warm:
            out = out + self.warm._apply(
                jnp.take(vec, self.warm_cols, axis=0), transpose=False,
                square=square)
        return out

    def matvec(self, w: Array) -> Array:
        return self._apply(w, transpose=False, square=False)

    def rmatvec(self, u: Array) -> Array:
        return self._apply(u, transpose=True, square=False)

    def row_sq_matvec(self, v: Array) -> Array:
        return self._apply(v, transpose=False, square=True)

    def sq_rmatvec(self, u: Array) -> Array:
        return self._apply(u, transpose=True, square=True)

    def col_nnz(self, row_mask=None) -> Array:
        return self.host_coo.col_nnz(row_mask)

    def col_min_max(self, row_mask=None):
        return self.host_coo.col_min_max(row_mask)

    def to_dense(self):
        return self.host_coo.to_dense()


def build_wide_host(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    n_cols: int,
    depth_cap: int = 128,
    pad_nnz: Optional[int] = None,
    dtype=jnp.float32,
) -> WideSparseMatrix:
    """The wide layout from host COO triples, on the host.  One
    ``layout.build`` span; its children are the tiled build's phases for
    the warm band (``layout.canonicalize``, ``.dense_split``,
    ``.col_perm``, ``.orient``), and for the cold band
    ``layout.wide_split`` (the warm columns chosen, the entries parted),
    ``layout.cold_orient`` (twice a side: the entries sorted by (block,
    lane), then laid out) and between the two ``layout.cold_spill`` (the
    depths chosen, the spill parted).  The span counts each storage
    class's entries (``stripe_nnz``, ``warm_tiled_nnz``, ``cold_nnz``: what
    the cold kernel holds; both bands' spilled ones in ``spilled``, the
    cold band's alone in ``cold_spilled``), the blocks stored against the
    grids' (``tiles_stored``: the warm band's tiles and the cold band's blocks,
    every one of which is stored; ``grid_tiles``: the ``TILE_R`` x
    ``TILE_C`` grid the tiled layout would store, and the cold band's
    grid), the slots allocated against the entries placed in them
    (``slots``, ``slot_entries``: both orientations of both bands), and the
    cold blocks each orientation's kernel puts into one basic block
    (``cold_bodies_f``, ``cold_bodies_b``, beside the depths ``cold_a_f``,
    ``cold_a_b``)."""
    with layer_span("layout.build") as build:
        with layer_span("layout.canonicalize"):
            r_all, c_all, v_all = canonicalize_coo(
                rows, cols, vals, n_rows, n_cols, pad_nnz)
            host_coo = HostCoo(r_all, c_all, v_all, int(n_rows), int(n_cols))
            if np.all(v_all != 0):
                r, c, v = r_all, c_all, v_all
            else:
                live = np.flatnonzero(v_all != 0)
                r, c, v = r_all[live], c_all[live], v_all[live]

        with layer_span("layout.wide_split") as split:
            warm_cols = _warm_prefix(
                np.bincount(c, minlength=n_cols), n_rows,
                CODE_BYTES + (0 if np.all(v == 1.0) else 4), depth_cap)
            k = len(warm_cols)
            slot = np.full(n_cols, k, np.int32)
            slot[warm_cols] = np.arange(k, dtype=np.int32)
            local = slot[c]
            is_warm = local < k
            cold = ~is_warm
            r_c, c_c, v_c = r[cold], c[cold], v[cold]
            warm_triples = [r[is_warm], local[is_warm], v[is_warm]]
            warm_nnz = len(warm_triples[0])
            del r, c, v, local, is_warm, cold
            split.set(warm_cols=k, cold_nnz=int(len(r_c)))

        warm = None
        if k:
            warm = _build_tiled(
                warm_triples, n_rows, k, depth_cap, None, dtype,
                _wide_max_stripes(n_rows), True, "auto", build,
                keep_coo=False)
        del warm_triples

        cold_nbr = max(1, -(-n_rows // COLD_TILE))
        cold_nbc = max(1, -(-n_cols // COLD_TILE))
        unit = bool(np.all(v_c == 1.0))
        with layer_span("layout.cold_orient", side="f"):
            f = _cold_sort(r_c, c_c, cold_nbc)
        with layer_span("layout.cold_orient", side="b"):
            b = _cold_sort(c_c, r_c, cold_nbr)
        with layer_span("layout.cold_spill"):
            a_f, a_b, spilled = _cold_depths(f[::2], b[::2],
                                             cold_nbr * cold_nbc)
            keep = np.ones(len(r_c), bool)
            keep[spilled] = False
            cold_nnz = len(r_c) - len(spilled)
            cold_spill = _spill_data(r_c[spilled], c_c[spilled],
                                     v_c[spilled], n_rows, n_cols, dtype)
        # The kept entries keep their places: a lane only loses entries.
        with layer_span("layout.cold_orient", side="f"):
            sel = keep[f[0]]
            f_code, f_val = _build_cold_orientation(
                r_c, c_c, v_c, cold_nbr, cold_nbc, unit,
                *(x[sel] for x in f), a_f)
        del f
        with layer_span("layout.cold_orient", side="b"):
            sel = keep[b[0]]
            b_code, b_val = _build_cold_orientation(
                c_c, r_c, v_c, cold_nbc, cold_nbr, unit,
                *(x[sel] for x in b), a_b)
        del b, keep, sel

        P = WideSparseMatrix(
            warm=warm, warm_cols=warm_cols.astype(np.int32), warm_slot=slot,
            cold_f_code=f_code, cold_f_val=f_val,
            cold_b_code=b_code, cold_b_val=b_val, cold_spill=cold_spill,
            host_coo=host_coo, n_rows=int(n_rows), n_cols=int(n_cols),
            cold_nbr=cold_nbr, cold_nbc=cold_nbc, cold_a_f=a_f, cold_a_b=a_b,
            has_warm=warm is not None, has_cold=bool(cold_nnz),
            cold_unit=unit,
        )
        cold_spilled = len(r_c) - cold_nnz
        stripe_nnz = warm_tiled = warm_spilled = 0
        slots = f_code.size + b_code.size
        tiles = cold_nbr * cold_nbc
        if warm is not None:
            stripe_nnz = int(np.count_nonzero(warm.dense_cols)
                             + np.count_nonzero(warm.dense_rows))
            warm_spilled = build.attrs["spilled"]
            warm_tiled = warm_nnz - stripe_nnz - warm_spilled
            slots += warm.f_code.size + warm.b_code.size
            tiles += warm.nbr * warm.nbc
        build.set(
            nnz=P.nnz, layout="wide", warm_cols=k, stripe_nnz=stripe_nnz,
            warm_tiled_nnz=warm_tiled, cold_nnz=cold_nnz,
            spilled=warm_spilled + cold_spilled, cold_spilled=cold_spilled,
            tiles_stored=tiles,
            grid_tiles=max(1, -(-n_rows // TILE_R))
            * max(1, -(-n_cols // TILE_C)) + cold_nbr * cold_nbc,
            cold_blocks=cold_nbr * cold_nbc, cold_a_f=a_f, cold_a_b=a_b,
            cold_bodies_f=_cold_plan(cold_nbr, cold_nbc, a_f, unit)[2],
            cold_bodies_b=_cold_plan(cold_nbc, cold_nbr, a_b, unit)[2],
            slots=int(slots), slot_entries=2 * (warm_tiled + cold_nnz),
        )
    return P


# ---------------------------------------------------------------------------
# Streaming support: uniform chunk layouts
# ---------------------------------------------------------------------------


class DroppedHostCoo(HostCoo):
    """Placeholder for streaming chunks whose host triples were freed.

    Streaming keeps MANY chunk layouts resident in host RAM; the canonical
    triples would roughly double that footprint for cold paths the trainer
    never touches.  Shape-class equality/hash (nnz == 0) still works, so jit
    caches behave; any cold-path use fails loudly instead of returning
    empty statistics.
    """

    def __init__(self, n_rows, n_cols):
        super().__init__(
            np.zeros(0, np.int32), np.zeros(0, np.int32),
            np.zeros(0, np.float32), int(n_rows), int(n_cols),
        )

    def _dropped(self, *args, **kwargs):
        raise RuntimeError(
            "host COO triples were dropped for this streaming chunk; "
            "cold-path statistics (col_nnz / col_min_max / to_dense) are "
            "unavailable — compute them at ingest time instead"
        )

    col_nnz = _dropped
    col_min_max = _dropped
    to_dense = _dropped


def layout_to_host(P: PallasSparseMatrix) -> PallasSparseMatrix:
    """Pull every array leaf of a layout back to host numpy (streaming
    chunks live in host RAM and are ``device_put`` per optimizer pass)."""
    return jax.tree.map(np.asarray, P)


def _pad_axis(
    arr: np.ndarray, axis: int, target: int, constant_values=0
) -> np.ndarray:
    """Zero-pad by default; slot-CODE arrays must pass
    ``constant_values=EMPTY_MARK`` — an all-zero code pad reads as a VALID
    slot (win 0, ohi 0, lo 0) under the unit-value layout."""
    cur = arr.shape[axis]
    if cur == target:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, target - cur)
    return np.pad(arr, widths, constant_values=constant_values)


def uniformize_pallas_layouts(
    mats: list[PallasSparseMatrix],
    drop_host_coo: bool = True,
) -> list[PallasSparseMatrix]:
    """Pad a list of layouts over the SAME (n_rows, n_cols) shape to one
    common pytree structure and shape set, so one jitted program serves
    every chunk of a streamed dataset (out-of-core training — SURVEY.md §7
    "Host→device ingest bandwidth for 1B rows").

    Chunks differ in packed sublane counts (a_f/a_b), spill size, and dense
    stripe counts; all are padded to the max across chunks with inert
    entries (zero values contribute ``g·0 = 0`` in the kernels; zero-value
    dense stripes and spill entries likewise).  Chunks must be built with
    ``col_permutation=False`` — per-chunk permutations could not share one
    compiled program.  All leaves must already be host numpy
    (:func:`layout_to_host`); padding happens entirely on host.
    """
    if not mats:
        return []
    targets = uniformize_targets(mats)
    return [uniformize_one(m, targets, drop_host_coo) for m in mats]


def uniformize_targets(mats: list[PallasSparseMatrix]) -> dict:
    """The cross-chunk max shapes/flags :func:`uniformize_one` pads to.
    Reads only metadata and (for the mixed unit-vals case, inside
    uniformize_one) codes — cheap on disk-backed (memmap) leaves, which
    is what lets a spilling chunk store pad-and-respill ONE chunk at a
    time instead of materializing every padded layout at once."""
    m0 = mats[0]
    for m in mats[1:]:
        if (m.n_rows, m.n_cols) != (m0.n_rows, m0.n_cols):
            raise ValueError(
                f"chunk shape mismatch: {(m.n_rows, m.n_cols)} vs "
                f"{(m0.n_rows, m0.n_cols)}"
            )
    if any(m.has_col_perm for m in mats):
        raise ValueError(
            "streaming chunks must be built with col_permutation=False"
        )
    return {
        "a_f": max(m.a_f for m in mats),
        "a_b": max(m.a_b for m in mats),
        "kc": max(m.dense_col_ids.shape[0] for m in mats),
        "kr": max(m.dense_row_ids.shape[0] for m in mats),
        "any_spill": any(m.spill.has_spill for m in mats),
        "spill_budget": max(max(m.spill.spill_coo.nnz for m in mats), 1),
        "depth_f": max(m.depth_f for m in mats),
        "depth_b": max(m.depth_b for m in mats),
        # unit_vals must be uniform (it is pytree meta).  A mixed set
        # keeps the valued layout: unit chunks materialize val = 1.0 at
        # valid slots.
        "all_unit": all(m.unit_vals for m in mats),
    }


def uniformize_one(
    m: PallasSparseMatrix, t: dict, drop_host_coo: bool = True
) -> PallasSparseMatrix:
    """Pad ONE layout to the :func:`uniformize_targets` shapes."""
    from photon_ml_tpu.ops.sparse import pad_coo_triples

    all_unit = t["all_unit"]
    if m.unit_vals and not all_unit:
        m = dataclasses.replace(
            m,
            f_val=(np.asarray(m.f_code) >= 0).astype(np.float32),
            b_val=(np.asarray(m.b_code) >= 0).astype(np.float32),
            unit_vals=False,
        )
    sc = m.spill.spill_coo
    rows, cols, vals = pad_coo_triples(
        np.asarray(sc.row_ids), np.asarray(sc.col_ids),
        np.asarray(sc.values), t["spill_budget"],
    )
    spill = SpillData(
        spill_coo=SparseMatrix(
            row_ids=rows, col_ids=cols, values=vals,
            n_rows=m.n_rows, n_cols=m.n_cols,
        ),
        has_spill=t["any_spill"],
    )
    host_coo = (
        DroppedHostCoo(m.n_rows, m.n_cols) if drop_host_coo
        else m.host_coo
    )
    return dataclasses.replace(
        m,
        f_code=_pad_axis(np.asarray(m.f_code), 2, t["a_f"],
                         constant_values=EMPTY_MARK),
        f_val=(
            np.asarray(m.f_val) if all_unit
            else _pad_axis(np.asarray(m.f_val), 2, t["a_f"])
        ),
        b_code=_pad_axis(np.asarray(m.b_code), 2, t["a_b"],
                         constant_values=EMPTY_MARK),
        b_val=(
            np.asarray(m.b_val) if all_unit
            else _pad_axis(np.asarray(m.b_val), 2, t["a_b"])
        ),
        spill=spill,
        dense_cols=_pad_axis(np.asarray(m.dense_cols), 0, t["kc"]),
        dense_col_ids=_pad_axis(
            np.asarray(m.dense_col_ids), 0, t["kc"]
        ),
        dense_rows=_pad_axis(np.asarray(m.dense_rows), 0, t["kr"]),
        dense_row_ids=_pad_axis(
            np.asarray(m.dense_row_ids), 0, t["kr"]
        ),
        host_coo=host_coo,
        a_f=t["a_f"], a_b=t["a_b"],
        depth_f=t["depth_f"], depth_b=t["depth_b"],
        has_dense_cols=t["kc"] > 0,
        has_dense_rows=t["kr"] > 0,
    )
