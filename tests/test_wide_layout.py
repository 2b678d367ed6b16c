"""The wide layout (``ops/sparse_pallas.WideSparseMatrix``: a warm band of
tiles and a cold band of mixed blocks) on the CPU, Pallas in interpret mode:
its four products against a float64 SciPy CSR (both bands, with and without
the cold band's spill, and the cold band alone at several depths and block
grids, where its kernel's products are also the same to the bit as with one
block a basic block), non-finite vector entries kept to the rows and
columns that read them, a warm-started L-BFGS grid on it against a float64
objective, the split's counts, the cold band's depths by cost, and the
rule of ``make_glm_data(use_pallas="auto")``, which has to build exactly
today's layout at every accepted configuration of the benchmark."""

import contextlib
import dataclasses
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.data.dataset import make_glm_data
from photon_ml_tpu.ops import sparse_pallas as spl
from photon_ml_tpu.telemetry import layer_spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("PHOTON_PALLAS_INTERPRET", "1")


def _wide_matrix(seed, unit, n=2048, d=65536):
    """A hashed-looking matrix: an intercept, hot columns from a power law,
    a uniform tail that collides within rows, 64 empty rows and five empty
    column tiles (columns 20,480 to 30,719)."""
    rng = np.random.default_rng(seed)
    hot = (rng.zipf(1.5, (n, 10)) - 1) % 300 * 67   # under column 20,480
    tail = rng.integers(0, d - 10 * 2048, (n, 6))
    tail[tail >= 20480] += 10240
    tail[:, 5] = tail[:, 4]                       # a collision in every row
    cols = np.concatenate([hot, tail, np.full((n, 1), d - 1)], axis=1)
    rows = np.repeat(np.arange(n), cols.shape[1])
    keep = (rows < 100) | (rows >= 164)           # rows 100..163 are empty
    vals = (np.ones(rows.size, np.float32) if unit
            else rng.uniform(-2.0, 2.0, rows.size).astype(np.float32))
    X = sp.coo_matrix((vals[keep], (rows[keep], cols.ravel()[keep])),
                      shape=(n, d)).tocsr()
    X.sum_duplicates()                            # collided entries: summed
    if unit:
        X.data[:] = 1.0                           # binary: merged into one
    return X


def _both_bands(X, spill=False):
    """The wide layout of ``X`` with its 4,096 most popular columns warm
    (the split's own rule is tested below), the cold band at its full
    depth; with ``spill``, the cold band's spill priced at nothing, so that
    every lane is cut to ``COLD_SUBPAD``."""
    counts = np.bincount(X.indices, minlength=X.shape[1])
    warm = np.sort(np.argsort(-counts, kind="stable")[:4096])
    coo = X.tocoo()
    with mock.patch.object(spl, "_warm_prefix", lambda *_: warm), \
            _spill_priced(0.0 if spill else 1.0):
        return spl.build_wide_host(coo.row, coo.col, coo.data, *X.shape)


def _spill_priced(seconds):
    """The cold spill's constant and slope both at ``seconds`` (at a
    second, the cold band keeps its full depth); ``None`` leaves them as
    they are."""
    if seconds is None:
        return contextlib.nullcontext()
    return mock.patch.multiple(spl, COLD_SPILL_FIXED_SECONDS=seconds,
                               COLD_SPILL_SECONDS=seconds)


def _lane_depth(out, gather):
    """The depth one cold orientation needs to hold the entries (out,
    gather) without a spill: the deepest (block, lane), rounded up."""
    key = spl._cold_key(out.astype(np.int64), gather.astype(np.int64),
                        1 << 20)
    deepest = np.unique(key, return_counts=True)[1].max() if key.size else 1
    return -(-int(deepest) // spl.COLD_SUBPAD) * spl.COLD_SUBPAD


def _cold_coo(X, P):
    """(rows, columns) of the entries of ``X`` in ``P``'s cold columns."""
    coo = X.tocoo()
    cold = np.ones(X.shape[1], bool)
    cold[np.asarray(P.warm_cols)] = False
    return coo.row[cold[coo.col]], coo.col[cold[coo.col]]


def _cold_entries(code, transpose=False):
    """The (row, column) of every entry a cold orientation's codes hold
    (``transpose``: orientation B, whose lanes are columns)."""
    bo, bg, _, lane = np.nonzero(code >= 0)
    c = code[code >= 0].astype(np.int64)
    out = bo * spl.COLD_TILE + ((c >> 7) & (spl.COLD_WINS - 1)) * 128 + lane
    gather = (bg * spl.COLD_TILE
              + ((c >> spl.COLD_WIN_SHIFT) & (spl.COLD_WINS - 1)) * 128
              + (c & 127))
    pairs = (gather, out) if transpose else (out, gather)
    return set(zip(*(p.tolist() for p in pairs)))


def _cold_only(seed, unit, nbr, nbc, depth):
    """Every column cold, over ``nbr`` x ``nbc`` cold blocks: scattered
    entries, one row with ``depth`` entries in one column block and one
    column with ``depth`` entries in one row block, so that both
    orientations are ``depth`` deep (a multiple of ``COLD_SUBPAD``)."""
    rng = np.random.default_rng(seed)
    n, d = nbr * spl.COLD_TILE, nbc * spl.COLD_TILE
    # nothing else in lane 0 of a block, where the deep row and column lie
    rows = rng.integers(0, n // 128, 3000) * 128 + rng.integers(1, 128, 3000)
    cols = rng.integers(0, d // 128, 3000) * 128 + rng.integers(1, 128, 3000)
    rows = np.concatenate([rows, np.zeros(depth, np.int64),
                           np.arange(1, depth + 1)])
    cols = np.concatenate([cols, np.arange(1, depth + 1),
                           np.zeros(depth, np.int64)])
    vals = (np.ones(rows.size, np.float32) if unit
            else (rng.uniform(0.5, 2.0, rows.size) * rng.choice(
                [-1.0, 1.0], rows.size)).astype(np.float32))
    X = sp.coo_matrix((vals, (rows, cols)), shape=(n, d)).tocsr()
    X.sum_duplicates()
    if unit:
        X.data[:] = 1.0
    coo = X.tocoo()
    # the kernel at this depth: the spill priced out
    with mock.patch.object(spl, "_warm_prefix",
                           lambda *_: np.zeros(0, np.int64)), \
            _spill_priced(1.0):
        P = spl.build_wide_host(coo.row, coo.col, coo.data, n, d)
    assert not P.has_warm and (P.cold_a_f, P.cold_a_b) == (depth, depth)
    assert not P.cold_spill.has_spill
    return X, P


def _close(got, want, scale):
    np.testing.assert_array_less(
        np.abs(np.asarray(got, np.float64) - want), 2e-6 * scale + 1e-6)


# Both bands, without and with the cold band's spill, then the cold band
# alone: 41 row blocks (a prime past the grid step's VMEM, so the forward
# product's steps hold one output block), 8 to 40 deep, two blocks a basic
# block, so that every grid step (3 blocks forward, 3 or 123 backward)
# leaves a remainder.
PRODUCT_CASES = [
    pytest.param(True, None, id="binary"),
    pytest.param(False, None, id="valued"),
    pytest.param(True, "spill", id="spill-binary"),
    pytest.param(False, "spill", id="spill-valued"),
    pytest.param(True, 8, id="cold-8-binary"),
    pytest.param(False, 16, id="cold-16-valued"),
    pytest.param(True, 24, id="cold-24-binary"),
    pytest.param(False, 40, id="cold-40-valued"),
]


@pytest.mark.parametrize("unit, depth", PRODUCT_CASES)
def test_products_against_float64(unit, depth):
    bodies = spl._cold_bodies
    both = depth in (None, "spill")
    if both:
        X = _wide_matrix(3 + unit, unit)
        P = _both_bands(X, spill=depth == "spill")
        assert P.has_warm and P.has_cold
        assert P.cold_unit is unit and P.warm.unit_vals is unit
        assert P.cold_spill.has_spill is (depth == "spill")
        if depth == "spill":
            # the lanes are uneven enough that the full depths would be
            # deeper on both sides
            r, c = _cold_coo(X, P)
            assert (P.cold_a_f, P.cold_a_b) == (8, 8)
            assert _lane_depth(r, c) > 8 and _lane_depth(c, r) > 8
    else:
        X, P = _cold_only(depth + unit, unit, 41, 3, depth)
        assert P.cold_unit is unit
        assert spl._pick_cold_rect(41, 3, depth, unit)[0] == 1
        bodies = lambda a: 2  # noqa: E731
    P = spl.place_pallas_matrix(P)
    rng = np.random.default_rng(7)
    n, d = X.shape
    w = rng.standard_normal(d).astype(np.float32)
    u = rng.standard_normal(n).astype(np.float32)
    X64, A64 = X.astype(np.float64), abs(X.astype(np.float64))
    products = [
        ("matvec", w, X64 @ w, A64 @ abs(w)),
        ("rmatvec", u, X64.T @ u, A64.T @ abs(u)),
    ]
    if not unit:
        S64 = X64.multiply(X64)
        products += [("row_sq_matvec", w, S64 @ w, S64 @ abs(w)),
                     ("sq_rmatvec", u, S64.T @ u, S64.T @ abs(u))]
    if not both:
        products = products[:3]
    with mock.patch.object(spl, "_cold_bodies", bodies):
        spl._cold_apply.clear_cache()
        got = {name: np.asarray(jax.jit(
            lambda P, v, name=name: getattr(P, name)(v))(P, jnp.asarray(vec)))
            for name, vec, _, _ in products}
    spl._cold_apply.clear_cache()
    for name, _vec, want, scale in products:
        _close(got[name], want, scale)
    if both:
        # the empty rows and the empty column tiles read exact zeros
        assert not np.any(got["matvec"][100:164])
        assert not np.any(got["rmatvec"][20480:30720])
        return
    # the kernel of several blocks a basic block adds up as the kernel of
    # one does, to the bit
    with mock.patch.object(spl, "_cold_bodies", lambda a: 1):
        for name, vec, _, _ in products[:2]:
            one = jax.jit(lambda P, v, name=name: getattr(P, name)(v))(
                P, jnp.asarray(vec))
            np.testing.assert_array_equal(np.asarray(one), got[name])
    spl._cold_apply.clear_cache()


@pytest.mark.parametrize("spill", [False, True], ids=["full", "spill"])
def test_build_counts_every_entry_once(spill):
    X = _wide_matrix(5, True)
    # the ring is bounded: after other tests it is full, so new spans are
    # told by their ids, not by their positions
    seen = {s["id"] for s in layer_spans()}
    P = _both_bands(X, spill=spill)
    spans = [s for s in layer_spans() if s["id"] not in seen]
    (build,) = [s for s in spans if s["name"] == "layout.build"]
    a = build["attrs"]
    assert a["layout"] == "wide" and a["nnz"] == X.nnz
    assert (a["stripe_nnz"] + a["warm_tiled_nnz"] + a["spilled"]
            + a["cold_nnz"]) == X.nnz
    assert a["warm_cols"] == len(P.warm_cols)
    cold_blocks = P.cold_nbr * P.cold_nbc
    assert a["tiles_stored"] == P.warm.nbr * P.warm.nbc + cold_blocks
    assert a["grid_tiles"] == 1 * 32 + cold_blocks   # 2,048 x 65,536
    assert 0 < a["slot_entries"] <= a["slots"]
    # every phase is a child of the one build span
    kids = {s["name"] for s in spans if s["parent"] == build["id"]}
    assert {"layout.canonicalize", "layout.wide_split", "layout.dense_split",
            "layout.col_perm", "layout.orient", "layout.cold_orient",
            "layout.cold_spill"} <= kids
    # every cold entry exactly once: in both orientations' blocks, each
    # (block, lane) no deeper than the chosen depth, or in the spill
    cold = set(zip(*(x.tolist() for x in _cold_coo(X, P))))
    sc = P.cold_spill.spill_coo
    spilled = (set(zip(np.asarray(sc.row_ids).tolist(),
                       np.asarray(sc.col_ids).tolist()))
               if P.cold_spill.has_spill else set())
    assert P.cold_f_code.shape[2] == P.cold_a_f == a["cold_a_f"]
    assert P.cold_b_code.shape[2] == P.cold_a_b == a["cold_a_b"]
    kept = _cold_entries(P.cold_f_code)
    assert kept == _cold_entries(P.cold_b_code, transpose=True)
    assert len(kept) == a["cold_nnz"] == int((P.cold_f_code >= 0).sum())
    assert not kept & spilled and kept | spilled == cold
    assert a["cold_spilled"] == len(spilled) == sc.nnz * P.cold_spill.has_spill
    assert a["spilled"] == a["cold_spilled"] + int(
        P.warm.spill.has_spill and P.warm.spill.spill_coo.nnz)
    assert bool(spilled) is spill
    if spill:
        assert (a["cold_a_f"], a["cold_a_b"]) == (8, 8)
        # the spill is sorted by row, as the segment sum is told
        assert np.all(np.diff(np.asarray(sc.row_ids)) >= 0)
    # the blocks a basic block each orientation's kernel traces with
    assert (a["cold_bodies_f"], a["cold_bodies_b"]) == (
        spl._cold_plan(P.cold_nbr, P.cold_nbc, P.cold_a_f, True)[2],
        spl._cold_plan(P.cold_nbc, P.cold_nbr, P.cold_a_b, True)[2])
    assert a["cold_bodies_f"] > 1 and a["cold_bodies_b"] > 1


@pytest.mark.parametrize("nbo, nbg, a, bodies", [
    (1024, 123, 16, 8), (123, 1024, 24, 4),     # glm_click_fit's two sides
    (1024, 123, 8, 8), (1024, 123, 64, 2),      # at most 8, 128 sublanes
    (1024, 123, 128, 1), (41, 1, 8, 1),         # and a step of one block
])
def test_cold_bodies_follow_the_depth(nbo, nbg, a, bodies):
    assert spl._cold_plan(nbo, nbg, a, True)[2] == bodies


def _click_like_depths(blocks=126, seed=0):
    """Each cold entry's depth in its (block, lane), both orientations, for
    a band cut like ``glm_click_fit``'s by a thousand (40,118 entries over
    126 blocks): 24 entries 8 to 13 deep forward, 222 entries 8 to 15 deep
    and one 16 deep backward, no entry deep on both sides; the rest under
    8."""
    rng = np.random.default_rng(seed)
    n = 40118
    f = rng.integers(0, 8, n)
    b = rng.integers(0, 8, n)
    f[:24] = rng.integers(8, 14, 24)
    b[24:246] = rng.integers(8, 16, 222)
    b[246] = 16
    return f, b, blocks


@pytest.mark.parametrize("entry_seconds, depths, spilled", [
    (20e-9, (8, 8), 247),          # the spill is cheap: every lane 8 deep
    (100e-9, (8, 16), 25),         # dear: only the backward band's 16th
    (1.0, (16, 24), 0),            # priced out: the full depths
])
def test_cold_depths_by_cost(entry_seconds, depths, spilled):
    """The depths trade the kernel's seconds, which every block pays,
    against the spill's, which only the spilled entries pay: a pair at
    (8, 16) is 79.3 us of kernel and 2 x 25 spilled entries, at (8, 8) 38.1
    us and 2 x 247, so the two meet at 93 ns an entry a product."""
    f, b, blocks = _click_like_depths()
    # each side sees the entries in an order of its own, as its sort has it
    rng = np.random.default_rng(1)
    of, ob = rng.permutation(len(f)), rng.permutation(len(b))
    with mock.patch.multiple(spl, COLD_SPILL_FIXED_SECONDS=0.0,
                             COLD_SPILL_SECONDS=entry_seconds):
        a_f, a_b, out = spl._cold_depths((of, f[of]), (ob, b[ob]), blocks)
    assert (a_f, a_b) == depths
    assert len(out) == spilled
    assert np.array_equal(out, np.flatnonzero((f >= a_f) | (b >= a_b)))


def test_an_even_cold_band_keeps_its_depth():
    """Every (block, lane) 10 deep in both orientations: 8 deep, a fifth of
    the band would spill, 256 entries a block against 8 sublanes of each
    orientation (654 ns a block and a pair, so at any price over 1.3 ns an
    entry a product), so the band keeps 16 sublanes a side and no spill;
    and the empty band is one sublane group deep."""
    r = np.arange(10 * 128)               # ten rows a lane ...
    with mock.patch.object(spl, "_warm_prefix",
                           lambda *_: np.zeros(0, np.int64)):
        P = spl.build_wide_host(r, r, np.ones(len(r), np.float32),
                                spl.COLD_TILE, spl.COLD_TILE)  # ... and cols
    assert (P.cold_a_f, P.cold_a_b) == (16, 16)
    assert P.has_cold and not P.cold_spill.has_spill
    none = (np.zeros(0, np.int32), np.zeros(0, np.int32))
    assert spl._cold_depths(none, none, 1)[:2] == (8, 8)


@pytest.mark.parametrize("mean", [2.5, 6.0])
def test_the_split_expects_the_spill_the_build_counts(mean):
    """The split prices the cold band as the build does: the expected spill
    of its Poisson lanes (``mean`` entries a (block, lane)) at each
    candidate depth is the count of the entries that deep in a band drawn
    with those loads, within three deviations of the count and 2%; the
    no-spill depth, the last candidate, spills nothing; and at the price
    of the spill that the sweep fits, the depths chosen from the
    expectation are the build's."""
    rng = np.random.default_rng(7)
    side = 32 * spl.COLD_TILE
    lanes = 32 * 32 * spl.WIN
    nnz = int(mean * lanes)
    r, c = rng.integers(0, side, nnz), rng.integers(0, side, nnz)
    order, _, depth = spl._cold_sort(r, c, 32)
    a, expected = spl._cold_spill_expected(np.array([mean]), lanes)
    assert a[-1] == spl._cold_depth(np.array([mean]), lanes)
    assert expected[-1] == 0 and len(a) > 1
    for x, e in zip(a[:-1], expected[:-1]):
        counted = int(np.count_nonzero(depth >= x))
        assert abs(e - counted) <= 3 * np.sqrt(counted) + 0.02 * counted, (
            x, e, counted)
    exact = spl._cold_depths((order, depth), (order, depth), 32 * 32)
    assert spl._cold_cheapest(32 * 32, a, a, expected[:, None]
                              + expected)[1:] == exact[:2]


@pytest.mark.parametrize("unit, spill", [
    (True, False), (False, False), (True, True), (False, True)],
    ids=["binary", "valued", "spill-binary", "spill-valued"])
def test_cold_nonfinite_entries_stay_localized(unit, spill):
    """A non-finite vector entry reaches only the rows (forward) or columns
    (backward) whose cold entries read it: never an empty slot, whose
    placeholder code gathers lane 0 of window 0 of its block (column 0 and
    8,192 here, row 0), nor a slot that reads another window; with
    ``spill``, row 5 holds nine entries in one (block, lane), so that its
    last, in column 8,201, is in the cold band's spill."""
    v = 1.0 if unit else 2.0
    n, d = spl.COLD_TILE, 2 * spl.COLD_TILE
    # gather block 0's windows 0, 1 and 0, and gather block 1's window 0
    rows, cols = np.arange(4), np.array([0, 133, 72, spl.COLD_TILE])
    if spill:
        rows = np.append(rows, np.full(9, 5))
        cols = np.append(cols, spl.COLD_TILE + np.arange(1, 10))
    with mock.patch.object(spl, "_warm_prefix",
                           lambda *_: np.zeros(0, np.int64)), \
            _spill_priced(0.0 if spill else None):
        P = spl.build_wide_host(rows, cols,
                                np.full(len(rows), v, np.float32), n, d)
    assert not P.has_warm and P.cold_unit is unit
    assert P.cold_spill.has_spill is spill
    if spill:
        sc = P.cold_spill.spill_coo
        assert (list(np.asarray(sc.row_ids)), list(np.asarray(sc.col_ids))
                ) == ([5], [spl.COLD_TILE + 9])
    P = spl.place_pallas_matrix(P)
    matvec = jax.jit(lambda P, x: P.matvec(x))
    rmatvec = jax.jit(lambda P, x: P.rmatvec(x))

    def vector(size, at):
        x = np.zeros(size, np.float32)
        x[list(at)] = list(at.values())
        return jnp.asarray(x)

    out = np.asarray(matvec(P, vector(d, {0: np.inf, 133: 5, 72: 3,
                                          spl.COLD_TILE: 1})))
    assert np.isinf(out[0]) and list(out[1:4]) == [5 * v, 3 * v, v]
    assert not np.any(out[4:])
    out = np.asarray(matvec(P, vector(d, {72: np.inf, spl.COLD_TILE: np.nan})))
    assert np.isinf(out[2]) and np.isnan(out[3])
    assert out[0] == out[1] == 0 and not np.any(out[4:])
    out = np.asarray(rmatvec(P, vector(n, {0: np.inf, 1: np.nan, 2: 2})))
    assert np.isinf(out[0]) and np.isnan(out[133]) and out[72] == 2 * v
    assert not np.any(np.delete(out, [0, 72, 133]))
    if not spill:
        return
    # the spilled entry's column, then row 5 whose entries both paths hold
    out = np.asarray(matvec(P, vector(d, {spl.COLD_TILE + 9: np.inf,
                                          spl.COLD_TILE + 1: 1})))
    assert np.isinf(out[5]) and not np.any(np.delete(out, 5))
    out = np.asarray(matvec(P, vector(d, {spl.COLD_TILE + 1: np.nan})))
    assert np.isnan(out[5]) and not np.any(np.delete(out, 5))
    row5 = spl.COLD_TILE + np.arange(1, 10)
    out = np.asarray(rmatvec(P, vector(n, {5: np.inf, 0: 1})))
    assert np.all(np.isinf(out[row5])) and out[0] == v
    assert not np.any(np.delete(out, [0, *row5]))


def test_lbfgs_grid_against_float64():
    from photon_ml_tpu.optim.problem import (
        GlmOptimizationConfig, GlmOptimizationProblem, OptimizerConfig,
        OptimizerType)
    from photon_ml_tpu.optim.regularization import RegularizationContext

    X = _wide_matrix(9, True)
    rng = np.random.default_rng(1)
    z = X @ (0.5 * rng.standard_normal(X.shape[1]))
    y = (rng.random(X.shape[0]) < 1 / (1 + np.exp(-z + 1))).astype(
        np.float32)
    data = make_glm_data(X, y, use_pallas=False)
    data = dataclasses.replace(data, features=spl.place_pallas_matrix(
        _both_bands(X)))
    problem = GlmOptimizationProblem("logistic", GlmOptimizationConfig(
        optimizer=OptimizerConfig(optimizer=OptimizerType("lbfgs"),
                                  max_iters=10, tolerance=1e-7, history=10),
        regularization=RegularizationContext.l2()))
    X64 = X.astype(np.float64)

    def f64(w, lam):
        m = X64 @ w
        value = np.sum(np.logaddexp(0, m) - y * m) + 0.5 * lam * w @ w
        return value, X64.T @ (1 / (1 + np.exp(-m)) - y) + lam * w

    g0 = np.linalg.norm(f64(np.zeros(X.shape[1]), 10.0)[1])
    start = np.zeros(X.shape[1])
    for lam, _model, res in problem.run_grid(data, [10.0, 1.0, 0.1]):
        w = np.asarray(res.w, np.float64)
        value, grad = f64(w, lam)
        assert abs(float(res.value) - value) <= 3e-6 * value
        assert np.linalg.norm(np.asarray(res.grad) - grad) <= 2e-5 * g0
        assert value < f64(start, lam)[0]        # the solve descended
        start = w


def _layout_signature(features):
    return (type(features).__name__,
            [(x.shape, str(x.dtype)) for x in jax.tree.leaves(features)],
            {k: getattr(features, k) for k in (
                "a_f", "a_b", "depth_f", "depth_b", "has_dense_cols",
                "has_dense_rows", "has_col_perm", "unit_vals")
             if hasattr(features, k)})


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", [
    "glm_logistic_l2_lbfgs_rcv1", "glm_logistic_l2_tron_rcv1",
    "glm_logistic_l1_owlqn_rcv1", "game_logistic_user_re_ml20m",
    "game_logistic_user_item_re_ml20m"])
def test_auto_keeps_the_accepted_layouts(name):
    """At every accepted configuration, at its full size and at its ``dry``
    size, the tile grid is full enough that ``auto`` keeps today's layout;
    at the dry size ``make_glm_data`` builds exactly what the tiled builder
    alone builds (class, leaves, depths)."""
    cfg = _config(name)
    dry = {**cfg, **cfg["dry"]}
    if name.startswith("game"):
        from benchmarks.datagen import game_ml20m

        host = game_ml20m.generate(dry, 11)
        X = game_ml20m.shards(host)[0]["global"]
        y = host["labels"]
        # the fixed effect's shard: a movie, 16 summary columns, the
        # intercept and at least one genre a row
        per_row = 19
        n_full = cfg["n_rows"]
        d_full = game_ml20m.layout({**host, "n_movies": cfg["n_movies"]})[
            "n_fixed"]
    else:
        from benchmarks.datagen import glm_sparse

        host = glm_sparse.generate(dry, 11)
        X, y = glm_sparse.as_csr(host), host["labels"]
        per_row = cfg["nnz_per_row"] + 1
        n_full, d_full = cfg["n_rows"], cfg["n_features"] + 1
    assert spl.grid_fill_bound(n_full * per_row, n_full, d_full) >= \
        spl.WIDE_FILL
    assert spl.grid_fill_bound(X.nnz, *X.shape) >= spl.WIDE_FILL
    auto = make_glm_data(X, y, use_pallas="auto").features
    if X.shape[0] >= 65536 and X.nnz >= 1 << 20:
        today = spl.host_layout_from_scipy_csr(X)
    else:  # under the size at which auto takes the kernels at all
        today = make_glm_data(X, y, use_pallas=False).features
    assert _layout_signature(auto) == _layout_signature(today)


def test_auto_chooses_the_wide_layout_for_a_click_log():
    from benchmarks.datagen import click_hashed

    cfg = _config("glm_logistic_l2_lbfgs_criteo")
    full = spl.grid_fill_bound(
        cfg["n_rows"] * cfg["nnz_per_row"], cfg["n_rows"],
        cfg["n_features"] + 1)
    assert full < spl.WIDE_FILL
    # bytes grow with the entries, not with the grid: the same log over
    # 7.6 times the columns, where the tile grid's least codes grow 7.6
    # times, takes under a third of that growth
    sizes, grid = {}, {}
    for d in (131072, 1_000_000):
        host = click_hashed.generate(
            {**cfg, **cfg["dry"], "n_rows": 65536, "n_features": d}, 2)
        X = click_hashed.as_csr(host)
        assert spl.grid_fill_bound(X.nnz, *X.shape) < spl.WIDE_FILL
        P = spl.host_layout_from_scipy_csr(X, wide=True)
        sizes[d] = sum(x.nbytes for x in jax.tree.leaves(P))
        grid[d] = 1 / spl.grid_fill_bound(1, *X.shape)
    assert sizes[1_000_000] / sizes[131072] < (
        grid[1_000_000] / grid[131072]) / 3
