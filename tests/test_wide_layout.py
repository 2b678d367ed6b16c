"""The wide layout (``ops/sparse_pallas.WideSparseMatrix``: a warm band of
tiles and a cold band of mixed blocks) on the CPU, Pallas in interpret mode:
its four products against a float64 SciPy CSR (both bands, and the cold band
alone at several depths and block grids, where its kernel's products are
also the same to the bit as with one block a basic block), non-finite
vector entries kept to the rows and columns that read them, a warm-started
L-BFGS grid on it against a float64 objective, the split's counts, and the
rule of ``make_glm_data(use_pallas="auto")``, which has to build exactly
today's layout at every accepted configuration of the benchmark."""

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


def _both_bands(X):
    """The wide layout of ``X`` with its 4,096 most popular columns warm
    (the split's own rule is tested below)."""
    counts = np.bincount(X.indices, minlength=X.shape[1])
    warm = np.sort(np.argsort(-counts, kind="stable")[:4096])
    coo = X.tocoo()
    with mock.patch.object(spl, "_warm_prefix", lambda *_: warm):
        return spl.build_wide_host(coo.row, coo.col, coo.data, *X.shape)


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
    with mock.patch.object(spl, "_warm_prefix",
                           lambda *_: np.zeros(0, np.int64)):
        P = spl.build_wide_host(coo.row, coo.col, coo.data, n, d)
    assert not P.has_warm and (P.cold_a_f, P.cold_a_b) == (depth, depth)
    return X, P


def _close(got, want, scale):
    np.testing.assert_array_less(
        np.abs(np.asarray(got, np.float64) - want), 2e-6 * scale + 1e-6)


# Both bands, then the cold band alone: 41 row blocks (a prime past the grid
# step's VMEM, so the forward product's steps hold one output block), 8 to
# 40 deep, two blocks a basic block, so that every grid step (3 blocks
# forward, 3 or 123 backward) leaves a remainder.
PRODUCT_CASES = [
    pytest.param(True, None, id="binary"),
    pytest.param(False, None, id="valued"),
    pytest.param(True, 8, id="cold-8-binary"),
    pytest.param(False, 16, id="cold-16-valued"),
    pytest.param(True, 24, id="cold-24-binary"),
    pytest.param(False, 40, id="cold-40-valued"),
]


@pytest.mark.parametrize("unit, depth", PRODUCT_CASES)
def test_products_against_float64(unit, depth):
    bodies = spl._cold_bodies
    if depth is None:
        X = _wide_matrix(3 + unit, unit)
        P = _both_bands(X)
        assert P.has_warm and P.has_cold
        assert P.cold_unit is unit and P.warm.unit_vals is unit
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
    if depth is not None:
        products = products[:3]
    with mock.patch.object(spl, "_cold_bodies", bodies):
        spl._cold_apply.clear_cache()
        got = {name: np.asarray(jax.jit(
            lambda P, v, name=name: getattr(P, name)(v))(P, jnp.asarray(vec)))
            for name, vec, _, _ in products}
    spl._cold_apply.clear_cache()
    for name, _vec, want, scale in products:
        _close(got[name], want, scale)
    if depth is None:
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


def test_build_counts_every_entry_once():
    X = _wide_matrix(5, True)
    # the ring is bounded: after other tests it is full, so new spans are
    # told by their ids, not by their positions
    seen = {s["id"] for s in layer_spans()}
    P = _both_bands(X)
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
            "layout.col_perm", "layout.orient", "layout.cold_orient"} <= kids
    cold_cols = np.setdiff1d(np.unique(X.indices), P.warm_cols)
    assert a["cold_nnz"] == int(np.isin(X.indices, cold_cols).sum())
    # the blocks a basic block each orientation's kernel traces with
    assert (a["cold_bodies_f"], a["cold_bodies_b"]) == (
        spl._cold_plan(P.cold_nbr, P.cold_nbc, P.cold_a_f, True)[2],
        spl._cold_plan(P.cold_nbc, P.cold_nbr, P.cold_a_b, True)[2])
    assert a["cold_bodies_f"] > 1 and a["cold_bodies_b"] > 1


@pytest.mark.parametrize("nbo, nbg, a, bodies", [
    (1024, 123, 16, 8), (123, 1024, 24, 4),     # glm_click_fit's two sides
    (1024, 123, 8, 16), (1024, 123, 64, 2),     # 128 sublanes in flight
    (1024, 123, 128, 1), (41, 1, 8, 1),         # and a step of one block
])
def test_cold_bodies_follow_the_depth(nbo, nbg, a, bodies):
    assert spl._cold_plan(nbo, nbg, a, True)[2] == bodies


@pytest.mark.parametrize("unit", [True, False], ids=["binary", "valued"])
def test_cold_nonfinite_entries_stay_localized(unit):
    """A non-finite vector entry reaches only the rows (forward) or columns
    (backward) whose cold entries read it: never an empty slot, whose
    placeholder code gathers lane 0 of window 0 of its block (column 0 and
    8,192 here, row 0), nor a slot that reads another window."""
    v = 1.0 if unit else 2.0
    n, d = spl.COLD_TILE, 2 * spl.COLD_TILE
    # gather block 0's windows 0, 1 and 0, and gather block 1's window 0
    cols = np.array([0, 133, 72, spl.COLD_TILE])
    with mock.patch.object(spl, "_warm_prefix",
                           lambda *_: np.zeros(0, np.int64)):
        P = spl.build_wide_host(np.arange(4), cols, np.full(4, v, np.float32),
                                n, d)
    assert not P.has_warm and P.cold_unit is unit
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
