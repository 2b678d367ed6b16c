"""The wide layout (``ops/sparse_pallas.WideSparseMatrix``: a warm band of
tiles and a cold band of mixed blocks) on the CPU, Pallas in interpret mode:
its four products against a float64 SciPy CSR, a warm-started L-BFGS grid
on it against a float64 objective, the split's counts, and the rule of
``make_glm_data(use_pallas="auto")``, which has to build exactly today's
layout at every accepted configuration of the benchmark."""

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


def _close(got, want, scale):
    np.testing.assert_array_less(
        np.abs(np.asarray(got, np.float64) - want), 2e-6 * scale + 1e-6)


@pytest.mark.parametrize("unit", [True, False], ids=["binary", "valued"])
def test_products_against_float64(unit):
    X = _wide_matrix(3 + unit, unit)
    P = _both_bands(X)
    assert P.has_warm and P.has_cold
    assert P.cold_unit is unit and P.warm.unit_vals is unit
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
    for name, vec, want, scale in products:
        got = jax.jit(lambda P, v, name=name: getattr(P, name)(v))(
            P, jnp.asarray(vec))
        _close(got, want, scale)
    # the empty rows and the empty column tiles read exact zeros
    assert not np.any(np.asarray(P.matvec(jnp.asarray(w)))[100:164])
    assert not np.any(np.asarray(P.rmatvec(jnp.asarray(u)))[20480:30720])


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
