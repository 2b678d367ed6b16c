"""Chip only: the dense stripes' share of the four products on a TPU against
a float64 host product (ROADMAP D12: the stripes state f32 products and f32
sums; at the MXU's default precision the same products read 4e-3 off).

``tests/conftest.py`` holds every test run to the CPU, so on the machine
with the chip run this file without it:

    chiprun -- python -m pytest --noconftest -q tests/test_stripe_products_tpu.py
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.ops.sparse_pallas import build_pallas_host

N_ROWS, N_COLS, HOT = 65_536, 16_384, 300


@pytest.fixture(scope="module")
def layout():
    if jax.default_backend() != "tpu":
        pytest.skip("needs a TPU: the CPU has no MXU to round for")
    rng = np.random.default_rng(0)
    # 300 columns in a quarter of the rows each, over a thin background
    hot_rows = np.flatnonzero(rng.random((HOT, N_ROWS)) < 0.25)
    rows = np.concatenate([hot_rows % N_ROWS,
                           rng.integers(0, N_ROWS, 8 * N_ROWS)])
    cols = np.concatenate([HOT + hot_rows // N_ROWS,
                           rng.integers(0, N_COLS, 8 * N_ROWS)])
    vals = rng.normal(size=len(rows)).astype(np.float32)
    P = build_pallas_host(rows, cols, vals, N_ROWS, N_COLS)
    assert len(P.dense_col_ids) >= HOT
    return P


@pytest.mark.parametrize("squared", [False, True])
@pytest.mark.parametrize("form", ["_stripes_t_dot", "_stripes_dot"])
def test_stripe_products_match_float64(layout, form, squared):
    rng = np.random.default_rng(1)
    block = layout.dense_cols
    ref_block = block.astype(np.float64) ** (2 if squared else 1)
    sq = (lambda x: x * x) if squared else (lambda x: x)
    if form == "_stripes_t_dot":
        vec = rng.normal(size=len(block)).astype(np.float32)
        fn = lambda d, v: layout._stripes_t_dot(v, sq(d))
        want = vec.astype(np.float64) @ ref_block
    else:
        vec = rng.normal(size=N_ROWS).astype(np.float32)
        fn = lambda d, v: layout._stripes_dot(sq(d), v)
        want = ref_block @ vec.astype(np.float64)
    got = np.asarray(jax.jit(fn)(jnp.asarray(block), jnp.asarray(vec)),
                     np.float64)
    assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()
