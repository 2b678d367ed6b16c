"""The program's trust-region Newton (``optim/tron.py``) against the plain
float64 NumPy one of ``benchmarks/reference_hv.py`` on seeded sparse logistic
problems, the Hessian-vector product on the Pallas layout against the
reference's and against ``jax.jvp`` of the gradient, and both solvers
against SciPy's float64 optimum.  CPU, small sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.optimize

from benchmarks import reference_hv
from benchmarks.datagen import glm_sparse
from photon_ml_tpu.data.dataset import make_glm_data
from photon_ml_tpu.ops import losses as losses_lib
from photon_ml_tpu.optim.objective import GlmObjective
from photon_ml_tpu.optim.problem import (
    GlmOptimizationConfig,
    GlmOptimizationProblem,
    OptimizerConfig,
    OptimizerType,
)
from photon_ml_tpu.optim.regularization import RegularizationContext
from photon_ml_tpu.optim.tron import TRONConfig, tron_solve

SHAPE = dict(
    n_rows=4096, n_features=300, nnz_per_row=12, data_seed=7,
    generator_params=dict(zipf_exponent=1.0, zipf_shift=16,
                          value_log_sigma=0.5, model_scale=3.0,
                          block_rows=1024))
TOLERANCE = 1e-4


@pytest.fixture(scope="module")
def corpus():
    host = glm_sparse.generate(SHAPE, 5)
    ref = reference_hv.GlmHvReference(
        host["cols"], host["vals"], host["labels"], host["n_features"])
    return host, ref


def _accepted(values, k):
    """The accept / reject pattern a solve's value tracker shows: a refused
    step leaves the value where it was."""
    v = np.asarray(values)[:k + 1]
    return [bool(v[i + 1] != v[i]) for i in range(k)]


# -- the algorithm, float64 against float64 --------------------------------
@pytest.mark.parametrize("lam, start, rejected", [
    (10.0, 0.0, 0), (1.0, 0.0, 0),
    # far from the optimum on the flat side of the sigmoids: the quadratic
    # model overshoots, a step is refused, two CGs end on the boundary
    (1.0, -8.0, 1),
])
def test_float64_tron_is_the_references_step_for_step(corpus, lam, start,
                                                      rejected):
    host, ref = corpus
    X = jnp.asarray(glm_sparse.as_csr(host).todense(), jnp.float64)
    y = jnp.asarray(host["labels"], jnp.float64)

    def vg(w):
        m = X @ w
        return (jnp.sum(jnp.logaddexp(0.0, m) - y * m) + 0.5 * lam * w @ w,
                X.T @ (jax.nn.sigmoid(m) - y) + lam * w)

    def d2(w):
        p = jax.nn.sigmoid(X @ w)
        return p * (1.0 - p)

    w0 = start * host["w_true"]
    res = jax.jit(lambda w: tron_solve(
        vg, lambda w, v, aux: X.T @ (aux * (X @ v)) + lam * v, w,
        TRONConfig(max_iters=40, tolerance=TOLERANCE), d2_fn=d2))(
            jnp.asarray(w0, jnp.float64))
    want = reference_hv.tron(ref, lam, w0, max_iters=40, tolerance=TOLERANCE)
    k = int(res.iterations)
    assert k == want["iterations"] and bool(res.converged)
    assert want["converged"] and want["stopped_by"] in ("gradient",
                                                        "improvement")
    assert _accepted(res.values, k) == want["accepted"]
    assert int(res.rejected_steps) == want["accepted"].count(False) == rejected
    assert int(res.boundary_exits) == sum(want["boundary"])
    assert int(res.cg_iterations) == sum(want["cg_iterations"])
    assert int(res.fn_evals) == k + 1
    np.testing.assert_allclose(np.asarray(res.values)[:k + 1],
                               want["values"], rtol=1e-10)
    np.testing.assert_allclose(np.asarray(res.w), want["w"], rtol=0,
                               atol=1e-8 * np.linalg.norm(want["w"]))


# -- the normal path, float32 against float64 ------------------------------
@pytest.fixture(scope="module")
def resident(corpus):
    host, _ref = corpus
    return make_glm_data(glm_sparse.as_csr(host), host["labels"],
                         use_pallas=False)


@pytest.mark.parametrize("lam", [10.0, 1.0, 0.1, 0.01])
def test_float32_solve_follows_the_reference(corpus, resident, lam):
    host, ref = corpus
    problem = GlmOptimizationProblem(
        "logistic",
        GlmOptimizationConfig(
            optimizer=OptimizerConfig(optimizer=OptimizerType.TRON,
                                      max_iters=30, tolerance=TOLERANCE),
            regularization=RegularizationContext.l2()))
    res = problem.solve_single_device(resident, lam)
    want = reference_hv.tron(ref, lam, np.zeros(host["n_features"] + 1),
                             max_iters=30, tolerance=TOLERANCE)
    k = int(res.iterations)
    assert k == want["iterations"] and bool(res.converged)
    assert _accepted(res.values, k) == want["accepted"]
    assert int(res.rejected_steps) == want["accepted"].count(False)
    # The float64 test above is exact; float32 is equal at the strong
    # ridges and at most two CG steps apart per outer iteration at the weak
    # ones (measured: 0, +4 in 4, +10 in 5, +9 in 6).  The first CG step on
    # this matrix (a dense intercept column beside sparse ones) raises the
    # residual fourfold before it falls, and float32 loses a step or two of
    # conjugacy on the way down to 0.1 |g|.
    assert abs(int(res.cg_iterations) - sum(want["cg_iterations"])) <= (
        0 if lam == 10.0 else 2 * k)
    assert all(c <= 50 for c in want["cg_iterations"])
    # ... so an iterate's objective differs by what one CG step is worth:
    # 1e-5 of it at the first iterations (measured: 1.1e-5 to 8e-5).
    np.testing.assert_allclose(np.asarray(res.values)[:k + 1],
                               want["values"], rtol=2e-4)
    np.testing.assert_allclose(float(res.value), want["value"], rtol=1e-6)
    # Both stop at |g| <= 1e-4 |g0|, not at the optimum: two points that
    # close to it lie within 1e-3 |w| of each other at the weakest ridge.
    assert np.linalg.norm(np.asarray(res.w) - want["w"]) <= (
        1e-3 * np.linalg.norm(want["w"]))
    best = scipy.optimize.minimize(
        lambda w: ref.value_and_grad(w, lam), np.zeros_like(want["w"]),
        jac=True, method="L-BFGS-B",
        options=dict(maxiter=2000, ftol=1e-15, gtol=1e-10))
    # by value both are within 1e-6 of SciPy's float64 optimum
    assert abs(float(res.value) - best.fun) <= 1e-6 * best.fun
    assert 0 <= want["value"] - best.fun + 1e-9 * best.fun <= 1e-6 * best.fun


# -- the Hessian-vector product on the tiled layout ------------------------
@pytest.fixture(scope="module")
def tiled():
    import os

    # enough rows for make_glm_data to build the tiled layout
    shape = {**SHAPE, "n_rows": 16384, "n_features": 1000}
    host = glm_sparse.generate(shape, 11)
    ref = reference_hv.GlmHvReference(
        host["cols"], host["vals"], host["labels"], host["n_features"])
    before = os.environ.get("PHOTON_PALLAS_INTERPRET")
    os.environ["PHOTON_PALLAS_INTERPRET"] = "1"
    try:
        data = make_glm_data(glm_sparse.as_csr(host), host["labels"],
                             use_pallas=True)
        yield host, ref, data
    finally:
        if before is None:
            os.environ.pop("PHOTON_PALLAS_INTERPRET", None)
        else:
            os.environ["PHOTON_PALLAS_INTERPRET"] = before


@pytest.mark.parametrize("lam", [100.0, 0.1])
def test_hvp_on_the_pallas_layout(tiled, lam):
    host, ref, data = tiled
    assert type(data.features).__name__ == "PallasSparseMatrix"
    objective = GlmObjective(losses_lib.get("logistic"))
    rng = np.random.default_rng(3)
    w = jnp.asarray(0.3 * host["w_true"], jnp.float32)
    v = jnp.asarray(rng.standard_normal(w.shape[0]), jnp.float32)
    got = objective.hvp(w, v, data, l2_weight=lam,
                        d2w=objective.d2_weights(w, data))
    want = ref.hvp(np.asarray(w, np.float64), np.asarray(v, np.float64), lam)
    scale = np.linalg.norm(want)
    assert np.linalg.norm(np.asarray(got, np.float64) - want) <= 2e-6 * scale
    # the closed form is the derivative of the gradient along v (taken on
    # the COO layout: a pallas_call has no JVP rule in interpret mode)
    flat = make_glm_data(glm_sparse.as_csr(host), host["labels"],
                         use_pallas=False)
    _g, along = jax.jvp(
        lambda w_: objective.value_and_grad(w_, flat, l2_weight=lam)[1],
        (w,), (v,))
    assert np.linalg.norm(np.asarray(along - got, np.float64)) <= 1e-5 * scale
    # one precision down the reference reads three orders worse
    rounded = ref.hvp(np.asarray(w, np.float64), np.asarray(v, np.float64),
                      lam, precision="bf16")
    assert np.linalg.norm(rounded - want) >= 1e-4 * scale


def test_reference_hvp_is_the_derivative_of_its_gradient(corpus):
    host, ref = corpus
    rng = np.random.default_rng(2)
    w = 0.5 * host["w_true"]
    v = rng.standard_normal(w.shape)
    eps = 1e-6
    slope = (ref.value_and_grad(w + eps * v, 0.7)[1]
             - ref.value_and_grad(w - eps * v, 0.7)[1]) / (2 * eps)
    np.testing.assert_allclose(ref.hvp(w, v, 0.7), slope, rtol=1e-6,
                               atol=1e-7 * np.linalg.norm(slope))
    # the faults the window plants are other products
    assert not np.allclose(ref.hvp(w, v, 0.0), ref.hvp(w, v, 0.7))
    assert not np.allclose(ref.hvp(0 * w, v, 0.7), ref.hvp(w, v, 0.7))
