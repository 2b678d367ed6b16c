"""The program's orthant-wise solver (``optim/owlqn.py``) against the plain
float64 NumPy one of ``benchmarks/reference_l1.py`` on seeded sparse logistic
problems -- iterations, evaluations, value per iteration, support -- both
against an independent float64 optimum (proximal gradient run long), the
pseudo-gradient against the reference's, the counts under ``vmap``, elastic
net through the same path, and the warm start that the rules before PR 37
ended after one iteration.  CPU, small sizes."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_l1
from benchmarks.datagen import glm_sparse
from photon_ml_tpu.data.dataset import make_glm_data
from photon_ml_tpu.optim.owlqn import (
    OWLQNConfig,
    _pseudo_gradient,
    owlqn_solve,
)
from photon_ml_tpu.optim.problem import (
    GlmOptimizationConfig,
    GlmOptimizationProblem,
    OptimizerConfig,
    OptimizerType,
)
from photon_ml_tpu.optim.regularization import RegularizationContext

SHAPE = dict(
    n_rows=4096, n_features=300, nnz_per_row=12, data_seed=7,
    generator_params=dict(zipf_exponent=1.0, zipf_shift=16,
                          value_log_sigma=0.5, model_scale=3.0,
                          block_rows=1024))
# enough rows for make_glm_data to build the tiled layout, and for the
# objective to be large beside a unit step's decrease
WIDE = {**SHAPE, "n_rows": 16384, "n_features": 1000}
TOLERANCE = 1e-4


def _corpus(shape, seed):
    host = glm_sparse.generate(shape, seed)
    ref = reference_l1.GlmL1Reference(
        host["cols"], host["vals"], host["labels"], host["n_features"])
    mask = np.ones(host["n_features"] + 1)
    mask[-1] = 0.0  # the intercept is not penalised
    return host, ref, mask


@pytest.fixture(scope="module")
def corpus():
    return _corpus(SHAPE, 5)


@pytest.fixture(scope="module")
def wide():
    return _corpus(WIDE, 11)


def _problem(tolerance, max_iters=100, alpha=1.0):
    return GlmOptimizationProblem("logistic", GlmOptimizationConfig(
        optimizer=OptimizerConfig(optimizer=OptimizerType.OWLQN,
                                  max_iters=max_iters, tolerance=tolerance),
        regularization=(RegularizationContext.l1() if alpha == 1.0
                        else RegularizationContext.elastic_net(alpha))))


def _support(w, mask):
    return np.flatnonzero((np.asarray(w) != 0) & (mask != 0))


def _same_path(res, want, rtol):
    """A ``SolveResult`` took the reference's path: the same iterations,
    evaluations, clamps and support, the same value at every iteration."""
    k = int(res.iterations)
    assert k == want["iterations"]
    assert int(res.fn_evals) == want["fn_evals"] == 1 + sum(want["trials"])
    assert bool(res.converged) == want["converged"]
    assert bool(res.stalled) == want["stalled"]
    assert int(res.orthant_clamps) == want["clamps"]
    assert int(res.nonzeros) == want["nonzeros"]
    np.testing.assert_allclose(np.asarray(res.values)[:k + 1],
                               want["values"], rtol=rtol)
    np.testing.assert_allclose(np.asarray(res.grad_norms)[:k + 1],
                               want["pg_norms"], rtol=3e3 * rtol, atol=1e-9)


# -- the algorithm, float64 against float64 --------------------------------
@pytest.mark.parametrize("lam, start, alpha", [
    (10.0, 0.0, 1.0), (1.0, 0.0, 1.0), (0.1, 0.0, 1.0),
    # from a dense far point: the projection clamps dozens of coordinates
    (1.0, 2.0, 1.0), (10.0, -1.0, 1.0),
    # elastic net: half of the weight a ridge on the smooth part
    (1.0, 0.0, 0.5), (0.1, 2.0, 0.5),
])
def test_float64_owlqn_is_the_references_step_for_step(corpus, lam, start,
                                                       alpha):
    host, ref, mask = corpus
    X = jnp.asarray(glm_sparse.as_csr(host).todense(), jnp.float64)
    y = jnp.asarray(host["labels"], jnp.float64)
    l1, l2 = alpha * lam, (1.0 - alpha) * lam

    def vg(w):
        m = X @ w
        return (jnp.sum(jnp.logaddexp(0.0, m) - y * m) + 0.5 * l2 * w @ w,
                X.T @ (jax.nn.sigmoid(m) - y) + l2 * w)

    w0 = start * host["w_true"]
    res = jax.jit(lambda w: owlqn_solve(
        vg, w, l1, OWLQNConfig(max_iters=60, tolerance=TOLERANCE),
        l1_mask=jnp.asarray(mask)))(jnp.asarray(w0, jnp.float64))
    want = reference_l1.owlqn(
        reference_l1.L1Objective(ref, mask, l2=l2), l1, w0, max_iters=60,
        tolerance=TOLERANCE)
    _same_path(res, want, rtol=1e-10)
    assert want["stopped_by"] in ("pgrad", "improvement", "stall", "cap")
    assert np.array_equal(_support(res.w, mask), _support(want["w"], mask))
    np.testing.assert_allclose(np.asarray(res.w), want["w"], rtol=0,
                               atol=1e-8 * np.linalg.norm(want["w"]))
    np.testing.assert_allclose(np.asarray(res.grad), want["pgrad"], rtol=0,
                               atol=1e-7 * want["pg_norms"][0])
    if start:
        assert want["clamps"] > 50


# -- an independent float64 optimum ----------------------------------------
def _proximal_gradient(host, mask, l1, l2, steps=30000):
    """Proximal gradient (ISTA) on the dense matrix with a fixed step 1 / L,
    L = |X|_2^2 / 4 + l2: no orthant, no history, no search, none of the
    reference's code."""
    X = np.asarray(glm_sparse.as_csr(host).todense(), np.float64)
    y = np.asarray(host["labels"], np.float64)
    L = 0.25 * np.linalg.norm(X, 2) ** 2 + l2

    def smooth(w):
        m = X @ w
        return (np.sum(np.logaddexp(0.0, m) - y * m) + 0.5 * l2 * w @ w,
                X.T @ (0.5 * (1.0 + np.tanh(0.5 * m)) - y) + l2 * w)

    w = np.zeros(X.shape[1])
    for _ in range(steps):
        z = w - smooth(w)[1] / L
        w = np.sign(z) * np.maximum(np.abs(z) - l1 * mask / L, 0.0)
    return w, smooth(w)[0] + l1 * float(mask @ np.abs(w))


@pytest.fixture(scope="module")
def tiny():
    return _corpus({**SHAPE, "n_rows": 512, "n_features": 40,
                    "nnz_per_row": 6}, 3)


@pytest.mark.parametrize("lam, alpha", [(3.0, 1.0), (0.3, 1.0), (3.0, 0.5)])
def test_both_reach_an_independent_optimum(tiny, lam, alpha):
    host, ref, mask = tiny
    l1, l2 = alpha * lam, (1.0 - alpha) * lam
    _w_best, f_best = _proximal_gradient(host, mask, l1, l2)
    objective = reference_l1.L1Objective(ref, mask, l2=l2)
    want = reference_l1.owlqn(objective, l1, np.zeros(mask.shape[0]),
                              max_iters=200, tolerance=1e-7)
    assert want["converged"]
    assert abs(want["value"] - f_best) <= 1e-7 * f_best
    data = make_glm_data(glm_sparse.as_csr(host), host["labels"],
                         use_pallas=False)
    res = _problem(1e-6, 200, alpha).solve_single_device(
        data, lam, l1_mask=jnp.asarray(mask, jnp.float32))
    assert abs(float(res.value) - f_best) <= 2e-6 * f_best
    # by the reference alone, at the float32 answer
    f, _pg = objective.value_and_pgrad(np.asarray(res.w, np.float64), l1)
    assert 0 <= f - f_best + 1e-9 * f_best <= 2e-6 * f_best


# -- the normal path, float32 against float64 ------------------------------
@pytest.mark.parametrize("lam, alpha", [(10.0, 1.0), (1.0, 1.0), (0.1, 1.0),
                                        (1.0, 0.5)])
def test_float32_solve_follows_the_reference(corpus, lam, alpha):
    host, ref, mask = corpus
    data = make_glm_data(glm_sparse.as_csr(host), host["labels"],
                         use_pallas=False)
    res = _problem(1e-3, alpha=alpha).solve_single_device(
        data, lam, l1_mask=jnp.asarray(mask, jnp.float32))
    l1, l2 = alpha * lam, (1.0 - alpha) * lam
    want = reference_l1.owlqn(
        reference_l1.L1Objective(ref, mask, l2=l2), l1,
        np.zeros(mask.shape[0]), max_iters=100, tolerance=1e-3)
    k = int(res.iterations)
    # The float64 test above is exact.  float32 keeps the reference's path
    # (the value at each common iteration within 3e-5, measured) until the
    # relative-decrease stop, which fires when one step happens to fall
    # under 1e-5 of F: the same iteration at three of the four, five apart
    # at the fourth (23 against 28), the answers 2e-4 apart by value there.
    assert abs(k - want["iterations"]) <= 6
    assert abs(int(res.fn_evals) - want["fn_evals"]) <= 8
    both = min(k, want["iterations"]) + 1
    np.testing.assert_allclose(np.asarray(res.values)[:both],
                               want["values"][:both], rtol=1e-4)
    np.testing.assert_allclose(float(res.value), want["value"], rtol=5e-4)
    assert bool(res.converged) and not bool(res.stalled)
    assert want["stopped_by"] == "improvement"
    mine, theirs = _support(res.w, mask), _support(want["w"], mask)
    assert len(np.setxor1d(mine, theirs)) <= max(5, len(theirs) // 50)
    assert int(res.nonzeros) == len(mine)
    assert int(res.fn_evals) >= k + 1


# -- the Pallas layout ------------------------------------------------------
@pytest.fixture(scope="module")
def tiled(wide):
    host, _ref, _mask = wide
    before = os.environ.get("PHOTON_PALLAS_INTERPRET")
    os.environ["PHOTON_PALLAS_INTERPRET"] = "1"
    try:
        yield make_glm_data(glm_sparse.as_csr(host), host["labels"],
                            use_pallas=True)
    finally:
        if before is None:
            os.environ.pop("PHOTON_PALLAS_INTERPRET", None)
        else:
            os.environ["PHOTON_PALLAS_INTERPRET"] = before


@pytest.mark.parametrize("lam", [100.0, 10.0])
def test_on_the_pallas_layout_against_the_reference(wide, tiled, lam):
    host, ref, mask = wide
    assert type(tiled.features).__name__ == "PallasSparseMatrix"
    res = _problem(0.005).solve_single_device(
        tiled, lam, l1_mask=jnp.asarray(mask, jnp.float32))
    want = reference_l1.owlqn(
        reference_l1.L1Objective(ref, mask), lam, np.zeros(mask.shape[0]),
        max_iters=100, tolerance=0.005)
    _same_path(res, want, rtol=2e-6)
    assert np.array_equal(_support(res.w, mask), _support(want["w"], mask))
    # the zeros are exact: what the device counted is what the host reads
    w = np.asarray(res.w)
    assert int(res.nonzeros) == np.count_nonzero(w[:-1])
    assert not np.any(np.signbit(w) & (w == 0))


# -- the warm start that ended after one iteration -------------------------
GRID = [100.0, 10.0, 1.0, 0.1]


def _chain(objective, **rules):
    """The warm-started grid in the reference: (iterations, values)."""
    start, iterations, values = np.zeros(objective.mask.shape[0]), [], []
    for lam in GRID:
        got = reference_l1.owlqn(objective, lam, start, max_iters=100,
                                 tolerance=0.02, **rules)
        assert got["stopped_by"] == "improvement"
        iterations.append(got["iterations"])
        values.append(got["value"])
        start = got["w"]
    return iterations, values


@pytest.mark.parametrize("from_pairs, first, last", [
    # the rules before PR 37: the solve from zero and the last warm-started
    # one each take the normalised steepest-descent step, which the search
    # cuts to 1/32, and read its decrease as convergence
    (0, 1, 1),
    # the empty history alone exempt: the next cut step ends them
    (1, 2, 2),
])
def test_the_old_rules_end_a_warm_start_at_once(wide, from_pairs, first,
                                                last):
    _host, ref, mask = wide
    objective = reference_l1.L1Objective(ref, mask)
    iterations, values = _chain(objective, rel_test_from_pairs=from_pairs)
    assert (iterations[0], iterations[3]) == (first, last)
    repaired, best = _chain(objective)
    assert min(repaired) >= 3 and repaired[3] >= 10
    # 16,384 rows at tolerance 0.02: the early ending stands 7% above
    assert values[3] > 1.07 * best[3]


def test_the_program_goes_on_where_the_old_rules_ended(wide, tiled):
    _host, ref, mask = wide
    _iterations, best = _chain(reference_l1.L1Objective(ref, mask))
    results = _problem(0.02).run_grid(
        tiled, GRID, l1_mask=jnp.asarray(mask, jnp.float32))
    for (lam, _model, res), value in zip(results, best):
        assert int(res.iterations) >= 3, lam
        assert bool(res.converged) and not bool(res.stalled)
        assert float(res.value) == pytest.approx(value, rel=2e-3), lam


# -- the pseudo-gradient ----------------------------------------------------
@pytest.mark.parametrize("w, grad, want", [
    # at zero, inside the subdifferential's interval: stays
    (0.0, 0.5, 0.0), (0.0, -0.5, 0.0), (0.0, 1.0, 0.0), (0.0, -1.0, 0.0),
    # at zero, outside it: the one-sided derivative of smaller size
    (0.0, 3.0, 2.0), (0.0, -3.0, -2.0),
    # away from zero: the gradient plus the penalty's slope
    (2.0, 0.5, 1.5), (-2.0, 0.5, -0.5), (1e-30, -3.0, -2.0),
    (-0.0, 3.0, 2.0),  # a negative zero is a zero
])
def test_pseudo_gradient_by_coordinate(w, grad, want):
    one = jnp.ones((1,), jnp.float32)
    got = _pseudo_gradient(w * one, grad * one, jnp.float32(1.0), one)
    assert float(got[0]) == pytest.approx(want)
    theirs = reference_l1.pseudo_gradient(
        np.array([w]), np.array([grad]), 1.0, np.ones(1))
    assert theirs[0] == pytest.approx(want)
    # an unpenalised coordinate keeps its gradient
    free = _pseudo_gradient(w * one, grad * one, jnp.float32(1.0), 0 * one)
    assert float(free[0]) == pytest.approx(grad)
    assert reference_l1.pseudo_gradient(
        np.array([w]), np.array([grad]), 1.0, np.zeros(1))[0] == grad


def test_pseudo_gradient_against_the_reference(corpus):
    host, ref, mask = corpus
    rng = np.random.default_rng(1)
    w = host["w_true"] * (rng.uniform(size=mask.shape[0]) < 0.4)
    _f, g = ref.value_and_grad(w, 0.0)
    for lam in (0.1, 5.0, 50.0):
        want = reference_l1.pseudo_gradient(w, g, lam, mask)
        got = _pseudo_gradient(jnp.asarray(w), jnp.asarray(g),
                               jnp.asarray(lam), jnp.asarray(mask))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-12)
        # it is the minimum-norm subgradient: no larger than any other
        # choice of sign at the zeros
        for sign in (-1.0, 1.0):
            other = g + lam * mask * np.where(w != 0, np.sign(w), sign)
            assert np.linalg.norm(want) <= np.linalg.norm(other) + 1e-12
        # the planted fault is another vector wherever a zero should move
        wrong = reference_l1.pseudo_gradient(w, g, lam, mask,
                                             one_sided=False)
        moves = (w == 0) & (want != 0)
        assert moves.any() == (not np.array_equal(wrong, want))


# -- the counts under vmap --------------------------------------------------
@pytest.mark.parametrize("lanes", [1, 5])
def test_counts_are_per_lane_under_vmap(lanes):
    rng = np.random.default_rng(4)
    # float64: a lane's line search then makes the trials it makes alone
    X = jnp.asarray(rng.normal(size=(lanes, 80, 9)), jnp.float64)
    y = jnp.asarray(rng.uniform(size=(lanes, 80)) < 0.5, jnp.float64)
    l1 = jnp.asarray(np.geomspace(8.0, 0.05, lanes), jnp.float64)
    config = OWLQNConfig(max_iters=40, tolerance=1e-5)

    def solve_one(X, y, l1):
        def vg(w):
            m = X @ w
            return (jnp.sum(jnp.logaddexp(0.0, m) - y * m),
                    X.T @ (jax.nn.sigmoid(m) - y))

        return owlqn_solve(vg, jnp.zeros((9,), jnp.float64), l1, config)

    batched = jax.jit(jax.vmap(solve_one))(X, y, l1)
    for name in ("fn_evals", "orthant_clamps", "nonzeros", "stalled",
                 "iterations"):
        assert getattr(batched, name).shape == (lanes,), name
    for i in range(lanes):
        alone = jax.jit(solve_one)(X[i], y[i], l1[i])
        assert int(batched.iterations[i]) == int(alone.iterations)
        assert int(batched.fn_evals[i]) == int(alone.fn_evals)
        assert int(batched.nonzeros[i]) == int(alone.nonzeros) == (
            np.count_nonzero(np.asarray(batched.w[i])))
        assert int(batched.orthant_clamps[i]) == int(alone.orthant_clamps)
        assert int(batched.fn_evals[i]) >= int(batched.iterations[i]) + 1
    if lanes > 1:  # the strongest penalty keeps the fewest coefficients
        assert int(batched.nonzeros[0]) < int(batched.nonzeros[-1])


def test_evaluations_are_counted_where_they_are_made():
    rng = np.random.default_rng(2)
    A = jnp.asarray(rng.normal(size=(60, 12)), jnp.float32)
    y = jnp.asarray(rng.uniform(size=60) < 0.5, jnp.float32)
    calls = []

    def vg(w):
        jax.debug.callback(lambda: calls.append(1))
        m = A @ w
        return (jnp.sum(jnp.logaddexp(0.0, m) - y * m),
                A.T @ (jax.nn.sigmoid(m) - y))

    res = jax.jit(lambda w0: owlqn_solve(
        vg, w0, 0.5, OWLQNConfig(max_iters=25, tolerance=1e-5)))(
            jnp.asarray(rng.normal(size=12) * 3, jnp.float32))
    jax.block_until_ready(res)
    jax.effects_barrier()
    assert int(res.iterations) >= 2
    assert int(res.fn_evals) == len(calls) > int(res.iterations)
