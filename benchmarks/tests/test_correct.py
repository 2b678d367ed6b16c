"""That the comparison which decides ``correct`` can fail: the control (the
reference in the program's place, one precision down) and the faults a fit
cell can have, at a size a test run can hold (2^14 rows, Pallas in
interpret mode).  The readings at the cells' own size, on the chip, are in
PERF.md section 2.  Run by hand: ``pytest benchmarks/tests``."""

import json
import os
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import reference, run as harness  # noqa: E402
from benchmarks.datagen import glm_sparse  # noqa: E402
from benchmarks.windows import fit  # noqa: E402

CFG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "glm_logistic_l2_lbfgs_rcv1.json")))
LIMITS = CFG["limits"]
SMALL = {**CFG, **CFG["dry"]}


class Answer:
    def __init__(self, lam, w, value, grad):
        self.lam, self.w, self.value, self.grad = lam, w, value, grad
        self.iterations, self.converged = 0, False


@pytest.fixture(scope="module")
def ref_and_points():
    host = glm_sparse.generate(SMALL, 123)
    ref = reference.GlmReference(host["cols"], host["vals"], host["labels"],
                                 host["n_features"])
    rng = np.random.default_rng(0)
    points = [(lam, host["w_true"] * s
               + 0.01 * rng.standard_normal(host["n_features"] + 1))
              for lam, s in zip(CFG["reg_weights"], (0.7, 0.9, 1.0, 1.1))]
    return ref, points


def _answers(ref, points, precision):
    return [Answer(lam, w, *ref.value_and_grad(w, lam, precision=precision))
            for lam, w in points]


def test_round_bf16_keeps_eight_bits():
    x = np.array([1.0, 1.0 + 2.0 ** -8, 1.0 + 2.0 ** -7, -3.14159, 0.0],
                 np.float32)
    got = reference.round_bf16(x)
    assert got.tolist() == [1.0, 1.0, 1.0078125, -3.140625, 0.0]


def test_reference_against_itself_reads_nothing(ref_and_points):
    ref, points = ref_and_points
    got, *_ = fit.compare(ref, _answers(ref, points, "f64"))
    assert got["value_gap"] == 0 and got["grad_gap"] == 0


def test_reference_gradient_is_the_derivative_of_its_value(ref_and_points):
    ref, points = ref_and_points
    lam, w = points[1]
    _, g = ref.value_and_grad(w, lam)
    d = np.random.default_rng(1).standard_normal(w.shape)
    eps = 1e-6
    slope = (ref.value_and_grad(w + eps * d, lam)[0]
             - ref.value_and_grad(w - eps * d, lam)[0]) / (2 * eps)
    assert slope == pytest.approx(g @ d, rel=1e-6)


def test_the_seed_mirrors_the_corpus():
    wide = {**SMALL, "n_features": CFG["n_features"]}  # the cell's own law
    a, b, c = (glm_sparse.generate(wide, s) for s in (123, 123, 2 ** 31 + 5))
    for key in ("cols", "vals", "labels", "w_true"):
        assert np.array_equal(a[key], b[key]), key
    # another seed: the same corpus with some columns negated
    assert np.array_equal(a["cols"], c["cols"])
    assert np.array_equal(a["labels"], c["labels"])
    assert np.array_equal(np.abs(a["vals"]), np.abs(c["vals"]))
    flipped = a["w_true"] != c["w_true"]
    assert 0.4 < flipped.mean() < 0.6
    assert np.array_equal(a["w_true"][flipped], -c["w_true"][flipped])
    assert np.array_equal(a["vals"] != c["vals"], flipped[a["cols"]])
    k = wide["nnz_per_row"]
    cols = a["cols"][:, :k]
    assert (np.diff(cols, axis=1) > 0).all()  # sorted and distinct
    assert (a["vals"][:, :k] != 0).all()
    assert np.allclose((a["vals"][:, :k] ** 2).sum(axis=1), 1.0, atol=1e-5)
    assert abs(a["labels"].mean() - 0.5) < 0.02
    # popularity is a power law: the commonest term is in about two rows
    # of five, most terms in next to none
    share = np.bincount(cols.ravel(), minlength=wide["n_features"]) / len(cols)
    assert 0.35 < share.max() < 0.5 and np.median(share) < 1e-3


def test_the_control_is_not_correct(ref_and_points):
    """bfloat16 in place of the float32 the configuration states, judged as
    a run's answers are, at the cell's own limits: not correct, by the
    gradient it reports."""
    ref, points = ref_and_points
    sound = _answers(ref, points, "f64")
    correct, numbers, _ = fit.judge(ref, sound, LIMITS, CFG["max_iters"])
    assert numbers["value_gap"]["value"] == 0 == numbers["grad_gap"]["value"]
    control = _answers(ref, points, "bf16")
    correct, numbers, _ = fit.judge(ref, control, LIMITS, CFG["max_iters"])
    assert correct is False
    assert numbers["grad_gap"]["value"] > 3 * LIMITS["grad_gap"]


def test_control_and_faults_through_the_check(capsys):
    """``--control 1``: the control and every planted fault, put in the
    place of a run's own answers, pass through the same ``judge`` and each
    comes out not correct."""
    capsys.readouterr()
    assert harness.main(["--workload", "glm_lbfgs_fit", "--seed", "77",
                         "--seconds", "0.2", "--trace", "0", "--dry",
                         "--control", "1"]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    res = res["not_a_result"]
    assert res["correct"] is True
    assert set(res["control"]) == {"bf16", "half_batch", "state_unchanged",
                                   "answer_altered"}
    for name, reading in res["control"].items():
        assert reading["correct"] is False, name


def _dry(capsys, cell="glm_lbfgs_fit", seed="11"):
    capsys.readouterr()
    assert harness.main(["--workload", cell, "--seed", seed, "--seconds",
                         "0.2", "--trace", "0", "--dry"]) == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(out)["not_a_result"]


def test_a_sound_run_is_correct(capsys):
    res = _dry(capsys)
    assert res["correct"] is True


def test_fault_state_returned_unchanged(capsys, monkeypatch):
    from photon_ml_tpu.optim.problem import GlmOptimizationProblem

    real = GlmOptimizationProblem.solve_single_device

    def unchanged(self, data, reg_weight=0.0, w0=None, *a, **kw):
        res = real(self, data, reg_weight, w0, *a, **kw)
        start = np.zeros(data.n_features, np.float32) if w0 is None else w0
        value, grad = self.objective.value_and_grad(start, data, reg_weight)
        return res._replace(w=start + 0 * res.w, value=value, grad=grad,
                            iterations=res.iterations * 0)

    monkeypatch.setattr(GlmOptimizationProblem, "solve_single_device",
                        unchanged)
    res = _dry(capsys)
    assert res["correct"] is False
    bad = {k for k, v in res["compared"].items() if v["value"] > v["limit"]}
    assert bad == {"inv_descent"}
    assert res["compared"]["inv_descent"]["value"] >= 1e29


def test_fault_half_of_the_batch_left_out(capsys, monkeypatch):
    from photon_ml_tpu.data import dataset

    real = dataset.make_glm_data

    def half(features, labels, weights=None, **kw):
        weights = np.zeros(features.shape[0], np.float32)
        weights[::2] = 2.0  # the mean taken over the rest
        return real(features, labels, weights, **kw)

    monkeypatch.setattr(dataset, "make_glm_data", half)
    res = _dry(capsys)
    assert res["correct"] is False
    assert res["compared"]["grad_gap"]["value"] > 100 * LIMITS["grad_gap"]


def test_fault_answer_altered_where_it_is_produced(capsys, monkeypatch):
    from photon_ml_tpu.optim.problem import GlmOptimizationProblem

    real = GlmOptimizationProblem.solve_single_device

    def altered(self, *a, **kw):
        res = real(self, *a, **kw)
        return res._replace(w=res.w * 1.001)

    monkeypatch.setattr(GlmOptimizationProblem, "solve_single_device", altered)
    res = _dry(capsys)
    assert res["correct"] is False
    assert res["compared"]["grad_gap"]["value"] > LIMITS["grad_gap"]
