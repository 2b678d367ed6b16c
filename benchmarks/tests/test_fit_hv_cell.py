"""Window kind ``fit_hv`` (cell ``glm_tron_fit``): that its ``judge`` can
fail -- the bfloat16 control of the Hessian-vector product and the two
planted Hv faults read not correct at the cell's own limits -- and that it
refuses a program whose solves do not count, at a size a test run can hold
(2^14 rows, Pallas in interpret mode).  The readings at the cell's own
size, on the chip, are in PERF.md section 2.  Run by hand:
``pytest benchmarks/tests``."""

import json
import os
import sys
import types

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import reference_hv, run as harness  # noqa: E402
from benchmarks.datagen import glm_sparse  # noqa: E402
from benchmarks.windows import fit_hv  # noqa: E402

CFG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "glm_logistic_l2_tron_rcv1.json")))
LIMITS = CFG["limits"]
SMALL = {**CFG, **CFG["dry"]}
CELL = "glm_tron_fit"


PATHS = {"path_iters_gap", "path_cg_gap", "path_value_gap"}


@pytest.fixture(scope="module")
def ref_answers_direction():
    """The reference in the program's place: its own warm-started chain down
    the grid as the timed solves, its own product at each answer."""
    host = glm_sparse.generate(SMALL, 123)
    ref = reference_hv.GlmHvReference(
        host["cols"], host["vals"], host["labels"], host["n_features"])
    v = fit_hv.direction(host["w_true"], SMALL["data_seed"])
    answers, start = [], np.zeros(host["n_features"] + 1)
    for lam in CFG["reg_weights"]:
        path = reference_hv.tron(
            ref, lam, start, max_iters=CFG["max_iters"],
            tolerance=CFG["tolerance"])
        start = path["w"]
        res = types.SimpleNamespace(
            w=path["w"], value=path["value"], grad=path["grad"],
            iterations=0, converged=False, values=[], grad_norms=[],
            **dict.fromkeys(fit_hv.COUNTS, 0))
        answers.append(fit_hv.Solve(0, lam, res, 0.0).with_hv(
            ref.hvp(path["w"], v, lam), path))
    return ref, answers, v, fit_hv.paths(ref, answers, CFG)


def _over(numbers):
    return {k for k, n in numbers.items() if n["value"] > n["limit"]}


def test_the_direction_is_mirrored_with_the_corpus():
    a, b = (glm_sparse.generate(SMALL, s) for s in (123, 2 ** 31 + 5))
    va, vb = (fit_hv.direction(h["w_true"], SMALL["data_seed"])
              for h in (a, b))
    assert np.linalg.norm(va) == pytest.approx(1.0)
    flipped = a["w_true"] != b["w_true"]
    assert np.array_equal(va[flipped], -vb[flipped])
    assert np.array_equal(va[~flipped], vb[~flipped])
    # so H v mirrors too, and its norm reads the same on every seed
    refs = [reference_hv.GlmHvReference(h["cols"], h["vals"], h["labels"],
                                        h["n_features"]) for h in (a, b)]
    ha = refs[0].hvp(0.5 * a["w_true"], va, 1.0)
    hb = refs[1].hvp(0.5 * b["w_true"], vb, 1.0)
    np.testing.assert_allclose(np.abs(ha), np.abs(hb), rtol=1e-12)


def test_the_reference_in_its_own_place_is_correct(ref_answers_direction):
    ref, sound, v, want = ref_answers_direction
    correct, numbers, _ = fit_hv.judge(ref, sound, CFG, v, want)
    assert correct is True
    for name in PATHS | {"hv_gap", "unconverged", "cg_over_cap"}:
        assert numbers[name]["value"] == 0, name
    assert list(numbers)[-6:] == [
        "path_iters_gap", "path_cg_gap", "path_value_gap", "hv_gap",
        "unconverged", "cg_over_cap"]


FAULTS = {
    "bf16": dict(precision="bf16"),
    "hv_no_ridge": dict(ridge=False),
    "hv_stale_curvature": dict(stale=True),
}


@pytest.mark.parametrize("name", FAULTS)
def test_hv_control_and_faults_are_not_correct(ref_answers_direction, name):
    """Each in the place of the CG's product, value and gradient sound: not
    correct by the path the solves took alone (what the timed window
    returned), and by the product at the answers alone."""
    ref, sound, v, want = ref_answers_direction
    fault = fit_hv.HvFault(ref, **FAULTS[name])
    wrong_paths = fit_hv.paths(fault, sound, CFG)
    by_path = [s.with_hv(s.hv, path) for s, path in zip(sound, wrong_paths)]
    correct, numbers, _ = fit_hv.judge(ref, by_path, CFG, v, want)
    if name == "bf16":
        # The limits are the cell's: at 2^14 rows the float64 CG on rounded
        # products makes half as many steps again as on exact ones, which is
        # the limit; at the cell's size 120% more, and an outer iteration
        # more (PERF.md section 2).
        # A CG that rounds its own output is caught at this size too:
        # test_a_fault_in_the_programs_own_cg_is_not_correct below.
        assert 0.4 < numbers["path_cg_gap"]["value"] <= 0.5
    else:
        assert correct is False
        assert _over(numbers) - {"unconverged"} <= PATHS
        assert {"path_iters_gap", "path_cg_gap", "path_value_gap"} == (
            _over(numbers) & PATHS)
    by_product = [s.with_hv(fault.hvp(s.w, v, s.lam)) for s in sound]
    correct, numbers, _ = fit_hv.judge(ref, by_product, CFG, v, want)
    assert correct is False and _over(numbers) == {"hv_gap"}
    assert numbers["hv_gap"]["value"] > 5 * LIMITS["hv_gap"]


def test_the_exact_counts_can_fail(ref_answers_direction):
    ref, sound, v, want = ref_answers_direction
    sound = [s.with_hv(s.hv) for s in sound]
    sound[2].converged = False
    sound[1].cg_iterations = CFG["max_cg_iters"] * sound[1].iterations + 1
    correct, numbers, _ = fit_hv.judge(ref, sound, CFG, v, want)
    assert correct is False
    assert _over(numbers) == {"unconverged", "cg_over_cap", "path_cg_gap"}
    assert numbers["unconverged"]["value"] == 1
    assert numbers["cg_over_cap"]["value"] == 1


def _dry(capsys, *more):
    capsys.readouterr()
    assert harness.main(["--workload", CELL, "--seed", "77", "--seconds",
                         "0.2", "--trace", "0", "--dry", *more]) == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(out)["not_a_result"]


def test_control_and_faults_through_the_check(capsys):
    res = _dry(capsys, "--control", "1")
    assert res["correct"] is True
    assert res["attempted"] == 4 * CFG_MIN_GRIDS
    assert set(res["control"]) == {
        "bf16", "half_batch", "state_unchanged", "answer_altered",
        "hv_no_ridge", "hv_stale_curvature"}
    for name, reading in res["control"].items():
        assert reading["correct"] is False, name
    control = res["control"]["bf16"]["numbers"]
    assert control["hv_gap"]["value"] > 5 * LIMITS["hv_gap"]
    assert control["grad_gap"]["value"] > 10 * LIMITS["grad_gap"]
    for name in ("hv_no_ridge", "hv_stale_curvature"):
        assert _over(res["control"][name]["numbers"]) >= PATHS, name
    for row in res["check"]["per_solve"]:  # the stop's record
        assert row["stopped_by"] in ("gradient", "improvement")
        assert row["cg_iterations"] >= row["iterations"]
        assert row["reference"]["iterations"] == row["iterations"]
        assert row["fn_evals"] == row["iterations"] + 1


CFG_MIN_GRIDS = json.load(open(os.path.join(
    ROOT, "benchmarks", "traffic", "lambda_sweep_hv.json")))["min_grids"]


def _no_ridge(objective):
    real = objective.hvp
    return "hvp", lambda self, w, v, data, l2_weight=0.0, **kw: real(
        self, w, v, data, l2_weight=0.0 * l2_weight, **kw)


def _stale_curvature(objective):
    real = objective.d2_weights
    return "d2_weights", lambda self, w, data, **kw: real(
        self, 0.0 * w, data, **kw)


def _one_precision_down(objective):
    import jax.numpy as jnp

    real = objective.hvp

    def down(x):
        return x.astype(jnp.bfloat16).astype(x.dtype)

    return "hvp", lambda self, w, v, data, l2_weight=0.0, d2w=None, **kw: (
        down(real(self, w, down(v), data, l2_weight=l2_weight,
                  d2w=None if d2w is None else down(d2w), **kw)))


@pytest.mark.parametrize("plant", [_no_ridge, _stale_curvature,
                                   _one_precision_down])
def test_a_fault_in_the_programs_own_cg_is_not_correct(capsys, monkeypatch,
                                                       plant):
    """The fault planted in the program, so in the CG of every timed solve:
    not correct by what the window returned (the paths), whatever the one
    product after the window reads."""
    from photon_ml_tpu.optim.objective import GlmObjective

    monkeypatch.setattr(GlmObjective, *plant(GlmObjective))
    res = _dry(capsys)
    assert res["correct"] is False
    assert _over(res["compared"]) & PATHS


def test_a_program_that_does_not_count_is_refused_at_once(monkeypatch):
    from photon_ml_tpu.optim.lbfgs import SolveResult

    monkeypatch.setattr(SolveResult, "_fields", tuple(
        f for f in SolveResult._fields if f not in fit_hv.COUNTS[1:]))
    with pytest.raises(SystemExit) as refused:
        harness.main(["--workload", CELL, "--seed", "1", "--seconds", "0.2",
                      "--trace", "0", "--dry"])
    assert "does not count" in str(refused.value.code)
