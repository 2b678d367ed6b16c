"""Window kind ``fit_l1`` (cell ``glm_owlqn_fit``): the metrics of its dry
line, that its ``judge`` can fail -- the bfloat16 control and the three
planted faults read not correct -- and that it refuses a program whose
solves do not count, at a size a test run can hold (2^14 rows, Pallas in
interpret mode).  The readings at the cell's own size, on the chip, are in
PERF.md section 2.  Run by hand: ``pytest benchmarks/tests``."""

import json
import os
import sys
import types

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import reference_l1, run as harness  # noqa: E402
from benchmarks.datagen import glm_sparse  # noqa: E402
from benchmarks.windows import fit_l1  # noqa: E402

CFG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "glm_logistic_l1_owlqn_rcv1.json")))
SMALL = {**CFG, **CFG["dry"]}
LIMITS = SMALL["limits"]
CELL = "glm_owlqn_fit"
MIN_GRIDS = json.load(open(os.path.join(
    ROOT, "benchmarks", "traffic", "lambda_sweep_l1.json")))["min_grids"]
REGISTRY = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

# What the issue names for this cell (PR 37) ...
OWLQN = {
    "owlqn_fn_evals_per_iter", "owlqn_clamps_per_iter", "owlqn_nonzeros_pct",
    "owlqn_passes_per_solve", "owlqn_solve_mfu", "owlqn_tile_kernel_roofline",
    "owlqn_tile_kernel_share_pct", "owlqn_tile_bwd_share_pct",
    "owlqn_grid_self_ms", "owlqn_data_ready_s", "owlqn_layout_build_s",
    "owlqn_place_s", "owlqn_layout_bytes_per_nnz"}
# ... and the readers without a list, which read every cell.
EVERY_CELL = {
    "device_idle_pct", "hbm_peak_gb", "iters_per_solve", "compile_s",
    "compiles_in_window", "process_start_s", "compile_trace_lower_s",
    "compile_backend_miss_s", "compile_unstored_s", "compile_cache_load_s",
    "compile_top_program_s", "place_gb_per_s", "place_dispatch_share_pct",
    "layout_col_perm_s", "layout_orient_s", "setup_unspanned_s"}
# What a CPU rehearsal cannot read: the device's trace and its memory.
OF_THE_CHIP = {
    "owlqn_passes_per_solve", "owlqn_solve_mfu", "owlqn_tile_kernel_roofline",
    "owlqn_tile_kernel_share_pct", "owlqn_tile_bwd_share_pct",
    "device_idle_pct", "hbm_peak_gb"}
EXACT = {"unconverged", "one_step_endings", "nnz_miscounted"}


def _over(numbers):
    return {k for k, n in numbers.items() if n["value"] > n["limit"]}


def _dry(capsys, *more, trace="0"):
    capsys.readouterr()
    assert harness.main(["--workload", CELL, "--seed", "77", "--seconds",
                         "0.2", "--trace", trace, "--dry", *more]) == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(out)["not_a_result"]


def test_the_registry_lists_what_the_issue_names():
    mine = {m["name"]: m for m in REGISTRY["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == OWLQN
    assert all(m["moves"] in ("solve_s", "setup_s") for m in mine.values())
    # no accepted metric's list gained this cell
    assert not [m["name"] for m in REGISTRY["per_layer"]
                if CELL in m.get("workloads", []) and m["name"] not in OWLQN]
    assert {m["name"] for m in REGISTRY["per_layer"]
            if "workloads" not in m} == EVERY_CELL
    (cell,) = [w for w in REGISTRY["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm_logistic_l1_owlqn_rcv1", "lambda_sweep_l1", 1)


def test_the_traced_dry_line_has_exactly_the_named_metrics(capsys):
    res = _dry(capsys, trace="1")
    assert set(res["metrics"]) == (OWLQN | EVERY_CELL) - OF_THE_CHIP
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 4 * MIN_GRIDS
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert metrics["compiles_in_window"] == 0
    per_solve = res["check"]["per_solve"]
    iters = sum(r["iterations"] for r in per_solve)
    evals = sum(r["fn_evals"] for r in per_solve)
    # the counters' readers against the window's own answers (every grid
    # of a window is the same work)
    assert metrics["iters_per_solve"] == pytest.approx(iters / 4)
    assert metrics["owlqn_fn_evals_per_iter"] == pytest.approx(
        (evals - 4) / iters)
    assert metrics["owlqn_clamps_per_iter"] == pytest.approx(
        sum(r["orthant_clamps"] for r in per_solve) / iters)
    assert metrics["owlqn_nonzeros_pct"] == pytest.approx(
        100 * sum(r["nonzeros"] for r in per_solve) / 4
        / SMALL["n_features"])
    assert list(res["compared"]) == [
        "value_gap", "pgrad_gap", "inv_descent", "path_iters_gap",
        "path_evals_gap", "path_value_gap", "nnz_gap", "unconverged",
        "one_step_endings", "nnz_miscounted"]


def test_control_and_faults_through_the_check(capsys):
    res = _dry(capsys, "--control", "1")
    assert res["correct"] is True
    assert set(res["control"]) == {
        "bf16", "no_one_sided_rule", "not_projected", "intercept_penalised"}
    for name, reading in res["control"].items():
        assert reading["correct"] is False, name
    over = {name: _over(r["numbers"]) for name, r in res["control"].items()}
    # the control: one precision down shows in the pseudo-gradient
    assert res["control"]["bf16"]["numbers"]["pgrad_gap"]["value"] > (
        10 * LIMITS["pgrad_gap"])
    # the support never grows from zero: nothing is selected, nothing descends
    assert {"nnz_gap", "inv_descent"} <= over["no_one_sided_rule"]
    assert res["control"]["no_one_sided_rule"]["numbers"]["nnz_gap"][
        "value"] == 1.0
    # unprojected trial points: another support
    assert "nnz_gap" in over["not_projected"]
    # a penalised intercept: another value and another pseudo-gradient
    assert {"value_gap", "pgrad_gap"} <= over["intercept_penalised"]
    for row in res["check"]["per_solve"]:  # the stop's record
        assert row["stopped_by"] in ("pgrad", "improvement")
        assert row["fn_evals"] > row["iterations"]
        assert row["nonzeros"] == row["nonzeros_host"]
        assert row["value_ulp_margin"] >= 10


@pytest.fixture(scope="module")
def reference_in_its_own_place():
    """The reference in the program's place: its own warm-started chain down
    the grid as the timed solves."""
    host = glm_sparse.generate(SMALL, 123)
    ref = reference_l1.GlmL1Reference(
        host["cols"], host["vals"], host["labels"], host["n_features"])
    objective = reference_l1.L1Objective(
        ref, fit_l1.host_mask(host["n_features"]))
    blank = types.SimpleNamespace(
        w=np.zeros(1), value=0.0, grad=np.zeros(1), iterations=0,
        converged=False, values=[], grad_norms=[],
        **dict.fromkeys(fit_l1.COUNTS, 0))
    answers, start = [], np.zeros(host["n_features"] + 1)
    for lam in CFG["reg_weights"]:
        path = reference_l1.owlqn(
            objective, lam, start, max_iters=CFG["max_iters"],
            tolerance=CFG["tolerance"], history=CFG["history"])
        answers.append(fit_l1.Solve(0, lam, blank, 0.0).with_path(path))
        start = path["w"]
    starts = fit_l1.starts_of(answers)
    return objective, answers, starts, fit_l1.paths(
        objective, answers, starts, SMALL)


def test_the_reference_in_its_own_place_is_correct(
        reference_in_its_own_place):
    objective, sound, starts, want = reference_in_its_own_place
    correct, numbers, _ = fit_l1.judge(objective, sound, starts, SMALL, want)
    assert correct is True
    for name in EXACT | {"value_gap", "pgrad_gap", "path_iters_gap",
                         "path_evals_gap", "path_value_gap", "nnz_gap"}:
        assert numbers[name]["value"] == 0, name


@pytest.mark.parametrize("plant, fails", [
    (lambda s: setattr(s, "converged", False), "unconverged"),
    (lambda s: setattr(s, "stalled", True), "unconverged"),
    (lambda s: setattr(s, "nonzeros", s.nonzeros + 1), "nnz_miscounted"),
])
def test_the_exact_counts_can_fail(reference_in_its_own_place, plant, fails):
    objective, sound, starts, want = reference_in_its_own_place
    wrong = [s.with_answer() for s in sound]
    plant(wrong[2])
    correct, numbers, _ = fit_l1.judge(objective, wrong, starts, SMALL, want)
    assert correct is False and _over(numbers) == {fails}


def test_a_one_step_ending_is_not_correct(reference_in_its_own_place):
    """The rules before PR 37 in the program's place: where they end a solve
    after one iteration with its pseudo-gradient test unmet, the exact count
    says so; and a solve that returns its start has made no descent."""
    objective, sound, starts, want = reference_in_its_own_place
    old = [s.with_path(p) for s, p in zip(sound, fit_l1.paths(
        objective, sound, starts, SMALL, rel_test_from_pairs=0))]
    ended_at_once = [s for s in old if s.iterations == 1
                     and s.pg_norms[1] > CFG["tolerance"] * s.pg_norms[0]]
    _correct, numbers, _ = fit_l1.judge(objective, old, starts, SMALL, want)
    assert numbers["one_step_endings"]["value"] == len(ended_at_once)
    stuck = [s.with_answer(w=start) for s, start in zip(sound, starts)]
    correct, numbers, _ = fit_l1.judge(objective, stuck, starts, SMALL, want)
    assert correct is False and numbers["inv_descent"]["value"] >= 1e29


def test_a_program_that_does_not_count_is_refused_at_once(monkeypatch):
    from photon_ml_tpu.optim.lbfgs import SolveResult

    monkeypatch.setattr(SolveResult, "_fields", tuple(
        f for f in SolveResult._fields
        if f not in ("orthant_clamps", "nonzeros")))
    with pytest.raises(SystemExit) as refused:
        harness.main(["--workload", CELL, "--seed", "1", "--seconds", "0.2",
                      "--trace", "0", "--dry"])
    assert "does not count" in str(refused.value.code)
    assert "orthant_clamps" in str(refused.value.code)
