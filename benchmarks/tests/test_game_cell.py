"""The cell ``game_cd_fit`` rehearsed on the CPU at its ``dry`` sizes: the
line's shape, the readers that need no chip, the control and every planted
fault read as not correct, and a program without the counts refused at
once.  Run by hand: ``pytest benchmarks/tests``."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
REGISTRY = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "game_cd_fit"
#: Readers that find something without a chip: the program's spans and
#: counters, the harness's clocks.
OFF_CHIP = {"game_group_s", "game_place_s", "game_data_ready_s",
            "re_padding_pct", "re_buckets", "re_iters_mean",
            "fe_layout_build_s", "fe_place_s", "fe_layout_bytes_per_nnz",
            "fe_fn_evals_per_iter", "iters_per_solve", "compile_s",
            "compiles_in_window", "process_start_s"}


def _run(*args):
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, *args], cwd=ROOT,
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])["not_a_result"]


@pytest.fixture(scope="module")
def traced():
    return _run("--seed", "2147483700", "--seconds", "1", "--trace", "1",
                "--dry", "--control", "1")


def test_the_cell_reports_what_the_registry_asks(traced):
    mine = {m["name"] for m in REGISTRY["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert OFF_CHIP <= mine
    assert set(traced["metrics"]) == OFF_CHIP
    assert traced["correct"] is True and traced["failed"] == 0
    assert traced["attempted"] % 4 == 0 and traced["attempted"] >= 4
    assert traced["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < traced["metrics"]["re_padding_pct"]["value"] < 100
    assert 1 <= traced["metrics"]["re_iters_mean"]["value"] <= 30
    for pair in traced["compared"].values():
        assert pair["value"] <= pair["limit"]


def test_every_layer_the_cell_runs_has_a_metric_on_it():
    layers = {m["layer"] for m in REGISTRY["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert layers >= {
        "entry and process", "compile cache", "device",
        "layout build and placement", "GAME data: grouping and placement",
        "coordinate descent", "GAME coordinates", "solvers", "whole step",
        "kernels"}
    by_name = {m["name"]: m for m in REGISTRY["per_layer"]}
    # the text cell's whole-step share cannot read this window
    assert by_name["solve_mfu"]["workloads"] == ["glm_lbfgs_fit"]


def test_solver_readers_read_the_fixed_effects_solves(traced):
    assert traced["metrics"]["iters_per_solve"]["value"] == 10
    assert traced["metrics"]["fe_fn_evals_per_iter"]["value"] >= 1
    assert traced["metrics"]["fe_layout_bytes_per_nnz"]["value"] > 0
    assert (traced["metrics"]["fe_layout_build_s"]["value"]
            < traced["metrics"]["game_data_ready_s"]["value"])


def test_the_control_and_every_fault_read_not_correct(traced):
    control = traced["control"]
    assert set(control) == {
        "bf16", "half_batch", "padding_rows_counted", "user_block_dropped",
        "offsets_not_refreshed", "state_unchanged"}
    for name, got in control.items():
        assert got["correct"] is False, name


def test_every_update_of_the_checked_fit_is_listed(traced):
    rows = traced["check"]["per_update"]
    assert [r["coordinate"] for r in rows] == [
        "fixed", "per_user", "fixed", "per_user"]
    assert all(r["inv_descent"] > 0 for r in rows)


def test_end_to_end_line_untraced():
    res = _run("--seed", "11", "--seconds", "1", "--trace", "0", "--dry")
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
    assert res["correct"] is True
