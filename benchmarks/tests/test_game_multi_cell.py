"""The cell ``game_cd_fit_user_item`` rehearsed on the CPU at its ``dry``
sizes (70,000 rows, a cap of 64 rows a movie, so that passive rows exist):
the line's shape, the readers that need no chip, the control and every
planted fault read as not correct, and a program that stores passive rows
padded refused at once.  Run by hand: ``pytest benchmarks/tests``."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
REGISTRY = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "game_cd_fit_user_item"
#: Readers that find something without a chip: the program's spans and
#: counters, the harness's clocks.
OFF_CHIP = {"mre_data_ready_s", "mre_user_group_s", "mre_item_group_s",
            "mre_place_s", "mre_fe_layout_build_s", "mre_fe_place_s",
            "mre_item_iters_mean", "mre_item_padding_pct",
            "mre_item_buckets", "mre_passive_rows_pct", "iters_per_solve",
            "compile_s", "compiles_in_window", "process_start_s"}
FAULTS = {"bf16", "half_batch", "padding_rows_counted", "user_block_dropped",
          "movie_block_dropped", "movie_passive_rows_unscored",
          "movie_cap_ignored", "offsets_from_one_coordinate",
          "state_unchanged"}


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
    assert {m for m in mine if m.startswith("mre_")} == {
        m["name"] for m in REGISTRY["per_layer"]
        if m.get("workloads") == [CELL]}
    assert set(traced["metrics"]) == OFF_CHIP
    assert traced["correct"] is True and traced["failed"] == 0
    # whole fits of six updates, never fewer than two
    assert traced["attempted"] % 6 == 0 and traced["attempted"] >= 12
    assert traced["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 <= traced["metrics"]["mre_item_padding_pct"]["value"] < 100
    assert 1 <= traced["metrics"]["mre_item_iters_mean"]["value"] <= 30
    for pair in traced["compared"].values():
        assert pair["value"] <= pair["limit"]


def test_passive_rows_are_the_generators_own_count(traced):
    movie = traced["random_effects"]["per_movie"]
    assert movie["rows_passive"] == movie["generator"]["rows_passive"] > 0
    assert traced["random_effects"]["per_user"]["rows_passive"] == 0
    assert traced["metrics"]["mre_passive_rows_pct"]["value"] == (
        pytest.approx(100.0 * movie["rows_passive"] / 70000))
    # stored flat: what the passive rows take is about what they are
    real = 4 * movie["rows_passive"] * (9 + 2)
    assert real <= movie["passive_bytes"] <= 1.3 * real


def test_every_layer_the_cell_runs_has_a_metric_on_it():
    layers = {m["layer"] for m in REGISTRY["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert layers >= {
        "entry and process", "compile cache", "device",
        "layout build and placement", "GAME data: grouping and placement",
        "coordinate descent", "GAME coordinates", "solvers", "whole step",
        "kernels"}


def test_the_control_and_every_fault_read_not_correct(traced):
    control = traced["control"]
    assert set(control) == FAULTS
    for name, got in control.items():
        assert got["correct"] is False, name


def test_every_update_of_the_checked_fit_is_listed(traced):
    rows = traced["check"]["per_update"]
    assert [r["coordinate"] for r in rows] == [
        "fixed", "per_user", "per_movie"] * 2
    assert all(r["inv_descent"] > 0 for r in rows)
    assert len(traced["fit_ends_s"]) >= 2


def test_a_program_that_pads_passive_rows_is_refused_at_once(tmp_path):
    """The parent's program under this benchmark: no ``PassiveRows`` in
    ``game.data``.  One line, a non-zero code, no data made."""
    code = (
        "import sys; sys.argv = ['run.py', '--workload', %r, '--seed', '1',"
        " '--seconds', '1', '--dry']\n"
        "import runpy\n"
        "from photon_ml_tpu.game import data\n"
        "del data.PassiveRows\n"
        "runpy.run_path(%r, run_name='__main__')\n" % (CELL, RUN))
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    assert proc.returncode not in (0, 2)
    assert "No result" in proc.stderr and proc.stdout.strip() == ""
    assert "datagen" not in proc.stderr
