"""Window kind ``fit_click`` (cell ``glm_click_fit``): the metrics of its
dry line, that its ``judge`` can fail -- the bfloat16 control and the
planted faults, the cold band's entries left out among them, read not
correct -- and that it refuses a program without the wide layout, at a size
a test run can hold (2^16 rows, Pallas in interpret mode).  The readings at
the cell's own size, on the chip, are in PERF.md section 2.  Run by hand:
``pytest benchmarks/tests``."""

import json
import os
import sys

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402
from benchmarks.datagen import click_hashed  # noqa: E402

CFG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "glm_logistic_l2_lbfgs_criteo.json")))
SMALL = {**CFG, **CFG["dry"]}
CELL = "glm_click_fit"
MIN_GRIDS = json.load(open(os.path.join(
    ROOT, "benchmarks", "traffic", "lambda_sweep_click.json")))["min_grids"]
REGISTRY = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

# What the issue names for this cell (PR 39) ...
CLICK = {
    "click_data_ready_s", "click_layout_build_s", "click_layout_bytes_per_nnz",
    "click_slot_fill_pct", "click_tiles_stored_pct", "click_cold_entries_pct",
    "click_cold_share_pct", "click_kernel_roofline", "click_kernel_share_pct",
    "click_solve_mfu",
    # the accepted readers of the layers this cell runs (PR 39's review)
    "click_fn_evals_per_iter", "click_passes_per_solve", "click_grid_self_ms",
    "click_place_s", "click_tile_bwd_share_pct", "click_tile_kernel_roofline"}
# ... and the readers without a list, which read every cell.
EVERY_CELL = {m["name"] for m in REGISTRY["per_layer"] if "workloads" not in m}
# What a CPU rehearsal cannot read: the device's trace and its memory.
OF_THE_CHIP = {m["name"] for m in REGISTRY["per_layer"]
               if m["source"] == "device_trace"} | {"hbm_peak_gb"}


def _dry(capsys, *more, trace="0"):
    capsys.readouterr()
    assert harness.main(["--workload", CELL, "--seed", "2147483701",
                         "--seconds", "0.2", "--trace", trace, "--dry",
                         *more]) == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(out)["not_a_result"]


def test_the_registry_lists_what_the_issue_names():
    mine = {m["name"]: m for m in REGISTRY["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == CLICK
    # no accepted metric's list gained this cell
    assert not [m["name"] for m in REGISTRY["per_layer"]
                if CELL in m.get("workloads", []) and m["name"] not in CLICK]
    (cell,) = [w for w in REGISTRY["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm_logistic_l2_lbfgs_criteo", "lambda_sweep_click", 1)
    (config,) = [c for c in REGISTRY["configs"]
                 if c["name"] == cell["config"]]
    assert config["reduced"] == CFG["reduced"] == ["n_rows", "max_iters"]
    # every published width kept
    assert CFG["n_features"] == CFG["published"]["n_features"] == 1_000_000
    assert CFG["nnz_per_row"] == CFG["published"]["nnz_per_row"] == 39
    assert CFG["values"] == "unit" and CFG["intercept"] is True


def test_the_seed_flips_the_labels_and_keeps_the_matrix():
    small = {**SMALL, "n_rows": 4096}
    a, b = (click_hashed.generate(small, s) for s in (2, 3))
    assert np.array_equal(a["cols"], b["cols"])
    assert np.array_equal(a["vals"], b["vals"])
    flipped = not np.array_equal(a["labels"], b["labels"])
    assert flipped and np.array_equal(a["labels"], 1.0 - b["labels"])
    assert np.array_equal(a["w_true"], -b["w_true"])
    # binary semantics: a merged column is one entry of value 1
    assert set(np.unique(a["vals"])) <= {0.0, 1.0}
    assert a["nnz"] == int(np.count_nonzero(a["vals"][:, :-1]))
    assert click_hashed.as_csr(a).nnz == a["nnz"] + small["n_rows"]


def test_the_traced_dry_line_has_exactly_the_named_metrics(capsys):
    res = _dry(capsys, trace="1")
    assert set(res["metrics"]) == (CLICK | EVERY_CELL) - OF_THE_CHIP
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 4 * MIN_GRIDS
    assert res["layout"]["type"] == "WideSparseMatrix"
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert metrics["compiles_in_window"] == 0
    assert 0 < metrics["iters_per_solve"] <= 10
    assert metrics["click_fn_evals_per_iter"] >= 1
    assert metrics["click_place_s"] > 0 and metrics["click_grid_self_ms"] > 0
    assert 0 < metrics["click_slot_fill_pct"] <= 100
    assert 0 < metrics["click_tiles_stored_pct"] < 100
    assert 0 < metrics["click_cold_entries_pct"] < 100
    assert {"layout.wide_split", "layout.cold_orient", "layout.col_perm",
            "layout.orient"} <= set(res["layout_phases"])


def test_control_and_faults_through_the_check(capsys):
    res = _dry(capsys, "--control", "1")
    assert res["correct"] is True
    assert set(res["control"]) == {
        "bf16", "half_batch", "state_unchanged", "answer_altered",
        "cold_dropped"}
    for name, reading in res["control"].items():
        assert reading["correct"] is False, name


def test_a_program_without_the_wide_layout_is_refused_at_once(monkeypatch):
    from photon_ml_tpu.ops import sparse_pallas

    monkeypatch.delattr(sparse_pallas, "WideSparseMatrix")
    with pytest.raises(SystemExit) as refused:
        harness.main(["--workload", CELL, "--seed", "1", "--seconds", "0.2",
                      "--trace", "0", "--dry"])
    assert "has no WideSparseMatrix" in str(refused.value.code)
