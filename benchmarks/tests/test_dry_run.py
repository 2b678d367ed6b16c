"""``run.py --dry`` end to end on the CPU (2^14 rows, Pallas in interpret
mode) for every cell, the refusal of a CPU backend without ``--dry``, and a
throw-away cell that is nothing but new files.  Run by hand:
``pytest benchmarks/tests``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
REGISTRY = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in REGISTRY["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(*args, env=None):
    proc = subprocess.run(
        [sys.executable, RUN, *args], cwd=ROOT, capture_output=True, text=True,
        timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})
    return proc


def _last(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _reported(cell, group, line):
    """Names of ``group`` that BENCHMARK.json makes this cell report."""
    e2e = {m["name"] for m in REGISTRY["end_to_end"]
           if cell in m.get("workloads", [cell])}
    if group == "end_to_end":
        return e2e
    return {m["name"] for m in REGISTRY["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in e2e}


@pytest.mark.parametrize("cell", CELLS)
def test_dry_run_end_to_end(cell):
    proc = _run("--workload", cell, "--seed", "2147483700", "--seconds", "1",
                "--trace", "0", "--dry")
    line = _last(proc)
    # Another shape than a result's: no check can take it for one.
    assert set(line) == {"dry_run", "not_a_result"} and line["dry_run"] is True
    res = line["not_a_result"]
    assert RESULT_KEYS <= set(res)
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 4 and res["attempted"] % 4 == 0
    assert set(res["metrics"]) == _reported(cell, "end_to_end", res)
    for name, m in res["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] >= 0
    assert res["device"]["platform"] == "cpu"
    for name, pair in res["compared"].items():
        assert pair["value"] <= pair["limit"], name
    # every number compared is printed beside its limit at the end of stderr
    tail = proc.stderr.strip().splitlines()[-2:]
    assert tail[0].startswith("compared") and tail[1] == "correct=True"
    for name in res["compared"]:
        assert name + "=" in tail[0]


@pytest.mark.parametrize("cell", CELLS)
def test_dry_run_traced(cell):
    res = _last(_run("--workload", cell, "--seed", "5", "--seconds", "1",
                     "--trace", "1", "--dry"))["not_a_result"]
    wanted = _reported(cell, "per_layer", res)
    of_the_chip = {m["name"] for m in REGISTRY["per_layer"]
                   if m["source"] == "device_trace"}
    of_the_chip |= {"solve_mfu", "hbm_peak_gb"}
    # A CPU trace has no device plane and a CPU no published peak: those
    # readers find nothing and return nothing; every other reader reports.
    assert set(res["metrics"]) == wanted - of_the_chip
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < res["metrics"]["iters_per_solve"]["value"] <= 10
    assert "busy_s" not in res["device"]


def test_without_dry_a_cpu_backend_is_refused():
    proc = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                "--trace", "0")
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "No result" in proc.stderr


def test_a_new_cell_is_new_files_only(tmp_path):
    """A third configuration, a traffic mix, a window kind and a per-layer
    metric, each a file of its own in another tree; no file of the harness
    is edited."""
    bench = tmp_path / "toybench"
    for sub in ("configs", "traffic", "windows", "metrics"):
        (bench / sub).mkdir(parents=True)
    cfg = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "glm_logistic_l2_lbfgs_rcv1.json")))
    cfg.update(name="toy_glm", max_iters=2, n_rows=8192,
               reg_weights=[10.0, 1.0], dry={})
    (bench / "configs" / "toy_glm.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "toy_sweep.json").write_text(json.dumps(
        {"name": "toy_sweep", "window": "toy_fit"}))
    # a window kind of its own: the stock one, with a counter added
    shutil.copy(os.path.join(ROOT, "benchmarks", "windows", "fit.py"),
                bench / "windows" / "toy_fit.py")
    with open(bench / "windows" / "toy_fit.py", "a") as f:
        f.write("\n\n_stock_window = window\n\n\ndef window(run, seconds):\n"
                "    win = _stock_window(run, seconds)\n"
                "    run.info['toy_grids'] = win['grids']\n    return win\n")
    (bench / "metrics" / "toy_grids.py").write_text(
        "def read(run):\n    return float(run.info['toy_grids'])\n")
    registry = {
        "command": ["python3", "benchmarks/run.py"], "paths": ["toybench"],
        "run_seconds": 1,
        "configs": [{"name": "toy_glm", "source": "test", "reduced": [],
                     "file": "toybench/configs/toy_glm.json", "why": "test"}],
        "workloads": [{"name": "toy.sweep", "config": "toy_glm",
                       "traffic": "toy_sweep", "chips": 1, "why": "test"}],
        "end_to_end": [m for m in REGISTRY["end_to_end"]
                       if m["name"] in ("solve_s", "setup_s")],
        "per_layer": [
            {"name": "toy_grids", "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "toy", "moves": "solve_s",
             "workloads": ["toy.sweep"]},
            {"name": "iters_per_solve", "unit": "iters", "better": "lower",
             "source": "program_counter", "layer": "solvers",
             "moves": "solve_s"}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(registry))
    args = ["--workload", "toy.sweep", "--seed", "9", "--seconds", "0.5",
            "--dry", "--registry", str(tmp_path / "BENCHMARK.json")]
    res = _last(_run(*args, "--trace", "0"))["not_a_result"]
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
    assert res["attempted"] % 2 == 0  # its own two-lambda grid
    res = _last(_run(*args, "--trace", "1"))["not_a_result"]
    assert res["metrics"]["toy_grids"]["value"] >= 1
    assert res["metrics"]["iters_per_solve"]["value"] <= 2
