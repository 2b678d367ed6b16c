#!/usr/bin/env python3
"""The quickest proof that the train -> serve path still starts on the chip.

A bring-up check, not a benchmark: it times nothing that a claim may rest
on (that is ``python3 benchmarks/run.py``, ``BENCHMARK.json`` and the
ledger: docs/performance.md "Measurement").

    python chip_smoke.py                  # one TPU v5e host, full width
    python chip_smoke.py --cpu-dry-run    # tiny sizes, CPU, interpret mode

Drives the system's main path once through the entry points a user calls,
at the width the repo's headline configurations have (sparse logistic GLM,
2^20 rows x 8,192 features x 32 nnz/row; MovieLens-shaped GAME, 512-feature
fixed effect + dim-8 per-user random effect over 100,000 zipf-tailed
entities), with data planted from a seed:

1. ``glm_driver``  — L2 logistic, 2-point lambda grid, train + validate;
2. ``game_training_driver`` (2 coordinate-descent iterations), then
   ``game_scoring_driver`` on a held-out file;
3. ``python -m photon_ml_tpu.serving`` on the model step 2 saved: POST
   /score (incl. an unknown entity) and compare with the batch scores,
   read /healthz and /stats, stop it with SIGINT;
4. two JSON lines on stdout.  First the report: mode, versions, the
   compile cache (directory, entries before/after), and per phase the wall
   and compile seconds, the feature layout and per-device bytes in use.
   Then, as the LAST line, the verdict and nothing else:
   ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
   with the device as JAX reports it.

One process per chip: this parent NEVER imports JAX.  Every entry point is
a child process run strictly after the previous one has exited; the
children that only generate data or send requests run with
``JAX_PLATFORMS=cpu`` and cannot take the chip.  Any failed check is a
non-zero exit with the reason on stderr and no JSON on stdout.

The run refuses to start unless JAX's default backend is a TPU v5e, and
when ``PHOTON_PALLAS_INTERPRET`` is set or ``JAX_PLATFORMS`` puts the CPU
first.  ``--cpu-dry-run`` is the explicit exception — the same code path
at tiny sizes on the CPU with the Pallas kernels interpreted, for the
sandbox and the tier-1 tests — and says so in its output; it is never
inferred from the absence of a chip.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

#: the driver allows 1200 s; leave room to stop children and report
DEADLINE_S = 1140.0

#: make_glm_data picks the Pallas layout only at >= 65,536 rows AND
#: >= 2^20 nnz (data/dataset.py), so even the dry run crosses both — a
#: smaller one would train on the XLA COO path and never build a kernel.
SIZES = {
    "chip": dict(
        glm_rows=1 << 20, glm_features=8192, glm_nnz=32, glm_val_rows=1 << 16,
        game_entities=100_000, game_min_rows=0, game_row_cap=128,
        game_fixed_features=512, game_fixed_nnz=8, game_re_dim=8,
        heldout_rows=20_000, max_iters=8,
    ),
    "cpu-dry-run": dict(
        glm_rows=1 << 16, glm_features=1024, glm_nnz=16, glm_val_rows=4096,
        game_entities=2_000, game_min_rows=1 << 16, game_row_cap=128,
        game_fixed_features=256, game_fixed_nnz=16, game_re_dim=8,
        heldout_rows=2_000, max_iters=4,
    ),
}
N_REQUESTS = 24  # /score rows compared with the batch scorer
N_UNKNOWN = 4  # held-out rows (and requests) whose entity was never trained
SCORE_TOL = 5e-7  # online vs batch (the verify skill's tolerance)


class SmokeFailure(Exception):
    """A check that did not hold; the message goes to stderr."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


# ---------------------------------------------------------------------------
# Children that never need the chip (run with JAX_PLATFORMS=cpu)
# ---------------------------------------------------------------------------


def child_datagen(work: str, mode: str) -> None:
    """Write every input file from a seed: LIBSVM train/validate for the
    GLM, GAME Avro train/held-out + the coordinate config, and the /score
    request rows (the first held-out records, verbatim)."""
    import numpy as np

    from photon_ml_tpu.data.game_reader import write_game_avro

    sz = SIZES[mode]
    rng = np.random.default_rng(20260926)

    # -- GLM: valued entries, planted sparse model -----------------------
    d, k = sz["glm_features"], sz["glm_nnz"]
    w_true = (
        rng.normal(size=d) * (rng.uniform(size=d) < 0.2)
    ).astype(np.float32)

    def write_libsvm(path: str, n: int) -> None:
        with open(path, "w") as f:
            for lo in range(0, n, 8192):
                m = min(8192, n - lo)
                cols = rng.integers(0, d, size=(m, k))
                vals = rng.normal(size=(m, k)).astype(np.float32)
                margin = np.einsum("ij,ij->i", vals, w_true[cols])
                y = rng.uniform(size=m) < 1.0 / (1.0 + np.exp(-margin))
                f.write("".join(
                    f"{int(yi)} "
                    + " ".join(f"{c + 1}:{v:.6g}" for c, v in zip(cr, vr))
                    + "\n"
                    for yi, cr, vr in zip(
                        y.tolist(), cols.tolist(), vals.tolist()
                    )
                ))

    write_libsvm(os.path.join(work, "glm_train.libsvm"), sz["glm_rows"])
    write_libsvm(os.path.join(work, "glm_val.libsvm"), sz["glm_val_rows"])

    # -- GAME: binary fixed-effect features (the unit-value layout) + a
    # dense per-user random effect, zipf-tailed rows per user --------------
    n_ent, fd, fk = (
        sz["game_entities"], sz["game_fixed_features"], sz["game_fixed_nnz"]
    )
    rd = sz["game_re_dim"]
    sizes = np.minimum(rng.zipf(1.8, n_ent), sz["game_row_cap"])
    deficit = max(0, sz["game_min_rows"] - int(sizes.sum()))
    sizes = sizes + rng.multinomial(deficit, np.full(n_ent, 1.0 / n_ent))
    w_fixed = (0.5 * rng.normal(size=fd)).astype(np.float32)
    u_user = (0.3 * rng.normal(size=(n_ent, rd))).astype(np.float32)

    def records(user_idx: np.ndarray, uid_prefix: str):
        n = len(user_idx)
        # Stratified columns: fk DISTINCT features per row, so every tiled
        # value is exactly 1.0 (a repeated column would sum to 2.0 and
        # drop the matrix back to the valued layout).
        stride = fd // fk
        for lo in range(0, n, 4096):
            users = user_idx[lo:lo + 4096]
            m = len(users)
            cols = rng.integers(0, stride, size=(m, fk)) + stride * np.arange(fk)
            xu = rng.normal(size=(m, rd)).astype(np.float32)
            known = users >= 0
            margin = w_fixed[cols].sum(axis=1) + np.where(
                known,
                np.einsum("ij,ij->i", xu, u_user[np.maximum(users, 0)]),
                0.0,
            )
            y = rng.uniform(size=m) < 1.0 / (1.0 + np.exp(-margin))
            for i in range(m):
                uid = (
                    f"u{users[i]}" if known[i] else f"unseen{lo + i}"
                )
                yield {
                    "uid": f"{uid_prefix}{lo + i}",
                    "response": float(y[i]),
                    "weight": None,
                    "offset": None,
                    "ids": {"userId": uid},
                    "features": {
                        "global": [
                            {"name": f"g{c}", "term": "", "value": 1.0}
                            for c in cols[i].tolist()
                        ],
                        "userFeatures": [
                            {"name": f"r{j}", "term": "", "value": float(v)}
                            for j, v in enumerate(xu[i].tolist())
                        ],
                    },
                }

    train_users = rng.permutation(np.repeat(np.arange(n_ent), sizes))
    write_game_avro(
        os.path.join(work, "game_train.avro"), records(train_users, "t")
    )
    # Held-out rows: users drawn by training frequency; the first N_UNKNOWN
    # carry ids no model has seen (fixed-effect-only scores).
    held_users = rng.choice(train_users, size=sz["heldout_rows"])
    held_users[:N_UNKNOWN] = -1
    held = list(records(held_users, "h"))
    write_game_avro(os.path.join(work, "game_heldout.avro"), held)
    with open(os.path.join(work, "requests.json"), "w") as f:
        json.dump([
            {"uid": r["uid"], "features": r["features"], "ids": r["ids"]}
            for r in held[:N_REQUESTS]
        ], f)

    opt = {
        "optimizer": "lbfgs", "max_iters": sz["max_iters"],
        "tolerance": 1e-7, "reg_type": "l2", "reg_weight": 1.0,
    }
    with open(os.path.join(work, "game_config.json"), "w") as f:
        json.dump({
            "task": "logistic",
            "iterations": 2,
            "evaluator": "auc",
            "coordinates": [
                {"name": "fixed", "type": "fixed",
                 "feature_shard": "global", **opt},
                # bucket_growth 4: the zipf tail consolidates into a
                # handful of compiled bucket shapes.
                {"name": "per_user", "type": "random",
                 "feature_shard": "userFeatures", "entity_key": "userId",
                 "bucket_growth": 4.0, **opt},
            ],
        }, f)
    with open(os.path.join(work, "datagen.json"), "w") as f:
        json.dump({"game_train_rows": int(sizes.sum())}, f)


def child_client(work: str, port: int, platform: str) -> None:
    """POST the request rows to the live server, compare with the batch
    scorer's output for the same records, read /healthz and /stats."""
    import numpy as np

    from photon_ml_tpu.io import avro

    base = f"http://127.0.0.1:{port}"

    def call(path: str, payload=None) -> dict:
        req = urllib.request.Request(
            base + path,
            data=None if payload is None else json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            return json.loads(resp.read())

    with open(os.path.join(work, "requests.json")) as f:
        requests = json.load(f)
    _, scored = avro.read_container(
        os.path.join(work, "out_score", "scores.avro")
    )
    batch = {r["uid"]: r["predictionScore"] for r in scored}

    online = []
    for lo in range(0, len(requests), 8):
        rows = [
            {"features": r["features"], "ids": r["ids"]}
            for r in requests[lo:lo + 8]
        ]
        results = call("/score", {"rows": rows})["results"]
        check(len(results) == len(rows), f"/score answered {results!r}")
        for r in results:
            check("score" in r, f"/score row failed: {r!r}")
            online.append(r["score"])
    online = np.asarray(online, np.float64)
    want = np.asarray([batch[r["uid"]] for r in requests], np.float64)
    check(bool(np.all(np.isfinite(online))), f"non-finite scores {online}")
    err = np.abs(online - want) / np.maximum(1.0, np.abs(want))
    check(
        float(err.max()) <= SCORE_TOL,
        f"online vs batch scores differ by {err.max():.3g} > {SCORE_TOL} "
        f"(online {online.tolist()}, batch {want.tolist()})",
    )

    health = call("/healthz")
    stats = call("/stats")
    rt = stats["runtime"]
    check(health["status"] == "ok", f"/healthz: {health}")
    check(health["degraded"] is False, f"/healthz degraded: {health}")
    check(
        rt["degraded"] is False and rt["degraded_batches"] == 0
        and rt["device_failures"] == 0,
        f"/stats runtime left the device path: {rt}",
    )
    check(
        rt["hot_sets"]["per_user"]["unknown_entities"] >= N_UNKNOWN,
        f"unknown-entity requests were not counted: {rt['hot_sets']}",
    )
    check(
        stats["device"]["platform"] == platform,
        f"server reports device {stats['device']}, expected {platform}",
    )
    with open(os.path.join(work, "client_result.json"), "w") as f:
        json.dump({
            "requests": len(requests),
            "unknown_entity_requests": N_UNKNOWN,
            "max_score_error": float(err.max()),
            "healthz_status": health["status"],
            "rows_scored": rt["rows_scored"],
            "buckets": rt["buckets"],
            "warmup_compiles": rt["warmup_compiles"],
        }, f)


# ---------------------------------------------------------------------------
# The parent: runs children one at a time, never touches JAX
# ---------------------------------------------------------------------------


def _child_setup() -> None:
    # Own process group (so a stuck child's whole tree can be killed) and
    # the default SIGINT action: a parent started in the background hands
    # down SIG_IGN, and the server must answer SIGINT.
    os.setsid()
    signal.signal(signal.SIGINT, signal.SIG_DFL)


class Smoke:
    def __init__(self, mode: str, work: str, cpu_devices: int = 1):
        self.mode = mode
        self.work = work
        self.sizes = SIZES[mode]
        self.t0 = time.monotonic()
        self.live: list = []
        env = dict(os.environ)
        if mode == "cpu-dry-run":
            env["JAX_PLATFORMS"] = "cpu"
            env["PHOTON_PALLAS_INTERPRET"] = "1"
            # Exactly the asked number of CPU devices (1: the single-chip
            # path; more: the mesh path a multi-chip host takes), whatever
            # a test harness put into XLA_FLAGS.
            flag = "--xla_force_host_platform_device_count"
            env["XLA_FLAGS"] = " ".join(
                [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith(flag)]
                + [f"{flag}={cpu_devices}"]
            )
        self.env = env
        self.cpu_env = {**env, "JAX_PLATFORMS": "cpu"}
        self.device: dict = {}
        self.cache_dir = ""

    # -- process plumbing --------------------------------------------------
    def remaining(self) -> float:
        return DEADLINE_S - (time.monotonic() - self.t0)

    def path(self, *parts: str) -> str:
        return os.path.join(self.work, *parts)

    def log_tail(self, name: str, n: int = 60) -> str:
        try:
            with open(self.path(f"{name}.log"), errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def spawn(self, name: str, argv: list, env: dict) -> subprocess.Popen:
        log = open(self.path(f"{name}.log"), "w")
        try:
            proc = subprocess.Popen(
                [sys.executable, *argv], cwd=HERE, env=env, stdout=log,
                stderr=subprocess.STDOUT, preexec_fn=_child_setup,
            )
        finally:
            log.close()
        self.live.append(proc)
        return proc

    def stop(self, proc: subprocess.Popen) -> None:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()

    def run(self, name: str, argv: list, env: dict, cap_s: float) -> float:
        """Run one child to its end; returns wall seconds."""
        timeout = min(cap_s, self.remaining())
        check(timeout > 0, f"out of time before phase {name!r}")
        t0 = time.monotonic()
        proc = self.spawn(name, argv, env)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.stop(proc)
            raise SmokeFailure(
                f"phase {name!r} still running after {timeout:.0f}s\n"
                + self.log_tail(name)
            )
        check(
            rc == 0,
            f"phase {name!r} exited with code {rc}\n" + self.log_tail(name),
        )
        return time.monotonic() - t0

    def read_json(self, *parts: str) -> dict:
        with open(self.path(*parts)) as f:
            return json.load(f)

    # -- checks shared by every entry point's runtime block ----------------
    def check_runtime(self, name: str, rt: dict) -> None:
        for key, want in (
            ("platform", self.device["platform"]),
            ("device_kind", self.device["device_kind"]),
            ("device_count", self.device["device_count"]),
            ("pallas_interpret", self.mode == "cpu-dry-run"),
            ("compile_cache_dir", self.cache_dir),
        ):
            check(
                rt.get(key) == want,
                f"{name}: runtime.{key} is {rt.get(key)!r}, expected {want!r}",
            )
        fell_back = [k for k, v in rt["native"].items() if v == "fallback"]
        check(
            not fell_back,
            f"{name}: native libraries fell back to Python: {fell_back}",
        )

    def expect_layout(self, name: str, layout: str, values: str) -> None:
        """One device trains on the Pallas layout (``values``: the
        ``valued`` or ``unit`` slot stream); on a mesh the drivers shard
        rows as plain COO — recorded as such, not asserted away."""
        n = self.device["device_count"]
        want = (
            f"PallasSparseMatrix[{values} " if n == 1
            else f"SparseMatrix x{n} row shards"
        )
        check(
            layout.startswith(want),
            f"{name}: feature layout {layout!r}, expected {want!r}...",
        )

    def phase_report(self, wall: float, result: dict) -> dict:
        rt = result["runtime"]
        return {
            "wall_s": round(wall, 2),
            "compile_s": rt["compile_seconds"],
            "backend_compile_s": rt["backend_compile_seconds"],
            "compile_cache_hits": rt["compile_cache_hits"],
            # the entry point's own clock (the server has none)
            "driver_wall_s": result.get("wall_seconds"),
            "feature_layout": rt["feature_layout"],
            "bytes_in_use": rt["bytes_in_use_after_placement"],
            "native": rt["native"],
        }

    # -- phases ------------------------------------------------------------
    def preflight(self) -> None:
        """Ask a child what JAX sees, with the repo's own report and cache
        resolver.  It exits (releasing the chip) before anything else."""
        code = (
            "import json\n"
            "from photon_ml_tpu.utils import compile_cache, device_report\n"
            "print(json.dumps({**device_report.describe_devices(), "
            "'cache_dir': compile_cache.cache_dir()}))\n"
        )
        self.run("preflight", ["-c", code], self.env, 180)
        with open(self.path("preflight.log")) as f:
            lines = [ln for ln in f.read().splitlines() if ln.startswith("{")]
        check(bool(lines), "preflight printed no device report")
        self.device = json.loads(lines[-1])
        self.cache_dir = self.device.pop("cache_dir")
        found = (
            f"{self.device['device_count']} x {self.device['platform']} "
            f"({self.device['device_kind']})"
        )
        if self.mode == "cpu-dry-run":
            check(
                self.device["platform"] == "cpu",
                f"--cpu-dry-run runs on the CPU, JAX found {found}",
            )
            return
        check(
            self.device["platform"] == "tpu",
            f"no TPU: JAX's default backend found {found}",
        )
        kind = self.device["device_kind"].lower()
        check(
            "v5 lite" in kind or "v5e" in kind,
            f"not a TPU v5e: JAX found {found}",
        )

    def cache_entries(self) -> int:
        try:
            return sum(1 for e in os.scandir(self.cache_dir) if e.is_file())
        except OSError:
            return 0

    def phase_data(self) -> dict:
        wall = self.run(
            "datagen",
            [os.path.abspath(__file__), "--child", "datagen",
             "--work", self.work, "--mode", self.mode],
            self.cpu_env, 420,
        )
        return {"wall_s": round(wall, 2), **self.read_json("datagen.json")}

    def phase_glm(self) -> dict:
        sz = self.sizes
        wall = self.run("glm", [
            "-m", "photon_ml_tpu.drivers.glm_driver",
            "--train-data", self.path("glm_train.libsvm"),
            "--validate-data", self.path("glm_val.libsvm"),
            "--n-features", str(sz["glm_features"]),
            "--task", "logistic", "--reg-type", "l2",
            "--reg-weights", "10,1", "--max-iters", str(sz["max_iters"]),
            "--data-parallel", "auto",
            "--output-dir", self.path("out_glm"),
        ], self.env, 480)
        result = self.read_json("out_glm", "training_result.json")
        self.check_runtime("glm", result["runtime"])
        self.expect_layout(
            "glm", result["runtime"]["feature_layout"], "valued"
        )
        check(
            result["n_rows"] == sz["glm_rows"]
            and result["n_features"] == sz["glm_features"] + 1,
            f"glm: trained on {result['n_rows']} x {result['n_features']}",
        )
        values = result["objective_values"]
        check(
            len(values) == 2
            and all(math.isfinite(v) for v in values.values()),
            f"glm: objective values {values}",
        )
        auc = max(result["metrics"].values())
        check(
            result["evaluator"] == "AreaUnderROCCurveEvaluator"
            and auc > 0.7,
            f"glm: validation {result['evaluator']} = {auc} (planted "
            "model: expected well above 0.5)",
        )
        return {
            **self.phase_report(wall, result),
            "rows": result["n_rows"], "features": result["n_features"],
            "validation_auc": round(auc, 4),
            "objective_values": values,
            "solver_wall_s": round(
                sum(result["solver_wall_seconds"].values()), 2
            ),
        }

    def phase_game_train(self) -> dict:
        wall = self.run("game_train", [
            "-m", "photon_ml_tpu.drivers.game_training_driver",
            "--train-data", self.path("game_train.avro"),
            "--config", self.path("game_config.json"),
            "--data-parallel", "auto",
            "--output-dir", self.path("out_game"),
        ], self.env, 480)
        result = self.read_json("out_game", "training_result.json")
        self.check_runtime("game_train", result["runtime"])
        layouts = result["runtime"]["feature_layout"]
        self.expect_layout("game_train", layouts["fixed"], "unit")
        check(
            result["n_rows"] == self.read_json("datagen.json")[
                "game_train_rows"
            ],
            f"game_train: trained on {result['n_rows']} rows",
        )
        metric = result["train_metric"]
        check(
            metric is not None and math.isfinite(metric) and metric > 0.6,
            f"game_train: training AUC {metric}",
        )
        return {
            **self.phase_report(wall, result),
            "rows": result["n_rows"], "train_auc": round(metric, 4),
        }

    def phase_game_score(self) -> dict:
        wall = self.run("game_score", [
            "-m", "photon_ml_tpu.drivers.game_scoring_driver",
            "--data", self.path("game_heldout.avro"),
            "--model-dir", self.path("out_game"),
            "--evaluator", "auc",
            "--output-dir", self.path("out_score"),
        ], self.env, 240)
        result = self.read_json("out_score", "scoring_result.json")
        self.check_runtime("game_score", result["runtime"])
        check(
            result["n_rows"] == self.sizes["heldout_rows"],
            f"game_score: scored {result['n_rows']} rows",
        )
        check(
            math.isfinite(result["metric"]) and result["metric"] > 0.6,
            f"game_score: held-out AUC {result['metric']}",
        )
        return {
            **self.phase_report(wall, result),
            "rows": result["n_rows"],
            "heldout_auc": round(result["metric"], 4),
        }

    def phase_serve(self) -> dict:
        t0 = time.monotonic()
        server = self.spawn("serve", [
            "-m", "photon_ml_tpu.serving",
            "--model-dir", self.path("out_game"), "--port", "0",
            "--output-dir", self.path("out_serve"),
        ], self.env)
        try:
            port = self._await_server(server, min(300, self.remaining()))
            ready_s = time.monotonic() - t0
            self.run(
                "client",
                [os.path.abspath(__file__), "--child", "client",
                 "--work", self.work, "--port", str(port),
                 "--platform", self.device["platform"]],
                self.cpu_env, 120,
            )
            server.send_signal(signal.SIGINT)
            try:
                rc = server.wait(timeout=60)
            except subprocess.TimeoutExpired:
                raise SmokeFailure(
                    "server ignored SIGINT for 60s\n" + self.log_tail("serve")
                )
            check(
                rc == 0,
                f"server exited with code {rc} after SIGINT\n"
                + self.log_tail("serve"),
            )
        finally:
            self.stop(server)
        wall = time.monotonic() - t0
        result = self.read_json("out_serve", "serving_result.json")
        self.check_runtime("serve", result["runtime"])
        rt = result["stats"]["runtime"]
        check(
            rt["degraded"] is False and rt["degraded_batches"] == 0
            and rt["device_failures"] == 0,
            f"serve: final stats left the device path: {rt}",
        )
        counters = self.read_json("out_serve", "metrics.json")["counters"]
        check(
            counters.get("serving_device_failures_total", 0) == 0
            and counters.get("serving_degraded_batches_total", 0) == 0,
            f"serve: degraded counters moved: {counters}",
        )
        check(
            rt["warmup_compiles"] == len(rt["buckets"]),
            f"serve: warmed {rt['warmup_compiles']} of {rt['buckets']}",
        )
        return {
            **self.phase_report(wall, result),
            "ready_s": round(ready_s, 2),
            **self.read_json("client_result.json"),
        }

    def _await_server(self, server: subprocess.Popen, timeout: float) -> int:
        marker = "serving on http://127.0.0.1:"
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            for line in self.log_tail("serve", 1000).splitlines():
                if line.startswith(marker):
                    return int(line[len(marker):].split()[0])
            check(
                server.poll() is None,
                f"server exited with code {server.returncode} before "
                "serving (a compile failure at warm-up raises here)\n"
                + self.log_tail("serve"),
            )
            time.sleep(0.25)
        raise SmokeFailure(
            f"server not serving after {timeout:.0f}s\n"
            + self.log_tail("serve")
        )

    def main(self) -> dict:
        self.preflight()
        entries_before = self.cache_entries()
        phases = {"data": self.phase_data()}
        phases["glm"] = self.phase_glm()
        phases["game_train"] = self.phase_game_train()
        phases["game_score"] = self.phase_game_score()
        phases["serve"] = self.phase_serve()
        entries_after = self.cache_entries()
        return {
            "mode": self.mode,
            "versions": {
                k: self.device[k] for k in ("jax", "jaxlib", "libtpu")
            },
            "pallas_interpret": self.device["pallas_interpret"],
            "compile_cache": {
                "dir": self.cache_dir,
                "entries_before": entries_before,
                "entries_after": entries_after,
                "new_entries": entries_after - entries_before,
            },
            "wall_s": round(time.monotonic() - self.t0, 2),
            "phases": phases,
        }


def refuse_hidden_cpu() -> None:
    """The chip run refuses an environment that would let the CPU or the
    interpreter do the work."""
    if os.environ.get("PHOTON_PALLAS_INTERPRET"):
        raise SmokeFailure(
            "PHOTON_PALLAS_INTERPRET is set: the chip run needs the "
            "Mosaic-compiled kernels (pass --cpu-dry-run for the CPU run)"
        )
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
    if first == "cpu":
        raise SmokeFailure(
            f"JAX_PLATFORMS={os.environ['JAX_PLATFORMS']} puts the CPU "
            "first: the chip run needs a TPU (pass --cpu-dry-run for the "
            "CPU run)"
        )


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument(
        "--cpu-dry-run", action="store_true",
        help="tiny sizes on the CPU with interpreted kernels (never "
        "inferred: without it the run fails unless it finds a TPU v5e)",
    )
    p.add_argument(
        "--cpu-devices", type=int, default=1, metavar="N",
        help="with --cpu-dry-run: N virtual CPU devices (N > 1 takes the "
        "mesh path of a multi-chip host)",
    )
    p.add_argument(
        "--keep-work", metavar="DIR",
        help="run in DIR and keep logs and outputs there (default: a "
        "temporary directory, removed at the end)",
    )
    p.add_argument("--child", choices=["datagen", "client"],
                   help=argparse.SUPPRESS)
    p.add_argument("--work", help=argparse.SUPPRESS)
    p.add_argument("--mode", choices=sorted(SIZES), help=argparse.SUPPRESS)
    p.add_argument("--port", type=int, help=argparse.SUPPRESS)
    p.add_argument("--platform", help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    mode = "cpu-dry-run" if args.cpu_dry_run else "chip"
    smoke = None
    work = args.keep_work
    try:
        if args.child == "datagen":
            child_datagen(args.work, args.mode)
            return 0
        if args.child == "client":
            child_client(args.work, args.port, args.platform)
            return 0
        if mode == "chip":
            check(
                args.cpu_devices == 1,
                "--cpu-devices needs --cpu-dry-run",
            )
            refuse_hidden_cpu()
        check(
            os.path.isdir(os.path.join(HERE, "photon_ml_tpu")),
            f"{HERE} holds no photon_ml_tpu package: nothing to run",
        )
        if work is None:
            work = tempfile.mkdtemp(prefix="chip_smoke_")
        else:
            os.makedirs(work, exist_ok=True)
        smoke = Smoke(mode, work, args.cpu_devices)
        report = smoke.main()
    except SmokeFailure as exc:
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr)
        return 1
    finally:
        if smoke is not None:
            for proc in smoke.live:
                smoke.stop(proc)
        if work is not None and args.keep_work is None:
            shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"report": report}))
    # The last line is the verdict, with exactly these keys.
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": str(smoke.device["platform"]),
            "kind": str(smoke.device["device_kind"]),
            "count": int(smoke.device["device_count"]),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
