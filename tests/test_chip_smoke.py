"""chip_smoke.py on the CPU: the explicit dry run is green end to end, the
chip run refuses every way of letting the CPU or the interpreter do the
work, and a compile failure on the serving device path fails instead of
degrading to the host."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env_changes, cwd=REPO, script=SMOKE, timeout=600):
    env = dict(os.environ)
    for key, value in env_changes.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


class TestCpuDryRun:
    @pytest.mark.parametrize("cpu_devices", [
        1,
        # The mesh path a multi-chip host takes: run it (-m slow) before
        # spending four chips' minutes on `chiprun --chips 4`.
        pytest.param(4, marks=pytest.mark.slow),
    ])
    def test_all_phases_green(self, cpu_devices, tmp_path):
        """Every phase through the real entry points; one device trains
        on the Pallas layout (interpreted), a mesh on row-sharded COO."""
        r = _run(
            ["--cpu-dry-run", "--cpu-devices", str(cpu_devices),
             "--keep-work", str(tmp_path)],
            # The dry run must not need the harness's help to stay on
            # the CPU or to interpret the kernels.
            {"JAX_PLATFORMS": None, "PHOTON_PALLAS_INTERPRET": None},
        )
        assert r.returncode == 0, r.stderr[-3000:]
        lines = r.stdout.strip().splitlines()
        # The last line is the verdict with exactly these keys; the
        # report is the line before it.
        assert json.loads(lines[-1]) == {
            "ok": True,
            "device": {
                "platform": "cpu", "kind": "cpu", "count": cpu_devices,
            },
        }
        report = json.loads(lines[-2])["report"]
        assert report["mode"] == "cpu-dry-run"  # printed as such
        assert report["pallas_interpret"] is True
        phases = report["phases"]
        assert list(phases) == [
            "data", "glm", "game_train", "game_score", "serve",
        ]
        glm = phases["glm"]["feature_layout"]
        fixed = phases["game_train"]["feature_layout"]["fixed"]
        if cpu_devices == 1:
            assert glm.startswith("PallasSparseMatrix[valued ")
            assert fixed.startswith("PallasSparseMatrix[unit ")
        else:
            assert glm == fixed == f"SparseMatrix x{cpu_devices} row shards"
        assert phases["glm"]["validation_auc"] > 0.7
        assert phases["serve"]["max_score_error"] <= 5e-7
        assert phases["serve"]["healthz_status"] == "ok"
        for name in ("glm", "game_train", "game_score", "serve"):
            assert phases[name]["compile_s"] >= 0.0
            assert "fallback" not in phases[name]["native"].values()
        # Stopped everything it started, including the server.
        log = (tmp_path / "serve.log").read_text()
        assert "shutting down" in log


class TestRefusals:
    """Without --cpu-dry-run the script fails before any phase, with no
    JSON on stdout, unless JAX's default backend is a TPU v5e."""

    def _refused(self, r, *needles):
        assert r.returncode != 0
        assert r.stdout.strip() == ""
        for needle in needles:
            assert needle in r.stderr, r.stderr[-2000:]

    def test_jax_platforms_cpu(self):
        r = _run([], {"JAX_PLATFORMS": "cpu", "PHOTON_PALLAS_INTERPRET": None})
        self._refused(r, "JAX_PLATFORMS=cpu", "--cpu-dry-run")

    def test_interpret_mode(self):
        r = _run(
            [], {"JAX_PLATFORMS": None, "PHOTON_PALLAS_INTERPRET": "1"}
        )
        self._refused(r, "PHOTON_PALLAS_INTERPRET")

    def test_no_chip_names_the_device_found(self):
        """JAX_PLATFORMS unset and no TPU: JAX falls back to the CPU
        quietly; the smoke does not."""
        r = _run([], {
            "JAX_PLATFORMS": None, "PHOTON_PALLAS_INTERPRET": None,
            "XLA_FLAGS": None,  # the harness's 8 virtual CPU devices
        })
        self._refused(r, "no TPU", "1 x cpu (cpu)")

    def test_alone_in_a_directory(self, tmp_path):
        alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
        r = _run(
            [], {"JAX_PLATFORMS": None, "PHOTON_PALLAS_INTERPRET": None},
            cwd=str(tmp_path), script=str(alone),
        )
        self._refused(r, "photon_ml_tpu")


class TestServingCompileFailure:
    """A Mosaic/XLA compile failure is ``JaxRuntimeError: INTERNAL: ...``;
    the serving runtime must not answer it from the host."""

    def _runtime(self):
        from photon_ml_tpu.serving.runtime import RuntimeConfig, ScoringRuntime
        from photon_ml_tpu.serving.synthetic import SyntheticWorkload

        workload = SyntheticWorkload(n_entities=24, seed=9)
        runtime = ScoringRuntime(
            workload.model, workload.index_maps,
            RuntimeConfig(max_batch_size=4, hot_entities=8),
        )
        return workload, runtime

    def _inject(self, monkeypatch, message):
        from jax.errors import JaxRuntimeError

        from photon_ml_tpu.chaos import core as chaos_core

        def maybe_fail(site, **ctx):
            if site == "serving.device":
                raise JaxRuntimeError(message)

        monkeypatch.setattr(chaos_core, "maybe_fail", maybe_fail)

    def test_compile_error_on_device_path_raises(self, monkeypatch):
        from jax.errors import JaxRuntimeError

        workload, runtime = self._runtime()
        row = runtime.parse_request(workload.request(0))
        self._inject(
            monkeypatch,
            "INTERNAL: Mosaic failed to compile TPU kernel: scoped "
            "allocation exceeds the limit",
        )
        with pytest.raises(JaxRuntimeError, match="Mosaic failed"):
            runtime.score_rows([row])
        assert runtime.degraded is False
        assert runtime.degraded_batches == 0 and runtime.device_failures == 0

    def test_device_lost_still_degrades(self, monkeypatch):
        workload, runtime = self._runtime()
        row = runtime.parse_request(workload.request(0))
        want = runtime.score_rows([row])[0]
        self._inject(monkeypatch, "UNAVAILABLE: device lost")
        got = runtime.score_rows([row])[0]
        assert runtime.degraded is True and runtime.degraded_batches == 1
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_compile_error_at_warmup_raises(self, monkeypatch):
        from jax.errors import JaxRuntimeError

        from photon_ml_tpu.serving import kernels as kernels_lib

        def build(mean_fn):
            def kernel(*args):
                raise JaxRuntimeError(
                    "INTERNAL: Mosaic failed to compile TPU kernel"
                )

            kernel._cache_size = lambda: 0
            return kernel

        monkeypatch.setattr(kernels_lib, "build_fused_bucket_kernel", build)
        with pytest.raises(JaxRuntimeError, match="Mosaic failed"):
            self._runtime()
