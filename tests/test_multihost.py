"""Multi-host glue (parallel/multihost.py): initialization fallbacks, the
process-block math, and global-array assembly on the virtual device mesh.
True multi-process runs need a pod; everything testable single-process is
tested here (the compute paths themselves are host-count-agnostic SPMD)."""

import numpy as np

from jax import shard_map
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.parallel import multihost


class TestInitialize:
    def test_noop_without_config_on_cpu(self, monkeypatch):
        for var in ("COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS",
                    "NUM_PROCESSES", "JAX_NUM_PROCESSES",
                    "PROCESS_ID", "JAX_PROCESS_ID", "PHOTON_MULTIHOST"):
            monkeypatch.delenv(var, raising=False)
        # CPU backend + no env: must not touch jax.distributed at all.
        assert multihost.initialize() is False

    def test_env_fallback_reads_both_prefixes(self, monkeypatch):
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "1.2.3.4:1234")
        assert multihost._env_first(multihost._ENV_COORD) == "1.2.3.4:1234"
        monkeypatch.setenv("COORDINATOR_ADDRESS", "5.6.7.8:99")
        assert multihost._env_first(multihost._ENV_COORD) == "5.6.7.8:99"


class TestProcessRowBounds:
    def test_single_process_owns_everything(self):
        assert multihost.host_local_rows(1000) == (0, 1000)

    @pytest.mark.parametrize(
        "n,nproc,ldc",
        [(10, 2, 2), (1000, 4, 8), (7, 4, 1), (8, 4, 2), (3, 4, 2)],
    )
    def test_blocks_tile_the_row_space_device_chunked(
        self, n, nproc, ldc, monkeypatch
    ):
        monkeypatch.setattr(jax, "process_count", lambda: nproc)
        total = nproc * ldc
        chunk = -(-n // total)
        covered = []
        for pid in range(nproc):
            start, stop = multihost._process_row_bounds(n, pid, ldc)
            assert start <= stop <= n
            # Matches the per-DEVICE ceil-chunk layout XLA uses.
            assert start == min(pid * ldc * chunk, n)
            covered.append((start, stop))
        # Contiguous tiling of [0, n).
        assert covered[0][0] == 0
        assert covered[-1][1] == n
        for (a, b), (c, d) in zip(covered, covered[1:]):
            assert b == c

    def test_uneven_case_differs_from_even_split(self, monkeypatch):
        # 10 rows, 2 procs x 2 devices: device chunks are 3,3,3,1 so
        # process 0 owns 6 rows — an even per-process split (5/5) would
        # disagree with the sharding.
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        assert multihost._process_row_bounds(10, 0, 2) == (0, 6)
        assert multihost._process_row_bounds(10, 1, 2) == (6, 10)


class TestAssembleGlobal:
    def test_single_process_roundtrip_sharded(self, rng):
        mesh = multihost.global_data_mesh()
        n = 8 * 13  # not a multiple of anything interesting per device
        x = rng.normal(size=(n, 5)).astype(np.float32)
        arr = multihost.assemble_global(x, n, mesh)
        assert arr.shape == (n, 5)
        np.testing.assert_allclose(np.asarray(arr), x)
        # Actually sharded over the data axis.
        assert len(arr.sharding.device_set) == len(jax.devices())

    def test_wrong_block_size_raises(self, rng):
        mesh = multihost.global_data_mesh()
        with pytest.raises(ValueError, match="owns"):
            multihost.assemble_global(
                np.zeros((5, 3), np.float32), 100, mesh
            )

    def test_assembled_array_feeds_psum_program(self, rng):
        """The assembled array works under shard_map with a psum — the
        treeAggregate-analogue consumption pattern."""
        from jax.sharding import PartitionSpec as P

        mesh = multihost.global_data_mesh()
        n = 16 * len(jax.devices())
        x = rng.normal(size=(n,)).astype(np.float32)
        arr = multihost.assemble_global(x, n, mesh)

        def f(block):
            return jax.lax.psum(jnp.sum(block), multihost.DATA_AXIS)

        total = jax.jit(shard_map(
            f, mesh=mesh, in_specs=P(multihost.DATA_AXIS),
            out_specs=P(),
        ))(arr)
        np.testing.assert_allclose(float(total), x.sum(), rtol=1e-5)


class TestPartialConfig:
    def test_partial_explicit_config_raises(self, monkeypatch):
        for var in ("COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS",
                    "NUM_PROCESSES", "JAX_NUM_PROCESSES",
                    "PROCESS_ID", "JAX_PROCESS_ID", "PHOTON_MULTIHOST"):
            monkeypatch.delenv(var, raising=False)
        with pytest.raises(ValueError, match="ALL of"):
            multihost.initialize(num_processes=4)
        monkeypatch.setenv("COORDINATOR_ADDRESS", "1.2.3.4:99")
        with pytest.raises(ValueError, match="ALL of"):
            multihost.initialize()

    def test_stray_generic_env_vars_are_ignored(self, monkeypatch):
        """Unrelated tooling commonly exports NUM_PROCESSES / PROCESS_ID;
        without a coordinator address they must not abort a single-host
        run (regression: the all-or-none check fired on them)."""
        for var in ("COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS",
                    "PHOTON_MULTIHOST"):
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setenv("NUM_PROCESSES", "4")
        monkeypatch.setenv("PROCESS_ID", "17")
        assert multihost.initialize() is False

    def test_partial_jax_prefixed_env_fails_loudly(self, monkeypatch):
        """JAX_-prefixed vars are deliberate config: a partial set (lost
        coordinator) must error, not silently run single-host."""
        for var in ("COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS",
                    "NUM_PROCESSES", "PROCESS_ID", "PHOTON_MULTIHOST"):
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setenv("JAX_NUM_PROCESSES", "8")
        monkeypatch.setenv("JAX_PROCESS_ID", "3")
        with pytest.raises(ValueError, match="ALL of"):
            multihost.initialize()
