"""Data-parallel training over a device mesh.

The analogue of the reference's Spark runtime layer (SURVEY.md §2
"Distributed communication backend", §3.1): rows live sharded across
executors, coefficients are broadcast each iteration, and gradients come
back through ``RDD.treeAggregate``.  Here:

- rows are sharded across devices of a ``jax.sharding.Mesh`` axis
  (``DATA_AXIS``) as equal-size row blocks, built once on the host and
  device_put once (the analogue of persisting the RDD);
- coefficients are *replicated* — no per-iteration broadcast exists because
  SPMD devices all hold w;
- each objective evaluation issues ONE fused ``lax.psum`` for (value, grad)
  over ICI — the ``treeAggregate`` replacement [CONFIRMED-BASELINE mapping];
- the ENTIRE optimizer loop runs inside ``shard_map``: every device executes
  the same while_loop and every convergence decision depends only on psum'd
  quantities, so control flow stays replicated with zero host round-trips
  per iteration (the reference pays a driver↔executor round trip per
  objective evaluation).

Scale-out note: the same code runs multi-host — devices of all hosts join
the mesh and XLA routes the psum over ICI within a slice and DCN across
slices; nothing here is host-count-aware.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.data.dataset import GlmData
from photon_ml_tpu.ops.sparse import DenseMatrix, SparseMatrix

Array = jax.Array

DATA_AXIS = "data"


def data_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A 1-D mesh over all (or the given) devices with axis ``DATA_AXIS``."""
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.array(devices), (DATA_AXIS,))


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["data"],
    meta_fields=["n_shards"],
)
@dataclasses.dataclass
class DistributedGlmData:
    """A GlmData whose arrays carry a leading shard axis of size n_shards.

    Built by :func:`shard_glm_data`; consumed inside ``shard_map`` where each
    device sees a leading axis of 1 — :meth:`local` squeezes it away and
    (for sparse features) re-materializes shard-local row ids.
    """

    data: GlmData  # every array: (n_shards, ...)
    n_shards: int

    def local(self) -> GlmData:
        return jax.tree.map(lambda x: x[0], self.data)


def _pad_rows_to(n_rows: int, n_shards: int) -> int:
    return ((n_rows + n_shards - 1) // n_shards) * n_shards


def shard_glm_data(
    data_host,
    labels,
    mesh: Optional[Mesh],
    weights=None,
    offsets=None,
    dtype=jnp.float32,
    n_shards: Optional[int] = None,
) -> DistributedGlmData:
    """Build row-block shards from host data and place them on the mesh.

    ``data_host`` is a numpy 2-D array or scipy sparse matrix.  Rows are
    padded (weight=0) to a multiple of the mesh size, split into contiguous
    blocks, and each block becomes a shard-local matrix with LOCAL row ids.
    Sparse blocks pad nnz to the max across shards so shapes are uniform.

    ``mesh=None`` builds LOGICAL shards: the same leading-shard-axis layout
    with ``n_shards`` row blocks, left on the default device — the
    single-device stand-in the host-loop solvers (solvers/admm.py,
    solvers/block_cd.py) vmap over when no mesh participates.
    """
    import scipy.sparse as sp

    from photon_ml_tpu.ops.sparse import canonicalize_coo

    if mesh is not None:
        n_shards = mesh.devices.size
    elif n_shards is None or n_shards < 1:
        raise ValueError("shard_glm_data needs a mesh or n_shards >= 1")
    n = data_host.shape[0]
    d = data_host.shape[1]
    total = _pad_rows_to(n, n_shards)
    rows_per = total // n_shards

    labels = np.asarray(labels, np.float32)
    weights = np.ones(n, np.float32) if weights is None else np.asarray(weights, np.float32)
    offsets = np.zeros(n, np.float32) if offsets is None else np.asarray(offsets, np.float32)
    pad = total - n
    labels = np.concatenate([labels, np.zeros(pad, np.float32)])
    weights = np.concatenate([weights, np.zeros(pad, np.float32)])
    offsets = np.concatenate([offsets, np.zeros(pad, np.float32)])

    # Every stacked array is built on the HOST and placed once: with a mesh
    # each device receives only its own row block (a jnp.stack here would
    # materialize the whole dataset on the default device first — on a
    # multi-chip host that is the full matrix on chip 0).
    if mesh is not None:
        sharding = NamedSharding(mesh, P(DATA_AXIS))

        def place(x: np.ndarray) -> Array:
            return jax.device_put(x, sharding)
    else:
        place = jnp.asarray

    if sp.issparse(data_host):
        csr = data_host.tocsr()
        csr.sum_duplicates()
        # nnz budget: max across row blocks, rounded up for stable shapes.
        block_nnz = [
            csr.indptr[min((i + 1) * rows_per, n)] - csr.indptr[min(i * rows_per, n)]
            for i in range(n_shards)
        ]
        budget = max(1, max(block_nnz))
        shards = []
        for i in range(n_shards):
            lo, hi = min(i * rows_per, n), min((i + 1) * rows_per, n)
            coo = csr[lo:hi].tocoo()
            shards.append(
                canonicalize_coo(coo.row, coo.col, coo.data, rows_per, d, budget)
            )
        row_ids, col_ids, values = (np.stack(x) for x in zip(*shards))
        features = SparseMatrix(
            row_ids=place(row_ids),
            col_ids=place(col_ids),
            values=place(values.astype(dtype)),
            n_rows=rows_per,
            n_cols=d,
        )
    else:
        dense = np.asarray(data_host, np.float32)
        dense = np.concatenate([dense, np.zeros((pad, d), np.float32)])
        features = DenseMatrix(
            place(dense.reshape(n_shards, rows_per, d).astype(dtype))
        )

    stacked = GlmData(
        features=features,
        labels=place(labels.reshape(n_shards, rows_per)),
        weights=place(weights.reshape(n_shards, rows_per)),
        offsets=place(offsets.reshape(n_shards, rows_per)),
    )
    return DistributedGlmData(data=stacked, n_shards=n_shards)


def run_grid_distributed(
    problem,
    dist_data: DistributedGlmData,
    mesh: Mesh,
    reg_weights,
    w0: Optional[Array] = None,
    l1_mask: Optional[Array] = None,
    warm_start: bool = True,
    solved: Optional[dict] = None,
    on_solved=None,
):
    """The λ-grid warm-start chain (optim.problem.grid_loop) on a
    row-sharded mesh: ONE jitted shard_map program serves every λ
    (reg_weight and the warm start are traced), each objective evaluation
    is one fused psum — the reference's per-λ ``treeAggregate`` loop
    collapsed onto ICI.  Coefficient variances, when configured, run as a
    second shard_map program (one psum'd squared-column reduction per λ).

    The host-loop solvers (``OptimizerConfig.solver`` naming admm or
    block_cd) cannot run inside the traced shard_map solve: their grid is
    ``solvers.sharded.run_grid_sharded``, which takes the same arguments."""
    import jax.numpy as jnp

    d = dist_data.data.features.shape[-1]
    if w0 is None:
        w0 = jnp.zeros((d,), jnp.float32)
    mask = (
        jnp.ones((d,), jnp.float32) if l1_mask is None
        else jnp.asarray(l1_mask, jnp.float32)
    )

    def spmd(dd: DistributedGlmData, w_start: Array, lam: Array, m: Array):
        return problem.solve(
            dd.local(), lam, w_start, axis_name=DATA_AXIS, l1_mask=m
        )

    solve_sm = jax.jit(
        shard_map(
            spmd,
            mesh=mesh,
            in_specs=(P(DATA_AXIS), P(), P(), P()),
            out_specs=P(),
            check_vma=False,
        )
    )

    def solve_fn(lam, w_prev):
        return solve_sm(
            dist_data, w_prev, jnp.asarray(lam, jnp.float32), mask
        )

    variance_fn = None
    if problem.config.compute_variances:
        def var_spmd(dd: DistributedGlmData, w: Array, lam: Array):
            return problem.coefficient_variances(
                w, dd.local(), lam, axis_name=DATA_AXIS
            )

        var_sm = jax.jit(
            shard_map(
                var_spmd,
                mesh=mesh,
                in_specs=(P(DATA_AXIS), P(), P()),
                out_specs=P(),
                check_vma=False,
            )
        )
        variance_fn = lambda w, lam: var_sm(
            dist_data, w, jnp.asarray(lam, jnp.float32)
        )

    return problem.grid_loop(
        solve_fn, reg_weights, w0, warm_start, solved, on_solved, variance_fn
    )


def distributed_solve(
    solve_fn: Callable[[GlmData, Array], object],
    dist_data: DistributedGlmData,
    w0: Array,
    mesh: Mesh,
):
    """Run ``solve_fn(local_data, w0) -> SolveResult`` SPMD over the mesh.

    ``solve_fn`` must reduce with ``axis_name=DATA_AXIS`` inside its
    objective (see GlmObjective's ``axis_name`` argument).  Results are
    replicated; the returned pytree is the single logical result.
    """

    def spmd(dd: DistributedGlmData, w0: Array):
        return solve_fn(dd.local(), w0)

    return jax.jit(
        shard_map(
            spmd,
            mesh=mesh,
            in_specs=(P(DATA_AXIS), P()),
            out_specs=P(),
            check_vma=False,
        )
    )(dist_data, w0)
