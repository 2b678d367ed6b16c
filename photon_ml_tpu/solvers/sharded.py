"""Grid runner + shard builders for the host-loop solvers (ADMM, block CD).

The host-loop solvers (``solvers.HOST_SOLVERS``) run a host-side outer loop
around one compiled step program, so they cannot execute inside the traced
``problem.solve``.  This module is their entry: :func:`run_grid_sharded`
plugs a host-loop solver's factory into the SAME
``problem.grid_loop`` warm-start chain the traced paths use — identical
checkpoint/resume semantics (GridCheckpointer via ``on_solved``, the
``grid.point`` chaos boundary), identical solver telemetry spans.

Sharding comes in two flavors, chosen by the caller:

- a real device mesh (``parallel.distributed.data_mesh``) — the solver's
  step program runs SPMD under ``shard_map`` with one ``lax.psum`` per
  outer iteration (multihost-ready);
- LOGICAL shards on one device (``mesh=None``) — the same leading-shard-axis
  layout (``shard_glm_data(..., mesh=None, n_shards=k)``), with ``vmap``'d
  per-shard subproblems and an axis-0 sum standing in for the psum, so
  reduces per solve (``solver_allreduce_count``) can be counted
  anywhere, and single-device callers (tuning ``fit_once``, the GAME
  fixed-effect coordinate) still get ≥2 shards.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from photon_ml_tpu.optim.problem import choose_solver
from photon_ml_tpu.solvers import HOST_SOLVERS

Array = jax.Array


def _host_solver(problem) -> str:
    """The host-loop solver ``problem``'s config names."""
    cfg = problem.config
    name = choose_solver(
        cfg.optimizer, l1_frac=cfg.regularization.l1_weight(1.0)
    )
    if name not in HOST_SOLVERS:
        raise ValueError(
            f"run_grid_sharded serves the host-loop solvers; {name!r} runs "
            "on the device (jit-kind) — use problem.run_grid / "
            "run_grid_distributed"
        )
    return name


def resolve_shard_count(opt, mesh=None, default: int = 2) -> int:
    """The shard count for a host-loop solve: the mesh size when a mesh
    participates, else the solver_options ``shards`` knob, else
    ``default`` logical shards."""
    if mesh is not None:
        return mesh.devices.size
    shards = int(opt.solver_options_dict().get("shards", 0) or 0)
    return shards if shards > 0 else default


def stack_resident(data, n_shards: int):
    """Device-resident GlmData → DistributedGlmData with ``n_shards``
    LOGICAL shards: rows padded (weight 0) to a multiple of the shard
    count, every array reshaped to a leading shard axis.  Dense features
    only — splitting a device-resident COO block into row shards would
    need a host round-trip; densify upstream instead."""
    from photon_ml_tpu.ops.sparse import DenseMatrix
    from photon_ml_tpu.parallel.distributed import (
        DistributedGlmData,
        _pad_rows_to,
    )

    if not isinstance(data.features, DenseMatrix):
        raise ValueError(
            "logical sharding of device-resident data needs DenseMatrix "
            "features; build shards from host data (shard_glm_data) for "
            "sparse inputs"
        )
    rows = int(data.labels.shape[0])
    total = _pad_rows_to(rows, n_shards)
    pad = total - rows
    rows_per = total // n_shards

    def pad_rows(a, fill=0.0):
        if pad == 0:
            return a
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, widths, constant_values=fill)

    stacked = dataclasses.replace(
        data,
        features=DenseMatrix(
            pad_rows(data.features.data).reshape(n_shards, rows_per, -1)
        ),
        labels=pad_rows(data.labels).reshape(n_shards, rows_per),
        weights=pad_rows(data.weights).reshape(n_shards, rows_per),
        offsets=pad_rows(data.offsets).reshape(n_shards, rows_per),
    )
    return DistributedGlmData(data=stacked, n_shards=n_shards)


def run_grid_sharded(
    problem,
    dist,
    mesh,
    reg_weights: Sequence[float],
    w0: Optional[Array] = None,
    l1_mask: Optional[Array] = None,
    warm_start: bool = True,
    solved: Optional[dict] = None,
    on_solved=None,
):
    """The λ-grid warm-start chain for a host-loop solver over sharded
    data — the host-loop counterpart of
    ``parallel.distributed.run_grid_distributed``, with the same
    arguments."""
    name = _host_solver(problem)
    if problem.config.compute_variances:
        raise ValueError(
            f"compute_variances is not supported with solver "
            f"{name!r}; drop the variance request or use an on-device "
            "solver"
        )
    solve = HOST_SOLVERS[name](problem, dist, mesh, l1_mask)
    d = int(dist.data.features.shape[-1])
    if w0 is None:
        w0 = jnp.zeros((d,), jnp.float32)
    return problem.grid_loop(
        lambda lam, w_prev: solve(lam, w_prev),
        reg_weights, w0, warm_start, solved, on_solved, None,
    )


def make_fixed_effect_trainer(problem, data, n_shards: int, l1_mask=None):
    """A GAME fixed-effect trainer backed by a host-loop solver:
    ``trainer(offsets, w0, reg_weight) → coefficients``.

    The dataset shards once (logical, dense); each GAME outer iteration's
    residual offsets re-slot into the SAME shard layout, so the solver's
    compiled step program is reused across iterations."""
    template = stack_resident(data, n_shards)
    rows = int(data.labels.shape[0])
    rows_per = int(template.data.labels.shape[-1])
    total = rows_per * n_shards

    solve = HOST_SOLVERS[_host_solver(problem)](
        problem, template, None, l1_mask)

    def trainer(offsets: Array, w0: Array, reg_weight: float) -> Array:
        off = jnp.asarray(offsets, jnp.float32)
        if total != rows:
            off = jnp.pad(off, (0, total - rows))
        dist_k = dataclasses.replace(
            template,
            data=dataclasses.replace(
                template.data, offsets=off.reshape(n_shards, rows_per)
            ),
        )
        return solve(reg_weight, w0, dist_override=dist_k).w

    return trainer
