"""Feature-dim (tensor) parallelism for wide fixed-effect GLMs.

The reference has no TP — its model is one weight vector small enough to
broadcast (SURVEY.md §2 parallelism table, TP row: "optional feature-dim
sharding for very wide models"; §5.7 scale axis (b): feature spaces up to
very wide sparse widths).  At 10⁸+ features, a replicated ``w`` (plus the
L-BFGS ``(m, d)`` history buffers — 10× ``w``!) no longer fits per-device
alongside the data, so here both are sharded over a second mesh axis:

- mesh: 2-D ``(data, feature)`` — rows sharded over ``data`` as in
  parallel/distributed.py, columns of X and entries of ``w`` sharded over
  ``feature``;
- each device holds ONE (row-block × column-slice) tile of X with local
  column ids, its slice of ``w``, and its slice of every history vector;
- margins: local tile matvec then ``psum`` over the FEATURE axis (each
  data-rank's row margins need every column's contribution);
- gradient: loss derivatives are replicated within a feature group (they
  depend only on margins), so the local ``rmatvec`` then ``psum`` over the
  DATA axis yields the gradient SLICE for the local columns — the gradient
  is born sharded exactly like ``w``, no all-gather anywhere;
- the whole L-BFGS loop runs on sharded state inside ``shard_map``: every
  w-space inner product / norm reduces over the feature axis
  (``optim.lbfgs`` ``w_axis``), so the iteration is an exact replica of the
  single-device one.

Per objective evaluation the wire cost is one (rows/dp)-length psum over
``feature`` + one fused scalar/slice psum over ``data`` — both ride ICI.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.ops import losses as losses_lib
from photon_ml_tpu.ops.sparse import DenseMatrix, SparseMatrix, from_coo
from photon_ml_tpu.optim.lbfgs import LBFGSConfig, SolveResult, lbfgs_solve
from photon_ml_tpu.optim.owlqn import OWLQNConfig, owlqn_solve
from photon_ml_tpu.optim.tron import TRONConfig, tron_solve
from photon_ml_tpu.parallel.distributed import DATA_AXIS

Array = jax.Array

FEATURE_AXIS = "feature"


def dp_tp_mesh(
    dp: int, tp: int, devices: Optional[Sequence[jax.Device]] = None
) -> Mesh:
    """A (data=dp, feature=tp) mesh.  Convention: the FEATURE axis is the
    minor (fastest-varying) one so a feature group's devices are ICI
    neighbors — the per-evaluation margin psum rides the shortest links."""
    devices = jax.devices() if devices is None else list(devices)
    if len(devices) < dp * tp:
        raise ValueError(f"need {dp * tp} devices, have {len(devices)}")
    return Mesh(
        np.asarray(devices[: dp * tp]).reshape(dp, tp),
        (DATA_AXIS, FEATURE_AXIS),
    )


def _ceil_to(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k


def shard_glm_data_dp_tp(
    X_host,
    labels: np.ndarray,
    mesh: Mesh,
    weights: Optional[np.ndarray] = None,
    offsets: Optional[np.ndarray] = None,
    dtype=jnp.float32,
):
    """Tile host data over the (data, feature) mesh.

    Rows pad (weight 0) to a multiple of dp; columns pad (all-zero) to a
    multiple of tp.  Returns ``(features, labels, weights, offsets, d)``
    where ``features`` arrays carry leading (dp, tp) tile axes, the row
    arrays carry a leading (dp,) axis (replicated over feature by their
    sharding), and ``d`` is the ORIGINAL feature count (strip padding from
    the solution with ``w[:d]``).
    """
    import scipy.sparse as sp

    dp, tp = (mesh.shape[DATA_AXIS], mesh.shape[FEATURE_AXIS])
    n, d = X_host.shape
    rows_per = _ceil_to(n, dp) // dp
    cols_per = _ceil_to(d, tp) // tp

    labels = np.asarray(labels, np.float32)
    weights = (
        np.ones(n, np.float32) if weights is None
        else np.asarray(weights, np.float32)
    )
    offsets = (
        np.zeros(n, np.float32) if offsets is None
        else np.asarray(offsets, np.float32)
    )
    pad = dp * rows_per - n
    labels = np.concatenate([labels, np.zeros(pad, np.float32)])
    weights = np.concatenate([weights, np.zeros(pad, np.float32)])
    offsets = np.concatenate([offsets, np.zeros(pad, np.float32)])

    if sp.issparse(X_host):
        csr = X_host.tocsr()
        csr.sum_duplicates()
        tiles = []
        budget = 1
        for i in range(dp):
            row_block = csr[min(i * rows_per, n): min((i + 1) * rows_per, n)]
            row_tiles = []
            for j in range(tp):
                tile = row_block[:, j * cols_per: min((j + 1) * cols_per, d)]
                coo = tile.tocoo()
                row_tiles.append((coo.row, coo.col, coo.data))
                budget = max(budget, coo.nnz)
            tiles.append(row_tiles)
        mats = [
            [
                from_coo(r, c, v, rows_per, cols_per, budget, dtype)
                for (r, c, v) in row_tiles
            ]
            for row_tiles in tiles
        ]
        features = SparseMatrix(
            row_ids=jnp.stack(
                [jnp.stack([m.row_ids for m in row]) for row in mats]
            ),
            col_ids=jnp.stack(
                [jnp.stack([m.col_ids for m in row]) for row in mats]
            ),
            values=jnp.stack(
                [jnp.stack([m.values for m in row]) for row in mats]
            ),
            n_rows=rows_per,
            n_cols=cols_per,
        )
    else:
        dense = np.asarray(X_host, np.float32)
        dense = np.pad(
            dense, ((0, dp * rows_per - n), (0, tp * cols_per - d))
        )
        features = DenseMatrix(
            jnp.asarray(
                dense.reshape(dp, rows_per, tp, cols_per).transpose(
                    0, 2, 1, 3
                ),
                dtype,
            )
        )

    feat_sharding = NamedSharding(mesh, P(DATA_AXIS, FEATURE_AXIS))
    row_sharding = NamedSharding(mesh, P(DATA_AXIS))
    features = jax.tree.map(
        lambda x: jax.device_put(x, feat_sharding), features
    )
    put_rows = lambda a: jax.device_put(
        jnp.asarray(a.reshape(dp, rows_per)), row_sharding
    )
    return (
        features,
        put_rows(labels),
        put_rows(weights),
        put_rows(offsets),
        d,
    )


# shard_map spec layout shared by every TP solver: the six data args
# (features tiles, three row arrays, the w0 shard, the traced scalar) and a
# replicated SolveResult with w/grad staying feature-sharded.
_TP_IN_SPECS = (
    P(DATA_AXIS, FEATURE_AXIS),
    P(DATA_AXIS),
    P(DATA_AXIS),
    P(DATA_AXIS),
    P(FEATURE_AXIS),
    P(),
)
_TP_OUT_SPECS = SolveResult(
    w=P(FEATURE_AXIS),
    value=P(),
    grad=P(FEATURE_AXIS),
    iterations=P(),
    converged=P(),
    values=P(),
    grad_norms=P(),
)


@functools.lru_cache(maxsize=None)
def _make_tp_solver(task: str, mesh: Mesh, config: LBFGSConfig):
    """ONE jitted shard_map program per (task, mesh, config) — reused across
    calls, so a λ sweep or repeated fits pay a single compile per data shape
    (``reg_weight`` and the data are traced arguments)."""
    loss = losses_lib.get(task)

    def spmd(feat, lab, wts, off, w0_local, lam):
        local = jax.tree.map(lambda x: x[0, 0], feat)
        vg = _smooth_vg(loss, local, lab[0], wts[0], off[0])
        return lbfgs_solve(
            lambda wl: vg(wl, lam), w0_local, config, w_axis=FEATURE_AXIS
        )

    return jax.jit(
        shard_map(
            spmd,
            mesh=mesh,
            in_specs=_TP_IN_SPECS,
            # lbfgs_solve alone counts its objective evaluations
            out_specs=_TP_OUT_SPECS._replace(fn_evals=P()),
            check_vma=False,
        )
    )


def _smooth_vg(loss, local, lab, wts, off):
    """The sharded smooth GLM objective shared by every TP solver: margins
    psum over FEATURE, weighted loss + gradient psum over DATA, L2 term via
    a feature-axis psum'd dot.  Returns vg(wl, l2) -> (value, grad_slice)."""

    def vg(wl, l2):
        m = lax.psum(local.matvec(wl), FEATURE_AXIS) + off
        val = lax.psum(jnp.sum(wts * loss.value(m, lab)), DATA_AXIS)
        u = wts * loss.d1(m, lab)
        g = lax.psum(local.rmatvec(u), DATA_AXIS)
        val = val + 0.5 * l2 * lax.psum(jnp.vdot(wl, wl), FEATURE_AXIS)
        return val, g + l2 * wl

    return vg


def _padded_width(features, mesh) -> int:
    tp = mesh.shape[FEATURE_AXIS]
    if isinstance(features, SparseMatrix):
        return features.n_cols * tp  # n_cols is the per-tile width
    return features.data.shape[1] * features.data.shape[3]


@functools.lru_cache(maxsize=None)
def _make_tp_owlqn_solver(task: str, mesh: Mesh, config: OWLQNConfig):
    """ONE jitted shard_map OWL-QN program per (task, mesh, config) — the
    L1/elastic-net counterpart of :func:`_make_tp_solver`.  The smooth part
    (value/grad + L2) reduces exactly as in the L-BFGS solver; the L1 term,
    pseudo-gradient norms, and orthant machinery run on w shards with
    feature-axis psums (``owlqn_solve`` w_axis)."""
    loss = losses_lib.get(task)

    def spmd(feat, lab, wts, off, w0_local, l1, l2, mask_local):
        local = jax.tree.map(lambda x: x[0, 0], feat)
        vg = _smooth_vg(loss, local, lab[0], wts[0], off[0])
        return owlqn_solve(
            lambda wl: vg(wl, l2), w0_local, l1, config,
            l1_mask=mask_local, w_axis=FEATURE_AXIS,
        )

    return jax.jit(
        shard_map(
            spmd,
            mesh=mesh,
            in_specs=_TP_IN_SPECS[:5] + (P(), P(), P(FEATURE_AXIS)),
            # owlqn_solve counts its evaluations, its projection's clamps
            # and its answer's non-zeros, and says when it stalled
            out_specs=_TP_OUT_SPECS._replace(
                stalled=P(), fn_evals=P(), orthant_clamps=P(),
                nonzeros=P()),
            check_vma=False,
        )
    )


def tp_owlqn_solve(
    task: str,
    features,
    labels: Array,
    weights: Array,
    offsets: Array,
    mesh: Mesh,
    l1_weight: Array | float,
    l2_weight: Array | float = 0.0,
    w0: Optional[Array] = None,
    config: OWLQNConfig = OWLQNConfig(),
    l1_mask: Optional[Array] = None,
) -> SolveResult:
    """L1/elastic-net fit with rows sharded over DATA and features over
    FEATURE — very wide sparse models keep w, the L-BFGS history, AND the
    orthant state sharded.  ``l1_mask`` (global, column-padded width) exempts
    columns (e.g. the intercept) from the penalty."""
    d_padded = _padded_width(features, mesh)
    if w0 is None:
        w0 = jnp.zeros((d_padded,), jnp.float32)
    mask = (
        jnp.ones((d_padded,), jnp.float32) if l1_mask is None
        else jnp.asarray(l1_mask, jnp.float32)
    )
    fn = _make_tp_owlqn_solver(losses_lib.get(task).name, mesh, config)
    return fn(
        features, labels, weights, offsets, w0,
        jnp.asarray(l1_weight, jnp.float32),
        jnp.asarray(l2_weight, jnp.float32),
        mask,
    )


@functools.lru_cache(maxsize=None)
def _make_tp_tron_solver(task: str, mesh: Mesh, config: TRONConfig):
    """ONE jitted shard_map TRON program per (task, mesh, config): the
    trust-region Newton-CG outer/inner loops run on w shards with
    feature-axis psums (``tron_solve`` w_axis); each CG step's HVP is one
    (margin psum over FEATURE) + (gradient-side psum over DATA) pair — the
    reference's per-CG-step ``HessianVectorAggregator`` treeAggregate
    collapsed onto ICI."""
    loss = losses_lib.get(task)

    def spmd(feat, lab, wts, off, w0_local, lam):
        local = jax.tree.map(lambda x: x[0, 0], feat)
        lab_l, wts_l, off_l = lab[0], wts[0], off[0]
        vg = _smooth_vg(loss, local, lab_l, wts_l, off_l)

        def d2f(wl):
            m = lax.psum(local.matvec(wl), FEATURE_AXIS) + off_l
            return wts_l * loss.d2(m, lab_l)

        def hvp(wl, v, aux):
            dm = lax.psum(local.matvec(v), FEATURE_AXIS)
            return lax.psum(local.rmatvec(aux * dm), DATA_AXIS) + lam * v

        return tron_solve(
            lambda wl: vg(wl, lam), hvp, w0_local, config, d2_fn=d2f,
            w_axis=FEATURE_AXIS,
        )

    return jax.jit(
        shard_map(
            spmd,
            mesh=mesh,
            in_specs=_TP_IN_SPECS,
            # tron_solve counts its evaluations and its CG's steps
            out_specs=_TP_OUT_SPECS._replace(
                fn_evals=P(), cg_iterations=P(), rejected_steps=P(),
                boundary_exits=P()),
            check_vma=False,
        )
    )


def tp_tron_solve(
    task: str,
    features,
    labels: Array,
    weights: Array,
    offsets: Array,
    mesh: Mesh,
    reg_weight: Array | float = 0.0,
    w0: Optional[Array] = None,
    config: TRONConfig = TRONConfig(),
) -> SolveResult:
    """Trust-region Newton fit with rows sharded over DATA and features
    over FEATURE (L2 only, like the single-device TRON)."""
    d_padded = _padded_width(features, mesh)
    if w0 is None:
        w0 = jnp.zeros((d_padded,), jnp.float32)
    fn = _make_tp_tron_solver(losses_lib.get(task).name, mesh, config)
    return fn(
        features, labels, weights, offsets, w0,
        jnp.asarray(reg_weight, jnp.float32),
    )


def tp_lbfgs_solve(
    task: str,
    features,
    labels: Array,
    weights: Array,
    offsets: Array,
    mesh: Mesh,
    reg_weight: Array | float = 0.0,
    w0: Optional[Array] = None,
    config: LBFGSConfig = LBFGSConfig(),
) -> SolveResult:
    """Fit an L2 GLM with rows sharded over DATA and features over FEATURE.

    ``features``/``labels``... come from :func:`shard_glm_data_dp_tp`.
    Returns a replicated :class:`SolveResult` whose ``w`` is the full
    (column-padded) coefficient vector — slice ``w[:d]``.  ``reg_weight``
    is a traced scalar and the compiled program is memoized per
    (task, mesh, config): λ sweeps reuse one compile.
    """
    d_padded = _padded_width(features, mesh)
    if w0 is None:
        w0 = jnp.zeros((d_padded,), jnp.float32)
    fn = _make_tp_solver(losses_lib.get(task).name, mesh, config)
    return fn(
        features, labels, weights, offsets, w0,
        jnp.asarray(reg_weight, jnp.float32),
    )
