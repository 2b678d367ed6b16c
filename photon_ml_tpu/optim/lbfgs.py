"""L-BFGS, fully on-device.

The analogue of the reference's ``LBFGS`` optimizer (photon-lib
``com.linkedin.photon.ml.optimization.LBFGS``, which wraps Breeze's L-BFGS —
SURVEY.md §2).  Where the reference runs the two-loop recursion on the driver
JVM and ships coefficients to executors once per objective evaluation, here
the *entire* optimize loop — two-loop recursion, line search, convergence
check — is one jitted ``lax.while_loop``: zero host round-trips per
iteration.  For a distributed objective, the only cross-device traffic is the
``psum`` inside each value+gradient evaluation (the ``treeAggregate``
analogue).

Fixed-size circular history (default m=10, matching Breeze/reference
defaults): ``S``/``Y`` are ``(m, d)`` buffers indexed modulo m, and the
two-loop recursion is a pair of ``lax.scan``s over the history axis with
masking for not-yet-filled slots — static shapes, MXU-friendly, no Python
control flow.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optim.linesearch import (
    LineSearchConfig,
    ValueAndGrad,
    pnorm,
    pvdot,
    wolfe_line_search,
)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class LBFGSConfig:
    """Mirrors the reference's optimizer config surface
    (maxNumIterations, tolerance, numCorrections)."""

    max_iters: int = 100
    # Relative convergence tolerance on both objective decrease and gradient
    # norm (Breeze-style: ||g|| / max(1, ||g0||) <= tol).
    tolerance: float = 1e-7
    history: int = 10
    line_search: LineSearchConfig = LineSearchConfig()


class SolveResult(NamedTuple):
    """What every solver returns (the reference returns a model + an
    ``OptimizationStatesTracker``; values/grad_norms are that tracker)."""

    w: Array
    value: Array
    grad: Array
    iterations: Array  # int32
    converged: Array  # bool
    values: Array  # (max_iters+1,) objective per iteration (nan-padded)
    grad_norms: Array  # (max_iters+1,)
    # True when the solve EXITED without meeting the gradient-norm
    # tolerance (objective-plateau or failed-line-search exit) —
    # distinct from ``converged`` so callers can tell a constrained
    # stationary point from a stall.  None for solvers that fold the
    # plateau exit into ``converged`` (the historical contract); SPG
    # reports it, and so does ``owlqn_solve``: a line search that found no
    # decrease at a point that does not meet the pseudo-gradient test
    # (its relative-decrease exit stays folded into ``converged``).
    stalled: Array | None = None
    # Objective (value+gradient) evaluations the solve made, the starting
    # one included, counted inside the solve; None for solvers that do not
    # count them.  ``lbfgs_solve``, ``tron_solve`` and ``owlqn_solve``
    # (every line-search trial is one) report it.
    fn_evals: Array | None = None
    # What a trust-region Newton solve counted in its loops' states
    # (``tron_solve``); None for every other solver.  ``cg_iterations``:
    # Steihaug CG steps over all outer iterations, which is also the count
    # of Hessian-vector products (one per CG step); ``rejected_steps``:
    # outer iterations whose step was refused (rho <= eta0);
    # ``boundary_exits``: CG runs that ended on the trust region's boundary.
    cg_iterations: Array | None = None
    rejected_steps: Array | None = None
    boundary_exits: Array | None = None
    # What an orthant-wise solve counted (``owlqn_solve``); None for every
    # other solver.  ``orthant_clamps``: coordinates the projection onto
    # the chosen orthant set to zero, summed over the accepted steps;
    # ``nonzeros``: penalised coefficients (``l1_mask`` != 0) of the answer
    # that are not exactly zero.
    orthant_clamps: Array | None = None
    nonzeros: Array | None = None


#: The counts a solver family adds to :class:`SolveResult`, keyed by the
#: field that marks the family (None in every other solve's result): each
#: field with the counter it feeds (None: a ``solver`` span attribute
#: only) and the type the span records it as.  ``grid_loop`` reads exactly
#: these; SPG's ``stalled`` marks no family and is not read.
SOLVE_COUNTS = {
    # trust-region Newton (``tron_solve``)
    "cg_iterations": (
        ("cg_iterations", "solver_cg_iterations", int),
        ("rejected_steps", None, int),
        ("boundary_exits", None, int),
    ),
    # orthant-wise (``owlqn_solve``)
    "orthant_clamps": (
        ("stalled", "solver_stalled_total", bool),
        ("orthant_clamps", "solver_orthant_clamps_total", int),
        ("nonzeros", None, int),
    ),
}


class _LBFGSState(NamedTuple):
    w: Array
    value: Array
    grad: Array
    S: Array  # (m, d) coefficient deltas
    Y: Array  # (m, d) gradient deltas
    rho: Array  # (m,) 1 / <s, y>;  0 marks an empty/skipped slot
    gamma: Array  # initial-Hessian scale <s,y>/<y,y>
    k: Array  # iteration counter
    fn_evals: Array  # objective evaluations so far
    n_pairs: Array  # total pairs ever stored (for masking)
    done: Array
    converged: Array
    values: Array
    grad_norms: Array


def _two_loop(grad: Array, S: Array, Y: Array, rho: Array, gamma: Array,
              k_pairs: Array, w_axis: str | None = None) -> Array:
    """Two-loop recursion over the circular (S, Y) history.

    Slots with index >= k_pairs (never written) or rho == 0 (curvature-skipped)
    are masked out.  Newest pair is at (k_pairs - 1) mod m.
    """
    m = S.shape[0]
    # Order indices newest → oldest for the first loop.
    offsets = jnp.arange(m)
    newest = (k_pairs - 1) % jnp.maximum(m, 1)
    idx_new_to_old = (newest - offsets) % m
    valid = offsets < jnp.minimum(k_pairs, m)

    def first_loop(q, i_and_valid):
        i, is_valid = i_and_valid
        alpha = rho[i] * pvdot(S[i], q, w_axis)
        alpha = jnp.where(jnp.logical_and(is_valid, rho[i] > 0), alpha, 0.0)
        return q - alpha * Y[i], alpha

    q, alphas = lax.scan(first_loop, grad, (idx_new_to_old, valid))

    r = gamma * q

    def second_loop(r, scan_in):
        i, is_valid, alpha = scan_in
        beta = rho[i] * pvdot(Y[i], r, w_axis)
        corr = jnp.where(jnp.logical_and(is_valid, rho[i] > 0),
                         alpha - beta, 0.0)
        return r + corr * S[i], None

    # Oldest → newest: reverse the scan inputs.
    r, _ = lax.scan(
        second_loop, r, (idx_new_to_old[::-1], valid[::-1], alphas[::-1])
    )
    return r


def update_history(
    S: Array, Y: Array, rho: Array, gamma: Array, n_pairs: Array,
    s_vec: Array, y_vec: Array, w_axis: str | None = None,
) -> tuple[Array, Array, Array, Array, Array]:
    """Insert a curvature pair into the circular history, skipping it when
    <s, y> is not safely positive (standard safeguard).  Shared by L-BFGS
    and OWL-QN so the history rules cannot drift apart."""
    m = S.shape[0]
    sy = pvdot(s_vec, y_vec, w_axis)
    good = sy > 1e-10 * pnorm(s_vec, w_axis) * pnorm(y_vec, w_axis)
    slot = n_pairs % m
    S = jnp.where(good, S.at[slot].set(s_vec), S)
    Y = jnp.where(good, Y.at[slot].set(y_vec), Y)
    rho = jnp.where(good, rho.at[slot].set(1.0 / sy), rho)
    gamma = jnp.where(good, sy / pvdot(y_vec, y_vec, w_axis), gamma)
    n_pairs = jnp.where(good, n_pairs + 1, n_pairs)
    return S, Y, rho, gamma, n_pairs


def lbfgs_solve(
    value_and_grad: ValueAndGrad,
    w0: Array,
    config: LBFGSConfig = LBFGSConfig(),
    w_axis: str | None = None,
) -> SolveResult:
    """Minimize via L-BFGS.  Pure function of (w0, closure data); safe to wrap
    in ``jit`` / ``vmap`` (the vmap'd form is what batched per-entity
    random-effect solves use) / ``shard_map`` (distributed objectives).

    ``w_axis``: mesh axis name when ``w0`` (and the objective's gradient) are
    feature-dim SHARDS of a wide coefficient vector (tensor parallelism —
    SURVEY.md §5.7 scale axis (b)).  Every w-space inner product and norm in
    the two-loop recursion, history update, and line search then reduces
    over that axis, so the solver runs an exact replica of the single-device
    iteration on sharded state."""
    m = config.history
    d = w0.shape[0]
    dtype = w0.dtype

    f0, g0 = value_and_grad(w0)
    g0_norm = pnorm(g0, w_axis)
    tol_scale = jnp.maximum(1.0, g0_norm)

    n_track = config.max_iters + 1
    values0 = jnp.full((n_track,), jnp.nan, dtype).at[0].set(f0.astype(dtype))
    gnorms0 = jnp.full((n_track,), jnp.nan, dtype).at[0].set(g0_norm)

    init = _LBFGSState(
        w=w0,
        value=f0,
        grad=g0,
        S=jnp.zeros((m, d), dtype),
        Y=jnp.zeros((m, d), dtype),
        rho=jnp.zeros((m,), dtype),
        gamma=jnp.asarray(1.0, dtype),
        k=jnp.asarray(0, jnp.int32),
        fn_evals=jnp.asarray(1, jnp.int32),
        n_pairs=jnp.asarray(0, jnp.int32),
        done=g0_norm <= config.tolerance * tol_scale,
        converged=g0_norm <= config.tolerance * tol_scale,
        values=values0,
        grad_norms=gnorms0,
    )

    def cond(s: _LBFGSState):
        return jnp.logical_and(~s.done, s.k < config.max_iters)

    def body(s: _LBFGSState):
        with jax.named_scope("lbfgs.two_loop"):
            direction = -_two_loop(
                s.grad, s.S, s.Y, s.rho, s.gamma, s.n_pairs, w_axis
            )
            dg = pvdot(direction, s.grad, w_axis)
            # Fall back to steepest descent if the history produced a
            # non-descent direction (can happen after skipped updates).
            bad = dg >= 0.0
            direction = jnp.where(bad, -s.grad, direction)

        # First iteration: scale the initial step like Breeze
        # (1 / ||g||, capped at 1) so the unit quasi-Newton step is sane later.
        first = s.n_pairs == 0
        init_step = jnp.where(
            first, jnp.minimum(1.0, 1.0 / pnorm(s.grad, w_axis)), 1.0
        )

        with jax.named_scope("lbfgs.linesearch"):
            ls = wolfe_line_search(
                value_and_grad, s.w, s.value, s.grad, direction,
                initial_step=init_step, config=config.line_search,
                w_axis=w_axis,
            )

        with jax.named_scope("lbfgs.update"):
            S, Y, rho, gamma, n_pairs = update_history(
                s.S, s.Y, s.rho, s.gamma, s.n_pairs, ls.w - s.w,
                ls.grad - s.grad, w_axis,
            )

        k = s.k + 1
        g_norm = pnorm(ls.grad, w_axis)
        # Converged when the gradient is small (relative, Breeze-style) or the
        # objective stops moving (relative function decrease).
        rel_impr = jnp.abs(s.value - ls.value) / jnp.maximum(
            jnp.abs(s.value), 1e-12
        )
        # A failed line search that also made no progress ends the run; the
        # incumbent iterate is kept (never adopt a trial point with a higher
        # objective than the current one).  Convergence is measured at the
        # iterate actually returned: the gradient test at the kept point on a
        # stalled step, the usual gradient/function-decrease tests otherwise.
        stalled = jnp.logical_and(~ls.success, ls.value >= s.value)
        converged = jnp.where(
            stalled,
            pnorm(s.grad, w_axis) <= config.tolerance * tol_scale,
            jnp.logical_or(
                g_norm <= config.tolerance * tol_scale,
                rel_impr <= config.tolerance * 1e-2,
            ),
        )
        w_next = jnp.where(stalled, s.w, ls.w)
        value_next = jnp.where(stalled, s.value, ls.value)
        grad_next = jnp.where(stalled, s.grad, ls.grad)

        return _LBFGSState(
            w=w_next,
            value=value_next,
            grad=grad_next,
            S=S, Y=Y, rho=rho, gamma=gamma,
            k=k,
            fn_evals=s.fn_evals + ls.n_evals,
            n_pairs=n_pairs,
            done=jnp.logical_or(converged, stalled),
            converged=converged,
            values=s.values.at[k].set(value_next.astype(s.values.dtype)),
            grad_norms=s.grad_norms.at[k].set(
                jnp.where(stalled, pnorm(s.grad, w_axis), g_norm)
            ),
        )

    final = lax.while_loop(cond, body, init)
    return SolveResult(
        w=final.w,
        value=final.value,
        grad=final.grad,
        iterations=final.k,
        converged=final.converged,
        values=final.values,
        grad_norms=final.grad_norms,
        fn_evals=final.fn_evals,
    )
