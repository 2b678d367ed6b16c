"""OWL-QN: Orthant-Wise Limited-memory Quasi-Newton, fully on-device.

The analogue of the reference's ``OWLQN`` optimizer (photon-lib wraps
Breeze's ``OWLQN`` for L1 / elastic-net — SURVEY.md §2; BASELINE.json:
"L1 / elastic-net (OWL-QN)").  Minimizes ``f(w) + λ·‖w∘mask‖₁`` where f is
the smooth (optionally L2-regularized) part, per Andrew & Gao (2007):

- the *pseudo-gradient* replaces the gradient where ``w_i = 0`` (picks the
  steepest one-sided derivative, or 0 inside the subdifferential interval);
- the quasi-Newton direction (two-loop over smooth-gradient pairs) is
  projected onto the pseudo-gradient's descent orthant;
- each trial point is projected back onto the chosen orthant (coordinates
  that would cross zero are clamped to zero), with Armijo backtracking.

Everything is one jitted ``lax.while_loop`` — same zero-host-round-trip
property as lbfgs.py, and ``vmap``-able for batched per-entity L1 solves.
``l1_mask`` lets callers exempt the intercept column from the penalty
(the reference never regularizes the intercept).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optim.lbfgs import SolveResult, _two_loop, update_history
from photon_ml_tpu.optim.linesearch import ValueAndGrad, pnorm, pvdot

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class OWLQNConfig:
    max_iters: int = 100
    tolerance: float = 1e-7
    history: int = 10
    max_line_search_evals: int = 30
    armijo_c1: float = 1e-4
    backtrack: float = 0.5


class _OWLQNState(NamedTuple):
    w: Array
    value: Array  # full value incl. L1 term
    grad: Array  # smooth-part gradient
    S: Array
    Y: Array
    rho: Array
    gamma: Array
    k: Array
    n_pairs: Array
    done: Array
    converged: Array
    values: Array
    grad_norms: Array  # pseudo-gradient norms
    fn_evals: Array  # value+gradient evaluations so far (every trial is one)
    clamps: Array  # coordinates the projection zeroed, over accepted steps


def _pseudo_gradient(w: Array, grad: Array, l1: Array, mask: Array) -> Array:
    """Steepest-descent direction of f + λ‖w‖₁ (Andrew & Gao eq. 4)."""
    lam = l1 * mask
    at_zero_pos = grad + lam  # right derivative at w_i = 0
    at_zero_neg = grad - lam  # left derivative at w_i = 0
    pg_zero = jnp.where(
        at_zero_neg > 0, at_zero_neg, jnp.where(at_zero_pos < 0, at_zero_pos, 0.0)
    )
    return jnp.where(w != 0, grad + lam * jnp.sign(w), pg_zero)


def owlqn_solve(
    value_and_grad: ValueAndGrad,
    w0: Array,
    l1_weight: Array | float,
    config: OWLQNConfig = OWLQNConfig(),
    l1_mask: Optional[Array] = None,
    w_axis: Optional[str] = None,
) -> SolveResult:
    """Minimize ``f(w) + l1_weight·Σ_i mask_i·|w_i|``.

    ``value_and_grad`` evaluates only the smooth part f.  Returned
    ``SolveResult.grad`` is the final *pseudo-gradient* (its norm is the
    convergence quantity, matching Breeze's OWLQN ``adjustedGradient``).

    ``w_axis``: mesh axis name when ``w0`` (and f's gradient) are
    feature-dim SHARDS of a wide coefficient vector (tensor parallelism);
    every w-space reduction — the L1 term, pseudo-gradient norms, the
    two-loop recursion, history update, Armijo products — then reduces over
    that axis, so the sharded iteration replicates the single-device one
    (the orthant machinery itself is elementwise).
    """
    m = config.history
    d = w0.shape[0]
    dtype = w0.dtype
    l1 = jnp.asarray(l1_weight, dtype)
    mask = (
        jnp.ones((d,), dtype) if l1_mask is None else jnp.asarray(l1_mask, dtype)
    )

    def full_value(w, smooth_value):
        return smooth_value + l1 * pvdot(mask, jnp.abs(w), w_axis)

    def count(flags):
        n = jnp.sum(flags, dtype=jnp.int32)
        return lax.psum(n, w_axis) if w_axis is not None else n

    f0_smooth, g0 = value_and_grad(w0)
    f0 = full_value(w0, f0_smooth)
    pg0 = _pseudo_gradient(w0, g0, l1, mask)
    pg0_norm = pnorm(pg0, w_axis)
    tol_scale = jnp.maximum(1.0, pg0_norm)

    n_track = config.max_iters + 1
    values0 = jnp.full((n_track,), jnp.nan, dtype).at[0].set(f0.astype(dtype))
    gnorms0 = jnp.full((n_track,), jnp.nan, dtype).at[0].set(pg0_norm)

    init = _OWLQNState(
        w=w0, value=f0, grad=g0,
        S=jnp.zeros((m, d), dtype),
        Y=jnp.zeros((m, d), dtype),
        rho=jnp.zeros((m,), dtype),
        gamma=jnp.asarray(1.0, dtype),
        k=jnp.asarray(0, jnp.int32),
        n_pairs=jnp.asarray(0, jnp.int32),
        done=pg0_norm <= config.tolerance * tol_scale,
        converged=pg0_norm <= config.tolerance * tol_scale,
        values=values0,
        grad_norms=gnorms0,
        fn_evals=jnp.asarray(1, jnp.int32),
        clamps=jnp.asarray(0, jnp.int32),
    )

    def cond(s: _OWLQNState):
        return jnp.logical_and(~s.done, s.k < config.max_iters)

    def body(s: _OWLQNState):
        with jax.named_scope("owlqn.pseudo_gradient"):
            pg = _pseudo_gradient(s.w, s.grad, l1, mask)

        with jax.named_scope("owlqn.direction"):
            direction = -_two_loop(
                pg, s.S, s.Y, s.rho, s.gamma, s.n_pairs, w_axis
            )
            # Project the direction onto the descent orthant of -pg: zero
            # any coordinate whose sign disagrees (Andrew & Gao §3.2
            # "alignment").
            direction = jnp.where(direction * (-pg) > 0, direction, 0.0)
            # Degenerate (all-zero) direction → steepest descent on pg.
            deg = pvdot(direction, direction, w_axis) == 0.0
            direction = jnp.where(deg, -pg, direction)

            # Orthant choice: sign(w) where nonzero, else sign of the step.
            xi = jnp.where(s.w != 0, jnp.sign(s.w), jnp.sign(-pg))

        first = s.n_pairs == 0
        t = jnp.where(
            first, jnp.minimum(1.0, 1.0 / pnorm(pg, w_axis)), 1.0
        )

        def project(w):
            # Clamp coordinates that crossed out of the chosen orthant.
            return jnp.where(w * xi >= 0, w, 0.0)

        def trial(t):
            w = project(s.w + t * direction)
            smooth, grad = value_and_grad(w)
            return w, full_value(w, smooth), grad

        def ls_cond(ls):
            t, w, value, _, n = ls
            # Armijo on the PROJECTED step (Andrew & Gao / Breeze OWLQN):
            # the trial point is orthant-projected, so the realized step is
            # w - s.w, not t*direction; using <pg, w - s.w> keeps the
            # sufficient-decrease threshold correctly scaled when the
            # projection clamps coordinates.  The inequality is non-strict:
            # a fully-clamped trial (w == s.w, dg_proj == 0) must keep
            # backtracking — a smaller t clamps fewer coordinates — rather
            # than be accepted as a zero step.
            dg_proj = pvdot(pg, w - s.w, w_axis)
            return jnp.logical_and(
                value >= s.value + config.armijo_c1 * dg_proj,
                n < config.max_line_search_evals,
            )

        def ls_body(ls):
            t, _, _, _, n = ls
            t_next = t * config.backtrack
            w, value, grad = trial(t_next)
            return (t_next, w, value, grad, n + 1)

        with jax.named_scope("owlqn.linesearch"):
            w1, f1, g1 = trial(t)
            t, w_new, f_new, g_new, n_trials = lax.while_loop(
                ls_cond, ls_body, (t, w1, f1, g1, jnp.asarray(1, jnp.int32))
            )

        # History pairs use the SMOOTH gradient (standard OWL-QN).
        S, Y, rho, gamma, n_pairs = update_history(
            s.S, s.Y, s.rho, s.gamma, s.n_pairs, w_new - s.w, g_new - s.grad,
            w_axis,
        )

        k = s.k + 1
        pg_new = _pseudo_gradient(w_new, g_new, l1, mask)
        pg_norm = pnorm(pg_new, w_axis)
        rel_impr = jnp.abs(s.value - f_new) / jnp.maximum(jnp.abs(s.value), 1e-12)
        # Line search made no progress: end the run and keep the incumbent
        # iterate (never adopt a trial point with a higher objective).
        # Convergence is measured at the iterate actually returned: the
        # pseudo-gradient test at the kept point on a stalled step, the usual
        # tests otherwise.  The relative-decrease test is taken only on a step
        # whose direction came from a history of two pairs or more.  From an
        # empty history the step is the normalised steepest-descent step
        # (length min(1, |pg|)), from one pair the same direction rescaled
        # by that pair's <s,y>/<y,y>: where the curvature along pg is large
        # the search cuts both down, and their decrease says how short the
        # step was, not how near the answer is.  On a warm start at a large
        # objective it read as convergence after one iteration (and, with
        # only the empty history exempt, after two).
        stalled = f_new >= s.value
        converged = jnp.where(
            stalled,
            pnorm(pg, w_axis) <= config.tolerance * tol_scale,
            jnp.logical_or(
                pg_norm <= config.tolerance * tol_scale,
                jnp.logical_and(
                    s.n_pairs >= 2, rel_impr <= config.tolerance * 1e-2
                ),
            ),
        )
        w_keep = jnp.where(stalled, s.w, w_new)
        f_keep = jnp.where(stalled, s.value, f_new)
        g_keep = jnp.where(stalled, s.grad, g_new)
        pg_norm = jnp.where(
            stalled, pnorm(pg, w_axis), pnorm(pg_new, w_axis)
        )
        # What the projection zeroed on the accepted step: coordinates of
        # the unprojected trial point that left the chosen orthant.
        clamped = count((s.w + t * direction) * xi < 0)

        return _OWLQNState(
            w=w_keep, value=f_keep, grad=g_keep,
            S=S, Y=Y, rho=rho, gamma=gamma,
            k=k, n_pairs=n_pairs,
            done=jnp.logical_or(converged, stalled),
            converged=converged,
            values=s.values.at[k].set(f_keep.astype(s.values.dtype)),
            grad_norms=s.grad_norms.at[k].set(pg_norm),
            fn_evals=s.fn_evals + n_trials,
            clamps=s.clamps + jnp.where(stalled, 0, clamped),
        )

    final = lax.while_loop(cond, body, init)
    pg_final = _pseudo_gradient(final.w, final.grad, l1, mask)
    return SolveResult(
        w=final.w,
        value=final.value,
        grad=pg_final,
        iterations=final.k,
        converged=final.converged,
        values=final.values,
        grad_norms=final.grad_norms,
        stalled=jnp.logical_and(final.done, ~final.converged),
        fn_evals=final.fn_evals,
        orthant_clamps=final.clamps,
        nonzeros=count(jnp.logical_and(final.w != 0, mask != 0)),
    )
