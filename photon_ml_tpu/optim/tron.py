"""TRON: Trust-Region Newton method, fully on-device.

The analogue of the reference's ``TRON`` optimizer (photon-lib; a port of
LIBLINEAR's trust-region Newton — SURVEY.md §2; BASELINE.json: "TRON
trust-region Newton with on-device Hessian-vector products").  Outer loop:
propose a step by approximately minimizing the quadratic model within a trust
region via Steihaug conjugate gradient; accept/reject by the actual-vs-
predicted reduction ratio; grow/shrink the radius.  Inner CG needs one
Hessian-vector product per step — in the reference that is one
``HessianVectorAggregator`` ``treeAggregate`` round per CG step
(SURVEY.md §3.1); here it is one (sparse) matvec pair, with ``psum`` when
distributed.

The GLM structure is exploited exactly as the reference does: the Hessian at
a fixed ``w`` is ``Xᵀ diag(weight·d2(m)) X + λI``, so ``d2_weights`` is
computed ONCE per accepted outer iterate and every CG step reuses it
(``hvp_fn(w, v, aux)`` with cached ``aux``).

Both loops are ``lax.while_loop``s inside one jitted program — no host
round-trips, matching lbfgs.py.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optim.lbfgs import SolveResult
from photon_ml_tpu.optim.linesearch import ValueAndGrad, pnorm, pvdot

Array = jax.Array

# hvp_fn(w, v, aux) -> H(w) @ v, where aux = d2_fn(w) is per-iterate cache.
HvpFn = Callable[[Array, Array, object], Array]
D2Fn = Callable[[Array], object]


@dataclasses.dataclass(frozen=True)
class TRONConfig:
    max_iters: int = 100
    tolerance: float = 1e-7
    max_cg_iters: int = 50
    # CG forcing tolerance: stop when ||r|| <= cg_tol · ||g|| (LIBLINEAR xi).
    cg_tol: float = 0.1
    # Step-acceptance threshold and radius-update constants (LIBLINEAR).
    eta0: float = 1e-4
    eta1: float = 0.25
    eta2: float = 0.75
    sigma1: float = 0.25
    sigma2: float = 0.5
    sigma3: float = 4.0


class _CGState(NamedTuple):
    s: Array  # current step estimate
    r: Array  # residual -g - H s
    p: Array  # search direction
    rr: Array  # <r, r>
    i: Array
    done: Array
    hit_boundary: Array


def _steihaug_cg(
    hvp: Callable[[Array], Array],
    g: Array,
    delta: Array,
    max_iters: int,
    tol: Array,
    w_axis: Optional[str] = None,
) -> tuple[Array, Array, Array, Array]:
    """Approximately minimize g·s + ½ sᵀHs subject to ‖s‖ ≤ delta.

    Returns (s, r, hit_boundary, steps) with r = -g - H·s the final
    residual (kept consistent with s even on boundary exits, so sᵀHs is
    recoverable without another HVP) and ``steps`` the CG iterations made,
    one Hessian-vector product each.  Negative-curvature and radius-crossing
    cases move to the trust-region boundary along the current direction.
    """
    d = g.shape[0]
    dtype = g.dtype

    def boundary_tau(s, p):
        # Solve ‖s + τ p‖ = delta for τ ≥ 0.
        pp = pvdot(p, p, w_axis)
        sp = pvdot(s, p, w_axis)
        ss = pvdot(s, s, w_axis)
        disc = jnp.maximum(sp * sp + pp * (delta * delta - ss), 0.0)
        return (-sp + jnp.sqrt(disc)) / jnp.maximum(pp, 1e-30)

    init = _CGState(
        s=jnp.zeros((d,), dtype),
        r=-g,
        p=-g,
        rr=pvdot(g, g, w_axis),
        i=jnp.asarray(0, jnp.int32),
        done=pnorm(g, w_axis) <= tol,
        hit_boundary=jnp.asarray(False),
    )

    def cond(c: _CGState):
        return jnp.logical_and(~c.done, c.i < max_iters)

    def body(c: _CGState):
        Hp = hvp(c.p)
        pHp = pvdot(c.p, Hp, w_axis)

        # Negative curvature → go to the boundary along p.
        neg_curv = pHp <= 0.0

        alpha = c.rr / jnp.where(pHp > 0, pHp, 1.0)
        s_next = c.s + alpha * c.p
        crosses = pnorm(s_next, w_axis) >= delta

        take_boundary = jnp.logical_or(neg_curv, crosses)
        tau = boundary_tau(c.s, c.p)
        step_len = jnp.where(take_boundary, tau, alpha)
        s_new = c.s + step_len * c.p
        # Maintain r = -g - H s for the RETURNED step, including the
        # boundary case, so callers can recover sᵀHs from r without an
        # extra Hessian-vector product.
        r_new = c.r - step_len * Hp

        rr_new = pvdot(r_new, r_new, w_axis)
        small = jnp.sqrt(rr_new) <= tol
        beta = rr_new / jnp.maximum(c.rr, 1e-30)
        p_new = r_new + beta * c.p

        done = jnp.logical_or(take_boundary, small)
        return _CGState(
            s=s_new,
            r=r_new,
            p=jnp.where(take_boundary, c.p, p_new),
            rr=rr_new,
            i=c.i + 1,
            done=done,
            hit_boundary=jnp.logical_or(c.hit_boundary, take_boundary),
        )

    with jax.named_scope("tron.cg"):
        final = lax.while_loop(cond, body, init)
    return final.s, final.r, final.hit_boundary, final.i


class _TRONState(NamedTuple):
    w: Array
    value: Array
    grad: Array
    aux: object  # cached d2 weights for the current iterate
    delta: Array  # trust-region radius
    k: Array
    done: Array
    converged: Array
    values: Array
    grad_norms: Array
    # Counted per solve (SolveResult's TRON-only fields).
    cg_iterations: Array
    rejected_steps: Array
    boundary_exits: Array


def tron_solve(
    value_and_grad: ValueAndGrad,
    hvp_fn: HvpFn,
    w0: Array,
    config: TRONConfig = TRONConfig(),
    d2_fn: Optional[D2Fn] = None,
    w_axis: Optional[str] = None,
) -> SolveResult:
    """Minimize via trust-region Newton-CG.

    ``hvp_fn(w, v, aux)`` must return the (regularized) Hessian-vector
    product; ``d2_fn(w)`` produces the reusable per-iterate cache passed as
    ``aux`` (pass None to recompute inside hvp_fn each call).

    ``w_axis``: mesh axis name when ``w0``/gradients/HVPs are feature-dim
    SHARDS (tensor parallelism) — every w-space inner product and norm in
    the outer loop and the Steihaug CG then reduces over that axis.
    """
    dtype = w0.dtype
    make_aux = d2_fn if d2_fn is not None else (lambda w: jnp.zeros((0,), dtype))

    f0, g0 = value_and_grad(w0)
    g0_norm = pnorm(g0, w_axis)
    tol_scale = jnp.maximum(1.0, g0_norm)

    n_track = config.max_iters + 1
    values0 = jnp.full((n_track,), jnp.nan, dtype).at[0].set(f0.astype(dtype))
    gnorms0 = jnp.full((n_track,), jnp.nan, dtype).at[0].set(g0_norm)

    init = _TRONState(
        w=w0,
        value=f0,
        grad=g0,
        aux=make_aux(w0),
        delta=g0_norm,  # LIBLINEAR: initial radius = ||g0||
        k=jnp.asarray(0, jnp.int32),
        done=g0_norm <= config.tolerance * tol_scale,
        converged=g0_norm <= config.tolerance * tol_scale,
        values=values0,
        grad_norms=gnorms0,
        cg_iterations=jnp.asarray(0, jnp.int32),
        rejected_steps=jnp.asarray(0, jnp.int32),
        boundary_exits=jnp.asarray(0, jnp.int32),
    )

    def cond(s: _TRONState):
        return jnp.logical_and(~s.done, s.k < config.max_iters)

    def body(s: _TRONState):
        cg_tol = config.cg_tol * pnorm(s.grad, w_axis)
        step, residual, hit_boundary, cg_steps = _steihaug_cg(
            lambda v: hvp_fn(s.w, v, s.aux),
            s.grad,
            s.delta,
            config.max_cg_iters,
            cg_tol,
            w_axis,
        )

        w_try = s.w + step
        f_try, g_try = value_and_grad(w_try)

        gs = pvdot(s.grad, step, w_axis)
        # r = -g - H·s  ⇒  sᵀHs = -s·r - s·g; saves one HVP (and its psum
        # round when distributed) per outer iteration, as LIBLINEAR does.
        sHs = -pvdot(step, residual, w_axis) - gs
        pred = -(gs + 0.5 * sHs)
        ared = s.value - f_try
        rho = ared / jnp.where(pred > 0, pred, 1e-30)

        accept = jnp.logical_and(rho > config.eta0, pred > 0)
        w_new = jnp.where(accept, w_try, s.w)
        f_new = jnp.where(accept, f_try, s.value)
        g_new = jnp.where(accept, g_try, s.grad)
        aux_new = jax.tree.map(
            lambda a, b: jnp.where(accept, a, b), make_aux(w_try), s.aux
        )

        # Radius update (LIBLINEAR-style).
        snorm = pnorm(step, w_axis)
        delta = jnp.where(
            rho < config.eta1,
            jnp.maximum(config.sigma1 * snorm, config.sigma2 * s.delta)
            * jnp.where(rho < config.eta0, config.sigma2, 1.0),
            jnp.where(
                rho > config.eta2,
                jnp.maximum(s.delta, config.sigma3 * snorm),
                s.delta,
            ),
        )
        delta = jnp.maximum(delta, 1e-20)

        k = s.k + 1
        g_norm = pnorm(g_new, w_axis)
        rel_impr = jnp.where(
            accept,
            jnp.abs(ared) / jnp.maximum(jnp.abs(s.value), 1e-12),
            jnp.asarray(jnp.inf, dtype),
        )
        converged = jnp.logical_or(
            g_norm <= config.tolerance * tol_scale,
            rel_impr <= config.tolerance * 1e-2,
        )
        # If the radius collapsed, no further progress is possible.
        stalled = delta <= 1e-18

        return _TRONState(
            w=w_new,
            value=f_new,
            grad=g_new,
            aux=aux_new,
            delta=delta,
            k=k,
            done=jnp.logical_or(converged, stalled),
            converged=converged,
            values=s.values.at[k].set(f_new.astype(s.values.dtype)),
            grad_norms=s.grad_norms.at[k].set(g_norm),
            cg_iterations=s.cg_iterations + cg_steps,
            rejected_steps=s.rejected_steps + (~accept).astype(jnp.int32),
            boundary_exits=s.boundary_exits + hit_boundary.astype(jnp.int32),
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
        # One value+gradient per outer iteration, accepted or not, and the
        # starting one; one Hessian-vector product per CG step, so
        # cg_iterations is the count of both.
        fn_evals=final.k + 1,
        cg_iterations=final.cg_iterations,
        rejected_steps=final.rejected_steps,
        boundary_exits=final.boundary_exits,
    )
