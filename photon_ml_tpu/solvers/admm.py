"""Consensus-ADMM for L1/elastic-net GLMs — one all-reduce per outer iteration.

The existing distributed solvers (L-BFGS / OWL-QN / TRON over ``shard_map``)
pay one fused ``psum`` per objective evaluation — several per line search,
dozens per solve.  Consensus ADMM (Boyd et al. §7.2; "Unwrapping ADMM"
/ PAPERS.md) restructures the solve so the only cross-shard communication is
ONE fixed-size all-reduce per OUTER iteration:

- **x-update** (per shard, zero communication): each shard s minimizes its
  local objective plus a proximal tie to the consensus,
  ``x_s = argmin f_s(x) + ρ/2·‖x − (z − u_s)‖²`` — warm-started local
  L-BFGS for any GLM loss, or (linear task) a CLOSED FORM through a cached
  eigendecomposition of the local Gram matrix: ``(G_s + ρI)x = b_s + ρv``
  solves as ``Q((Qᵀ(b_s + ρv)) / (Λ + ρ))``, the "transpose reduction"
  trick — the factorization is computed once per dataset and survives every
  outer iteration AND every adaptive-ρ change.
- **consensus z-update** (replicated): with the whole L1/L2 regularizer
  carried by z, the update is one soft-threshold,
  ``z = S_{λ₁·mask/(λ₂+Nρ)}(ρ·Σ_s(x̂_s + u_s)/(λ₂+Nρ))``, where
  ``x̂ = α·x + (1−α)·z`` is the over-relaxed iterate (α ∈ [1, 1.8]).
- **dual update** (per shard): ``u_s += x̂_s − z``.

The single all-reduce carries ``[Σ(x̂+u), Σx, ‖x‖², ‖u‖², f_s(x_s),
iters]`` — 2d+4 floats.  The exact primal residual falls out of the
identity ``Σ‖x_s − z‖² = Σ‖x_s‖² − 2⟨Σx_s, z⟩ + N‖z‖²``, so residual-based
stopping and adaptive ρ (μ/τ rule, with the scaled dual rescaled when ρ
changes) need nothing beyond that one reduce.  ρ is a TRACED argument of
the one compiled step program, so adaptation never recompiles.

Two sharding modes, same math: a real mesh (``shard_map`` + ``lax.psum``
over ``parallel.distributed.DATA_AXIS`` — multihost-ready, nothing here is
host-count-aware) when ≥2 devices participate, or LOGICAL shards (leading
shard axis + ``vmap`` x-updates + an axis-0 sum standing in for the psum)
on one device, so reduces per solve (``solver_allreduce_count``) can be
counted anywhere.

Chaos sites: ``distributed.allreduce`` fires before each step dispatch (the
reduce seam), ``admm.consensus`` after the consensus z-update commits (the
outer-iteration boundary).  A kill at either resumes bitwise through the
GridCheckpointer: the in-flight λ re-solves deterministically from the same
warm start (docs/robustness.md).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from photon_ml_tpu import telemetry as telemetry_mod
from photon_ml_tpu.chaos import core as chaos_mod
from photon_ml_tpu.optim.lbfgs import LBFGSConfig, SolveResult, lbfgs_solve
from photon_ml_tpu.optim.owlqn import _pseudo_gradient

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ADMMOptions:
    """Knobs, settable via ``OptimizerConfig.solver_options`` (docs/solvers.md).

    ``max_outer`` of 0 defers to ``OptimizerConfig.max_iters``; likewise
    ``abstol`` of 0 defers to ``OptimizerConfig.tolerance``."""

    rho: float = 1.0  # initial penalty
    adaptive_rho: bool = True
    mu: float = 10.0  # residual-imbalance trigger (Boyd §3.4.1)
    tau: float = 2.0  # ρ scale factor on trigger
    over_relaxation: float = 1.5  # α ∈ [1.0, 1.8]
    abstol: float = 0.0
    reltol: float = 1e-4
    max_outer: int = 0
    local_solver: str = "auto"  # auto | lbfgs | ridge
    local_max_iters: int = 25  # L-BFGS subproblem budget
    local_tolerance: float = 1e-8
    shards: int = 0  # logical-shard count (0 = auto; sharded.py reads it)

    @classmethod
    def from_options(cls, options: dict) -> "ADMMOptions":
        fields = {f.name: f.type for f in dataclasses.fields(cls)}
        unknown = sorted(set(options) - set(fields))
        if unknown:
            raise ValueError(
                f"unknown admm solver_options {unknown}; valid: {sorted(fields)}"
            )
        coerced = {}
        for key, val in options.items():
            if key == "local_solver":
                coerced[key] = str(val)
            elif key == "adaptive_rho":
                coerced[key] = bool(val)
            elif key in ("max_outer", "local_max_iters", "shards"):
                coerced[key] = int(val)
            else:
                coerced[key] = float(val)
        opts = cls(**coerced)
        if opts.local_solver not in ("auto", "lbfgs", "ridge"):
            raise ValueError(
                f"admm local_solver must be auto|lbfgs|ridge, got "
                f"{opts.local_solver!r}"
            )
        if not 1.0 <= opts.over_relaxation <= 1.8:
            raise ValueError(
                "admm over_relaxation must lie in [1.0, 1.8] "
                f"(got {opts.over_relaxation})"
            )
        return opts


def _soft_threshold(t: Array, thresh: Array) -> Array:
    return jnp.sign(t) * jnp.maximum(jnp.abs(t) - thresh, 0.0)


def make_sharded_solver(problem, dist, mesh, l1_mask=None):
    """``HOST_SOLVERS`` factory: bind (problem, sharded data, mesh) once,
    return ``solve_fn(lam, w_prev, dist_override=None) → SolveResult``.

    ``dist`` is a ``parallel.distributed.DistributedGlmData`` (every array
    carrying a leading shard axis); ``mesh`` is a 1-D device mesh over
    ``DATA_AXIS`` for real SPMD execution, or None for logical shards on
    the default device.  ``dist_override`` lets callers swap the dataset
    (same shapes) per solve without recompiling — the GAME fixed-effect
    coordinate re-slots its per-iteration offsets this way."""
    from jax import shard_map
    from photon_ml_tpu.parallel.distributed import DATA_AXIS

    obj = problem.objective
    cfg = problem.config
    opt = cfg.optimizer
    opts = ADMMOptions.from_options(opt.solver_options_dict())
    max_outer = opts.max_outer or opt.max_iters
    abstol = opts.abstol or opt.tolerance
    l1_frac = cfg.regularization.l1_weight(1.0)
    l2_frac = cfg.regularization.l2_weight(1.0)
    alpha = opts.over_relaxation

    n = dist.n_shards
    d = int(dist.data.features.shape[-1])
    mask = (
        jnp.ones((d,), jnp.float32)
        if l1_mask is None
        else jnp.asarray(l1_mask, jnp.float32)
    )
    use_ridge = opts.local_solver == "ridge" or (
        opts.local_solver == "auto" and problem.task == "squared"
    )
    if use_ridge and problem.task != "squared":
        raise ValueError(
            "admm local_solver='ridge' needs the linear (squared) task "
            "(the closed "
            f"form assumes a quadratic objective); task is {problem.task!r}"
        )
    local_cfg = LBFGSConfig(
        max_iters=opts.local_max_iters,
        tolerance=opts.local_tolerance,
        history=opt.history,
    )

    # -- per-shard pieces (pure; run under shard_map OR vmap) ---------------
    def x_update_lbfgs(local, x_prev, v, rho):
        def vg(w):
            val, g = obj.raw_value_and_grad(w, local)
            dw = w - v
            return val + 0.5 * rho * jnp.vdot(dw, dw), g + rho * dw

        res = lbfgs_solve(vg, x_prev, local_cfg)
        dw = res.w - v
        f_loc = res.value - 0.5 * rho * jnp.vdot(dw, dw)
        return res.w, res.iterations.astype(jnp.float32), f_loc

    def ridge_prep(local):
        zero = jnp.zeros((d,), jnp.float32)
        c, g0 = obj.raw_value_and_grad(zero, local)
        d2w = obj.d2_weights(zero, local)
        gram = jax.vmap(
            lambda e: obj.raw_hvp(zero, e, local, d2w)
        )(jnp.eye(d, dtype=jnp.float32))
        evals, q = jnp.linalg.eigh(gram)
        return q, evals, -g0, c

    def x_update_ridge(prep, v, rho):
        q, evals, b, c = prep
        x = q @ ((q.T @ (b + rho * v)) / (evals + rho))
        gx = q @ (evals * (q.T @ x))
        f_loc = 0.5 * jnp.vdot(x, gx) - jnp.vdot(b, x) + c
        return x, jnp.ones((), jnp.float32), f_loc

    def shard_step(solve_local, xl, ul, z, rho):
        """x-update + over-relaxation + the shard's psum payload."""
        x_new, iters, f_loc = solve_local(z - ul, rho)
        xh = alpha * x_new + (1.0 - alpha) * z
        scalars = jnp.stack([
            jnp.vdot(x_new, x_new), jnp.vdot(ul, ul), f_loc, iters,
        ])
        return x_new, xh, jnp.concatenate([xh + ul, x_new, scalars])

    def consensus(tot, z_prev, rho, l1, l2):
        """z-update + residuals from the reduced payload (replicated)."""
        p_sum, x_sum = tot[:d], tot[d : 2 * d]
        sum_x2, sum_u2 = tot[2 * d], tot[2 * d + 1]
        f_sum, iters_sum = tot[2 * d + 2], tot[2 * d + 3]
        denom = l2 + rho * n
        z = _soft_threshold(rho * p_sum / denom, (l1 / denom) * mask)
        r2 = jnp.maximum(
            sum_x2 - 2.0 * jnp.vdot(x_sum, z) + n * jnp.vdot(z, z), 0.0
        )
        obj_proxy = (
            f_sum
            + l1 * jnp.sum(jnp.abs(z) * mask)
            + 0.5 * l2 * jnp.vdot(z, z)
        )
        stats = jnp.stack([
            obj_proxy, r2, jnp.linalg.norm(z - z_prev), sum_x2, sum_u2,
            iters_sum, jnp.linalg.norm(z),
        ])
        return z, stats

    # -- the ONE compiled step program (+ one final exact evaluation) -------
    if mesh is not None:
        spec_data = jax.sharding.PartitionSpec(DATA_AXIS)
        spec_repl = jax.sharding.PartitionSpec()

        def spmd_step(dd, prep, x, u, z, rho, l1, l2):
            local = dd.local() if prep is None else None
            solve_local = (
                (lambda v, r: x_update_ridge(
                    jax.tree.map(lambda a: a[0], prep), v, r))
                if use_ridge
                else (lambda v, r: x_update_lbfgs(local, x[0], v, r))
            )
            x_new, xh, payload = shard_step(solve_local, x[0], u[0], z, rho)
            tot = lax.psum(payload, DATA_AXIS)
            z_new, stats = consensus(tot, z, rho, l1, l2)
            u_new = u[0] + xh - z_new
            return x_new[None], u_new[None], z_new, stats

        def _make_step(prep_in_spec):
            return jax.jit(shard_map(
                spmd_step,
                mesh=mesh,
                in_specs=(
                    spec_data, prep_in_spec, spec_data, spec_data,
                    spec_repl, spec_repl, spec_repl, spec_repl,
                ),
                out_specs=(spec_data, spec_data, spec_repl, spec_repl),
                check_vma=False,
            ))

        step_lbfgs = None if use_ridge else _make_step(spec_repl)
        step_ridge = _make_step(spec_data) if use_ridge else None

        def spmd_prep(dd):
            q, evals, b, c = ridge_prep(dd.local())
            return q[None], evals[None], b[None], c[None]

        prep_fn = jax.jit(shard_map(
            spmd_prep,
            mesh=mesh,
            in_specs=(spec_data,),
            out_specs=(spec_data,) * 4,
            check_vma=False,
        )) if use_ridge else None

        def spmd_eval(dd, z, l1, l2):
            val, grad = obj.raw_value_and_grad(z, dd.local())
            val, grad = lax.psum((val, grad), DATA_AXIS)
            val = (
                val + l1 * jnp.sum(jnp.abs(z) * mask)
                + 0.5 * l2 * jnp.vdot(z, z)
            )
            return val, _pseudo_gradient(z, grad + l2 * z, l1, mask)

        eval_fn = jax.jit(shard_map(
            spmd_eval,
            mesh=mesh,
            in_specs=(spec_data, spec_repl, spec_repl, spec_repl),
            out_specs=(spec_repl, spec_repl),
            check_vma=False,
        ))

        def spmd_local_grad(dd, z):
            return obj.raw_value_and_grad(z, dd.local())[1][None]

        # Shard-local gradients, NO collective: each device keeps its row.
        local_grad_fn = jax.jit(shard_map(
            spmd_local_grad,
            mesh=mesh,
            in_specs=(spec_data, spec_repl),
            out_specs=spec_data,
            check_vma=False,
        ))
    else:
        def logical_step(dd, prep, x, u, z, rho, l1, l2):
            if use_ridge:
                one = lambda pr, xl, ul: shard_step(
                    lambda v, r: x_update_ridge(pr, v, r), xl, ul, z, rho
                )
                x_new, xh, payload = jax.vmap(one)(prep, x, u)
            else:
                one = lambda local, xl, ul: shard_step(
                    lambda v, r: x_update_lbfgs(local, xl, v, r), xl, ul,
                    z, rho,
                )
                x_new, xh, payload = jax.vmap(one)(dd.data, x, u)
            tot = jnp.sum(payload, axis=0)  # the psum's stand-in
            z_new, stats = consensus(tot, z, rho, l1, l2)
            u_new = u + xh - z_new
            return x_new, u_new, z_new, stats

        step_jit = jax.jit(logical_step)
        step_lbfgs = None if use_ridge else step_jit
        step_ridge = step_jit if use_ridge else None
        prep_fn = jax.jit(
            lambda dd: jax.vmap(ridge_prep)(dd.data)
        ) if use_ridge else None

        def logical_eval(dd, z, l1, l2):
            vals, grads = jax.vmap(
                lambda local: obj.raw_value_and_grad(z, local)
            )(dd.data)
            val = jnp.sum(vals)
            grad = jnp.sum(grads, axis=0)
            val = (
                val + l1 * jnp.sum(jnp.abs(z) * mask)
                + 0.5 * l2 * jnp.vdot(z, z)
            )
            return val, _pseudo_gradient(z, grad + l2 * z, l1, mask)

        eval_fn = jax.jit(logical_eval)

        local_grad_fn = jax.jit(lambda dd, z: jax.vmap(
            lambda local: obj.raw_value_and_grad(z, local)[1]
        )(dd.data))

    payload_bytes = (2 * d + 4) * 4
    prep_cache: dict[int, tuple] = {}

    def solve_fn(lam, w_prev, dist_override=None) -> SolveResult:
        dd = dist if dist_override is None else dist_override
        l1 = jnp.asarray(l1_frac * float(lam), jnp.float32)
        l2 = jnp.asarray(l2_frac * float(lam), jnp.float32)
        if w_prev is None:
            w_prev = jnp.zeros((d,), jnp.float32)
        prep = None
        if use_ridge:
            # The Gram factorization is cached for the BOUND dataset (it
            # survives every λ of a grid and every ρ change); an override
            # (GAME's per-iteration offsets shift b and c) re-runs the
            # one-time prep program for its own data.
            if dist_override is None:
                prep = prep_cache.get("default")
                if prep is None:
                    prep = prep_cache["default"] = prep_fn(dist)
            else:
                prep = prep_fn(dd)
        step = step_ridge if use_ridge else step_lbfgs

        z = jnp.asarray(w_prev, jnp.float32)
        x = jnp.broadcast_to(z, (n, d)) + jnp.zeros((n, d), jnp.float32)
        rho = float(opts.rho)
        # Warm dual: at the consensus fixed point u*_s = -grad f_s(z*)/rho
        # (x-update stationarity at x=z), so seeding the duals from the
        # shard-local gradients at z0 removes the cold-dual transient.
        # Deterministic in (data, z0, rho) -> bitwise-safe under resume.
        u = -local_grad_fn(dd, z) / jnp.asarray(rho, jnp.float32)
        values, rnorms = [], []
        rounds = 0
        converged = False
        r = s = float("inf")
        local_iters = 0.0
        for k in range(max_outer):
            # The reduce seam: the step program about to run carries this
            # iteration's single all-reduce (docs/robustness.md).
            chaos_mod.maybe_fail(
                "distributed.allreduce", solver="admm", outer=k
            )
            x, u, z_new, stats = step(
                dd, prep, x, u, z, jnp.asarray(rho, jnp.float32), l1, l2
            )
            stats = np.asarray(stats, np.float64)
            (obj_proxy, r2, dz, sum_x2, sum_u2,
             iters_sum, znorm) = stats.tolist()
            rounds = k + 1
            local_iters += iters_sum
            r = float(np.sqrt(r2))
            s = rho * float(np.sqrt(n)) * dz
            values.append(obj_proxy)
            rnorms.append(r)
            z = z_new
            # The consensus commit: z is adopted; a kill here loses only
            # the in-flight λ, which re-solves deterministically on resume.
            chaos_mod.maybe_fail(
                "admm.consensus", solver="admm", outer=k, rho=rho
            )
            eps_pri = (
                np.sqrt(n * d) * abstol
                + opts.reltol * max(np.sqrt(sum_x2), np.sqrt(n) * znorm)
            )
            eps_dual = (
                np.sqrt(n * d) * abstol
                + opts.reltol * rho * np.sqrt(sum_u2)
            )
            if r <= eps_pri and s <= eps_dual:
                converged = True
                break
            if opts.adaptive_rho:
                # μ/τ imbalance rule; the SCALED dual u = y/ρ rescales
                # inversely with ρ (Boyd §3.4.1).
                if r > opts.mu * s:
                    rho *= opts.tau
                    u = u / opts.tau
                elif s > opts.mu * r:
                    rho /= opts.tau
                    u = u * opts.tau

        value, grad = eval_fn(dd, z, l1, l2)
        tel = telemetry_mod.current()
        if tel.enabled:
            tel.counter("solver_outer_iterations_total").inc(rounds)
            # One reduce per outer round + the final exact evaluation.
            tel.counter("solver_allreduce_count").inc(rounds + 1)
            tel.counter("solver_allreduce_bytes_total").inc(
                rounds * payload_bytes + (d + 1) * 4
            )
            tel.gauge("solver_consensus_residual").set(r)
            tel.counter("solvers_sharded_solves_total").inc()
        return SolveResult(
            w=z,
            value=value,
            grad=grad,
            iterations=jnp.asarray(rounds, jnp.int32),
            converged=jnp.asarray(converged),
            values=jnp.asarray(values, jnp.float32),
            grad_norms=jnp.asarray(rnorms, jnp.float32),
        )

    return solve_fn

