"""Optimization problems: objective + optimizer + regularization + normalization.

The analogue of the reference's ``GeneralizedLinearOptimizationProblem`` /
``DistributedOptimizationProblem`` / ``SingleNodeOptimizationProblem`` and
their ``OptimizationProblemConfig`` (SURVEY.md §2): bind everything needed to
produce a trained ``GeneralizedLinearModel``, optionally with coefficient
variances, and sweep a regularization-weight grid with warm starts (the
reference's ``ModelTraining`` trains the λ grid chained — SURVEY.md §3.1).

The distributed/single-node split is ONE class here: ``axis_name=None`` is
single-device; an axis name + ``shard_map`` (parallel/distributed.py) is the
distributed problem.  λ is a runtime argument, so one compiled solver serves
the whole grid without recompilation.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from photon_ml_tpu import telemetry as telemetry_mod
from photon_ml_tpu.chaos import core as chaos_mod
from photon_ml_tpu.data.dataset import GlmData
from photon_ml_tpu.data.normalization import NormalizationContext
from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.ops import losses as losses_lib
from photon_ml_tpu.optim.lbfgs import (
    SOLVE_COUNTS,
    LBFGSConfig,
    SolveResult,
    lbfgs_solve,
)
from photon_ml_tpu.optim.objective import GlmObjective
from photon_ml_tpu.optim.owlqn import OWLQNConfig, owlqn_solve
from photon_ml_tpu.optim.projected import SPGConfig, spg_solve
from photon_ml_tpu.optim.regularization import RegularizationContext
from photon_ml_tpu.optim.tron import TRONConfig, tron_solve

Array = jax.Array


class OptimizerType(enum.Enum):
    LBFGS = "lbfgs"
    OWLQN = "owlqn"
    TRON = "tron"


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Mirrors the reference's ``OptimizerConfig`` (optimizerType,
    maximumIterations, tolerance).

    ``solver`` names a solver explicitly (:data:`DEVICE_SOLVERS` or
    :data:`HOST_LOOP_SOLVERS`); None keeps the historical routing (bounds →
    SPG, any L1 component → OWL-QN, else ``optimizer``) bitwise — see
    :func:`choose_solver`.  ``solver_options`` is a tuple of (key, value)
    pairs — a TUPLE, not a dict, because this config lives in lru_cache
    keys (GAME block solvers, fixed-effect jit caches) and must stay
    hashable."""

    optimizer: OptimizerType = OptimizerType.LBFGS
    max_iters: int = 100
    tolerance: float = 1e-7
    history: int = 10  # L-BFGS/OWL-QN corrections
    solver: Optional[str] = None
    solver_options: tuple = ()

    def solver_options_dict(self) -> dict:
        """``solver_options`` as a plain dict."""
        return dict(self.solver_options)


#: The solvers a traced solve runs (``GlmOptimizationProblem.solve``, the
#: streamed grid, the GAME block solvers).
DEVICE_SOLVERS = ("lbfgs", "owlqn", "tron", "spg")
#: The solvers that run a host-side outer loop around a compiled step
#: program (consensus ADMM, distributed block CD); ``solvers.HOST_SOLVERS``
#: holds their factories.  Both handle L1.
HOST_LOOP_SOLVERS = ("admm", "block_cd")


def choose_solver(opt: OptimizerConfig, *, l1_frac: float,
                  has_bounds: bool = False) -> str:
    """The solver an ``OptimizerConfig`` runs, by name.

    ``opt.solver`` unset is the historical routing: bounds → SPG, any L1
    component → OWL-QN (the only orthant-capable machinery, as in the
    reference), else the configured optimizer.  An explicit name is honored
    as-is, but incompatible combinations (an L1 component with a solver
    that has no subgradient handling; bounds with anything but SPG; SPG
    without bounds) are refused here — statically, before any compute is
    spent: ``l1_frac`` is a float, the name a config string."""
    name = opt.solver
    if name is None:
        if has_bounds:
            return "spg"
        if l1_frac > 0.0:
            return "owlqn"
        return opt.optimizer.value
    if name not in DEVICE_SOLVERS + HOST_LOOP_SOLVERS:
        raise KeyError(
            f"unknown solver {name!r}; known: "
            f"{sorted(DEVICE_SOLVERS + HOST_LOOP_SOLVERS)}"
        )
    if has_bounds and name != "spg":
        raise ValueError(
            f"solver {name!r} does not support box constraints; "
            "only 'spg' does — drop the bounds or the solver override"
        )
    if l1_frac > 0.0 and name not in ("owlqn",) + HOST_LOOP_SOLVERS:
        raise ValueError(
            f"solver {name!r} has no L1 subgradient handling; use "
            "'owlqn', 'admm', or 'block_cd' for L1/elastic-net configs"
        )
    if name == "spg" and not has_bounds:
        # SPG is a projection method: without box constraints there is no
        # feasible set to project onto.
        raise ValueError(
            "solver 'spg' needs box constraints (lower/upper bounds); "
            "use 'lbfgs' or 'tron' for unconstrained smooth configs"
        )
    return name


@dataclasses.dataclass(frozen=True)
class GlmOptimizationConfig:
    """Mirrors the reference's per-coordinate ``GLMOptimizationConfiguration``:
    optimizer config + regularization context + weight(s) + variance flag."""

    optimizer: OptimizerConfig = OptimizerConfig()
    regularization: RegularizationContext = RegularizationContext.none()
    compute_variances: bool = False


class GlmOptimizationProblem:
    """Trains GLMs for a task under a config.

    All solve paths are pure jittable functions; this class only does static
    dispatch (optimizer type, loss) and host-side bookkeeping, so it can be
    used identically on one device or inside ``shard_map``.
    """

    def __init__(
        self,
        task: str,
        config: GlmOptimizationConfig = GlmOptimizationConfig(),
        normalization: Optional[NormalizationContext] = None,
        accumulate: str = "f32",
    ):
        self.task = losses_lib.get(task).name  # canonicalize aliases
        self.config = config
        #: per-λ blocking wall seconds of the LAST grid_loop run (drivers
        #: read it to put real wall-clock on convergence trackers).
        self.grid_wall_seconds: dict[float, float] = {}
        self.objective = GlmObjective(
            losses_lib.get(task), normalization, accumulate=accumulate
        )
        self.normalization = normalization
        # One compiled program serves every single-device solve: data,
        # reg_weight, w0, and l1_mask are traced arguments, so a λ grid or
        # repeated fits never re-trace (the GAME coordinates already did
        # this; the legacy-driver path goes through here).
        self._solve_jit = jax.jit(
            lambda data, reg_weight, w0, l1_mask, bounds: self.solve(
                data, reg_weight, w0, None, l1_mask, bounds
            )
        )

    def solve_single_device(
        self,
        data: GlmData,
        reg_weight: Array | float = 0.0,
        w0: Optional[Array] = None,
        l1_mask: Optional[Array] = None,
        bounds: Optional[tuple[Array, Array]] = None,
    ) -> SolveResult:
        """Jit-cached single-device :meth:`solve` (axis_name=None)."""
        if w0 is None:
            w0 = jnp.zeros((data.n_features,), jnp.float32)
        return self._solve_jit(
            data, jnp.asarray(reg_weight, jnp.float32), w0, l1_mask, bounds
        )

    # -- core solve (jit/shard_map-safe) -----------------------------------
    def solve(
        self,
        data: GlmData,
        reg_weight: Array | float = 0.0,
        w0: Optional[Array] = None,
        axis_name: Optional[str] = None,
        l1_mask: Optional[Array] = None,
        bounds: Optional[tuple[Array, Array]] = None,
    ) -> SolveResult:
        """One optimization run at one regularization weight.

        ``reg_weight`` may be a traced scalar: the split into L1/L2 uses only
        the (static) regularization type.

        ``bounds`` = (lower, upper) per-coefficient arrays (±inf entries
        unconstrained) routes the solve to the box-constrained SPG path —
        the reference's constraint-map support on its optimizer layer.
        """
        obj = self.objective
        cfg = self.config
        d = data.n_features
        if bounds is not None and cfg.compute_variances:
            # The diag-inverse-Hessian variance (coefficient_variances)
            # assumes an interior optimum; a coefficient pinned at an
            # active bound has a nonzero gradient there and its reported
            # variance would be meaningless.  Static config check, so it
            # raises at trace time, before any compute is spent.
            raise ValueError(
                "bounds are incompatible with compute_variances=True: "
                "diag-inverse-Hessian variances assume an interior "
                "optimum and are wrong for coefficients at an active "
                "bound — drop the bounds or the variance request"
            )
        if w0 is None:
            w0 = jnp.zeros((d,), jnp.float32)
        reg_weight = jnp.asarray(reg_weight, w0.dtype)
        # Static split coefficients (floats), dynamic weight (traced scalar).
        l1_frac = cfg.regularization.l1_weight(1.0)
        l1 = l1_frac * reg_weight
        l2 = cfg.regularization.l2_weight(1.0) * reg_weight
        opt = cfg.optimizer

        if bounds is not None and l1_frac > 0.0:
            # Box constraints conflict with the orthant-wise machinery
            # for any solver choice.
            raise NotImplementedError(
                "box constraints combined with L1 regularization are "
                "not supported: the orthant-wise and projection "
                "machineries conflict (drop the L1 component or the "
                "bounds)"
            )
        name = choose_solver(
            opt, l1_frac=l1_frac, has_bounds=bounds is not None
        )
        if name not in DEVICE_SOLVERS:
            raise ValueError(
                f"solver {name!r} runs a host-side outer loop and "
                "cannot execute inside a traced solve; route through "
                "solvers.sharded.run_grid_sharded (glm_driver --solver "
                "does this automatically)"
            )
        vg = lambda w: obj.value_and_grad(
            w, data, l2_weight=l2, axis_name=axis_name
        )
        if name == "lbfgs":
            return lbfgs_solve(vg, w0, LBFGSConfig(
                max_iters=opt.max_iters,
                tolerance=opt.tolerance,
                history=opt.history,
            ))
        if name == "owlqn":
            return owlqn_solve(
                vg,
                w0,
                l1,
                OWLQNConfig(
                    max_iters=opt.max_iters,
                    tolerance=opt.tolerance,
                    history=opt.history,
                ),
                l1_mask=l1_mask,
            )
        if name == "tron":
            return tron_solve(
                vg,
                lambda w, v, aux: obj.hvp(
                    w, v, data, l2_weight=l2, axis_name=axis_name, d2w=aux
                ),
                w0,
                TRONConfig(max_iters=opt.max_iters, tolerance=opt.tolerance),
                d2_fn=lambda w: obj.d2_weights(w, data),
            )
        return spg_solve(
            vg,
            w0,
            bounds[0],
            bounds[1],
            SPGConfig(max_iters=opt.max_iters, tolerance=opt.tolerance),
            w_axis=None,
        )

    # -- variances (reference: optional coefficient variance computation) ---
    def coefficient_variances(
        self,
        w: Array,
        data: GlmData,
        reg_weight: Array | float = 0.0,
        axis_name: Optional[str] = None,
    ) -> Array:
        """Diagonal-inverse-Hessian approximation ``1 / H_jj`` — the
        reference's ``VarianceComputationType.SIMPLE``.  ``H_jj = Σ_i wᵢ·d2ᵢ·
        X²ᵢⱼ + λ₂``, one squared-column reduction."""
        l2 = self.config.regularization.l2_weight(1.0) * jnp.asarray(
            reg_weight, w.dtype
        )
        d2w = self.objective.d2_weights(w, data)
        diag = data.features.sq_rmatvec(d2w)
        if axis_name is not None:
            from jax import lax

            diag = lax.psum(diag, axis_name)
        return 1.0 / jnp.maximum(diag + l2, 1e-12)

    # -- model construction (host side) ------------------------------------
    def make_model(
        self, w: Array, variances: Optional[Array] = None
    ) -> GeneralizedLinearModel:
        """Map scaled-space coefficients back to the original feature space
        (normalization) and wrap them as a model."""
        if self.normalization is not None:
            w = self.normalization.model_to_original(w)
            # Variances are not transformed through normalization shifts;
            # scale-only transforms square the factors (as the reference's
            # coefficient summaries do).
            if variances is not None:
                variances = variances * self.normalization.factors**2
        return GeneralizedLinearModel(Coefficients(w, variances), self.task)

    # -- grid sweep with warm start (the reference's ModelTraining loop) ----
    def grid_loop(
        self,
        solve_fn,
        reg_weights: Sequence[float],
        w0: Optional[Array] = None,
        warm_start: bool = True,
        solved: Optional[dict] = None,
        on_solved=None,
        variance_fn=None,
    ) -> list[tuple[float, GeneralizedLinearModel, Optional[SolveResult]]]:
        """The warm-started λ chain shared by the single-device and
        distributed grids; ``solve_fn(lam, w_prev) → SolveResult`` is the
        only thing that differs between them.

        Checkpoint/resume: ``solved`` (λ → coefficient vector, from
        io/checkpoint.GridCheckpointer) skips already-solved λs — their
        entries come back with ``res=None`` and the warm-start chain
        continues from the restored coefficients, so a resumed grid matches
        the uninterrupted one bit-for-bit.  ``on_solved(lam, w)`` fires
        after each fresh solve (the driver persists the checkpoint there).
        ``variance_fn(w, lam)`` runs for EVERY grid point (including
        restored ones) when coefficient variances are requested.

        The call is one ``grid`` layer span; each fresh solve runs under
        a ``solver`` layer span (telemetry.layer_span: recorded with or
        without a hub) that ends at the blocking read of the solution
        vector -- the grid is a warm-start chain, so solves were already
        serialized; the block only moves the sync to where it can be
        attributed.  Per-λ walls, the span's own duration, land in
        ``self.grid_wall_seconds`` so drivers can put real wall-clock on
        their convergence trackers."""
        tel = telemetry_mod.current()
        self.grid_wall_seconds: dict[float, float] = {}
        results = []
        w_prev = w0
        solved = solved or {}
        with telemetry_mod.layer_span("grid"):
            for lam in sorted(reg_weights, reverse=True):
                if lam in solved:
                    w = jnp.asarray(solved[lam])
                    res = None
                    tel.event("grid.restored", reg_weight=float(lam))
                else:
                    with telemetry_mod.layer_span(
                        "solver",
                        reg_weight=float(lam),
                        optimizer=self.config.optimizer.optimizer.value,
                    ) as sp:
                        res = solve_fn(lam, w_prev)
                        # Queue the scalars' copies behind the solve: they
                        # are on the host when the blocking read returns,
                        # and the read-back below waits for nothing (a
                        # copy asked for only then idles the device ~1 ms
                        # a solve on a TPU v5e).
                        counts = (res.iterations, res.fn_evals, res.converged)
                        # a solver family's own counts ride along
                        # (SOLVE_COUNTS); every other solve reads what it
                        # read
                        family = next(
                            (fields for mark, fields in SOLVE_COUNTS.items()
                             if getattr(res, mark) is not None), ())
                        counts += tuple(
                            getattr(res, field) for field, _, _ in family)
                        counts = jax.copy_to_host_async(counts)
                        jax.block_until_ready(res.w)
                        wall = sp.stop()
                        iters, fn_evals, converged, *extras = jax.device_get(
                            counts)
                        iters = int(iters)
                        sp.set(
                            iterations=iters,
                            converged=bool(converged),
                            wall_seconds=wall,
                        )
                        tel.counter("solver_iterations").inc(iters)
                        tel.histogram("solver_wall_seconds").observe(wall)
                        if fn_evals is not None:
                            sp.set(fn_evals=int(fn_evals))
                            tel.counter("solver_fn_evals").inc(int(fn_evals))
                        for (field, counter, kind), v in zip(family, extras):
                            if counter is not None:
                                tel.counter(counter).inc(int(v))
                            sp.set(**{field: kind(int(v))})
                    self.grid_wall_seconds[lam] = wall
                    w = res.w
                    if on_solved is not None:
                        on_solved(lam, w)
                    # The natural crash/resume boundary of the warm-start
                    # chain: the point is solved AND persisted, nothing of
                    # the next λ has started (docs/robustness.md).
                    chaos_mod.maybe_fail("grid.point", reg_weight=float(lam))
                variances = (variance_fn(w, lam) if variance_fn is not None
                             else None)
                results.append((lam, self.make_model(w, variances), res))
                if warm_start:
                    w_prev = w
        return results

    def run_grid(
        self,
        data: GlmData,
        reg_weights: Sequence[float],
        w0: Optional[Array] = None,
        axis_name: Optional[str] = None,
        l1_mask: Optional[Array] = None,
        warm_start: bool = True,
        solved: Optional[dict] = None,
        on_solved=None,
        bounds: Optional[tuple[Array, Array]] = None,
    ) -> list[tuple[float, GeneralizedLinearModel, Optional[SolveResult]]]:
        """Train one model per regularization weight (see :meth:`grid_loop`
        for the warm-start/checkpoint semantics)."""
        if bounds is not None and self.config.compute_variances:
            # Mirrors solve()'s guard, but raised eagerly here — before
            # the grid loop touches the device at all.
            raise ValueError(
                "run_grid with bounds is incompatible with "
                "compute_variances=True: diag-inverse-Hessian variances "
                "assume an interior optimum (see solve())"
            )

        def solve_fn(lam, w_prev):
            return (
                self.solve_single_device(data, lam, w_prev, l1_mask, bounds)
                if axis_name is None
                else self.solve(data, lam, w_prev, axis_name, l1_mask, bounds)
            )

        variance_fn = None
        if self.config.compute_variances:
            variance_fn = lambda w, lam: self.coefficient_variances(
                w, data, lam, axis_name
            )
        return self.grid_loop(
            solve_fn, reg_weights, w0, warm_start, solved, on_solved,
            variance_fn,
        )
