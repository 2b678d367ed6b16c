"""GAME coordinates: per-effect training and scoring units.

The analogue of the reference's ``...ml.algorithm`` coordinates
([CONFIRMED-BASELINE], SURVEY.md §2, §3.2):

- ``FixedEffectCoordinate`` — one distributed GLM fit over all rows (the
  stage-3.1 solver with per-row offsets from the other coordinates);
- ``RandomEffectCoordinate`` — millions of independent per-entity GLM fits.
  The reference runs them inside Spark ``mapPartitions`` (executor-local
  L-BFGS per entity, zero communication — SURVEY.md §3.2); here each
  size-bucket block solves as ONE ``vmap``'d L-BFGS/OWL-QN ``while_loop``
  over its entity lanes, one jitted program per block shape.  Converged
  lanes freeze (lax batching selects old carries), so ragged per-entity
  convergence inside a batch is handled by construction.

Coordinates hold their (device-resident) datasets — the analogue of the
reference persisting per-coordinate RDDs — and expose
``train(offsets, warm) → state`` / ``score(state) → per-row scores``,
mirroring the reference's ``Coordinate.trainModel`` / ``score``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.dataset import GlmData
from photon_ml_tpu.game.data import (
    EntityBlock,
    FixedEffectDataset,
    RandomEffectDataset,
)
from photon_ml_tpu.game.model import (
    EntityLanes,
    EntityVariances,
    FixedEffectModel,
    RandomEffectModel,
)
from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.ops import losses as losses_lib
from photon_ml_tpu.optim.lbfgs import LBFGSConfig, lbfgs_solve
from photon_ml_tpu.optim.owlqn import OWLQNConfig, owlqn_solve
from photon_ml_tpu.optim.problem import (
    DEVICE_SOLVERS,
    HOST_LOOP_SOLVERS,
    GlmOptimizationConfig,
    choose_solver,
)
from photon_ml_tpu import telemetry as telemetry_mod

Array = jax.Array


class Coordinate:
    """Protocol: train against offsets, score into the global row space."""

    name: str
    #: "fixed" or "random": what the descent's ``coordinate.train`` layer
    #: span says this coordinate is.
    kind: str = "coordinate"
    #: Rows this coordinate scores and never trains on (a random effect's
    #: rows beyond its active-row cap): on the ``coordinate.score`` span.
    rows_passive: int = 0

    def train_counts(self) -> dict:
        """What the last ``train`` counted, as attributes for the descent's
        ``coordinate.train`` layer span: a tree of host numbers and 0-d
        DEVICE arrays not yet read.  The descent reads every update's in
        one batched read, with its history flush, and amends the span.
        Nothing by default."""
        return {}

    @property
    def feature_layout(self) -> str:
        """What holds this coordinate's features while it trains — the
        drivers report it, so a run shows which kernels it exercised.
        Fixed effects name their feature-matrix class (it follows the
        backend and the data size); the default is the coordinate class."""
        return type(self).__name__

    def train(self, offsets: Array, warm_state=None):
        raise NotImplementedError

    def prestage(self, warm_state=None) -> None:
        """Hint that ``train(..., warm_state)`` is about to be called.

        The pipelined descent schedule (game/descent.py) issues this for
        the NEXT coordinate before blocking on the current one's solve:
        work that does not depend on the offsets — host-side slice
        packing, warm-start staging — may start in the background.  The
        contract is strictly a latency hint: results must stay bitwise
        identical whether or not prestage ran, so the default is a
        no-op and implementations must key any staged buffers to the
        exact ``warm_state`` they were built from."""
        return None

    def score(self, state) -> Array:
        raise NotImplementedError

    def finalize(self, state, offsets=None):
        """Turn device state into the host-side model object.

        ``offsets`` are this coordinate's final residual offsets (base +
        the other coordinates' scores) — required for coefficient-variance
        computation, whose Hessian must be evaluated at the full final
        margins, not this coordinate's margins alone."""
        raise NotImplementedError

    def make_validation_scorer(self, shards: dict, ids: dict):
        """Build a reusable validation scorer for this coordinate (see
        game/validation.py) from raw validation columns."""
        raise NotImplementedError


def _layout_sig(tree) -> tuple:
    """Hashable shape/dtype signature of a pytree of arrays.  Program
    caches key on it purely as an EVICTION GRANULE: ``jax.jit`` retraces
    per shape signature anyway, but without the sig in the lru key one
    shared wrapper would accumulate an executable per distinct dataset
    layout for process lifetime — keying (and bounding) on the layout
    lets old layouts' compiled programs be dropped with their entry."""
    return tuple(
        (tuple(leaf.shape), str(leaf.dtype))
        for leaf in jax.tree.leaves(tree)
    )


@functools.lru_cache(maxsize=64)
def _fixed_effect_jits(
    task: str, config: GlmOptimizationConfig, axis_name: Optional[str],
    data_sig: tuple,
):
    """Jitted (train, score) programs for a fixed-effect coordinate,
    memoized PROCESS-WIDE on (task, config, axis_name) plus the
    dataset's layout signature, like ``_make_block_solver``: per-instance
    ``jax.jit`` closures meant every new coordinate object — a second
    ``fit``, every ``fit_grid`` point, a fresh estimator in the same
    process — re-traced and re-COMPILED identical programs (~3 s each on
    the chip, 41 of 72 s of a repeat flagship fit)."""
    from photon_ml_tpu.optim.problem import GlmOptimizationProblem

    problem = GlmOptimizationProblem(task, config)

    # Dataset AND reg_weight are jit ARGUMENTS (not closure constants):
    # closures bake them into the HLO, forcing recompiles per dataset /
    # per tuning point and oversized programs.  Hyperparameter tuning
    # mutates reg_weight between runs at zero recompile cost.
    # The functions' names are the programs' names in a device trace
    # (``jit_fixed_effect_train``, ``jit_fixed_effect_score``).
    def fixed_effect_train(
        data: GlmData, offsets: Array, w0: Array, reg_weight: Array
    ):
        data = dataclasses.replace(data, offsets=offsets)
        return problem.solve(data, reg_weight, w0, axis_name=axis_name)

    def fixed_effect_score(data: GlmData, w: Array) -> Array:
        # Margin WITHOUT offsets: coordinate scores are additive pieces.
        return data.features.matvec(w)

    return jax.jit(fixed_effect_train), jax.jit(fixed_effect_score)


class FixedEffectCoordinate(Coordinate):
    """Reference: ``FixedEffectCoordinate`` — DistributedOptimizationProblem
    over the full dataset (SURVEY.md §3.2)."""

    kind = "fixed"
    #: The last ``train``'s whole ``SolveResult``, on the device and not
    #: yet read: the value and gradient the solver reported at the
    #: coefficients it returned, and what it counted.  ``None`` before the
    #: first ``train`` and for a trainer that reports none.
    last_solve = None

    def __init__(
        self,
        name: str,
        dataset: FixedEffectDataset,
        task: str,
        config: GlmOptimizationConfig,
        reg_weight: float = 0.0,
        feature_shard: str = "global",
        axis_name: Optional[str] = None,
    ):
        from photon_ml_tpu.optim.problem import GlmOptimizationProblem

        self.name = name
        self.dataset = dataset
        self.task = losses_lib.get(task).name
        self.problem = GlmOptimizationProblem(task, config)
        self.reg_weight = reg_weight
        self.feature_shard = feature_shard
        self.axis_name = axis_name
        self._sharded_trainer = None
        solver_name = config.optimizer.solver
        if solver_name in HOST_LOOP_SOLVERS:
            # The host-loop solvers (ADMM, block CD) distribute this
            # coordinate's solve over logical row shards; per-GAME-
            # iteration offsets re-slot into one shard template so the
            # compiled step program is reused across iterations.
            from photon_ml_tpu.solvers import sharded as solvers_sharded

            if axis_name is not None:
                raise ValueError(
                    f"solver {solver_name!r} manages its own mesh "
                    "collectives; it cannot nest inside an existing "
                    f"axis {axis_name!r} (drop data-parallel GAME or "
                    "the solver override)"
                )
            self._sharded_trainer = solvers_sharded.make_fixed_effect_trainer(
                self.problem,
                dataset.data,
                solvers_sharded.resolve_shard_count(config.optimizer),
            )
        self._train_jit, self._score_jit = _fixed_effect_jits(
            self.task, config, axis_name, _layout_sig(dataset.data)
        )

    def train_counts(self) -> dict:
        res = self.last_solve
        if res is None:
            return {}
        counts = {"iterations": res.iterations, "value": res.value}
        if res.fn_evals is not None:
            counts["fn_evals"] = res.fn_evals
        return counts

    @property
    def feature_layout(self) -> str:
        from photon_ml_tpu.utils.device_report import describe_layout

        return describe_layout(self.dataset.data.features)

    def train(self, offsets: Array, warm_state: Optional[Array] = None) -> Array:
        w0 = (
            jnp.zeros((self.dataset.data.n_features,), jnp.float32)
            if warm_state is None
            else warm_state
        )
        if self._sharded_trainer is not None:
            self.last_solve = None
            return self._sharded_trainer(offsets, w0, self.reg_weight)
        self.last_solve = self._train_jit(
            self.dataset.data, offsets, w0,
            jnp.asarray(self.reg_weight, jnp.float32),
        )
        return self.last_solve.w

    def score(self, state: Array) -> Array:
        return self._score_jit(self.dataset.data, state)

    def finalize(self, state: Array, offsets=None) -> FixedEffectModel:
        variances = None
        if self.problem.config.compute_variances and offsets is not None:
            data = dataclasses.replace(
                self.dataset.data, offsets=jnp.asarray(offsets, jnp.float32)
            )
            variances = self.problem.coefficient_variances(
                state, data, self.reg_weight
            )
        return FixedEffectModel(
            GeneralizedLinearModel(Coefficients(state, variances), self.task),
            self.feature_shard,
        )

    def make_validation_scorer(self, shards: dict, ids: dict):
        from photon_ml_tpu.game.validation import FixedEffectValidationScorer

        return FixedEffectValidationScorer(shards[self.feature_shard])


def _make_block_solver(task: str, config: GlmOptimizationConfig):
    """Canonicalize the task name before the cache lookup: raw aliases
    ("logistic_regression") and the canonical name ("logistic") must hit
    ONE cache entry, or every bucket shape compiles twice."""
    return _make_block_solver_cached(losses_lib.get(task).name, config)


@functools.lru_cache(maxsize=None)
def _make_block_solver_cached(task: str, config: GlmOptimizationConfig):
    """Build a jitted (block, offsets, w0, l1, l2) → (E, D) batched solver.

    Optimizer dispatch: any L1 component (static on the regularization
    TYPE) routes to OWL-QN.  SMOOTH problems prefer an exact fast path
    when one exists for the block shape — rank-1 Newton (R == 1), scalar
    Newton (D == 1), or batched damped Newton with a direct entity-minor
    solve (D <= 32) — regardless of
    whether the config names L-BFGS or TRON: these solve the identical
    regularized objective to the identical stationary point, the config's
    optimizer choice only governs HOW, and the fast paths are 2-13x
    cheaper on TPU (per-entity problems this small are sequential-step-
    bound).  Only blocks with no fast path (D > 32) run the configured
    L-BFGS/TRON machinery.  l1/l2 are traced scalars so tuning sweeps
    don't recompile.  Memoized on (task, config) —
    both hashable — so every coordinate/grid point with the same optimizer
    setup shares ONE jit cache (one compile per block shape process-wide).
    ``solver.path(block)`` names the path a block's static shape takes.
    """
    from photon_ml_tpu.optim.tron import TRONConfig, tron_solve

    loss = losses_lib.get(task)
    opt = config.optimizer
    # Random-effect blocks are batched per-entity traced solves, so only
    # the on-device solvers apply here (the host-loop ADMM / block CD
    # distribute the FIXED-effect coordinate — see FixedEffectCoordinate).
    name = choose_solver(opt, l1_frac=config.regularization.l1_weight(1.0))
    if name not in DEVICE_SOLVERS:
        raise ValueError(
            f"solver {name!r} runs a host-side loop and cannot run the "
            "per-entity random-effect blocks; set it on the "
            "fixed-effect coordinate's spec instead"
        )
    use_owlqn = name == "owlqn"
    use_tron = name == "tron"

    def rank1_newton(block, offsets_block, w0, l2):
        """Single-row entities (R == 1 — the LARGEST bucket class in
        long-tailed data) have a closed structure: the stationarity
        condition ℓ'(m)·x + λw = 0 forces w ∝ x, so the whole per-entity
        GLM collapses to a 1-D problem in α (w = α·x).  A few damped Newton
        steps replace the full vmapped L-BFGS machinery — ~30 sequential
        device ops instead of hundreds (the while_loop step count, not
        FLOPs, dominates these buckets).  Smooth objectives only (L1 breaks
        the proportionality)."""
        X = block.x_erd[:, 0, :]                   # (E, D)
        y = block.labels[:, 0]
        wt = block.weights[:, 0]
        off = offsets_block[:, 0].astype(X.dtype)  # robust under x64 callers
        s = jnp.sum(X * X, axis=1)                 # (E,) = ‖x‖²
        safe_s = jnp.maximum(s, 1e-12)
        alpha = jnp.sum(w0 * X, axis=1) / safe_s   # warm start projection
        # Margin-change clamp: Δmargin = Δα·s, so |Δα| ≤ 20/s bounds each
        # step's margin movement at 20 — keeps the undamped Newton step sane
        # when the curvature flattens (λ = 0, saturated logistic / large
        # Poisson counts) without capping total movement (12 × 20 margins).
        clip = 20.0 / safe_s

        def grad_at(alpha):
            m = alpha * s + off
            return m, s * (wt * loss.d1(m, y) + l2 * alpha)

        _, g0 = grad_at(alpha)
        gtol = opt.tolerance * jnp.maximum(1.0, jnp.abs(g0))
        done0 = (jnp.abs(g0) <= gtol) | (s <= 0)

        def cond(carry):
            i, _alpha, done, _n = carry
            return (i < 30) & ~jnp.all(done)

        def body(carry):
            i, alpha, done, n = carry
            m, g1 = grad_at(alpha)
            done = done | (jnp.abs(g1) <= gtol)
            g2 = wt * loss.d2(m, y) * s * s + l2 * s
            step = g1 / jnp.maximum(g2, 1e-12)
            step = jnp.clip(step, -clip, clip)
            alpha = alpha - jnp.where(done, 0.0, step)
            return i + 1, alpha, done, n + ~done

        # Up to 30 damped steps with a per-lane relative-gradient exit
        # (newton_block's test, seeded so lanes converged at entry run
        # zero bodies): exp-family losses can overshoot to the clamp
        # ceiling then crawl back ~1 margin-unit per Newton step (a huge
        # Poisson count), so the cap must stay high — but warm-started CD
        # iterations converge every lane in 1-3 steps, and sequential
        # step count is what these buckets are bound by.
        _, alpha, _, n = jax.lax.while_loop(
            cond, body,
            (jnp.zeros((), jnp.int32), alpha, done0, _no_steps(done0)),
        )
        return alpha[:, None] * X, n

    def dim1_newton(block, offsets_block, w0, l2):
        """Single-FEATURE entities (D == 1 — the reference's flagship
        GAME shape: a per-entity bias/intercept random effect, e.g.
        MovieLens per-user) are a 1-D problem in w regardless of row
        count: damped scalar Newton replaces the vmapped L-BFGS
        machinery, as rank1_newton does for R == 1.  Smooth objectives
        only."""
        X = block.x_erd[:, :, 0]                   # (E, R)
        y = block.labels
        wt = block.weights
        off = offsets_block.astype(X.dtype)
        w = w0[:, 0]                               # (E,)
        # Margin-change clamp: |Δw|·max|x| ≤ 20 per step (same damping
        # rationale as rank1_newton's).
        xmax = jnp.max(jnp.abs(X), axis=1)
        clip = 20.0 / jnp.maximum(xmax, 1e-12)

        def grad_at(w):
            m = w[:, None] * X + off
            return m, jnp.sum(wt * loss.d1(m, y) * X, axis=1) + l2 * w

        _, g0 = grad_at(w)
        gtol = opt.tolerance * jnp.maximum(1.0, jnp.abs(g0))

        def cond(carry):
            i, _w, done, _n = carry
            return (i < 30) & ~jnp.all(done)

        def body(carry):
            i, w, done, n = carry
            m, g = grad_at(w)
            done = done | (jnp.abs(g) <= gtol)
            h = jnp.sum(wt * loss.d2(m, y) * X * X, axis=1) + l2
            # All-zero-feature lanes (padding, degenerate entities) need
            # no special case: g = l2·w, h = l2 → one exact step to the
            # regularized solution w = 0 (and with l2 = 0 the step is 0/ε
            # = 0, leaving w unchanged — same stationary point the
            # generic solver reports).
            step = jnp.clip(g / jnp.maximum(h, 1e-12), -clip, clip)
            w = w - jnp.where(done, 0.0, step)
            return i + 1, w, done, n + ~done

        # Same per-lane relative-gradient exit + 30-step cap as
        # rank1_newton, seeded from the entry gradient.
        done0 = jnp.abs(g0) <= gtol
        _, w, _, n = jax.lax.while_loop(
            cond, body,
            (jnp.zeros((), jnp.int32), w, done0, _no_steps(done0)),
        )
        return w[:, None], n

    def newton_block(block, offsets_block, w0, l2, max_iters, tol):
        """Batched damped Newton for smooth objectives on small-D blocks:
        one exact solve of the regularised Newton system per trip replaces
        the vmapped L-BFGS machinery.  The win is SEQUENTIAL structure:
        one Newton body is a short chain (margin, gradient, one batched-
        matmul Hessian build, one direct solve, damp) vs L-BFGS's nested
        scan + zoom while_loop per iteration, and quadratic convergence
        needs fewer outer trips.  Per-lane freezing + the Breeze-style
        relative gradient test match the L-BFGS convergence semantics.

        Everything D x D or D long lives ENTITY-MINOR inside the loop:
        ``H`` as ``(D, D, E)``, ``w``, ``g`` and the step as ``(D, E)``.
        A TPU pads an array's minor axis to 128 lanes, so ``(E, D, D)``
        at D = 21 is seven times its bytes in every pass over it, and the
        D-step CG that used to solve the system made D such passes a
        trip (:func:`_spd_solve_direct` has the measurement).  The
        Hessian is built on the matrix unit, or as elementwise pairs
        for the short-row shapes where that was timed and won
        (``_PAIRS_HESSIAN_UP_TO``): a choice read from the block's static
        shape.  The dot products
        with ``X`` run at HIGHEST precision: default MXU bf16 puts a
        noise floor above the 1e-6 gradient tolerance, which silently
        disables the early exit."""
        X, yb, wt = block.x_erd, block.labels, block.weights
        off = offsets_block.astype(X.dtype)
        l2_eye = l2 * jnp.eye(block.block_dim, dtype=X.dtype)[:, :, None]
        by_pairs = any(
            block.block_dim <= dim and block.rows_per_entity <= rows
            for dim, rows in _PAIRS_HESSIAN_UP_TO)

        def hessian(d2):
            Xw = X * d2[:, :, None]
            if by_pairs:
                # One multiply-and-reduce fusion over all D x D pairs, in
                # float32 on the vector unit: no dot pins X to its
                # lane-padded rows-minor order, so the compiler keeps a
                # short-row block entity-minor through the whole body.
                H = jnp.sum(Xw[:, :, :, None] * X[:, :, None, :], axis=1)
                return jnp.transpose(H, (1, 2, 0)) + l2_eye
            return jnp.einsum(
                "erd,erk->dke", Xw, X, precision=_HI) + l2_eye

        def grad_at(w):
            m = jnp.einsum("erd,de->er", X, w, precision=_HI) + off
            g = jnp.einsum(
                "er,erd->de", wt * loss.d1(m, yb), X, precision=_HI
            ) + l2 * w
            return m, g

        w0 = w0.T
        _, g0 = grad_at(w0)
        gtol = tol * jnp.maximum(1.0, jnp.linalg.norm(g0, axis=0))

        def cond(carry):
            i, _w, done, _n = carry
            return (i < max_iters) & ~jnp.all(done)

        def body(carry):
            i, w, done, n = carry
            m, g = grad_at(w)
            newly = jnp.linalg.norm(g, axis=0) <= gtol
            step = _spd_solve_direct(hessian(wt * loss.d2(m, yb)), g)
            # Margin-change damp (the rank1/dim1 clamp, per lane): one
            # step moves no row's margin by more than 20.
            dm = jnp.einsum("erd,de->er", X, step, precision=_HI)
            scale = jnp.minimum(
                1.0,
                20.0 / jnp.maximum(jnp.max(jnp.abs(dm), axis=1), 1e-12),
            )
            keep = done | newly
            w = jnp.where(keep, w, w - scale * step)
            return i + 1, w, keep, n + ~keep

        done0 = jnp.zeros((X.shape[0],), bool)
        _, w, _, n = jax.lax.while_loop(
            cond, body,
            (jnp.zeros((), jnp.int32), w0, done0, _no_steps(done0)),
        )
        return w.T, n

    def make_solve_one(history: int):
        def solve_one(X, y, wts, off, w0, l1, l2):
            def vg(w):
                m = X @ w + off
                val = jnp.sum(wts * loss.value(m, y)) + 0.5 * l2 * jnp.vdot(w, w)
                g = X.T @ (wts * loss.d1(m, y)) + l2 * w
                return val, g

            if use_owlqn:
                return owlqn_solve(
                    vg,
                    w0,
                    l1,
                    OWLQNConfig(
                        max_iters=opt.max_iters,
                        tolerance=opt.tolerance,
                        history=history,
                    ),
                )
            if use_tron:
                def hvp(w, v, aux):
                    return X.T @ (aux * (X @ v)) + l2 * v

                def d2f(w):
                    return wts * loss.d2(X @ w + off, y)

                return tron_solve(
                    vg, hvp, w0,
                    TRONConfig(
                        max_iters=opt.max_iters, tolerance=opt.tolerance
                    ),
                    d2_fn=d2f,
                )
            return lbfgs_solve(
                vg,
                w0,
                LBFGSConfig(
                    max_iters=opt.max_iters,
                    tolerance=opt.tolerance,
                    history=history,
                ),
            )

        return solve_one

    def path(block: EntityBlock) -> str:
        """The static (trace-time) path a block's shape takes."""
        if use_owlqn:
            return "owlqn"
        # Single-row buckets, single-feature buckets and small-D buckets
        # each have an exact Newton form.  (A gram-space dual Newton for
        # 2 <= R <= 16 was tried and measured 4.5x SLOWER than the vmapped
        # L-BFGS: batched small jnp.linalg.solve lowers to scalar-heavy LU
        # loops on TPU.)
        if block.rows_per_entity == 1:
            return "rank1"
        if block.block_dim == 1:
            return "dim1"
        if block.block_dim <= 32:
            return "newton_direct"
        return "tron" if use_tron else "lbfgs"

    def solve_block(
        block: EntityBlock, offsets_block: Array, w0: Array, l1: Array, l2: Array
    ) -> tuple[Array, Array]:
        """``(E, D)`` coefficients and each lane's iteration count."""
        taken = path(block)
        if taken == "rank1":
            return rank1_newton(block, offsets_block, w0, l2)
        if taken == "dim1":
            return dim1_newton(block, offsets_block, w0, l2)
        if taken == "newton_direct":
            return newton_block(
                block, offsets_block, w0, l2,
                opt.max_iters, opt.tolerance,
            )
        # History beyond the LOCAL problem dimension buys nothing (L-BFGS
        # with m >= d already behaves Newton-like) but every extra pair
        # adds two scan steps per iteration — sequential step count is what
        # dominates these small batched solves.
        solve_one = make_solve_one(min(opt.history, block.block_dim))
        res = jax.vmap(
            solve_one, in_axes=(0, 0, 0, 0, 0, None, None)
        )(block.x_erd, block.labels, block.weights, offsets_block, w0, l1, l2)
        return res.w, res.iterations

    return _BlockSolver(solve_block, path)


_HI = jax.lax.Precision.HIGHEST

#: ``(columns, rows)`` of an entity up to which ``newton_block`` builds the
#: Hessian as elementwise pairs and not on the matrix unit: the shapes
#: where both were timed and the pairs won, and nothing beyond them.  A
#: batched matmul costs about the same for every 128-row chunk of an entity
#: whatever the chunk holds, and its (D, D) result pads to 128 lanes; the
#: pairs cost rows x D^2.  Measured a trip on one TPU v5 lite (PERF.md
#: section 6, PR 32), matmul / pairs: 32 rows x 21 columns 3.52 / 1.45
#: ms, 64 x 21 6.91 / 6.07, 128 x 9 0.76 / 0.55, 256 x 9 0.66 / 0.57;
#: 128 x 21 5.52 / 7.99, 256 x 21 5.23 / 9.20.  The pairs' product is
#: ``(E, R, D, D)`` before its reduction, 4.3 GB at 38,069 x 64 x 21: the
#: compiler has to fuse the two, and tests/test_kernel_names_v5e.py holds
#: it to that at these shapes.
_PAIRS_HESSIAN_UP_TO = ((21, 64), (9, 256))

#: A pivot at or under this share of its own diagonal entry is rounding
#: noise (float32 resolves 1.2e-7): the column is a combination of the
#: ones before it, and its component of the solution is set to zero.
_PIVOT_FLOOR = 1e-6


def _spd_solve_direct(H: Array, g: Array) -> Array:
    """``x`` with ``H x = g`` per lane, ENTITY-MINOR: ``H`` is ``(D, D, E)``
    symmetric positive semi-definite per lane, ``g`` and ``x`` are
    ``(D, E)``.  One right-looking LDL^T elimination, a ``fori_loop`` over
    the D columns, then one back substitution; every operation is
    elementwise over E, so no reduction crosses lanes and nothing pads a
    D-wide axis to 128 lanes.  Only ``H``'s upper triangle is read.

    NO lax.linalg (batched ``jnp.linalg.solve`` lowers to scalar-heavy LU
    loops on TPU: measured 4.5x slower than the vmapped L-BFGS it was meant
    to replace) and no matvec pass: the D-step CG this replaces re-read a
    lane-padded ``(E, D, D)`` D times a trip, 39 of a trip's 77 ms on the
    MovieLens-20M per-user ladder (PERF.md section 6, PR 32).

    A pivot at the floor (an all-zero or duplicated column with no L2, or
    with an L2 under half of ``_PIVOT_FLOOR`` times the column's diagonal
    entry: a duplicate's pivot is about twice the L2) zeroes that component of ``x``, so the step stays finite.  In
    ``newton_block`` such a coefficient keeps its start value, trip after
    trip, where CG would have moved it; its gradient is left at what the
    rest of the lane leaves there (of the order of L2 times the
    coefficient), and a lane that this keeps over its gradient test counts
    to the cap.  A padding lane (``H = l2 I``, ``g = 0``) returns exactly
    zero."""
    dim = g.shape[0]
    below = jnp.arange(dim)[:, None]                  # row index, (D, 1)

    def eliminate(j, carry):
        # After step j row j of ``A`` holds d_j L[k, j] for k > j and the
        # pivot d_j at k = j; rows under it hold the Schur complement.
        A, r, inv_d = carry
        row = A[j]
        pivot = row[j]
        sound = pivot > jnp.maximum(_PIVOT_FLOOR * H[j, j], 1e-30)
        inv = jnp.where(sound, 1.0 / pivot, 0.0)
        under = below > j
        col = jnp.where(under, row * inv, 0.0)        # L[:, j] under d_j
        A = A - col[:, None, :] * jnp.where(under, row, 0.0)[None, :, :]
        r = r - col * r[j]
        return A, r, inv_d.at[j].set(inv)

    A, y, inv_d = jax.lax.fori_loop(
        0, dim, eliminate, (H, g, jnp.zeros_like(g)))

    def substitute(t, x):
        j = dim - 1 - t
        tail = jnp.sum(jnp.where(below > j, A[j] * x, 0.0), axis=0)
        return x.at[j].set(inv_d[j] * (y[j] - tail))

    return jax.lax.fori_loop(0, dim, substitute, jnp.zeros_like(g))


def _no_steps(done: Array) -> Array:
    """A per-lane step counter at zero, for a solver loop's carry."""
    return jnp.zeros(done.shape, jnp.int32)


class _BlockSolver:
    """``solver(block, offsets_block, w0, l1, l2)`` gives the ``(E, D)``
    coefficients; ``solver.counted(...)`` also each lane's iteration count
    (a lane that froze early stopped counting).  Both jitted; under an
    outer jit they inline.  ``solver.path(block)`` names the static path
    the block's shape takes (``"rank1"``, ``"dim1"``, ``"newton_direct"``,
    ``"lbfgs"``, ``"owlqn"``, ``"tron"``)."""

    def __init__(self, solve, path):
        self.counted = jax.jit(solve)
        self.path = path
        self._coefficients = jax.jit(lambda *args: solve(*args)[0])

    def __call__(self, *args) -> Array:
        return self._coefficients(*args)


def _gather_block_offsets(offsets: Array, block: EntityBlock) -> Array:
    """Per-row offsets for one entity block; padding rows (sentinel index)
    read the appended zero slot."""
    padded = jnp.concatenate([offsets, jnp.zeros((1,), offsets.dtype)])
    return jnp.take(padded, block.row_index, axis=0)


def _program_name(stem: str, coordinate: str) -> str:
    """``stem`` with the coordinate's name, as an identifier: a jitted
    function's name is its program's in a device trace, so two random
    effects' ladders are told apart there (``jit_random_effect_train_
    per_user``, ``..._per_movie``)."""
    suffix = "".join(c if c.isalnum() else "_" for c in coordinate)
    return f"{stem}_{suffix}" if suffix else stem


@functools.lru_cache(maxsize=64)
def _re_train_all_jit(
    task: str, config: GlmOptimizationConfig, layout_sig: tuple,
    coordinate: str = "",
):
    """ONE jitted program for ALL buckets: per-bucket dispatches each pay
    a host→device round trip, and a long-tailed dataset has many buckets.
    Bucket shapes differ but are static, so a single trace inlines every
    bucket's solver into one HLO.  Memoized PROCESS-WIDE on
    (task, config, dataset layout) like ``_make_block_solver`` —
    per-instance jits meant every new coordinate object (a second fit, a
    grid point, a fresh estimator) re-traced and re-compiled identical
    programs.  ``layout_sig`` is unused inside: it is the eviction
    granule (see ``_layout_sig``).

    Returns ``(states, counts)``: per bucket the ``(E, D)`` coefficients
    and what its solve counted -- the block's iterations (its slowest
    lane's), the sum over its real lanes, and how many real lanes froze
    before the block's loop ended.  A real lane is one with a weighted
    row.  The function's name is the program's in a device trace
    (``jit_random_effect_train_<coordinate>``)."""
    solver = _make_block_solver(task, config)

    def random_effect_train(blocks, offsets, w0s, l1, l2):
        states, counts = [], []
        for b, w0 in zip(blocks, w0s):
            with jax.named_scope("re.block_solve"):
                w, n = solver.counted(
                    b, _gather_block_offsets(offsets, b), w0, l1, l2)
            real = jnp.any(b.weights > 0, axis=1)
            n = jnp.where(real, n, 0)
            states.append(w)
            counts.append({
                "iterations_max": jnp.max(n),
                "iterations_sum": jnp.sum(n),
                "frozen_early": jnp.sum(real & (n < jnp.max(n))),
            })
        return states, counts

    random_effect_train.__name__ = _program_name(
        "random_effect_train", coordinate)
    return jax.jit(random_effect_train)


@functools.lru_cache(maxsize=64)
def _re_score_all_jit(n_rows: int, layout_sig: tuple, coordinate: str = ""):
    """One jitted scoring scatter over all buckets (active + passive),
    memoized on (global row count, dataset layout).  BOUNDED: layouts
    vary per dataset/fold, and an unbounded cache would pin one compiled
    program per distinct layout for process lifetime."""

    # The function's name is the program's in a device trace
    # (``jit_random_effect_score_<coordinate>``).
    def random_effect_score(blocks, passive_blocks, coefs_list):
        total = jnp.zeros((n_rows + 1,), jnp.float32)
        passive = passive_blocks or [None] * len(blocks)
        for block, passive_rows, coefs in zip(blocks, passive, coefs_list):
            s = jnp.einsum("erd,ed->er", block.x_erd, coefs)
            # Padding rows (sentinel index) scatter into the trailing slot.
            total = total.at[block.row_index.ravel()].add(s.ravel())
            if passive_rows is not None:
                # Active/passive split: capped-out rows are never trained
                # on but MUST be scored, or other coordinates would see
                # offsets missing this coordinate's contribution there.
                total = total.at[passive_rows.row_index].add(
                    passive_rows.scores(coefs))
        return total[:n_rows]

    random_effect_score.__name__ = _program_name(
        "random_effect_score", coordinate)
    return jax.jit(random_effect_score)


class RandomEffectCoordinate(Coordinate):
    """Reference: ``RandomEffectCoordinate`` — per-entity solves, batched.

    State is a list of per-bucket coefficient arrays ``(E, D)`` in each
    block's LOCAL (projected) column space.
    """

    kind = "random"
    #: What the last ``train`` counted (rebound by ``train``; a subclass
    #: with a ``train`` of its own counts nothing).
    _counts: dict = {}

    @property
    def rows_passive(self) -> int:
        return self.dataset.rows_passive

    def __init__(
        self,
        name: str,
        dataset: RandomEffectDataset,
        task: str,
        config: GlmOptimizationConfig,
        reg_weight: float = 0.0,
        feature_shard: str = "global",
        entity_key: str = "",
    ):
        self.name = name
        self.dataset = dataset
        self.task = losses_lib.get(task).name
        self.config = config
        self.reg_weight = reg_weight
        self.feature_shard = feature_shard
        self.entity_key = entity_key or name
        self._solver = _make_block_solver(task, config)
        sig = _layout_sig((dataset.blocks, dataset.passive_blocks))
        self._train_all_jit = _re_train_all_jit(self.task, config, sig, name)
        self._score_all_jit = _re_score_all_jit(
            dataset.n_global_rows, sig, name)
        tel = telemetry_mod.current()
        tel.gauge("game_re_bucket_count").set(len(dataset.blocks))
        tel.gauge(
            _program_name("game_re", name).lower() + "_passive_rows"
        ).set(dataset.rows_passive)

    def train_counts(self) -> dict:
        return self._counts

    def train(self, offsets: Array, warm_state=None) -> list[Array]:
        l1 = jnp.asarray(
            self.config.regularization.l1_weight(1.0) * self.reg_weight,
            jnp.float32,
        )
        l2 = jnp.asarray(
            self.config.regularization.l2_weight(1.0) * self.reg_weight,
            jnp.float32,
        )
        w0s = [
            (
                warm_state[bi]
                if warm_state is not None
                else jnp.zeros(
                    (block.n_entities, block.block_dim), jnp.float32
                )
            )
            for bi, block in enumerate(self.dataset.blocks)
        ]
        state, counts = self._train_all_jit(
            self.dataset.blocks, jnp.asarray(offsets, jnp.float32), w0s,
            l1, l2,
        )
        # All buckets solve inside ONE program, so a bucket has no host
        # interval of its own: its shape and what its solve counted on the
        # device are one entry of the update's ``buckets`` attribute.
        rows_real = self.dataset.block_rows_real or [None] * len(w0s)
        self._counts = {"buckets": [
            {"lanes": block.n_entities,
             "rows_padded": block.n_entities * block.rows_per_entity,
             "rows_real": real, "dim": block.block_dim,
             "solver": self._solver.path(block), **counted}
            for block, real, counted in zip(
                self.dataset.blocks, rows_real, counts)
        ]}
        return state

    def score(self, state: list[Array]) -> Array:
        return self._score_all_jit(
            self.dataset.blocks, self.dataset.passive_blocks, state
        )

    def _block_variances(self, block: EntityBlock, coefs: Array,
                         offsets: Array) -> np.ndarray:
        """Per-entity diagonal-inverse-Hessian variances (the reference's
        SIMPLE variance type, per entity): 1 / (Σ_r w·d2(m)·X² + λ₂),
        evaluated at the FULL final margins (residual offsets included)."""
        loss = losses_lib.get(self.task)
        l2 = jnp.asarray(
            self.config.regularization.l2_weight(1.0) * self.reg_weight,
            jnp.float32,
        )
        off_b = _gather_block_offsets(jnp.asarray(offsets, jnp.float32), block)
        X = block.x_erd
        m = jnp.einsum("erd,ed->er", X, coefs) + off_b
        d2w = block.weights * loss.d2(m, block.labels)
        diag = jnp.einsum("er,erd->ed", d2w, X * X) + l2
        return np.asarray(1.0 / jnp.maximum(diag, 1e-12))

    @functools.cached_property
    def _lanes(self) -> EntityLanes:
        """The ladder's constants for the model table (entity keys and
        ``col_map``s): read to the host and sorted once, not once a fit."""
        return EntityLanes(
            self.dataset.entity_ids,
            [b.col_map for b in self.dataset.blocks],
        )

    def finalize(self, state: list[Array], offsets=None) -> RandomEffectModel:
        """The model table as flat arrays (:class:`EntityTable`): no Python
        object per entity is made, here or when the old table is freed."""
        variances = None
        if self.config.compute_variances and offsets is not None:
            variances = [
                self._block_variances(block, coefs, offsets)
                for block, coefs in zip(self.dataset.blocks, state)
            ]
        table = self._lanes.table(jax.device_get(list(state)), variances)
        return RandomEffectModel(
            coefficients=table,
            feature_shard=self.feature_shard,
            entity_key=self.entity_key,
            task=self.task,
            n_features=self.dataset.n_features,
            variances=(
                None if variances is None else EntityVariances(table)
            ),
        )

    def make_validation_scorer(self, shards: dict, ids: dict):
        from photon_ml_tpu.game.validation import RandomEffectValidationScorer

        return RandomEffectValidationScorer(
            self.dataset, ids[self.entity_key], shards[self.feature_shard]
        )
