"""GLM objective functions: value / gradient / Hessian-vector product.

The analogue of the reference's ``ObjectiveFunction`` hierarchy —
``DistributedGLMLossFunction`` / ``SingleNodeGLMLossFunction`` and their
``ValueAndGradientAggregator`` / ``HessianVectorAggregator`` hot loops
(SURVEY.md §2, §3.1).  Where the reference splits "distributed" and
"single-node" into separate class trees (Spark treeAggregate vs local loops),
here ONE pure function serves both: computed per-shard, it is the single-node
objective; wrapped in ``shard_map`` with ``axis_name='data'`` it becomes the
distributed objective, with ``lax.psum`` playing the role of
``RDD.treeAggregate`` (see photon_ml_tpu.parallel.distributed).

Semantics follow the reference: the data term is a **weighted sum** (not
mean) of per-example losses; L2 adds ``½·λ·‖w‖²`` to the value, ``λ·w`` to
the gradient, and ``λ·v`` to the HVP.  L1 never appears here — it lives in
OWL-QN's orthant logic (optim/owlqn.py), as in the reference.

The Hessian-vector product uses the Gauss-Newton/GLM closed form
``Xᵀ(weight ⊙ d2(m) ⊙ (X v))`` — what the reference's
``HessianVectorAggregator`` computes with per-row BLAS — rather than
generic forward-over-reverse autodiff, because it reuses the cached margins
and keeps the hot loop at exactly two (sparse) matvecs per CG step.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.data.dataset import GlmData
from photon_ml_tpu.data.normalization import NormalizationContext
from photon_ml_tpu.ops.losses import PointwiseLoss

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class GlmObjective:
    """Binds a pointwise loss and optional normalization into a GLM objective.

    All methods are pure and jit/vmap/shard_map-safe.  ``l2_weight`` is a
    method argument (not a field) so a single compiled optimizer can sweep a
    regularization grid without recompilation — the TPU analogue of the
    reference's warm-start loop over regularization weights.
    """

    loss: PointwiseLoss
    normalization: Optional[NormalizationContext] = None
    # "f32": plain XLA tree reduction (default — summands are non-negative
    # for every supported loss, so the tree sum's relative error is already
    # ~log₂(n)·ε).  "f64": the VALUE reduction upcasts to float64 before
    # summing (the reference accumulates in f64 end-to-end via Breeze) and
    # the returned value STAYS f64 so convergence tests in the solvers see
    # the extra precision; needs ``jax_enable_x64`` (works on this TPU —
    # XLA emulates f64 — at a cost on the value pass only; the gradient's
    # per-coordinate sums stay f32 tree reductions).
    accumulate: str = "f32"

    def __post_init__(self):
        if self.accumulate not in ("f32", "f64"):
            raise ValueError(
                f"accumulate must be f32|f64, got {self.accumulate!r}"
            )
        if self.accumulate == "f64":
            import jax as _jax

            if not _jax.config.jax_enable_x64:
                raise ValueError(
                    "accumulate='f64' needs jax_enable_x64 "
                    "(jax.config.update('jax_enable_x64', True))"
                )

    def _wsum(self, weights: Array, vals: Array) -> Array:
        """The objective's weighted-sum reduction (see ``accumulate``)."""
        prod = weights * vals
        if self.accumulate == "f64":
            return jnp.sum(prod.astype(jnp.float64))
        return jnp.sum(prod)

    # -- normalized linear maps (see data/normalization.py) ----------------
    def _matvec(self, data: GlmData, w: Array) -> Array:
        norm = self.normalization
        if norm is None:
            return data.features.matvec(w)
        m = data.features.matvec(w * norm.factors)
        return m - jnp.dot(w, norm.factors * norm.shifts)

    def _rmatvec(self, data: GlmData, u: Array) -> Array:
        norm = self.normalization
        if norm is None:
            return data.features.rmatvec(u)
        g = data.features.rmatvec(u)
        return norm.factors * (g - norm.shifts * jnp.sum(u))

    def margins(self, w: Array, data: GlmData) -> Array:
        return self._matvec(data, w) + data.offsets

    # -- local (per-shard) pieces, no regularization -----------------------
    def raw_value(self, w: Array, data: GlmData) -> Array:
        m = self.margins(w, data)
        return self._wsum(data.weights, self.loss.value(m, data.labels))

    def raw_value_and_grad(self, w: Array, data: GlmData) -> tuple[Array, Array]:
        m = self.margins(w, data)
        value = self._wsum(data.weights, self.loss.value(m, data.labels))
        u = data.weights * self.loss.d1(m, data.labels)
        return value, self._rmatvec(data, u)

    def d2_weights(self, w: Array, data: GlmData) -> Array:
        """``weight ⊙ d2(m, y)`` — compute once per outer iterate and pass to
        :meth:`raw_hvp`/:meth:`hvp` so each CG step costs two matvecs, not three."""
        with jax.named_scope("objective.d2_weights"):
            m = self.margins(w, data)
            return data.weights * self.loss.d2(m, data.labels)

    def raw_hvp(
        self, w: Array, v: Array, data: GlmData, d2w: Array | None = None
    ) -> Array:
        if d2w is None:
            d2w = self.d2_weights(w, data)
        dm = self._matvec(data, v)
        return self._rmatvec(data, d2w * dm)

    # -- full objective (optionally reduced over a mesh axis) --------------
    def value(
        self, w: Array, data: GlmData, l2_weight=0.0, axis_name: str | None = None
    ) -> Array:
        val = self.raw_value(w, data)
        if axis_name is not None:
            val = lax.psum(val, axis_name)
        return val + 0.5 * l2_weight * jnp.dot(w, w)

    def value_and_grad(
        self, w: Array, data: GlmData, l2_weight=0.0, axis_name: str | None = None
    ) -> tuple[Array, Array]:
        with jax.named_scope("objective.value_and_grad"):
            val, grad = self.raw_value_and_grad(w, data)
            if axis_name is not None:
                # The treeAggregate analogue: one fused all-reduce over ICI.
                val, grad = lax.psum((val, grad), axis_name)
            return (val + 0.5 * l2_weight * jnp.dot(w, w),
                    grad + l2_weight * w)

    def hvp(
        self,
        w: Array,
        v: Array,
        data: GlmData,
        l2_weight=0.0,
        axis_name: str | None = None,
        d2w: Array | None = None,
    ) -> Array:
        with jax.named_scope("objective.hvp"):
            h = self.raw_hvp(w, v, data, d2w)
            if axis_name is not None:
                h = lax.psum(h, axis_name)
            return h + l2_weight * v

    # -- scoring -----------------------------------------------------------
    def mean(self, w: Array, data: GlmData) -> Array:
        """Mean response (inverse link of the margin) — scoring-time output."""
        return self.loss.mean_fn(self.margins(w, data))
