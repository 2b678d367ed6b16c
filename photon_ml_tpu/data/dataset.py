"""On-device GLM datasets.

The analogue of the reference's ``LabeledPoint`` RDDs and ``FixedEffectDataset``
(SURVEY.md §2, "GAME data layer"), reshaped for TPU: instead of millions of
per-row objects scattered across JVM partitions, one statically-shaped pytree
per shard — features as a :class:`~photon_ml_tpu.ops.sparse.FeatureMatrix`,
labels / weights / offsets as flat arrays.  Padding rows (needed to make every
device's shard the same size) carry ``weight = 0`` so they contribute nothing
to any weighted sum, which is how all downstream math stays mask-free.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.ops.sparse import DenseMatrix, FeatureMatrix, from_scipy_csr
from photon_ml_tpu.telemetry import layer_span
from photon_ml_tpu.utils.placement import place_leaves

Array = jax.Array


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["features", "labels", "weights", "offsets"],
    meta_fields=[],
)
@dataclasses.dataclass
class GlmData:
    """One shard of GLM training data.

    Mirrors the reference's ``LabeledPoint`` (label, features, offset, weight)
    but batched: all arrays have leading dimension ``n_rows``.
    """

    features: FeatureMatrix
    labels: Array  # (n_rows,)
    weights: Array  # (n_rows,) — 0 for padding rows
    offsets: Array  # (n_rows,) — fixed per-row margin offsets

    @property
    def n_rows(self) -> int:
        return self.features.shape[0]

    @property
    def n_features(self) -> int:
        return self.features.shape[1]

    @property
    def weight_sum(self) -> Array:
        return jnp.sum(self.weights)


def make_glm_data(
    features,
    labels,
    weights=None,
    offsets=None,
    pad_rows: int | None = None,
    pad_nnz: int | None = None,
    dtype=jnp.float32,
    use_pallas: bool | str = "auto",
) -> GlmData:
    """Build a GlmData shard from host data.

    ``features`` may be a numpy 2-D array (→ DenseMatrix) or a scipy sparse
    matrix (→ SparseMatrix / PallasSparseMatrix).  ``pad_rows`` pads the row
    dimension with zero-weight rows up to a static budget.

    ``use_pallas`` selects the tiled Pallas layout for sparse features
    (ops/sparse_pallas.py): ``"auto"`` uses it on TPU when the matrix is
    large enough for the kernels to win (the tiled layout costs host build
    time and ~3x slot memory, and pays off via ~70x faster value+grad),
    and its wide form (``WideSparseMatrix``) where the tile grid would be
    mostly empty: ``grid_fill_bound`` under ``WIDE_FILL``;
    ``True``/``False`` force the tiled layout or COO.

    The result is resident when this returns (every leaf is ready): the
    call is one ``data.make_glm_data`` layer span, the host build of the
    tiled layout its child ``layout.build``, and everything from the first
    host-to-device copy to the last leaf being ready its child
    ``layout.place`` (docs/telemetry.md "Layer spans"; the COO layout has
    no build of its own, so its host canonicalisation counts as placing),
    which says of every leaf what its copy cost the host (``leaves``) and
    how long the one wait for all of them was (``wait_s``).
    """
    import scipy.sparse as sp

    with layer_span("data.make_glm_data") as span:
        n = features.shape[0]
        labels = np.asarray(labels, dtype=np.float32)
        weights = (
            np.ones(n, np.float32) if weights is None
            else np.asarray(weights, np.float32)
        )
        offsets = (
            np.zeros(n, np.float32) if offsets is None
            else np.asarray(offsets, np.float32)
        )
        target_rows = pad_rows if pad_rows is not None else n
        if target_rows < n:
            raise ValueError(f"pad_rows={target_rows} < n_rows={n}")
        pad = target_rows - n
        if pad:
            labels = np.concatenate([labels, np.zeros(pad, np.float32)])
            weights = np.concatenate([weights, np.zeros(pad, np.float32)])
            offsets = np.concatenate([offsets, np.zeros(pad, np.float32)])

        if sp.issparse(features):
            if pad:
                features = sp.vstack(
                    [features.tocsr(), sp.csr_matrix((pad, features.shape[1]))]
                )
            wide = False
            if use_pallas == "auto":
                from photon_ml_tpu.ops.sparse_pallas import (
                    WIDE_FILL, grid_fill_bound, pallas_available)

                use_pallas = (
                    pallas_available()
                    and features.shape[0] >= 65536
                    and features.nnz >= 1 << 20
                )
                wide = use_pallas and grid_fill_bound(
                    features.nnz, *features.shape) < WIDE_FILL
            nnz = int(features.nnz)
            if use_pallas:
                from photon_ml_tpu.ops.sparse_pallas import (
                    host_layout_from_scipy_csr,
                )

                features = host_layout_from_scipy_csr(
                    features, pad_nnz=pad_nnz, dtype=dtype, wide=wide)
                to_device = None  # the host layout's leaves are placed below
            else:
                to_device = partial(
                    from_scipy_csr, pad_nnz=pad_nnz, dtype=dtype)
        else:
            features = np.asarray(features)
            if pad:
                features = np.concatenate([
                    features,
                    np.zeros((pad, features.shape[1]), features.dtype),
                ])
            nnz = int(features.size)

            def to_device(dense) -> FeatureMatrix:
                return DenseMatrix(jnp.asarray(dense, dtype=dtype))

        with layer_span("layout.place") as place:
            if to_device is not None:
                # COO and dense features canonicalise / cast and place in
                # one call: their leaves arrive below resident already.
                t0 = time.perf_counter()
                features = to_device(features)
                place.set(features_s=time.perf_counter() - t0)
            data, leaves = place_leaves(GlmData(
                features=features, labels=labels, weights=weights,
                offsets=offsets))
            # Every caller solves on these next; waiting here (the one
            # sync) is what lets the span say when the data is resident.
            t0 = time.perf_counter()
            jax.block_until_ready(data)
            place.set(
                wait_s=time.perf_counter() - t0,
                bytes=sum(leaf["bytes"] for leaf in leaves), leaves=leaves)
        span.set(rows=int(target_rows), nnz=nnz,
                 layout=type(data.features).__name__)
    return data
