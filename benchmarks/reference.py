"""The plain reference of the GLM cells: the L2-regularised GLM objective

    f(w) = sum_i loss(x_i . w, y_i) + lambda/2 |w|^2

its gradient, and what they certify about a solver's answer, in float64 on
the host over the ELL arrays the generator made.  It imports nothing of the
program and takes nothing the program made.

``precision="bf16"`` is the CONTROL: the same arithmetic with the matrix
values, the coefficients and the per-row derivative rounded to bfloat16
before each product (accumulation stays wide, as an MXU's would).  It is
the step below the float32 the configurations state, and the comparison in
``windows/fit.py`` has to call it not correct.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp


def round_bf16(x: np.ndarray) -> np.ndarray:
    """Round float values to the nearest bfloat16 (ties to even), returned
    as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    r = ((u >> np.uint32(16)) & np.uint32(1)) + np.uint32(0x7FFF)
    return ((u + r) & np.uint32(0xFFFF0000)).view(np.float32)


def _logistic(z: np.ndarray, y: np.ndarray):
    """Per-row value softplus(z) - y z and derivative sigmoid(z) - y."""
    value = np.logaddexp(0.0, z) - y * z
    d1 = 0.5 * (1.0 + np.tanh(0.5 * z)) - y
    return value, d1


_LOSSES = {"logistic": _logistic}


class GlmReference:
    def __init__(self, cols, vals, labels, n_features, loss="logistic",
                 block_rows=1 << 16, threads=None, row_scale=None):
        if loss not in _LOSSES:
            raise ValueError(f"the reference has no loss {loss!r}: "
                             f"{sorted(_LOSSES)}")
        self.cols, self.vals = cols, vals
        self.labels = np.asarray(labels, np.float64)
        self.n, self.k1 = cols.shape
        self.d1 = int(n_features) + 1
        self.loss = _LOSSES[loss]
        self.block_rows = block_rows
        self.threads = threads or max(1, min(12, os.cpu_count() or 1))
        # Per-row weights; only the fault tests set them.
        self.row_scale = row_scale

    def _block(self, lo, hi, bf16):
        v = self.vals[lo:hi].reshape(-1)
        v = round_bf16(v) if bf16 else v
        indptr = np.arange(0, (hi - lo + 1) * self.k1, self.k1, dtype=np.int32)
        return sp.csr_matrix(
            (v.astype(np.float64), self.cols[lo:hi].reshape(-1).astype(np.int32),
             indptr), shape=(hi - lo, self.d1))

    def value_and_grad(self, w, lam, precision="f64"):
        """(f(w), grad f(w)) as float64."""
        bf16 = precision == "bf16"
        if precision not in ("f64", "bf16"):
            raise ValueError(f"precision {precision!r}: f64 or bf16")
        w = np.asarray(w, np.float64)
        wq = round_bf16(w).astype(np.float64) if bf16 else w

        def part(lo):
            hi = min(self.n, lo + self.block_rows)
            X = self._block(lo, hi, bf16)
            value, d1 = self.loss(X @ wq, self.labels[lo:hi])
            if self.row_scale is not None:
                value = value * self.row_scale[lo:hi]
                d1 = d1 * self.row_scale[lo:hi]
            if bf16:
                d1 = round_bf16(d1).astype(np.float64)
            return value.sum(), d1 @ X

        with ThreadPoolExecutor(self.threads) as pool:
            parts = list(pool.map(part, range(0, self.n, self.block_rows)))
        value = float(sum(p[0] for p in parts)) + 0.5 * lam * float(w @ w)
        grad = np.sum([p[1] for p in parts], axis=0) + lam * w
        return value, grad
