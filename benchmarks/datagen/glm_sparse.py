"""Seeded sparse GLM data in the shape of a bag-of-words corpus (RCV1-v2 is
the configuration that uses it): every row has ``nnz_per_row`` distinct
terms drawn by popularity, positive weights scaled to unit length, and a
label from a planted model.

The corpus -- which term id is how popular, each row's columns and values,
the planted model and the labels -- comes from the configuration's
``data_seed``.  ``--seed`` mirrors it: every column the seed draws a -1 for
has its values and its planted coefficient negated.  That is a symmetry of
the objective which floating point keeps exactly, so every seed is the same
problem and the same work on other numbers, and its answer is the mirror
image of any other seed's.  Drawing the corpus itself from the seed was
measured on the chip and withdrawn: the line search then makes another
number of trials on every corpus, and ``solve_s`` spread 8.7% over six seeds
with every solve stopped after 10 iterations (12.8% run to tolerance), where
the widest bound admits 5%; moving the rows about by the seed changes the
order of the float32 sums, which a warm-started chain of solves on this
ill-conditioned problem amplifies until the fourth solve's objective differs
in its third digit (CPU, two seeds).  PERF.md section 6 has the readings.

The configuration gives the sizes (``n_rows``, ``n_features``,
``nnz_per_row``) and, under ``generator_params``, the law:

  ``zipf_exponent``, ``zipf_shift``: the term of popularity rank r is drawn
      with probability proportional to ``(r + shift) ** -exponent``
      (Zipf-Mandelbrot), without replacement within a row;
  ``value_log_sigma``: a term's weight is ``exp(sigma * N(0,1))`` before the
      row is scaled to unit Euclidean length (cosine normalisation);
  ``model_scale``: planted coefficients are ``scale * N(0,1)``; the planted
      intercept puts the median row at even odds, so about half of the
      labels, which are Bernoulli of the planted sigmoid, are positive;
  ``block_rows``: rows made at a time (one random stream per block).

Returned arrays are in ELL form, ``(n_rows, nnz_per_row + 1)``: the last
column is the intercept (column id ``n_features``, value 1).  Columns of a
row are sorted and distinct, so the CSR view over them is canonical.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _threads() -> int:
    return max(1, min(12, os.cpu_count() or 1))


def _distinct_ranks(rng, cdf, rows: int, k: int):
    """``rows`` x ``k`` popularity ranks, distinct within a row: drawn by
    the law, a repeated rank drawn again until none repeats."""
    ranks = np.searchsorted(cdf, rng.random((rows, k)), side="right")
    ranks = ranks.astype(np.int32)
    while True:
        ranks.sort(axis=1)
        dup = np.zeros(ranks.shape, bool)
        dup[:, 1:] = ranks[:, 1:] == ranks[:, :-1]
        n_dup = int(dup.sum())
        if n_dup == 0:
            return ranks
        ranks[dup] = np.searchsorted(cdf, rng.random(n_dup), side="right")


def generate(cfg: dict, seed: int) -> dict:
    """The data of one run, from the configuration's sizes and law."""
    n = int(cfg["n_rows"])
    d = int(cfg["n_features"])
    k = int(cfg["nnz_per_row"])
    law = cfg["generator_params"]
    block_rows = int(law["block_rows"])
    data_seed = int(cfg["data_seed"])
    if k > d:
        raise ValueError(f"nnz_per_row={k} > n_features={d}")
    seed = int(seed) % (1 << 63)

    # Popularity rank -> column id: popular terms lie anywhere in the
    # vocabulary, as in a corpus whose ids follow the alphabet.
    col_of_rank = np.random.default_rng(
        [data_seed, 3]).permutation(d).astype(np.int32)
    p = (np.arange(1, d + 1) + float(law["zipf_shift"])) ** -float(
        law["zipf_exponent"])
    cdf = np.cumsum(p / p.sum())
    cdf[-1] = 1.0
    w_plant = np.zeros(d + 1, np.float64)
    w_plant[:d] = float(law["model_scale"]) * np.random.default_rng(
        [data_seed, 0]).standard_normal(d)
    sigma = np.float32(law["value_log_sigma"])

    cols = np.empty((n, k + 1), np.int32)
    vals = np.empty((n, k + 1), np.float32)
    labels = np.empty(n, np.float32)
    cols[:, k] = d
    vals[:, k] = 1.0

    def fill(lo: int) -> np.ndarray:
        """Columns and values of one block; returns its rows' planted
        scores without the intercept."""
        hi = min(n, lo + block_rows)
        rng = np.random.default_rng([data_seed, 1, lo // block_rows])
        c = col_of_rank[_distinct_ranks(rng, cdf, hi - lo, k)]
        c.sort(axis=1)
        v = np.exp(sigma * rng.standard_normal((hi - lo, k), dtype=np.float32))
        v /= np.sqrt(np.einsum("ij,ij->i", v, v))[:, None]
        cols[lo:hi, :k] = c
        vals[lo:hi, :k] = v
        return np.einsum("ij,ij->i", v, w_plant[c], dtype=np.float64)

    with ThreadPoolExecutor(_threads()) as pool:
        z = np.concatenate(list(pool.map(fill, range(0, n, block_rows))))
    w_plant[d] = -np.median(z)
    labels[:] = np.random.default_rng([data_seed, 2]).random(n) < 1.0 / (
        1.0 + np.exp(-(z + w_plant[d])))

    # The seed's part: the mirror image in the columns it picks.
    signs = np.ones(d + 1, np.float32)
    signs[:d] = 2.0 * np.random.default_rng([seed, 11]).integers(0, 2, d) - 1.0
    vals[:, :k] *= signs[cols[:, :k]]
    w_plant *= signs
    return {
        "cols": cols, "vals": vals, "labels": labels, "w_true": w_plant,
        "n_rows": n, "n_features": d, "nnz_per_row": k,
        "nnz": n * k,  # valued entries; the intercept column is dense
    }


def as_csr(data: dict):
    """A scipy CSR over fresh copies of the ELL arrays (the program sorts
    and sums in place; the reference keeps the originals)."""
    import scipy.sparse as sp

    n, k1 = data["cols"].shape
    indptr = np.arange(0, (n + 1) * k1, k1, dtype=np.int64)
    if indptr[-1] < (1 << 31):
        indptr = indptr.astype(np.int32)
    return sp.csr_matrix(
        (data["vals"].reshape(-1).copy(), data["cols"].reshape(-1).copy(),
         indptr),
        shape=(n, data["n_features"] + 1),
    )
