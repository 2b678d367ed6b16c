"""Seeded click-log data in the shape of the LIBSVM ``criteo`` file (the
Criteo Display Advertising Challenge, preprocessed as the winning solution
did: Juan, Zhuang & Chin): every row is one impression with one binary
feature from each of 39 fields, hashed into ``n_features`` columns, and a
click label from a planted model.

The log -- each field's law, the hash, every row's columns, the planted
model and the labels -- comes from the configuration's ``data_seed``.
``--seed`` flips it: on an odd draw every label is flipped and the planted
model negated, intercept included.  The matrix, and so the layout and all
the work, is the same on every seed; logistic regression maps the answer of
one to the negated answer of the other (``softplus(-m) + m = softplus(m)``),
so every compared number reads the same up to float32's last bits.  Values
stay 1: negating columns, as ``glm_sparse`` does, would give the matrix
values of -1 and another layout.

The configuration gives the sizes (``n_rows``, ``n_features``) and, under
``generator_params``:

  ``integer_fields``, ``integer_buckets``, ``integer_zipf``: the 13 integer
      fields, each bucketed (the winning solution's ``floor(log(v)^2)``) into
      ``integer_buckets`` values of Zipf-Mandelbrot law ``[exponent, shift]``;
  ``categorical_cardinalities``, ``categorical_zipf``: the 26 categorical
      fields, each of Zipf-Mandelbrot law ``[exponent, shift]`` over its
      published number of values;
  ``rare_min_count``, ``published_rows``: a value whose expected count in the
      published log is under ``rare_min_count`` becomes its field's one
      "rare" value (the winning solution's preprocessing);
  ``model_scale``, ``positive_share``: planted coefficients ``scale * N(0,1)``
      for every column, and the planted intercept that makes
      ``positive_share`` of the labels (Bernoulli of the planted sigmoid)
      positive;
  ``block_rows``: rows made at a time (one random stream per block).

The value of rank r of a field is drawn with probability proportional to
``(r + shift) ** -exponent``, by the inverse of the law's continuous CDF (a
draw per entry, no table of the field's values).  Its column is a 64-bit mix
of (field, rank) modulo ``n_features``: columns of different fields
collide, as in the hashed file, and two fields of one row can hash to one
column, which the binary file holds as one entry of value 1.

Returned arrays are in ELL form, ``(n_rows, 40)``: 39 hashed columns, then
the intercept (column ``n_features``, value 1).  A column merged with the
one before it in its row has value 0 (the reference sums it as nothing);
``as_csr`` gives the program the distinct entries alone.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _threads() -> int:
    return max(1, min(12, os.cpu_count() or 1))


def fields(law: dict) -> list[tuple[int, float, float]]:
    """(values, exponent, shift) of each field, integer fields first, with
    the rare values merged: a field keeps the ranks whose expected count in
    the published log is at least ``rare_min_count``, and one rank more for
    all the others."""
    out = []
    n_int = int(law["integer_fields"])
    s_i, q_i = (float(x) for x in law["integer_zipf"])
    out += [(int(law["integer_buckets"]), s_i, q_i)] * n_int
    s_c, q_c = (float(x) for x in law["categorical_zipf"])
    out += [(int(k), s_c, q_c) for k in law["categorical_cardinalities"]]
    kept = []
    for k, s, q in out:
        p = _pmf_head(k, s, q)
        frequent = int(np.count_nonzero(
            p * float(law["published_rows"]) >= float(law["rare_min_count"])))
        kept.append((min(k, frequent + 1), s, q))
    return kept


def _norm(k: int, s: float, q: float) -> float:
    """The law's continuous mass over ranks [0, k)."""
    if abs(s - 1.0) < 1e-12:
        return float(np.log((k + q) / q))
    return float((q ** (1 - s) - (k + q) ** (1 - s)) / (s - 1))


def _pmf_head(k: int, s: float, q: float, head: int = 1 << 22) -> np.ndarray:
    """Probability of each of the first ``min(k, head)`` ranks (the mass
    between r and r + 1 of the continuous law)."""
    r = np.arange(min(k, head) + 1, dtype=np.float64)
    if abs(s - 1.0) < 1e-12:
        cdf = np.log((r + q) / q)
    else:
        cdf = (q ** (1 - s) - (r + q) ** (1 - s)) / (s - 1)
    return np.diff(cdf) / _norm(k, s, q)


def _draw(rng, n: int, k_full: int, kept: int, s: float, q: float):
    """``n`` ranks of the field's law over ``k_full`` values, every rank past
    ``kept - 1`` folded into the rare rank ``kept - 1``."""
    u = rng.random(n)
    total = _norm(k_full, s, q)
    if abs(s - 1.0) < 1e-12:
        x = q * np.exp(u * total) - q
    else:
        x = (q ** (1 - s) - u * total * (s - 1)) ** (1.0 / (1 - s)) - q
    r = np.minimum(x.astype(np.int64), kept - 1)
    return np.maximum(r, 0)


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser: a 64-bit hash of each element."""
    x = x.astype(np.uint64)
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return x


def hash_columns(field: int, ranks: np.ndarray, d: int,
                 data_seed: int) -> np.ndarray:
    key = (np.uint64(data_seed) << np.uint64(40)) ^ (
        np.uint64(field) << np.uint64(32)) ^ ranks.astype(np.uint64)
    return (_mix(key) % np.uint64(d)).astype(np.int32)


def generate(cfg: dict, seed: int) -> dict:
    """The data of one run, from the configuration's sizes and law."""
    n = int(cfg["n_rows"])
    d = int(cfg["n_features"])
    law = cfg["generator_params"]
    block_rows = int(law["block_rows"])
    data_seed = int(cfg["data_seed"])
    seed = int(seed) % (1 << 63)
    full = [(int(law["integer_buckets"]),)] * int(law["integer_fields"]) + [
        (int(k),) for k in law["categorical_cardinalities"]]
    kept = fields(law)
    k = len(kept)

    w_plant = np.zeros(d + 1, np.float64)
    w_plant[:d] = float(law["model_scale"]) * np.random.default_rng(
        [data_seed, 0]).standard_normal(d)

    cols = np.empty((n, k + 1), np.int32)
    vals = np.ones((n, k + 1), np.float32)
    cols[:, k] = d

    def fill(lo: int) -> np.ndarray:
        """Columns and values of one block; returns its rows' planted
        scores without the intercept."""
        hi = min(n, lo + block_rows)
        rng = np.random.default_rng([data_seed, 1, lo // block_rows])
        c = np.empty((hi - lo, k), np.int32)
        for f, ((k_full,), (k_kept, s, q)) in enumerate(zip(full, kept)):
            c[:, f] = hash_columns(
                f, _draw(rng, hi - lo, k_full, k_kept, s, q), d, data_seed)
        c.sort(axis=1)
        v = np.ones((hi - lo, k), np.float32)
        v[:, 1:][c[:, 1:] == c[:, :-1]] = 0.0  # one entry of value 1
        cols[lo:hi, :k] = c
        vals[lo:hi, :k] = v
        return np.einsum("ij,ij->i", v, w_plant[c], dtype=np.float64)

    with ThreadPoolExecutor(_threads()) as pool:
        z = np.concatenate(list(pool.map(fill, range(0, n, block_rows))))
    w_plant[d] = _intercept_for(z, float(law["positive_share"]))
    labels = (np.random.default_rng([data_seed, 2]).random(n) < 1.0 / (
        1.0 + np.exp(-(z + w_plant[d])))).astype(np.float32)

    # The seed's part: the mirror image in the labels.
    if np.random.default_rng([seed, 11]).integers(0, 2):
        labels = 1.0 - labels
        w_plant = -w_plant
    merged = int(np.count_nonzero(vals[:, :k] == 0.0))
    return {
        "cols": cols, "vals": vals, "labels": labels, "w_true": w_plant,
        "n_rows": n, "n_features": d, "fields": k,
        # valued entries; the intercept column is dense
        "nnz": n * k - merged, "merged": merged,
    }


def _intercept_for(z: np.ndarray, share: float) -> float:
    """The planted intercept b with mean(sigmoid(z + b)) = share."""
    lo, hi = -60.0, 60.0
    sample = z[:: max(1, len(z) // (1 << 20))]
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(sample + mid)))) < share:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def as_csr(data: dict):
    """A scipy CSR over the distinct entries of the ELL arrays (fresh
    arrays: the program sorts and sums in place; the reference keeps the
    originals)."""
    import scipy.sparse as sp

    n, k1 = data["cols"].shape
    keep = data["vals"] != 0.0
    counts = keep.sum(axis=1)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    if indptr[-1] < (1 << 31):
        indptr = indptr.astype(np.int32)
    return sp.csr_matrix(
        (data["vals"][keep], data["cols"][keep], indptr),
        shape=(n, data["n_features"] + 1),
    )
