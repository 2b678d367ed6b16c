"""Seeded GAME data at the shape of MovieLens-20M (GroupLens; Harper &
Konstan, ACM TiiS 5(4), 2015): 20,000,263 ratings of 27,278 movies by
138,493 users, each user with at least 20.  The ratings themselves are not
in the sandbox (no network), so every law below is stated in the
configuration's ``assumed`` and drawn from its ``data_seed``:

  ratings per user   ``20 + lognormal(mu, sigma)`` clipped to the published
                     maximum, then the largest-remainder rounding of a
                     common scale so that the counts sum to ``n_rows``
                     exactly; rows lie in the file's order, user by user;
  movie of a row     popularity rank r drawn with probability proportional
                     to ``(r + shift) ** -exponent`` (with replacement: a
                     user may meet a movie twice), ranks laid over the ids
                     by a permutation;
  genres of a movie  1 to 6 of 20 indicators: the count by ``genre_count_pmf``,
                     which ones by ``genre_incidence`` without replacement;
  summary features   ``n_dense`` columns, half a function of the user and
                     half of the movie, each N(0, 1) (standardised summaries:
                     a user's mean rating and activity, a movie's mean,
                     popularity, age ...);
  response           Bernoulli of the planted mixed model's sigmoid: "rating
                     >= 4", with the planted intercept putting the median
                     row at even odds.

Two feature shards over one row space, as ``game_training_driver`` reads
them: ``global`` (one-hot movie id, genre indicators, summary features,
intercept) for the fixed effect, and ``per_user`` (genre indicators,
intercept) for the random effect keyed by ``userId``.

``--seed`` mirrors the data: it draws a sign for each summary column of
``global`` and each column of ``per_user``; values and planted coefficients
of the columns drawn -1 are negated.  That is a symmetry of the objective
which floating point keeps exactly, so every seed is the same work on
mirrored numbers (PERF.md section 2).  One-hot and indicator columns of
``global`` keep their 1s: the tiled layout's unit-value path depends on
them.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: Rows made at a time, each block from a random stream of its own.
BLOCK_ROWS = 1 << 20


def _threads() -> int:
    return max(1, min(12, os.cpu_count() or 1))

#: Share of ML-20M's movies that carry each genre tag (the 19 genres and
#: "(no genres listed)"), as remembered from the data set's README and
#: movies.csv; only their order of magnitude matters here.
GENRE_INCIDENCE = (
    0.49, 0.31, 0.15, 0.15, 0.13, 0.11, 0.096, 0.091, 0.085, 0.064,
    0.056, 0.052, 0.044, 0.042, 0.038, 0.038, 0.025, 0.012, 0.007, 0.009)


def ratings_per_user(rng, n_users: int, n_rows: int, law: dict) -> np.ndarray:
    """Counts with the published minimum and maximum that sum to ``n_rows``."""
    lo, hi = int(law["min_ratings"]), int(law["max_ratings"])
    if not lo * n_users <= n_rows <= hi * n_users:
        raise ValueError(f"{n_rows} rows cannot be {n_users} users of "
                         f"{lo} to {hi} ratings")
    extra = np.exp(float(law["lognormal_mu"]) + float(law["lognormal_sigma"])
                   * rng.standard_normal(n_users))
    extra = np.minimum(extra, hi - lo)
    # One common scale brings the sum to the published total; the clip
    # binds again for a handful of users, so iterate to a fixed point.
    target = n_rows - lo * n_users
    for _ in range(64):
        scaled = np.minimum(extra * (target / extra.sum()), hi - lo)
        if abs(scaled.sum() - target) < 0.5:
            break
        extra = scaled
    floor = np.floor(scaled).astype(np.int64)
    short = int(target - floor.sum())
    # Largest remainders first; a user at the maximum has remainder 0.
    bump = np.argsort(-(scaled - floor), kind="stable")[:short]
    floor[bump] += 1
    counts = floor + lo
    assert counts.sum() == n_rows and counts.min() >= lo and counts.max() <= hi
    return counts


def movie_genres(rng, n_movies: int, law: dict) -> tuple[np.ndarray, np.ndarray]:
    """``(n_movies, 6)`` genre ids ascending, padded with -1, and the count
    of each movie."""
    pmf = np.asarray(law["genre_count_pmf"], np.float64)
    k = 1 + rng.choice(len(pmf), size=n_movies, p=pmf / pmf.sum())
    # Gumbel top-k: k distinct genres by incidence, without replacement.
    score = np.log(np.asarray(GENRE_INCIDENCE)) + rng.gumbel(
        size=(n_movies, len(GENRE_INCIDENCE)))
    top = np.argsort(-score, axis=1)[:, :len(pmf)]
    top[np.arange(len(pmf))[None, :] >= k[:, None]] = np.iinfo(np.int64).max
    top.sort(axis=1)
    top[top == np.iinfo(np.int64).max] = -1
    return top.astype(np.int32), k.astype(np.int32)


def generate(cfg: dict, seed: int) -> dict:
    """The data of one run: row-level ids, per-movie and per-user tables,
    the planted model and the labels.  The shards are built from these by
    :func:`shards`; the reference reads the same arrays."""
    law = cfg["generator_params"]
    n, n_users, n_movies = (int(cfg[k]) for k in (
        "n_rows", "n_users", "n_movies"))
    n_genres, n_dense = int(cfg["n_genres"]), int(cfg["n_dense"])
    if n_genres != len(GENRE_INCIDENCE) or n_dense % 2:
        raise ValueError("the generator has 20 genres and an even number "
                         "of summary features")
    data_seed = int(cfg["data_seed"])
    seed = int(seed) % (1 << 63)
    stream = lambda k: np.random.default_rng([data_seed, k])  # noqa: E731

    counts = ratings_per_user(stream(0), n_users, n, law)
    user = np.repeat(np.arange(n_users, dtype=np.int32), counts)
    p = (np.arange(1, n_movies + 1) + float(law["movie_zipf_shift"])) ** -float(
        law["movie_zipf_exponent"])
    cdf = np.cumsum(p / p.sum())
    cdf[-1] = 1.0
    id_of_rank = stream(1).permutation(n_movies).astype(np.int32)
    genres, genre_count = movie_genres(stream(3), n_movies, law)
    half = n_dense // 2
    user_feat = stream(4).standard_normal((n_users, half), dtype=np.float32)
    movie_feat = stream(5).standard_normal((n_movies, half), dtype=np.float32)

    # The seed's part: a sign for each summary column of ``global`` and
    # for each column of ``per_user``.
    sign_rng = np.random.default_rng([seed, 11])
    s_dense = (2.0 * sign_rng.integers(0, 2, n_dense) - 1.0).astype(np.float32)
    s_re = (2.0 * sign_rng.integers(0, 2, n_genres + 1) - 1.0).astype(
        np.float32)
    user_feat *= s_dense[:half]
    movie_feat *= s_dense[half:]

    # The planted model, in the mirrored coordinates.
    plant = stream(6)
    beta = {
        "movie": float(law["movie_scale"]) * plant.standard_normal(n_movies),
        "genre": float(law["genre_scale"]) * plant.standard_normal(n_genres),
        "dense": float(law["dense_scale"]) * plant.standard_normal(n_dense)
        * s_dense,
    }
    gamma = plant.standard_normal((n_users, n_genres + 1)) * np.asarray(
        [float(law["user_genre_scale"])] * n_genres
        + [float(law["user_intercept_scale"])]) * s_re

    host = {
        "user": user, "movie": np.empty(n, np.int32), "counts": counts,
        "genres": genres, "genre_count": genre_count,
        "user_feat": user_feat, "movie_feat": movie_feat,
        "s_re": s_re, "n_rows": n, "n_users": n_users, "n_movies": n_movies,
        "n_genres": n_genres, "n_dense": n_dense,
    }
    z = np.empty(n, np.float64)
    coin = np.empty(n, np.float64)

    def rows(lo):
        """Movies, planted margins and label draws of one block of rows
        (one random stream per block)."""
        hi = min(n, lo + BLOCK_ROWS)
        rng = np.random.default_rng([data_seed, 2, lo // BLOCK_ROWS])
        host["movie"][lo:hi] = id_of_rank[np.searchsorted(
            cdf, rng.random(hi - lo), side="right").clip(max=n_movies - 1)]
        coin[lo:hi] = rng.random(hi - lo)
        z[lo:hi] = Margins(host, lo, hi).of(beta, gamma)

    _for_blocks(rows, n)
    beta["intercept"] = -float(np.median(z))
    host["labels"] = (coin < 1.0 / (
        1.0 + np.exp(-(z + beta["intercept"])))).astype(np.float32)
    host["planted"] = {"beta": beta, "gamma": gamma}
    # Valued entries of each shard (the intercepts included).
    n_tags = int(genre_count[host["movie"]].sum(dtype=np.int64))
    host["fixed_nnz"] = n * (2 + n_dense) + n_tags
    host["random_nnz"] = n + n_tags
    return host


def _for_blocks(fn, n: int) -> None:
    with ThreadPoolExecutor(_threads()) as pool:
        list(pool.map(fn, range(0, n, BLOCK_ROWS)))


class Margins:
    """Margins of the rows ``lo:hi`` under a mixed model, in float64: the
    generator's planted one (for the labels) and the reference's.  ``beta``
    has ``movie``, ``genre``, ``dense`` and optionally ``intercept``;
    ``gamma`` is ``(n_users, n_genres + 1)`` in the shard's (mirrored)
    columns, or ``None``."""

    def __init__(self, host: dict, lo: int, hi: int):
        self.host = host
        self.u = host["user"][lo:hi]
        self.m = host["movie"][lo:hi]
        self.g = host["genres"][self.m]                     # (rows, 6)

    def fixed(self, beta: dict) -> np.ndarray:
        h = self.host
        half = h["n_dense"] // 2
        dense = np.asarray(beta["dense"], np.float64)
        per_movie = (np.asarray(beta["movie"], np.float64)
                     + genre_sums(h, beta["genre"])
                     + h["movie_feat"].astype(np.float64) @ dense[half:])
        per_user = h["user_feat"].astype(np.float64) @ dense[:half]
        return (per_movie[self.m] + per_user[self.u]
                + float(beta.get("intercept", 0.0)))

    def random(self, gamma: np.ndarray) -> np.ndarray:
        h = self.host
        s = h["s_re"].astype(np.float64)
        # a zero column for the -1 padding of the genre table
        table = np.concatenate(
            [gamma[:, :-1] * s[:-1], np.zeros((len(gamma), 1))], axis=1)
        z = gamma[self.u, -1] * s[-1]
        for j in range(self.g.shape[1]):
            z += table[self.u, self.g[:, j]]
        return z

    def of(self, beta: dict, gamma) -> np.ndarray:
        z = self.fixed(beta)
        return z if gamma is None else z + self.random(gamma)


def genre_sums(host: dict, w_genre) -> np.ndarray:
    """Per movie, the sum of ``w_genre`` over the movie's genres."""
    w = np.append(np.asarray(w_genre, np.float64), 0.0)   # -1 reads the 0
    return w[host["genres"]].sum(axis=1)


def layout(host: dict) -> dict:
    """Column offsets of the two shards."""
    n_movies, n_genres, n_dense = (
        host["n_movies"], host["n_genres"], host["n_dense"])
    return {
        "movie": 0, "genre": n_movies, "dense": n_movies + n_genres,
        "intercept": n_movies + n_genres + n_dense,
        "n_fixed": n_movies + n_genres + n_dense + 1,
        "n_random": n_genres + 1,
    }


def shards(host: dict):
    """``({"global": csr, "per_user": csr}, {"userId": ids})`` over fresh
    arrays: canonical CSR (columns of a row ascending and distinct), as the
    driver's reader hands them to ``GameEstimator``."""
    import scipy.sparse as sp

    n, n_dense, n_genres = host["n_rows"], host["n_dense"], host["n_genres"]
    half = n_dense // 2
    cols = layout(host)
    k_all = host["genre_count"][host["movie"]].astype(np.int64)
    tags = np.zeros(n + 1, np.int64)
    np.cumsum(k_all, out=tags[1:])
    row = np.arange(n + 1, dtype=np.int64)
    # global: [movie][genres ascending][summary features][intercept]
    # per_user: [genres ascending][intercept], mirrored by the seed
    ptr_f, ptr_r = tags + (2 + n_dense) * row, tags + row
    idx_f = np.empty(ptr_f[-1], np.int32)
    val_f = np.ones(ptr_f[-1], np.float32)
    idx_r = np.empty(ptr_r[-1], np.int32)
    val_r = np.empty(ptr_r[-1], np.float32)
    dense_cols = cols["dense"] + np.arange(n_dense, dtype=np.int32)

    def fill(lo):
        hi = min(n, lo + BLOCK_ROWS)
        u, m = host["user"][lo:hi], host["movie"][lo:hi]
        g, k = host["genres"][m], k_all[lo:hi]
        f0, r0 = ptr_f[lo:hi], ptr_r[lo:hi]
        idx_f[f0] = m
        for j in range(g.shape[1]):
            has = np.flatnonzero(k > j)
            gj = g[has, j]
            idx_f[f0[has] + 1 + j] = cols["genre"] + gj
            idx_r[r0[has] + j] = gj
            val_r[r0[has] + j] = host["s_re"][gj]
        at = (f0 + 1 + k)[:, None] + np.arange(n_dense)
        idx_f[at] = dense_cols
        val_f[at[:, :half]] = host["user_feat"][u]
        val_f[at[:, half:]] = host["movie_feat"][m]
        idx_f[f0 + 1 + k + n_dense] = cols["intercept"]
        idx_r[r0 + k] = n_genres
        val_r[r0 + k] = host["s_re"][n_genres]

    _for_blocks(fill, n)

    def canonical(data, indices, indptr, width):
        if indptr[-1] < (1 << 31):
            indptr = indptr.astype(np.int32)
        mat = sp.csr_matrix((data, indices, indptr), shape=(n, width),
                            copy=False)
        mat.has_sorted_indices = True
        mat.has_canonical_format = True
        return mat

    return ({"global": canonical(val_f, idx_f, ptr_f, cols["n_fixed"]),
             "per_user": canonical(val_r, idx_r, ptr_r, cols["n_random"])},
            {"userId": host["user"]})
