"""The plain reference of the GAME cells: the mixed-effects objective

    F(beta, gamma) = sum_i loss(y_i, x_i . beta + z_i . gamma_u(i))
                     + lam_f/2 |beta|^2 + lam_u/2 sum_u |gamma_u|^2

(the program's own convention: half the weight times the squared norm), the
gradient of each block of coordinates with the other held as per-row
offsets, and the per-row scores of each block, in float64 on the host over
the generator's arrays (``datagen/game_ml20m.py``).  It imports nothing of
the program and takes nothing the program made.  It does not follow the
solvers step for step: ``windows/cd_fit.py`` checks every coordinate update
as an answer.

A row's fixed-effect features are its movie's one-hot id and genre
indicators, the summary features of its user and of its movie, and the
intercept; its random-effect features are the genre indicators and the
intercept, mirrored by the seed's signs.  The sums below go over rows, in
blocks, one thread a block; per-movie and per-user tables only gather.

``precision="bf16"`` is the CONTROL: coefficients, summary-feature values
and the per-row derivative rounded to bfloat16 before each product
(accumulation stays wide), one step below the float32 the configuration
states.  The comparison has to call it not correct.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.datagen import game_ml20m
from benchmarks.reference import round_bf16

BLOCK_ROWS = 1 << 20


def _logistic(z, y):
    """Per-row value softplus(z) - y z and derivative sigmoid(z) - y."""
    return np.logaddexp(0.0, z) - y * z, 0.5 * (1.0 + np.tanh(0.5 * z)) - y


_LOSSES = {"logistic": _logistic}


class GameReference:
    """``beta`` is a vector in the ``global`` shard's columns (movies,
    genres, summary features, intercept); ``gamma`` is ``(n_users,
    n_genres + 1)`` in the ``per_user`` shard's columns."""

    def __init__(self, host: dict, lam_fixed: float, lam_random: float,
                 loss="logistic", row_scale=None, threads=None):
        if loss not in _LOSSES:
            raise ValueError(f"the reference has no loss {loss!r}: "
                             f"{sorted(_LOSSES)}")
        self.host, self.loss = host, _LOSSES[loss]
        self.lam_fixed, self.lam_random = float(lam_fixed), float(lam_random)
        self.n = host["n_rows"]
        self.cols = game_ml20m.layout(host)
        self.labels = host["labels"].astype(np.float64)
        # Per-row weights; only the fault tests set them.
        self.row_scale = row_scale
        self.threads = threads or max(1, min(12, os.cpu_count() or 1))

    # -- coefficients ------------------------------------------------------
    def split(self, beta, bf16=False) -> dict:
        c = self.cols
        b = np.asarray(beta, np.float64)
        if bf16:
            b = round_bf16(b).astype(np.float64)
        return {"movie": b[:c["genre"]], "genre": b[c["genre"]:c["dense"]],
                "dense": b[c["dense"]:c["intercept"]],
                "intercept": b[c["intercept"]]}

    def _host(self, bf16):
        if not bf16:
            return self.host
        return dict(self.host,
                    user_feat=round_bf16(self.host["user_feat"]),
                    movie_feat=round_bf16(self.host["movie_feat"]))

    def _blocks(self, fn):
        with ThreadPoolExecutor(self.threads) as pool:
            return list(pool.map(fn, range(0, self.n, BLOCK_ROWS)))

    # -- scores ------------------------------------------------------------
    def scores(self, beta=None, gamma=None, precision="f64"):
        """Per-row ``(x . beta, z . gamma_u)`` as float64; ``None`` for a
        block that is not given."""
        bf16 = _bf16(precision)
        host = self._host(bf16)
        parts = self.split(beta, bf16) if beta is not None else None
        if gamma is not None:
            gamma = np.asarray(gamma, np.float64)
            if bf16:
                gamma = round_bf16(gamma).astype(np.float64)
        fixed = np.empty(self.n) if beta is not None else None
        random = np.empty(self.n) if gamma is not None else None

        def part(lo):
            hi = min(self.n, lo + BLOCK_ROWS)
            rows = game_ml20m.Margins(host, lo, hi)
            if fixed is not None:
                fixed[lo:hi] = rows.fixed(parts)
            if random is not None:
                random[lo:hi] = rows.random(gamma)

        self._blocks(part)
        return fixed, random

    # -- the objective and its two gradients -----------------------------------
    def _rows(self, margins, precision):
        """Per-row loss values and derivatives at ``margins``."""
        value, d1 = np.empty(self.n), np.empty(self.n)
        bf16 = _bf16(precision)

        def part(lo):
            hi = min(self.n, lo + BLOCK_ROWS)
            v, d = self.loss(margins[lo:hi], self.labels[lo:hi])
            if self.row_scale is not None:
                v, d = v * self.row_scale[lo:hi], d * self.row_scale[lo:hi]
            value[lo:hi] = v
            d1[lo:hi] = round_bf16(d).astype(np.float64) if bf16 else d

        self._blocks(part)
        return value, d1

    def full_objective(self, beta, gamma, fixed, random) -> float:
        """F at coefficients whose per-row scores are already known."""
        value, _ = self._rows(fixed + random, "f64")
        b, g = np.asarray(beta, np.float64), np.asarray(gamma, np.float64)
        return (float(value.sum()) + 0.5 * self.lam_fixed * float(b @ b)
                + 0.5 * self.lam_random * float((g * g).sum()))

    def objective(self, beta, gamma) -> float:
        return self.full_objective(beta, gamma, *self.scores(beta, gamma))

    def fixed_value_and_grad(self, beta, offsets, precision="f64",
                             scores=None):
        """The fixed effect's own objective, sum loss(x.beta + offsets) +
        lam_f/2 |beta|^2, and its gradient in beta.  ``scores``: the
        rows' ``x . beta`` where the caller has them."""
        bf16 = _bf16(precision)
        host = self._host(bf16)
        fixed = (self.scores(beta, None, precision)[0]
                 if scores is None else scores)
        value, d1 = self._rows(fixed + offsets, precision)
        b = np.asarray(beta, np.float64)
        c, half = self.cols, host["n_dense"] // 2
        d_movie = np.bincount(host["movie"], weights=d1,
                              minlength=host["n_movies"])
        d_user = np.bincount(host["user"], weights=d1,
                             minlength=host["n_users"])
        grad = np.empty(c["n_fixed"])
        grad[:c["genre"]] = d_movie
        # a genre's column sums the movies that carry it
        tags = host["genres"]
        grad[c["genre"]:c["dense"]] = np.bincount(
            tags[tags >= 0], weights=np.broadcast_to(
                d_movie[:, None], tags.shape)[tags >= 0],
            minlength=host["n_genres"])
        grad[c["dense"]:c["dense"] + half] = (
            host["user_feat"].astype(np.float64).T @ d_user)
        grad[c["dense"] + half:c["intercept"]] = (
            host["movie_feat"].astype(np.float64).T @ d_movie)
        grad[c["intercept"]] = d1.sum()
        return (float(value.sum()) + 0.5 * self.lam_fixed * float(b @ b),
                grad + self.lam_fixed * b)

    def random_grad(self, gamma, offsets, precision="f64", scores=None):
        """``(n_users, n_genres + 1)``: each user's gradient of sum
        loss(z.gamma_u + offsets) + lam_u/2 |gamma_u|^2 over its rows.
        ``scores``: the rows' ``z . gamma_u`` where the caller has them."""
        host = self.host
        random = (self.scores(None, gamma, precision)[1]
                  if scores is None else scores)
        _, d1 = self._rows(random + offsets, precision)
        n_users, width = host["n_users"], host["n_genres"] + 1
        s = host["s_re"].astype(np.float64)

        def part(lo):
            """The block's sums, for the range of users it touches (a
            short one: rows lie in user order)."""
            hi = min(self.n, lo + BLOCK_ROWS)
            u = host["user"][lo:hi].astype(np.int64)
            first, last = int(u.min()), int(u.max())
            cell = (u - first) * width
            size = (last - first + 1) * width
            tags = host["genres"][host["movie"][lo:hi]]
            d = d1[lo:hi]
            out = np.bincount(cell + (width - 1), weights=d * s[-1],
                              minlength=size)
            for j in range(tags.shape[1]):
                has = tags[:, j] >= 0
                gj = tags[has, j]
                out += np.bincount(cell[has] + gj, weights=d[has] * s[gj],
                                   minlength=size)
            return first, out

        flat = np.zeros(n_users * width)
        for first, out in self._blocks(part):
            flat[first * width:first * width + len(out)] += out
        g = np.asarray(gamma, np.float64)
        return flat.reshape(n_users, width) + self.lam_random * g


def _bf16(precision: str) -> bool:
    if precision not in ("f64", "bf16"):
        raise ValueError(f"precision {precision!r}: f64 or bf16")
    return precision == "bf16"
