"""``game_ml20m``'s data with a second entity key: the same ratings, users,
movies, summary features, planted model and labels, from the same
``data_seed`` and mirrored by ``--seed`` in the same way (the accepted
generator makes them; nothing of it is changed here), plus what a random
effect per MOVIE needs:

  ``movieId``        the row's movie, as an entity key.  Rows lie user by
                     user, so this key is scattered over the whole file:
                     grouping by it is a real sort of 20 M keys;
  shard ``per_movie``  the 8 user-side summary features of the ``global``
                     shard (the same columns, so the seed's mirror gives
                     them the same signs) and an intercept: 9 dense valued
                     columns a row.  A movie's random effect is how its
                     appeal varies with who is watching.

The planted model has no per-movie interaction: what the per-movie effect
fits is each movie's intercept (which the fixed effect's one-hot column
shares with it) and the sampling noise around it, at the published skew of
ratings per movie.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.datagen import game_ml20m
from benchmarks.datagen.game_ml20m import BLOCK_ROWS


def generate(cfg: dict, seed: int) -> dict:
    host = game_ml20m.generate(cfg, seed)
    host["movie_counts"] = np.bincount(
        host["movie"], minlength=host["n_movies"]).astype(np.int64)
    host["item_nnz"] = host["n_rows"] * (host["n_dense"] // 2 + 1)
    return host


def rows_active(counts: np.ndarray, cap) -> int:
    """Rows some entity trains on under an active-row cap."""
    counts = np.asarray(counts, np.int64)
    return int(counts.sum() if cap is None else np.minimum(counts, cap).sum())


class _Shards(dict):
    """The shards by name; ``per_movie`` is made when it is first asked
    for.  ``GameEstimator.build_coordinates`` builds its coordinates one
    after the other and the fixed effect's layout build is the host's peak:
    1.6 GB of a shard that nothing reads before the third coordinate are
    not held through it.  ``lazy_seconds``: what making it took."""

    def __init__(self, made, host):
        super().__init__(made)
        self._host, self.lazy_seconds = host, 0.0

    def __missing__(self, name):
        if name != "per_movie":
            raise KeyError(name)
        start = time.perf_counter()
        self[name] = _per_movie_shard(self._host)
        self.lazy_seconds += time.perf_counter() - start
        return self[name]


def shards(host: dict):
    """The accepted generator's two shards and ``userId``, with the
    ``per_movie`` shard and ``movieId``."""
    out, ids = game_ml20m.shards(host)
    ids["movieId"] = host["movie"]
    return _Shards(out, host), ids


def _per_movie_shard(host: dict):
    import scipy.sparse as sp

    n, half = host["n_rows"], host["n_dense"] // 2
    width = half + 1
    data = np.ones((n, width), np.float32)

    def fill(lo):
        hi = min(n, lo + BLOCK_ROWS)
        data[lo:hi, :half] = host["user_feat"][host["user"][lo:hi]]

    game_ml20m._for_blocks(fill, n)
    indptr = np.arange(0, (n + 1) * width, width, dtype=np.int64)
    if indptr[-1] < (1 << 31):
        indptr = indptr.astype(np.int32)
    mat = sp.csr_matrix(
        (data.reshape(-1), np.tile(np.arange(width, dtype=np.int32), n),
         indptr), shape=(n, width), copy=False)
    mat.has_sorted_indices = True
    mat.has_canonical_format = True
    return mat
