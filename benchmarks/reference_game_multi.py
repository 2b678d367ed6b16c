"""The plain reference of the GAME cells with any number of random effects:

    s_i = x_i . beta + sum_c z_i^c . coef^c_{e_c(i)}
    F   = sum_i loss(y_i, s_i) + lam_f/2 |beta|^2
          + sum_c lam_c/2 sum_e |coef^c_e|^2

in float64 on the host over the generator's arrays (``datagen/
game_ml20m_multi.py``).  It imports nothing of the program and takes nothing
the program made.  The fixed effect, the per-row loss and the bfloat16
rounding are ``reference_game.py``'s, through an instance of it; the random
effects are written here, one :class:`Effect` each, from the
configuration's ``random_effects`` list.

An effect with an ACTIVE-ROW CAP (upstream's ``active.data.upper.bound``)
trains an entity of more rows on a subset: the rows at positions
``linspace(0, rows - 1, cap)`` (truncated to whole numbers) of the entity's
rows in the file's order.  The rest are passive: they are scored (every row
has a margin under every effect) and never trained on, so an entity's
gradient and what its update minimises go over its active rows alone.

``precision="bf16"`` is the CONTROL, as in ``reference_game.py``:
coefficients, summary-feature values and the per-row derivative rounded to
bfloat16 before each product.
"""

from __future__ import annotations

import numpy as np

from benchmarks import reference_game
from benchmarks.reference import round_bf16
from benchmarks.reference_game import BLOCK_ROWS


def active_rows(entity: np.ndarray, n_entities: int, cap):
    """``(n,)`` bool: the rows an effect keyed by ``entity`` trains on under
    ``cap``; ``None`` where every row is (no cap, or no entity over it)."""
    if cap is None:
        return None
    counts = np.bincount(entity, minlength=n_entities)
    over = np.flatnonzero(counts > cap)
    if not len(over):
        return None
    order = np.argsort(entity, kind="stable")   # an entity's rows, file order
    first = np.cumsum(counts) - counts
    active = np.ones(len(entity), bool)
    for e in over:
        rows = order[first[e]:first[e] + counts[e]]
        keep = np.linspace(0, counts[e] - 1, cap).astype(int)
        active[rows] = False
        active[rows[keep]] = True
    return active


class Effect:
    """One random effect.  ``spec``: ``name``, ``entity`` (the host array
    that keys it: ``user`` or ``movie``), ``kind`` (``genres``: the 20
    genre indicators of the row's movie and an intercept, mirrored by the
    seed; ``user_summary``: the row's user's summary features and an
    intercept), ``reg_weight`` and optionally ``max_rows_per_entity``."""

    def __init__(self, host: dict, spec: dict):
        self.name, self.kind = spec["name"], spec["kind"]
        self.entity = host[spec["entity"]]
        self.n_entities = int(host[{"user": "n_users",
                                    "movie": "n_movies"}[spec["entity"]]])
        self.lam = float(spec["reg_weight"])
        self.cap = spec.get("max_rows_per_entity")
        if self.kind == "genres":
            self.width = host["n_genres"] + 1
        elif self.kind == "user_summary":
            self.width = host["n_dense"] // 2 + 1
        else:
            raise ValueError(f"the reference has no features {self.kind!r}")
        self.active = active_rows(self.entity, self.n_entities, self.cap)

    def zeros(self):
        return np.zeros((self.n_entities, self.width))

    # -- one block of rows -------------------------------------------------
    def margins(self, host, lo, hi, coef):
        e = self.entity[lo:hi]
        if self.kind == "genres":
            s = host["s_re"].astype(np.float64)
            tags = host["genres"][host["movie"][lo:hi]]
            # a zero column for the -1 padding of the genre table
            table = np.concatenate(
                [coef[:, :-1] * s[:-1], np.zeros((len(coef), 1))], axis=1)
            z = coef[e, -1] * s[-1]
            for j in range(tags.shape[1]):
                z += table[e, tags[:, j]]
            return z
        feat = host["user_feat"][host["user"][lo:hi]].astype(np.float64)
        return (feat * coef[e, :-1]).sum(axis=1) + coef[e, -1]

    def grad_sums(self, host, lo, hi, d):
        """Flat ``(n_entities * width,)``: the block's sum of ``d_i z_i``
        into each entity's cells."""
        width, size = self.width, self.n_entities * self.width
        cell = self.entity[lo:hi].astype(np.int64) * width
        if self.kind == "genres":
            s = host["s_re"].astype(np.float64)
            tags = host["genres"][host["movie"][lo:hi]]
            out = np.bincount(cell + (width - 1), weights=d * s[-1],
                              minlength=size)
            for j in range(tags.shape[1]):
                has = tags[:, j] >= 0
                gj = tags[has, j]
                out += np.bincount(cell[has] + gj, weights=d[has] * s[gj],
                                   minlength=size)
            return out
        feat = host["user_feat"][host["user"][lo:hi]].astype(np.float64)
        out = np.bincount(cell + (width - 1), weights=d, minlength=size)
        for k in range(width - 1):
            out += np.bincount(cell + k, weights=d * feat[:, k],
                               minlength=size)
        return out

    def dense(self, host, rows):
        """``(len(rows), width)`` float64 features of the given rows."""
        if self.kind == "genres":
            s = host["s_re"].astype(np.float64)
            X = np.zeros((len(rows), self.width))
            X[:, -1] = s[-1]
            tags = host["genres"][host["movie"][rows]]
            for j in range(tags.shape[1]):
                has = np.flatnonzero(tags[:, j] >= 0)
                X[has, tags[has, j]] = s[tags[has, j]]
            return X
        X = np.ones((len(rows), self.width))
        X[:, :-1] = host["user_feat"][host["user"][rows]]
        return X


class MultiReference:
    """``beta`` as in ``reference_game.GameReference``; each effect's
    coefficients are ``(n_entities, width)`` in its shard's columns."""

    def __init__(self, host: dict, lam_fixed: float, effects: list,
                 loss="logistic", row_scale=None, threads=None):
        self.base = reference_game.GameReference(
            host, lam_fixed, 0.0, loss=loss, row_scale=row_scale,
            threads=threads)
        self.host, self.n, self.cols = host, self.base.n, self.base.cols
        self.lam_fixed = float(lam_fixed)
        self.effects = {s["name"]: Effect(host, s) for s in effects}

    # -- scores ------------------------------------------------------------
    def fixed_scores(self, beta, precision="f64"):
        return self.base.scores(beta, None, precision)[0]

    def effect_scores(self, name, coef, precision="f64"):
        """Every row's margin under the effect, passive rows too."""
        eff = self.effects[name]
        bf16 = reference_game._bf16(precision)
        host = self.base._host(bf16)
        coef = np.asarray(coef, np.float64)
        if bf16:
            coef = round_bf16(coef).astype(np.float64)
        out = np.empty(self.n)

        def part(lo):
            hi = min(self.n, lo + BLOCK_ROWS)
            out[lo:hi] = eff.margins(host, lo, hi, coef)

        self.base._blocks(part)
        return out

    # -- the objective and the gradients -------------------------------------
    def fixed_value_and_grad(self, beta, offsets, precision="f64",
                             scores=None):
        return self.base.fixed_value_and_grad(beta, offsets, precision,
                                              scores)

    def effect_grad(self, name, coef, offsets, precision="f64", scores=None):
        """``(n_entities, width)``: each entity's gradient of
        ``sum_active loss(z . coef_e + offsets) + lam/2 |coef_e|^2``."""
        eff = self.effects[name]
        mine = (self.effect_scores(name, coef, precision)
                if scores is None else scores)
        _, d1 = self.base._rows(mine + offsets, precision)
        if eff.active is not None:
            d1 = d1 * eff.active
        host = self.base._host(reference_game._bf16(precision))

        def part(lo):
            hi = min(self.n, lo + BLOCK_ROWS)
            return eff.grad_sums(host, lo, hi, d1[lo:hi])

        flat = np.zeros(eff.n_entities * eff.width)
        for out in self.base._blocks(part):
            flat += out
        return flat.reshape(eff.n_entities, eff.width) + eff.lam * np.asarray(
            coef, np.float64)

    def objective(self, beta, coefs: dict, margins, rows=None) -> float:
        """F at coefficients whose summed per-row margins are known; over
        the rows of the bool mask ``rows`` where one is given (what an
        update of a capped effect minimises)."""
        value, _ = self.base._rows(margins, "f64")
        if rows is not None:
            value = value[rows]
        b = np.asarray(beta, np.float64)
        total = float(value.sum()) + 0.5 * self.lam_fixed * float(b @ b)
        for name, eff in self.effects.items():
            g = np.asarray(coefs[name], np.float64)
            total += 0.5 * eff.lam * float((g * g).sum())
        return total

    # -- a float64 solve, for the planted faults ---------------------------
    def solve_entities(self, name, entities, offsets, rows="all",
                       iterations=25):
        """``(len(entities), width)``: each named entity's minimiser of its
        own objective at ``offsets``, by damped Newton in float64 (until no
        entity's step moves a coefficient by 1e-10), over all of its rows
        (``rows="all"``: what a program that ignored the cap would train
        on) or its active rows."""
        eff = self.effects[name]
        slot = np.full(eff.n_entities, -1, np.int64)
        slot[entities] = np.arange(len(entities))
        idx = np.flatnonzero(slot[eff.entity] >= 0)
        if rows != "all" and eff.active is not None:
            idx = idx[eff.active[idx]]
        e = slot[eff.entity[idx]]
        # columns contiguous: every sum below reads whole columns
        X = np.asfortranarray(eff.dense(self.host, idx))
        y, off = self.base.labels[idx], np.asarray(offsets, np.float64)[idx]
        n_e, w = len(entities), eff.width

        def sums(v):
            return np.bincount(e, weights=v, minlength=n_e)

        def value_of(coef):
            z = off.copy()
            for k in range(w):
                z += X[:, k] * coef[e, k]
            v, d = self.base.loss(z, y)
            return sums(v) + 0.5 * eff.lam * (coef * coef).sum(axis=1), z, d

        coef = np.zeros((n_e, w))
        f, z, d = value_of(coef)
        for _ in range(iterations):
            p = 0.5 * (1.0 + np.tanh(0.5 * z))
            d2 = p * (1.0 - p)
            g = np.stack([sums(d * X[:, k]) for k in range(w)], 1) + (
                eff.lam * coef)
            H = np.zeros((n_e, w, w))
            for a in range(w):
                d2a = d2 * X[:, a]
                for b in range(a, w):
                    H[:, a, b] = H[:, b, a] = sums(d2a * X[:, b])
            H[:, np.arange(w), np.arange(w)] += eff.lam
            step = np.linalg.solve(H, g[:, :, None])[:, :, 0]
            if np.abs(step).max() < 1e-10:
                break
            scale = np.ones(n_e)
            for _halving in range(20):
                trial = coef - scale[:, None] * step
                f_new, z_new, d_new = value_of(trial)
                worse = f_new > f + 1e-12 * np.abs(f)
                if not worse.any():
                    break
                scale[worse] *= 0.5
            coef, f, z, d = trial, f_new, z_new, d_new
        return coef
