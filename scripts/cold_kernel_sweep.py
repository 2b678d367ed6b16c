"""Time the wide layout's cold kernel (``ops/sparse_pallas._cold_apply``) on
synthetic codes, for every depth and every number of blocks a basic block
given, on the block grids given; each product is held to the bit against the
one-block-a-basic-block kernel on the same codes.  Then the line through two
depths at the bodies ``_cold_bodies`` chooses: the time model
(``COLD_BLOCK_SECONDS``, ``COLD_SUBLANE_SECONDS``) by which ``_warm_prefix``
parts the bands and ``_cold_depths`` chooses the band's depths.  Then the
cold band's spill of ``glm_click_fit``'s own log (its generator at the
cell's 2^23 rows, parted into warm and cold columns and sorted by (block,
lane) as the build does), at each depth pair given: the entries above the
depths, a ``SpillData`` as the build makes it, its forward and backward
product each added into a vector of the band's output length, timed by the
device's busy seconds in a profiler trace; and the least-squares line
through them (``COLD_SPILL_FIXED_SECONDS``, ``COLD_SPILL_SECONDS``: the mean
of the two products' constants and slopes).

On one TPU chip (the log's generator and sort take ~1 min of the host):

    python scripts/cold_kernel_sweep.py --out cold_sweep.json

With ``PHOTON_PALLAS_INTERPRET=1``, ``--grids 2x3 --depths 8,16`` and
``--spill-rows 65536`` it rehearses on the CPU (the times then mean
nothing).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time
from unittest import mock

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from photon_ml_tpu.ops import sparse_pallas as spl  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: The configuration of the cell whose cold band's spill is timed.
CELL_CONFIG = os.path.join(
    ROOT, "benchmarks", "configs", "glm_logistic_l2_lbfgs_criteo.json")


def synthetic(nbo, nbg, a, seed, fill=0.15):
    """Codes of ``fill`` of the slots, random windows and lanes, the rest
    empty; one row of blocks drawn and repeated down the grid (a block's
    time does not depend on its codes), and a vector to gather from."""
    rng = np.random.default_rng(seed)
    shape = (1, nbg, a, spl.WIN)
    code = ((rng.integers(0, spl.COLD_WINS, shape, dtype=np.int32)
             << spl.COLD_WIN_SHIFT)
            | (rng.integers(0, spl.COLD_WINS, shape, dtype=np.int32) << 7)
            | rng.integers(0, spl.WIN, shape, dtype=np.int32))
    code[rng.random(shape) >= fill] = spl.COLD_EMPTY
    vec = rng.standard_normal(nbg * spl.COLD_TILE).astype(np.float32)
    return (jnp.tile(jnp.asarray(code), (nbo, 1, 1, 1)),
            jnp.zeros((1,), jnp.float32), jnp.asarray(vec))


def product(code, val, vec, nbo, nbg, bodies):
    """The compiled forward product with ``bodies`` blocks a basic block."""
    with mock.patch.object(spl, "_cold_bodies", lambda a: bodies):
        spl._cold_apply.clear_cache()
        compiled = spl._cold_apply.lower(
            code, val, vec, nbo=nbo, nbg=nbg, square=False, side="fwd",
            unit=True).compile()
    spl._cold_apply.clear_cache()
    return compiled


def cell_spills(n_rows, pairs, seed):
    """The cold band of ``glm_click_fit``'s own log at ``n_rows`` rows, split
    as ``build_wide_host`` splits it (the warm columns by ``_warm_prefix``,
    each cold entry's depth in its (block, lane) by ``_cold_sort``), and for
    each depth pair the entries it leaves above the depths: the spill the
    build would make, a ``SpillData`` sorted by row."""
    from benchmarks.datagen import click_hashed

    cfg = json.load(open(CELL_CONFIG))
    cfg = {**cfg, "n_rows": n_rows}
    csr = click_hashed.as_csr(click_hashed.generate(cfg, seed))
    coo = csr.tocoo()
    del csr
    n_cols = coo.shape[1]
    warm = spl._warm_prefix(np.bincount(coo.col, minlength=n_cols), n_rows,
                            spl.CODE_BYTES, 128)
    cold = np.ones(n_cols, bool)
    cold[warm] = False
    cold = cold[coo.col]
    r, c = coo.row[cold].astype(np.int64), coo.col[cold].astype(np.int64)
    del coo, cold
    nbr = -(-n_rows // spl.COLD_TILE)
    nbc = -(-n_cols // spl.COLD_TILE)
    depth = []
    for order, _, d in (spl._cold_sort(r, c, nbc), spl._cold_sort(c, r, nbr)):
        depth.append(np.empty_like(d))
        depth[-1][order] = d
    out = []
    for a_f, a_b in pairs:
        spilled = (depth[0] >= a_f) | (depth[1] >= a_b)
        if spilled.any():
            out.append(((a_f, a_b), len(r), spl._spill_data(
                r[spilled], c[spilled], np.ones(int(spilled.sum())),
                n_rows, n_cols, jnp.float32)))
    return out, n_rows, n_cols


def spill_rows(spills, n_rows, n_cols, reps, seed):
    """Device seconds of each spill's forward and backward product, each
    added into a vector of the band's output length: the device's busy
    time in a profiler trace of ``reps`` calls, one trace a product, over
    ``reps`` (on the CPU, which has no device plane, the host's)."""
    from benchmarks import trace

    rng = np.random.default_rng(seed)
    vecs = {n: jnp.asarray(rng.standard_normal(n), jnp.float32)
            for n in (n_rows, n_cols)}
    rows = []
    for depths, cold, spill in spills:
        spill = jax.device_put(spill)
        for name, length, out_length in (("matvec", n_cols, n_rows),
                                         ("rmatvec", n_rows, n_cols)):
            fn = jax.jit(lambda S, v, b, name=name: b + getattr(S, name)(v))
            args = (spill, vecs[length], vecs[out_length])
            fn(*args).block_until_ready()
            with tempfile.TemporaryDirectory() as tmp:
                t0 = time.perf_counter()
                with jax.profiler.trace(tmp):
                    for _ in range(reps):
                        fn(*args).block_until_ready()
                wall = time.perf_counter() - t0
                try:
                    busy = trace.reduce_dir(tmp).busy_s
                except ValueError:  # no device plane: a CPU rehearsal
                    busy = wall
            nnz = spill.spill_coo.nnz
            row = dict(depths=list(depths), spill=nnz,
                       spill_pct=100.0 * nnz / cold, product=name,
                       n_rows=n_rows, n_cols=n_cols, ms=1e3 * busy / reps)
            rows.append(row)
            print(json.dumps(row), flush=True)
    return rows


def spill_fit(rows):
    """The least-squares line of each product's device seconds in its
    entries, and their mean: the spill's constant and its slope an
    entry."""
    lines = {}
    for name in ("matvec", "rmatvec"):
        x = np.array([r["spill"] for r in rows if r["product"] == name])
        y = 1e-3 * np.array([r["ms"] for r in rows if r["product"] == name])
        if len(set(x)) < 2:
            return {}
        slope, const = np.polyfit(x, y, 1)
        lines[name] = dict(fixed_seconds=float(const),
                           entry_seconds=float(slope))
    return dict(lines, fixed_seconds=float(np.mean(
        [v["fixed_seconds"] for v in lines.values()])),
        entry_seconds=float(np.mean(
            [v["entry_seconds"] for v in lines.values()])))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--grids", default="1024x123,123x1024",
                    help="output blocks x gather blocks, comma-separated")
    ap.add_argument("--depths", default="8,16,24,32,40,64")
    ap.add_argument("--bodies", default="1,2,4,8,16")
    ap.add_argument("--fit", default="8,16",
                    help="the two depths the time model's line goes through")
    ap.add_argument("--spill", default="8x8,16x8,8x16,16x16",
                    help="the cold band's depth pairs (forward x backward) "
                         "whose spills to time, comma-separated; empty for "
                         "none")
    ap.add_argument("--spill-rows", type=int, default=None,
                    help="rows of the click log (default: the cell's)")
    ap.add_argument("--reps", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    grids = [tuple(int(x) for x in g.split("x"))
             for g in args.grids.split(",") if g]
    depths = [int(x) for x in args.depths.split(",") if x]
    bodies = [int(x) for x in args.bodies.split(",")]
    rows = []
    for nbo, nbg in grids:
        for a in depths:
            code, val, vec = synthetic(nbo, nbg, a, args.seed)
            one = None
            for k in sorted(set(bodies) | {1}):
                fn = product(code, val, vec, nbo, nbg, k)
                out = np.asarray(fn(code, val, vec))
                if one is None:
                    one = out
                times = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    fn(code, val, vec).block_until_ready()
                    times.append(time.perf_counter() - t0)
                ms = 1e3 * statistics.median(times)
                row = dict(nbo=nbo, nbg=nbg, a=a, bodies=k, ms=ms,
                           ns_per_block=1e6 * ms / (nbo * nbg),
                           ms_each=[1e3 * t for t in times],
                           same_bits_as_one_body=bool(
                               np.array_equal(out, one)),
                           rule=spl._cold_bodies(a))
                rows.append(row)
                print(json.dumps(row), flush=True)
            del code, val, vec
    fit = {}
    lo_a, hi_a = (int(x) for x in args.fit.split(","))
    nbo, nbg = grids[0] if grids else (0, 0)
    at = {r["a"]: r["ns_per_block"] for r in rows
          if (r["nbo"], r["nbg"]) == (nbo, nbg)
          and r["bodies"] == min(spl._cold_bodies(r["a"]), max(bodies))}
    if lo_a in at and hi_a in at:
        slope = (at[hi_a] - at[lo_a]) / (hi_a - lo_a)
        fit = dict(grid=[nbo, nbg], depths=[lo_a, hi_a],
                   block_seconds=1e-9 * (at[lo_a] - slope * lo_a),
                   sublane_seconds=1e-9 * slope)
        print(json.dumps({"fit": fit}), flush=True)
    pairs = [tuple(int(x) for x in p.split("x"))
             for p in args.spill.split(",") if p]
    spill = []
    if pairs:
        n_rows = args.spill_rows or json.load(open(CELL_CONFIG))["n_rows"]
        spill = spill_rows(*cell_spills(n_rows, pairs, args.seed),
                           args.reps, args.seed)
    if spill_fit(spill):
        fit["spill"] = spill_fit(spill)
        print(json.dumps({"spill_fit": fit["spill"]}), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(dict(device=jax.devices()[0].device_kind, rows=rows,
                       spill=spill, fit=fit), f, indent=1)


if __name__ == "__main__":
    main()
