"""Time the wide layout's cold kernel (``ops/sparse_pallas._cold_apply``) on
synthetic codes, for every depth and every number of blocks a basic block
given, on the block grids given; each product is held to the bit against the
one-block-a-basic-block kernel on the same codes.  Then the line through two
depths at the bodies ``_cold_bodies`` chooses: the time model
(``COLD_BLOCK_SECONDS``, ``COLD_SUBLANE_SECONDS``) by which ``_warm_prefix``
parts the bands.

On one TPU chip:

    python scripts/cold_kernel_sweep.py --out cold_sweep.json

With ``PHOTON_PALLAS_INTERPRET=1`` and ``--grids 2x3 --depths 8,16`` it
rehearses on the CPU (the times then mean nothing).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from unittest import mock

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from photon_ml_tpu.ops import sparse_pallas as spl  # noqa: E402


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


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--grids", default="1024x123,123x1024",
                    help="output blocks x gather blocks, comma-separated")
    ap.add_argument("--depths", default="8,16,24,32,40,64")
    ap.add_argument("--bodies", default="1,2,4,8,16")
    ap.add_argument("--fit", default="8,16",
                    help="the two depths the time model's line goes through")
    ap.add_argument("--reps", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    grids = [tuple(int(x) for x in g.split("x")) for g in args.grids.split(",")]
    depths = [int(x) for x in args.depths.split(",")]
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
    nbo, nbg = grids[0]
    at = {r["a"]: r["ns_per_block"] for r in rows
          if (r["nbo"], r["nbg"]) == (nbo, nbg)
          and r["bodies"] == min(spl._cold_bodies(r["a"]), max(bodies))}
    if lo_a in at and hi_a in at:
        slope = (at[hi_a] - at[lo_a]) / (hi_a - lo_a)
        fit = dict(grid=[nbo, nbg], depths=[lo_a, hi_a],
                   block_seconds=1e-9 * (at[lo_a] - slope * lo_a),
                   sublane_seconds=1e-9 * slope)
        print(json.dumps({"fit": fit}), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(dict(device=jax.devices()[0].device_kind, rows=rows,
                       fit=fit), f, indent=1)


if __name__ == "__main__":
    main()
