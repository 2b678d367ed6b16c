"""Headline benchmarks — all three BASELINE.json metrics.

1. ``logistic_glm_rows_per_sec`` (primary): fused value+gradient throughput
   of the sparse logistic objective — the hot op behind BASELINE's "1B-row
   logistic GLM epoch time" (epoch seconds = 1e9 / rows_per_sec per
   objective evaluation; SURVEY.md §3.1 hot loop).
2. ``game_cd_iters_per_sec``: full GAME coordinate-descent iterations
   (fixed effect + long-tailed per-user random effect) per second on a
   MovieLens-shaped synthetic — 10⁵ entities, zipf-tailed row counts
   (BASELINE metric "GAME coord-descent iters/sec").
3. ``glm_driver_wall_seconds``: end-to-end legacy GLM driver wall-clock
   (read → index → summarize → train λ grid → validate → select → write) on
   an a1a-shaped dataset (BASELINE config 1).

MEASUREMENT METHODOLOGY: iterations are chained inside ONE jitted
``fori_loop`` and the clock stops only after a slice of the result is read
back to host.  GAME CD is timed as the median over ``N_REPS`` runs of >=3
iterations each with a spread report; the driver metric reports COLD and
WARM wall seconds separately, each from its own child process.

Every number is reported with the device it ran on (``device`` in the
output line); there is no recorded baseline to compare against — none of
the repo's earlier figures were taken on the current hardware — so the
line carries raw values only.  A section that fails is logged, named in
``extra.failed_sections``, and makes the exit code non-zero.

Prints ONE JSON line: {"metric", "value", "unit", "device", "extra"} — the
primary metric in the required fields, the other metrics under "extra".

Env knobs: BENCH_SMALL=1 shrinks every workload (CI/smoke); BENCH_ONLY=
glm|game|driver|stream|serving|freshness|tuning|solvers|chaos|analysis|
cluster runs a single section (cluster: the 3-host control-plane drill as
a gate plus the checksum-verified snapshot-fetch MB/s).
"""

import json
import os
import sys
import tempfile
import time

import numpy as np


def _log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()

SMALL = os.environ.get("BENCH_SMALL") == "1"
ONLY = os.environ.get("BENCH_ONLY", "")

N_ROWS = 1 << (16 if SMALL else 20)
N_FEATURES = 1 << 13
NNZ_PER_ROW = 32
N_CHAINED = 10  # objective evals chained inside one jit
N_REPS = 3  # timed repetitions (min taken)

GAME_ENTITIES = 2_000 if SMALL else 100_000
GAME_FIXED_FEATURES = 512
GAME_FIXED_NNZ = 8
GAME_RE_DIM = 8
GAME_TIMED_ITERS = 3   # iterations per timed run (VERDICT r2: >=3)
GAME_TIMED_RUNS = 5    # median over this many runs, spread reported
GAME_BUCKET_GROWTH = 4.0  # consolidate the zipf tail: ~5 compiled shapes
GAME_ROW_CAP = 128

STREAM_CHUNKS = 4  # streaming A/B: resident vs 4-chunk double-buffered
STREAM_OS_CHUNKS = 16  # oversubscription leg: store sized past HBM budget
STREAM_OS_HOT_FRAC = 0.7  # hot working-set budget as fraction of wire store

def _read_sync(x) -> None:
    """Force true completion: read one element back to host."""
    np.asarray(x.ravel()[0:1])


def bench_glm_throughput() -> dict:
    """rows/s of the fused sparse logistic value+grad (primary metric),
    plus the achieved HBM bandwidth of one pass for roofline tracking."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.data.dataset import GlmData
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.optim.objective import GlmObjective

    rng = np.random.default_rng(0)
    nnz = N_ROWS * NNZ_PER_ROW
    rows = np.repeat(np.arange(N_ROWS, dtype=np.int64), NNZ_PER_ROW)
    cols = rng.integers(0, N_FEATURES, size=nnz).astype(np.int64)
    values = rng.normal(size=nnz).astype(np.float32)
    w_true = (rng.normal(size=N_FEATURES) *
              (rng.uniform(size=N_FEATURES) < 0.2)).astype(np.float32)
    margins_true = np.zeros(N_ROWS, np.float32)
    np.add.at(margins_true, rows, values * w_true[cols.astype(np.int64)])
    y = (rng.uniform(size=N_ROWS) < 1 / (1 + np.exp(-margins_true))).astype(
        np.float32)

    from photon_ml_tpu.ops.sparse_pallas import (
        build_pallas_matrix,
        pallas_available,
    )

    # The tiled layout where its kernels can run (a TPU, or interpret
    # mode); the XLA COO path elsewhere — and the result says which.
    if pallas_available():
        X = build_pallas_matrix(rows, cols, values, N_ROWS, N_FEATURES)
    else:
        from photon_ml_tpu.ops.sparse import from_coo

        X = from_coo(rows, cols, values, N_ROWS, N_FEATURES)
    _log(f"glm: feature layout {type(X).__name__}")

    data = jax.device_put(GlmData(
        features=X,
        labels=jnp.asarray(y),
        weights=jnp.ones(N_ROWS, jnp.float32),
        offsets=jnp.zeros(N_ROWS, jnp.float32),
    ))
    obj = GlmObjective(losses.logistic)

    # Data is an ARGUMENT, not a closure constant: closed-over arrays get
    # baked into the HLO as literals (a 400 MB program).
    @jax.jit
    def chain(w, data):
        def body(i, w):
            val, grad = obj.value_and_grad(w, data, l2_weight=1.0)
            return w - 1e-4 * grad
        return jax.lax.fori_loop(0, N_CHAINED, body, w)

    _log("glm: compiling throughput chain...")
    w = jnp.zeros(N_FEATURES, jnp.float32)
    out = chain(w, data)
    _read_sync(out)  # compile + prime true sync

    best = np.inf
    for i in range(N_REPS):
        wp = jnp.full((N_FEATURES,), np.float32(1e-3 * (i + 1)))
        _read_sync(wp)
        t0 = time.perf_counter()
        out = chain(wp, data)
        _read_sync(out)  # force real completion
        best = min(best, (time.perf_counter() - t0) / N_CHAINED)

    # Bytes one fused value+grad pass must move through HBM — the layout
    # leaves (which ALREADY hold separate forward and backward
    # orientations, each read once: margins ride the f_* grids, the
    # gradient scatter the b_* grids), the three per-row columns, and the
    # w/grad vectors (reads + the fori body's update) — over the measured
    # pass time.
    x_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(X))
    bytes_per_pass = (
        x_bytes + 3 * (N_ROWS * 4) + 5 * (N_FEATURES * 4)
    )
    return {
        "rows_per_sec": N_ROWS / best,
        "achieved_gbps": bytes_per_pass / best / 1e9,
        "layout": type(X).__name__,
    }


def bench_game_cd() -> dict:
    """Full coordinate-descent iterations per second on a MovieLens-shaped
    synthetic: one fixed effect over sparse global features + one per-user
    random effect with a zipf long tail of rows per user."""
    import scipy.sparse as sp

    from photon_ml_tpu.game.coordinates import (
        FixedEffectCoordinate,
        RandomEffectCoordinate,
    )
    from photon_ml_tpu.game.data import (
        FixedEffectDataset,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.game.descent import CoordinateDescent
    from photon_ml_tpu.data.dataset import make_glm_data
    from photon_ml_tpu.optim.problem import (
        GlmOptimizationConfig,
        OptimizerConfig,
    )
    from photon_ml_tpu.optim.regularization import RegularizationContext

    rng = np.random.default_rng(1)
    # Long-tailed rows per entity (MovieLens-like): zipf, capped so bucket
    # count (= compile count) stays bounded.
    sizes = np.minimum(rng.zipf(1.8, GAME_ENTITIES), GAME_ROW_CAP)
    n = int(sizes.sum())
    users = np.repeat(
        np.array([f"u{i}" for i in range(GAME_ENTITIES)], dtype=object),
        sizes,
    )
    perm = rng.permutation(n)
    users = users[perm]

    nnzf = n * GAME_FIXED_NNZ
    Xg = sp.csr_matrix(
        (rng.normal(size=nnzf).astype(np.float32),
         (np.repeat(np.arange(n, dtype=np.int64), GAME_FIXED_NNZ),
          rng.integers(0, GAME_FIXED_FEATURES, size=nnzf))),
        shape=(n, GAME_FIXED_FEATURES),
    )
    Xu = sp.csr_matrix(rng.normal(size=(n, GAME_RE_DIM)).astype(np.float32))
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    weights = np.ones(n, np.float32)

    opt = GlmOptimizationConfig(
        optimizer=OptimizerConfig(max_iters=10, tolerance=1e-6),
        regularization=RegularizationContext.l2(),
    )
    fixed = FixedEffectCoordinate(
        "fixed",
        FixedEffectDataset(data=make_glm_data(Xg, y), n_global_rows=n),
        "logistic", opt, reg_weight=1.0,
    )
    _log(f"game: {n} rows, {GAME_ENTITIES} entities; grouping...")
    re_ds = build_random_effect_dataset(
        users, Xu, y, weights, bucket_growth=GAME_BUCKET_GROWTH
    )
    _log(f"game: {len(re_ds.blocks)} buckets "
         f"{[(b.n_entities, b.rows_per_entity) for b in re_ds.blocks]}")
    re = RandomEffectCoordinate(
        "per_user", re_ds,
        "logistic", opt, reg_weight=1.0, entity_key="userId",
    )
    cd = CoordinateDescent([fixed, re])

    import jax.numpy as jnp

    base = jnp.zeros(n, jnp.float32)
    _log("game: warmup iteration (compiles every bucket shape)...")
    warm = cd.run(base, n_iterations=1)  # warmup: compiles every bucket shape
    _read_sync(warm.scores["per_user"])
    # One untimed run at the TIMED shape: the first multi-iteration run
    # after compile pays allocator/pipeline warm-in (~2x a steady rep —
    # it alone put >100% spread on the 5-rep sample), steady state after.
    _read_sync(cd.run(base, n_iterations=GAME_TIMED_ITERS).scores["per_user"])
    _log("game: warmup done; timing...")

    # Median over GAME_TIMED_RUNS runs of GAME_TIMED_ITERS iterations each,
    # with the within-session spread reported (the chip stream rate drifts
    # even within a session; 1-iteration best-of-2 carried error bars
    # comparable to round-over-round gains — VERDICT r2).
    per_iter = []
    for r in range(GAME_TIMED_RUNS):
        t0 = time.perf_counter()
        result = cd.run(base, n_iterations=GAME_TIMED_ITERS)
        _read_sync(result.scores["per_user"])
        per_iter.append((time.perf_counter() - t0) / GAME_TIMED_ITERS)
    med = float(np.median(per_iter))
    spread_pct = 100.0 * (max(per_iter) - min(per_iter)) / med
    _log(f"game: median {med:.3f}s/iter over {GAME_TIMED_RUNS}x"
         f"{GAME_TIMED_ITERS} iters (spread {spread_pct:.1f}%)")

    # Per-coordinate breakdown: one manual pass per coordinate with a sync
    # after each update (the headline number above keeps the production
    # batched-readback path; this is diagnostic only).
    states = {c.name: warm.states[c.name] for c in cd.coordinates}
    scores = dict(warm.scores)
    total = base
    for s in scores.values():
        total = total + s
    breakdown = {}
    for coord in cd.coordinates:
        best_c = np.inf
        for _ in range(2):
            offsets = total - scores[coord.name]
            t0 = time.perf_counter()
            st = coord.train(offsets, warm_state=states[coord.name])
            sc = coord.score(st)
            _read_sync(sc)
            best_c = min(best_c, time.perf_counter() - t0)
        breakdown[coord.name] = round(best_c, 3)
    _log(f"game: per-coordinate seconds {breakdown}")
    return {
        "iters_per_sec": 1.0 / med,
        "spread_pct": round(spread_pct, 1),
        "coordinate_seconds": breakdown,
    }


def bench_game_multi_re() -> dict:
    """BASELINE config 5's shape at chip scale: coordinate descent over
    fixed + THREE random effects (user + item + context, MovieLens-like
    geometry — zipf-tailed users and items, few heavy contexts with the
    active-set cap exercising the active/passive split).  This is the
    flagship multi-random-effect number the north star cares about;
    until round 5 it only ran in CPU tests and the dryrun."""
    import scipy.sparse as sp

    from photon_ml_tpu.data.dataset import make_glm_data
    from photon_ml_tpu.game.coordinates import (
        FixedEffectCoordinate,
        RandomEffectCoordinate,
    )
    from photon_ml_tpu.game.data import (
        FixedEffectDataset,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.game.descent import CoordinateDescent
    from photon_ml_tpu.optim.problem import (
        GlmOptimizationConfig,
        OptimizerConfig,
    )
    from photon_ml_tpu.optim.regularization import RegularizationContext

    rng = np.random.default_rng(3)
    sizes = np.minimum(rng.zipf(1.8, GAME_ENTITIES), GAME_ROW_CAP)
    n = int(sizes.sum())
    users = np.repeat(
        np.array([f"u{i}" for i in range(GAME_ENTITIES)], dtype=object),
        sizes,
    )[rng.permutation(n)]
    n_items = max(2, GAME_ENTITIES // 5)
    item_sizes = np.minimum(rng.zipf(1.5, n_items), 4 * GAME_ROW_CAP)
    # Each row draws its item from the zipf-weighted pool (with
    # replacement), giving items a matching long-tailed row distribution.
    item_pool = np.repeat(
        np.array([f"i{i}" for i in range(n_items)], dtype=object),
        item_sizes,
    )
    items = item_pool[rng.integers(0, len(item_pool), size=n)]
    n_ctx = 200
    contexts = np.array(
        [f"c{rng.integers(n_ctx)}" for _ in range(n)], dtype=object
    )

    nnzf = n * GAME_FIXED_NNZ
    Xg = sp.csr_matrix(
        (rng.normal(size=nnzf).astype(np.float32),
         (np.repeat(np.arange(n, dtype=np.int64), GAME_FIXED_NNZ),
          rng.integers(0, GAME_FIXED_FEATURES, size=nnzf))),
        shape=(n, GAME_FIXED_FEATURES),
    )
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    weights = np.ones(n, np.float32)
    opt = GlmOptimizationConfig(
        optimizer=OptimizerConfig(max_iters=10, tolerance=1e-6),
        regularization=RegularizationContext.l2(),
    )

    fixed = FixedEffectCoordinate(
        "fixed",
        FixedEffectDataset(data=make_glm_data(Xg, y), n_global_rows=n),
        "logistic", opt, reg_weight=1.0,
    )
    coords = [fixed]
    _log(f"multire: {n} rows; grouping user/item/context...")
    for name, keys, cap in (
        ("per_user", users, None),
        ("per_item", items, None),
        # Few heavy contexts: the active-set cap bounds training rows,
        # the passive remainder still scores (the reference's split).
        ("per_context", contexts, 256),
    ):
        Xe = sp.csr_matrix(
            rng.normal(size=(n, GAME_RE_DIM)).astype(np.float32)
        )
        ds = build_random_effect_dataset(
            keys, Xe, y, weights,
            max_rows_per_entity=cap, bucket_growth=GAME_BUCKET_GROWTH,
        )
        _log(f"multire: {name}: {len(ds.blocks)} buckets "
             f"{[(b.n_entities, b.rows_per_entity) for b in ds.blocks]}")
        coords.append(RandomEffectCoordinate(
            name, ds, "logistic", opt, reg_weight=1.0, entity_key=name,
        ))
    cd = CoordinateDescent(coords)

    import jax.numpy as jnp

    base = jnp.zeros(n, jnp.float32)
    _log("multire: warmup iteration (compiles every bucket shape)...")
    warm = cd.run(base, n_iterations=1)
    _read_sync(warm.scores["per_context"])
    # Untimed run at the timed shape — same warm-in discipline as game_cd.
    _read_sync(
        cd.run(base, n_iterations=GAME_TIMED_ITERS).scores["per_context"]
    )
    _log("multire: warmup done; timing...")
    per_iter = []
    for _ in range(GAME_TIMED_RUNS):
        t0 = time.perf_counter()
        result = cd.run(base, n_iterations=GAME_TIMED_ITERS)
        _read_sync(result.scores["per_context"])
        per_iter.append((time.perf_counter() - t0) / GAME_TIMED_ITERS)
    med = float(np.median(per_iter))
    spread_pct = 100.0 * (max(per_iter) - min(per_iter)) / med
    _log(f"multire: median {med:.3f}s/iter over {GAME_TIMED_RUNS}x"
         f"{GAME_TIMED_ITERS} iters (spread {spread_pct:.1f}%)")

    states = {c.name: warm.states[c.name] for c in cd.coordinates}
    scores = dict(warm.scores)
    total = base
    for s in scores.values():
        total = total + s
    breakdown = {}
    for coord in cd.coordinates:
        best_c = np.inf
        for _ in range(2):
            offsets = total - scores[coord.name]
            t0 = time.perf_counter()
            st = coord.train(offsets, warm_state=states[coord.name])
            sc = coord.score(st)
            _read_sync(sc)
            best_c = min(best_c, time.perf_counter() - t0)
        breakdown[coord.name] = round(best_c, 3)
    _log(f"multire: per-coordinate seconds {breakdown}")
    return {
        "iters_per_sec": 1.0 / med,
        "spread_pct": round(spread_pct, 1),
        "coordinate_seconds": breakdown,
        "rows": n,
    }


def _game_scaling_problem(n_devices: int):
    """Deterministic multi-random-effect CD problem for the device-scaling
    leg — random effects ONLY, because the bitwise contract under test is
    the bucket-shard plan's (the distributed fixed effect is allclose,
    not bitwise, so it would mask the comparison)."""
    import scipy.sparse as sp

    import jax.numpy as jnp

    from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
    from photon_ml_tpu.game.data import build_random_effect_dataset
    from photon_ml_tpu.game.descent import CoordinateDescent
    from photon_ml_tpu.game.hierarchical import (
        ShardedBucketRandomEffectCoordinate,
    )
    from photon_ml_tpu.optim.problem import (
        GlmOptimizationConfig,
        OptimizerConfig,
    )
    from photon_ml_tpu.optim.regularization import RegularizationContext
    from photon_ml_tpu.parallel.distributed import data_mesh

    rng = np.random.default_rng(7)
    n_ent = 600 if SMALL else 4_000
    sizes = np.minimum(rng.zipf(1.8, n_ent), 64)
    n = int(sizes.sum())
    users = np.repeat(
        np.array([f"u{i}" for i in range(n_ent)], dtype=object), sizes
    )[rng.permutation(n)]
    items = np.array(
        [f"i{rng.integers(max(2, n_ent // 5))}" for _ in range(n)],
        dtype=object,
    )
    contexts = np.array(
        [f"c{rng.integers(200)}" for _ in range(n)], dtype=object
    )
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    weights = np.ones(n, np.float32)
    opt = GlmOptimizationConfig(
        optimizer=OptimizerConfig(max_iters=10, tolerance=1e-6),
        regularization=RegularizationContext.l2(),
    )
    mesh = data_mesh() if n_devices > 1 else None
    coords = []
    plans = {}
    for name, keys in (
        ("per_user", users), ("per_item", items), ("per_context", contexts)
    ):
        Xe = sp.csr_matrix(
            rng.normal(size=(n, GAME_RE_DIM)).astype(np.float32)
        )
        ds = build_random_effect_dataset(
            keys, Xe, y, weights,
            bucket_growth=GAME_BUCKET_GROWTH, device=mesh is None,
        )
        if mesh is not None:
            coord = ShardedBucketRandomEffectCoordinate(
                name, ds, mesh, "logistic", opt, reg_weight=1.0,
                entity_key=name,
            )
            plans[name] = [coord.plan.n_split, coord.plan.n_packed]
        else:
            coord = RandomEffectCoordinate(
                name, ds, "logistic", opt, reg_weight=1.0, entity_key=name
            )
        coords.append(coord)
    base = jnp.asarray(rng.normal(size=n).astype(np.float32))
    return CoordinateDescent(coords), base, plans


def _game_scaling_worker(n_devices: int) -> None:
    """Subprocess body for ``bench.py --game-scaling-worker N`` (the XLA
    host device count is fixed at backend init, so each scaling point
    needs its own process).  Prints ONE JSON line: iters/sec plus a
    sha256 over the final score vectors — the cross-device-count
    bitwise-parity witness."""
    import hashlib

    import jax

    assert jax.device_count() == n_devices, (
        f"expected {n_devices} devices, got {jax.device_count()} — was "
        "XLA_FLAGS=--xla_force_host_platform_device_count set?"
    )
    cd, base, plans = _game_scaling_problem(n_devices)
    _log(f"scaling worker ({n_devices} devices): warmup...")
    warm = cd.run(base, n_iterations=1)
    _read_sync(warm.scores["per_context"])
    _read_sync(cd.run(base, n_iterations=2).scores["per_context"])
    per_iter = []
    for _ in range(3):
        t0 = time.perf_counter()
        result = cd.run(base, n_iterations=2)
        _read_sync(result.scores["per_context"])
        per_iter.append((time.perf_counter() - t0) / 2)
    digest = hashlib.sha256()
    for coord in cd.coordinates:
        digest.update(
            np.asarray(result.scores[coord.name], np.float32).tobytes()
        )
    print(json.dumps({
        "n_devices": n_devices,
        "iters_per_sec": 1.0 / float(np.median(per_iter)),
        "score_sha256": digest.hexdigest(),
        "plans": plans,
    }))


def bench_game_device_scaling() -> dict:
    """Hierarchical-execution scaling gate (ISSUE 20): multi-RE CD
    iterations/sec at 1 vs 4 forced CPU host devices, with the sharded
    run's final scores required BITWISE equal to the single-device
    geometric-ladder baseline.  The >=1.5x speedup gate only arms when
    >=4 CPU cores are actually visible — 4 forced host devices on fewer
    cores timeshare, so a speedup there is unmeasurable by construction."""
    import subprocess

    results = {}
    for nd in (1, 4):
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=f"--xla_force_host_platform_device_count={nd}",
        )
        _log(f"scaling: launching {nd}-device worker...")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--game-scaling-worker", str(nd)],
            env=env, capture_output=True, text=True, timeout=1800,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"{nd}-device scaling worker failed: "
                f"{proc.stderr.strip().splitlines()[-5:]}"
            )
        results[nd] = json.loads(proc.stdout.strip().splitlines()[-1])
    scaling = results[4]["iters_per_sec"] / results[1]["iters_per_sec"]
    bitwise = results[1]["score_sha256"] == results[4]["score_sha256"]
    cores = len(os.sched_getaffinity(0))
    out = {
        "game_scaling_iters_per_sec_1dev": round(
            results[1]["iters_per_sec"], 3
        ),
        "game_scaling_iters_per_sec_4dev": round(
            results[4]["iters_per_sec"], 3
        ),
        "game_scaling_speedup_4dev": round(scaling, 3),
        "game_scaling_bitwise_ok": bitwise,
        "game_scaling_plans_4dev": results[4]["plans"],
    }
    if cores >= 4:
        out["game_scaling_gate_ok"] = bool(scaling >= 1.5 and bitwise)
    else:
        out["game_scaling_gate_ok"] = (
            f"waived: {cores} CPU core(s) visible — 4 forced host devices "
            "timeshare, parallel speedup unmeasurable (bitwise parity "
            f"still checked: {'PASS' if bitwise else 'FAIL'})"
        )
        if not bitwise:
            raise RuntimeError(
                "sharded scores diverged bitwise from the single-device "
                "ladder baseline"
            )
    _log(f"scaling: 1dev {results[1]['iters_per_sec']:.3f} it/s, "
         f"4dev {results[4]['iters_per_sec']:.3f} it/s "
         f"({scaling:.2f}x), bitwise {'PASS' if bitwise else 'FAIL'}, "
         f"gate {out['game_scaling_gate_ok']}")
    return out


def bench_game_repack_ab() -> dict:
    """Cost-model repacker A/B (ISSUE 20): realized padded FLOPs of the
    bench zipf entity distribution under the geometric ladder vs the
    repacker plan at the same program budget."""
    import scipy.sparse as sp

    from photon_ml_tpu.game.data import build_random_effect_dataset

    rng = np.random.default_rng(1)
    n_ent = min(GAME_ENTITIES, 20_000)
    sizes = np.minimum(rng.zipf(1.8, n_ent), GAME_ROW_CAP)
    n = int(sizes.sum())
    keys = np.repeat(
        np.array([f"u{i}" for i in range(n_ent)], dtype=object), sizes
    )
    Xe = sp.csr_matrix(rng.normal(size=(n, GAME_RE_DIM)).astype(np.float32))
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    weights = np.ones(n, np.float32)
    flops, blocks = {}, {}
    for repack in ("geometric", "cost_model"):
        ds = build_random_effect_dataset(
            keys, Xe, y, weights, device=False,
            bucket_growth=GAME_BUCKET_GROWTH, repack=repack,
            program_budget=16,
        )
        flops[repack] = sum(
            b.n_entities * b.rows_per_entity * b.block_dim
            for b in ds.blocks
        )
        blocks[repack] = len(ds.blocks)
    reduction = 100.0 * (1.0 - flops["cost_model"] / flops["geometric"])
    _log(f"repack A/B: geometric {flops['geometric']:.3g} padded FLOPs "
         f"({blocks['geometric']} programs) vs cost_model "
         f"{flops['cost_model']:.3g} ({blocks['cost_model']} programs): "
         f"{reduction:.1f}% reduction")
    return {
        "game_repack_padded_flops_geometric": flops["geometric"],
        "game_repack_padded_flops_cost_model": flops["cost_model"],
        "game_repack_programs": blocks,
        "game_repack_flop_reduction_pct": round(reduction, 1),
    }


def bench_glm_driver() -> dict:
    """Wall-clock of the full legacy GLM driver on an a1a-shaped dataset
    (1605 train / 2000 validate rows, 123 binary features, 3-point λ grid),
    COLD then WARM.

    Both runs are CHILD processes — a real job each: interpreter, imports,
    tracing, then compiles served (or not) by the persistent cache — run
    one after the other by a parent that has not initialised a JAX
    backend: a chip belongs to one process, so a parent holding it would
    leave its child to fail or hang.  They share the one compile-cache
    directory every entry point uses (utils/compile_cache.cache_dir); what
    the first run found there is reported, not assumed (``cold`` means the
    first child saw no cache hits)."""
    import subprocess

    import scipy.sparse as sp
    from jax._src import xla_bridge

    from photon_ml_tpu.data import libsvm
    from photon_ml_tpu.utils import compile_cache

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "bench_glm_driver must run before this process initialises a "
            "JAX backend: its children need the chip"
        )
    rng = np.random.default_rng(2)
    n_train, n_val, d = (400, 200, 123) if SMALL else (1605, 2000, 123)
    X = sp.random(
        n_train + n_val, d, density=0.11, random_state=4, format="csr"
    )
    X.data[:] = 1.0
    w_true = rng.normal(size=d) * (rng.uniform(size=d) < 0.3)
    logits = X @ w_true - 0.5
    y = np.where(
        rng.uniform(size=n_train + n_val) < 1 / (1 + np.exp(-logits)),
        1.0, -1.0,
    )
    repo = os.path.dirname(os.path.abspath(__file__))
    out = {
        "glm_driver_cache_entries_before": compile_cache.cache_entry_count(
            compile_cache.cache_dir()
        ) or 0,
    }
    with tempfile.TemporaryDirectory() as td:
        train = os.path.join(td, "a1a_shaped.libsvm")
        val = os.path.join(td, "a1a_shaped.t.libsvm")
        libsvm.write_libsvm(train, X[:n_train], y[:n_train])
        libsvm.write_libsvm(val, X[n_train:], y[n_train:])
        for leg in ("cold", "warm"):
            out_dir = os.path.join(td, f"out_{leg}")
            _log(f"driver: {leg} run (child process)...")
            t0 = time.perf_counter()
            r = subprocess.run(
                [sys.executable, "-m", "photon_ml_tpu.drivers.glm_driver",
                 "--train-data", train, "--validate-data", val,
                 "--output-dir", out_dir, "--task", "logistic",
                 "--reg-type", "l2", "--reg-weights", "0.1,1.0,10.0",
                 "--n-features", str(d)],
                cwd=repo, capture_output=True, text=True, timeout=1800,
            )
            wall = time.perf_counter() - t0
            if r.returncode != 0:
                raise RuntimeError(
                    f"glm_driver {leg} child exited {r.returncode}: "
                    f"{r.stderr.strip().splitlines()[-5:]}"
                )
            with open(os.path.join(out_dir, "training_result.json")) as f:
                rt = json.load(f)["runtime"]
            out[f"glm_driver_wall_seconds_{leg}"] = round(wall, 2)
            out[f"glm_driver_compile_seconds_{leg}"] = rt["compile_seconds"]
            out[f"glm_driver_cache_hits_{leg}"] = rt["compile_cache_hits"]
            out["glm_driver_device"] = (
                f"{rt['device_count']} x {rt['platform']} "
                f"({rt['device_kind']})"
            )
            _log(f"driver: {leg} {wall:.2f}s (compile "
                 f"{rt['compile_seconds']}s, {rt['compile_cache_hits']} "
                 "cache hits)")
    return out


def bench_streaming() -> dict:
    """Out-of-core A/B: the streamed objective pass (host chunks,
    double-buffered device_put — data/streaming.py) vs the device-resident
    pass on the SAME data, timed identically (host loop per pass, readback
    sync).  The VERDICT r2 acceptance bar is streamed ≥ 0.75x resident."""
    import jax
    import jax.numpy as jnp
    import scipy.sparse as sp

    from photon_ml_tpu.data.dataset import make_glm_data
    from photon_ml_tpu.data.streaming import make_streaming_glm_data
    from photon_ml_tpu.optim.objective import GlmObjective
    from photon_ml_tpu.optim.streaming import StreamingObjective
    from photon_ml_tpu.ops import losses

    # Calibrate host→device FIRST and size the workload from it: each
    # streamed pass re-transfers the whole chunk store, so the A/B is
    # budgeted at ~15 s of transfer per streamed pass (capped at N_ROWS),
    # and the measured link rate is reported so the ratio is
    # interpretable anywhere.
    blob = np.ones(32 << 20, np.uint8)
    dev = jax.device_put(blob)  # warmup: backend init / first-call cost
    np.asarray(dev[0:1])
    del dev
    t0 = time.perf_counter()
    dev = jax.device_put(blob)
    np.asarray(dev[0:1])
    h2d_gbps = blob.nbytes / (time.perf_counter() - t0) / 1e9
    del dev, blob
    bytes_per_row = NNZ_PER_ROW * 16  # measured ~500 B/row incl. layout pad
    n = int(min(N_ROWS, max(1 << 14, 15.0 * h2d_gbps * 1e9 / bytes_per_row)))
    _log(f"stream: h2d {h2d_gbps:.3f} GB/s -> {n} rows")

    rng = np.random.default_rng(5)
    nnz = n * NNZ_PER_ROW
    rows = np.repeat(np.arange(n, dtype=np.int64), NNZ_PER_ROW)
    cols = rng.integers(0, N_FEATURES, size=nnz).astype(np.int64)
    values = rng.normal(size=nnz).astype(np.float32)
    X = sp.coo_matrix((values, (rows, cols)), shape=(n, N_FEATURES)).tocsr()
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)

    _log(f"stream: building {STREAM_CHUNKS}-chunk store + resident copy...")
    from photon_ml_tpu.ops.sparse_pallas import pallas_available

    use_pallas = pallas_available()
    _log(f"stream: {'tiled Pallas' if use_pallas else 'XLA COO'} layout")
    stream = make_streaming_glm_data(
        X, y, chunk_rows=-(-n // STREAM_CHUNKS), use_pallas=use_pallas
    )
    if stream.staged is None:
        # The coalesced staging pipeline IS the thing being measured; a
        # silent fall-back to per-leaf device_put would report the slow
        # path's numbers as if they were the pipeline's (the failure mode
        # that would quietly re-open the 150x gap).
        raise RuntimeError(
            "bench_streaming: chunk store built UNSTAGED — the prefetch "
            "pipeline would fall back to per-leaf transfers; fix the "
            "store build (this is a measurement bug, not a workload "
            "property)"
        )
    sobj = StreamingObjective("logistic", stream)
    data = make_glm_data(X, y, use_pallas=use_pallas)
    obj = GlmObjective(losses.logistic)
    w = jnp.zeros(N_FEATURES, jnp.float32)

    # Fairness: the resident side is ONE jitted program (data as an
    # argument, never a closure constant), exactly like the streamed
    # side's jitted per-chunk program — otherwise eager dispatch overhead
    # inflates t_res and flatters the ratio.
    res_fn = jax.jit(
        lambda w, data: obj.value_and_grad(w, data, l2_weight=1.0)
    )

    # Warm both (compile) with a readback.
    _v, g = res_fn(w, data)
    _read_sync(g)
    _v, g = sobj.value_and_grad(w, 1.0)
    _read_sync(g)

    def timed(fn, reps=3):
        best = np.inf
        for _ in range(reps):
            t0 = time.perf_counter()
            _val, grad = fn()
            _read_sync(grad)
            best = min(best, time.perf_counter() - t0)
        return best

    t_res = timed(lambda: res_fn(w, data))
    # Transfer observability over the TIMED streamed passes only (the
    # warmup pass above would pollute the per-chunk numbers with
    # compile-time noise).  ONE timed pass for the stage attribution so
    # stage seconds and wall seconds describe the same window (timed()
    # keeps the best-of-3 wall for the headline rate).
    t_str = timed(lambda: sobj.value_and_grad(w, 1.0))
    sobj.transfer_stats.reset()
    t0 = time.perf_counter()
    _val, grad = sobj.value_and_grad(w, 1.0)
    _read_sync(grad)
    wall_1pass = time.perf_counter() - t0
    st = sobj.transfer_stats
    # Stage-attribution overlap witness: with pack ∥ transfer ∥ compute
    # pipelined, the SUMMED per-stage seconds exceed the pass's wall
    # clock (ratio > 1); serialized stages sum to ≤ wall.  A regression
    # in any one stage now names itself instead of hiding in the total.
    overlap = st.stage_seconds / wall_1pass if wall_1pass > 0 else 0.0

    # ---- Oversubscription leg (ISSUE 14): a chunk store split well past
    # the per-pass HBM budget, streamed with lossless wire compression +
    # the importance-aware hot working-set cache (hot budget = 70% of the
    # WIRE store, so ~11 of 16 chunks go resident and skip pack+transfer
    # entirely).  The headline stream_vs_resident is THIS configuration;
    # the uncompressed, uncached 4-chunk ratio stays reported as
    # stream_vs_resident_raw.  Two guards make the number honest: the
    # codec must have actually compressed (ratio > 1.02 — COO int64
    # indices always delta/downcast on this workload, so ~raw means the
    # planner silently fell back), and the compressed+cached gradient
    # must be BITWISE the raw streamed gradient on the same store.
    from photon_ml_tpu.data.staging import plan_compression

    _log(f"stream: oversubscription leg ({STREAM_OS_CHUNKS} chunks, "
         f"lossless wire + hot cache)...")
    stream_os = make_streaming_glm_data(
        X, y, chunk_rows=-(-n // STREAM_OS_CHUNKS), use_pallas=use_pallas
    )
    plan = plan_compression(stream_os.staging, stream_os.staged, "lossless")
    wire_store = plan.wire_nbytes * stream_os.n_chunks
    sobj_os_raw = StreamingObjective("logistic", stream_os)
    sobj_os = StreamingObjective(
        "logistic", stream_os, compress="lossless",
        hot_budget_bytes=int(STREAM_OS_HOT_FRAC * wire_store),
    )
    codec = sobj_os._codec
    if codec.ratio <= 1.02:
        raise RuntimeError(
            f"bench_streaming: lossless compression ratio {codec.ratio:.3f}"
            " — the wire chunks are effectively RAW, so the oversubscribed"
            " leg would time the uncompressed path while reporting it as"
            " compressed; the codec planner fell back (measurement bug,"
            " not a workload property)"
        )
    _vr, g_raw = sobj_os_raw.value_and_grad(w, 1.0)
    _read_sync(g_raw)
    # Warm passes: pass 1 compiles + scores chunk importance, pass 2
    # admits the hot set; the timed passes then run at steady-state hit
    # rate.  Bitwise gate on the LAST timed pass below.
    for _ in range(2):
        _vc, g_comp = sobj_os.value_and_grad(w, 1.0)
        _read_sync(g_comp)
    cache = sobj_os._hot_cache
    hits0, misses0 = cache.hits, cache.misses
    t_comp = timed(lambda: sobj_os.value_and_grad(w, 1.0), reps=2)
    _vc, g_comp = sobj_os.value_and_grad(w, 1.0)
    _read_sync(g_comp)
    if np.asarray(g_comp).tobytes() != np.asarray(g_raw).tobytes():
        raise RuntimeError(
            "bench_streaming: compressed+cached streamed gradient is NOT"
            " bitwise identical to the raw streamed gradient on the same"
            " oversubscribed store — the transfer-avoidance path changed"
            " the numbers it was supposed to only move faster"
        )
    d_hits = cache.hits - hits0
    d_misses = cache.misses - misses0
    hot_hit_rate = d_hits / max(1, d_hits + d_misses)
    logical_pass = stream_os.staging.nbytes * stream_os.n_chunks
    effective_gbps = logical_pass / t_comp / 1e9
    _log(f"stream: oversubscribed compressed+cached "
         f"{n / t_comp / 1e6:.1f} M rows/s (ratio {t_res / t_comp:.3f} vs "
         f"resident), codec {codec.ratio:.2f}x, hot hit rate "
         f"{hot_hit_rate:.2f} ({len(cache)} chunks / "
         f"{cache.resident_bytes / 1e6:.1f} MB resident), effective "
         f"{effective_gbps:.3f} GB/s logical")

    _log(f"stream: resident {n / t_res / 1e6:.1f} M rows/s, "
         f"streamed {n / t_str / 1e6:.1f} M rows/s "
         f"(ratio {t_res / t_str:.3f}, h2d {h2d_gbps:.3f} GB/s)")
    _log(f"stream: per-chunk h2d {st.gbps:.3f} GB/s "
         f"({st.chunk_seconds * 1e3:.1f} ms/chunk, "
         f"{len(stream.staged[0])} coalesced buffers), "
         f"stalls: consumer {st.consumer_stalls} "
         f"({st.consumer_stall_seconds:.2f}s) / producer "
         f"{st.producer_stalls} ({st.producer_stall_seconds:.2f}s), "
         f"max {st.max_live} chunks live")
    _log(f"stream: stage attribution over one {wall_1pass:.3f}s pass — "
         f"pack {st.pack_seconds:.3f}s | dispatch "
         f"{st.dispatch_seconds:.3f}s | h2d {st.h2d_seconds:.3f}s | "
         f"compute {st.consume_seconds:.3f}s; summed stages "
         f"{st.stage_seconds:.3f}s = {overlap:.2f}x wall "
         f"({'overlapped' if overlap > 1.0 else 'serialized'})")
    return {
        "stream_rows_per_sec": round(n / t_str, 1),
        "stream_rows": n,
        "stream_layout": "pallas" if use_pallas else "coo",
        "resident_rows_per_sec": round(n / t_res, 1),
        # Headline: the oversubscribed store streamed with lossless wire
        # compression + the hot working-set cache (the ISSUE 14
        # configuration); _raw is the uncompressed, uncached 4-chunk A/B
        # the r2/r05 bars were set against.
        "stream_vs_resident": round(t_res / t_comp, 4),
        "stream_vs_resident_raw": round(t_res / t_str, 4),
        "stream_os_rows_per_sec": round(n / t_comp, 1),
        "stream_os_chunks": stream_os.n_chunks,
        "stream_compression_ratio": round(codec.ratio, 3),
        "stream_hot_hit_rate": round(hot_hit_rate, 4),
        "stream_hot_resident_chunks": len(cache),
        "stream_hot_resident_mb": round(cache.resident_bytes / 1e6, 2),
        "stream_effective_gbps": round(effective_gbps, 3),
        "h2d_gbps": round(h2d_gbps, 3),
        # Per-chunk ingest pipeline metrics (ops/README.md "Reading the
        # streamed-ingest h2d metrics"): achieved staging-buffer rate,
        # mean per-chunk transfer time, and queue-stall counters over
        # the timed passes.
        "stream_h2d_gbps": round(st.gbps, 3),
        "stream_h2d_chunk_ms": round(st.chunk_seconds * 1e3, 2),
        "stream_consumer_stalls": st.consumer_stalls,
        "stream_producer_stalls": st.producer_stalls,
        "stream_consumer_stall_s": round(st.consumer_stall_seconds, 3),
        "stream_producer_stall_s": round(st.producer_stall_seconds, 3),
        "stream_prefetch_max_live": st.max_live,
        # Per-STAGE wall attribution over one measured pass (pack thread /
        # put() dispatch / transfer completion / consumer compute) and
        # the overlap witness: summed stage seconds vs the pass's wall
        # clock — > 1.0 means the pipeline stages genuinely overlapped.
        "stream_pack_s": round(st.pack_seconds, 3),
        "stream_dispatch_s": round(st.dispatch_seconds, 3),
        "stream_h2d_s": round(st.h2d_seconds, 3),
        "stream_compute_s": round(st.consume_seconds, 3),
        "stream_pass_wall_s": round(wall_1pass, 3),
        "stream_stage_overlap": round(overlap, 3),
    }


def bench_chaos() -> dict:
    """Chaos-harness cost + recovery latency (ISSUE 6 acceptance gates).

    1. **Disabled-path overhead gate**: with no FaultPlan installed every
       ``chaos.maybe_fail`` seam costs one global read + one branch.
       Measured directly (tight-loop ns/call), multiplied by the EXACT
       per-pass call count (an empty installed plan counts occurrences
       without injecting), and compared against a streamed objective
       pass's wall — the ``bench_streaming`` workload shape.  Gate:
       ≤ 1% of the streamed pass wall.
    2. **Recovery latency**: a scripted kill at a λ-grid boundary, then
       the watchdog resume — reported as the resumed attempt's wall
       (checkpoint reload + remaining solves) next to the uninterrupted
       grid's wall.
    3. **Serving degrade/re-promote**: wall of the first degraded
       (host cold path) batch and of the re-promotion probe batch.
    """
    import jax.numpy as jnp
    import scipy.sparse as sp

    from photon_ml_tpu import chaos
    from photon_ml_tpu.data.streaming import make_streaming_glm_data
    from photon_ml_tpu.io.checkpoint import GridCheckpointer
    from photon_ml_tpu.optim.problem import (
        GlmOptimizationConfig,
        GlmOptimizationProblem,
        OptimizerConfig,
    )
    from photon_ml_tpu.optim.regularization import RegularizationContext
    from photon_ml_tpu.optim.streaming import (
        StreamingObjective,
        streaming_run_grid,
    )
    from photon_ml_tpu.utils.watchdog import RetryPolicy, run_with_retries

    assert chaos.current_plan() is None, "bench needs the disabled path"

    # -- 1a. per-call cost of the disabled hook ----------------------------
    reps = 200_000
    t0 = time.perf_counter()
    for _ in range(reps):
        chaos.maybe_fail("grid.point")
    per_call_s = (time.perf_counter() - t0) / reps

    # -- 1b. streamed pass wall + exact per-pass seam-call count -----------
    rng = np.random.default_rng(17)
    n, d = (1 << 13), 256
    nnz = n * 16
    rows = np.repeat(np.arange(n, dtype=np.int64), 16)
    cols = rng.integers(0, d, size=nnz).astype(np.int64)
    X = sp.coo_matrix(
        (rng.normal(size=nnz).astype(np.float32), (rows, cols)),
        shape=(n, d),
    ).tocsr()
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    stream = make_streaming_glm_data(
        X, y, chunk_rows=-(-n // STREAM_CHUNKS), use_pallas=False
    )
    sobj = StreamingObjective("logistic", stream)
    w = jnp.zeros(d, jnp.float32)
    _v, g = sobj.value_and_grad(w, 1.0)  # warm (compile)
    _read_sync(g)
    wall = np.inf
    for _ in range(N_REPS):
        t0 = time.perf_counter()
        _v, g = sobj.value_and_grad(w, 1.0)
        _read_sync(g)
        wall = min(wall, time.perf_counter() - t0)
    # Exact call count: an EMPTY plan counts occurrences, injects nothing
    # (this pass runs the enabled-no-match path; only the count is used).
    counter_plan = chaos.FaultPlan([])
    with counter_plan:
        _v, g = sobj.value_and_grad(w, 1.0)
        _read_sync(g)
    calls = sum(
        counter_plan.occurrences(site) for site in chaos.KNOWN_SITES
    )
    overhead_frac = calls * per_call_s / wall if wall > 0 else 0.0
    gate_ok = overhead_frac <= 0.01
    _log(
        f"chaos: disabled maybe_fail {per_call_s * 1e9:.0f} ns/call x "
        f"{calls} calls/pass over a {wall * 1e3:.1f} ms streamed pass "
        f"-> {overhead_frac * 100:.4f}% overhead "
        f"({'PASS' if gate_ok else 'FAIL'} @ <=1%)"
    )

    # -- 2. kill/resume recovery latency -----------------------------------
    problem = GlmOptimizationProblem(
        "logistic",
        GlmOptimizationConfig(
            optimizer=OptimizerConfig(max_iters=25),
            regularization=RegularizationContext.l2(),
        ),
    )
    lams = [3.0, 1.0, 0.3]
    t0 = time.perf_counter()
    streaming_run_grid(problem, stream, lams)
    full_wall = time.perf_counter() - t0

    import tempfile

    with tempfile.TemporaryDirectory(prefix="bench_chaos_") as td:
        ckpt = GridCheckpointer(td)
        plan = chaos.FaultPlan([chaos.FaultSpec(site="grid.point", at=1)])
        attempt_walls = []

        def train(attempt):
            t0 = time.perf_counter()
            solved = ckpt.load() if attempt else {}
            acc = dict(solved)

            def on_solved(lam, w_):
                acc[lam] = np.asarray(w_)
                ckpt.save(acc)

            try:
                return streaming_run_grid(
                    problem, stream, lams, solved=solved,
                    on_solved=on_solved,
                )
            finally:
                attempt_walls.append(time.perf_counter() - t0)

        with plan:
            run_with_retries(
                train, RetryPolicy(max_retries=1), sleep=lambda s: None
            )
    recovery_wall = attempt_walls[-1]
    _log(
        f"chaos: kill@λ-boundary recovery {recovery_wall:.3f}s resume vs "
        f"{full_wall:.3f}s uninterrupted grid"
    )

    # -- 3. serving degrade / re-promote latency ---------------------------
    from photon_ml_tpu.serving.runtime import RuntimeConfig, ScoringRuntime
    from photon_ml_tpu.serving.synthetic import SyntheticWorkload

    workload = SyntheticWorkload(n_entities=256, seed=21)
    runtime = ScoringRuntime(
        workload.model, workload.index_maps,
        RuntimeConfig(max_batch_size=8, hot_entities=32,
                      breaker_cooldown_s=0.0),
    )
    batch = [runtime.parse_request(workload.request(i)) for i in range(8)]
    runtime.score_rows(batch)  # healthy warm batch
    with chaos.FaultPlan([
        chaos.FaultSpec(site="serving.device", at=0,
                        exception="InjectedDeviceLost"),
    ]):
        t0 = time.perf_counter()
        runtime.score_rows(batch)  # fault -> degrade -> host path
        degrade_wall = time.perf_counter() - t0
        t0 = time.perf_counter()
        runtime.score_rows(batch)  # probe -> re-promotion
        repromote_wall = time.perf_counter() - t0
    assert runtime.degraded is False and runtime.repromotions == 1
    _log(
        f"chaos: serving degrade batch {degrade_wall * 1e3:.2f} ms, "
        f"re-promotion probe {repromote_wall * 1e3:.2f} ms"
    )

    return {
        "chaos_maybe_fail_ns": round(per_call_s * 1e9, 1),
        "chaos_calls_per_streamed_pass": calls,
        "chaos_streamed_pass_wall_s": round(wall, 4),
        "chaos_disabled_overhead_frac": round(overhead_frac, 6),
        "chaos_overhead_gate_ok": gate_ok,
        "chaos_grid_full_wall_s": round(full_wall, 3),
        "chaos_grid_recovery_wall_s": round(recovery_wall, 3),
        "chaos_serving_degrade_ms": round(degrade_wall * 1e3, 2),
        "chaos_serving_repromote_ms": round(repromote_wall * 1e3, 2),
    }


def bench_analysis() -> dict:
    """Lock-order sanitizer cost gate (ISSUE 10 acceptance): the ENABLED
    sanitizer — every tracked-lock acquire/release feeding the witness
    graph — must add ≤ 1% to a streamed GLM pass.  The DISABLED path is
    free by construction (``sanitizers.tracked`` returns the raw lock
    when nothing is installed), asserted here rather than timed.

    Gate methodology mirrors ``bench_chaos``: the
    tracked acquire+release pair cost is measured in a tight loop and
    multiplied by the exact per-pass acquisition count (prefetch's
    ``_bump`` takes ``prefetch.live`` twice per chunk), then compared
    against the streamed pass wall; the measured A/B delta (sanitizer
    installed vs not — the prefetch pipeline creates its locks per pass,
    so installation flips the real hot path) is reported alongside.
    The static checker's own wall time over the full tree rides along
    as an informational number (it runs in check.sh, not per pass).
    """
    import threading

    import jax.numpy as jnp
    import scipy.sparse as sp

    from photon_ml_tpu.analysis import check as analysis_check
    from photon_ml_tpu.analysis import sanitizers
    from photon_ml_tpu.data.streaming import make_streaming_glm_data
    from photon_ml_tpu.optim.streaming import StreamingObjective

    # -- workload: the bench_chaos streamed shape -------------------------
    rng = np.random.default_rng(29)
    n, d = (1 << 13), 256
    nnz = n * 16
    rows = np.repeat(np.arange(n, dtype=np.int64), 16)
    cols = rng.integers(0, d, size=nnz).astype(np.int64)
    X = sp.coo_matrix(
        (rng.normal(size=nnz).astype(np.float32), (rows, cols)),
        shape=(n, d),
    ).tocsr()
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    stream = make_streaming_glm_data(
        X, y, chunk_rows=-(-n // STREAM_CHUNKS), use_pallas=False
    )
    sobj = StreamingObjective("logistic", stream)
    w = jnp.zeros(d, jnp.float32)

    def one_pass():
        _v, g = sobj.value_and_grad(w, 1.0)
        _read_sync(g)

    # Disabled path: tracked() must hand back the raw lock untouched.
    raw = threading.Lock()
    assert sanitizers.tracked(raw, "bench.check") is raw

    one_pass()  # warm (compile)
    wall_off = np.inf
    for _ in range(N_REPS):
        t0 = time.perf_counter()
        one_pass()
        wall_off = min(wall_off, time.perf_counter() - t0)

    reps = 100_000
    t0 = time.perf_counter()
    for _ in range(reps):
        raw.acquire()
        raw.release()
    raw_pair_s = (time.perf_counter() - t0) / reps

    with sanitizers.LockOrderSanitizer() as san:
        one_pass()  # re-warm: locks are now created tracked
        wall_on = np.inf
        for _ in range(N_REPS):
            t0 = time.perf_counter()
            one_pass()
            wall_on = min(wall_on, time.perf_counter() - t0)

        tl = sanitizers.tracked(threading.Lock(), "bench.unit")
        t0 = time.perf_counter()
        for _ in range(reps):
            tl.acquire()
            tl.release()
        tracked_pair_s = (time.perf_counter() - t0) / reps
        n_reports = len(san.reports)

    # -- per-pass accounting ----------------------------------------------
    chunks = stream.n_chunks
    # prefetch._bump takes prefetch.live once per +1 and once per -1.
    tracked_calls = 2 * chunks
    overhead_frac = (
        tracked_calls * max(tracked_pair_s - raw_pair_s, 0.0) / wall_off
    )
    gate_ok = overhead_frac <= 0.01
    measured_delta = (wall_on - wall_off) / wall_off

    t0 = time.perf_counter()
    report = analysis_check()
    check_wall_s = time.perf_counter() - t0

    _log(
        f"analysis: lock-order sanitizer — tracked pair "
        f"{tracked_pair_s * 1e9:.0f} ns vs raw {raw_pair_s * 1e9:.0f} ns "
        f"x {tracked_calls}/pass -> {overhead_frac * 100:.4f}% of a "
        f"{wall_off * 1e3:.1f} ms streamed pass "
        f"({'PASS' if gate_ok else 'FAIL'} @ <=1%); measured A/B delta "
        f"{measured_delta * 100:+.2f}%; {n_reports} inversion report(s); "
        f"static --check {'clean' if report.ok else 'FAILED'} in "
        f"{check_wall_s * 1e3:.0f} ms over {report.files} files"
    )
    return {
        "analysis_tracked_pair_ns": round(tracked_pair_s * 1e9, 1),
        "analysis_raw_pair_ns": round(raw_pair_s * 1e9, 1),
        "analysis_sanitizer_overhead_frac": round(overhead_frac, 6),
        "analysis_sanitizer_gate_ok": gate_ok,
        "analysis_measured_delta_frac": round(measured_delta, 4),
        "analysis_inversion_reports": n_reports,
        "analysis_check_wall_s": round(check_wall_s, 3),
        "analysis_check_ok": report.ok,
    }


def bench_avro_write() -> dict:
    """Scoring-result write rate (VERDICT r4 weak #5: the write path was
    the last pure-Python hot loop and had never been measured).  Times
    the columnar writer with the native encoder vs the Python fallback
    on 100k MovieLens-shaped scoring rows, deflate codec (the driver's
    default)."""
    from photon_ml_tpu import native as native_mod
    from photon_ml_tpu.io import avro

    rng = np.random.default_rng(7)
    n = 20_000 if SMALL else 100_000
    uids = [f"row{i}" for i in range(n)]
    scores = rng.normal(size=n).astype(np.float32)
    labels = (rng.uniform(size=n) < 0.5).astype(np.float32)
    ids = {
        "movieId": [f"m{i % 3883}" for i in range(n)],
        "userId": [f"u{i % 6040}" for i in range(n)],
    }
    block = (uids, scores, labels, ids)
    out = {}
    saved_env = os.environ.get("PHOTON_NO_NATIVE")
    try:
        with tempfile.TemporaryDirectory() as td:
            for label_, env in (("native", None), ("python", "1")):
                if env is None:
                    os.environ.pop("PHOTON_NO_NATIVE", None)
                else:
                    os.environ["PHOTON_NO_NATIVE"] = env
                native_mod._CACHE.pop("encoder", None)
                if env is None and native_mod.load_score_encoder() is None:
                    # No toolchain: don't report the fallback's rate as
                    # the native number.
                    out["avro_write_native_recs_per_sec"] = (
                        "unavailable (encoder build failed)"
                    )
                    continue
                path = os.path.join(td, f"w_{label_}.avro")
                best = np.inf
                for _ in range(3):
                    t0 = time.perf_counter()
                    avro.write_scoring_container(path, [block])
                    best = min(best, time.perf_counter() - t0)
                out[f"avro_write_{label_}_recs_per_sec"] = round(n / best, 1)
    finally:
        if saved_env is None:
            os.environ.pop("PHOTON_NO_NATIVE", None)
        else:
            os.environ["PHOTON_NO_NATIVE"] = saved_env
        native_mod._CACHE.pop("encoder", None)
    _log(
        f"avro: write native={out.get('avro_write_native_recs_per_sec')} "
        f"python={out.get('avro_write_python_recs_per_sec')} rec/s"
    )
    return out


def bench_serving() -> dict:
    """Online serving (PR 3): closed-loop throughput + latency of the
    micro-batched scoring service on a synthetic GAME model with ≥10k
    random-effect entities (zipf-skewed request stream, so the LRU hot
    set sees realistic hits over a cold tail).  In-process submits — no
    HTTP framing — so the number is the batcher+kernel path itself."""
    from photon_ml_tpu.serving import loadgen
    from photon_ml_tpu.serving.batcher import BatcherConfig
    from photon_ml_tpu.serving.runtime import RuntimeConfig, ScoringRuntime
    from photon_ml_tpu.serving.service import ScoringService
    from photon_ml_tpu.serving.synthetic import SyntheticWorkload

    n_entities = 10_000 if SMALL else 50_000
    duration = 2.0 if SMALL else 6.0
    clients = 16
    _log(f"serving: building synthetic GAME model "
         f"({n_entities} entities)...")
    workload = SyntheticWorkload(
        n_entities=n_entities, fixed_dim=64, re_dim=8, seed=9
    )
    runtime = ScoringRuntime(
        workload.model, workload.index_maps,
        RuntimeConfig(max_batch_size=64, hot_entities=4096),
    )
    _log(f"serving: warmed {runtime.warmup_compiles} bucket kernels "
         f"{runtime.buckets}; loading...")
    service = ScoringService(runtime, BatcherConfig(
        max_batch_size=64, max_wait_us=1000, max_queue=1024,
    ))
    with service:
        # Short warm run: first-touch allocator/pipeline costs and the
        # initial hot-set fill stay out of the timed window.
        loadgen.closed_loop(
            service.submit, workload.request, clients=4, duration_s=0.5
        )
        report = loadgen.closed_loop(
            service.submit, workload.request,
            clients=clients, duration_s=duration,
        )
    snap = report.snapshot()
    stats = runtime.stats()
    hot = stats["hot_sets"]["per_entity"]
    mean_batch = (
        stats["rows_scored"] / stats["batches"] if stats["batches"] else None
    )
    _log(f"serving: {snap['throughput_rps']} rps over {clients} closed-"
         f"loop clients, p50 {snap['latency_p50_ms']} ms / p99 "
         f"{snap['latency_p99_ms']} ms / p99.9 {snap['latency_p999_ms']} "
         f"ms, mean batch {mean_batch and round(mean_batch, 1)} rows, "
         f"hot hit rate {hot['hit_rate'] and round(hot['hit_rate'], 3)}")
    out = {
        "serving_throughput_rps": snap["throughput_rps"],
        "serving_latency_p50_ms": snap["latency_p50_ms"],
        "serving_latency_p99_ms": snap["latency_p99_ms"],
        "serving_latency_p999_ms": snap["latency_p999_ms"],
        "serving_completed": report.completed,
        "serving_rejected": report.rejected,
        "serving_clients": clients,
        "serving_entities": n_entities,
        "serving_mean_batch_rows": (
            None if mean_batch is None else round(mean_batch, 2)
        ),
        "serving_hot_hit_rate": (
            None if hot["hit_rate"] is None else round(hot["hit_rate"], 4)
        ),
    }
    out.update(_bench_serving_wire(workload))
    out.update(_bench_serving_scenarios(workload))
    out.update(_bench_serving_process(workload))
    out.update(_bench_serving_tenancy(workload))
    out.update(_bench_serving_fleet(workload))
    return out


def _bench_serving_scenarios(workload) -> dict:
    """Scripted HA scenarios against a 2-replica supervisor: per-scenario
    p50/p99 + error counts.  The replica-kill and swap-under-load
    scenarios must complete with ZERO failed requests — that is the HA
    acceptance gate, reported (not asserted) here so a regression shows
    up in the bench diff."""
    import tempfile

    from photon_ml_tpu.io.game_store import save_game_model
    from photon_ml_tpu.serving import loadgen
    from photon_ml_tpu.serving.batcher import BatcherConfig
    from photon_ml_tpu.serving.runtime import RuntimeConfig, ScoringRuntime
    from photon_ml_tpu.serving.service import ScoringService
    from photon_ml_tpu.serving.supervisor import ReplicaSupervisor
    from photon_ml_tpu.serving.synthetic import SyntheticWorkload

    rate = 150.0 if SMALL else 400.0
    rt_cfg = RuntimeConfig(max_batch_size=32, hot_entities=1024)

    def factory() -> ScoringRuntime:
        return ScoringRuntime(
            workload.model, workload.index_maps, rt_cfg
        )

    def make_request(i: int, phase) -> dict:
        if phase.entity_pool is None:
            return workload.request(i)
        # Skew shift: draw the entity from the phase's fraction range of
        # the entity space (disjoint ranges churn the LRU hot set).
        lo, hi = phase.entity_pool
        req = workload.request(i)
        span = max(1, int((hi - lo) * workload.n_entities))
        req["ids"][workload.entity_key] = (
            f"u{int(lo * workload.n_entities) + i % span}"
        )
        return req

    out: dict = {}
    with tempfile.TemporaryDirectory(prefix="bench_serving_swap_") as td:
        v2 = SyntheticWorkload(
            n_entities=workload.n_entities, fixed_dim=workload.fixed_dim,
            re_dim=workload.re_dim, seed=10,
        )
        v2_dir = os.path.join(td, "v2")
        _log("serving: saving swap-target model...")
        save_game_model(v2.model, v2.index_maps, v2_dir)
        for name, scenario in loadgen.SCENARIOS.items():
            if name == "noisy_neighbor":
                # Tenant-aware: needs per-tenant outcome accounting, so
                # _bench_serving_tenancy replays it via
                # run_noisy_neighbor — the tenant-blind run_scenario
                # here would lump aggressor sheds in with victim counts.
                continue
            wired = {"swap", "kill_replica"}
            if any(
                p.action is not None and p.action not in wired
                for p in scenario.phases
            ):
                # Scenarios needing other substrates run elsewhere:
                # worker_kill in _bench_serving_process (worker pool),
                # host_kill / quota_partition in _bench_serving_fleet
                # (multi-host router + lease coordinator).
                # run_scenario refuses unwired actions by design.
                continue
            supervisor = ReplicaSupervisor(
                factory, n_replicas=2, probe_interval_s=0.1
            )
            service = ScoringService(supervisor, BatcherConfig(
                max_batch_size=32, max_wait_us=1000, max_queue=1024,
            ))
            with service:
                actions = {
                    "swap": lambda svc=service: svc.reload(
                        v2_dir
                    ).to_dict(),
                    "kill_replica": lambda sup=supervisor: {
                        "killed": sup.kill_replica(0).rid
                    },
                }
                report = loadgen.run_scenario(
                    service.submit, make_request, scenario,
                    base_rate_rps=rate, actions=actions,
                )
            snap = report.snapshot()
            _log(
                f"serving scenario {name}: {report.completed} ok / "
                f"{report.rejected} shed / {report.errors} errors, p50 "
                f"{snap['latency_p50_ms']} ms p99 {snap['latency_p99_ms']}"
                " ms"
            )
            out[f"serving_scenario_{name}_p50_ms"] = snap["latency_p50_ms"]
            out[f"serving_scenario_{name}_p99_ms"] = snap["latency_p99_ms"]
            out[f"serving_scenario_{name}_p999_ms"] = (
                snap["latency_p999_ms"]
            )
            out[f"serving_scenario_{name}_completed"] = report.completed
            out[f"serving_scenario_{name}_rejected"] = report.rejected
            out[f"serving_scenario_{name}_errors"] = report.errors
    return out


def _bench_serving_process(workload) -> dict:
    """Process-mode HA gate: the ``worker_kill`` scenario delivers a real
    SIGKILL to a worker process while ≥120 rps flows through a 2-worker
    pool-backed supervisor.  The acceptance gate is zero errors AND zero
    rejections across the whole scenario (the pipe-EOF resubmission path
    absorbing the crash), reported as an explicit boolean so a
    regression is unmissable in the bench diff, alongside the tail
    latency (p99.9) the kill window costs."""
    from photon_ml_tpu.serving import loadgen
    from photon_ml_tpu.serving.batcher import BatcherConfig
    from photon_ml_tpu.serving.procpool import WorkerPool
    from photon_ml_tpu.serving.runtime import RuntimeConfig
    from photon_ml_tpu.serving.service import ScoringService
    from photon_ml_tpu.serving.supervisor import ReplicaSupervisor

    rate = 120.0 if SMALL else 240.0
    _log("serving: publishing model to shared memory (process mode)...")
    pool = WorkerPool(
        workload.model, workload.index_maps,
        runtime_config=RuntimeConfig(
            max_batch_size=32, hot_entities=1024
        ),
    )
    supervisor = ReplicaSupervisor(
        pool=pool, n_replicas=2, probe_interval_s=0.1
    )
    service = ScoringService(supervisor, BatcherConfig(
        max_batch_size=32, max_wait_us=1000, max_queue=1024,
    ))
    scenario = loadgen.SCENARIOS["worker_kill"]
    with service:
        report = loadgen.run_scenario(
            service.submit,
            lambda i, phase: workload.request(i),
            scenario,
            base_rate_rps=rate,
            actions={
                "kill_worker": lambda: {
                    "killed": supervisor.kill_replica(0).rid
                },
            },
        )
    snap = report.snapshot()
    zero_failed = report.errors == 0 and report.rejected == 0
    _log(
        f"serving process-mode worker_kill @ {rate:g} rps: "
        f"{report.completed} ok / {report.rejected} shed / "
        f"{report.errors} errors, p99 {snap['latency_p99_ms']} ms "
        f"p99.9 {snap['latency_p999_ms']} ms, zero-failed gate "
        f"{'PASS' if zero_failed else 'FAIL'}"
    )
    return {
        "serving_proc_worker_kill_rate_rps": rate,
        "serving_proc_worker_kill_p50_ms": snap["latency_p50_ms"],
        "serving_proc_worker_kill_p99_ms": snap["latency_p99_ms"],
        "serving_proc_worker_kill_p999_ms": snap["latency_p999_ms"],
        "serving_proc_worker_kill_completed": report.completed,
        "serving_proc_worker_kill_rejected": report.rejected,
        "serving_proc_worker_kill_errors": report.errors,
        "serving_proc_worker_kill_zero_failed": zero_failed,
    }


def _bench_serving_tenancy(workload) -> dict:
    """Multi-tenant isolation gate: the ``noisy_neighbor`` scenario in
    BOTH thread and process mode.  An aggressor tenant bursts to 10x
    its token-bucket quota while a victim tenant holds 40 rps; the
    acceptance gate (``*_isolation_pass``) is victim ZERO failures AND
    victim p99 inside its configured SLO AND the aggressor actually
    shed — reported per mode so a containment regression is unmissable
    in the bench diff."""
    from photon_ml_tpu.serving import loadgen
    from photon_ml_tpu.serving.batcher import BatcherConfig
    from photon_ml_tpu.serving.procpool import WorkerPool
    from photon_ml_tpu.serving.runtime import RuntimeConfig, ScoringRuntime
    from photon_ml_tpu.serving.service import ScoringService
    from photon_ml_tpu.serving.supervisor import ReplicaSupervisor
    from photon_ml_tpu.serving.tenancy import TenancyConfig, TenantSpec

    victim_slo_ms = 500.0
    n_units = 2
    rt_cfg = RuntimeConfig(max_batch_size=32, hot_entities=1024)
    # Quotas are enforced per batcher (per replica/worker): size the
    # aggressor's so the 10x burst is 10x its AGGREGATE admitted rate.
    aggressor_quota = 40.0 / n_units
    tenancy = TenancyConfig(tenants=(
        TenantSpec(
            name="victim", max_queue=256, p99_slo_ms=victim_slo_ms,
        ),
        TenantSpec(
            name="aggressor", quota_rps=aggressor_quota,
            burst=max(aggressor_quota / 2.0, 1.0), max_queue=128,
        ),
    ))
    batcher_cfg = BatcherConfig(
        max_batch_size=32, max_wait_us=1000, max_queue=1024,
        tenancy=tenancy,
    )

    def make_request(i: int, phase, tenant: str) -> dict:
        req = dict(workload.request(i))
        req["tenant"] = tenant
        return req

    out: dict = {}
    for mode, prefix in (("thread", "serving_tenant"),
                         ("process", "serving_proc_tenant")):
        if mode == "thread":
            supervisor = ReplicaSupervisor(
                lambda: ScoringRuntime(
                    workload.model, workload.index_maps, rt_cfg
                ),
                n_replicas=n_units, probe_interval_s=0.1,
            )
        else:
            _log("serving: publishing model to shared memory "
                 "(tenancy, process mode)...")
            pool = WorkerPool(
                workload.model, workload.index_maps,
                runtime_config=rt_cfg,
            )
            supervisor = ReplicaSupervisor(
                pool=pool, n_replicas=n_units, probe_interval_s=0.1
            )
        service = ScoringService(supervisor, batcher_cfg)
        with service:
            report = loadgen.run_noisy_neighbor(
                service.submit, make_request,
                victim_rate_rps=40.0, aggressor_rate_rps=40.0,
            )
        gate = report.isolation(victim_slo_ms)
        _log(
            f"serving tenancy noisy_neighbor ({mode}): victim "
            f"{gate['victim_completed']} ok / {gate['victim_failed']} "
            f"failed, p99 {gate['victim_p99_ms']} ms (SLO "
            f"{victim_slo_ms:g} ms); aggressor "
            f"{gate['aggressor_completed']} ok / "
            f"{gate['aggressor_shed']} shed; isolation gate "
            f"{'PASS' if gate['pass'] else 'FAIL'}"
        )
        out.update({
            f"{prefix}_victim_completed": gate["victim_completed"],
            f"{prefix}_victim_failed": gate["victim_failed"],
            f"{prefix}_victim_p99_ms": gate["victim_p99_ms"],
            f"{prefix}_victim_slo_ms": victim_slo_ms,
            f"{prefix}_aggressor_completed": (
                gate["aggressor_completed"]
            ),
            f"{prefix}_aggressor_shed": gate["aggressor_shed"],
            f"{prefix}_isolation_pass": gate["pass"],
        })
    return out


def _bench_serving_wire(workload) -> dict:
    """Data-plane A/B (ISSUE 16): the same service, the same request
    stream, measured over HTTP with persistent connections under both
    wire formats, plus adaptive-vs-static micro-batching in process.

    - ``serving_wire_{json,binary}_*``: closed-loop throughput and
      open-loop p50/p99/p999 at a FIXED offered rate for the JSON
      compatibility path vs the binary frame path.  The speedup ratio
      is reported, not hard-gated (accelerator-dependent).
    - ``serving_adaptive_*`` / ``serving_static_*``: open-loop latency
      at the same offered rate with the coalescing wait sized by the
      arrival-rate EWMA vs the static ``max_wait_us`` knob.
    """
    from photon_ml_tpu.serving import loadgen
    from photon_ml_tpu.serving.batcher import BatcherConfig
    from photon_ml_tpu.serving.runtime import RuntimeConfig, ScoringRuntime
    from photon_ml_tpu.serving.service import ScoringService, start_http_server

    duration = 2.0 if SMALL else 5.0
    clients = 16
    rate = 300.0 if SMALL else 1000.0
    out: dict = {}

    def service():
        return ScoringService(
            ScoringRuntime(
                workload.model, workload.index_maps,
                RuntimeConfig(max_batch_size=64, hot_entities=4096),
            ),
            BatcherConfig(
                max_batch_size=64, max_wait_us=1000, max_queue=1024,
            ),
        )

    # -- JSON vs binary over HTTP ------------------------------------------
    for fmt in ("json", "binary"):
        svc = service()
        with svc:
            server, _ = start_http_server(svc, port=0)
            base = f"http://127.0.0.1:{server.server_address[1]}"
            try:
                with loadgen.HttpSubmitter(
                    base, wire_format=fmt, workers=clients * 2
                ) as sub:
                    loadgen.closed_loop(  # warmup
                        sub.submit, workload.request,
                        clients=4, duration_s=0.5,
                    )
                    closed = loadgen.closed_loop(
                        sub.submit, workload.request,
                        clients=clients, duration_s=duration,
                    )
                    fixed = loadgen.open_loop(
                        sub.submit, workload.request,
                        rate_rps=rate, duration_s=duration,
                    )
            finally:
                server.shutdown()
                server.server_close()
        snap_c, snap_o = closed.snapshot(), fixed.snapshot()
        _log(
            f"serving wire[{fmt}]: {snap_c['throughput_rps']} rps closed"
            f"-loop; open-loop @{rate:g} rps p50 "
            f"{snap_o['latency_p50_ms']} / p99 {snap_o['latency_p99_ms']}"
            f" / p99.9 {snap_o['latency_p999_ms']} ms"
        )
        out.update({
            f"serving_wire_{fmt}_throughput_rps": snap_c["throughput_rps"],
            f"serving_wire_{fmt}_open_p50_ms": snap_o["latency_p50_ms"],
            f"serving_wire_{fmt}_open_p99_ms": snap_o["latency_p99_ms"],
            f"serving_wire_{fmt}_open_p999_ms": snap_o["latency_p999_ms"],
            f"serving_wire_{fmt}_errors": closed.errors + fixed.errors,
        })
    j = out["serving_wire_json_throughput_rps"]
    b = out["serving_wire_binary_throughput_rps"]
    out["serving_wire_speedup"] = round(b / j, 3) if j else None
    _log(f"serving wire: binary/json throughput ratio "
         f"{out['serving_wire_speedup']}")

    # -- codec microbench: framing cost without socket noise ----------------
    # The server-side work a request batch buys before scoring: encode
    # on the client, decode + validate into Rows on the server.  This
    # is where the binary format's zero-copy columns pay — JSON pays
    # json.loads + per-row parse allocations.
    import json as json_mod
    import time as time_mod

    from photon_ml_tpu.serving import wire as wire_mod

    runtime = ScoringRuntime(
        workload.model, workload.index_maps,
        RuntimeConfig(max_batch_size=64, hot_entities=4096),
    )
    parser = runtime._parser
    batch = [workload.request(i) for i in range(512)]
    reps = 5 if SMALL else 20

    def timed(fn) -> float:
        fn()  # warm
        t0 = time_mod.perf_counter()
        for _ in range(reps):
            fn()
        return (time_mod.perf_counter() - t0) / reps

    def json_path():
        raw = json_mod.dumps({"rows": batch}).encode()
        rows = json_mod.loads(raw)["rows"]
        return [parser.parse(r) for r in rows]

    def binary_path():
        raw = wire_mod.encode_request(batch)
        return wire_mod.decode_request(raw, parser)

    t_json = timed(json_path)
    t_bin = timed(binary_path)
    out["serving_wire_codec_json_ms"] = round(t_json * 1e3, 3)
    out["serving_wire_codec_binary_ms"] = round(t_bin * 1e3, 3)
    out["serving_wire_codec_speedup"] = round(t_json / t_bin, 2)
    _log(
        f"serving wire codec (512 rows): json {t_json * 1e3:.2f} ms, "
        f"binary {t_bin * 1e3:.2f} ms — {t_json / t_bin:.1f}x"
    )

    # -- adaptive vs static micro-batching ---------------------------------
    for label, adaptive in (("static", False), ("adaptive", True)):
        svc = ScoringService(
            ScoringRuntime(
                workload.model, workload.index_maps,
                RuntimeConfig(max_batch_size=64, hot_entities=4096),
            ),
            BatcherConfig(
                max_batch_size=64, max_wait_us=1000, max_queue=1024,
                adaptive_wait=adaptive,
            ),
        )
        with svc:
            loadgen.open_loop(  # warmup
                svc.submit, workload.request,
                rate_rps=rate / 2, duration_s=0.5,
            )
            report = loadgen.open_loop(
                svc.submit, workload.request,
                rate_rps=rate, duration_s=duration,
            )
        snap = report.snapshot()
        _log(
            f"serving batching[{label}]: open-loop @{rate:g} rps p50 "
            f"{snap['latency_p50_ms']} / p99 {snap['latency_p99_ms']} / "
            f"p99.9 {snap['latency_p999_ms']} ms"
        )
        out.update({
            f"serving_{label}_open_p50_ms": snap["latency_p50_ms"],
            f"serving_{label}_open_p99_ms": snap["latency_p99_ms"],
            f"serving_{label}_open_p999_ms": snap["latency_p999_ms"],
        })
    return out


def _bench_serving_fleet(workload) -> dict:
    """Fleet tier gates (serving/fleet.py): whole HOSTS behind one
    ``FleetRouter`` with a ``QuotaCoordinator`` leasing each tenant's
    fleet budget across hosts.

    - ``serving_fleet_host_kill_pass``: the ``host_kill`` scenario at
      >= 120 rps — a host's listener dies mid-phase and returns — must
      cost ZERO failed requests and ZERO rejections for the in-quota
      tenant (the ReplicaSupervisor's gate, one tier up).
    - ``serving_fleet_quota_partition_pass``: the ``quota_partition``
      scenario — every host's LeaseClient loses the coordinator — must
      hold fleet-wide admission within ONE LEASE WINDOW of the budget
      (degrade-to-last-lease: never unlimited, never zero) and recover
      to exact enforcement after heal, with zero non-shed failures.
      ``serving_fleet_quota_error_rps`` is the measured partition-phase
      over-admission rate; its allowance is one lease window spread
      over the phase.
    """
    from photon_ml_tpu.serving import loadgen
    from photon_ml_tpu.serving.batcher import BatcherConfig
    from photon_ml_tpu.serving.fleet import (
        FleetBudget, FleetRouter, LocalHost, QuotaCoordinator,
    )
    from photon_ml_tpu.serving.runtime import RuntimeConfig, ScoringRuntime
    from photon_ml_tpu.serving.service import ScoringService
    from photon_ml_tpu.serving.tenancy import TenancyConfig, TenantSpec

    n_hosts = 2 if SMALL else 3
    kill_rate = 120.0 if SMALL else 240.0
    acme_budget = 600.0 if SMALL else 1200.0
    budget_rps = 60.0
    burst_s = 0.25
    lease_ttl_s = 1.0
    rt_cfg = RuntimeConfig(max_batch_size=32, hot_entities=1024)
    tenancy = TenancyConfig(tenants=(
        TenantSpec(
            name="acme", quota_rps=acme_budget / n_hosts,
            burst=max(acme_budget * burst_s / n_hosts, 1.0),
            max_queue=512,
        ),
        TenantSpec(
            name="metered", quota_rps=budget_rps / n_hosts,
            burst=max(budget_rps * burst_s / n_hosts, 1.0),
            max_queue=512,
        ),
    ))
    batcher_cfg = BatcherConfig(
        max_batch_size=32, max_wait_us=1000, max_queue=1024,
        tenancy=tenancy,
    )

    def make_request(i: int, phase, tenant: str) -> dict:
        req = dict(workload.request(i))
        req["tenant"] = tenant
        return req

    _log(f"serving fleet: starting {n_hosts} HTTP hosts + router...")
    hosts = [
        LocalHost(
            f"host{i}",
            ScoringService(
                ScoringRuntime(workload.model, workload.index_maps, rt_cfg),
                batcher_cfg,
            ),
        ).start()
        for i in range(n_hosts)
    ]
    coordinator = QuotaCoordinator(
        [
            FleetBudget("acme", acme_budget, burst_s=burst_s),
            FleetBudget("metered", budget_rps, burst_s=burst_s),
        ],
        lease_ttl_s=lease_ttl_s,
    )
    clients = [h.attach_lease_client(coordinator).start() for h in hosts]
    router = FleetRouter(
        [h.base_url for h in hosts], probe_interval_s=0.1
    ).start()
    out: dict = {}
    try:
        for i in range(n_hosts * 4):  # warm ladders + settle leases
            router.score(make_request(i, None, "acme"))
        time.sleep(1.5 * lease_ttl_s)

        report = loadgen.run_fleet_scenario(
            router.submit, make_request,
            loadgen.SCENARIOS["host_kill"], tenant="acme",
            base_rate_rps=kill_rate,
            actions={
                "kill_host": hosts[0].kill,
                "restart_host": hosts[0].restart,
            },
        )
        kill_pass = (
            report.failed == 0 and report.shed == 0
            and report.completed >= kill_rate
        )
        snap = report.snapshot()
        _log(
            f"serving fleet host_kill: {report.completed} ok / "
            f"{report.shed} shed / {report.failed} failed at "
            f"{kill_rate:g} rps, p99 "
            f"{snap['phases']['kill']['latency_p99_ms']} ms in the kill "
            f"phase; gate {'PASS' if kill_pass else 'FAIL'}"
        )
        out.update({
            "serving_fleet_hosts": n_hosts,
            "serving_fleet_host_kill_rate_rps": kill_rate,
            "serving_fleet_host_kill_completed": report.completed,
            "serving_fleet_host_kill_rejected": report.shed,
            "serving_fleet_host_kill_failed": report.failed,
            "serving_fleet_host_kill_kill_p99_ms": (
                snap["phases"]["kill"]["latency_p99_ms"]
            ),
            "serving_fleet_host_kill_pass": kill_pass,
        })

        def partition() -> bool:
            for lc in clients:
                lc.partitioned = True
            return True

        def heal() -> bool:
            for lc in clients:
                lc.partitioned = False
            return True

        q_report = loadgen.run_fleet_scenario(
            router.submit, make_request,
            loadgen.SCENARIOS["quota_partition"], tenant="metered",
            base_rate_rps=2.5 * budget_rps,
            actions={"partition": partition, "heal": heal},
            seed=1,
        )
        burst_total = budget_rps * burst_s
        q_pass = q_report.failed == 0
        quota_error_rps = None
        for name, duration, _, pr in q_report.phases:
            window = lease_ttl_s if name == "partition" else 0.0
            bound = (
                budget_rps * (duration + window) * 1.15
                + burst_total + 10
            )
            if pr.completed > bound or (
                pr.completed < 0.4 * budget_rps * duration
            ):
                q_pass = False
            if name == "partition":
                quota_error_rps = round(
                    max(0.0, pr.completed / duration - budget_rps), 2
                )
        if any(lc.stale for lc in clients):
            q_pass = False  # renewal never recovered after heal
        _log(
            f"serving fleet quota_partition: {q_report.completed} "
            f"admitted / {q_report.shed} shed / {q_report.failed} "
            f"failed against budget {budget_rps:g} rps; partition "
            f"over-admission {quota_error_rps} rps (allowance: one "
            f"{lease_ttl_s:g}s lease window); gate "
            f"{'PASS' if q_pass else 'FAIL'}"
        )
        out.update({
            "serving_fleet_quota_budget_rps": budget_rps,
            "serving_fleet_quota_admitted": q_report.completed,
            "serving_fleet_quota_shed": q_report.shed,
            "serving_fleet_quota_failed": q_report.failed,
            "serving_fleet_quota_error_rps": quota_error_rps,
            "serving_fleet_lease_window_s": lease_ttl_s,
            "serving_fleet_quota_partition_pass": q_pass,
        })
    finally:
        router.stop()
        for h in hosts:
            h.stop()
    return out


def bench_freshness() -> dict:
    """Continuous train→serve loop (PR 12): the wall cost of staying
    fresh.  Two measurements:

    1. The ``freshness`` loadgen scenario against a 2-replica supervised
       service — an online-refined delta publishes and hot-applies
       MID-PHASE under open-loop traffic.  Reports p50/p99 and the
       zero-failed-requests gate (reported, not asserted, so a
       regression shows in the bench diff) plus the event→servable
       freshness SLO actually achieved.
    2. Delta apply vs full reload of the SAME refined model: the delta
       path's whole point is patching K changed rows instead of
       rebuilding n_entities tables from disk — both walls and the
       ratio, over several refine→publish→apply cycles.
    """
    import tempfile

    from photon_ml_tpu.freshness.applier import DeltaApplier
    from photon_ml_tpu.freshness.online import (
        LabeledEvent,
        OnlineRefiner,
        RefinerConfig,
    )
    from photon_ml_tpu.freshness.publisher import DeltaPublisher
    from photon_ml_tpu.io.game_store import save_game_model
    from photon_ml_tpu.serving import loadgen
    from photon_ml_tpu.serving.batcher import BatcherConfig
    from photon_ml_tpu.serving.runtime import RuntimeConfig, ScoringRuntime
    from photon_ml_tpu.serving.service import ScoringService
    from photon_ml_tpu.serving.supervisor import ReplicaSupervisor
    from photon_ml_tpu.serving.synthetic import SyntheticWorkload

    n_entities = 5_000 if SMALL else 20_000
    n_events = 200
    n_cycles = 2 if SMALL else 4  # quiet cycles after the scenario one
    rate = 150.0 if SMALL else 400.0
    workload = SyntheticWorkload(
        n_entities=n_entities, fixed_dim=32, re_dim=8, seed=21
    )
    rng = np.random.default_rng(22)
    rt_cfg = RuntimeConfig(max_batch_size=32, hot_entities=1024)

    def drift_events(now_wall: float) -> list:
        events = []
        for _ in range(n_events):
            events.append(LabeledEvent(
                features={
                    workload.fixed_shard: rng.normal(
                        size=workload.fixed_dim
                    ).astype(np.float32),
                    workload.re_shard: rng.normal(
                        size=workload.re_dim
                    ).astype(np.float32),
                },
                ids={
                    workload.entity_key: f"u{rng.integers(n_entities)}"
                },
                label=float(rng.integers(2)),
                wall_epoch=now_wall,
            ))
        return events

    def make_request(i: int, phase) -> dict:
        req = workload.request(i)
        if phase.entity_pool is not None:
            lo, hi = phase.entity_pool
            span = max(1, int((hi - lo) * n_entities))
            req["ids"][workload.entity_key] = (
                f"u{int(lo * n_entities) + i % span}"
            )
        return req

    out: dict = {}
    with tempfile.TemporaryDirectory(prefix="bench_freshness_") as td:
        v1_dir = os.path.join(td, "v1")
        _log(f"freshness: saving base model ({n_entities} entities)...")
        save_game_model(workload.model, workload.index_maps, v1_dir)

        def factory() -> ScoringRuntime:
            return ScoringRuntime.load(v1_dir, rt_cfg)

        supervisor = ReplicaSupervisor(
            factory, n_replicas=2, probe_interval_s=0.1
        )
        service = ScoringService(supervisor, BatcherConfig(
            max_batch_size=32, max_wait_us=1000, max_queue=1024,
        ))
        publisher = DeltaPublisher(os.path.join(td, "publications"))
        applier = DeltaApplier(service, publisher.root)
        base_model, _ = ScoringRuntime.load_model(v1_dir)
        event_to_servable: list[float] = []
        apply_walls: list[float] = []
        delta_rows: list[int] = []
        # Each cycle warm-starts a refiner from the model the replicas
        # currently serve (bitwise: the previous cycle's refined model),
        # so every delta's base checksum matches the live tables.
        state = {"base": base_model, "event_wall": 0.0, "refiner": None}

        def publish_delta() -> dict:
            event_wall = time.time()
            state["event_wall"] = event_wall
            refiner = OnlineRefiner(state["base"], RefinerConfig(seed=23))
            refiner.consume(drift_events(event_wall))
            state["refiner"] = refiner
            pub = refiner.publish(publisher)
            delta_rows.append(pub.n_changed_rows)
            return {"seq": pub.seq, "rows": pub.n_changed_rows}

        def apply_delta_action() -> dict:
            t0 = time.perf_counter()
            results = applier.poll_once()
            apply_walls.append(time.perf_counter() - t0)
            now_wall = time.time()
            event_to_servable.append(now_wall - state["event_wall"])
            state["base"] = state["refiner"].refined_model()
            return {
                "applied": [r.status for r in results],
                "version": service.swapper.version,
            }

        with service:
            report = loadgen.run_scenario(
                service.submit, make_request,
                loadgen.SCENARIOS["freshness"],
                base_rate_rps=rate,
                actions={
                    "publish_delta": publish_delta,
                    "apply_delta": apply_delta_action,
                },
            )
            # Quiet cycles: more apply-wall / event→servable samples
            # without traffic jitter.
            for _ in range(n_cycles):
                publish_delta()
                apply_delta_action()
            # The honest alternative to the delta path: a FULL disk
            # reload of the same refined model on the same service.
            refined_dir = os.path.join(td, "refined")
            save_game_model(
                state["base"], workload.index_maps, refined_dir
            )
            t0 = time.perf_counter()
            full = service.reload(refined_dir)
            full_reload_wall = time.perf_counter() - t0
        snap = report.snapshot()
        zero_failed = report.errors == 0 and report.rejected == 0
        apply_ms = round(float(np.median(apply_walls)) * 1e3, 2)
        e2s_p50 = round(float(np.percentile(event_to_servable, 50)), 3)
        e2s_p99 = round(float(np.percentile(event_to_servable, 99)), 3)
        _log(
            f"freshness scenario @ {rate:g} rps: {report.completed} ok / "
            f"{report.rejected} shed / {report.errors} errors, p99 "
            f"{snap['latency_p99_ms']} ms, zero-failed gate "
            f"{'PASS' if zero_failed else 'FAIL'}; event→servable p50 "
            f"{e2s_p50}s p99 {e2s_p99}s; delta apply {apply_ms} ms vs "
            f"full reload {round(full_reload_wall * 1e3, 1)} ms "
            f"({full.status})"
        )
        out.update({
            "freshness_scenario_p50_ms": snap["latency_p50_ms"],
            "freshness_scenario_p99_ms": snap["latency_p99_ms"],
            "freshness_scenario_completed": report.completed,
            "freshness_scenario_rejected": report.rejected,
            "freshness_scenario_errors": report.errors,
            "freshness_zero_failed": zero_failed,
            "freshness_event_to_servable_p50_s": e2s_p50,
            "freshness_event_to_servable_p99_s": e2s_p99,
            "freshness_delta_apply_ms": apply_ms,
            "freshness_full_reload_ms": round(full_reload_wall * 1e3, 1),
            "freshness_reload_speedup": round(
                full_reload_wall * 1e3 / max(apply_ms, 1e-3), 1
            ),
            "freshness_delta_rows_per_cycle": int(np.median(delta_rows)),
            "freshness_deltas_applied": applier.applied,
        })
    return out


def bench_tuning() -> dict:
    """Tuning orchestrator (PR 4): sequential vs parallel-4 wall clock of
    the SAME synthetic GLM λ sweep (GridProposer over a fixed λ path, so
    both runs fit the identical trial set), plus best-metric parity.
    λ-path warm starts stay ON — parity within 1e-6 is the acceptance
    bar: the L2 problem is strictly convex, so different warm-start
    availability under parallel scheduling must not move the selected
    optimum beyond solver tolerance."""
    import tempfile as _tf

    from photon_ml_tpu.drivers.glm_driver import make_fit_once
    from photon_ml_tpu.tuning.executor import (
        TuningConfig,
        TuningOrchestrator,
    )
    from photon_ml_tpu.tuning.scheduler import GridProposer, SearchSpace
    from photon_ml_tpu.tuning.state import TuningJournal

    n_rows = 20_000 if SMALL else 120_000
    d = 256
    rng = np.random.default_rng(17)
    X = rng.normal(size=(n_rows, d)).astype(np.float32)
    w_true = (
        rng.normal(size=d) * (rng.uniform(size=d) < 0.3)
    ).astype(np.float32)
    y = (
        rng.uniform(size=n_rows) < 1.0 / (1.0 + np.exp(-(X @ w_true)))
    ).astype(np.float32)
    split = int(n_rows * 0.8)
    lambdas = np.geomspace(1e-4, 1e2, 8)
    _log(f"tuning: {split} train rows x {d} features, "
         f"{len(lambdas)}-point λ sweep...")
    fit_once = make_fit_once(
        X[:split], y[:split], X[split:], y[split:],
        task="logistic", reg_type="l2", max_iters=60, tolerance=1e-8,
    )
    fit_once(np.array([1.0]), 0, None)  # compile outside the timing

    space = SearchSpace.create([(1e-5, 1e3)], log_scale=True,
                               names=["lambda"])

    def sweep(workers: int) -> tuple:
        with _tf.TemporaryDirectory(prefix="bench_tuning_") as td:
            journal = TuningJournal(td, fsync=False)
            cfg = TuningConfig(
                max_trials=len(lambdas), workers=workers,
                maximize=fit_once.larger_is_better,
            )
            t0 = time.perf_counter()
            result = TuningOrchestrator(
                space, fit_once,
                GridProposer(space, [[lam] for lam in lambdas]),
                cfg, journal,
            ).run()
            wall = time.perf_counter() - t0
            journal.close()
        return result, wall

    seq, seq_wall = sweep(1)
    par, par_wall = sweep(4)
    delta = abs(seq.best_metric - par.best_metric)
    _log(f"tuning: sequential {seq_wall:.2f}s vs parallel-4 "
         f"{par_wall:.2f}s ({seq_wall / par_wall:.2f}x), best metric "
         f"{seq.best_metric:.6f} vs {par.best_metric:.6f} "
         f"(delta {delta:.2e})")
    return {
        "tuning_seq_seconds": round(seq_wall, 3),
        "tuning_par4_seconds": round(par_wall, 3),
        "tuning_speedup": round(seq_wall / par_wall, 3),
        "tuning_best_lambda": seq.best_params[0],
        "tuning_best_metric_delta": delta,
        "tuning_parity_ok": bool(delta <= 1e-6),
        "tuning_trials": seq.n_trials,
    }


def bench_solvers() -> dict:
    """Distributed solver A/B (PR 18): consensus-ADMM over ≥2 shards vs
    streamed OWL-QN on the SAME elastic-net lasso λ grid.

    The claim under test is COMMUNICATION, not FLOPs: OWL-QN pays one
    logical all-reduce per objective evaluation (every streamed pass
    publishes ``solver_allreduce_count`` — optim/streaming.py), while
    ADMM folds each outer iteration into ONE fixed-size psum
    (solvers/admm.py), so both sides are read off the same counter.
    The OWL-QN leg runs ``batch_linesearch=False``: batching the
    line-search bracket into one pass is a single-device streaming
    trick — on a real mesh every candidate evaluation is its own psum,
    and the bench counts the communication a mesh would pay.  The
    design matrix is moderately ill-conditioned (geometric spectrum
    1 → 0.02) so first-order line searches pay their usual toll; the
    squared-loss task also exercises ADMM's cached-eigendecomposition
    ridge x-update (one Gram factorization for the whole grid AND
    every ρ).  Gates: ≥5x fewer reduces per solve AND ≤1e-5 relative
    objective gap (both solvers scored by one resident evaluator)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu import telemetry as telemetry_mod
    from photon_ml_tpu.data.streaming import make_streaming_glm_data
    from photon_ml_tpu.ops import losses as losses_lib
    from photon_ml_tpu.optim.problem import (
        GlmOptimizationConfig,
        GlmOptimizationProblem,
        OptimizerConfig,
        OptimizerType,
    )
    from photon_ml_tpu.optim.regularization import RegularizationContext
    from photon_ml_tpu.optim.streaming import streaming_run_grid
    from photon_ml_tpu.parallel.distributed import shard_glm_data
    from photon_ml_tpu.solvers import sharded as solvers_sharded

    n, d = (2048, 48) if SMALL else (8192, 96)
    n_shards = 4
    rng = np.random.default_rng(7)
    Z = rng.normal(size=(n, d))
    Q, _ = np.linalg.qr(rng.normal(size=(d, d)))
    spec = np.geomspace(1.0, 0.02, d)
    X = ((Z * spec) @ Q.T / np.sqrt(d)).astype(np.float32)
    w_true = (
        rng.normal(size=d) * (rng.uniform(size=d) < 0.3)
    ).astype(np.float32)
    y = (X @ w_true + 0.1 * rng.normal(size=n)).astype(np.float32)
    grid = [3e-1, 1e-1, 3e-2]
    reg = RegularizationContext.elastic_net(0.5)
    loss = losses_lib.get("squared")
    Xj, yj = jnp.asarray(X), jnp.asarray(y)

    @jax.jit
    def objective(w, l1, l2):
        m = Xj @ w
        return (jnp.sum(loss.value(m, yj)) + l1 * jnp.sum(jnp.abs(w))
                + 0.5 * l2 * jnp.vdot(w, w))

    def score(results):
        return {
            lam: float(objective(
                jnp.asarray(model.coefficients.means),
                reg.l1_weight(lam), reg.l2_weight(lam),
            ))
            for lam, model, _res in results
        }

    def make_problem(solver=None, options=()):
        return GlmOptimizationProblem("linear", GlmOptimizationConfig(
            optimizer=OptimizerConfig(
                optimizer=OptimizerType.LBFGS, max_iters=200,
                tolerance=1e-8, solver=solver, solver_options=options,
            ),
            regularization=reg,
        ))

    tel = telemetry_mod.current()

    def counted(run):
        c0 = tel.counter("solver_allreduce_count").value
        b0 = tel.counter("solver_allreduce_bytes_total").value
        t0 = time.perf_counter()
        results = run()
        wall = time.perf_counter() - t0
        return (results, wall,
                tel.counter("solver_allreduce_count").value - c0,
                tel.counter("solver_allreduce_bytes_total").value - b0)

    _log(f"solvers: {n} rows x {d} features, {len(grid)}-point L1 grid, "
         f"ADMM over {n_shards} shards vs streamed OWL-QN...")
    stream = make_streaming_glm_data(X, y, chunk_rows=max(256, n // 8))
    p_ref = make_problem()
    ref_run = lambda: streaming_run_grid(
        p_ref, stream, grid, batch_linesearch=False
    )
    ref_run()  # compile outside the timing
    ref_results, ref_wall, ref_reduces, ref_bytes = counted(ref_run)

    p_admm = make_problem("admm", (
        ("rho", "0.05"), ("reltol", "1e-4"), ("over_relaxation", "1.8"),
    ))
    dist = shard_glm_data(X, y, None, n_shards=n_shards)
    admm_run = lambda: solvers_sharded.run_grid_sharded(
        p_admm, dist, None, grid
    )
    admm_run()  # compile outside the timing
    admm_results, admm_wall, admm_reduces, admm_bytes = counted(admm_run)

    f_ref, f_admm = score(ref_results), score(admm_results)
    gap = max(
        abs(f_admm[lam] - f_ref[lam]) / max(1.0, abs(f_ref[lam]))
        for lam in f_ref
    )
    reduce_ratio = ref_reduces / max(1, admm_reduces)
    _log(f"solvers: reduces/solve owlqn {ref_reduces / len(grid):.0f} vs "
         f"admm {admm_reduces / len(grid):.0f} ({reduce_ratio:.1f}x), "
         f"bytes {ref_bytes / 1e6:.2f} vs {admm_bytes / 1e6:.2f} MB, "
         f"wall {ref_wall:.2f}s vs {admm_wall:.2f}s, "
         f"objective gap {gap:.2e}")
    return {
        "solvers_owlqn_reduces_per_solve": round(ref_reduces / len(grid), 1),
        "solvers_admm_reduces_per_solve": round(admm_reduces / len(grid), 1),
        "solvers_reduce_ratio": round(reduce_ratio, 2),
        "solvers_owlqn_bytes": ref_bytes,
        "solvers_admm_bytes": admm_bytes,
        "solvers_owlqn_wall_seconds": round(ref_wall, 3),
        "solvers_admm_wall_seconds": round(admm_wall, 3),
        "solvers_objective_gap": gap,
        "solvers_gap_ok": bool(gap <= 1e-5),
        "solvers_reduce_ratio_ok": bool(reduce_ratio >= 5.0),
    }


def bench_cluster() -> dict:
    """Cluster control plane (ISSUE 19): the 3-host drill as a gate,
    plus a distribution wire microbench.

    The drill (the same one ``python -m photon_ml_tpu.cluster
    --selfcheck`` runs) kills the leader quota-coordinator replica
    under >= 120 rps open-loop load — failover must land within one
    lease TTL with ZERO failed requests and journal-replay-bounded
    over-admission — then cold-starts a third host from the newest
    snapshot publication over HTTP (bit-identical scores) while
    another host drains.  The microbench times a fresh snapshot fetch
    through :class:`PublicationClient` — every byte sha256-verified
    end to end — so the reported MB/s is the VERIFIED ingest rate a
    joining host actually sees, not raw socket throughput."""
    import shutil
    import tempfile

    from photon_ml_tpu.cluster import PublicationClient, PublicationServer
    from photon_ml_tpu.cluster.__main__ import run_cluster_drill
    from photon_ml_tpu.freshness.publisher import DeltaPublisher

    out: dict = {}
    _log("cluster: 3-host drill (coordinator kill + join/drain + "
         "cold start)...")
    td = tempfile.mkdtemp(prefix="bench_cluster_")
    try:
        t0 = time.perf_counter()
        failures = run_cluster_drill(
            td, drill_rate=60.0 if SMALL else 150.0, lease_ttl_s=1.0
        )
        out["cluster_drill_wall_seconds"] = round(
            time.perf_counter() - t0, 2
        )
        out["cluster_drill_ok"] = not failures
        if failures:
            out["cluster_drill_failures"] = failures[:3]

        # Verified-ingest microbench: one snapshot, fetched cold.
        payload_mb = 2 if SMALL else 16
        root = os.path.join(td, "bench_pub_root")
        model = os.path.join(td, "bench_model")
        os.makedirs(model)
        rng = np.random.default_rng(5)
        for i in range(4):
            with open(os.path.join(model, f"block{i}.bin"), "wb") as f:
                f.write(rng.bytes(payload_mb * 1024 * 1024 // 4))
        pub = DeltaPublisher(root, fsync=False).publish_snapshot(model)
        server = PublicationServer(root).serve()
        try:
            client = PublicationClient(
                server.base_url, os.path.join(td, "bench_cache")
            )
            remote = [
                p for p in client.publications() if p.seq == pub.seq
            ][0]
            t0 = time.perf_counter()
            client.fetch(remote)
            fetch_wall = time.perf_counter() - t0
        finally:
            server.close()
        out["cluster_fetch_mb_per_sec"] = round(
            payload_mb / fetch_wall, 1
        )
        _log(f"cluster: drill "
             f"{'ok' if out['cluster_drill_ok'] else 'FAILED'} in "
             f"{out['cluster_drill_wall_seconds']}s, verified fetch "
             f"{out['cluster_fetch_mb_per_sec']} MB/s "
             f"({payload_mb} MB snapshot)")
    finally:
        shutil.rmtree(td, ignore_errors=True)
    return out


def main() -> int:
    import traceback

    extra = {}
    failed: list[str] = []

    def section(key: str, fn) -> None:
        """Run one bench section; a failure is logged with its traceback,
        named in the output and turns the exit code non-zero — the other
        sections still run."""
        try:
            extra.update(fn())
        except Exception:  # noqa: BLE001 — reported, then the bench fails
            _log(f"{key}: FAILED\n{traceback.format_exc()}")
            failed.append(key)

    # FIRST, before this process initialises a JAX backend: the driver
    # leg's children need the chip (bench_glm_driver refuses otherwise).
    if ONLY in ("", "driver"):
        section("driver", bench_glm_driver)

    # Sink-less but ENABLED telemetry hub: the streamed/ooc sections'
    # prefetch pipelines feed their TransferStats into its registry
    # (h2d_gbps, stall counters — data/prefetch.py), events stay
    # one-branch no-ops.  The snapshot rides the bench JSON so a recorded
    # line carries stall/bandwidth/compile attribution.
    from photon_ml_tpu import telemetry as telemetry_mod
    from photon_ml_tpu.utils import compile_cache, device_report

    # The one compile cache every entry point uses (bench reruns skip
    # most of their compile wall).
    compile_cache.enable_compile_cache("auto")
    bench_tel = telemetry_mod.Telemetry(enabled=True, sinks=[])
    prev_tel = telemetry_mod.set_current(bench_tel)
    device = device_report.describe_devices()
    _log(f"device: {device}")

    def game_cd() -> dict:
        g = bench_game_cd()
        return {
            "game_cd_iters_per_sec": round(g["iters_per_sec"], 3),
            "game_cd_spread_pct": g["spread_pct"],
            "game_cd_coordinate_seconds": g["coordinate_seconds"],
        }

    def game_multi_re() -> dict:
        m = bench_game_multi_re()
        return {
            "game_multi_re_iters_per_sec": round(m["iters_per_sec"], 3),
            "game_multi_re_spread_pct": m["spread_pct"],
            "game_multi_re_coordinate_seconds": m["coordinate_seconds"],
            "game_multi_re_rows": m["rows"],
        }

    if ONLY in ("", "game"):
        section("game_cd", game_cd)
        section("game_repack", bench_game_repack_ab)
        section("game_scaling", bench_game_device_scaling)
    if ONLY in ("", "game", "multire"):
        section("game_multi_re", game_multi_re)
    for key, fn in (
        ("stream", bench_streaming),
        ("avro", bench_avro_write),
        ("serving", bench_serving),
        ("freshness", bench_freshness),
        ("tuning", bench_tuning),
        ("solvers", bench_solvers),
        ("chaos", bench_chaos),
        ("analysis", bench_analysis),
        ("cluster", bench_cluster),
    ):
        if ONLY in ("", key):
            section(key, fn)

    out = {
        "metric": "logistic_glm_rows_per_sec",
        "unit": "rows/s",
        "value": None,
        "device": {
            "platform": device["platform"],
            "kind": device["device_kind"],
            "count": device["device_count"],
        },
        "extra": extra,
    }

    def glm() -> dict:
        g = bench_glm_throughput()
        out["value"] = round(g["rows_per_sec"], 1)
        return {
            "glm_layout": g["layout"],
            "kernel_achieved_gbps": round(g["achieved_gbps"], 1),
        }

    if ONLY in ("", "glm"):
        section("glm", glm)
    else:
        out["note"] = f"primary metric skipped (BENCH_ONLY={ONLY})"
    telemetry_mod.set_current(prev_tel)
    snap = bench_tel.snapshot()
    extra["telemetry_metrics"] = {
        "counters": snap["counters"],
        "gauges": snap["gauges"],
    }
    extra["failed_sections"] = failed
    print(json.dumps(out))
    return 1 if failed else 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--game-scaling-worker":
        _game_scaling_worker(int(sys.argv[2]))
    else:
        sys.exit(main())
