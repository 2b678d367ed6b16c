"""Hierarchical GAME execution (ISSUE 20): the parity matrix.

Three claims, each pinned here:

- **Sharded is single-device: bit for bit where the program is the
  same, within 4 ulp where it is not.**  The bucket-shard plan
  (game/hierarchical.py) moves WHERE each block's program runs, never
  the shapes or the math, and the score scatter re-runs on one device
  in global block order — so every PACKED block (one whole block on one
  device: the same program) must reproduce the single-device
  coordinate bit for bit, resident AND out-of-core, across per_user /
  per_item / per_context shapes.  A SPLIT block is another program: the
  mesh runs its vmapped solve 2 lanes a device where one device runs
  all 13, and XLA:CPU sums a lane's rows in another order at another
  batch width.  ``PARITY_ULPS`` states, case by case, where that shows
  (at most 1 ulp of the block's largest coefficient when the bound was
  set) and holds those blocks, and the scores built from them, to 4 ulp.
- **Pipelined is bitwise serial.**  The overlap schedule
  (game/descent.py ``pipeline=True``) prestages only offset-independent
  host work; the Gauss-Seidel trajectory is untouched.
- **Repacked is numerical, NOT bitwise.**  The cost-model repacker
  (game/data.py) changes realized block shapes, and f32 reductions are
  not bitwise-stable under padding-length changes — so the repacked
  model is asserted allclose, while the PLAN itself is asserted fully
  deterministic and budget-feasible.

Plus the chaos seams: a kill at "game.bucket_shard" (mid-update device
dispatch) or "game.repack" (plan construction) must retry/resume to the
uninterrupted result bitwise (docs/robustness.md contract).
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from photon_ml_tpu import chaos
from photon_ml_tpu import telemetry as telemetry_mod
from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
from photon_ml_tpu.game.data import (
    build_random_effect_dataset,
    plan_entity_buckets,
)
from photon_ml_tpu.game.descent import CoordinateDescent
from photon_ml_tpu.game.hierarchical import (
    ShardedBucketRandomEffectCoordinate,
    plan_bucket_shards,
)
from photon_ml_tpu.game.ooc_random import OutOfCoreRandomEffectCoordinate
from photon_ml_tpu.optim.problem import (
    GlmOptimizationConfig,
    OptimizerConfig,
)
from photon_ml_tpu.optim.regularization import RegularizationContext
from photon_ml_tpu.parallel.distributed import data_mesh
from photon_ml_tpu.utils.watchdog import (
    RetryPolicy,
    RetryStats,
    run_with_retries,
)


def _bitwise(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.tobytes() == b.tobytes()


def _zipf_data(seed, n_entities=60, d=5, max_rows=40):
    """Long-tailed per-entity row counts: a multi-rung bucket ladder
    with a big head bucket (splits over the mesh) and a long tail
    (packs whole) — the shape mix the shard plan exists for."""
    rng = np.random.default_rng(seed)
    keys, rows, labels = [], [], []
    true_w = rng.normal(size=(n_entities, d))
    for e in range(n_entities):
        n_e = int(np.clip(rng.zipf(1.7), 1, max_rows))
        for _ in range(n_e):
            x = np.zeros(d, np.float32)
            nz = rng.choice(d, size=rng.integers(1, d + 1), replace=False)
            x[nz] = rng.normal(size=len(nz)).astype(np.float32)
            m = float(x @ true_w[e])
            keys.append(f"e{e}")
            rows.append(x)
            labels.append(float(rng.uniform() < 1 / (1 + np.exp(-m))))
    X = sp.csr_matrix(np.asarray(rows, np.float32))
    y = np.asarray(labels, np.float32)
    return keys, X, y, np.ones_like(y)


def _config():
    return GlmOptimizationConfig(
        optimizer=OptimizerConfig(max_iters=25, tolerance=1e-7),
        regularization=RegularizationContext.l2(),
    )


#: the parity matrix's coordinate axis — three entity populations with
#: different seeds/shapes (per_user wide tail, per_item narrower
#: features, per_context more features).
COORD_GRID = [
    ("per_user", dict(seed=3, n_entities=120, d=5)),
    ("per_item", dict(seed=5, n_entities=90, d=4)),
    ("per_context", dict(seed=9, n_entities=80, d=6)),
]


#: Per case, the ulps allowed on (a SPLIT block's state, the resident
#: scores of a split block's rows, the out-of-core scores of those rows);
#: 0 is bit for bit, which is what every packed block, and every row of
#: one, is held to in every case.  A split block's solve
#: is the same vmapped program at another batch width (2 lanes a device
#: against the whole block on one), and XLA:CPU orders a lane's f32
#: sums by that width: measured, the states of one split block differ
#: by at most 1.2e-7 absolute on coefficients up to 1.3 (0.8 ulp of the
#: largest) and the scores by at most 4.8e-7 on scores up to 6.1 (0.7
#: ulp).  A wrong block, a lost lane or stale offsets move these by
#: 1e-2 and more.  per_item's split blocks agreed to the bit while the
#: Newton system was solved by CG.  The direct entity-minor solve (PR 32)
#: is elementwise over the entity axis, and one of its operations gives
#: other bits at another width: the elimination's right-hand-side update
#: ``r - col * r[j]`` (measured alone on 10 lanes against 2: up to 2.4e-7
#: there; the margin, the gradient, both Hessian builds, the pivots, the
#: Schur complement and the back substitution are the same bits at both
#: widths).  One lane of per_item's (10, 8, 4) split block then differs
#: by 6.0e-8 (0.5 ulp of the block's largest); its other split block,
#: every packed block and every packed row still agree to the bit.
PARITY_ULPS = {
    "per_user": (4, 4, 4),
    "per_item": (4, 4, 4),
    "per_context": (4, 0, 4),
}


def _assert_parity(a, b, ulps):
    """``ulps`` units in the last place of ``a``'s largest entry, f32
    (iterates of a solve are accurate to the block's scale, not to each
    entry's own); 0 asks for the same bits."""
    a, b = np.asarray(a), np.asarray(b)
    if ulps == 0:
        assert _bitwise(a, b)
        return
    assert a.dtype == b.dtype == np.float32
    bound = ulps * np.finfo(np.float32).eps * np.abs(a).max()
    np.testing.assert_allclose(b, a, rtol=0, atol=bound)


def _assert_states_match(st_ref, st_sharded, n_entities, placements, ulps):
    """Packed blocks must be bitwise the single-device state; a split
    block (entity-padding lanes appended by the resident coordinate:
    only the real lanes compare) within ``ulps``."""
    assert len(st_ref) == len(st_sharded) == len(placements)
    for a, b, n, p in zip(st_ref, st_sharded, n_entities, placements):
        b = np.asarray(b)
        assert b.shape[0] >= n
        _assert_parity(a, b[:n], ulps if p[0] == "split" else 0)


def _assert_scores_match(ref, got, blocks, placements, ulps):
    """Rows of packed blocks must score bitwise as on a single device;
    rows of split blocks within ``ulps``."""
    ref, got = np.asarray(ref), np.asarray(got)
    assert ref.shape == got.shape
    split = np.zeros(ref.shape[0], bool)
    for block, p in zip(blocks, placements):
        rows = np.asarray(block.row_index).ravel()
        split[rows[rows < ref.shape[0]]] = p[0] == "split"
    assert split.any() and not split.all()
    _assert_parity(ref[~split], got[~split], 0)
    _assert_parity(ref[split], got[split], ulps)


# ---------------------------------------------------------------------------
# The shard plan itself
# ---------------------------------------------------------------------------

class TestBucketShardPlan:
    def test_plan_mixes_split_and_packed(self):
        keys, X, y, w = _zipf_data(seed=3, n_entities=120)
        ds = build_random_effect_dataset(keys, X, y, w, device=False)
        plan = plan_bucket_shards(ds.blocks, 8)
        assert len(plan.placements) == len(ds.blocks)
        assert plan.n_split >= 1, "head bucket should split"
        assert plan.n_packed >= 1, "tail buckets should pack"
        assert plan.imbalance_ratio >= 1.0
        # split blocks have at least one entity lane per device
        for p, b in zip(plan.placements, ds.blocks):
            if p[0] == "split":
                assert b.n_entities >= 8
            else:
                assert 0 <= p[1] < 8

    def test_plan_deterministic(self):
        keys, X, y, w = _zipf_data(seed=5)
        ds = build_random_effect_dataset(keys, X, y, w, device=False)
        p1 = plan_bucket_shards(ds.blocks, 8, split_factor=0.5)
        p2 = plan_bucket_shards(ds.blocks, 8, split_factor=0.5)
        assert p1 == p2

    def test_single_device_packs_everything(self):
        keys, X, y, w = _zipf_data(seed=3)
        ds = build_random_effect_dataset(keys, X, y, w, device=False)
        plan = plan_bucket_shards(ds.blocks, 1)
        assert plan.n_split == 0
        assert all(p == ("pack", 0) for p in plan.placements)

    def test_rejects_bad_device_count(self):
        with pytest.raises(ValueError, match="n_devices"):
            plan_bucket_shards([], 0)


# ---------------------------------------------------------------------------
# Sharded-vs-single parity (packed blocks bitwise, split blocks 4 ulp):
# resident and out-of-core
# ---------------------------------------------------------------------------

class TestShardedParity:
    @pytest.mark.parametrize("name,shape", COORD_GRID)
    def test_resident_bitwise(self, name, shape, eight_devices):
        keys, X, y, w = _zipf_data(**shape)
        mesh = data_mesh(eight_devices)
        ref = RandomEffectCoordinate(
            name, build_random_effect_dataset(keys, X, y, w),
            "logistic", _config(), reg_weight=0.7,
        )
        sharded = ShardedBucketRandomEffectCoordinate(
            name, build_random_effect_dataset(keys, X, y, w, device=False),
            mesh, "logistic", _config(), reg_weight=0.7,
        )
        assert sharded.plan.n_split >= 1 and sharded.plan.n_packed >= 1
        offsets = jnp.asarray(
            np.random.default_rng(0).normal(size=len(y)).astype(np.float32)
        )
        state_ulps, score_ulps, _ = PARITY_ULPS[name]
        n_entities = [b.n_entities for b in ref.dataset.blocks]
        placements = sharded.plan.placements
        st_ref = ref.train(offsets)
        st_sh = sharded.train(offsets)
        _assert_states_match(
            st_ref, st_sh, n_entities, placements, state_ulps
        )
        blocks = ref.dataset.blocks
        _assert_scores_match(
            ref.score(st_ref), sharded.score(st_sh), blocks, placements,
            score_ulps)
        # warm-started second round: same contract
        st_ref2 = ref.train(offsets, warm_state=st_ref)
        st_sh2 = sharded.train(offsets, warm_state=st_sh)
        _assert_states_match(
            st_ref2, st_sh2, n_entities, placements, state_ulps
        )
        _assert_scores_match(
            ref.score(st_ref2), sharded.score(st_sh2), blocks, placements,
            score_ulps)

    @pytest.mark.parametrize("name,shape", COORD_GRID)
    def test_out_of_core_bitwise(self, name, shape, eight_devices):
        keys, X, y, w = _zipf_data(**shape)
        mesh = data_mesh(eight_devices)
        ds = build_random_effect_dataset(keys, X, y, w, device=False)
        budget = 1 << 20  # far below the dataset: several pass groups

        def coord(m):
            return OutOfCoreRandomEffectCoordinate(
                name, ds, "logistic", _config(), reg_weight=0.7,
                device_budget_bytes=budget, mesh=m,
            )

        single, sharded = coord(None), coord(mesh)
        assert sharded.bucket_plan is not None
        offsets = jnp.asarray(
            np.random.default_rng(1).normal(size=len(y)).astype(np.float32)
        )
        state_ulps, _, score_ulps = PARITY_ULPS[name]
        n_entities = [b.n_entities for b in ds.blocks]
        placements = sharded.bucket_plan.placements
        st_s = single.train(offsets)
        st_m = sharded.train(offsets)
        _assert_states_match(st_s, st_m, n_entities, placements, state_ulps)
        _assert_scores_match(
            single.score(st_s), sharded.score(st_m), ds.blocks, placements,
            score_ulps)
        # warm round
        st_s2 = single.train(offsets, warm_state=st_s)
        st_m2 = sharded.train(offsets, warm_state=st_m)
        _assert_states_match(
            st_s2, st_m2, n_entities, placements, state_ulps
        )

    def test_sharded_coordinate_finalize_exact_entities(self, eight_devices):
        keys, X, y, w = _zipf_data(seed=3, n_entities=120)
        mesh = data_mesh(eight_devices)
        sharded = ShardedBucketRandomEffectCoordinate(
            "re", build_random_effect_dataset(keys, X, y, w, device=False),
            mesh, "logistic", _config(), reg_weight=0.7, entity_key="uid",
        )
        assert sharded.plan.n_split >= 1  # padded lanes exist to drop
        model = sharded.finalize(
            sharded.train(jnp.zeros(len(y), jnp.float32))
        )
        assert model.n_entities == 120  # padding lanes dropped

    def test_shard_imbalance_gauge_set(self, eight_devices):
        keys, X, y, w = _zipf_data(seed=3)
        mesh = data_mesh(eight_devices)
        with telemetry_mod.Telemetry(enabled=True, sinks=[]) as tel:
            sharded = ShardedBucketRandomEffectCoordinate(
                "re",
                build_random_effect_dataset(keys, X, y, w, device=False),
                mesh, "logistic", _config(),
            )
            g = tel.gauge("game_shard_imbalance_ratio").value
        assert g == sharded.plan.imbalance_ratio >= 1.0


# ---------------------------------------------------------------------------
# Cost-model repacker: deterministic plan, numerical-only model parity
# ---------------------------------------------------------------------------

class TestRepacker:
    def _counts(self, seed=7, n=400):
        rng = np.random.default_rng(seed)
        rows = np.clip(rng.zipf(1.6, size=n), 1, 200).astype(np.int64)
        cols = rng.integers(1, 30, size=n).astype(np.int64)
        return rows, cols

    def test_plan_deterministic(self):
        rows, cols = self._counts()
        p1 = plan_entity_buckets(rows, cols, program_budget=8, seed=0)
        p2 = plan_entity_buckets(rows, cols, program_budget=8, seed=0)
        assert _bitwise(p1.shapes, p2.shapes)
        assert _bitwise(p1.assignment, p2.assignment)
        assert p1.padded_flops == p2.padded_flops
        assert p1.exact_flops == p2.exact_flops

    def test_budget_and_fit_invariants(self):
        rows, cols = self._counts(seed=11)
        for budget in (1, 4, 16):
            plan = plan_entity_buckets(rows, cols, program_budget=budget)
            assert 1 <= len(plan.shapes) <= budget
            assert plan.padded_flops >= plan.exact_flops
            assert plan.assignment.shape == rows.shape
            assert plan.assignment.min() >= 0
            assert plan.assignment.max() < len(plan.shapes)
            # every entity fits the bucket it was assigned
            assert np.all(plan.shapes[plan.assignment, 0] >= rows)
            assert np.all(plan.shapes[plan.assignment, 1] >= cols)

    def test_more_budget_never_pads_more(self):
        # greedy agglomeration: a larger budget stops the merge sequence
        # earlier, and every merge only adds padding.
        rows, cols = self._counts(seed=13)
        padded = [
            plan_entity_buckets(rows, cols, program_budget=b).padded_flops
            for b in (2, 4, 8, 16)
        ]
        assert padded == sorted(padded, reverse=True)

    def test_dataset_block_count_within_budget(self):
        keys, X, y, w = _zipf_data(seed=3)
        ds = build_random_effect_dataset(
            keys, X, y, w, device=False, repack="cost_model",
            program_budget=4,
        )
        assert 1 <= len(ds.blocks) <= 4

    def test_dataset_build_deterministic(self):
        keys, X, y, w = _zipf_data(seed=5)
        kw = dict(device=False, repack="cost_model", program_budget=6)
        a = build_random_effect_dataset(keys, X, y, w, **kw)
        b = build_random_effect_dataset(keys, X, y, w, **kw)
        assert len(a.blocks) == len(b.blocks)
        for ba, bb in zip(a.blocks, b.blocks):
            for la, lb in zip(jax.tree.leaves(ba), jax.tree.leaves(bb)):
                assert _bitwise(la, lb)

    def test_repacked_model_matches_numerically(self):
        # The repacker changes realized block shapes, and f32 reductions
        # are not bitwise-stable under padding-length changes — so the
        # contract is NUMERICAL equivalence, not bitwise (contrast the
        # shard plan above).
        keys, X, y, w = _zipf_data(seed=3)
        offsets = jnp.asarray(
            np.random.default_rng(2).normal(size=len(y)).astype(np.float32)
        )
        scores = {}
        for repack in ("geometric", "cost_model"):
            ds = build_random_effect_dataset(
                keys, X, y, w, repack=repack, program_budget=8
            )
            coord = RandomEffectCoordinate(
                "re", ds, "logistic", _config(), reg_weight=0.7
            )
            scores[repack] = np.asarray(coord.score(coord.train(offsets)))
        np.testing.assert_allclose(
            scores["geometric"], scores["cost_model"], atol=1e-4
        )

    def test_padding_gauge_and_bad_policy(self):
        keys, X, y, w = _zipf_data(seed=5)
        with telemetry_mod.Telemetry(enabled=True, sinks=[]) as tel:
            build_random_effect_dataset(
                keys, X, y, w, device=False, repack="cost_model",
                program_budget=8,
            )
            ratio = tel.gauge("game_bucket_padding_ratio").value
        assert ratio >= 1.0
        with pytest.raises(ValueError, match="repack"):
            build_random_effect_dataset(
                keys, X, y, w, device=False, repack="bogus"
            )


# ---------------------------------------------------------------------------
# Pipelined coordinate descent: bitwise the serial schedule
# ---------------------------------------------------------------------------

def _two_coordinate_problem():
    """Two random effects over the same rows — one resident, one
    out-of-core (the prestage beneficiary) — so the pipelined schedule
    has real host work to overlap."""
    rng = np.random.default_rng(17)
    n, d = 400, 4
    X = sp.random(n, d, density=0.6, random_state=4, format="csr",
                  dtype=np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    w = np.ones(n, np.float32)
    users = [f"u{u}" for u in rng.integers(12, size=n)]
    items = [f"i{i}" for i in rng.integers(25, size=n)]
    resident = RandomEffectCoordinate(
        "per_item", build_random_effect_dataset(items, X, y, w),
        "logistic", _config(), reg_weight=0.5,
    )
    ooc = OutOfCoreRandomEffectCoordinate(
        "per_user",
        build_random_effect_dataset(users, X, y, w, device=False),
        "logistic", _config(), reg_weight=0.5,
        device_budget_bytes=1 << 16,  # several pass groups
    )
    return [resident, ooc], n


class TestPipelinedDescent:
    def test_trajectory_bitwise_identical_to_serial(self):
        def run(pipeline):
            coords, n = _two_coordinate_problem()
            return CoordinateDescent(coords, pipeline=pipeline).run(
                jnp.zeros(n, jnp.float32), n_iterations=3
            )

        serial, piped = run(False), run(True)
        for name in serial.states:
            assert _bitwise(serial.scores[name], piped.scores[name])
            for a, b in zip(serial.states[name], piped.states[name]):
                assert _bitwise(a, b)
        assert len(serial.history) == len(piped.history)
        for es, ep in zip(serial.history, piped.history):
            assert es["iteration"] == ep["iteration"]
            assert es["coordinate"] == ep["coordinate"]
            assert _bitwise(es["score_norm"], ep["score_norm"])

    def test_overlap_counter_accumulates(self):
        coords, n = _two_coordinate_problem()
        with telemetry_mod.Telemetry(enabled=True, sinks=[]) as tel:
            CoordinateDescent(coords, pipeline=True).run(
                jnp.zeros(n, jnp.float32), n_iterations=2
            )
            overlap = tel.counter("game_coordinate_overlap_seconds").value
        assert overlap > 0.0

    def test_estimator_pipeline_flag_bitwise(self):
        from photon_ml_tpu.game.estimator import (
            FixedEffectCoordinateConfig,
            GameEstimator,
            RandomEffectCoordinateConfig,
        )

        rng = np.random.default_rng(13)
        n, n_users = 300, 10
        Xg = rng.normal(size=(n, 3)).astype(np.float32)
        users = rng.integers(n_users, size=n)
        margin = 1.3 * Xg[:, 0] - 0.7 * Xg[:, 1]
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(
            np.float32
        )
        shards = {
            "global": sp.csr_matrix(Xg),
            "userFeatures": sp.csr_matrix(np.ones((n, 1), np.float32)),
        }
        ids = {"userId": np.array([f"u{u}" for u in users])}
        configs = {
            "fixed": FixedEffectCoordinateConfig(
                feature_shard="global", optimization=_config(),
                reg_weight=0.5,
            ),
            "per_user": RandomEffectCoordinateConfig(
                feature_shard="userFeatures", entity_key="userId",
                optimization=_config(), reg_weight=0.5,
                device_budget_bytes=1 << 14,  # out-of-core: prestage real
            ),
        }

        def fit(pipeline):
            return GameEstimator(
                "logistic", configs, n_iterations=2, pipeline=pipeline
            ).fit(shards, ids, y)

        (m_serial, _), (m_piped, _) = fit(False), fit(True)
        assert _bitwise(
            m_serial["fixed"].model.coefficients.means,
            m_piped["fixed"].model.coefficients.means,
        )
        cs, cp = (m["per_user"].coefficients for m in (m_serial, m_piped))
        assert set(cs) == set(cp)
        for k in cs:
            assert _bitwise(cs[k][1], cp[k][1])


# ---------------------------------------------------------------------------
# Chaos seams: kill at the dispatch/plan sites, resume bitwise
# ---------------------------------------------------------------------------

class TestChaosSites:
    def test_bucket_shard_kill_midupdate_retry_bitwise_resident(
        self, eight_devices
    ):
        keys, X, y, w = _zipf_data(seed=3)
        mesh = data_mesh(eight_devices)
        sharded = ShardedBucketRandomEffectCoordinate(
            "re", build_random_effect_dataset(keys, X, y, w, device=False),
            mesh, "logistic", _config(), reg_weight=0.7,
        )
        offsets = jnp.asarray(
            np.random.default_rng(3).normal(size=len(y)).astype(np.float32)
        )
        clean = sharded.train(offsets)
        # kill at the SECOND dispatch group: the first group's device
        # programs are already in flight when the update aborts.
        plan = chaos.FaultPlan([
            chaos.FaultSpec(site="game.bucket_shard", at=1),
        ])
        with plan:
            with pytest.raises(chaos.InjectedFault):
                sharded.train(offsets)
            retried = sharded.train(offsets)
        assert len(plan.fired_at("game.bucket_shard")) == 1
        for a, b in zip(clean, retried):
            assert _bitwise(a, b)

    def test_bucket_shard_kill_retry_bitwise_out_of_core(
        self, eight_devices
    ):
        keys, X, y, w = _zipf_data(seed=5)
        mesh = data_mesh(eight_devices)
        ooc = OutOfCoreRandomEffectCoordinate(
            "re", build_random_effect_dataset(keys, X, y, w, device=False),
            "logistic", _config(), reg_weight=0.7,
            device_budget_bytes=1 << 20, mesh=mesh,
        )
        offsets = jnp.zeros(len(y), jnp.float32)
        clean = ooc.train(offsets)
        plan = chaos.FaultPlan([
            chaos.FaultSpec(site="game.bucket_shard", at=0),
        ])
        with plan:
            with pytest.raises(chaos.InjectedFault):
                ooc.train(offsets)
            retried = ooc.train(offsets)
        assert len(plan.fired_at("game.bucket_shard")) == 1
        for a, b in zip(clean, retried):
            assert _bitwise(a, b)

    def test_repack_kill_rebuild_bitwise(self):
        keys, X, y, w = _zipf_data(seed=9, n_entities=30, d=6)
        kw = dict(device=False, repack="cost_model", program_budget=6)
        clean = build_random_effect_dataset(keys, X, y, w, **kw)
        plan = chaos.FaultPlan([
            chaos.FaultSpec(site="game.repack", at=0),
        ])
        with plan:
            with pytest.raises(chaos.InjectedFault):
                build_random_effect_dataset(keys, X, y, w, **kw)
            rebuilt = build_random_effect_dataset(keys, X, y, w, **kw)
        fired = plan.fired_at("game.repack")
        assert len(fired) == 1 and fired[0]["n_entities"] == 30
        assert len(clean.blocks) == len(rebuilt.blocks)
        for ba, bb in zip(clean.blocks, rebuilt.blocks):
            for la, lb in zip(jax.tree.leaves(ba), jax.tree.leaves(bb)):
                assert _bitwise(la, lb)

    def test_estimator_survives_bucket_shard_kill(self, eight_devices):
        # the full kill/resume loop: a watchdog retry after a fault in
        # the sharded dispatch must land on the unfaulted model bitwise.
        from photon_ml_tpu.game.estimator import (
            FixedEffectCoordinateConfig,
            GameEstimator,
            RandomEffectCoordinateConfig,
        )

        rng = np.random.default_rng(23)
        n, n_users = 240, 9
        Xg = rng.normal(size=(n, 3)).astype(np.float32)
        users = rng.integers(n_users, size=n)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        shards = {
            "global": sp.csr_matrix(Xg),
            "userFeatures": sp.csr_matrix(np.ones((n, 1), np.float32)),
        }
        ids = {"userId": np.array([f"u{u}" for u in users])}
        configs = {
            "fixed": FixedEffectCoordinateConfig(
                feature_shard="global", optimization=_config(),
                reg_weight=0.5,
            ),
            "per_user": RandomEffectCoordinateConfig(
                feature_shard="userFeatures", entity_key="userId",
                optimization=_config(), reg_weight=0.5,
            ),
        }
        mesh = data_mesh(eight_devices)

        def fit():
            return GameEstimator(
                "logistic", configs, n_iterations=2, mesh=mesh
            ).fit(shards, ids, y)

        model_full, _ = fit()
        plan = chaos.FaultPlan([
            chaos.FaultSpec(site="game.bucket_shard", at=0),
        ])
        stats = RetryStats()
        with plan:
            model_res, _ = run_with_retries(
                lambda a: fit(), RetryPolicy(max_retries=1),
                sleep=lambda s: None, stats=stats,
            )
        assert stats.retries == 1
        assert _bitwise(
            model_full["fixed"].model.coefficients.means,
            model_res["fixed"].model.coefficients.means,
        )
        cf = model_full["per_user"].coefficients
        cr = model_res["per_user"].coefficients
        assert set(cf) == set(cr)
        for k in cf:
            assert _bitwise(cf[k][1], cr[k][1])
