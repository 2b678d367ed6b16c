"""A GAME fit with TWO random effects and an active-row cap against the plain
float64 reference with any number of random effects (benchmarks/
reference_game_multi.py), at a small size on the CPU: every coordinate update
of a fit checked as an answer, as the benchmark's cell
``game_cd_fit_user_item`` checks it on the chip; and what the cell's shape
forced of the program: passive rows stored flat, programs and spans that
carry their coordinate's name."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from benchmarks import reference_game, reference_game_multi
from benchmarks.datagen import game_ml20m_multi
from benchmarks.metrics import _game
from benchmarks.windows import cd_fit, cd_fit_multi
from photon_ml_tpu import telemetry
from photon_ml_tpu.game import data as game_data
from photon_ml_tpu.game.data import build_random_effect_dataset
from photon_ml_tpu.game.estimator import GameEstimator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CAP = 32

#: What float32 on the CPU reads against float64 at this size, with room:
#: sums of 9,000 rows in f32 are good to ~1e-7 of their size, a solve run
#: to tolerance 1e-7 leaves a gradient of that order beside the one at
#: zero, and scores are single products.  Each is 20-500x under what the
#: bfloat16 control reads (2.5e-3 on gradients and scores, 3e-5 on values).
LIMITS = {
    "value_gap": 1e-6, "fixed_grad_gap": 1e-5,
    "user_grad_gap_max": 1e-4, "user_grad_gap_mean": 1e-5,
    "movie_grad_gap_max": 1e-4, "movie_grad_gap_mean": 1e-5,
    "offsets_gap": 1e-5, "scores_gap": 1e-5,
    # every update descends; the last of six by about a thousandth
    "inv_descent": 1e5,
}


def small_cfg(**over):
    with open(os.path.join(
            ROOT, "benchmarks/configs/"
            "game_logistic_user_item_re_ml20m.json")) as f:
        cfg = json.load(f)
    effects = [dict(e) for e in cfg["random_effects"]]
    effects[1]["max_rows_per_entity"] = CAP
    return {**cfg, "n_rows": 9000, "n_users": 150, "n_movies": 200,
            "random_effects": effects, **over}


@pytest.fixture(scope="module")
def host():
    return game_ml20m_multi.generate(small_cfg(), 5)


@pytest.fixture(scope="module")
def fitted(host):
    """One fit through the estimator with a recorder around each of the
    three coordinates, and the layer spans it made."""
    cfg = small_cfg()
    before = {r["id"] for r in telemetry.layer_spans()}
    shards, ids = game_ml20m_multi.shards(host)
    est = GameEstimator(cfg["task"], cd_fit_multi._coordinate_configs(cfg),
                        n_iterations=cfg["cd_iterations"],
                        device_metrics=True)
    coordinates = est.build_coordinates(shards, ids, host["labels"])
    log = []
    est.fit_coordinates(
        [cd_fit.Recorder(c, log) for c in coordinates], host["labels"])

    class Run:
        state = {"coordinates": coordinates, "shape": {
            "effects": cd_fit_multi.effect_shapes(cfg, host)}}

    readers = cd_fit_multi._coefficient_readers(Run)
    updates = [cd_fit.Update(r, readers.get(r["coordinate"])) for r in log]
    ref = reference_game_multi.MultiReference(
        host, cfg["fixed_effect"]["reg_weight"], cfg["random_effects"])
    spans = [r for r in telemetry.layer_spans() if r["id"] not in before]
    return cfg, updates, ref, coordinates, spans


class TestFitAgainstReference:
    def test_updates_in_upstreams_order(self, fitted):
        assert [u.coordinate for u in fitted[1]] == [
            "fixed", "per_user", "per_movie"] * 2

    def test_the_cap_bites(self, fitted, host):
        movie = fitted[2].effects["per_movie"]
        assert (host["movie_counts"] > CAP).sum() >= 20
        assert movie.active is not None and 0 < movie.active.sum() < host[
            "n_rows"]
        assert fitted[2].effects["per_user"].active is None

    def test_every_compared_number_within_its_cpu_limit(self, fitted):
        cfg, updates, ref, *_ = fitted
        _l, caps, roles = cd_fit_multi._judging(cfg)
        correct, numbers, (per_update, _s) = cd_fit_multi.judge(
            ref, updates, LIMITS, caps, roles)
        assert correct, {k: v for k, v in numbers.items()
                         if not v["value"] <= v["limit"]}
        assert set(numbers) == set(cfg["limits"]) | {"iters_over_cap"}
        # each update minimises its own rows' objective, so each descends
        assert all(0 < r["inv_descent"] < 1e5 for r in per_update)

    def test_the_cells_limits_hold_and_control_and_faults_fail(self, fitted):
        cfg, updates, ref, *_ = fitted
        how = cd_fit_multi._judging(cfg)
        assert cd_fit_multi.judge(ref, updates, *how)[0]

        class Run:
            state = {"host": ref.host}

        Run.cfg, Run.seed = cfg, 5
        seen = []
        for name, wrong in cd_fit_multi.wrong_answers(Run, ref, updates):
            seen.append(name)
            assert not cd_fit_multi.judge(ref, wrong, *how)[0], name
            if name == "bf16":  # and by the CPU limits, by a wide margin
                got = cd_fit_multi.judge(ref, wrong, LIMITS, *how[1:])[1]
                assert got["scores_gap"]["value"] > 20 * LIMITS["scores_gap"]
        assert seen == [
            "bf16", "half_batch", "padding_rows_counted",
            "user_block_dropped", "movie_block_dropped",
            "movie_passive_rows_unscored", "movie_cap_ignored",
            "offsets_from_one_coordinate", "state_unchanged"]

    def test_offsets_are_the_sum_of_the_two_other_coordinates(self, fitted):
        _cfg, updates, ref, *_ = fitted
        last = updates[-1]  # per_movie, second iteration
        others = ref.fixed_scores(updates[3].coef) + ref.effect_scores(
            "per_user", updates[4].coef)
        assert cd_fit._rel(last.offsets - others, others) < 1e-5

    def test_passive_rows_are_scored_and_not_trained_on(self, fitted):
        _cfg, updates, ref, *_ = fitted
        up, eff = updates[-1], ref.effects["per_movie"]
        passive = ~eff.active
        want = ref.effect_scores("per_movie", up.coef)
        assert np.abs(want[passive]).min() > 0
        np.testing.assert_allclose(up.scores[passive], want[passive],
                                   rtol=1e-4, atol=1e-5)
        # trained on the active rows alone: the gradient over them is at
        # the solver's tolerance, the gradient over all rows is not
        capped = np.flatnonzero(np.bincount(eff.entity[passive]))
        g_active = ref.effect_grad("per_movie", up.coef, up.offsets)
        eff.active = None
        try:
            g_all = ref.effect_grad("per_movie", up.coef, up.offsets)
        finally:
            eff.active = ~passive
        assert np.linalg.norm(g_active[capped], axis=1).max() < 1e-3
        assert np.linalg.norm(g_all[capped], axis=1).min() > 0.1


def test_one_effect_and_no_cap_is_the_accepted_reference_to_the_bit(host):
    cfg = small_cfg()
    user = cfg["random_effects"][0]
    new = reference_game_multi.MultiReference(host, 10.0, [user])
    old = reference_game.GameReference(host, 10.0, user["reg_weight"])
    rng = np.random.default_rng(1)
    beta = 0.1 * rng.standard_normal(new.cols["n_fixed"])
    gamma = 0.1 * rng.standard_normal((host["n_users"], 21))
    fixed, random = old.scores(beta, gamma)
    assert np.array_equal(new.fixed_scores(beta), fixed)
    assert np.array_equal(new.effect_scores("per_user", gamma), random)
    assert np.array_equal(new.effect_grad("per_user", gamma, fixed),
                          old.random_grad(gamma, fixed))
    assert new.objective(beta, {"per_user": gamma}, fixed + random) == (
        old.full_objective(beta, gamma, fixed, random))
    v_new, g_new = new.fixed_value_and_grad(beta, random)
    v_old, g_old = old.fixed_value_and_grad(beta, random)
    assert v_new == v_old and np.array_equal(g_new, g_old)
    assert np.array_equal(
        new.effect_scores("per_user", gamma, "bf16"),
        old.scores(None, gamma, "bf16")[1])


def test_the_references_cap_is_the_programs_subset(host):
    """Written twice, independently: the rows the program trains on are the
    rows the reference calls active."""
    shards, ids = game_ml20m_multi.shards(host)
    n = host["n_rows"]
    ds = build_random_effect_dataset(
        ids["movieId"], shards["per_movie"], host["labels"],
        np.ones(n, np.float32), max_rows_per_entity=CAP, device=False)
    active = reference_game_multi.active_rows(
        host["movie"], host["n_movies"], CAP)
    trained = np.concatenate([
        np.asarray(b.row_index)[np.asarray(b.row_index) < n]
        for b in ds.blocks])
    scored = np.concatenate([
        np.asarray(p.row_index)[:p.n_rows]
        for p in ds.passive_blocks if p is not None])
    assert np.array_equal(np.sort(trained), np.flatnonzero(active))
    assert np.array_equal(np.sort(scored), np.flatnonzero(~active))
    assert (ds.rows_active, ds.rows_passive) == (
        int(active.sum()), int((~active).sum()))
    # every capped movie's rows are partitioned: cap active, the rest passive
    for p, b, ids_b in zip(ds.passive_blocks, ds.blocks, ds.entity_ids):
        if p is None:
            continue
        assert np.all(np.asarray(p.row_index)[p.n_rows:] == n)
        per_lane = np.bincount(np.asarray(p.lanes)[np.asarray(p.slot)],
                               minlength=b.n_entities)
        per_lane[np.asarray(p.lanes)[-1]] -= len(p.slot) - p.n_rows
        for lane in np.flatnonzero(per_lane):
            movie = int(ids_b[lane])
            assert per_lane[lane] == host["movie_counts"][movie] - CAP
            assert (np.asarray(b.weights)[lane] > 0).sum() == CAP
        assert np.all(np.diff(np.asarray(p.slot)) >= 0)


def _tiled_bytes(shape, tile=(8, 128)):
    """Bytes of a 32-bit array under the TPU's tiling: the two minor axes
    padded to (8, 128); a vector to 1,024 elements."""
    if len(shape) == 1:
        return 4 * -(-shape[0] // 1024) * 1024
    *lead, sub, lanes = shape
    return 4 * int(np.prod(lead, dtype=np.int64)) * (
        -(-sub // tile[0]) * tile[0]) * (-(-lanes // tile[1]) * tile[1])


def test_passive_rows_are_stored_at_about_their_real_size(monkeypatch):
    """One entity ten times over the cap and twenty just over it, 9 dense
    columns: flat, the passive rows take what their real rows take under
    the TPU's tiling (16 sublanes a row and an index each); lane-aligned and
    padded to the heaviest entity, as they were stored, over five times
    that."""
    monkeypatch.setattr(game_data, "_device_tile", lambda: (8, 128))
    cap, rng = 64, np.random.default_rng(0)
    counts = [10 * cap] + [cap + int(k) for k in rng.integers(1, 9, 20)]
    keys = np.repeat(np.arange(len(counts)), counts)
    n = len(keys)
    X = sp.csr_matrix(rng.standard_normal((n, 9)).astype(np.float32))
    ds = build_random_effect_dataset(
        keys, X, np.zeros(n, np.float32), np.ones(n, np.float32),
        max_rows_per_entity=cap)
    (block,), (rows,) = ds.blocks, ds.passive_blocks
    assert rows.n_rows == n - cap * len(counts) == ds.rows_passive
    assert rows.x_minor == "r" and rows.X.shape == (9, len(rows.slot))
    stored = sum(_tiled_bytes(np.shape(x)) for x in (
        rows.X, rows.row_index, rows.slot, rows.lanes))
    real = rows.n_rows * 4 * (16 + 2)  # 9 columns pad to 16 sublanes
    assert real <= stored <= 1.5 * real
    aligned = rows.lane_aligned(block, n)
    assert aligned.X.shape == (len(counts), 9 * cap, 9)
    as_it_was = _tiled_bytes((len(counts), 9, 9 * cap)) + 3 * _tiled_bytes(
        (len(counts), 9 * cap))
    assert as_it_was > 5 * real
    # the same rows either way
    coefs = rng.standard_normal((len(counts), 9)).astype(np.float32)
    flat = np.asarray(rows.scores(jnp.asarray(coefs)))
    assert np.all(flat[rows.n_rows:] == 0)
    flat = flat[:rows.n_rows]
    dense = np.einsum("erd,ed->er", np.asarray(aligned.X), coefs)
    index = np.asarray(aligned.row_index)
    np.testing.assert_allclose(
        flat[np.argsort(np.asarray(rows.row_index)[:rows.n_rows])],
        dense[index < n][np.argsort(index[index < n])], rtol=1e-5, atol=1e-6)


class TestNamesAndSpans:
    def test_programs_carry_their_coordinates_name(self, fitted):
        coordinates = fitted[3]
        names = {}
        for c in coordinates[1:]:
            state = [jnp.zeros((b.n_entities, b.block_dim), jnp.float32)
                     for b in c.dataset.blocks]
            text = c._score_all_jit.lower(
                c.dataset.blocks, c.dataset.passive_blocks, state).as_text()
            names[c.name] = (c._train_all_jit.__name__,
                             text.split("module @")[1].split()[0])
        assert names == {
            "per_user": ("random_effect_train_per_user",
                         "jit_random_effect_score_per_user"),
            "per_movie": ("random_effect_train_per_movie",
                          "jit_random_effect_score_per_movie")}

    def test_the_accepted_readers_substrings_still_match(self, fitted):
        class Run:
            state = {"module_seconds": {
                "jit_random_effect_train_per_user": [(0.0, 4.0)],
                "jit_random_effect_score_per_movie": [(5.0, 1.0)],
                "jit_fixed_effect_train": [(6.0, 2.0)],
                "jit_device_auc": [(8.0, 0.5)]}}

        assert _game.program_seconds(Run, "random_effect_") == 5.0
        assert _game.program_seconds(Run, "fixed_effect_") == 2.0
        from benchmarks.metrics import _multi

        Run.state["shape"] = {"effects": {
            "per_user": {"role": "user"}, "per_movie": {"role": "movie"}}}
        assert _multi.program_seconds(Run, "user") == 4.0
        assert _multi.program_seconds(Run, "movie") == 1.0
        assert _multi.program_seconds(
            Run, "movie", "random_effect_train_") is None

    def test_group_and_place_say_whose_and_how_many(self, fitted, host):
        spans = fitted[4]
        movie_active = int(np.minimum(host["movie_counts"], CAP).sum())
        for name in ("game.group", "game.place"):
            got = {r["attrs"]["coordinate"]: r["attrs"]
                   for r in spans if r["name"] == name}
            assert set(got) == {"per_user", "per_movie"}
            assert got["per_user"]["entities"] == host["n_users"]
            assert got["per_user"]["rows_passive"] == 0
            assert got["per_movie"]["entities"] == host["n_movies"]
            assert got["per_movie"]["rows_active"] == movie_active
            assert got["per_movie"]["rows_passive"] == (
                host["n_rows"] - movie_active)

    def test_train_and_score_spans_carry_the_coordinate(self, fitted, host):
        spans = fitted[4]
        trains = [r for r in spans if r["name"] == "coordinate.train"]
        assert [r["attrs"]["coordinate"] for r in trains] == [
            "fixed", "per_user", "per_movie"] * 2
        assert all("buckets" in r["attrs"] for r in trains
                   if r["attrs"]["kind"] == "random")
        scores = {r["attrs"]["coordinate"]: r["attrs"]["rows_passive"]
                  for r in spans if r["name"] == "coordinate.score"}
        assert scores["fixed"] == 0 and scores["per_user"] == 0
        assert scores["per_movie"] == host["n_rows"] - int(
            np.minimum(host["movie_counts"], CAP).sum())

    def test_a_gauge_of_passive_rows_per_coordinate(self, host):
        shards, ids = game_ml20m_multi.shards(host)
        cfg = small_cfg()
        with telemetry.Telemetry(enabled=True, sinks=[]) as hub:
            GameEstimator(
                cfg["task"], cd_fit_multi._coordinate_configs(cfg),
                n_iterations=1,
            ).build_coordinates(shards, ids, host["labels"])
            gauges = hub.metrics.snapshot()["gauges"]
        assert gauges["game_re_per_user_passive_rows"] == 0
        assert gauges["game_re_per_movie_passive_rows"] == (
            host["n_rows"] - int(np.minimum(host["movie_counts"], CAP).sum()))
        assert gauges["game_re_bucket_count"] >= 1
