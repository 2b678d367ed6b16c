"""The GAME fit against the plain float64 reference (benchmarks/
reference_game.py), at a small size on the CPU: every coordinate update of a
fit checked as an answer, as the benchmark's cell ``game_cd_fit`` checks it
on the chip; and what this PR's grouping, block layout and spans promise."""

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmarks import reference_game
from benchmarks.datagen import game_ml20m
from benchmarks.windows import cd_fit
from photon_ml_tpu import telemetry
from photon_ml_tpu.game import data as game_data
from photon_ml_tpu.game.data import build_random_effect_dataset
from photon_ml_tpu.game.estimator import GameEstimator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_cfg(**over):
    with open(os.path.join(
            ROOT, "benchmarks/configs/game_logistic_user_re_ml20m.json")) as f:
        cfg = json.load(f)
    return {**cfg, "n_rows": 9000, "n_users": 150, "n_movies": 200, **over}


@pytest.fixture(scope="module")
def host():
    return game_ml20m.generate(small_cfg(), 5)


def fit(cfg, host, **random_over):
    """One fit through the estimator with a recorder around each
    coordinate; returns (updates, reference, coordinates)."""
    shards, ids = game_ml20m.shards(host)
    configs = cd_fit._coordinate_configs(cfg)
    name = cfg["random_effect"]["name"]
    configs[name] = dataclasses.replace(configs[name], **random_over)
    est = GameEstimator(cfg["task"], configs,
                        n_iterations=cfg["cd_iterations"],
                        device_metrics=True)
    coordinates = est.build_coordinates(shards, ids, host["labels"])
    log = []
    est.fit_coordinates(
        [cd_fit.Recorder(c, log) for c in coordinates], host["labels"])

    class Run:
        state = {"coordinates": coordinates, "shape": host}

    gamma_of = cd_fit._gamma_reader(Run)
    ref = reference_game.GameReference(
        host, cfg["fixed_effect"]["reg_weight"],
        cfg["random_effect"]["reg_weight"])
    return [cd_fit.Update(r, gamma_of) for r in log], ref, coordinates


@pytest.fixture(scope="module", params=[2.0, 4.0], ids=["pow2", "pow4"])
def fitted(request, host):
    cfg = small_cfg()
    updates, ref, coordinates = fit(
        cfg, host, bucket_growth=request.param)
    return cfg, updates, ref, coordinates, cd_fit.compare(ref, updates)


class TestFitAgainstReference:
    """Logistic, two bucket ladders; float32 on the CPU against float64."""

    def test_updates_in_order(self, fitted):
        _cfg, updates, *_ = fitted
        assert [u.coordinate for u in updates] == [
            "fixed", "per_user", "fixed", "per_user"]

    def test_reported_objective(self, fitted):
        assert fitted[4][0]["value_gap"] < 1e-5

    def test_fixed_gradient(self, fitted):
        assert fitted[4][0]["fixed_grad_gap"] < 1e-4

    def test_user_gradients(self, fitted):
        got = fitted[4][0]
        assert got["user_grad_gap_max"] < 1e-4
        assert got["user_grad_gap_mean"] < 1e-5

    def test_every_update_descends(self, fitted):
        per_update = fitted[4][1]
        assert all(0 < r["inv_descent"] < 1e6 for r in per_update)
        objective = [r["objective"] for r in per_update]
        assert objective == sorted(objective, reverse=True)

    def test_offsets_handed_over(self, fitted):
        got = fitted[4][0]
        assert got["offsets_gap"] < 1e-5 and got["scores_gap"] < 1e-5

    def test_iterations_within_caps(self, fitted):
        cfg, updates, *_ = fitted
        caps = {"fixed": cfg["max_iters"],
                "random": cfg["random_effect"]["max_iters"]}
        assert all(0 < u.iterations <= caps[u.kind] for u in updates)

    def test_limits_of_the_cell_hold_and_faults_fail(self, fitted):
        cfg, updates, ref, _c, _got = fitted
        caps = {"fixed": cfg["max_iters"],
                "random": cfg["random_effect"]["max_iters"]}
        assert cd_fit.judge(ref, updates, cfg["limits"], caps)[0]

        class Run:
            state = {"host": ref.host}

        Run.cfg = cfg
        Run.seed = 5
        for name, wrong in cd_fit.wrong_answers(Run, ref, updates):
            assert not cd_fit.judge(ref, wrong, cfg["limits"], caps)[0], name


def test_seed_mirrors_the_data_exactly(host):
    """Another seed: the same labels and movies, summary and per-user
    columns negated together with their planted coefficients."""
    other = game_ml20m.generate(small_cfg(), 6)
    assert np.array_equal(other["labels"], host["labels"])
    assert np.array_equal(other["movie"], host["movie"])
    assert np.array_equal(np.abs(other["user_feat"]),
                          np.abs(host["user_feat"]))
    z = lambda h: game_ml20m.Margins(h, 0, h["n_rows"]).of(  # noqa: E731
        h["planted"]["beta"], h["planted"]["gamma"])
    assert np.array_equal(z(other), z(host))


def test_shards_are_canonical_and_match_the_margins(host):
    shards, ids = game_ml20m.shards(host)
    cols = game_ml20m.layout(host)
    import scipy.sparse as sp

    for mat in shards.values():
        # the flag the generator sets is true of the arrays
        fresh = sp.csr_matrix((mat.data, mat.indices, mat.indptr),
                              shape=mat.shape)
        assert mat.has_canonical_format and fresh.has_canonical_format
    assert shards["global"].shape == (host["n_rows"], cols["n_fixed"])
    assert shards["global"].nnz == host["fixed_nnz"]
    assert shards["per_user"].nnz == host["random_nnz"]
    rng = np.random.default_rng(0)
    beta = rng.standard_normal(cols["n_fixed"])
    gamma = rng.standard_normal((host["n_users"], cols["n_random"]))
    ref = reference_game.GameReference(host, 1.0, 1.0)
    fixed, random = ref.scores(beta, gamma)
    np.testing.assert_allclose(shards["global"] @ beta, fixed, rtol=1e-6,
                               atol=1e-6)
    z = np.asarray(shards["per_user"].multiply(gamma[ids["userId"]]).sum(1))
    np.testing.assert_allclose(z.ravel(), random, rtol=1e-6, atol=1e-6)


def test_reference_gradients_are_derivatives(host):
    """Finite differences of the reference's own objective."""
    cols = game_ml20m.layout(host)
    rng = np.random.default_rng(1)
    beta = 0.1 * rng.standard_normal(cols["n_fixed"])
    gamma = 0.1 * rng.standard_normal((host["n_users"], cols["n_random"]))
    ref = reference_game.GameReference(host, 3.0, 2.0)
    fixed, random = ref.scores(beta, gamma)
    _v, g = ref.fixed_value_and_grad(beta, random)
    gu = ref.random_grad(gamma, fixed)
    eps = 1e-5
    for j in (0, cols["genre"] + 3, cols["dense"] + 9, cols["intercept"]):
        e = np.zeros_like(beta)
        e[j] = eps
        fd = (ref.objective(beta + e, gamma)
              - ref.objective(beta - e, gamma)) / (2 * eps)
        assert fd == pytest.approx(g[j], rel=1e-5, abs=1e-6)
    for u, j in ((0, 2), (77, 20)):
        e = np.zeros_like(gamma)
        e[u, j] = eps
        fd = (ref.objective(beta, gamma + e)
              - ref.objective(beta, gamma - e)) / (2 * eps)
        assert fd == pytest.approx(gu[u, j], rel=1e-5, abs=1e-6)


TILES = {"dense": None, "tpu": (8, 128)}


@pytest.fixture(params=sorted(TILES))
def tile(request, monkeypatch):
    monkeypatch.setattr(game_data, "_device_tile",
                        lambda: TILES[request.param])
    return request.param


def _dataset(host, **kw):
    shards, ids = game_ml20m.shards(host)
    n = host["n_rows"]
    return build_random_effect_dataset(
        ids["userId"], shards["per_user"], host["labels"],
        np.ones(n, np.float32), **kw)


def test_real_rows_partition_the_data(host, tile):
    """Every row is some block's real row exactly once; padding rows carry
    the sentinel and no weight and are counted nowhere."""
    ds = _dataset(host)
    n = host["n_rows"]
    real = []
    for block, count in zip(ds.blocks, ds.block_rows_real):
        index = np.asarray(block.row_index)
        weights = np.asarray(block.weights)
        is_real = index < n
        assert np.array_equal(is_real, weights > 0)
        assert int(is_real.sum()) == count
        assert np.all(index[~is_real] == n)
        real.append(index[is_real])
    assert sum(ds.block_rows_real) == n
    assert np.array_equal(np.sort(np.concatenate(real)), np.arange(n))


def test_rows_minor_storage_holds_the_same_features(host, monkeypatch):
    monkeypatch.setattr(game_data, "_device_tile", lambda: None)
    plain = _dataset(host)
    monkeypatch.setattr(game_data, "_device_tile", lambda: (8, 128))
    tiled = _dataset(host)
    assert {b.x_minor for b in plain.blocks} == {"d"}
    assert "r" in {b.x_minor for b in tiled.blocks}
    for a, b in zip(plain.blocks, tiled.blocks):
        if b.x_minor == "r":
            assert b.X.shape == (a.X.shape[0], a.X.shape[2], a.X.shape[1])
        assert np.array_equal(np.asarray(a.x_erd), np.asarray(b.x_erd))
        assert np.array_equal(np.asarray(a.row_index),
                              np.asarray(b.row_index))


def test_rows_minor_storage_fits_the_same_model(host, monkeypatch):
    cfg = small_cfg()
    monkeypatch.setattr(game_data, "_device_tile", lambda: None)
    plain, _ref, _c = fit(cfg, host)
    monkeypatch.setattr(game_data, "_device_tile", lambda: (8, 128))
    tiled, _ref, coordinates = fit(cfg, host)
    assert "r" in {b.x_minor for b in coordinates[1].dataset.blocks}
    for a, b in zip(plain, tiled):
        np.testing.assert_allclose(a.coef, b.coef, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(a.scores, b.scores, rtol=1e-4, atol=1e-5)


def test_rows_minor_storage_fits_the_same_factored_model(host, monkeypatch):
    """A factored random effect projects its blocks through V before the
    block solver reads them: the projected block is stored ``(E, R, k)``
    whatever order the block keeps its own features in."""
    import jax.numpy as jnp

    from photon_ml_tpu.game import factored

    config = cd_fit._coordinate_configs(small_cfg())["per_user"].optimization
    base = jnp.zeros(host["n_rows"], jnp.float32)
    V = jnp.asarray(np.random.default_rng(0).normal(
        size=(host["n_genres"] + 1, 3)), jnp.float32)
    got = {}
    for name, tile in sorted(TILES.items()):
        monkeypatch.setattr(game_data, "_device_tile", lambda t=tile: t)
        ds = _dataset(host)
        coord = factored.FactoredRandomEffectCoordinate(
            "fre", ds, "logistic", config, rank=3, reg_weight=1.0,
            alternations=2, entity_key="userId")
        got[name] = (ds, [factored._project_block(b, V, 3)
                          for b in ds.blocks],
                     np.asarray(coord.score(coord.train(base))))
    assert {b.x_minor for b in got["dense"][0].blocks} == {"d"}
    assert "r" in {b.x_minor for b in got["tpu"][0].blocks}
    for a, b in zip(got["dense"][1], got["tpu"][1]):
        assert b.x_minor == "d" and b.X.shape == a.X.shape
        np.testing.assert_allclose(a.X, b.X, rtol=1e-5, atol=1e-6)
    # The alternation is ill-conditioned (V and the u_e share a rotation),
    # so the two storage orders' sums part by more than rounding; a block
    # read in the wrong order would part by the scores' own size.
    a, b = got["dense"][2], got["tpu"][2]
    assert np.linalg.norm(a - b) < 0.02 * np.linalg.norm(a)


@pytest.mark.parametrize("rows,dim,want", [
    (32, 21, "r"), (20, 21, "d"), (9254, 21, "r"), (64, 200, "d"),
    (1, 21, "d"), (4096, 1, "r"),
    # flat passive rows (PR 31): 5.5 M rows of 9 columns in whole chunks
    (5513216, 9, "r")])
def test_minor_axis_is_the_one_that_pads_less(rows, dim, want):
    assert game_data._x_minor(rows, dim, (8, 128)) == want
    assert game_data._x_minor(rows, dim, None) == "d"


@pytest.mark.parametrize("dtype", ["int32", "int64", "U", "object"])
def test_rows_sort_by_the_entities_string_keys(dtype):
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 120, 5000)
    keys = keys.astype(str).astype(object) if dtype == "object" else (
        keys.astype(dtype))
    order, starts, ent_keys = game_data._sort_by_entity(keys)
    as_str = keys.astype(str)
    want = np.argsort(as_str, kind="stable")
    assert np.array_equal(order, want)
    sorted_keys = as_str[want]
    want_starts = np.flatnonzero(np.concatenate(
        [[True], sorted_keys[1:] != sorted_keys[:-1]]))
    assert np.array_equal(starts, want_starts)
    assert np.array_equal(ent_keys, sorted_keys[want_starts])


def test_active_columns_by_table_and_by_sort_agree(host, monkeypatch):
    table = _dataset(host, device=False)
    monkeypatch.setattr(game_data, "_PAIR_TABLE_CELLS", 0)
    by_sort = _dataset(host, device=False)
    for a, b in zip(table.blocks, by_sort.blocks):
        assert np.array_equal(a.X, b.X)
        assert np.array_equal(a.col_map, b.col_map)


class TestSpans:
    @pytest.fixture(scope="class")
    def spans(self, host):
        before = {r["id"] for r in telemetry.layer_spans()}
        fit(small_cfg(), host)
        return [r for r in telemetry.layer_spans() if r["id"] not in before]

    def test_build_has_group_and_place(self, spans):
        build = [r for r in spans if r["name"] == "game.build"]
        assert len(build) == 1
        kids = {r["name"] for r in spans if r["parent"] == build[0]["id"]}
        assert {"game.group", "game.place", "data.make_glm_data"} <= kids

    def test_fit_nests_iterations_and_updates(self, spans):
        by_id = {r["id"]: r for r in spans}
        trains = [r for r in spans if r["name"] == "coordinate.train"]
        assert len(trains) == 4
        for r in trains:
            it = by_id[r["parent"]]
            assert it["name"] == "cd.iteration"
            assert by_id[it["parent"]]["name"] == "cd.fit"
        assert len([r for r in spans if r["name"] == "coordinate.score"]) == 4

    def test_the_blocking_read_is_a_span_of_its_own(self, spans):
        """``cd.flush`` (PR 35): where a resident fit's device seconds show
        on the host's clock; without a logger, once, after the iterations."""
        (fit_span,) = [r for r in spans if r["name"] == "cd.fit"]
        (flush,) = [r for r in spans if r["name"] == "cd.flush"]
        assert flush["parent"] == fit_span["id"]
        assert flush["attrs"] == {"updates": 4}
        last = max(r["ts"] + r["dur"] for r in spans
                   if r["name"] == "cd.iteration")
        assert last <= flush["ts"]
        assert flush["ts"] + flush["dur"] <= fit_span["ts"] + fit_span["dur"]

    def test_each_coordinate_is_finalized_under_a_span(self, spans):
        ends = [r for r in spans if r["name"] == "coordinate.finalize"]
        fit_end = max(r["ts"] + r["dur"] for r in spans
                      if r["name"] == "cd.fit")
        assert [(r["attrs"]["coordinate"], r["attrs"]["kind"])
                for r in ends] == [("fixed", "fixed"), ("per_user", "random")]
        assert all(r["ts"] >= fit_end for r in ends)
        assert "table" not in ends[0]["attrs"]
        assert ends[1]["attrs"]["table"] == "arrays"
        assert 0 < ends[1]["attrs"]["entities"] <= 150

    def test_fixed_update_carries_what_its_solve_counted(self, spans):
        fixed = [r for r in spans if r["name"] == "coordinate.train"
                 and r["attrs"]["kind"] == "fixed"]
        for r in fixed:
            assert r["attrs"]["iterations"] == 10
            assert r["attrs"]["fn_evals"] > r["attrs"]["iterations"]
            assert r["attrs"]["value"] > 0

    def test_random_update_carries_its_buckets_shapes_and_counts(self, spans):
        random = [r for r in spans if r["name"] == "coordinate.train"
                  and r["attrs"]["kind"] == "random"]
        assert len(random) == 2
        for r in random:
            buckets = r["attrs"]["buckets"]
            assert len(buckets) > 1
            for a in buckets:
                assert 0 < a["rows_real"] <= a["rows_padded"]
                assert 0 < a["iterations_max"] <= 30
                assert a["iterations_max"] <= a["iterations_sum"] <= (
                    a["iterations_max"] * a["lanes"])
                assert 0 <= a["frozen_early"] < a["lanes"] or a["lanes"] == 1
            assert sum(a["rows_real"] for a in buckets) == 9000

    def test_no_span_is_filed_twice_for_one_interval(self, spans):
        names = {r["name"] for r in spans}
        assert not names & {"cd_iteration", "solver", "re.block_solve"}


def test_amend_reaches_the_filed_record():
    with telemetry.layer_span("amend.probe", a=1) as span:
        pass
    span.amend(b=2, c=np.float32(1.5), d=[{"e": np.int32(3)}])
    record = [r for r in telemetry.layer_spans()
              if r["name"] == "amend.probe"][-1]
    assert record["attrs"] == {"a": 1, "b": 2, "c": 1.5, "d": [{"e": 3}]}


def test_a_hub_gets_the_descents_layer_spans_once(host, tmp_path):
    with telemetry.Telemetry(output_dir=str(tmp_path)) as hub:
        fit(small_cfg(), host)
        names = [r.get("name") for r in hub.recorder.snapshot()
                 if r.get("type") == "span"]
    assert names.count("cd.fit") == 1 and names.count("cd.iteration") == 2
    assert names.count("coordinate.train") == 4 == names.count("coordinate")
    assert "cd_iteration" not in names and "solver" not in names
