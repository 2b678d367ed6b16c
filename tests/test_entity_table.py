"""The random-effect model table as flat arrays (``game.model.EntityTable``).

``RandomEffectCoordinate.finalize`` used to cut each bucket into one small
array per entity (two ``np.split``s) and fill a ``dict`` in a loop over
every entity.  That loop stays HERE as the plain reference: the table the
coordinate builds with whole-array operations has to equal it entity for
entity and array for array.
"""

import time

import numpy as np
import pytest

from photon_ml_tpu.game.model import (
    EntityLanes,
    EntityTable,
    EntityVariances,
    GameModel,
    RandomEffectModel,
)


def reference_tables(entity_ids, col_maps, coefs, variances=None):
    """The old ``pack_entity_tables`` + per-entity loop, verbatim in what
    it computes: ``(coefficients dict, variances dict or None)``."""
    table, var_table = {}, ({} if variances is not None else None)
    for b, ids in enumerate(entity_ids):
        cmap, w = np.asarray(col_maps[b]), np.asarray(coefs[b])
        valid = (cmap >= 0) & (w != 0)
        bounds = np.cumsum(valid.sum(axis=1))[:-1]
        col_parts = np.split(cmap[valid].astype(np.int32), bounds)
        val_parts = np.split(w[valid].astype(np.float32), bounds)
        var_parts = (
            np.split(np.asarray(variances[b])[valid].astype(np.float32),
                     bounds)
            if variances is not None else None)
        for lane, key in enumerate(ids):
            table[key] = (col_parts[lane], val_parts[lane])
            if var_parts is not None:
                var_table[key] = var_parts[lane]
    return table, var_table


def ladder(rng, keys, shapes, padding_lanes=3, n_features=40):
    """A bucket ladder by hand: ``shapes`` is ``[(lanes, dim)]``; every
    bucket has ``padding_lanes`` lanes past its ids, ``-1`` columns at each
    lane's end, exact zeros among the coefficients and columns ascending
    within a lane (as ``game.data`` builds them)."""
    keys = list(rng.permutation(np.asarray(keys)))
    entity_ids, col_maps, coefs, variances = [], [], [], []
    for lanes, dim in shapes:
        ids, keys = keys[:lanes], keys[lanes:]
        E = lanes + padding_lanes
        cmap = np.full((E, dim), -1, np.int32)
        for e in range(lanes):
            n = int(rng.integers(0, dim + 1))
            cmap[e, :n] = np.sort(rng.choice(n_features, n, replace=False))
        w = rng.normal(size=(E, dim)).astype(np.float32)
        w[rng.uniform(size=w.shape) < 0.25] = 0.0
        entity_ids.append(ids)
        col_maps.append(cmap)
        coefs.append(w)
        variances.append(rng.uniform(0.1, 2.0, (E, dim)).astype(np.float32))
    assert not keys
    return entity_ids, col_maps, coefs, variances


KEYS = {
    "int": np.arange(1000, 1057) * 7919 % 100003,
    "str": np.array([f"user-{i * 37 % 101}" for i in range(57)]),
}
SHAPES = [(20, 5), (0, 3), (30, 9), (7, 1)]


@pytest.fixture(params=sorted(KEYS))
def built(request, rng):
    ids, col_maps, coefs, variances = ladder(rng, KEYS[request.param], SHAPES)
    table = EntityLanes(ids, col_maps).table(coefs, variances)
    want, want_var = reference_tables(ids, col_maps, coefs, variances)
    return table, want, want_var


def assert_equal_tables(table, want):
    assert len(table) == len(want)
    assert set(table) == set(want)
    for key, (cols, vals) in want.items():
        got_cols, got_vals = table[key]
        assert got_cols.dtype == np.int32 and got_vals.dtype == np.float32
        np.testing.assert_array_equal(got_cols, cols)
        np.testing.assert_array_equal(got_vals, vals)


class TestEqualsTheLoop:
    def test_entity_for_entity(self, built):
        table, want, want_var = built
        assert_equal_tables(table, want)
        variances = EntityVariances(table)
        assert len(variances) == len(want_var)
        for key, var in want_var.items():
            assert variances[key].dtype == np.float32
            np.testing.assert_array_equal(variances[key], var)
            assert len(variances[key]) == len(table[key][0])

    def test_no_variances_asked_for(self, rng):
        ids, col_maps, coefs, _ = ladder(rng, KEYS["int"], SHAPES)
        table = EntityLanes(ids, col_maps).table(coefs)
        assert table.variances is None
        assert_equal_tables(table, reference_tables(ids, col_maps, coefs)[0])

    def test_a_key_met_twice_keeps_its_last_lane(self, rng):
        ids, col_maps, coefs, variances = ladder(rng, KEYS["int"], SHAPES)
        ids[2][4] = ids[0][1]
        table = EntityLanes(ids, col_maps).table(coefs, variances)
        want, _ = reference_tables(ids, col_maps, coefs, variances)
        assert len(want) == 56
        assert_equal_tables(table, want)

    def test_an_empty_ladder(self):
        table = EntityLanes([], []).table([])
        assert len(table) == 0 and list(table) == []
        assert table.get(3) is None and 3 not in table

    @pytest.mark.parametrize("compute_variances", [False, True])
    def test_finalize_of_a_trained_coordinate(self, rng, compute_variances):
        """Through ``RandomEffectCoordinate.finalize`` itself, on a ladder
        the dataset built (string keys, several buckets)."""
        import dataclasses

        import jax.numpy as jnp
        import scipy.sparse as sp

        from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
        from photon_ml_tpu.game.data import build_random_effect_dataset
        from photon_ml_tpu.optim.problem import (
            GlmOptimizationConfig, OptimizerConfig)
        from photon_ml_tpu.optim.regularization import RegularizationContext

        sizes = np.minimum(rng.zipf(1.7, 120), 48)
        n = int(sizes.sum())
        users = np.repeat(
            np.array([f"u{i}" for i in range(120)], dtype=object), sizes)
        X = rng.normal(size=(n, 6)).astype(np.float32)
        X[rng.uniform(size=X.shape) < 0.5] = 0.0
        ds = build_random_effect_dataset(
            users, sp.csr_matrix(X),
            (rng.uniform(size=n) < 0.5).astype(np.float32),
            np.ones(n, np.float32))
        assert len(ds.blocks) > 2
        opt = dataclasses.replace(
            GlmOptimizationConfig(
                optimizer=OptimizerConfig(max_iters=8),
                regularization=RegularizationContext.l2()),
            compute_variances=compute_variances)
        coord = RandomEffectCoordinate(
            "re", ds, "logistic", opt, reg_weight=0.5, entity_key="userId")
        offsets = jnp.zeros(n, jnp.float32)
        state = coord.train(offsets)
        model = coord.finalize(state, offsets=offsets)
        variances = [
            coord._block_variances(b, w, offsets)
            for b, w in zip(ds.blocks, state)] if compute_variances else None
        want, want_var = reference_tables(
            ds.entity_ids, [b.col_map for b in ds.blocks], state, variances)
        assert isinstance(model.coefficients, EntityTable)
        assert model.n_entities == 120
        assert_equal_tables(model.coefficients, want)
        if compute_variances:
            for key, var in want_var.items():
                np.testing.assert_array_equal(model.variances[key], var)
        else:
            assert model.variances is None


class TestMappingContract:
    def test_len_in_get_getitem(self, built):
        table, want, _ = built
        assert len(table) == len(want) == 57
        key = next(iter(want))
        assert key in table
        cols, vals = table.get(key)
        np.testing.assert_array_equal(cols, want[key][0])
        for unseen in (-5, "nobody", 2.5, None, (1, 2)):
            assert unseen not in table
            assert table.get(unseen) is None
            assert table.get(unseen, "default") == "default"
            with pytest.raises(KeyError):
                table[unseen]

    def test_iterates_in_sorted_order(self, built):
        table, want, _ = built
        assert list(table) == sorted(want)
        assert list(table.keys()) == sorted(want)

    def test_items_and_values(self, built):
        table, want, _ = built
        items = table.items()
        assert len(items) == len(want)
        seen = 0
        for (key, (cols, vals)), value in zip(items, table.values()):
            np.testing.assert_array_equal(cols, want[key][0])
            np.testing.assert_array_equal(vals, want[key][1])
            np.testing.assert_array_equal(value[1], vals)
            seen += 1
        assert seen == len(want)
        copied = dict(table)
        assert set(copied) == set(want)

    def test_read_only(self, built):
        table, want, _ = built
        key = next(iter(want))
        with pytest.raises(TypeError):
            table[key] = (np.zeros(1, np.int32), np.zeros(1, np.float32))
        with pytest.raises(TypeError):
            del table[key]
        assert not hasattr(table, "pop") and not hasattr(table, "update")
        cols, vals = next(v for v in table.values() if len(v[0]))
        with pytest.raises(ValueError):
            vals[0] = 1.0
        with pytest.raises(ValueError):
            cols[0] = 1
        with pytest.raises(ValueError):
            table.vals[0] = 1.0

    def test_columns_ascend_within_an_entity(self, built):
        table, _want, _ = built
        for cols, _vals in table.values():
            assert np.all(np.diff(cols) > 0)

    def test_a_table_whose_arrays_disagree_is_refused(self):
        with pytest.raises(ValueError):
            EntityTable(np.arange(3), np.zeros(3, np.int64),
                        np.zeros(0, np.int32), np.zeros(0, np.float32))


class TestPackedLookup:
    """``_ensure_packed`` and ``coefficient_matrix_for`` from the table's
    arrays, with no loop, against the equal ``dict``'s."""

    @pytest.fixture
    def models(self, built):
        table, want, _ = built

        def model(coefficients):
            return RandomEffectModel(
                coefficients=coefficients, feature_shard="s",
                entity_key="userId", task="logistic", n_features=40)

        return model(table), model(want)

    def test_packed_arrays_equal(self, models):
        from_table, from_dict = models
        got, want = from_table._ensure_packed(), from_dict._ensure_packed()
        assert got[3] == want[3] == 41
        assert list(got[0]) == list(want[0])
        assert got[0].dtype == want[0].dtype == object
        for g, w in zip(got[1:3], want[1:3]):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(g, w)

    def test_coefficient_matrix_equal(self, models, rng):
        from_table, from_dict = models
        keys = list(from_dict.coefficients)
        unseen = "stranger" if isinstance(keys[0], str) else -1
        lanes = [keys[i] for i in rng.integers(0, len(keys), 12)] + [unseen]
        col_map = rng.integers(-1, 40, (len(lanes), 7)).astype(np.int32)
        got = from_table.coefficient_matrix_for(col_map, lanes)
        np.testing.assert_array_equal(
            got, from_dict.coefficient_matrix_for(col_map, lanes))
        assert np.any(got != 0) and not np.any(got[-1])


class TestStoreRoundTrip:
    def test_save_then_load(self, built, tmp_path):
        from photon_ml_tpu.io.game_store import (
            load_game_model, save_game_model)
        from photon_ml_tpu.data.index_map import IndexMap

        table, want, want_var = built
        model = GameModel(models={"per_user": RandomEffectModel(
            coefficients=table, feature_shard="s", entity_key="userId",
            task="logistic", n_features=40,
            variances=EntityVariances(table))}, task="logistic")
        imap = IndexMap.build([f"f{j}" for j in range(40)])
        save_game_model(model, {"s": imap}, str(tmp_path / "m"))
        loaded, _maps = load_game_model(str(tmp_path / "m"))
        sub = loaded["per_user"]
        by_str = {str(k): k for k in want}
        assert set(sub.coefficients) == set(by_str) and len(by_str) == 57
        for key in by_str:
            cols, vals = sub.coefficients[key]
            order = np.argsort(cols)
            np.testing.assert_array_equal(
                np.asarray(cols)[order], want[by_str[key]][0])
            np.testing.assert_array_equal(
                np.asarray(vals, np.float32)[order], want[by_str[key]][1])
            # (the loader keeps no variance entry for an entity that has
            # no coefficient)
            np.testing.assert_array_equal(
                np.asarray(sub.variances.get(key, ()), np.float32)[order],
                want_var[by_str[key]])


class TestNoObjectPerEntity:
    def test_one_array_each_and_a_fraction_of_the_loops_wall(self, rng):
        """100,000 entities over 13 buckets of width 21 (the per-user
        ladder's shape), a fit's share of ``finalize``: the ladder's
        constants (``EntityLanes``) are built once a coordinate, the table
        once a fit.  The old loop makes four Python objects an entity; the
        table is five arrays.  A ratio and not seconds, with 8x of room
        (the loop measures ~40x the table here), so that it is steady on a
        loaded machine."""
        n, dim, buckets = 100_000, 21, 13
        keys = rng.permutation(n * 3)[:n]
        entity_ids, col_maps, coefs = [], [], []
        for ids in np.array_split(keys, buckets):
            cmap = np.tile(np.arange(dim, dtype=np.int32), (len(ids), 1))
            cmap[rng.uniform(size=cmap.shape) < 0.3] = -1
            entity_ids.append(list(ids))
            col_maps.append(cmap)
            coefs.append(rng.normal(size=cmap.shape).astype(np.float32))

        def wall(build, repeats=3):
            best = float("inf")
            for _ in range(repeats):
                start = time.perf_counter()
                out = build()
                best = min(best, time.perf_counter() - start)
            return best, out

        loop_s, (want, _) = wall(
            lambda: reference_tables(entity_ids, col_maps, coefs))
        lanes = EntityLanes(entity_ids, col_maps)
        table_s, table = wall(lambda: lanes.table(coefs))
        for flat in (table.ids, table.starts, table.cols, table.vals):
            assert isinstance(flat, np.ndarray) and flat.ndim == 1
        assert table.ids.dtype != object
        assert len(table.cols) == len(table.vals) == table.starts[-1]
        assert len(table.ids) == n and len(table.starts) == n + 1
        assert table_s < loop_s / 5, (table_s, loop_s)
        for key in keys[:200]:
            np.testing.assert_array_equal(table[key][0], want[key][0])
            np.testing.assert_array_equal(table[key][1], want[key][1])

    def test_finalize_keeps_the_ladders_constants(self):
        """The coordinate reads and sorts its keys and ``col_map``s once:
        a second ``finalize`` shares the first one's keys and columns."""
        import jax.numpy as jnp
        import scipy.sparse as sp

        from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
        from photon_ml_tpu.game.data import build_random_effect_dataset
        from photon_ml_tpu.optim.problem import GlmOptimizationConfig

        rng = np.random.default_rng(3)
        users = np.repeat(np.arange(40), rng.integers(1, 9, 40))
        n = len(users)
        ds = build_random_effect_dataset(
            users, sp.csr_matrix(rng.normal(size=(n, 4)).astype(np.float32)),
            np.zeros(n, np.float32), np.ones(n, np.float32))
        coord = RandomEffectCoordinate(
            "re", ds, "squared", GlmOptimizationConfig(), reg_weight=1.0)
        state = [jnp.ones((b.n_entities, b.block_dim), jnp.float32)
                 for b in ds.blocks]
        first = coord.finalize(state).coefficients
        second = coord.finalize(state).coefficients
        assert np.shares_memory(first.ids, second.ids)
        assert np.shares_memory(first.cols, second.cols)
        assert not np.shares_memory(first.vals, second.vals)
        np.testing.assert_array_equal(first.vals, second.vals)
