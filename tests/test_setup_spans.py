"""What set-up's spans say since PR 35: ``layout.place`` and ``game.place``
by leaf (dispatch against one wait), and ``game.group`` by phase."""

import json
import os

import jax
import numpy as np
import pytest
import scipy.sparse as sp

from benchmarks.datagen import game_ml20m_multi
from benchmarks.windows import cd_fit_multi
from photon_ml_tpu import telemetry
from photon_ml_tpu.data.dataset import make_glm_data
from photon_ml_tpu.game.estimator import GameEstimator
from photon_ml_tpu.utils.placement import place_leaves

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GROUP_PHASES = ["sort", "cap", "gather", "columns", "plan", "fill"]
LEAF_KEYS = {"path", "bytes", "src_dtype", "dtype", "contiguous",
             "dispatch_s"}


def _new_spans(before):
    return [r for r in telemetry.layer_spans() if r["id"] not in before]


def _seen():
    return {r["id"] for r in telemetry.layer_spans()}


def _check_place(span, tree):
    attrs = span["attrs"]
    leaves = attrs["leaves"]
    assert len(leaves) == len(jax.tree.leaves(tree))
    assert all(set(leaf) == LEAF_KEYS for leaf in leaves)
    assert sum(leaf["bytes"] for leaf in leaves) == attrs["bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(tree))
    assert all(leaf["dispatch_s"] >= 0.0 for leaf in leaves)
    # every dispatch and the one wait lie inside the span
    assert attrs["wait_s"] >= 0.0
    assert sum(leaf["dispatch_s"] for leaf in leaves) + attrs[
        "wait_s"] <= span["dur"]
    assert len({leaf["path"] for leaf in leaves}) == len(leaves)


@pytest.mark.parametrize("layout", ["tiled", "coo", "dense"])
def test_layout_place_reports_every_leaf(layout, monkeypatch):
    monkeypatch.setenv("PHOTON_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(3)
    X = sp.random(512, 300, density=0.02, random_state=4, format="csr",
                  dtype=np.float32)
    y = (rng.uniform(size=512) < 0.5).astype(np.float32)
    before = _seen()
    data = make_glm_data(X.toarray() if layout == "dense" else X, y,
                         use_pallas=layout == "tiled")
    (place,) = [r for r in _new_spans(before) if r["name"] == "layout.place"]
    _check_place(place, data)
    paths = [leaf["path"] for leaf in place["attrs"]["leaves"]]
    assert paths[-3:] == [".labels", ".weights", ".offsets"]
    assert all(p.startswith(".features") for p in paths[:-3])
    host = [leaf for leaf in place["attrs"]["leaves"]
            if leaf["contiguous"] is not None]
    to_coo = [r for r in _new_spans(before) if r["name"] == "layout.to_coo"]
    if layout == "tiled":  # every leaf goes host array -> device here
        assert len(host) == len(paths) and "features_s" not in place["attrs"]
        assert ".features.f_code" in paths
        # the CSR's way to triples, before the build opens
        (made,) = [r for r in _new_spans(before)
                   if r["name"] == "data.make_glm_data"]
        (build,) = [r for r in _new_spans(before)
                    if r["name"] == "layout.build"]
        assert to_coo[0]["parent"] == made["id"] and len(to_coo) == 1
        assert to_coo[0]["attrs"] == {"nnz": X.nnz}
        assert to_coo[0]["ts"] + to_coo[0]["dur"] <= build["ts"]
    else:  # the features canonicalise / cast and place in one call
        assert len(host) == 3 and place["attrs"]["features_s"] > 0.0
        assert not to_coo


def test_place_leaves_says_what_it_was_given():
    strided = np.arange(64, dtype=np.float64).reshape(8, 8)[:, ::2]
    tree = {"a": strided, "b": [np.ones(3, np.int16), 2.5]}
    placed, leaves = place_leaves(tree, prefix="t")
    assert jax.tree.structure(placed) == jax.tree.structure(tree)
    np.testing.assert_array_equal(np.asarray(placed["a"]), strided)
    by_path = {leaf["path"]: leaf for leaf in leaves}
    assert list(by_path) == ["t['a']", "t['b'][0]", "t['b'][1]"]
    assert by_path["t['a']"]["contiguous"] is False
    assert by_path["t['a']"]["src_dtype"] == "float64"
    assert by_path["t['b'][0]"]["contiguous"] is True
    assert by_path["t['b'][0]"]["bytes"] == 6
    assert by_path["t['b'][1]"]["contiguous"] is None
    assert by_path["t['b'][1]"]["src_dtype"] == "float"


@pytest.fixture(scope="module")
def built():
    """``build_coordinates`` at the dry sizes of ``game_cd_fit_user_item``
    (70,000 rows, the movies capped: passive rows exist), and its spans."""
    os.environ["PHOTON_PALLAS_INTERPRET"] = "1"
    try:
        with open(os.path.join(
                ROOT, "benchmarks/configs/"
                "game_logistic_user_item_re_ml20m.json")) as f:
            cfg = json.load(f)
        cfg = {**cfg, **cfg["dry"]}
        host = game_ml20m_multi.generate(cfg, 11)
        shards, ids = game_ml20m_multi.shards(host)
        before = _seen()
        coordinates = GameEstimator(
            cfg["task"], cd_fit_multi._coordinate_configs(cfg),
            n_iterations=1).build_coordinates(shards, ids, host["labels"])
        return coordinates, _new_spans(before)
    finally:
        del os.environ["PHOTON_PALLAS_INTERPRET"]


class TestGameSetUp:
    def test_group_children_tile_their_parent(self, built):
        _coordinates, spans = built
        groups = [r for r in spans if r["name"] == "game.group"]
        assert [g["attrs"]["coordinate"] for g in groups] == [
            "per_user", "per_movie"]
        for g in groups:
            kids = sorted((r for r in spans if r["parent"] == g["id"]),
                          key=lambda r: r["ts"])
            assert [k["name"] for k in kids] == [
                "game.group." + p for p in GROUP_PHASES]
            for a, b in zip(kids, kids[1:]):  # in the code's order, apart
                assert a["ts"] + a["dur"] <= b["ts"]
            assert kids[0]["ts"] >= g["ts"]
            assert kids[-1]["ts"] + kids[-1]["dur"] <= g["ts"] + g["dur"]
            covered = sum(k["dur"] for k in kids)
            assert covered >= 0.95 * g["dur"], (covered, g["dur"])
            assert kids[-1]["attrs"] == {
                "buckets": g["attrs"]["buckets"], "method": "native"}

    def test_game_place_reports_every_block(self, built):
        coordinates, spans = built
        places = {r["attrs"]["coordinate"]: r for r in spans
                  if r["name"] == "game.place"}
        assert set(places) == {"per_user", "per_movie"}
        for c in coordinates:
            if c.kind != "random":
                continue
            ds = c.dataset
            _check_place(places[c.name], (ds.blocks, ds.passive_blocks))
            paths = [x["path"] for x in places[c.name]["attrs"]["leaves"]]
            assert "blocks[0].X" in paths
            assert any(p.startswith("passive[") for p in paths) == bool(
                ds.rows_passive)

    def test_the_fixed_effects_placement_is_under_game_build(self, built):
        coordinates, spans = built
        (build,) = [r for r in spans if r["name"] == "game.build"]
        (made,) = [r for r in spans if r["name"] == "data.make_glm_data"]
        (place,) = [r for r in spans if r["name"] == "layout.place"]
        assert made["parent"] == build["id"] and place["parent"] == made["id"]
        fixed = next(c for c in coordinates if c.kind == "fixed")
        _check_place(place, fixed.dataset.data)
