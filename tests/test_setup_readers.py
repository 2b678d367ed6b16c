"""The readers of the set-up's account (``benchmarks/metrics/``, PR 35) on
recorded lists of records: pure arithmetic, no device, no clock."""

import importlib
import json
import os
import types

import pytest

from benchmarks.metrics import _layer_spans, _setup

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = ["compile_trace_lower_s", "compile_backend_miss_s",
       "compile_unstored_s", "compile_cache_load_s", "compile_top_program_s",
       "place_gb_per_s", "place_dispatch_share_pct", "layout_col_perm_s",
       "layout_orient_s", "group_sort_s", "group_gather_s", "group_fill_s",
       "setup_unspanned_s"]
GAME_ONLY = {"group_sort_s", "group_gather_s", "group_fill_s"}


def _span(id, name, ts, dur, parent=None, **attrs):
    return {"type": "span", "name": name, "ts": ts, "dur": dur, "id": id,
            "parent": parent, "tid": 1, "attrs": attrs}


def _compile(program, ts, backend_s, cache, trace_s=0.0, lower_s=0.0,
             retrieval_s=0.0, span=None):
    return {"type": "compile", "program": program, "ts": ts,
            "dur": backend_s, "trace_s": trace_s, "lower_s": lower_s,
            "backend_s": backend_s, "cache": cache,
            "retrieval_s": retrieval_s, "span": span, "tid": 1}


#: A set-up of 100 s (marks 1000 -> 1100) and a window after it.
SPANS = [
    _span(1, "game.build", 1010.0, 60.0, coordinates=["fixed", "per_user"]),
    _span(2, "data.make_glm_data", 1012.0, 30.0, parent=1),
    _span(3, "layout.build", 1013.0, 20.0, parent=2),
    _span(4, "layout.canonicalize", 1013.0, 2.0, parent=3),
    _span(5, "layout.dense_split", 1015.0, 3.0, parent=3),
    _span(6, "layout.col_perm", 1018.0, 5.0, parent=3),
    _span(7, "layout.orient", 1023.0, 4.0, parent=3, side="f"),
    _span(8, "layout.orient", 1027.0, 6.0, parent=3, side="b"),
    _span(9, "layout.place", 1034.0, 8.0, parent=2, bytes=4_000_000_000,
          wait_s=1.0, leaves=[
              {"path": ".features.dense_cols", "bytes": 3_900_000_000,
               "src_dtype": "float32", "dtype": "float32",
               "contiguous": True, "dispatch_s": 5.0},
              {"path": ".labels", "bytes": 100_000_000,
               "src_dtype": "float32", "dtype": "float32",
               "contiguous": True, "dispatch_s": 1.0}]),
    _span(10, "game.group", 1045.0, 20.0, parent=1, coordinate="per_user"),
    _span(11, "game.group.sort", 1045.0, 4.0, parent=10),
    _span(12, "game.group.cap", 1049.0, 1.0, parent=10),
    _span(13, "game.group.gather", 1050.0, 3.0, parent=10),
    _span(14, "game.group.columns", 1053.0, 5.0, parent=10),
    _span(15, "game.group.plan", 1058.0, 1.0, parent=10),
    _span(16, "game.group.fill", 1059.0, 6.0, parent=10, buckets=13),
    _span(17, "game.place", 1066.0, 2.0, parent=1, coordinate="per_user",
          bytes=1_000_000, wait_s=0.5, leaves=[
              {"path": "blocks[0].X", "bytes": 1_000_000,
               "src_dtype": "float32", "dtype": "float32",
               "contiguous": True, "dispatch_s": 1.25}]),
    _span(18, "cd.fit", 1075.0, 20.0),
    _span(19, "coordinate.train", 1075.0, 12.0, parent=18,
          coordinate="per_user"),
    _span(20, "coordinate.score", 1088.0, 7.0, parent=18,
          coordinate="per_user"),
    # the window's own
    _span(21, "cd.fit", 1101.0, 4.0),
    _span(22, "coordinate.train", 1101.0, 4.0, parent=21,
          coordinate="per_user"),
]
TRAIN = {"name": "coordinate.train", "id": 19, "coordinate": "per_user"}
COMPILES = [
    # before the first device operation: not set-up's
    _compile("jit(early)", 990.0, 1.0, "stored", trace_s=9.0),
    # outside every span, in the harness's stretch
    _compile("jit(convert_element_type)", 1004.0, 0.25, "unstored",
             trace_s=0.125, lower_s=0.125),
    _compile("jit(convert_element_type)", 1005.0, 0.25, "unstored"),
    # under the warm fit's train span: covered twice over
    _compile("jit(random_effect_train_per_user)", 1076.0, 8.0, "stored",
             trace_s=1.0, lower_s=0.5, span=TRAIN),
    _compile("jit(fixed_effect_train)", 1085.0, 1.5, "hit", trace_s=0.5,
             lower_s=0.25, retrieval_s=1.0, span=TRAIN),
    _compile("jit(no_cache)", 1096.0, 2.0, "off"),
    # inside the window
    _compile("jit(late)", 1102.0, 0.5, "unstored", trace_s=0.25,
             span={"name": "coordinate.train", "id": 22,
                   "coordinate": "per_user"}),
]


@pytest.fixture
def run(monkeypatch):
    monkeypatch.setattr(_layer_spans, "records", lambda: list(SPANS))
    monkeypatch.setattr(_setup, "compile_records", lambda: list(COMPILES))
    return types.SimpleNamespace(
        marks={"process_start": 985.0, "first_device_op": 1000.0,
               "window_start": 1100.0, "window_end": 1110.0},
        spans={"datagen": 6.0}, info={}, state={}, compile_events=[])


def _read(name, run):
    return importlib.import_module("benchmarks.metrics." + name).read(run)


class TestCompileReaders:
    def test_the_four_sums(self, run):
        assert _read("compile_trace_lower_s", run) == 0.25 + 1.5 + 0.75
        assert _read("compile_backend_miss_s", run) == 0.5 + 8.0 + 2.0
        assert _read("compile_unstored_s", run) == 0.5
        assert _read("compile_cache_load_s", run) == 1.0

    def test_top_program_and_the_result_lines_keys(self, run):
        assert _read("compile_top_program_s", run) == 9.5
        rows = run.info["compile_by_program"]
        assert [r["program"] for r in rows] == [
            "jit(random_effect_train_per_user)", "jit(fixed_effect_train)",
            "jit(no_cache)", "jit(convert_element_type)"]
        assert rows[0] == {
            "program": "jit(random_effect_train_per_user)", "seconds": 9.5,
            "count": 1, "trace_s": 1.0, "lower_s": 0.5, "backend_s": 8.0,
            "cache": {"stored": 1},
            "span": {"coordinate.train[per_user]": 9.5}}
        # a hit's retrieval lies inside its backend_s: counted once
        assert rows[1]["seconds"] == 0.5 + 0.25 + 1.5
        assert rows[3]["count"] == 2 and rows[3]["cache"] == {"unstored": 2}
        assert rows[3]["span"] == {"none": 0.75}
        totals = run.info["compile_totals"]
        assert totals["records"] == 5
        assert totals["cache"]["hit"] == {
            "n": 1, "backend_s": 1.5, "retrieval_s": 1.0}
        assert run.info["compiled_in_window"] == [
            {"program": "jit(late)", "span": "coordinate.train[per_user]",
             "cache": "unstored", "seconds": 0.75}]

    def test_a_clean_window_names_nothing(self, run, monkeypatch):
        monkeypatch.setattr(_setup, "compile_records",
                            lambda: list(COMPILES[:-1]))
        _read("compile_top_program_s", run)
        assert "compiled_in_window" not in run.info


class TestSpanReaders:
    def test_placement(self, run):
        assert _read("place_gb_per_s", run) == 0.5
        assert _read("place_dispatch_share_pct", run) == 75.0
        rows = run.info["place_leaves"]
        assert set(rows) == {"layout.place", "game.place[per_user]"}
        assert rows["layout.place"]["wait_s"] == 1.0
        assert rows["layout.place"]["n_leaves"] == 2
        assert rows["game.place[per_user]"]["dispatch_s"] == 1.25

    def test_layout_children(self, run):
        assert _read("layout_col_perm_s", run) == 5.0
        assert _read("layout_orient_s", run) == 10.0
        assert run.info["layout_phases"] == {
            "layout.build": 20.0, "layout.canonicalize": 2.0,
            "layout.dense_split": 3.0, "layout.col_perm": 5.0,
            "layout.orient": 10.0}

    def test_group_children(self, run):
        assert _read("group_sort_s", run) == 4.0
        assert _read("group_gather_s", run) == 3.0
        assert _read("group_fill_s", run) == 6.0
        assert run.info["group_phases"] == {"per_user": {
            "game.group": 20.0, "sort": 4.0, "cap": 1.0, "gather": 3.0,
            "columns": 5.0, "plan": 1.0, "fill": 6.0}}

    def test_group_children_sum_over_coordinates(self, run, monkeypatch):
        movie = [_span(30, "game.group", 1068.0, 5.0, parent=1,
                       coordinate="per_movie"),
                 _span(31, "game.group.sort", 1068.0, 2.5, parent=30)]
        monkeypatch.setattr(_layer_spans, "records",
                            lambda: list(SPANS) + movie)
        assert _read("group_sort_s", run) == 6.5
        assert _read("group_fill_s", run) == 6.0
        assert set(run.info["group_phases"]) == {"per_user", "per_movie"}


class TestUnspanned:
    def test_the_union(self, run):
        """Covered: spans without children and compile records, clipped to
        set-up.  Of 100 s: [1004, 1004.25] + [1005, 1005.25] (compiles in
        the open), the layout's children [1013, 1033] and [1034, 1042],
        the group's [1045, 1065], [1066, 1068], the warm fit's [1075, 1087]
        (a compile nested in it) and [1088, 1095], [1096, 1098]."""
        covered = 0.5 + 20.0 + 8.0 + 20.0 + 2.0 + 12.0 + 7.0 + 2.0
        assert _read("setup_unspanned_s", run) == pytest.approx(
            100.0 - covered - 6.0)
        gaps = run.info["setup_gaps"]
        assert len(gaps) == 5
        assert gaps[0] == {
            "at_s": 70.0, "seconds": 5.0, "inside": None,
            "before": "end of game.build", "after": "start of cd.fit"}
        assert [g["seconds"] for g in gaps] == [5.0, 4.75, 4.0, 3.0, 2.0]
        # a span with children names what lies between them
        assert gaps[3] == {
            "at_s": 42.0, "seconds": 3.0, "inside": "game.build",
            "before": "end of data.make_glm_data",
            "after": "start of game.group[per_user]"}
        # the harness's own stretch is cut where the program's span starts
        first = next(g for g in gaps if g["at_s"] == 0.0)
        assert first == {"at_s": 0.0, "seconds": 4.0, "inside": None,
                         "before": "first_device_op",
                         "after": "start of compile "
                                  "jit(convert_element_type)"}

    def test_records_around_the_marks_are_clipped(self):
        spans = [_span(1, "grid", 5.0, 10.0),       # straddles the start
                 _span(2, "solver", 18.0, 10.0),    # straddles the end
                 _span(3, "grid", 30.0, 5.0)]       # after it
        compiles = [_compile("jit(f)", 0.0, 2.0, "off"),   # before it
                    _compile("jit(g)", 16.0, 3.0, "off")]  # overlaps 2
        pieces = _setup.uncovered(10.0, 20.0, spans, compiles)
        assert [(a, b) for a, b, *_ in pieces] == [(15.0, 16.0)]
        assert pieces[0][2:] == (None, "end of grid",
                                 "start of compile jit(g)")

    def test_nested_spans_cover_through_their_children_alone(self):
        spans = [_span(1, "cd.fit", 0.0, 10.0),
                 _span(2, "cd.iteration", 1.0, 8.0, parent=1),
                 _span(3, "coordinate.train", 2.0, 3.0, parent=2),
                 _span(4, "coordinate.score", 5.0, 2.0, parent=2)]
        pieces = _setup.uncovered(0.0, 10.0, spans, [])
        assert [(a, b, inside) for a, b, inside, *_ in pieces] == [
            (0.0, 1.0, "cd.fit"), (1.0, 2.0, "cd.iteration"),
            (7.0, 9.0, "cd.iteration"), (9.0, 10.0, "cd.fit")]

    def test_everything_covered(self):
        assert _setup.uncovered(
            0.0, 4.0, [_span(1, "grid", -1.0, 9.0)], []) == []

    def test_datagen_never_makes_it_negative(self, run):
        run.spans["datagen"] = 500.0
        assert _read("setup_unspanned_s", run) == 0.0


class TestNothingToRead:
    @pytest.mark.parametrize("name", NEW)
    def test_none_on_empty_lists(self, name, run, monkeypatch):
        monkeypatch.setattr(_layer_spans, "records", lambda: [])
        monkeypatch.setattr(_setup, "compile_records", lambda: [])
        if name == "setup_unspanned_s":  # nothing covered, all of it bare
            assert _read(name, run) == 94.0
        elif name.startswith("compile_") and name != "compile_top_program_s":
            assert _read(name, run) == 0
        else:
            assert _read(name, run) is None
        assert "compile_by_program" not in run.info

    @pytest.mark.parametrize("name", NEW)
    def test_none_on_a_program_without_the_records(self, name, run,
                                                   monkeypatch):
        """The parent commit: layer spans without the new children and
        attributes, and no compile records at all."""
        old = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                              if k not in ("leaves", "wait_s")})
               for s in SPANS if not s["name"].startswith("game.group.")]
        monkeypatch.setattr(_layer_spans, "records", lambda: old)
        monkeypatch.setattr(_setup, "compile_records", lambda: None)
        had = {"place_gb_per_s": 0.5, "layout_col_perm_s": 5.0,
               "layout_orient_s": 10.0}
        assert _read(name, run) == had.get(name)


def test_the_registry_names_each_reader_once():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        registry = json.load(f)
    entries = {m["name"]: m for m in registry["per_layer"]}
    # PR 35's entries, together and in order (later PRs append after them)
    names = [m["name"] for m in registry["per_layer"]]
    first = names.index(NEW[0])
    assert names[first:first + len(NEW)] == NEW
    for name in NEW:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "metrics", name + ".py")), name
        entry = entries[name]
        assert entry["moves"] == "setup_s"
        assert entry["source"] == "program_span"
        if name in GAME_ONLY:
            assert entry["workloads"] == [
                "game_cd_fit", "game_cd_fit_user_item"]
        else:
            assert "workloads" not in entry
