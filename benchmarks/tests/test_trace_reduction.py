"""The reduction in benchmarks/trace.py and the needed-bytes arithmetic in
benchmarks/roofline.py.  Run by hand: ``pytest benchmarks/tests``."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import roofline, trace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
KERNEL = "%_tiled_apply.7 = f32[5,16,128]{2,1,0} custom-call(s16[5,8,256,128] %a)"


def _events():
    # One program: a while loop (a container) over two kernel launches and a
    # fusion, then 2 s of nothing while the host restarts, then one kernel
    # that overlaps the start of the next fusion.
    device = [
        ("%while.1 = (f32[8]) while(%t), body=%b", 1.0, 3.0),
        (KERNEL, 1.0, 1.0),
        ("%fusion.3 = f32[8]{0} fusion(%x), kind=kLoop", 2.0, 0.5),
        (KERNEL, 3.0, 1.0),
        (KERNEL, 6.0, 1.0),
        ("%fusion.4 = f32[8]{0} fusion(%x), kind=kLoop", 6.9, 0.6),
    ]
    host = [
        ("grid", 0.5, 4.0),
        ("$problem.py:236 grid_loop", 0.6, 3.8),
        ("$timer.py:28 stop_blocking", 2.4, 0.7),
        ("grid", 5.5, 2.5),
    ]
    return {"device": {"/device:TPU:0": device}, "host": host}


def test_leaves_drop_containers_but_keep_overlaps():
    names = [trace.short_name(e[0]) for e in
             trace.leaves(_events()["device"]["/device:TPU:0"])]
    assert "while.1" not in names
    assert names.count("_tiled_apply.7") == 3 and "fusion.4" in names


def test_busy_union_idle_share_and_kernel_time():
    r = trace.reduce(_events(), window=(0.5, 8.0))
    # leaves: [1,2] [2,2.5] [3,4] [6,7] [6.9,7.5] -> 1 + .5 + 1 + 1.5 = 4.0
    assert r.window_s == pytest.approx(7.5)
    assert r.busy_s == pytest.approx(4.0)
    assert 100 * (1 - r.busy_s / r.window_s) == pytest.approx(100 * 3.5 / 7.5)
    assert r.kernel_durations_s == pytest.approx([1.0, 1.0, 1.0])
    ops = dict(r.device_ops)
    assert ops["_tiled_apply.7"] == pytest.approx(3.0)
    assert r.device_ops[0][0] == "_tiled_apply.7"


def test_gaps_are_labelled_by_what_the_host_did():
    gaps = dict(trace.reduce(_events(), window=(0.5, 8.0)).idle_gaps)
    # [0.5,1]: the innermost call over its middle is grid_loop; [2.5,3]:
    # stop_blocking; [4,6]: its middle, 5.0, lies between the two grid
    # annotations; [7.5,8]: inside the second annotation alone.
    assert gaps["problem.py:236 grid_loop"] == pytest.approx(0.5)
    assert gaps["timer.py:28 stop_blocking"] == pytest.approx(0.5)
    assert gaps["grid_restart"] == pytest.approx(2.0)
    assert gaps["grid"] == pytest.approx(0.5)


def test_short_gaps_are_summed_under_one_label():
    ev = {"device": {"/device:TPU:0": [("a", 0.0, 1.0), ("b", 1.00001, 1.0)]},
          "host": [("grid", 0.0, 3.0)]}
    gaps = dict(trace.reduce(ev).idle_gaps)
    assert list(gaps) == [trace.SHORT_GAP_LABEL]


def test_window_defaults_to_the_device_ops_and_clips():
    r = trace.reduce(_events())
    assert r.window_s == pytest.approx(6.5)
    clipped = trace.reduce(_events(), window=(1.5, 3.5))
    assert clipped.busy_s == pytest.approx(0.5 + 0.5 + 0.5)


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce({"device": {}, "host": []})


def test_recorded_tron_grid():
    """One traced TRON grid from the chip (probe 1 of PR 24: 4 solves of 6
    outer iterations; 24 CG loops of 4 steps)."""
    events = trace.load_events(os.path.join(HERE, "data", "tron_grid.xplane.pb"))
    assert list(events["device"]) == ["/device:TPU:0"]
    grids = [e for e in events["host"] if e[0] == trace.WINDOW_ANNOTATION]
    assert len(grids) == 1
    r = trace.reduce(events, (grids[0][1], grids[0][1] + grids[0][2]))
    # 2 x (96 Hv products + 24 trial value+grads + 4 first value+grads)
    assert len(r.kernel_durations_s) == 248
    assert r.window_s == pytest.approx(6.2188, abs=1e-3)
    assert r.busy_s == pytest.approx(6.2064, abs=1e-3)
    assert sum(r.kernel_durations_s) == pytest.approx(6.1741, abs=1e-3)
    assert r.device_ops[0][0].startswith("_tiled_apply")
    assert all(not n.startswith("while") for n, _ in r.device_ops)
    assert len(r.breakdown()["device_ops"]) == 10


def test_needed_bytes_of_a_hand_counted_matrix():
    # 3 x 4, five valued entries:  [[a, 0, b, 0], [0, 0, 0, c], [d, e, 0, 0]]
    # per entry a 2-byte index and a 4-byte value; per row and per column one
    # f32 of the vectors: 5*6 + 4*(3+4) = 58 bytes, 10 operations.
    assert roofline.sparse_product_bytes(5, 3, 4) == 58
    assert roofline.sparse_product_ops(5) == 10
    # all values 1: the index alone.
    assert roofline.sparse_product_bytes(5, 3, 4, unit_values=True) == 38


def test_roofline_says_which_peak_bounds_it():
    peak = roofline.peaks("TPU v5 lite")
    least, bound = roofline.product_min_seconds(201326592, 6291456, 8192, peak)
    assert bound == "bytes"
    assert least == pytest.approx(
        (201326592 * 6 + 4 * (6291456 + 8192)) / 819e9)
    assert roofline.min_seconds(1e15, 1.0, peak)[1] == "ops"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.peaks("_source")
