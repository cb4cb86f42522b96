"""Trace reduction, the peak table and the byte counts behind the
roofline shares."""
from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from lib import roofline  # noqa: E402
from lib import trace as tr  # noqa: E402
from lib.peaks import UnknownDevice, peaks_for  # noqa: E402

RECORDED = os.path.join(BENCH, "tests", "data", "v5e_sort.xplane.pb")


def _op(start, dur, op="fusion", module="jit_f", device="/device:TPU:0"):
    return {"device": device, "module": module, "op": op,
            "start": float(start), "dur": float(dur)}


def test_reduce_by_hand():
    # window [0, 100) ns; ops [10, 30) and [20, 40) overlap, [60, 70)
    events = {
        "ops": [_op(10, 20, "a"), _op(20, 20, "b"), _op(60, 10, "a"),
                _op(95, 20, "c")],
        "spans": [{"name": "bench.window", "start": 0.0, "dur": 100.0},
                  {"name": "bench.wait", "start": 40.0, "dur": 20.0},
                  {"name": "bench.send", "start": 0.0, "dur": 6.0}]}
    r = tr.reduce(events)
    assert r["window_s"] == pytest.approx(100e-9)
    # busy: [10, 40) + [60, 70) + [95, 100) = 45 ns (the last op clipped)
    assert r["busy_s"] == pytest.approx(45e-9)
    assert r["idle_share"] == pytest.approx(0.55)
    assert r["op_s"]["jit_f/a"] == pytest.approx(30e-9)
    assert r["op_s"]["jit_f/c"] == pytest.approx(5e-9)
    assert r["device_ops"][0][0] == "jit_f/a"
    # gaps: [40, 60) 20 ns in bench.wait, [70, 95) 25, [0, 10) 10
    assert r["idle_gaps"][0] == ["none", pytest.approx(25e-9)]
    assert r["idle_gaps"][1] == ["bench.wait", pytest.approx(20e-9)]
    assert r["idle_gaps"][2] == ["bench.send", pytest.approx(10e-9)]


def test_nested_ops_count_once():
    # a while op [0, 100) holding fusions [10, 30) and [40, 90)
    events = {"ops": [_op(0, 100, "while.1", "jit_run"),
                      _op(10, 20, "fusion.1", "jit_run"),
                      _op(40, 50, "fusion.2", "jit_run")],
              "spans": [{"name": "bench.window", "start": 0.0,
                         "dur": 200.0}]}
    r = tr.reduce(events)
    assert r["busy_s"] == pytest.approx(100e-9)
    assert r["module_s"]["jit_run"] == pytest.approx(100e-9)
    assert r["op_s"]["jit_run/while.1"] == pytest.approx(30e-9)
    assert r["op_s"]["jit_run/fusion.2"] == pytest.approx(50e-9)


def test_reduce_averages_devices():
    events = {"ops": [_op(0, 50, device="/device:TPU:0"),
                      _op(0, 100, device="/device:TPU:1")],
              "spans": [{"name": "bench.window", "start": 0.0,
                         "dur": 100.0}]}
    r = tr.reduce(events)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(75e-9)


def test_reduce_needs_window():
    with pytest.raises(ValueError):
        tr.reduce({"ops": [], "spans": []})


def test_names():
    assert tr.op_short_name("%sort.6 = (f32[2]) sort(f32[2] %x)") == "sort.6"
    assert tr.module_short_name("jit__solve_fn(123456)") == "jit__solve_fn"


def test_recorded_v5e_trace():
    """A jitted sort run three times on one v5e chip, inside a
    bench.window span with three bench.step spans."""
    events = tr.load_events(RECORDED)
    assert len(events["ops"]) == 18
    assert {o["module"] for o in events["ops"]} == {"jit_f"}
    assert [s["name"] for s in events["spans"]].count("bench.step") == 3
    r = tr.reduce(events)
    window = [s for s in events["spans"] if s["name"] == "bench.window"][0]
    assert r["window_s"] == pytest.approx(window["dur"] * 1e-9)
    # busy time by brute force over 1 ns cells of the window
    lo, hi = window["start"], window["start"] + window["dur"]
    cells = set()
    for o in events["ops"]:
        s, e = max(o["start"], lo), min(o["start"] + o["dur"], hi)
        cells.update(range(int(s - lo), int(e - lo)))
    assert r["busy_s"] == pytest.approx(len(cells) * 1e-9, rel=1e-6)
    assert r["device_ops"][0][0] == "jit_f/sort.6"
    assert 0.0 < r["idle_share"] < 1.0
    assert r["devices"] == 1


def test_peaks_reject_unknown_device():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")


def test_topk_sweep_bytes_by_hand():
    # N=4, kk=3, L=2: per level s, r, a read and r, a written, 5 arrays
    # of 4 x 3 f32 = 5 * 48 B; two levels; the int32 column map once
    assert roofline.topk_sweep_bytes(4, 3, 2) == 2 * 5 * 48 + 48
    # the sift128 cell: N=2^17, kk=33, L=3
    assert roofline.topk_sweep_bytes(1 << 17, 33, 3) == \
        16 * (1 << 17) * 33 * 4


def test_dense_sweep_bytes_by_hand():
    assert roofline.dense_sweep_bytes(3, 2) == 2 * 5 * 9 * 4


def test_bound_says_which_limit():
    peaks = peaks_for("TPU v5 lite")
    t, kind = roofline.bound(819e9, 1.0, peaks)
    assert (t, kind) == (pytest.approx(1.0), "memory")
    t, kind = roofline.bound(1.0, 197e12, peaks)
    assert (t, kind) == (pytest.approx(1.0), "compute")


def _metric(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_build_sweep_and_rest_split_a_solve():
    """Per solve: the fused build's program alone, the sweep program per
    sweep, and the rest (preference, layout, finalize) apart."""
    events = {"ops": [_op(0, 30, "topk_similarity_fused.1",
                          "jit_topk_similarity_fused"),
                      _op(30, 5, "sort.2", "jit__sample_preference"),
                      _op(40, 50, "fusion.7", "jit_run_topk"),
                      _op(95, 5, "copy.1", "jit_finalize")],
              "spans": [{"name": "bench.window", "start": 0.0,
                         "dur": 100.0}]}
    r = tr.reduce(events)

    class Run:
        data = {"solves": 2, "sweeps": 5}

    ms = {name: _metric(name).read(Run, r)
          for name in ("topk_build_ms", "topk_rest_ms", "topk_sweep_ms")}
    assert ms["topk_build_ms"] == pytest.approx(15e-6)
    assert ms["topk_rest_ms"] == pytest.approx(5e-6)
    assert ms["topk_sweep_ms"] == pytest.approx(5e-6)
    total = ms["topk_build_ms"] + ms["topk_rest_ms"] + 5 * ms[
        "topk_sweep_ms"]
    assert total * 2 == pytest.approx(r["busy_s"] * 1e3)
    assert _metric("topk_rest_ms").read(Run, None) is None
