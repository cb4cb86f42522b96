"""The sweep program's device time split by the program's named scopes
(``lib.sweep_scopes``) and the three readers built on it:
``topk_colsum_ms``, ``topk_gather_ms``, ``topk_rowwise_ms``."""
from __future__ import annotations

import gzip
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from lib import sweep_scopes as ss  # noqa: E402
from lib import trace as tr  # noqa: E402
from repro.runtime import trace as scopes  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
RECORDED = os.path.join(DATA, "v5e_scoped_sweep.xplane.pb")
RECORDED_HLO = os.path.join(DATA, "v5e_scoped_sweep.hlo.txt.gz")
SORT = os.path.join(DATA, "v5e_sort.xplane.pb")
READERS = ("topk_colsum_ms", "topk_gather_ms", "topk_rowwise_ms")
PART = dict(zip(READERS, ("colsum", "gather", "rowwise")))
SIFT = {"n": 131072, "kk": 33, "levels": 3, "sweeps": 20}
MOD = ss.sweep_module(scopes)

HLO = '''HloModule jit_run_topk_scoped
  %fusion.1 = f32[8]{0} fusion(%p), kind=kCustom, metadata={op_name="jit(run_topk)/while/body/closed_call/hap_tau/hap_colsum/scatter-add"}
  %fusion.2 = f32[8,3]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(run_topk)/while/body/closed_call/hap_alpha/hap_gather/gather" source_file="x.py"}
  %fusion.3 = f32[8,3]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(run_topk)/while/body/closed_call/hap_rho/vmap()/reduce_max"}
  ROOT %gather.4 = f32[8]{0} gather(%p), metadata={op_name="jit(run_topk)/while/body/closed_call/hap_tau/gather"}
  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(run_topk)/while/body/closed_call"}
  %copy.6 = f32[8]{0} copy(%p)
'''


def _metric(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "m_" + name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Run:
    def __init__(self, data):
        self.data = dict(data)
        self.notes = {}


def test_op_paths_and_parts():
    paths = ss.op_paths(HLO)
    assert paths["gather.4"].endswith("hap_tau/gather")
    assert "copy.6" not in paths
    assert [ss.part_of(paths.get(f"{k}"), scopes) for k in (
        "fusion.1", "fusion.2", "fusion.3", "gather.4", "fusion.5",
        "copy.6")] == ["colsum", "gather", "rowwise", "rowwise",
                       "unscoped", "unscoped"]


def test_split_seconds_covers_the_module():
    op_s = {f"{MOD}/fusion.1": 1.0, f"{MOD}/fusion.2": 2.0,
            f"{MOD}/fusion.3": 3.0, f"{MOD}/gather.4": 4.0,
            f"{MOD}/fusion.5": 0.5, f"{MOD}/copy.6": 0.25,
            f"{MOD}/while.9": 0.125, "jit_other/fusion.1": 64.0}
    parts, unscoped = ss.split_seconds(op_s, ss.op_paths(HLO), scopes)
    assert parts == {"colsum": 1.0, "gather": 2.0, "rowwise": 7.0,
                     "unscoped": 0.875}
    assert unscoped == [("fusion.5", 0.5), ("copy.6", 0.25),
                        ("while.9", 0.125)]


def test_readers_report_per_sweep_and_note_the_rest(monkeypatch):
    """Two solves of 20 sweeps: each part over 40 sweeps, in ms; the
    unscoped rest goes to the notes, not to a metric."""
    monkeypatch.setattr(ss, "sweep_hlo", lambda data, solve: HLO)
    trace = {"op_s": {f"{MOD}/fusion.1": 0.4, f"{MOD}/fusion.2": 0.8,
                      f"{MOD}/fusion.3": 0.2, f"{MOD}/fusion.5": 0.04},
             "module_s": {MOD: 1.44}}
    run = Run(dict(SIFT, solves=2))
    got = {name: _metric(name).read(run, trace) for name in READERS}
    assert got == pytest.approx({"topk_colsum_ms": 10.0,
                                 "topk_gather_ms": 20.0,
                                 "topk_rowwise_ms": 5.0})
    assert run.notes[ss.NOTE]["unscoped"] == pytest.approx(1.0)
    assert run.notes[ss.NOTE_OPS] == [["fusion.5", pytest.approx(1.0)]]
    assert run.notes[ss.NOTE_HLO_S] >= 0.0


def test_nothing_from_an_unscoped_executable(monkeypatch):
    """An executable compiled before the scopes (loaded from a cache
    whose key leaves metadata out) names no scope: no metric, and the
    notes show the whole sweep unscoped."""
    bare = "\n".join(line.replace("hap_", "") for line in HLO.splitlines())
    monkeypatch.setattr(ss, "sweep_hlo", lambda data, solve: bare)
    trace = {"op_s": {f"{MOD}/fusion.1": 0.4, f"{MOD}/fusion.2": 0.8},
             "module_s": {MOD: 1.2}}
    run = Run(dict(SIFT, solves=2))
    assert [_metric(name).read(run, trace) for name in READERS] == \
        [None] * 3
    assert run.notes[ss.NOTE]["unscoped"] == pytest.approx(30.0)


def test_settings_come_from_the_matching_configuration():
    solve = ss.solve_settings(SIFT)
    assert solve["backend"] == "dense_topk" and solve["damping"] == 0.7
    assert ss.solve_settings(dict(SIFT, n=4096)) is None


def test_sweep_hlo_names_the_ops_on_cpu():
    """The program lowered again at the run's shapes: every op of the
    sweep has a path, the column-sum scatters under ``hap_colsum``."""
    solve = ss.solve_settings(SIFT)
    paths = ss.op_paths(ss.sweep_hlo(dict(SIFT, n=512), solve))
    parts = {ss.part_of(p, scopes) for p in paths.values()}
    assert parts == set(ss.PARTS)
    assert any(p.endswith("hap_colsum/scatter-add") for p in paths.values())


def test_nothing_without_the_program_scopes(monkeypatch):
    """The parent program has no ``repro.runtime.trace``: the readers
    give nothing and raise nothing."""
    import repro.runtime
    monkeypatch.delattr(repro.runtime, "trace")
    monkeypatch.setitem(sys.modules, "repro.runtime.trace", None)
    trace = {"op_s": {"jit_run_topk/fusion.1": 0.4},
             "module_s": {"jit_run_topk": 0.4}}
    run = Run(dict(SIFT, solves=2))
    assert [_metric(name).read(run, trace) for name in READERS] == \
        [None] * 3
    assert run.notes == {}


def test_nothing_without_a_sweep():
    run = Run(dict(SIFT, solves=2))
    reduced = tr.reduce(tr.load_events(SORT))
    for name in READERS:
        assert _metric(name).read(run, reduced) is None
        assert _metric(name).read(run, None) is None
    assert run.notes == {}


def test_recorded_scoped_sweep(monkeypatch):
    """One ``run_topk`` of 3 sweeps at N=4096, k=32, L=3 on one v5e chip
    (``bench/tools/record_scoped_sweep.py``), with its optimized HLO:
    every op of the sweep program in the trace is an instruction of the
    HLO, the four parts add up to the program's self time, the readers
    report the three scoped parts per sweep and the notes the rest."""
    with gzip.open(RECORDED_HLO, "rt") as f:
        hlo = f.read()
    reduced = tr.reduce(tr.load_events(RECORDED))
    paths = ss.op_paths(hlo)
    ops = [k.split("/", 1)[1] for k in reduced["op_s"]
           if k.startswith(MOD + "/")]
    names = set(re.findall(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=", hlo, re.M))
    assert ops and set(ops) <= names
    parts, unscoped = ss.split_seconds(reduced["op_s"], paths, scopes)
    total = reduced["module_s"][MOD]
    assert sum(parts.values()) == pytest.approx(total)
    assert min(parts["colsum"], parts["gather"], parts["rowwise"]) > 0
    assert parts["unscoped"] < 0.05 * total

    monkeypatch.setattr(ss, "sweep_hlo", lambda data, solve: hlo)
    monkeypatch.setattr(ss, "solve_settings", lambda data: {})
    run = Run({"n": 4096, "kk": 33, "levels": 3, "sweeps": 3,
               "solves": 1})
    got = {name: _metric(name).read(run, reduced) for name in READERS}
    for name in READERS:
        assert got[name] == pytest.approx(parts[PART[name]] / 3 * 1e3)
    assert run.notes[ss.NOTE]["unscoped"] == pytest.approx(
        parts["unscoped"] / 3 * 1e3)
    assert sum(got.values()) + run.notes[ss.NOTE]["unscoped"] == \
        pytest.approx(total / 3 * 1e3)
    assert [op for op, _ in run.notes[ss.NOTE_OPS]] == \
        [op for op, _ in unscoped[:ss.TOP_UNSCOPED]]
