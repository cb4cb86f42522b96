"""The edge-list cell on the CPU at a tiny scale: found by name, correct
against the plain reference, and ``correct`` false under a layout that
truncates the hub rows, under an answer moved off the reference's and
under the control (the reference with its messages in bfloat16). A
program without the edge-layout counters stops before it builds
anything. The look for a chip is skipped (``require_tpu=False``)."""
from __future__ import annotations

import json
import os
import shutil
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(REPO, "src"))

import run  # noqa: E402

SEED = 2**31 + 77
SECONDS = 2
CELL = "tiny_graph500.solve20"
TINY = {"scale": 10}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with the benchmark's files and a tiny edge cell added
    as new files and new entries only."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    os.symlink(os.path.join(REPO, "src"), root / "src")
    cfg = json.loads((root / "bench" / "configs" /
                      "graph500_s18.json").read_text())
    cfg["data"].update(TINY)
    (root / "bench" / "configs" / "tiny_graph500.json").write_text(
        json.dumps(cfg))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_graph500", "source": "test",
                            "file": "bench/configs/tiny_graph500.json",
                            "reduced": ["scale"], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny_graph500",
                              "traffic": "solve20", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "graph500_s18.solve20" in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


def cell(root, trace=False):
    return run.run_cell(root, CELL, SEED, SECONDS, trace, require_tpu=False)


def test_edge_cell_is_correct(root):
    res = cell(root, trace=True)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"edge_miss", "exemplar_diff"}
    assert res["checks"]["edge_miss"]["value"] == 0
    notes = res["notes"]
    assert notes["buckets"] > 1
    assert notes["layout_entries"] == notes["edges"] + notes["n"]
    assert notes["padded"] <= 2 * notes["layout_entries"] + 8 * notes["n"]
    assert res["metrics"]["edge_layout_ms"]["value"] > 0


def test_truncated_hub_rows_fail(root, monkeypatch):
    """Each row keeps only its 8 heaviest edges: the hubs lose theirs."""
    from repro.graph.edges import EdgeList
    orig = EdgeList.to_blocks
    monkeypatch.setattr(EdgeList, "to_blocks",
                        lambda self, k=None, fill=None: orig(self, 8, fill))
    res = cell(root)
    assert not res["correct"]
    assert res["checks"]["edge_miss"]["value"] > 0


def test_moved_exemplars_fail(root, monkeypatch):
    """Every exemplar one row over, as the sweep returns them."""
    from repro.solver import topk
    orig = topk.run_topk

    def moved(*a, **k):
        state, e, *rest = orig(*a, **k)
        return (state, (e + 1) % e.shape[1], *rest)

    monkeypatch.setattr(topk, "run_topk", moved)
    assert not cell(root)["correct"]


def test_control_fails(root):
    _, system, ctx, _ = run.make_context(root, CELL, SEED, SECONDS, False,
                                         require_tpu=False)
    graph = system.make_graph(ctx.config["data"], SEED)
    ref_e = system.reference_solve(ctx.reference, graph, ctx.config)
    ctl_e = system.reference_solve(ctx.reference, graph, ctx.config,
                                   dtype="bfloat16")
    diff = system.exemplar_diff(ctl_e, ref_e)
    assert diff > ctx.config["limits"]["exemplar_diff"], diff


def test_program_without_counters_stops(root, monkeypatch):
    from repro.runtime import trace
    monkeypatch.delattr(trace, "edge_layout")
    with pytest.raises(RuntimeError, match="edge-layout counters"):
        cell(root)
