"""The harness on the CPU at tiny sizes: cells found by name from new
files alone, the timed path checked against the plain reference, and
``correct`` false under the control and under faults planted in the
timed path. The look for a chip is skipped (``require_tpu=False``)."""
from __future__ import annotations

import json
import os
import re
import shutil
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(REPO, "src"))

import run  # noqa: E402

SEED = 2**31 + 99
SECONDS = 2

TINY_SIFT = {
    "data": {"n_points": 1024, "dim": 16, "centers": 8, "box": 10.0,
             "spread": 1.0},
    "solve": {"k": 8, "build": "reference"},
    "check": {"sample_rows": 32, "ref_block": 256},
}
TINY_SEG = {
    "requests": {"tiles": [10, 10]},
    "service": {"buckets": [[128, 3, 2]]},
    "check": {"sample": 6},
}
TINY_MIX = {"rate_rps": 30.0}
DUMMY_METRIC = '''
def read(run, trace):
    return float(run.attempted)
'''


def _merge(base: dict, patch: dict) -> dict:
    out = dict(base)
    for k, v in patch.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) and \
            isinstance(base.get(k), dict) and k not in ("limits",) else v
    return out


# The served cell is kept out of BENCHMARK.json for now (see PERF.md);
# the tests add it the way a later benchmark change would: new entries.
SERVED_E2E = ("p50_ms", "p95_ms")
SERVED_LAYERS = {"serve_solve_ms": ("ms", "lower", "program_counter",
                                    "dense batched sweep", "p50_ms"),
                 "dense_sweep_roofline": ("%", "higher", "device_trace",
                                          "dense batched sweep", "p50_ms"),
                 "sweeps_per_launch": ("sweeps", "lower", "program_counter",
                                       "HAP core", "p95_ms"),
                 "serve_queue_ms": ("ms", "lower", "program_counter",
                                    "serve host path", "p95_ms"),
                 "gen_late_ms": ("ms", "lower", "host_clock",
                                 "load generator", "p95_ms"),
                 "device_idle.serve": ("%", "lower", "device_trace",
                                       "device", "p95_ms")}


def _add_served(spec: dict, cells: list) -> None:
    spec["end_to_end"] += [
        {"name": n, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": cells} for n in SERVED_E2E]
    spec["per_layer"] += [
        {"name": n, "unit": u, "better": b, "source": src, "layer": layer,
         "moves": moves, "workloads": cells}
        for n, (u, b, src, layer, moves) in SERVED_LAYERS.items()]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with the benchmark's files and the tiny cells added as
    new files and new entries only."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    os.symlink(os.path.join(REPO, "src"), root / "src")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "seg_rgb", "source": "test",
                            "file": "bench/configs/seg_rgb.json",
                            "reduced": ["tiles"], "why": "test"})
    for name, base, patch in (("tiny_sift", "sift128", TINY_SIFT),
                              ("tiny_seg", "seg_rgb", TINY_SEG)):
        cfg = json.loads((root / "bench" / "configs" /
                          f"{base}.json").read_text())
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(_merge(cfg, patch)))
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "test"})
    mix = json.loads((root / "bench" / "traffic" / "serve.json").read_text())
    (root / "bench" / "traffic" / "tiny_serve.json").write_text(
        json.dumps(dict(mix, **TINY_MIX)))
    (root / "bench" / "metrics" / "dummy_attempted.py").write_text(
        DUMMY_METRIC)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [
                w.replace("sift128", "tiny_sift") for w in m["workloads"]]
    _add_served(spec, ["seg_rgb.serve", "tiny_seg.serve"])
    spec["workloads"] += [
        {"name": "seg_rgb.serve", "config": "seg_rgb", "traffic": "serve",
         "chips": 1, "why": "test"},
        {"name": "tiny_sift.solve20", "config": "tiny_sift",
         "traffic": "solve20", "chips": 1, "why": "test"},
        {"name": "tiny_seg.serve", "config": "tiny_seg",
         "traffic": "tiny_serve", "chips": 1, "why": "test"}]
    spec["per_layer"].append({
        "name": "dummy_attempted", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "test", "moves": "p95_ms",
        "workloads": ["tiny_seg.serve"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


def cell(root, name, trace=False, seed=SEED):
    return run.run_cell(root, name, seed, SECONDS, trace, require_tpu=False)


def test_new_files_are_found_by_name(root):
    assert run.list_cells(root)[-2:] == ["tiny_sift.solve20",
                                         "tiny_seg.serve"]
    res = cell(root, "tiny_seg.serve", trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["dummy_attempted"]["value"] == res["attempted"]
    assert set(res["metrics"]) >= {"serve_queue_ms", "serve_solve_ms",
                                   "sweeps_per_launch", "gen_late_ms"}
    assert list(res)[-1] == "checks"
    assert res["device"]["window_s"] > 0


def test_no_result_without_the_program(tmp_path, capsys):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    with pytest.raises(run.NoProgram):
        run.make_context(str(tmp_path), "sift128.solve20", 1, 1, False)


def test_batch_cell_is_correct(root):
    res = cell(root, "tiny_sift.solve20")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "solve_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_serve_cell_is_correct(root):
    res = cell(root, "tiny_seg.serve")
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "p50_ms", "p95_ms"}
    assert res["metrics"]["p95_ms"]["value"] >= \
        res["metrics"]["p50_ms"]["value"] > 0
    assert res["notes"]["requests"] == res["attempted"] == 60


# ----------------------------------------------------------- faults
@pytest.fixture
def fresh_jit():
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sweep_that_leaves_state_unchanged(root, monkeypatch, fresh_jit):
    from repro.core import hap
    monkeypatch.setattr(hap, "jacobi_sweep",
                        lambda state, *a, **k: state)
    assert not cell(root, "tiny_sift.solve20")["correct"]


def test_batch_answer_altered(root, monkeypatch):
    from repro.solver import topk
    orig = topk.run_topk

    def altered(*a, **k):
        state, e, *rest = orig(*a, **k)
        return (state, (e + 1) % e.shape[1], *rest)

    monkeypatch.setattr(topk, "run_topk", altered)
    assert not cell(root, "tiny_sift.solve20")["correct"]


def _patch_served(monkeypatch, change):
    from repro.solver import compiled
    orig = compiled.BatchedDenseSolver.run

    def run_changed(self, points, n_real):
        return change(orig(self, points, n_real), n_real)

    monkeypatch.setattr(compiled.BatchedDenseSolver, "run", run_changed)


def test_half_the_batch_left_out(root, monkeypatch):
    """Riders past the first half of each launch are not solved: they
    come back as the untouched state decodes, every point its own
    exemplar."""
    import dataclasses

    def half(raw, n_real):
        riders = int(np.sum(np.asarray(n_real) > 2))
        e = np.array(raw.exemplars)
        e[max(riders // 2, 1):] = np.arange(e.shape[-1])
        return dataclasses.replace(raw, exemplars=e)

    _patch_served(monkeypatch, half)
    assert not cell(root, "tiny_seg.serve")["correct"]


def test_served_answer_altered(root, monkeypatch):
    """Each point's exemplar moved to the next point of its request."""
    import dataclasses

    def shift(raw, n_real):
        nr = np.asarray(n_real)[:, None, None]
        return dataclasses.replace(raw, exemplars=(raw.exemplars + 1) % nr)

    _patch_served(monkeypatch, shift)
    assert not cell(root, "tiny_seg.serve")["correct"]


# ---------------------------------------------------------- control
def _ctx(root, name):
    _, system, ctx, _ = run.make_context(root, name, SEED, SECONDS, False,
                                         require_tpu=False)
    return system, ctx


def test_batch_control_fails(root):
    system, ctx = _ctx(root, "tiny_sift.solve20")
    x, _ = system.make_points(ctx.config["data"], SEED)
    ref_e, _, _ = system.reference_solve(ctx.reference, x, ctx.config, SEED)
    ctl_e, vals, idx = system.reference_solve(
        ctx.reference, x, ctx.config, SEED, precision="high",
        dtype="bfloat16")
    rows = system.sample_rows(x.shape[0], 32, SEED)
    nums = system.numbers(ctx.reference, ctx.config, np.asarray(x), rows,
                          vals[rows], idx[rows], ctl_e, ref_e)
    limits = ctx.config["limits"]
    assert any(nums[k] > limits[k] for k in limits), nums


def test_serve_control_fails(root):
    """On tiles of the served configuration's own size (400-480 pixels):
    the control's exemplars part from the reference's by more than the
    limit."""
    system, ctx = _ctx(root, "seg_rgb.serve")
    reqs = system.plan(ctx, SEED, 10)[:4]
    diffs = []
    for r in reqs:
        ref_e = system.reference_exemplars(ctx.reference, ctx.config,
                                           r["points"])
        ctl_e = system.reference_exemplars(
            ctx.reference, ctx.config, r["points"], precision="high",
            dtype="bfloat16")
        diffs.append(float(np.mean(ctl_e != ref_e)))
    nums = system.numbers(diffs)
    limits = ctx.config["limits"]
    assert any(nums[k] > limits[k] for k in limits), nums


# ---------------------------------------------------------- contract
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keeps_the_contract():
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    names = [c["name"] for c in spec["configs"]]
    cells = [w["name"] for w in spec["workloads"]]
    metrics = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    for n in names + cells + metrics:
        assert NAME.match(n), n
    assert len(set(metrics)) == len(metrics)
    used = {w["config"] for w in spec["workloads"]}
    assert used == set(names)
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(REPO, c["file"]))
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in spec["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        for w in m.get("workloads", ()):
            assert w in e2e[m["moves"]].get("workloads", cells)
    for w in cells:
        reported = [m for m in spec["end_to_end"]
                    if w in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(w in m.get("workloads", cells)
                   for m in spec["per_layer"])
