"""Named scopes on the sparse sweep and host spans around ``solve()``
(``repro.runtime.trace``).

* every column-sum scatter of the compiled sweep program carries
  ``hap_colsum`` in its ``op_name`` path, every gather of the alpha
  update ``hap_gather``, and each job of the sweep its own scope — in
  ``run_topk`` and in the row-sharded program on two host devices;
* the scopes change only metadata: the optimized HLO without it is the
  same as with the scopes taken out;
* a traced ``solve()`` writes the four ``repro.solve*`` spans on the
  host plane, the call numbered in its metadata.
"""
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import trace
from repro.solver import solve
from repro.solver.topk import build_from_points, run_topk

OP = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s*\S+\s+([\w\-]+)\(.*?'
                r'op_name="([^"]*)"')
HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                      "trace_scopes_check.py")


def _ops(hlo_text):
    """[(name, opcode, op_name path components)] of an HLO module."""
    return [(m.group(1), m.group(2), m.group(3).split("/"))
            for m in map(OP.match, hlo_text.splitlines()) if m]


def _layout(n=256, k=8, levels=3):
    x = np.random.default_rng(0).standard_normal((n, 3)).astype(np.float32)
    return build_from_points(jnp.asarray(x), k, levels)


def _sweep_ops(**opts):
    s3k, idx = _layout()
    opts = dict(dict(max_iterations=2, damping=0.7), **opts)
    return _ops(run_topk.lower(s3k, idx, **opts).compile().as_text())


def _check_sweep_scopes(ops):
    scatters = [p for _, opc, p in ops if opc == "scatter"]
    assert any(p[-1] == "scatter-add" for p in scatters)
    for path in scatters:
        if path[-1] == "scatter-add":
            assert trace.SCOPE_COLSUM in path, path
        else:                                  # the self-slot writes
            assert {trace.SCOPE_COLSUM, trace.SCOPE_GATHER} & set(path)
    alpha_gathers = [p for _, opc, p in ops
                     if opc == "gather" and trace.SCOPE_ALPHA in p]
    assert alpha_gathers
    for path in alpha_gathers:
        assert trace.SCOPE_GATHER in path, path


def test_scope_names_are_not_primitives():
    names = set(trace.SWEEP_SCOPES)
    assert len(names) == len(trace.SWEEP_SCOPES)
    assert not names & set(dir(jax.lax))
    assert all(n.startswith("hap_") for n in names)
    assert all(s.startswith("repro.solve") for s in trace.SPANS)


@pytest.mark.parametrize("stop", ["fixed", "converged"])
def test_run_topk_ops_carry_their_scopes(stop):
    ops = _sweep_ops(stop=stop)
    _check_sweep_scopes(ops)
    seen = {s for _, _, p in ops for s in p}
    for scope in (trace.SCOPE_TAU, trace.SCOPE_C, trace.SCOPE_RHO,
                  trace.SCOPE_PHI, trace.SCOPE_ALPHA, trace.SCOPE_ASSIGN,
                  trace.SCOPE_COLSUM, trace.SCOPE_GATHER):
        assert scope in seen, scope
    assert trace.SCOPE_S_NEXT not in seen      # s_mode="off"


def test_s_next_scope_with_refinement():
    ops = _sweep_ops(s_mode="evidence", kappa=0.1)
    assert any(trace.SCOPE_S_NEXT in p for _, _, p in ops)


def test_scopes_change_only_metadata(monkeypatch):
    """The sweep program compiled with every scope turned into a no-op
    is the same HLO, once op metadata and source locations are left
    out."""
    import contextlib

    def strip(text):
        lines = text.splitlines()
        start = next(i for i, line in enumerate(lines)
                     if line.startswith(("%", "ENTRY")))
        return [re.sub(r",? metadata=\{[^}]*\}", "", line)
                for line in lines[start:]]

    s3k, idx = _layout()
    opts = dict(max_iterations=2, damping=0.7)
    scoped = run_topk.lower(s3k, idx, **opts).compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        plain = run_topk.lower(s3k, idx, **opts).compile().as_text()
    finally:
        jax.clear_caches()
    assert "hap_colsum" in scoped and "hap_colsum" not in plain
    assert strip(scoped) == strip(plain)


def test_sharded_ops_carry_their_scopes():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, HELPER], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    assert [d["exchange"] for d in lines] == ["psum", "allgather"]
    for d in lines:
        _check_sweep_scopes([(n, opc, p.split("/"))
                             for n, opc, p in d["ops"]])


def test_traced_solve_writes_its_spans(tmp_path):
    from jax.profiler import ProfileData

    x = np.random.default_rng(1).standard_normal((200, 3)).astype(
        np.float32)
    opts = dict(backend="dense_topk", k=8, levels=2, max_iterations=3)
    solve(x, **opts)                               # compile outside
    with jax.profiler.trace(str(tmp_path)):
        solve(x, **opts)
        solve(x, **opts)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [e for plane in ProfileData.from_file(path[0]).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("repro.")]
    names = [e.name for e in events]
    for span in trace.SPANS:
        assert names.count(span) == 2, (span, names)
    calls = [dict(e.stats) for e in events if e.name == trace.SPAN_SOLVE]
    assert {c["backend"] for c in calls} == {"dense_topk"}
    assert {c["n"] for c in calls} == {200}
    assert calls[1]["call"] == calls[0]["call"] + 1
    solve_ev = [e for e in events if e.name == trace.SPAN_SOLVE]
    for e in events:                            # all inside their solve
        assert any(s.start_ns <= e.start_ns and e.start_ns + e.duration_ns
                   <= s.start_ns + s.duration_ns for s in solve_ev)
