"""Split the sparse sweep program's device time by the program's named
scopes.

The program names the work inside one Jacobi sweep with ``jax.
named_scope`` (``repro.runtime.trace``): each op of the compiled sweep
program (``jit_`` + ``SWEEP_PROGRAM``, a name that holds ``run_topk``)
carries the path of scopes it was traced under as its ``op_name`` (a
fusion carries its root op's). The reduced
trace (``lib.trace.reduce``) keys each op's self time by its name alone,
so the path comes from the compiled program's HLO text: ``run_topk`` is
lowered again at the run's shapes and its cell's settings, which JAX's
in-memory cache holds from the window, so nothing compiles again. An
op's name is unique within its program, and the trace and the HLO name
it alike.

Each op of the sweep program falls in one part:

* ``colsum``: its path holds ``hap_colsum`` (the availability and tau
  column sums, a scatter-add over the stored edges);
* ``gather``: it holds ``hap_gather`` (the column statistics gathered
  back through the column map in the alpha update);
* ``rowwise``: it holds another sweep scope (rho top-2, phi, c, damping,
  assign and the change count);
* ``unscoped``: no sweep scope (the loop's own ops, copies, ops the
  compiler made without a path), and any op the HLO does not name.

``split`` puts the four parts, in device milliseconds per sweep, into
the run's notes (``sweep_scopes_ms``), with the longest unscoped ops, so
every traced run shows how much of ``topk_sweep_ms`` the scopes cover,
and the seconds the HLO took to get (``sweep_hlo_s``: milliseconds when
the in-memory cache holds the program).
A program without the scopes (``repro.runtime.trace`` missing) gives
nothing.
"""
from __future__ import annotations

import json
import os
import re
import time

PARTS = ("colsum", "gather", "rowwise", "unscoped")
NOTE = "sweep_scopes_ms"
NOTE_OPS = "sweep_unscoped_ops"
NOTE_HLO_S = "sweep_hlo_s"
TOP_UNSCOPED = 5

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_INSTR = re.compile(
    r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?metadata=\{[^}]*?op_name="([^"]*)"')


def op_paths(hlo_text: str) -> dict:
    """{instruction name: op_name path} of each instruction in an HLO
    module's text that carries an ``op_name``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def part_of(path: str | None, scopes) -> str:
    """The part an op with this ``op_name`` path falls in (module doc);
    ``scopes`` is ``repro.runtime.trace``."""
    names = set(path.split("/")) if path else set()
    if scopes.SCOPE_COLSUM in names:
        return "colsum"
    if scopes.SCOPE_GATHER in names:
        return "gather"
    if names & set(scopes.SWEEP_SCOPES):
        return "rowwise"
    return "unscoped"


def sweep_module(scopes) -> str:
    """The sweep program's module name in a trace."""
    return "jit_" + scopes.SWEEP_PROGRAM


def split_seconds(op_s: dict, paths: dict, scopes) -> tuple:
    """Self seconds of the sweep program's ops by part -> ({part: s},
    [(op, s)] of the unscoped ops, longest first)."""
    parts = dict.fromkeys(PARTS, 0.0)
    unscoped = []
    prefix = sweep_module(scopes) + "/"
    for key, s in op_s.items():
        if not key.startswith(prefix):
            continue
        op = key[len(prefix):]
        part = part_of(paths.get(op), scopes)
        parts[part] += s
        if part == "unscoped":
            unscoped.append((op, s))
    unscoped.sort(key=lambda kv: -kv[1])
    return parts, unscoped


def solve_settings(data: dict) -> dict | None:
    """The ``solve`` settings of the one batch configuration in
    ``BENCHMARK.json`` whose sizes the run reports, else None."""
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    found = []
    for entry in spec["configs"]:
        with open(os.path.join(_ROOT, entry["file"])) as f:
            config = json.load(f)
        sv = config.get("solve", {})
        if (config.get("system") == "batch_solve"
                and config.get("data", {}).get("n_points") == data["n"]
                and sv.get("k", -1) + 1 == data["kk"]
                and sv.get("levels") == data["levels"]
                and sv.get("max_iterations") == data["sweeps"]):
            found.append(sv)
    return found[0] if len(found) == 1 else None


def sweep_hlo(data: dict, solve: dict) -> str:
    """Optimized HLO text of the run's ``run_topk`` program."""
    import jax
    import jax.numpy as jnp

    from repro.solver import SolveConfig
    from repro.solver.topk import run_topk

    cfg = SolveConfig(**solve)
    n, kk, levels = data["n"], data["kk"], data["levels"]
    return run_topk.lower(
        jax.ShapeDtypeStruct((levels, n, kk), jnp.float32),
        jax.ShapeDtypeStruct((n, kk), jnp.int32),
        max_iterations=cfg.max_iterations, damping=cfg.damping,
        kappa=cfg.kappa, s_mode=cfg.s_mode, stop=cfg.stop,
        patience=cfg.patience).compile().as_text()


def split(run, trace) -> dict | None:
    """{part: device ms per sweep} of the window's sweeps (module doc),
    also written to ``run.notes``; None without a trace, a sweep
    program, the program's scopes or the run's configuration."""
    if NOTE in run.notes:
        return run.notes[NOTE]
    data = run.data
    if trace is None or data.get("solves", 0) < 1 or "kk" not in data:
        return None
    try:
        from repro.runtime import trace as scopes
    except ImportError:
        return None
    if sweep_module(scopes) not in trace["module_s"]:
        return None
    solve = solve_settings(data)
    if solve is None:
        return None
    t0 = time.perf_counter()
    paths = op_paths(sweep_hlo(data, solve))
    run.notes[NOTE_HLO_S] = time.perf_counter() - t0
    parts, unscoped = split_seconds(trace["op_s"], paths, scopes)
    per = 1e3 / (data["solves"] * data["sweeps"])
    run.notes[NOTE] = {p: s * per for p, s in parts.items()}
    run.notes[NOTE_OPS] = [[op, s * per] for op, s in
                           unscoped[:TOP_UNSCOPED]]
    return run.notes[NOTE]


def read(run, trace, part: str) -> float | None:
    """One part's device ms per sweep, for a metric reader."""
    parts = split(run, trace)
    if parts is None or parts[part] <= 0:
        return None
    return parts[part]
