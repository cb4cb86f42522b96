"""System driver: a batch ``repro.solver.solve()`` of an edge list,
repeated.

Before anything else it asks the program for its edge-layout counters
(``repro.runtime.trace.edge_layout``): a program without them lays a
power-law graph out at its largest degree, which at the configured scale
does not fit the host, so the run stops there, within seconds. Set-up
makes the graph on the host (``lib/kronecker.py``: the configured
graph, its vertex numbering and weights from the seed) and
runs one whole solve (``solve()`` compiles as it runs). The window runs
whole solves of the same graph back to back, keeping each solve's
exemplars and the layout's counters, and only the last solve's result.
Afterwards the last window solve is compared with the plain reference
(``bench/reference/<reference>.py``):

* ``edge_miss``: on rows sampled from the seed, the 32 of highest degree
  among them, the graph's (dst, weight) pairs that are missing from the
  program's layout or stored with another weight, plus entries the
  layout holds that the graph does not;
* ``exemplar_diff``: the share of (level, node) exemplars, after each
  node follows its exemplar's exemplar, that differ from the reference's
  solve of the same graph (its own preference and sweeps).
"""
from __future__ import annotations

import time

import numpy as np

from lib import kronecker
from lib.spans import span, window

CHECK_STREAM = 3
HUBS = 32


def seed31(seed: int) -> int:
    """The solver's ``SolveConfig.seed`` (a 31-bit PRNG seed)."""
    return int(seed) % (1 << 31)


def make_graph(data: dict, seed: int):
    """-> (src, dst, weight, N) of the configured Kronecker graph."""
    return kronecker.generate(data["scale"], data["edgefactor"],
                              data["initiator"], data["graph_seed"], seed)


def solve_config(config: dict, seed: int):
    from repro.launch.mesh import make_worker_mesh
    from repro.solver import SolveConfig
    return SolveConfig(**config["solve"], seed=seed31(seed),
                       keep_state=True, mesh=make_worker_mesh(1))


def sample_rows(degrees: np.ndarray, count: int, seed: int) -> np.ndarray:
    """``count`` nodes: the ``HUBS`` of highest degree and the rest drawn
    from the seed."""
    hubs = np.argsort(-degrees, kind="stable")[:HUBS]
    rest = np.setdiff1d(np.arange(len(degrees)), hubs)
    rng = np.random.default_rng([int(seed) % (1 << 63), CHECK_STREAM])
    drawn = rng.choice(rest, size=min(count - len(hubs), len(rest)),
                       replace=False)
    return np.sort(np.concatenate([hubs, drawn]))


def _blocks(x):
    return x if isinstance(x, tuple) else (x,)


def program_rows(state, n: int, rows: np.ndarray) -> dict:
    """The program's stored (dst, weight) of each of ``rows``, in
    original ids, self slot and padding dropped: node -> (dst, weight)
    sorted by dst."""
    nodes = (np.arange(n) if state.nodes is None
             else np.asarray(state.nodes))
    laid = np.empty(n, np.int64)
    laid[nodes] = np.arange(n)
    idx_b = _blocks(state.idx)
    s_b = _blocks(state.hap.s)
    bounds = np.cumsum([0] + [i.shape[0] for i in idx_b])
    on_host = {}
    out = {}
    for node in rows:
        at = int(laid[node])
        b = int(np.searchsorted(bounds, at, side="right") - 1)
        if b not in on_host:
            on_host[b] = (np.asarray(idx_b[b]), np.asarray(s_b[b][0]))
        idx = on_host[b][0][at - bounds[b], 1:]
        val = on_host[b][1][at - bounds[b], 1:]
        real = idx != at
        dst, w = nodes[idx[real]], val[real]
        order = np.argsort(dst, kind="stable")
        out[int(node)] = (dst[order], w[order])
    return out


def edge_miss(graph, got: dict) -> int:
    src, dst, weight, _ = graph
    miss = 0
    for node, (g_dst, g_w) in got.items():
        lo, hi = np.searchsorted(src, [node, node + 1])
        want = set(zip(dst[lo:hi].tolist(), weight[lo:hi].tolist()))
        have = set(zip(g_dst.tolist(), g_w.tolist()))
        miss += len(want - have) + len(have - want)
        miss += len(g_dst) - len(have)                  # duplicates
    return miss


def reference_solve(ref, graph, config: dict, *, dtype: str = "float32"):
    """-> canonical (L, N) exemplars of the plain reference."""
    import jax.numpy as jnp
    src, dst, weight, n = graph
    sv = config["solve"]
    e = ref.sweeps(jnp.asarray(src), jnp.asarray(dst), jnp.asarray(weight),
                   ref.preference(weight), n=n, levels=sv["levels"],
                   iterations=sv["max_iterations"],
                   damping=float(sv["damping"]), dtype=dtype)
    return ref.canonical(np.asarray(e))


def exemplar_diff(got_e, ref_e) -> float:
    return float(np.mean(np.asarray(got_e) != np.asarray(ref_e)))


def run(ctx):
    try:
        from repro.runtime.trace import edge_layout
    except ImportError as exc:
        raise RuntimeError(
            "the program has no edge-layout counters (repro.runtime.trace."
            "edge_layout): it lays an edge list out at its largest degree, "
            "which at this scale does not fit; not running it") from exc
    edge_layout()
    import jax

    from repro.graph.edges import EdgeList
    from repro.solver import solve
    from run import Run

    graph = make_graph(ctx.config["data"], ctx.seed)
    src, dst, weight, n = graph
    el = EdgeList(src, dst, weight, n_nodes=n)
    cfg = solve_config(ctx.config, ctx.seed)
    last, layouts = [], []

    def call():
        last.clear()                  # one solve's result held at a time
        res = solve(el, cfg)
        jax.block_until_ready(res.state)
        last.append(res)
        layouts.append(edge_layout())
        return res.exemplars

    warm_e = call()
    compiles0 = ctx.compiles.count
    setup_s = time.perf_counter() - ctx.t_process
    layouts.clear()
    with window(ctx.trace_dir):
        calls = ctx.generator.drive(call, ctx.seconds, span)
    window_s = calls[-1][1] - calls[0][0]
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    window_compiles = ctx.compiles.count - compiles0

    res = last.pop()
    rows = sample_rows(np.bincount(src, minlength=n),
                       ctx.config["check"]["sample_rows"], ctx.seed)
    got = program_rows(res.state, n, rows)
    got_e = res.exemplars
    same = sum(bool(np.array_equal(c[2], warm_e)) for c in calls)
    walls = [c[1] - c[0] for c in calls]
    del calls, res

    ref_e = reference_solve(ctx.reference, graph, ctx.config)
    nums = {"edge_miss": float(edge_miss(graph, got)),
            "exemplar_diff": exemplar_diff(got_e, ref_e)}
    checks = [(name, nums[name], float(limit))
              for name, limit in ctx.config["limits"].items()]
    sv = ctx.config["solve"]
    lay = layouts[-1]
    host_s = [c["edge_layout.host_s"] for c in layouts]
    return Run(
        e2e={"setup_s": setup_s, "solve_s": window_s / len(walls)},
        attempted=len(walls), failed=0, checks=checks,
        data={"solves": len(walls), "sweeps": int(sv["max_iterations"]),
              "levels": int(sv["levels"]),
              "entries": int(lay["edge_layout.entries"]),
              "layout_host_s": host_s, "walls": walls},
        memory_peak_bytes=peak,
        notes={"solves": len(walls), "window_s": window_s,
               "solve_wall_s": walls, "window_compiles": window_compiles,
               "solves_equal_warmup": same, "n": n, "edges": len(src),
               "layout_entries": lay["edge_layout.entries"],
               "padded": lay["edge_layout.padded"],
               "buckets": lay["edge_layout.buckets"],
               "max_degree": lay["edge_layout.max_degree"],
               "layout_host_s": host_s})
