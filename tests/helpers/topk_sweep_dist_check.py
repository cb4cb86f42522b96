"""Subprocess helper: sharded dense_topk *sweep* parity on 8 forced host
devices — the ISSUE-6 acceptance check.

N=1000 does not divide 8 workers, so the driver pads with inert dummy
rows; the input is duplicate-heavy (exact duplicate points produce tied
(alpha + rho) rows whose Eq 2.8 decode exercises the (value desc,
col asc) tie-break across shard boundaries). Checked against the
single-device ``run_topk`` oracle:

* ``exchange="allgather"``: exact exemplars and per-sweep trace, the
  full message state (s/r/a/tau/phi/c) within the numeric contract
  (``parity.py``), for both stopping rules; ``stop="converged"`` exits
  on the same sweep with the same flag.
* ``exchange="psum"``: identical exemplar sets per level (documented
  float-associativity tolerance on the messages), same converged sweep.
* the ``solve()`` front door with ``sweep="sharded"`` equals
  ``sweep="single"`` end-to-end.

Exits nonzero on any mismatch.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax.numpy as jnp
import numpy as np
from parity import state_close

from repro.launch.mesh import make_worker_mesh
from repro.solver import solve
from repro.solver.topk import build_from_points, run_topk
from repro.solver.topk_sharded import run_topk_sharded

N, K, LEVELS = 1000, 24, 3


def duplicate_heavy_points(n: int, seed: int = 4) -> np.ndarray:
    """A few tight centers plus many *exact* duplicates: tied messages
    whose decode must break ties identically on every shard layout."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((5, 3)).astype(np.float32) * 4.0
    x = centers[rng.integers(0, 5, n)]
    x[: n // 2] += 0.05 * rng.standard_normal((n // 2, 3)).astype(np.float32)
    return x          # second half: exact duplicates of the 5 centers


def main() -> int:
    x = duplicate_heavy_points(N)
    s3k, idx = build_from_points(jnp.asarray(x), K, LEVELS)
    mesh = make_worker_mesh()
    assert mesh.shape["workers"] == 8, mesh.shape
    ok = True

    for stop in ("fixed", "converged"):
        st, e, ns, conv, tr = run_topk(
            s3k, idx, max_iterations=40, damping=0.7, stop=stop, patience=5)
        e, tr = np.asarray(e), np.asarray(tr)

        st2, e2, ns2, conv2, tr2 = run_topk_sharded(
            s3k, idx, mesh, max_iterations=40, damping=0.7, stop=stop,
            patience=5, exchange="allgather")
        exact = (np.array_equal(e, np.asarray(e2)[:, :N])
                 and np.array_equal(tr, np.asarray(tr2))
                 and int(ns) == int(ns2) and bool(conv) == bool(conv2))
        close = state_close(st.hap, st2.hap, N)
        print(f"[{stop}] allgather x 8 workers: exemplars_trace_exact="
              f"{exact} state_within_contract={close} "
              f"(sweeps {int(ns)} vs {int(ns2)})")
        ok &= exact and close

        st3, e3, ns3, conv3, _ = run_topk_sharded(
            s3k, idx, mesh, max_iterations=40, damping=0.7, stop=stop,
            patience=5, exchange="psum")
        e3 = np.asarray(e3)[:, :N]
        sets = all(set(np.unique(e3[l])) == set(np.unique(e[l]))
                   for l in range(LEVELS))
        lock = int(ns3) == int(ns) and bool(conv3) == bool(conv)
        print(f"[{stop}] psum x 8 workers: exemplar_sets_equal={sets} "
              f"same_stop={lock} (sweeps {int(ns)} vs {int(ns3)})")
        ok &= sets and lock

    ref = solve(x, backend="dense_topk", k=K, levels=2, max_iterations=25,
                stop="converged", sweep="single")
    res = solve(x, backend="dense_topk", k=K, levels=2, max_iterations=25,
                stop="converged", sweep="sharded", exchange="allgather")
    same = (np.array_equal(res.exemplars, ref.exemplars)
            and res.n_sweeps == ref.n_sweeps
            and res.converged == ref.converged)
    print(f"solve(sweep='sharded') x 8 workers: end_to_end_equal={same}")
    ok &= same
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
