"""Sharded dense_topk sweeps (docs/solver.md "Distributed sweeps").

Contracts:

* ``run_topk_sharded`` on a degenerate 1-worker mesh matches the
  single-device ``run_topk`` oracle under the numeric contract
  (``tests/helpers/parity.py``): exemplars, trace, and the converged-stop
  sweep count exactly, the full message state within tolerance — for
  both exchanges and both stopping rules (the real 8-worker parity check,
  including duplicate-heavy tie-breaks across shard boundaries, runs in
  the nightly slow tier via ``tests/helpers/topk_sweep_dist_check.py``);
* padding inserts inert dummy rows (self-pointing edges, repelling
  values) and the engine strips them;
* the ``sweep``/``exchange`` knobs resolve and validate at the front
  door, and a 1-device host falls back to the single-device loop;
* ``maybe_init_distributed`` is a strict no-op without a multi-process
  environment.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from helpers.parity import assert_values_close
from repro.launch.mesh import make_worker_mesh
from repro.launch.mesh import maybe_init_distributed
from repro.solver import solve
from repro.solver.topk import build_from_points, run_topk
from repro.solver.topk_sharded import (
    ALLGATHER_MAX_ELEMS, EXCHANGE_MODES, SHARDED_SWEEP_N, SWEEP_MODES,
    comm_bytes_per_sweep, pad_topk, resolve_exchange, resolve_sweep,
    run_topk_sharded,
)


def _dup_points(n=150, seed=3):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((4, 2)).astype(np.float32) * 4.0
    x = centers[rng.integers(0, 4, n)]
    x[: n // 2] += 0.05 * rng.standard_normal((n // 2, 2)).astype(np.float32)
    return x                               # second half: exact duplicates


# ---------------------------------------------------------------- parity
@pytest.mark.parametrize("stop", ["fixed", "converged"])
@pytest.mark.parametrize("exchange", ["allgather", "psum"])
def test_single_worker_mesh_bit_exact(stop, exchange):
    """W=1 runs the full shard_map program (identity collectives); both
    exchanges must reproduce the oracle's exemplars, trace and stop
    exactly, and its message state within the numeric contract."""
    s3k, idx = build_from_points(jnp.asarray(_dup_points()), 12, 3)
    st, e, ns, conv, tr = run_topk(
        s3k, idx, max_iterations=25, damping=0.7, stop=stop, patience=5)
    st2, e2, ns2, conv2, tr2 = run_topk_sharded(
        s3k, idx, make_worker_mesh(), max_iterations=25, damping=0.7,
        stop=stop, patience=5, exchange=exchange)
    n = e.shape[1]
    np.testing.assert_array_equal(np.asarray(e), np.asarray(e2)[:, :n])
    np.testing.assert_array_equal(np.asarray(tr), np.asarray(tr2))
    assert int(ns) == int(ns2) and bool(conv) == bool(conv2)
    for f in ("s", "r", "a", "tau", "phi", "c"):
        assert_values_close(np.asarray(getattr(st2.hap, f))[:, :n],
                            getattr(st.hap, f), err_msg=f)


def test_levels_1_edge_case():
    s3k, idx = build_from_points(jnp.asarray(_dup_points(100)), 9, 1)
    _, e, *_ = run_topk(s3k, idx, max_iterations=10, damping=0.7)
    _, e2, *_ = run_topk_sharded(
        s3k, idx, make_worker_mesh(), max_iterations=10, damping=0.7)
    np.testing.assert_array_equal(np.asarray(e),
                                  np.asarray(e2)[:, : e.shape[1]])


def test_solve_sharded_matches_single_end_to_end():
    x = _dup_points(130)
    ref = solve(x, backend="dense_topk", k=16, levels=2, max_iterations=20,
                stop="converged", sweep="single")
    res = solve(x, backend="dense_topk", k=16, levels=2, max_iterations=20,
                stop="converged", sweep="sharded")
    # one host device: the backend falls back to the single-device loop,
    # so this pins the fallback branch AND end-to-end equality on
    # multi-device hosts (where the sharded program actually runs)
    np.testing.assert_array_equal(res.exemplars, ref.exemplars)
    np.testing.assert_array_equal(res.labels, ref.labels)
    assert res.n_sweeps == ref.n_sweeps
    assert res.converged == ref.converged


# --------------------------------------------------------------- padding
def test_pad_topk_inert_dummies():
    s3k, idx = build_from_points(jnp.asarray(_dup_points(100)), 8, 2)
    s_p, idx_p, n_real = pad_topk(s3k, idx, 8)
    assert n_real == 100 and s_p.shape[1] == 104 and idx_p.shape[0] == 104
    # dummy edges all point back at the dummy row itself, values repel
    pads_i = np.asarray(idx_p)[100:]
    assert np.array_equal(pads_i, np.repeat(np.arange(100, 104)[:, None],
                                            idx_p.shape[1], axis=1))
    pads_v = np.asarray(s_p)[:, 100:, :]
    assert np.all(pads_v[:, :, 0] == -1.0e9)
    assert np.all(pads_v[:, :, 1:] == -2.0e9)
    # real rows untouched
    np.testing.assert_array_equal(np.asarray(s_p)[:, :100], np.asarray(s3k))
    # already divisible: strict passthrough
    s_q, idx_q, n_q = pad_topk(s3k, idx, 4)
    assert s_q is s3k and idx_q is idx and n_q == 100


# ---------------------------------------------------------- knob routing
def test_sweep_resolution_rules():
    assert set(SWEEP_MODES) == {"auto", "single", "sharded"}
    assert resolve_sweep("auto", n=SHARDED_SWEEP_N, n_devices=8) == "sharded"
    assert resolve_sweep("auto", n=SHARDED_SWEEP_N - 1,
                         n_devices=8) == "single"
    assert resolve_sweep("auto", n=10**6, n_devices=1) == "single"
    assert resolve_sweep("sharded", n=100, n_devices=1) == "sharded"
    assert resolve_sweep("single", n=10**6, n_devices=8) == "single"
    with pytest.raises(ValueError, match="sweep mode"):
        resolve_sweep("nope", n=100)


def test_exchange_resolution_rules():
    assert set(EXCHANGE_MODES) == {"auto", "allgather", "psum"}
    assert resolve_exchange("auto", n=1000, kk=33) == "allgather"
    assert resolve_exchange("auto", n=ALLGATHER_MAX_ELEMS // 33 + 1,
                            kk=33) == "psum"
    assert resolve_exchange("psum", n=10, kk=3) == "psum"
    with pytest.raises(ValueError, match="exchange mode"):
        resolve_exchange("nope", n=100, kk=9)


def test_invalid_knobs_rejected_at_entry():
    x = np.zeros((10, 2), np.float32)
    with pytest.raises(ValueError, match="SolveConfig.sweep"):
        solve(x, backend="dense_topk", sweep="nope")
    with pytest.raises(ValueError, match="SolveConfig.exchange"):
        solve(x, backend="dense_topk", exchange="nope")


def test_non_worker_mesh_rejected():
    from repro.launch.mesh import make_mesh
    s3k, idx = build_from_points(jnp.asarray(_dup_points(40)), 5, 2)
    bad = make_mesh((1, 1), ("rows", "cols"))
    with pytest.raises(ValueError, match="1-D mesh"):
        run_topk_sharded(s3k, idx, bad, max_iterations=3)


def test_comm_volume_psum_beats_allgather_at_large_k():
    ag = comm_bytes_per_sweep(10**6, 64, 3, 8, "allgather")
    ps = comm_bytes_per_sweep(10**6, 64, 3, 8, "psum")
    assert ps < ag / 8                     # the O(N*k) -> O(N) win


# ------------------------------------------------------- jax.distributed
def test_maybe_init_distributed_single_process_noop(monkeypatch):
    for var in ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
                "JAX_NUM_PROCESSES", "NUM_PROCESSES", "JAX_PROCESS_ID",
                "PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    assert maybe_init_distributed() is False
    # an advertised single-process "cluster" must also be a no-op
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "127.0.0.1:1234")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "1")
    assert maybe_init_distributed() is False


# ------------------------------------------------------------- slow tier
HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                      "topk_sweep_dist_check.py")


@pytest.mark.slow
def test_sharded_sweep_8_workers():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, HELPER], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
