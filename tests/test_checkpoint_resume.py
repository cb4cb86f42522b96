"""Solver checkpoint/resume: interrupted solves resume *bit-exact*.

The contract under test: a solve with ``checkpoint_every`` set runs the
same op sequence as an uncheckpointed one per segment program, snapshots
the compressed message state at segment boundaries, and a crash +
``resume_from`` replays to exactly the assignments and trace tail the
uninterrupted run produces. Crashes are injected deterministically via
``repro.runtime.faultinject`` — the sites fire *after* each save, so an
injected crash always leaves a resumable directory behind.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers.parity import assert_values_close
from repro.data import gaussian_blobs
from repro.launch.mesh import make_worker_mesh
from repro.runtime import faultinject
from repro.runtime.faultinject import FaultInjector, InjectedFault, Rule
from repro.solver import SolveConfig, solve
from repro.solver import checkpointing, topk


def _pts(n=160, seed=0):
    x, _ = gaussian_blobs(n=n, k=5, seed=seed, spread=0.3, box=14.0)
    return x


def _assert_same(a, b):
    np.testing.assert_array_equal(a.labels, b.labels)
    np.testing.assert_array_equal(a.exemplars, b.exemplars)
    assert a.n_sweeps == b.n_sweeps and a.converged == b.converged
    np.testing.assert_array_equal(a.trace, b.trace)


# --------------------------------------------------- dense_topk (single)
@pytest.mark.parametrize("stop", ["converged", "fixed"])
def test_checkpointed_solve_matches_plain(tmp_path, stop):
    """checkpoint_every on, no crash: identical to the plain solve —
    checkpointing must be observationally free."""
    x = _pts()
    cfg = SolveConfig(backend="dense_topk", k=16, stop=stop,
                      max_iterations=40, patience=5, preference="median")
    plain = solve(x, cfg)
    ckpt = solve(x, cfg.replace(checkpoint_every=3,
                                checkpoint_dir=str(tmp_path / "ck")))
    _assert_same(ckpt, plain)


def test_crash_resume_is_bit_exact(tmp_path):
    """Kill the solve at the second segment boundary; resume finishes
    with the uninterrupted run's exact assignments and trace tail."""
    x = _pts()
    d = str(tmp_path / "ck")
    cfg = SolveConfig(backend="dense_topk", k=16, stop="converged",
                      max_iterations=60, patience=5, preference="median",
                      checkpoint_every=4, checkpoint_dir=d)
    plain = solve(x, cfg.replace(checkpoint_every=0, checkpoint_dir=None))

    inj = FaultInjector().add(Rule("solver.sweep", nth=1))
    with faultinject.active(inj), pytest.raises(InjectedFault):
        solve(x, cfg)
    resumed = solve(x, cfg.replace(resume_from=d))
    _assert_same(resumed, plain)


def test_resume_skips_completed_sweeps(tmp_path):
    """The resumed run fires fewer segment boundaries than a fresh one —
    proof it restored state instead of recomputing from sweep 0."""
    x = _pts()
    d = str(tmp_path / "ck")
    cfg = SolveConfig(backend="dense_topk", k=16, stop="fixed",
                      max_iterations=20, preference="median",
                      checkpoint_every=4, checkpoint_dir=d)
    inj_full = FaultInjector()
    with faultinject.active(inj_full):
        solve(x, cfg)
    full_hits = inj_full.hits("solver.sweep")

    inj = FaultInjector().add(Rule("solver.sweep", nth=2))
    with faultinject.active(inj), pytest.raises(InjectedFault):
        solve(x, cfg)
    inj_resume = FaultInjector()
    with faultinject.active(inj_resume):
        solve(x, cfg.replace(resume_from=d))
    assert 0 < inj_resume.hits("solver.sweep") < full_hits


# ------------------------------------------------- dense_topk (sharded)
def test_sharded_crash_resume_bit_exact(tmp_path):
    """The sharded sweep program checkpoints/resumes to the
    single-device oracle's exemplars, sweep count and trace exactly, and
    its message state within the numeric contract (tests/helpers/
    parity.py) — driven directly so a 1-device host still exercises the
    shard_map program; the 8-device variant is
    tests/helpers/resume_parity_check.py."""
    x = _pts(n=96)
    cfg = SolveConfig(k=12, stop="converged", max_iterations=25,
                      patience=5, damping=0.7, preference="median",
                      checkpoint_every=4,
                      checkpoint_dir=str(tmp_path / "ck"),
                      exchange="allgather")
    s3k, idx = topk.build_from_points(
        jnp.asarray(x), cfg.k, cfg.levels, metric=cfg.metric,
        preference=cfg.preference, key=jax.random.PRNGKey(cfg.seed),
        config=cfg)
    o_state, o_e, o_sweeps, o_conv, o_trace = topk.run_topk(
        s3k, idx, max_iterations=cfg.max_iterations, damping=cfg.damping,
        kappa=cfg.kappa, s_mode=cfg.s_mode, stop=cfg.stop,
        patience=cfg.patience)

    mesh = make_worker_mesh()
    inj = FaultInjector().add(
        Rule("solver.sweep", nth=1, match={"kind": "sharded"}))
    with faultinject.active(inj), pytest.raises(InjectedFault):
        checkpointing.run_topk_checkpointed(s3k, idx, cfg, mesh=mesh)
    state, e, n_sweeps, conv, trace = checkpointing.run_topk_checkpointed(
        s3k, idx, cfg.replace(resume_from=cfg.checkpoint_dir), mesh=mesh)
    np.testing.assert_array_equal(np.asarray(e), np.asarray(o_e))
    assert int(n_sweeps) == int(o_sweeps) and bool(conv) == bool(o_conv)
    np.testing.assert_array_equal(np.asarray(trace), np.asarray(o_trace))
    jax.tree.map(assert_values_close, state, o_state)


# -------------------------------------------------------------- coarsen
COARSEN_CFG = dict(backend="coarsen", partition_size=64, coarsen_batch=2,
                   stop="converged", max_iterations=60, patience=5,
                   preference="median")


def test_coarsen_midlocal_crash_resume(tmp_path):
    """A crash between local batch groups resumes at the interrupted
    group — same final result, fewer re-fired group boundaries."""
    x = _pts(n=600, seed=3)
    d = str(tmp_path / "ck")
    cfg = SolveConfig(**COARSEN_CFG, checkpoint_every=2, checkpoint_dir=d)
    plain = solve(x, cfg.replace(checkpoint_every=0, checkpoint_dir=None))

    inj = FaultInjector().add(
        Rule("solver.coarsen", nth=1, match={"stage": "local"}))
    with faultinject.active(inj), pytest.raises(InjectedFault):
        solve(x, cfg)
    inj_resume = FaultInjector()
    with faultinject.active(inj_resume):
        resumed = solve(x, cfg.replace(resume_from=d))
    _assert_same(resumed, plain)
    # the resumed run revisits strictly fewer stage boundaries than the
    # 2 local-group fires + 1 global fire a fresh run pays
    assert inj_resume.hits("solver.coarsen") < inj.hits("solver.coarsen") + 2


def test_coarsen_global_stage_crash_resume(tmp_path):
    """A crash after the global exemplar solve's artifact saved resumes
    past stage 3 entirely (the global solve is not re-run)."""
    x = _pts(n=600, seed=3)
    d = str(tmp_path / "ck")
    cfg = SolveConfig(**COARSEN_CFG, checkpoint_every=2, checkpoint_dir=d)
    plain = solve(x, cfg.replace(checkpoint_every=0, checkpoint_dir=None))

    inj = FaultInjector().add(
        Rule("solver.coarsen", match={"stage": "global"}))
    with faultinject.active(inj), pytest.raises(InjectedFault):
        solve(x, cfg)
    inj_resume = FaultInjector()
    with faultinject.active(inj_resume):
        resumed = solve(x, cfg.replace(resume_from=d))
    _assert_same(resumed, plain)
    assert not [e for e in inj_resume.events]          # nothing re-fired
    assert inj_resume.hits("solver.coarsen") == 0      # stages all cached


# ---------------------------------------------------------- guard rails
def test_resume_rejects_mismatched_config(tmp_path):
    x = _pts()
    d = str(tmp_path / "ck")
    cfg = SolveConfig(backend="dense_topk", k=16, max_iterations=20,
                      stop="fixed", preference="median",
                      checkpoint_every=4, checkpoint_dir=d)
    solve(x, cfg)
    with pytest.raises(ValueError, match="checkpoint"):
        solve(x, cfg.replace(resume_from=d, damping=0.8))


def test_resume_refuses_checkpoint_without_carried_sums(tmp_path):
    """A checkpoint written before the loop carried alpha's column sums
    (its meta has no ``carry`` entry) is refused, not resumed."""
    import json
    import os

    x = _pts()
    d = str(tmp_path / "ck")
    cfg = SolveConfig(backend="dense_topk", k=16, levels=3,
                      max_iterations=20, stop="fixed", preference="median",
                      checkpoint_every=4, checkpoint_dir=d)
    solve(x, cfg)
    path = os.path.join(d, checkpointing.META_NAME)
    with open(path) as f:
        meta = json.load(f)
    assert meta.pop("carry") == ["col"]
    with open(path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(ValueError, match="carry"):
        solve(x, cfg.replace(resume_from=d))


def test_checkpoint_config_validation():
    x = _pts(n=32)
    with pytest.raises(ValueError, match="checkpoint_every"):
        solve(x, SolveConfig(backend="dense_topk", k=8,
                             checkpoint_every=-1))
    with pytest.raises(ValueError, match="checkpoint_dir"):
        solve(x, SolveConfig(backend="dense_topk", k=8,
                             checkpoint_every=2))
    with pytest.raises(ValueError, match="dense_parallel"):
        solve(x, SolveConfig(backend="dense_parallel",
                             checkpoint_every=2, checkpoint_dir="/tmp/x"))
