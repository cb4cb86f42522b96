"""Subprocess helper: sharded checkpoint/resume parity on 8 forced host
devices — the ISSUE-10 acceptance check for real multi-worker resume.

N=1000 does not divide 8 workers, so the checkpointed sharded run pads
with inert dummy rows; checkpoints store the *unpadded logical* state
and resume re-pads it, which this check exercises against two oracles:

* an uninterrupted single-device ``run_topk`` run — exact exemplars,
  sweep count and per-sweep trace, full message state within the numeric
  contract (``parity.py``);
* a crash (injected at the second segment boundary via
  ``repro.runtime.faultinject``) + resume — the same contract, and the
  resumed run fires strictly fewer segment boundaries than a fresh run
  (proof it restored state instead of recomputing).

Exits nonzero on any mismatch.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from parity import assert_values_close

from repro.launch.mesh import make_worker_mesh
from repro.runtime import faultinject
from repro.runtime.faultinject import FaultInjector, InjectedFault, Rule
from repro.solver import SolveConfig, checkpointing
from repro.solver.topk import build_from_points, run_topk

N, K, LEVELS = 1000, 24, 3


def main() -> int:
    rng = np.random.default_rng(4)
    centers = rng.normal(size=(6, 3)) * 8
    x = (centers[rng.integers(0, 6, N)]
         + rng.normal(size=(N, 3)) * 0.25).astype(np.float32)

    mesh = make_worker_mesh()
    assert mesh.shape["workers"] == 8, mesh.shape

    with tempfile.TemporaryDirectory() as d:
        cfg = SolveConfig(k=K, levels=LEVELS, stop="converged",
                          max_iterations=60, patience=5, damping=0.7,
                          preference="median", exchange="allgather",
                          checkpoint_every=4, checkpoint_dir=d)
        s3k, idx = build_from_points(
            jnp.asarray(x), K, LEVELS, metric=cfg.metric,
            preference=cfg.preference, key=jax.random.PRNGKey(cfg.seed),
            config=cfg)
        o_state, o_e, o_sweeps, o_conv, o_trace = run_topk(
            s3k, idx, max_iterations=cfg.max_iterations,
            damping=cfg.damping, kappa=cfg.kappa, s_mode=cfg.s_mode,
            stop=cfg.stop, patience=cfg.patience)

        def check(tag, got):
            state, e, n_sweeps, conv, trace = got
            np.testing.assert_array_equal(np.asarray(e), np.asarray(o_e))
            assert int(n_sweeps) == int(o_sweeps), (
                tag, int(n_sweeps), int(o_sweeps))
            assert bool(conv) == bool(o_conv), tag
            np.testing.assert_array_equal(np.asarray(trace),
                                          np.asarray(o_trace))
            jax.tree.map(assert_values_close, state, o_state)
            print(f"{tag}: matches the single-device oracle "
                  f"(sweeps={int(n_sweeps)})")

        # uninterrupted checkpointed sharded run
        check("sharded checkpointed",
              checkpointing.run_topk_checkpointed(s3k, idx, cfg,
                                                  mesh=mesh))

        # crash at the 2nd segment boundary, then resume
        inj_fresh = FaultInjector().add(
            Rule("solver.sweep", nth=1, match={"kind": "sharded"}))
        crashed = False
        with faultinject.active(inj_fresh):
            try:
                checkpointing.run_topk_checkpointed(s3k, idx, cfg,
                                                    mesh=mesh)
            except InjectedFault:
                crashed = True
        assert crashed, "injected crash did not fire"

        inj_resume = FaultInjector()
        with faultinject.active(inj_resume):
            check("sharded interrupt+resume",
                  checkpointing.run_topk_checkpointed(
                      s3k, idx, cfg.replace(resume_from=d), mesh=mesh))
        fresh_hits = inj_fresh.hits("solver.sweep")
        resume_hits = inj_resume.hits("solver.sweep")
        assert 0 < resume_hits, "resume fired no segment boundaries"
        assert resume_hits + fresh_hits <= (
            (int(o_sweeps) + cfg.checkpoint_every - 1)
            // cfg.checkpoint_every + 1), (
            "crash+resume did more segments than one fresh run",
            fresh_hits, resume_hits)
        print(f"resume skipped completed segments "
              f"(fresh-before-crash={fresh_hits}, resumed={resume_hits})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
