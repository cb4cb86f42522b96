"""Subprocess helper: elastic restart. Phase 1 trains on a (2,2) mesh and
checkpoints; phase 2 restores onto a (1,2) mesh (half the devices lost)
and keeps training — losses must continue from the same state."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.data.pipeline import synthetic_token_stream
from repro.models import Mode, model_init
from repro.runtime.elastic import reshard_state
from repro.sharding import shape_safe_shardings
from repro.launch.mesh import make_mesh
from repro.train.loop import (
    init_train_state, make_train_step, train_state_specs,
)


def mesh_of(shape):
    n = int(np.prod(shape))
    return make_mesh(shape, ("data", "model"),
                     devices=jax.devices()[:n])


def main() -> int:
    cfg = get_arch("tinyllama-1.1b-smoke")
    params, specs = model_init(jax.random.PRNGKey(0), cfg)
    state = init_train_state(params)
    state_specs = train_state_specs(specs)
    step = make_train_step(cfg, Mode("train", "dense"),
                           lr_kwargs={"peak": 1e-3, "warmup": 2,
                                      "total": 20})
    stream = synthetic_token_stream(cfg.vocab, 8, 32, seed=0)
    batches = [jnp.asarray(next(stream)) for _ in range(8)]
    ckdir = tempfile.mkdtemp()

    # ---- phase 1: 4 devices (2 data x 2 model)
    mesh1 = mesh_of((2, 2))
    sds = jax.eval_shape(lambda: state)
    shard1 = shape_safe_shardings(mesh1, sds, state_specs)
    with jax.set_mesh(mesh1):
        st = reshard_state(state, state_specs, mesh1)
        fn = jax.jit(step, in_shardings=(shard1, None),
                     out_shardings=(shard1, None))
        for b in batches[:4]:
            st, m = fn(st, {"tokens": b})
        mgr = CheckpointManager(ckdir, async_save=False)
        mgr.save(4, st)
        # continue on the SAME mesh for the reference losses
        ref_losses = []
        for b in batches[4:]:
            st, m = fn(st, {"tokens": b})
            ref_losses.append(float(m["loss"]))

    # ---- phase 2: "pod lost": restore onto 2 devices (1 data x 2 model)
    mesh2 = mesh_of((1, 2))
    _, restored = CheckpointManager(ckdir).restore_latest(state)
    with jax.set_mesh(mesh2):
        st2 = reshard_state(restored, state_specs, mesh2)
        shard2 = shape_safe_shardings(mesh2, jax.eval_shape(lambda: state),
                                      state_specs)
        fn2 = jax.jit(step, in_shardings=(shard2, None),
                      out_shardings=(shard2, None))
        new_losses = []
        for b in batches[4:]:
            st2, m = fn2(st2, {"tokens": b})
            new_losses.append(float(m["loss"]))

    err = max(abs(a - b) for a, b in zip(ref_losses, new_losses))
    print(f"ref={ref_losses} new={new_losses} err={err:.2e}")
    return 0 if err < 1e-3 else 1


if __name__ == "__main__":
    sys.exit(main())
