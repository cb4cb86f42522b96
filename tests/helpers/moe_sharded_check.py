"""Subprocess helper: sharded-MoE vs dense equality on a 2x2 mesh, both
expert-parallel (E=8 over model=2) and ffn-parallel (E=3) layouts."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers.moe import _moe_dense, moe_apply, moe_init
from repro.launch.mesh import make_mesh


def main() -> int:
    key = jax.random.PRNGKey(0)
    ok = True
    for e, label in [(8, "expert-parallel"), (3, "ffn-parallel")]:
        p, _ = moe_init(key, 32, 64, e)
        x = jax.random.normal(key, (4, 16, 32), jnp.float32) * 0.5
        dense = _moe_dense(p, x, top_k=2, capacity_factor=8.0)
        mesh = make_mesh((2, 2), ("data", "model"))
        with jax.set_mesh(mesh):
            sh = jax.jit(lambda p, x: moe_apply(
                p, x, top_k=2, capacity_factor=8.0))(p, x)
        dy = float(jnp.max(jnp.abs(sh.y - dense.y)))
        da = abs(float(sh.aux_loss) - float(dense.aux_loss))
        print(f"{label}: max|dy|={dy:.2e} |daux|={da:.2e}")
        if dy > 1e-5 or da > 1e-5:
            ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
