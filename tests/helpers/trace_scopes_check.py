"""Subprocess helper: the op paths of the row-sharded sparse sweep on two
forced host devices.

Compiles the ``run_topk_sharded`` program for both exchanges (``psum``,
``allgather``) at a small N and prints one JSON line per exchange:
``[name, opcode, op_name path]`` of every optimized-HLO instruction that
carries a path. ``tests/test_trace_scopes.py`` checks the scopes on
them.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import json
import re
import sys

import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_worker_mesh
from repro.solver.topk import build_from_points
from repro.solver.topk_sharded import _sharded_program, pad_topk

OP = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s*\S+\s+([\w\-]+)\(.*?'
                r'op_name="([^"]*)"')


def main() -> int:
    mesh = make_worker_mesh()
    workers = mesh.shape["workers"]
    if workers != 2:
        print(f"expected 2 workers, got {mesh.shape}", file=sys.stderr)
        return 1
    x = np.random.default_rng(0).standard_normal((256, 3)).astype(
        np.float32)
    s3k, idx = build_from_points(jnp.asarray(x), 8, 2)
    s3k_p, idx_p, n_real = pad_topk(s3k, idx, workers)
    for exchange in ("psum", "allgather"):
        fn = _sharded_program(mesh, n_real, exchange, max_iterations=2,
                              damping=0.7, kappa=0.0, s_mode="off",
                              stop="fixed", patience=5)
        text = fn.lower(s3k_p, idx_p).compile().as_text()
        ops = [list(m.groups()) for m in map(OP.match, text.splitlines())
               if m]
        print(json.dumps({"exchange": exchange, "ops": ops}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
