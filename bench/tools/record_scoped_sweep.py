#!/usr/bin/env python3
"""Record the small scoped sweep trace the trace tests read.

    python3 bench/tools/record_scoped_sweep.py [--out bench/tests/data]

Runs the sparse sweep program (``repro.solver.topk.run_topk``) once on
N=4096 points (k=32, L=3, 3 fixed sweeps, seeded Gaussian blobs) inside
a ``bench.window`` span under the JAX profiler, and writes the trace as
``v5e_scoped_sweep.xplane.pb`` and the program's optimized HLO as
``v5e_scoped_sweep.hlo.txt.gz``. Needs the chip: the program compiles
before the trace starts, so the trace holds one call. The trace leaves
out what no reader of it reads and most of its size is: the
``/host:metadata`` plane (the programs' HLO protos) and each op's
``source_stack`` and ``shape_with_layout`` (read with TensorFlow's
``xplane_pb2``).
"""
from __future__ import annotations

import argparse
import glob
import gzip
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))

N, K, LEVELS, SWEEPS = 4096, 32, 3, 3
NAME = "v5e_scoped_sweep"
DROP_STATS = ("source_stack", "shape_with_layout")


def trim(src: str, dst: str) -> None:
    """Copy an ``.xplane.pb`` without the ``/host:metadata`` plane and
    the ops' ``DROP_STATS``."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    keep = [p for p in space.planes if p.name != "/host:metadata"]
    del space.planes[:]
    space.planes.extend(keep)
    for plane in space.planes:
        drop = {k for k, m in plane.stat_metadata.items()
                if m.name in DROP_STATS}
        for meta in plane.event_metadata.values():
            stats = [st for st in meta.stats if st.metadata_id not in drop]
            del meta.stats[:]
            meta.stats.extend(stats)
    with open(dst, "wb") as f:
        f.write(space.SerializeToString())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "bench", "tests",
                                                  "data"))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from repro.solver.topk import build_from_points, run_topk

    if jax.devices()[0].platform != "tpu":
        print("record_scoped_sweep: needs a TPU", file=sys.stderr)
        return 3
    key = jax.random.PRNGKey(0)
    ctr = jax.random.uniform(key, (16, 8), jnp.float32, 0.0, 10.0)
    lab = jax.random.randint(jax.random.fold_in(key, 1), (N,), 0, 16)
    x = ctr[lab] + jax.random.normal(jax.random.fold_in(key, 2), (N, 8))
    s3k, idx = build_from_points(x, K, LEVELS)
    opts = dict(max_iterations=SWEEPS, damping=0.7, stop="fixed")
    jax.block_until_ready(run_topk(s3k, idx, **opts))
    tmp = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(tmp):
            with jax.profiler.TraceAnnotation("bench.window"):
                jax.block_until_ready(run_topk(s3k, idx, **opts))
        xplane = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                                  recursive=True))[-1]
        os.makedirs(args.out, exist_ok=True)
        trim(xplane, os.path.join(args.out, NAME + ".xplane.pb"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    text = run_topk.lower(s3k, idx, **opts).compile().as_text()
    with gzip.open(os.path.join(args.out, NAME + ".hlo.txt.gz"), "wt") as f:
        f.write(text)
    for suffix in (".xplane.pb", ".hlo.txt.gz"):
        path = os.path.join(args.out, NAME + suffix)
        print(path, os.path.getsize(path))
    return 0


if __name__ == "__main__":
    sys.exit(main())
