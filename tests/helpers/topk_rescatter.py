"""The sparse sweep as it ran before tau took alpha's carried column
sums: tau scatters the state's rho again at every sweep.

``run_topk_rescatter`` drives ``hap.jacobi_sweep`` through
``make_topk_sweep``'s sweep, with the state's ``col`` replaced before
each sweep by a fresh scatter of the rho that tau reads
(``col_stats_topk``, level by level, as tau scattered it) and dropped
after it. The carried sums must reproduce this schedule bit for bit.
Same return contract as ``run_topk``, with a bare ``HAPState``.
"""
import functools

import jax
import jax.numpy as jnp

from repro.core import hap
from repro.kernels.topk_ops import as_blocks, col_stats_topk
from repro.solver import dense
from repro.solver.topk import make_topk_sweep


@functools.partial(jax.jit, static_argnames=(
    "max_iterations", "damping", "kappa", "s_mode", "stop", "patience"))
def run_topk_rescatter(s3k, idx, *, max_iterations, damping, kappa=0.0,
                       s_mode="off", stop="fixed", patience=5):
    s3k = jax.tree.map(lambda b: b.astype(jnp.float32), s3k)
    init = hap.hap_init(s3k)
    levels, n = init.c.shape
    sweep, assign = make_topk_sweep(idx, damping=damping, kappa=kappa,
                                    s_mode=s_mode)

    def rescatter(state, it):
        if levels == 1:                          # nothing is carried
            return sweep(state, it)
        r = as_blocks(state.r)
        col = jnp.stack([col_stats_topk(tuple(b[l] for b in r), idx)[0]
                         for l in range(levels - 1)])
        return sweep(state._replace(col=col), it)._replace(col=None)

    return dense.drive_sweeps(
        init, rescatter, assign, levels, n, max_iterations=max_iterations,
        stop=stop, patience=patience)
