"""The sparse loop carries alpha's column sums into the next sweep's tau
(``hap.jacobi_sweep``, ``HAPState.col``).

Tau used to scatter max(0, rho) over levels 0..L-2 of the state's rho:
the same scatter of the same rho that the previous sweep's alpha update
made. Reading alpha's sums instead must change no bit: ``run_topk``'s
final state, exemplars, sweep count and trace equal those of the
schedule that scatters again (``helpers.topk_rescatter``), on one
layout and on an edge list's row blocks, at L = 1, 2 and 3, with and
without the Eq 2.7 refinement, for both stopping rules.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from helpers.edge_hap_ref import kronecker
from helpers.topk_rescatter import run_topk_rescatter
from repro.data import gaussian_blobs
from repro.graph.edges import EdgeList
from repro.kernels.topk_ops import col_stats_topk
from repro.solver import SolveConfig
from repro.solver.backends import _edge_layout
from repro.solver.topk import build_from_points, init_state, run_topk


def _layout(kind, levels):
    if kind == "one":
        x, _ = gaussian_blobs(n=96, k=4, dim=3, seed=2, spread=0.5)
        return build_from_points(jnp.asarray(x), 8, levels)
    el = EdgeList(*kronecker(7, seed=3))
    s3k, idx, _, _ = _edge_layout(el, SolveConfig(levels=levels),
                                  blocked=True)
    assert isinstance(idx, tuple) and len(idx) > 1
    return s3k, idx


def _leaves(x):
    return [np.asarray(b) for b in (x if isinstance(x, tuple) else (x,))]


@pytest.mark.parametrize("stop", ["fixed", "converged"])
@pytest.mark.parametrize("s_mode", ["off", "paper"])
@pytest.mark.parametrize("levels", [1, 2, 3])
@pytest.mark.parametrize("kind", ["one", "blocks"])
def test_carried_sums_equal_a_fresh_scatter(kind, levels, s_mode, stop):
    s3k, idx = _layout(kind, levels)
    kw = dict(max_iterations=12, damping=0.7, kappa=0.1, s_mode=s_mode,
              stop=stop, patience=3)
    st, e, ns, conv, tr = run_topk(s3k, idx, **kw)
    st2, e2, ns2, conv2, tr2 = run_topk_rescatter(s3k, idx, **kw)
    assert (st.hap.col is None) == (levels == 1)
    assert st2.col is None
    for f in ("s", "r", "a", "tau", "phi", "c"):
        for got, want in zip(_leaves(getattr(st.hap, f)),
                             _leaves(getattr(st2, f))):
            assert np.array_equal(got, want), f
    assert np.array_equal(np.asarray(e), np.asarray(e2))
    assert np.array_equal(np.asarray(tr), np.asarray(tr2))
    assert int(ns) == int(ns2) and bool(conv) == bool(conv2)


@pytest.mark.parametrize("levels", [1, 3])
def test_initial_carry_is_the_scatter_of_zero_rho(levels):
    """The first sweep's tau reads the scatter of the initial rho = 0;
    at L = 1 nothing is carried."""
    s3k, idx = _layout("one", levels)
    init = init_state(s3k)
    if levels == 1:
        assert init.col is None
        return
    zero = np.asarray(col_stats_topk(init.r[0], idx)[0])
    assert init.col.shape == (levels - 1, *zero.shape)
    for col in np.asarray(init.col):
        assert np.array_equal(col, zero)
