"""The sparse alpha update reads its column statistics with one gather.

``alpha_from_stats`` sums c + phi, rho_jj and the availability column
sum in (N,) space and gathers the sum once per level. That must be the
same f32 result, bit for bit, as gathering the three statistics and
adding the gathered blocks in the same order (the formula written out
below, in numpy), for the one-device layout and for a row block of the
sharded sweep; and one sweep must hold exactly one (N, kk) gather per
level, so the three gathers cannot come back unnoticed.

Likewise one sweep holds one column-sum scatter-add a level per row
block: alpha's, whose sums tau reads at the next sweep
(``hap.jacobi_sweep``); tau scattering the same rho again made 2L - 1.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.topk_ops import alpha_from_stats
from repro.solver.topk import init_state, make_topk_sweep

N, KK = 64, 9


def _idx(rng, n=N, kk=KK):
    """(n, kk) layout: self slot first, then kk - 1 ascending distinct
    neighbor columns."""
    nbr = [np.sort(rng.choice(np.delete(np.arange(n), i), kk - 1,
                              replace=False)) for i in range(n)]
    return np.concatenate([np.arange(n)[:, None], np.stack(nbr)],
                          axis=1).astype(np.int32)


def _values(rng, shape, kind):
    z = rng.standard_normal(shape)
    if kind == "negative":
        z = -np.abs(z) * 10.0 - 1.0
    elif kind == "magnitudes":                   # 1e-3 .. 1e30, both signs
        z = z * 10.0 ** rng.uniform(-3, 30, shape)
    return z.astype(np.float32)


def _three_gathers(r, idx, col, base, rdiag):
    """The oracle: each statistic gathered on its own, then added."""
    a_off = np.minimum(np.float32(0), base[idx] + rdiag[idx] + col[idx]
                       - np.maximum(r, np.float32(0)))
    rows = idx[:, 0]
    a_off[:, 0] = base[rows] + col[rows]
    return a_off


@pytest.mark.parametrize("kind", ["normal", "negative", "magnitudes"])
@pytest.mark.parametrize("rows", [slice(0, N), slice(16, 40)],
                         ids=["one_device", "row_block"])
def test_alpha_from_stats_equals_three_gathers(rows, kind):
    rng = np.random.default_rng(7)
    idx = _idx(rng)[rows]                        # global ids, as sharded
    r = _values(rng, idx.shape, kind)
    col, base, rdiag = (_values(rng, (N,), kind) for _ in range(3))
    got = alpha_from_stats(*map(jnp.asarray, (r, idx, col, base, rdiag)))
    want = _three_gathers(r, idx, col, base, rdiag)
    assert np.isfinite(want).all()
    assert np.array_equal(np.asarray(got), want)


def _sweep_text(s3k, idx):
    """One sweep after the first, lowered."""
    sweep, _ = make_topk_sweep(idx, damping=0.7, kappa=0.0, s_mode="off")
    return jax.jit(sweep).lower(init_state(s3k), jnp.int32(1)).as_text()


def test_sweep_gathers_once_per_level():
    levels = 3
    rng = np.random.default_rng(3)
    idx = jnp.asarray(_idx(rng))
    s3k = jnp.asarray(np.broadcast_to(_values(rng, (N, KK), "normal"),
                                      (levels, N, KK)))
    text = _sweep_text(s3k, idx)
    gathers = re.findall(r'"?stablehlo\.gather"?.*-> tensor<(\S+)>', text)
    assert gathers
    assert gathers.count(f"{N}x{KK}xf32") == levels


#: a scatter whose region adds (not the self slot's set), and its result
SCATTER = re.compile(r'"stablehlo\.scatter".*\n.*\^bb0.*\n\s*(?:%\S+ = )?'
                     r'stablehlo\.(\w+)[^\n]*\n(?:.*\n)*?\s*\}\) : '
                     r'.*-> tensor<(\S+)>')


@pytest.mark.parametrize("levels", [1, 2, 3])
@pytest.mark.parametrize("blocks", [1, 2], ids=["one_layout", "row_blocks"])
def test_sweep_scatters_once_per_level(levels, blocks):
    """L column-sum scatter-adds a sweep on one layout, L per block on
    row blocks (the second block keeps 5 of its rows' 9 slots)."""
    rng = np.random.default_rng(3)
    idx, vals = _idx(rng), _values(rng, (N, KK), "normal")
    if blocks == 2:
        h = N // 2
        idx, vals = (idx[:h], idx[h:, :5]), (vals[:h], vals[h:, :5])
    else:
        idx, vals = (idx,), (vals,)
    s3k = tuple(jnp.asarray(np.broadcast_to(v, (levels, *v.shape)))
                for v in vals)
    idx = tuple(map(jnp.asarray, idx))
    if blocks == 1:
        s3k, idx = s3k[0], idx[0]
    found = SCATTER.findall(_sweep_text(s3k, idx))
    adds = [shape for op, shape in found if op == "add"]
    assert adds == [f"{N}xf32"] * (levels * blocks)
