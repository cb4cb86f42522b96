import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    link_hierarchy, pairwise_similarity, purity, run_hap, set_preferences,
    stack_levels,
)
from repro.core.hap import (
    alpha_update, c_update, column_sum, hap_init, phi_from_level,
    rho_update, tau_from_level,
)
from repro.core.preferences import median_preference
from repro.data import gaussian_blobs


def _s3(x, levels=3):
    s = pairwise_similarity(jnp.asarray(x))
    s = set_preferences(s, median_preference(s))
    return stack_levels(s, levels)


def test_init_boundary_conventions():
    s3 = _s3(gaussian_blobs(n=20, k=2)[0])
    st = hap_init(s3)
    assert np.all(np.isinf(np.asarray(st.tau)))
    assert np.all(np.asarray(st.phi) == 0)
    assert np.all(np.asarray(st.c) == 0)


def test_rho_reduces_to_flat_ap_at_level1():
    """With tau = +inf, Eq 2.1 must equal the flat AP responsibility."""
    from repro.core.affinity import responsibility_update
    rng = np.random.default_rng(0)
    s = jnp.asarray(-rng.random((12, 12)).astype(np.float32))
    a = jnp.asarray(rng.standard_normal((12, 12)).astype(np.float32))
    tau = jnp.full((12,), jnp.inf)
    np.testing.assert_allclose(np.asarray(rho_update(s, a, tau)),
                               np.asarray(responsibility_update(s, a)),
                               atol=1e-6)


def test_alpha_with_zero_c_phi_matches_flat():
    from repro.core.affinity import availability_update
    rng = np.random.default_rng(1)
    r = jnp.asarray(rng.standard_normal((10, 10)).astype(np.float32))
    z = jnp.zeros((10,))
    np.testing.assert_allclose(np.asarray(alpha_update(r, z, z)),
                               np.asarray(availability_update(r)), atol=1e-6)


@pytest.mark.parametrize("rows", [1, 2, 3, 384, 1000])
def test_column_sum_is_order_fixed(rows):
    """column_sum sums in one order whatever the program: trailing zero
    rows (a bucket-padded request) and a vmapped batch give the same
    bits as the plain call, and the value is the column sum."""
    x = np.random.default_rng(rows).standard_normal((rows, 7)).astype(
        np.float32)
    got = np.asarray(jax.jit(column_sum)(x))
    np.testing.assert_allclose(got, x.astype(np.float64).sum(axis=0),
                               rtol=1e-5, atol=1e-5)
    padded = np.concatenate([x, np.zeros((rows + 5, 7), np.float32)])
    np.testing.assert_array_equal(np.asarray(jax.jit(column_sum)(padded)),
                                  got)
    batched = jax.jit(jax.vmap(column_sum))(np.stack([padded, 2 * padded]))
    np.testing.assert_array_equal(np.asarray(batched)[0], got)


def test_tau_equation_manual():
    r = jnp.asarray([[1.0, -2.0], [3.0, 0.5]], jnp.float32)
    c = jnp.asarray([0.1, 0.2], jnp.float32)
    tau = np.asarray(tau_from_level(r, c))
    # tau_j = c_j + r_jj + sum_{k != j} max(0, r_kj)
    assert abs(tau[0] - (0.1 + 1.0 + 3.0)) < 1e-6
    assert abs(tau[1] - (0.2 + 0.5 + 0.0)) < 1e-6


def test_phi_and_c_are_rowwise_max():
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32))
    s = jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(phi_from_level(a, s)),
                               np.asarray(a + s).max(1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(c_update(a, s)),
                               np.asarray(a + s).max(1), atol=1e-6)


@pytest.mark.parametrize("order", ["sequential", "parallel"])
def test_hap_boundaries_preserved_after_run(order):
    s3 = _s3(gaussian_blobs(n=30, k=3, seed=5)[0])
    res = run_hap(s3, iterations=15, damping=0.6, order=order)
    assert np.all(np.isinf(np.asarray(res.state.tau)[0]))   # tau^1 == inf
    assert np.all(np.asarray(res.state.phi)[-1] == 0)       # phi^L == 0


@pytest.mark.parametrize("order", ["sequential", "parallel"])
def test_hap_bottom_level_clusters_blobs(order):
    x, y = gaussian_blobs(n=120, k=4, seed=6, spread=0.4)
    res = run_hap(_s3(x), iterations=40, damping=0.7, order=order)
    from repro.core import canonicalize
    labels = np.asarray(canonicalize(res.exemplars[0]))
    assert purity(labels, y) > 0.9


def test_hierarchy_aggregates_upward():
    x, _ = gaussian_blobs(n=150, k=5, seed=7, spread=0.5)
    res = run_hap(_s3(x), iterations=40, damping=0.7, order="parallel")
    k = [int(v) for v in res.n_clusters]
    assert k[0] >= k[1] >= k[2] >= 1


def test_link_hierarchy_parents_consistent():
    x, _ = gaussian_blobs(n=100, k=4, seed=8)
    res = run_hap(_s3(x), iterations=30, damping=0.7, order="parallel")
    hier = link_hierarchy(res.exemplars)
    for l, parents in enumerate(hier.parents):
        assert parents.shape[0] == hier.n_clusters[l]
        assert np.all(parents < hier.n_clusters[l + 1])


def test_s_update_modes_run():
    s3 = _s3(gaussian_blobs(n=40, k=3, seed=9)[0])
    for mode in ("paper", "evidence"):
        res = run_hap(s3, iterations=10, damping=0.6, order="parallel",
                      kappa=0.3, s_mode=mode)
        assert np.all(np.isfinite(np.asarray(res.state.r)))
        # level-1 similarities never modified
        np.testing.assert_allclose(np.asarray(res.state.s[0]),
                                   np.asarray(s3[0]))
