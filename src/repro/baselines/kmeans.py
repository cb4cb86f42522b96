"""K-means (Lloyd's) in JAX — the building block of the paper's HK-Means
comparison baseline (Mahout's MapReduce K-means).

``kmeans`` is the dense jitted version; ``kmeans_distributed`` shards the
points over a mesh axis and psums per-cluster sufficient statistics — the
literal MapReduce formulation (map: assign + partial sums; reduce: psum),
mirroring how Mahout distributes a single K-means iteration (paper §4.2).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


class KMeansResult(NamedTuple):
    centers: jnp.ndarray   # (k, d)
    labels: jnp.ndarray    # (n,)
    inertia: jnp.ndarray   # scalar


def _assign(x, centers):
    d2 = (jnp.sum(x * x, 1)[:, None] + jnp.sum(centers * centers, 1)[None, :]
          - 2.0 * x @ centers.T)
    return jnp.argmin(d2, axis=1), jnp.min(d2, axis=1)


def _update(x, labels, k):
    hot = jax.nn.one_hot(labels, k, dtype=x.dtype)          # (n, k)
    sums = hot.T @ x                                        # (k, d)
    counts = jnp.sum(hot, axis=0)[:, None]                  # (k, 1)
    return sums, counts


@functools.partial(jax.jit, static_argnames=("k", "iterations"))
def kmeans(
    x: jnp.ndarray, k: int, *, iterations: int = 25,
    init_centers: jnp.ndarray | None = None, key: jax.Array | None = None,
) -> KMeansResult:
    n = x.shape[0]
    if init_centers is None:
        if key is None:
            key = jax.random.PRNGKey(0)
        idx = jax.random.choice(key, n, (k,), replace=False)
        init_centers = x[idx]

    def step(centers, _):
        labels, _ = _assign(x, centers)
        sums, counts = _update(x, labels, k)
        new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), centers)
        return new, None

    centers, _ = jax.lax.scan(step, init_centers, None, length=iterations)
    labels, d2 = _assign(x, centers)
    return KMeansResult(centers, labels.astype(jnp.int32), jnp.sum(d2))


def kmeans_distributed(
    x: jnp.ndarray, k: int, mesh: Mesh, *, iterations: int = 25,
    init_centers: jnp.ndarray | None = None, key: jax.Array | None = None,
    axis_name: str = "workers",
) -> KMeansResult:
    """MapReduce K-means: points sharded over ``axis_name``, centers
    replicated, per-iteration psum of (sums, counts) — Mahout's scheme."""
    n, d = x.shape
    workers = mesh.shape[axis_name]
    if n % workers:
        raise ValueError(f"N={n} must divide workers={workers}")
    if init_centers is None:
        if key is None:
            key = jax.random.PRNGKey(0)
        idx = jax.random.choice(key, n, (k,), replace=False)
        init_centers = x[idx]

    def body(x_loc, centers0):
        def step(centers, _):
            labels, _ = _assign(x_loc, centers)
            sums, counts = _update(x_loc, labels, k)
            sums = jax.lax.psum(sums, axis_name)            # the "reduce"
            counts = jax.lax.psum(counts, axis_name)
            new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1),
                            centers)
            return new, None
        centers, _ = jax.lax.scan(step, centers0, None, length=iterations)
        labels, d2 = _assign(x_loc, centers)
        return centers, labels.astype(jnp.int32), jax.lax.psum(
            jnp.sum(d2), axis_name)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name, None), P(None, None)),
        out_specs=(P(None, None), P(axis_name), P()),
    )
    centers, labels, inertia = jax.jit(fn)(x, init_centers)
    return KMeansResult(centers, labels, inertia)
