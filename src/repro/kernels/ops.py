"""Jitted public wrappers for the Pallas kernels.

On TPU the kernels compile natively; elsewhere (this CPU container) they run
under ``interpret=True``, which executes the same kernel bodies in Python —
the correctness surface the sweep tests validate. ``use_ref=True`` forces
the pure-jnp oracle (used by the serving/clustering paths when tile overhead
is not worth it for tiny N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.availability import availability_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.responsibility import responsibility_pallas
from repro.kernels.similarity import similarity_pallas


@functools.partial(jax.jit, static_argnames=("lam", "block", "use_ref"))
def responsibility(s, a, tau, r_old, *, lam: float = 0.5, block: int = 256,
                   use_ref: bool = False):
    if use_ref:
        return ref.responsibility(s, a, tau, r_old, lam)
    return responsibility_pallas(
        s, a, tau, r_old, lam, block_i=block, block_j=block)


@functools.partial(jax.jit, static_argnames=("lam", "block", "use_ref"))
def availability(r, c, phi, a_old, *, lam: float = 0.5, block: int = 256,
                 use_ref: bool = False):
    if use_ref:
        return ref.availability(r, c, phi, a_old, lam)
    return availability_pallas(
        r, c, phi, a_old, lam, block_i=block, block_j=block)


@functools.partial(jax.jit, static_argnames=("block", "use_ref"))
def neg_sqeuclidean(x, y=None, *, block: int = 256, use_ref: bool = False):
    if use_ref:
        return ref.neg_sqeuclidean(x, x if y is None else y)
    return similarity_pallas(x, y, block_i=block, block_j=block)


def hap_iteration_kernels(s, r, a, tau, c, phi, *, lam: float = 0.5,
                          block: int = 256):
    """One flat-AP-level (rho then alpha) iteration built from the kernels —
    the single-device TPU hot path for one hierarchy level."""
    r = responsibility(s, a, tau, r, lam=lam, block=block)
    a = availability(r, c, phi, a, lam=lam, block=block)
    return r, a


@functools.partial(jax.jit,
                   static_argnames=("causal", "block", "use_ref"))
def flash_attention(q, k, v, *, causal: bool = True, block: int = 256,
                    use_ref: bool = False):
    """Flash attention over (BH, S, D) tensors (heads folded into batch).

    GQA callers broadcast KV heads to the query-head count before folding
    (cheap view; the kernel then streams each head's KV once).
    """
    if use_ref:
        return ref.flash_attention(q, k, v, causal)
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block,
                                  block_k=block)


@functools.partial(jax.jit,
                   static_argnames=("iterations", "lam", "block"))
def affinity_propagation_kernels(s, *, iterations: int = 100,
                                 lam: float = 0.5, block: int = 256):
    """Flat AP driven entirely by the Pallas kernels — the single-device
    TPU hot path (interpret-mode on CPU; tested against
    repro.core.affinity.affinity_propagation)."""
    n = s.shape[-1]
    s = s.astype(jnp.float32)
    tau = jnp.full((n,), jnp.inf, jnp.float32)
    zero = jnp.zeros((n,), jnp.float32)

    def step(carry, _):
        r, a = carry
        r, a = hap_iteration_kernels(s, r, a, tau, zero, zero, lam=lam,
                                     block=block)
        return (r, a), None

    (r, a), _ = jax.lax.scan(
        step, (jnp.zeros_like(s), jnp.zeros_like(s)), None,
        length=iterations)
    return jnp.argmax(a + r, axis=1).astype(jnp.int32), r, a
