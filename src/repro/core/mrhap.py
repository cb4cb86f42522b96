"""MR-HAP: the paper's MapReduce parallelization of HAP, on a JAX mesh.

The paper (§3) splits each HAP iteration into three MapReduce jobs and
shuttles the (L, N, N) message tensors between *exemplar-based* (column) and
*node-based* (row) shardings — the Hadoop shuffle is a distributed transpose.
Here the same dataflow runs under ``jax.shard_map`` over a 1-D ``workers``
mesh axis, with two communication modes:

* ``transpose`` — **paper-faithful**: rho lives row-sharded (the paper's
  node-based format, Job 1's reducer layout), alpha lives column-sharded
  (exemplar-based, Job 2's reducer layout), and each iteration performs the
  paper's two format switches as ``lax.all_to_all`` distributed transposes
  (O(L*N^2/W) moved per worker per iteration, exactly the Hadoop shuffle
  volume). Job 3's final switch is one more all_to_all at extraction.

* ``stats`` — **beyond-paper optimization** (DESIGN §2): every tensor stays
  row-sharded; because the cross-worker reductions of Eq. 2.2/2.3/2.4 are
  *column sums of max(0, rho)* and *diagonals*, only O(L*N) statistics are
  psum/all_gather'ed per iteration. Communication drops from O(L*N^2/W) to
  O(L*N) per iteration with bit-identical semantics (up to float reduction
  order).

Both modes implement the paper's Jacobi schedule (all levels in parallel;
tau/c skipped on the first iteration — §3.0.1) and match
``repro.core.hap.run_hap(order="parallel")`` numerically, which is what the
equivalence tests assert.
"""
from __future__ import annotations

import functools
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import hap
from repro.core.affinity import masked_top2

CommMode = Literal["stats", "transpose"]
AXIS = "workers"


class MRHAPResult(NamedTuple):
    exemplars: jnp.ndarray   # (L, N) int32
    n_clusters: jnp.ndarray  # (L,)
    r: jnp.ndarray           # (L, N, N) responsibilities (row-sharded)
    a: jnp.ndarray           # (L, N, N) availabilities


# ------------------------------------------------------------ local helpers
def _local_rows(w: jnp.ndarray, n_local: int) -> jnp.ndarray:
    """Global row indices owned by worker ``w``."""
    return w * n_local + jnp.arange(n_local)


def _rho_rows(s, a, tau_rows):
    """Eq 2.1 on a (L, Nl, N) row block; reductions are row-local."""
    def one(s_l, a_l, tau_l):
        v = a_l + s_l
        m1, i1, m2 = masked_top2(v)
        j = jnp.arange(s_l.shape[-1])
        row_max = jnp.where(j[None, :] == i1[:, None], m2[:, None], m1[:, None])
        return s_l + jnp.minimum(tau_l[:, None], -row_max)
    return jax.vmap(one)(s, a, tau_rows)


def _alpha_rows(r, c_g, phi_g, col_g, diag_g, rows):
    """Eq 2.2/2.3 on a (L, Nl, N) row block from global column statistics.

    col_g[l, j] = sum_{k != j} max(0, rho_kj);  diag_g[l, j] = rho_jj.
    """
    n = r.shape[-1]
    eye = rows[:, None] == jnp.arange(n)[None, :]          # (Nl, N)
    rp = jnp.where(eye[None], 0.0, jnp.maximum(r, 0.0))    # exclude own diag
    base = (c_g + phi_g)[:, None, :]
    a_off = jnp.minimum(0.0, base + (diag_g + col_g)[:, None, :] - rp)
    a_diag = base + col_g[:, None, :]
    return jnp.where(eye[None], a_diag, a_off)


#: per-level column sums in the dense core's fixed order
#: (``hap.column_sum``): on one worker the shard's partial sums are then
#: the dense sweep's own
_col_sums = jax.vmap(hap.column_sum)


def _col_stats_rows(r, rows):
    """Partial column sums of max(0, rho) excluding the diagonal, plus the
    locally-owned diagonal slice. Shapes: (L, N) partial, (L, Nl) diag."""
    n = r.shape[-1]
    eye = rows[:, None] == jnp.arange(n)[None, :]
    col_part = _col_sums(jnp.where(eye[None], 0.0, jnp.maximum(r, 0.0)))
    nl = rows.shape[0]
    diag_loc = r[:, jnp.arange(nl), rows]                  # (L, Nl)
    return col_part, diag_loc


def _slice_rows(x_g, w, n_local):
    """Slice this worker's row block out of a replicated (L, N) vector."""
    return jax.lax.dynamic_slice_in_dim(x_g, w * n_local, n_local, axis=1)


# ------------------------------------------------------------- stats mode
def _sweep_stats(carry, it, *, s_loc, lam, n_local):
    """One MR iteration, all tensors row-sharded, O(L*N) communication.

    carry: r, a (L, Nl, N); c_g (L, N); col_g, diag_g (L, N) = stats of the
    carried rho (so Job 1 reuses Job 2's reduction from the previous
    iteration — one psum per iteration instead of two).
    """
    r, a, c_g, col_g, diag_g = carry
    w = jax.lax.axis_index(AXIS)
    rows = _local_rows(w, n_local)
    first = it == 0

    # --- Job 1: tau, c (gated on first iteration), then rho -------------
    tau_upper = c_g + diag_g + col_g                       # (L, N): tau^{l+1}
    inf_row = jnp.full_like(tau_upper[:1], jnp.inf)
    tau_g = jnp.concatenate([inf_row, tau_upper[:-1]], axis=0)
    tau_g = jnp.where(first, jnp.full_like(tau_g, jnp.inf), tau_g)

    c_new_loc = jnp.max(a + r, axis=2)                     # (L, Nl) row-local
    c_new_g = jax.lax.all_gather(c_new_loc, AXIS, axis=1, tiled=True)
    c_g = jnp.where(first, c_g, c_new_g)

    tau_rows = _slice_rows(tau_g, w, n_local)
    r = lam * r + (1.0 - lam) * _rho_rows(s_loc, a, tau_rows)

    # --- Job 2: phi, then alpha -----------------------------------------
    phi_loc = jnp.max(a[1:] + s_loc[1:], axis=2)           # from OLD alpha
    phi_loc = jnp.concatenate(
        [phi_loc, jnp.zeros_like(phi_loc[:1])], axis=0)    # phi[L-1] == 0
    phi_g = jax.lax.all_gather(phi_loc, AXIS, axis=1, tiled=True)

    col_part, diag_loc = _col_stats_rows(r, rows)
    col_g = jax.lax.psum(col_part, AXIS)                   # (L, N)
    diag_g = jax.lax.all_gather(diag_loc, AXIS, axis=1, tiled=True)

    a = lam * a + (1.0 - lam) * _alpha_rows(r, c_g, phi_g, col_g, diag_g, rows)
    return (r, a, c_g, col_g, diag_g), None


# --------------------------------------------------------- transpose mode
def _sweep_transpose(carry, it, *, s_row, s_col, lam, n_local):
    """One MR iteration with the paper's two format switches (shuffles).

    rho is node-based (row-sharded, Job 1's output format); alpha is
    exemplar-based (column-sharded, Job 2's output format). Each iteration:
    all_to_all #1 moves alpha to node format for the rho update; all_to_all
    #2 moves the fresh rho to exemplar format for the alpha update — the
    Hadoop shuffle volume, O(L*N^2/W) per worker per switch.
    """
    r_row, r_col, a_col, c_g = carry
    w = jax.lax.axis_index(AXIS)
    rows = _local_rows(w, n_local)
    n = r_row.shape[-1]
    first = it == 0

    # --- Job 1 mapper side: column statistics from exemplar-based rho ---
    eye_col = jnp.arange(n)[:, None] == rows[None, :]      # (N, Nl)
    rp = jnp.where(eye_col[None], 0.0, jnp.maximum(r_col, 0.0))
    col_loc = _col_sums(rp)                                # (L, Nl)
    diag_loc = r_col[:, rows, jnp.arange(n_local)]         # (L, Nl)
    col_g = jax.lax.all_gather(col_loc, AXIS, axis=1, tiled=True)
    diag_g = jax.lax.all_gather(diag_loc, AXIS, axis=1, tiled=True)

    tau_upper = c_g + diag_g + col_g
    inf_row = jnp.full_like(tau_upper[:1], jnp.inf)
    tau_g = jnp.concatenate([inf_row, tau_upper[:-1]], axis=0)
    tau_g = jnp.where(first, jnp.full_like(tau_g, jnp.inf), tau_g)

    # --- shuffle #1: alpha exemplar-format -> node-format ----------------
    a_row = jax.lax.all_to_all(a_col, AXIS, split_axis=1, concat_axis=2,
                               tiled=True)                 # (L, Nl, N)

    c_new_loc = jnp.max(a_row + r_row, axis=2)
    c_new_g = jax.lax.all_gather(c_new_loc, AXIS, axis=1, tiled=True)
    c_g = jnp.where(first, c_g, c_new_g)

    tau_rows = _slice_rows(tau_g, w, n_local)
    r_row = lam * r_row + (1.0 - lam) * _rho_rows(s_row, a_row, tau_rows)

    # --- shuffle #2: fresh rho node-format -> exemplar-format ------------
    r_col = jax.lax.all_to_all(r_row, AXIS, split_axis=2, concat_axis=1,
                               tiled=True)                 # (L, N, Nl)

    # --- Job 2: phi (row-local on old alpha), then alpha (column-local) --
    phi_loc = jnp.max(a_row[1:] + s_row[1:], axis=2)
    phi_loc = jnp.concatenate([phi_loc, jnp.zeros_like(phi_loc[:1])], axis=0)
    phi_g = jax.lax.all_gather(phi_loc, AXIS, axis=1, tiled=True)

    rp_new = jnp.where(eye_col[None], 0.0, jnp.maximum(r_col, 0.0))
    col_new = _col_sums(rp_new)                            # (L, Nl) local cols
    rdiag_new = r_col[:, rows, jnp.arange(n_local)]        # (L, Nl)
    c_cols = _slice_rows(c_g, w, n_local)
    phi_cols = _slice_rows(phi_g, w, n_local)
    base = (c_cols + phi_cols)[:, None, :]                 # (L, 1, Nl)
    a_off = jnp.minimum(
        0.0, base + (rdiag_new + col_new)[:, None, :] - rp_new)
    a_diag = base + col_new[:, None, :]
    a_new = jnp.where(eye_col[None], a_diag, a_off)
    a_col = lam * a_col + (1.0 - lam) * a_new
    return (r_row, r_col, a_col, c_g), None


# ------------------------------------------------------------------ driver
def _run_body_stats(s3, *, iterations, lam, n_local):
    z = jnp.zeros_like(s3)
    levels, _, n = s3.shape
    zero_g = jnp.zeros((levels, n), s3.dtype)
    # all_gather outputs are vma-varying over AXIS; match the carry types.
    vary = lambda x: jax.lax.pcast(x, (AXIS,), to="varying")
    carry = (z, z, vary(zero_g), zero_g, vary(zero_g))
    sweep = functools.partial(_sweep_stats, s_loc=s3, lam=lam, n_local=n_local)
    carry, _ = jax.lax.scan(sweep, carry, jnp.arange(iterations))
    r, a = carry[0], carry[1]
    e_loc = jnp.argmax(a + r, axis=2).astype(jnp.int32)    # (L, Nl)
    return e_loc, r, a


def _run_body_transpose(s_row, s_col, *, iterations, lam, n_local):
    levels, _, n = s_row.shape
    z_row = jnp.zeros_like(s_row)
    z_col = jnp.zeros_like(s_col)
    zero_g = jax.lax.pcast(jnp.zeros((levels, n), s_row.dtype), (AXIS,),
                           to="varying")
    carry = (z_row, z_col, z_col, zero_g)
    sweep = functools.partial(
        _sweep_transpose, s_row=s_row, s_col=s_col, lam=lam, n_local=n_local)
    carry, _ = jax.lax.scan(sweep, carry, jnp.arange(iterations))
    r_row, _, a_col, _ = carry
    # Job 3's final format switch: alpha back to node format for extraction.
    a_row = jax.lax.all_to_all(a_col, AXIS, split_axis=1, concat_axis=2,
                               tiled=True)
    e_loc = jnp.argmax(a_row + r_row, axis=2).astype(jnp.int32)
    return e_loc, r_row, a_row


def run_mrhap(
    s3: jnp.ndarray,
    mesh: Mesh,
    *,
    iterations: int = 30,
    damping: float = 0.5,
    comm_mode: CommMode = "stats",
    axis_name: str = AXIS,
) -> MRHAPResult:
    """Distributed HAP over ``mesh[axis_name]``; N must divide evenly.

    .. deprecated:: prefer ``repro.solver.solve`` (backends
       ``mr1d_stats`` / ``mr1d_transpose``), which pads N to the mesh
       automatically and strips the dummies from results.
    """
    levels, n, n2 = s3.shape
    assert n == n2, "similarity tensor must be (L, N, N)"
    workers = mesh.shape[axis_name]
    if n % workers:
        raise ValueError(
            f"N={n} must be divisible by workers={workers}; pad with "
            "repro.core.mrhap.pad_similarity first.")
    s3 = s3.astype(jnp.float32)
    fn = _mrhap_program(mesh, axis_name, comm_mode, iterations, damping,
                        n // workers)
    e, r, a = fn(s3) if comm_mode == "stats" else fn(s3, s3)

    hot = jax.vmap(lambda ei: jnp.zeros((n,), bool).at[ei].set(True))(e)
    k = jnp.sum(hot, axis=1).astype(jnp.int32)
    return MRHAPResult(e, k, r, a)


@functools.lru_cache(maxsize=32)
def _mrhap_program(mesh: Mesh, axis_name: str, comm_mode: CommMode,
                   iterations: int, damping: float, n_local: int):
    """Jitted shard_map program, cached so repeated run_mrhap calls with
    the same mesh/config hit XLA's compile cache instead of rebuilding a
    fresh jit wrapper (and re-tracing) every call."""
    row_spec = P(None, axis_name, None)
    col_spec = P(None, None, axis_name)
    vec_spec = P(None, axis_name)
    if comm_mode == "stats":
        body = functools.partial(
            _run_body_stats, iterations=iterations, lam=damping,
            n_local=n_local)
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(row_spec,),
            out_specs=(vec_spec, row_spec, row_spec)))
    body = functools.partial(
        _run_body_transpose, iterations=iterations, lam=damping,
        n_local=n_local)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(row_spec, col_spec),
        out_specs=(vec_spec, row_spec, row_spec)))


# -------------------------------------------------------------- utilities
def pad_similarity(s3: jnp.ndarray, multiple: int,
                   neg: float = -1.0e9) -> tuple[jnp.ndarray, int]:
    """Pad (L, N, N) to N' = ceil(N/multiple)*multiple with inert dummies.

    Dummy points repel everything (2*neg) but mildly prefer themselves
    (neg), so each becomes its own singleton exemplar and never perturbs
    real clusters. Returns (padded tensor, original N).
    """
    levels, n, _ = s3.shape
    pad = (-n) % multiple
    if pad == 0:
        return s3, n
    np_ = n + pad
    out = jnp.full((levels, np_, np_), 2.0 * neg, s3.dtype)
    out = out.at[:, :n, :n].set(s3)
    idx = jnp.arange(n, np_)
    out = out.at[:, idx, idx].set(neg)
    return out, n


def comm_bytes_per_iteration(
    n: int, levels: int, workers: int, mode: CommMode,
    bytes_per_el: int = 4,
) -> int:
    """Analytic per-iteration communication volume (whole cluster).

    transpose: two all_to_alls of an (L, N, N) tensor — each worker sends
    (W-1)/W of its L*N*N/W elements, summed over workers; plus the O(L*N)
    gathers shared with stats mode.
    stats: one psum + three all_gathers of (L, N) vectors
    (ring: each moves ~2*(W-1)/W * L*N elements cluster-wide).
    """
    small = 4 * levels * n * (workers - 1) * 2 * bytes_per_el
    if mode == "stats":
        return small
    big = 2 * levels * n * n * (workers - 1) // workers * bytes_per_el
    return big + small


# ===================================================================== 2-D
# Beyond the paper's parallelism ceiling: MR-HAP keys work by (i,l) or
# (j,l), so its maximum useful worker count is M <= L*N (§3.1). Sharding
# BOTH tensor axes over a 2-D mesh (rows x cols tiles — the production
# 16x16 mesh) lifts the ceiling to L*N^2/tile: every reduction either stays
# tile-local or decomposes into a psum / small gathered-statistic merge,
# exactly like the 1-D stats mode.
AXIS_R, AXIS_C = "rows", "cols"


def _row_top2_2d(v, col0):
    """Row top-2 across column tiles via pmax/pmin reductions (outputs
    invariant over the column axis — the vma property the caller needs).

    First-occurrence ties: winner index is the SMALLEST global column
    among value-ties (matches jnp.argmax); a duplicated max on a losing
    shard correctly becomes the second max."""
    m1 = jnp.max(v, axis=-1)
    i1 = jnp.argmax(v, axis=-1).astype(jnp.int32) + col0
    hot = jax.nn.one_hot(i1 - col0, v.shape[-1], dtype=bool)
    m2 = jnp.max(jnp.where(hot, -jnp.inf, v), axis=-1)

    g1 = jax.lax.pmax(m1, AXIS_C)
    idx_cand = jnp.where(m1 == g1, i1, jnp.int32(2 ** 30))
    gidx = jax.lax.pmin(idx_cand, AXIS_C)
    cand2 = jnp.where(i1 == gidx, m2, m1)       # winner shard offers its m2
    g2 = jax.lax.pmax(cand2, AXIS_C)
    return g1, gidx, g2


def _sweep_stats_2d(carry, it, *, s_loc, lam, nr_loc, nc_loc):
    """One MR iteration on (L, nr_loc, nc_loc) tiles; all cross-tile
    traffic is O(L*N/axis) statistics (psum / gathered triples)."""
    r, a, c_g, col_c, diag_c = carry
    ri = jax.lax.axis_index(AXIS_R)
    ci = jax.lax.axis_index(AXIS_C)
    rows = ri * nr_loc + jnp.arange(nr_loc)     # global row ids
    cols = ci * nc_loc + jnp.arange(nc_loc)     # global col ids
    first = it == 0
    levels, n = c_g.shape

    # --- Job 1: tau (cols stats from prev rho), c, then rho -------------
    tau_upper = (jax.lax.dynamic_slice_in_dim(c_g, ci * nc_loc, nc_loc, 1)
                 + diag_c + col_c)              # (L, nc_loc) per col shard
    tau_g = jax.lax.all_gather(tau_upper, AXIS_C, axis=1, tiled=True)
    inf_row = jnp.full_like(tau_g[:1], jnp.inf)
    tau_g = jnp.concatenate([inf_row, tau_g[:-1]], axis=0)
    tau_g = jnp.where(first, jnp.full_like(tau_g, jnp.inf), tau_g)

    c_loc = jnp.max(a + r, axis=2)              # (L, nr_loc) partial
    c_rows = jax.lax.pmax(c_loc, AXIS_C)        # full row max
    c_new_g = jax.lax.all_gather(c_rows, AXIS_R, axis=1, tiled=True)
    c_g = jnp.where(first, c_g, c_new_g)

    # rho: row top-2 of (a + s) merged across column tiles
    v = a + s_loc
    m1, i1, m2 = _row_top2_2d(v, ci * nc_loc)   # (L, nr_loc)
    row_max = jnp.where(cols[None, None, :] == i1[..., None],
                        m2[..., None], m1[..., None])
    tau_rows = jax.lax.dynamic_slice_in_dim(tau_g, ri * nr_loc, nr_loc, 1)
    r = lam * r + (1 - lam) * (
        s_loc + jnp.minimum(tau_rows[..., None], -row_max))

    # --- Job 2: phi, then alpha ------------------------------------------
    phi_loc = jnp.max(a + s_loc, axis=2)        # from OLD alpha
    phi_rows = jax.lax.pmax(phi_loc, AXIS_C)    # (L, nr_loc)
    phi_g = jax.lax.all_gather(phi_rows, AXIS_R, axis=1, tiled=True)
    phi_g = jnp.concatenate(
        [phi_g[1:], jnp.zeros_like(phi_g[:1])], axis=0)

    eye = rows[:, None] == cols[None, :]
    rp = jnp.where(eye[None], 0.0, jnp.maximum(r, 0.0))
    col_c = jax.lax.psum(_col_sums(rp), AXIS_R)           # (L, nc_loc)
    diag_c = jax.lax.psum(
        jnp.sum(jnp.where(eye[None], r, 0.0), axis=1), AXIS_R)
    base = (jax.lax.dynamic_slice_in_dim(c_g, ci * nc_loc, nc_loc, 1)
            + jax.lax.dynamic_slice_in_dim(phi_g, ci * nc_loc, nc_loc, 1))
    a_off = jnp.minimum(0.0, (base + diag_c + col_c)[:, None, :] - rp)
    a_diag = (base + col_c)[:, None, :]
    a = lam * a + (1 - lam) * jnp.where(eye[None], a_diag, a_off)
    return (r, a, c_g, col_c, diag_c), None


def _run_body_2d(s_loc, *, iterations, lam, nr_loc, nc_loc, n, levels):
    z = jnp.zeros_like(s_loc)
    vary = lambda x, ax: jax.lax.pcast(x, ax, to="varying")
    # vma bookkeeping: all_gather over R -> varying {R}; psum over R of a
    # tile-varying value -> varying {C}.
    c_g = vary(jnp.zeros((levels, n), s_loc.dtype), (AXIS_R,))
    zero_c = jnp.zeros((levels, nc_loc), s_loc.dtype)
    carry = (z, z, c_g, vary(zero_c, (AXIS_C,)), vary(zero_c, (AXIS_C,)))
    sweep = functools.partial(_sweep_stats_2d, s_loc=s_loc, lam=lam,
                              nr_loc=nr_loc, nc_loc=nc_loc)
    carry, _ = jax.lax.scan(sweep, carry, jnp.arange(iterations))
    r, a = carry[0], carry[1]
    # extraction: row argmax of (a + r) merged across column tiles
    ci = jax.lax.axis_index(AXIS_C)
    m1, i1, _ = _row_top2_2d(a + r, ci * nc_loc)
    return i1.astype(jnp.int32), r, a


def run_mrhap_2d(
    s3: jnp.ndarray, mesh: Mesh, *, iterations: int = 30,
    damping: float = 0.5, row_axis: str = AXIS_R, col_axis: str = AXIS_C,
) -> MRHAPResult:
    """2-D tile-decomposed MR-HAP over mesh[row_axis] x mesh[col_axis].

    .. deprecated:: prefer ``repro.solver.solve`` (backend ``mr2d``).
    """
    levels, n, n2 = s3.shape
    assert n == n2
    nr = mesh.shape[row_axis]
    nc = mesh.shape[col_axis]
    if n % nr or n % nc:
        raise ValueError(f"N={n} must divide both mesh axes ({nr}, {nc})")
    s3 = s3.astype(jnp.float32)
    fn = _mrhap_2d_program(mesh, row_axis, col_axis, iterations, damping,
                           n // nr, n // nc, n, levels)
    e, r, a = fn(s3)
    hot = jax.vmap(lambda ei: jnp.zeros((n,), bool).at[ei].set(True))(e)
    k = jnp.sum(hot, axis=1).astype(jnp.int32)
    return MRHAPResult(e, k, r, a)


@functools.lru_cache(maxsize=32)
def _mrhap_2d_program(mesh: Mesh, row_axis: str, col_axis: str,
                      iterations: int, damping: float, nr_loc: int,
                      nc_loc: int, n: int, levels: int):
    """Cached jitted 2-D program (same rationale as ``_mrhap_program``)."""
    body = functools.partial(
        _run_body_2d, iterations=iterations, lam=damping,
        nr_loc=nr_loc, nc_loc=nc_loc, n=n, levels=levels)
    tile = P(None, row_axis, col_axis)
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(tile,),
        out_specs=(P(None, row_axis), tile, tile)))
