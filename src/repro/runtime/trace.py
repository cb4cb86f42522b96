"""Names the profiler sees: named scopes on the sweep's device ops and
host spans around ``solve()``.

Scopes (``jax.named_scope``) change only op metadata: each device op's
``op_name`` in the optimized HLO, which a profiler trace shows as the
op's ``tf_op``, then carries the path of scopes it was traced under, for
example ``jit(run_topk_scoped)/while/body/closed_call/hap_alpha/hap_colsum/
scatter-add``. A fusion carries its root op's path. One Jacobi sweep
(``repro.core.hap.jacobi_sweep``) scopes each job; the sparse column
statistics (``repro.kernels.topk_ops``) scope their scatter and their
gathers inside the job that calls them:

=================  ====================================================
``hap_tau``        Eq 2.4, the upward message (reads the column sums
                   the previous sweep's ``hap_alpha`` scattered)
``hap_c``          Eq 2.6, the cluster preference (row max)
``hap_rho``        Eq 2.1, responsibilities (row top-2) and damping
``hap_phi``        Eq 2.5, the downward message (row max)
``hap_alpha``      Eq 2.2/2.3, availabilities and damping (holds
                   ``hap_colsum`` and ``hap_gather``)
``hap_s_next``     Eq 2.7, the similarity refinement (``s_mode`` only)
``hap_assign``     Eq 2.8 decode and the assignment-change count
``hap_colsum``     the column sums over stored edges (a scatter-add)
``hap_gather``     the column statistics gathered back through the
                   column map, with the arithmetic fused onto them
=================  ====================================================

No scope is named like a JAX primitive, so a path component that reads
``hap_gather`` is always this scope, never a bare ``gather`` op.

Spans (``jax.profiler.TraceAnnotation``) are host intervals on the
profiler's clock, so they line up with the device ops of the same
trace. They record only while a profiler runs (``jax.profiler.trace``);
otherwise each costs a few microseconds.

Counters: ``edge_layout()`` returns what the process's last edge-list
layout (``repro.solver.backends``, in the ``repro.solve.layout`` span)
recorded: ``edge_layout.buckets`` (row blocks), ``.entries`` (stored
edges plus one self slot a row), ``.padded`` (entries the sweep computes
on), ``.max_degree`` and ``.host_s`` (seconds of the host step).
"""
from __future__ import annotations

import itertools

import jax

SCOPE_TAU = "hap_tau"
SCOPE_C = "hap_c"
SCOPE_RHO = "hap_rho"
SCOPE_PHI = "hap_phi"
SCOPE_ALPHA = "hap_alpha"
SCOPE_S_NEXT = "hap_s_next"
SCOPE_ASSIGN = "hap_assign"
SCOPE_COLSUM = "hap_colsum"
SCOPE_GATHER = "hap_gather"

#: every scope a sweep program's ops may carry
SWEEP_SCOPES = (SCOPE_TAU, SCOPE_C, SCOPE_RHO, SCOPE_PHI, SCOPE_ALPHA,
                SCOPE_S_NEXT, SCOPE_ASSIGN, SCOPE_COLSUM, SCOPE_GATHER)

#: The XLA program name (``jit_run_topk_scoped``) of the single-device
#: sparse sweep, ``repro.solver.topk.run_topk``. JAX's persistent
#: compilation cache leaves op metadata out of its key, so under its
#: earlier name a cache filled before the scopes existed handed back an
#: executable whose ops carry none. Rename it when the scopes change.
SWEEP_PROGRAM = "run_topk_scoped"

#: the whole call (backend, n and the process's call number as metadata)
SPAN_SOLVE = "repro.solve"
#: the top-k build: similarities, preference, layout
SPAN_BUILD = "repro.solve.build"
#: the sweep program, until its sweep count and trace are on the host
SPAN_SWEEPS = "repro.solve.sweeps"
#: padding strip, canonical exemplars, labels
SPAN_FINALIZE = "repro.solve.finalize"

#: the spans every ``dense_topk`` solve writes
SPANS = (SPAN_SOLVE, SPAN_BUILD, SPAN_SWEEPS, SPAN_FINALIZE)

#: an edge list laid out for the sweep, on the host (inside the build;
#: edge-list input only)
SPAN_LAYOUT = "repro.solve.layout"

#: the counters of one edge-list layout, in this order
EDGE_LAYOUT_COUNTERS = ("buckets", "entries", "padded", "max_degree",
                        "host_s")

_solve_calls = itertools.count(1)
_edge_layout: dict | None = None


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A host span named ``name``, with ``meta`` as its arguments."""
    return jax.profiler.TraceAnnotation(name, **meta)


def solve_span(backend: str, n: int) -> jax.profiler.TraceAnnotation:
    """The ``repro.solve`` span of one call, numbered within the
    process so the solves of one trace are told apart."""
    return span(SPAN_SOLVE, backend=backend, n=n, call=next(_solve_calls))


def record_edge_layout(**counters) -> None:
    """Keep one edge-list layout's counters (``EDGE_LAYOUT_COUNTERS``)."""
    global _edge_layout
    _edge_layout = {f"edge_layout.{name}": counters[name]
                    for name in EDGE_LAYOUT_COUNTERS}


def edge_layout() -> dict | None:
    """The counters of the process's last edge-list layout, by their
    ``edge_layout.*`` names; None before the first."""
    return None if _edge_layout is None else dict(_edge_layout)
