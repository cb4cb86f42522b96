"""The numeric contract of the cross-path parity checks (docs/solver.md
"Numeric contract").

Exact on every path: exemplars, labels, sweep counts, convergence flags,
per-sweep assignment-change traces, and top-k edge index sets.

Within tolerance: f32 message state (s, r, a, tau, phi, c) and similarity
values, elementwise ``|actual - desired| <= RTOL * (|desired| + scale)``
with ``scale = max |desired|`` over the array. Paths that evaluate the
same formulas in another fusion or reduction order (XLA's choice per
program, a Pallas tile order, a shard_map body, the TPU's matmul passes)
round apart by a few ulps of the *summands*: a message that is a sum or
difference of terms of size ``scale`` carries an absolute error that
scales with ``scale``, however close to zero the result lands. Measured
drift between the repo's paths on CPU stays below ``1e-6 * scale``.

Imported by the test modules (``helpers.parity``) and by the subprocess
helpers next to it (``parity``).
"""
import numpy as np

RTOL = 1e-5


def _tol(desired: np.ndarray) -> np.ndarray:
    finite = np.abs(desired[np.isfinite(desired)])
    scale = float(finite.max()) if finite.size else 0.0
    return RTOL * (np.abs(desired) + scale)


def values_close(actual, desired) -> bool:
    actual, desired = np.asarray(actual), np.asarray(desired)
    if actual.shape != desired.shape:
        return False
    same = actual == desired            # covers matching infinities
    with np.errstate(invalid="ignore"):
        near = np.abs(actual - desired) <= _tol(desired)
    return bool(np.all(same | near))


def assert_values_close(actual, desired, err_msg: str = "") -> None:
    actual, desired = np.asarray(actual), np.asarray(desired)
    assert actual.shape == desired.shape, (actual.shape, desired.shape,
                                           err_msg)
    if not values_close(actual, desired):
        with np.errstate(invalid="ignore", divide="ignore"):
            err = np.abs(actual.astype(np.float64) - desired)
            ratio = np.where(actual == desired, 0.0, err / _tol(desired))
        worst = np.unravel_index(np.nanargmax(ratio), err.shape)
        raise AssertionError(
            f"{err_msg}: values outside the numeric contract (rtol="
            f"{RTOL} of |value| + max|value|); worst at {worst}: "
            f"{actual[worst]!r} vs {desired[worst]!r}")


def state_close(a, b, n: int) -> bool:
    """Dense or top-k HAP state ``a`` against ``b`` cut to its first ``n``
    rows (the padded sharded layout)."""
    return all(values_close(np.asarray(getattr(b, f))[:, :n], getattr(a, f))
               for f in ("s", "r", "a", "tau", "phi", "c"))
