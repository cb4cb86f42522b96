"""Machine-readable benchmark records.

Every bench main() calls ``emit(name, rows)`` after printing its CSV
lines, writing ``BENCH_<name>.json`` in the working directory. The
nightly workflow uploads these as artifacts (the perf trajectory), and
``check_regression.py`` gates PR runs against the committed
``baseline_smoke.json``.
"""
from __future__ import annotations

import json
import os
import platform
import subprocess
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")


def peak_rss_mb() -> float:
    """Peak resident set size of this process tree, in MB.

    ``ru_maxrss`` of the process itself plus the max over its reaped
    children (bench workers fork subprocesses for forced device counts
    and big-N solves — their peak is usually *the* peak). Linux reports
    KB, macOS bytes; 0.0 where ``resource`` is unavailable.
    """
    try:
        import resource
        rss = max(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss,
                  resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss)
        scale = 1024.0 if platform.system() == "Darwin" else 1.0
        return round(rss * scale / 1024.0, 2)
    except Exception:
        return 0.0


def run_cpu_child(script: str, args, devices: int, timeout: float) -> dict:
    """Run a bench worker on ``devices`` forced host CPU devices and
    return the JSON record it prints last, stamped ``platform: cpu``.

    The child emulates a ``devices``-worker mesh on virtual CPU devices.
    ``JAX_PLATFORMS=cpu`` is explicit: a parent on a chip host may hold
    the chip, and a child that reached for it would fail or hang."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([SRC, env.get("PYTHONPATH", "")])
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, script, *map(str, args)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    rec["platform"] = "cpu"
    return rec


def _git_sha() -> str:
    """Commit the record was produced from: CI env first (no subprocess
    on runners), then git; "unknown" when neither is available."""
    sha = os.environ.get("GITHUB_SHA")
    if sha:
        return sha
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def emit(name: str, rows: list, meta: dict | None = None,
         out_dir: str = ".") -> str:
    """Write BENCH_<name>.json: {"bench", "rows", "meta"}; returns path.

    Every record is stamped with the git SHA and jax version so the
    nightly bench trajectory is attributable to a commit + toolchain,
    and with the process tree's peak RSS so memory-wall claims are
    measured, not inferred. A record taken off the CPU must name its
    device: an accelerator row without a ``device_kind`` cannot be
    compared with anything, so it raises instead of being written.
    """
    import jax
    devices = jax.devices()
    backend = devices[0].platform
    n_devices = len(devices)
    device_kind = getattr(devices[0], "device_kind", "") or ""
    if not device_kind:
        if backend != "cpu":
            raise RuntimeError(
                f"bench record on platform {backend!r} cannot name its "
                "device kind; refusing to write it")
        device_kind = "cpu"
    jax_version = jax.__version__
    rec = {
        "bench": name,
        "rows": rows,
        "meta": {
            "unix_time": int(time.time()),
            "platform": platform.platform(),
            "python": platform.python_version(),
            "git_sha": _git_sha(),
            "jax_version": jax_version,
            "jax_backend": backend,
            "n_devices": n_devices,
            # device kind makes rows comparable across runners; sharded
            # rows additionally carry the mesh shape they ran on
            "device_kind": device_kind,
            "peak_rss_mb": peak_rss_mb(),
            **(meta or {}),
        },
    }
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=float)
    print(f"[bench] wrote {path} ({len(rows)} rows)")
    return path
