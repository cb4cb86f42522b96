#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload sift128.solve20 --seed 7 --seconds 40 --trace 0
    python3 bench/run.py --list

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell's configuration file (``bench/configs/<config>.json``,
which names the system driver in ``bench/systems/`` and the plain
reference in ``bench/reference/``), its traffic mix
(``bench/traffic/<traffic>.json``, which names its generator in
``bench/generators/``) and each per-layer metric
(``bench/metrics/<metric>.py``, a ``read(run, trace)`` function). A new
cell, mix or metric is new files plus entries in ``BENCHMARK.json``.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces
the window with the JAX profiler and prints its per-layer metrics, the
device's busy and window seconds and a breakdown. Every run checks what
its window produced against the plain reference and prints each number
compared beside its limit, on the last lines of standard error and under
``checks``, the last key of the result line. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


class NoProgram(RuntimeError):
    """The checkout holds no system under test (``src/repro``)."""


@dataclasses.dataclass
class Context:
    """What a system driver gets: the cell's data and the run's knobs."""
    root: str
    workload: dict
    config: dict
    traffic: dict
    generator: object
    reference: object
    seed: int
    seconds: int
    trace_dir: str | None
    t_process: float
    compiles: object


@dataclasses.dataclass
class Run:
    """What a system driver returns."""
    e2e: dict                  # end-to-end metric name -> value
    attempted: int
    failed: int
    checks: list               # [(name, value, limit)], correct iff <=
    data: dict                 # what the metric readers read
    memory_peak_bytes: int | None
    notes: dict = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return self.failed == 0 and bool(self.checks) and all(
            math.isfinite(v) and v <= lim for _, v, lim in self.checks)


# ------------------------------------------------------------- lookup
def load_spec(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def list_cells(root: str) -> list:
    return [w["name"] for w in load_spec(root)["workloads"]]


def resolve(root: str, workload: str) -> tuple:
    """-> (spec, workload entry, config dict, traffic dict)."""
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    wl = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    config = load_json(os.path.join(root, entry["file"]))
    bench = os.path.join(root, "bench")
    traffic = load_json(os.path.join(bench, "traffic",
                                     wl["traffic"] + ".json"))
    return spec, wl, config, traffic


def cell_metrics(spec: dict, workload: str, kind: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports."""
    reported_e2e = {m["name"] for m in spec["end_to_end"]
                    if workload in m.get("workloads", [workload])}
    out = []
    for m in spec[kind]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in reported_e2e:
            out.append(m)
    return out


# ------------------------------------------------------------- device
def check_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"needs a TPU; JAX's first device is "
                       f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chip(s); JAX sees "
                       f"{len(devices)}")
    return devices


def use_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


# --------------------------------------------------------------- run
def make_context(root: str, workload: str, seed: int, seconds: int,
                 trace: bool, *, require_tpu: bool = True,
                 t_process: float = T_PROCESS) -> tuple:
    """Resolve a cell and check the device; -> (spec, system module,
    Context, devices)."""
    spec, wl, config, traffic = resolve(root, workload)
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        raise NoProgram(f"no system under test at {root}/src/repro")
    for path in (os.path.join(root, "src"), os.path.join(root, "bench")):
        if path not in sys.path:
            sys.path.insert(0, path)
    if require_tpu:
        devices = check_devices(int(wl["chips"]))
        use_compile_cache(root)
    else:
        import jax
        devices = jax.devices()
    from lib.spans import Compiles, span
    with span("bench.setup"):       # the first annotation loads the
        pass                        # profiler's hooks: not in a window
    bench = os.path.join(root, "bench")
    system = load_module(os.path.join(bench, "systems",
                                      config["system"] + ".py"),
                         "bench_system_" + config["system"])
    ctx = Context(
        root=root, workload=wl, config=config, traffic=traffic,
        generator=load_module(
            os.path.join(bench, "generators", traffic["generator"] + ".py"),
            "bench_generator_" + traffic["generator"]),
        reference=load_module(
            os.path.join(bench, "reference", config["reference"] + ".py"),
            "bench_reference_" + config["reference"]),
        seed=int(seed), seconds=int(seconds),
        trace_dir=(os.path.join(root, ".bench_traces", f"{workload}.{seed}")
                   if trace else None),
        t_process=t_process, compiles=Compiles())
    return spec, system, ctx, devices


def run_cell(root: str, workload: str, seed: int, seconds: int,
             trace: bool, *, require_tpu: bool = True,
             t_process: float = T_PROCESS) -> dict:
    """Run one cell once; returns the result dict (see module doc)."""
    spec, system, ctx, devices = make_context(
        root, workload, seed, seconds, trace, require_tpu=require_tpu,
        t_process=t_process)
    wl = ctx.workload
    bench = os.path.join(root, "bench")
    run = system.run(ctx)
    run.data.setdefault("device_kind", devices[0].device_kind)

    reduced = None
    if trace:
        from lib import trace as tr
        try:
            reduced = tr.reduce(tr.load_events(tr.find_xplane(
                ctx.trace_dir)))
        finally:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)

    metrics = {}
    kind = "per_layer" if trace else "end_to_end"
    for m in cell_metrics(spec, workload, kind):
        value = run.e2e.get(m["name"])
        if value is None:
            path = os.path.join(bench, "metrics", m["name"] + ".py")
            if os.path.isfile(path):
                value = load_module(path, "bench_metric_" + m["name"]).read(
                    run, reduced)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(wl["chips"]),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["notes"] = run.notes
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in run.checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=int)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--list", action="store_true",
                    help="print the cells BENCHMARK.json names and exit")
    args = ap.parse_args(argv)
    if args.list:
        print("\n".join(list_cells(ROOT)))
        return 0
    if not args.workload:
        ap.error("--workload is required")
    seconds = args.seconds or load_spec(ROOT)["run_seconds"]
    try:
        result = run_cell(ROOT, args.workload, args.seed, seconds,
                          bool(args.trace))
    except (NoDevice, NoProgram) as exc:
        print(f"bench: {exc}", file=sys.stderr, flush=True)
        return 3
    print("bench: notes " + json.dumps(result["notes"]), file=sys.stderr)
    for name, c in result["checks"].items():
        ok = c["value"] <= c["limit"]
        print(f"bench: check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
