"""Multi-pod dry run: lower + compile every (arch x shape x mesh) cell.

For each cell the step function (train_step / prefill / decode) is lowered
with ShapeDtypeStruct inputs (no allocation), compiled for the production
mesh, and the compiled artifact is mined for the roofline terms:
  - cost_analysis(): per-device HLO FLOPs + bytes accessed
  - optimized HLO text: collective wire bytes (launch/hlo_analysis.py)
  - memory_analysis(): per-device buffer sizes (proves it fits)

Usage:
  python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k \
      --mesh single --out results/dryrun/granite_train_single.json
  python -m repro.launch.dryrun --all --mesh both   # every applicable cell
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (including repro.*):
# jax locks the device count at first init (see MULTI-POD DRY-RUN spec).

import argparse
import functools
import json
import math
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, applicable_shapes, arch_names, get_arch
from repro.configs.base import ArchConfig, ShapeConfig
from repro.launch.hlo_analysis import V5E, roofline_terms
from repro.launch.hlo_cost import analyze as hlo_analyze
from repro.launch.mesh import make_production_mesh
from repro.models import (
    Mode, input_sharding, input_specs, model_init, model_state_init,
    model_state_specs, pick_mode,
)
from repro.serve.engine import make_decode_step, make_prefill_step
from repro.sharding import shape_safe_shardings
from repro.train.loop import (
    init_train_state, make_train_step, train_state_specs,
)


def _eval_shape_with_specs(fn):
    """eval_shape a (params, specs) init; capture the static spec tree."""
    box = {}

    def wrapped(*a):
        p, s = fn(*a)
        box["specs"] = s
        return p

    sds = jax.eval_shape(wrapped, jax.ShapeDtypeStruct((2,), jnp.uint32))
    return sds, box["specs"]


def n_active_params(cfg: ArchConfig, params_sds) -> tuple[int, int]:
    """(total, active) param counts; MoE experts scaled by top_k/E."""
    total = active = 0
    flat = jax.tree_util.tree_flatten_with_path(params_sds)[0]
    for path, leaf in flat:
        keypath = "/".join(str(k) for k in path)
        n = int(leaf.size)
        total += n
        if cfg.n_experts and "moe" in keypath and any(
                t in keypath for t in ("gate", "up", "down")):
            active += n * cfg.top_k // cfg.n_experts
        else:
            active += n
    return total, active


def model_flops(cfg: ArchConfig, shape: ShapeConfig, active: int) -> float:
    """MODEL_FLOPS: 6*N*D train, 2*N*D inference (D = processed tokens)."""
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        return 6.0 * active * d
    if shape.kind == "prefill":
        d = shape.global_batch * shape.seq_len
        return 2.0 * active * d
    return 2.0 * active * shape.global_batch      # decode: one token/seq


def build_cell(cfg: ArchConfig, shape: ShapeConfig, mesh):
    """Returns (jitted fn, arg ShapeDtypeStructs) ready to lower."""
    params_sds, param_specs = _eval_shape_with_specs(
        lambda k: model_init(k, cfg))
    in_sds = input_specs(cfg, shape)
    in_specs_tree = input_sharding(cfg, shape)
    in_shard = shape_safe_shardings(mesh, in_sds, in_specs_tree)

    if shape.kind == "train":
        mode = pick_mode(cfg, "train", shape.seq_len)
        step = make_train_step(cfg, mode)
        state_sds = jax.eval_shape(init_train_state, params_sds)
        # ZeRO only where it pays (see train_state_specs docstring)
        state_specs = train_state_specs(
            param_specs, zero=cfg.family not in ("ssm", "hybrid"))
        state_shard = shape_safe_shardings(mesh, state_sds, state_specs)
        fn = jax.jit(step, in_shardings=(state_shard, in_shard),
                     out_shardings=(state_shard, None),
                     donate_argnums=(0,))
        return fn, (state_sds, in_sds)

    buf = shape.seq_len
    # decode: unrolled layer loop + per-layer donated caches (Perf iter 4)
    layout = "list" if (shape.kind == "decode"
                        and cfg.family != "audio") else "stacked"
    layout = os.environ.get("REPRO_DECODE_LAYOUT", layout) \
        if shape.kind == "decode" and cfg.family != "audio" else layout
    states_sds = jax.eval_shape(
        lambda: model_state_init(cfg, shape.global_batch, buf,
                                 layout=layout))
    states_specs = model_state_specs(cfg, layout=layout)
    states_shard = shape_safe_shardings(mesh, states_sds, states_specs)
    params_shard = shape_safe_shardings(mesh, params_sds, param_specs)

    if shape.kind == "prefill":
        step = make_prefill_step(cfg, shape.seq_len)
    else:
        step = make_decode_step(cfg)
    fn = jax.jit(step, in_shardings=(params_shard, in_shard, states_shard),
                 out_shardings=(None, states_shard),
                 donate_argnums=(2,))
    return fn, (params_sds, in_sds, states_sds)


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = math.prod(mesh.devices.shape)
    t0 = time.time()
    fn, args = build_cell(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as exc:  # noqa: BLE001
        mem_d = {"error": str(exc)}

    hlo = compiled.as_text()
    hc = hlo_analyze(hlo, world=chips)     # trip-count-aware walker

    params_sds, _ = _eval_shape_with_specs(lambda k: model_init(k, cfg))
    total_p, active_p = n_active_params(cfg, params_sds)
    mflops = model_flops(cfg, shape, active_p)
    terms = roofline_terms(hc.flops, hc.bytes, hc.wire_bytes, chips)
    hlo_total = hc.flops * chips
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "hlo_flops_per_chip": hc.flops, "hlo_bytes_per_chip": hc.bytes,
        "collective_bytes_per_chip": hc.wire_bytes,
        "collective_ops": hc.collective_ops,
        "collective_by_type": hc.wire_by_type,
        "xla_cost_flops_once": float(cost.get("flops", 0.0)),
        "params_total": total_p, "params_active": active_p,
        "model_flops": mflops,
        "useful_ratio": mflops / hlo_total if hlo_total else None,
        "memory": mem_d,
        **terms,
    }
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for name in arch_names():
            for sh in applicable_shapes(get_arch(name)):
                cells.append((name, sh.name))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    results, failures = [], []
    for arch, shape in cells:
        for multi in meshes:
            label = f"{arch} x {shape} x {'multi' if multi else 'single'}"
            try:
                res = run_cell(arch, shape, multi)
                results.append(res)
                print(f"[OK] {label}: compile={res['compile_s']}s "
                      f"flops/chip={res['hlo_flops_per_chip']:.3e} "
                      f"coll/chip={res['collective_bytes_per_chip']:.3e}B "
                      f"dominant={res['dominant']}", flush=True)
            except Exception as exc:  # noqa: BLE001
                failures.append({"cell": label, "error": str(exc)})
                traceback.print_exc()
                print(f"[FAIL] {label}: {exc}", flush=True)

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"results": results, "failures": failures}, f, indent=1)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
