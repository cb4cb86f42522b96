"""Reduce a JAX profiler trace to device busy time, per-op time and idle
gaps named by the benchmark's own host spans.

``load_events(path)`` reads an ``.xplane.pb`` with ``jax.profiler.
ProfileData`` into plain records; everything after that (``reduce``) is
pure Python over those records, so the tests check it on a small recorded
trace without a chip.

Records (all times in ns on the trace's clock):

* device ops: ``{"device": "/device:TPU:0", "module": "jit_f",
  "op": "sort.6", "start": ns, "dur": ns}`` from each device plane's
  "XLA Ops" line, the module taken from the "XLA Modules" event that
  contains the op;
* host spans: ``{"name": "bench.window", "start": ns, "dur": ns}`` from
  the host plane, only the benchmark's own (names starting ``bench.``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

_OP_NAME = re.compile(r"^%?([^\s=]+)")
_MODULE_NAME = re.compile(r"^(.*?)(\(\d+\))?$")


def op_short_name(hlo_text: str) -> str:
    """``%sort.6 = (f32[...]) sort(...)`` -> ``sort.6``."""
    m = _OP_NAME.match(hlo_text.strip())
    return m.group(1) if m else hlo_text


def module_short_name(name: str) -> str:
    """``jit_f(5157808763314170431)`` -> ``jit_f``."""
    return _MODULE_NAME.match(name).group(1)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_events(path: str) -> dict:
    """xplane.pb -> {"ops": [...], "spans": [...]} (see module doc)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted(
                (e.start_ns, e.start_ns + e.duration_ns,
                 module_short_name(e.name))
                for e in lines.get("XLA Modules", ()))
            starts = [m[0] for m in modules]
            for e in lines.get("XLA Ops", ()):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                module = (modules[i][2] if i >= 0
                          and e.start_ns < modules[i][1] else "?")
                ops.append({"device": plane.name, "module": module,
                            "op": op_short_name(e.name),
                            "start": float(e.start_ns),
                            "dur": float(e.duration_ns)})
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append({"name": e.name,
                                      "start": float(e.start_ns),
                                      "dur": float(e.duration_ns)})
    return {"ops": ops, "spans": spans}


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _self_times(ops, lo, hi):
    """(op, self ns inside [lo, hi)) for each op that overlaps the
    window. An op that holds others on its device (a ``while`` around
    its body's fusions) keeps only the time its children do not cover,
    so per-op and per-module sums count each nanosecond once."""
    out = []
    for dev in sorted({o["device"] for o in ops}):
        mine = sorted((o for o in ops if o["device"] == dev),
                      key=lambda o: (o["start"], -o["dur"]))
        stack = []                       # [op, end, self ns]
        for o in mine:
            end = o["start"] + o["dur"]
            while stack and stack[-1][1] <= o["start"]:
                out.append(stack.pop())
            clipped = _clip([(o["start"], end)], lo, hi)
            own = clipped[0][1] - clipped[0][0] if clipped else 0.0
            if stack and end <= stack[-1][1]:
                stack[-1][2] -= own
            stack.append([o, end, own])
        out.extend(stack)
    return [(o, own) for o, end, own in out
            if _clip([(o["start"], end)], lo, hi)]


def _span_at(spans, t):
    """Innermost benchmark span open at ``t`` (latest start), else
    ``"none"``."""
    best = None
    for sp in spans:
        if sp["start"] <= t < sp["start"] + sp["dur"] and \
                sp["name"] != WINDOW_SPAN:
            if best is None or sp["start"] > best["start"]:
                best = sp
    return best["name"] if best else "none"


def reduce(events: dict, top: int = 10) -> dict:
    """Busy and idle time over the window, top device ops and longest
    idle gaps.

    The window is the ``bench.window`` span. Busy time is the union of
    op intervals inside it, per device, averaged over the devices that
    ran any op. Returns seconds: ``window_s``, ``busy_s``, ``idle_share``
    (0..1), ``op_s`` ({"module/op": s}), ``module_s`` ({module: s}),
    ``device_ops`` and ``idle_gaps`` (lists of [name, s], longest first).
    """
    windows = [s for s in events["spans"] if s["name"] == WINDOW_SPAN]
    if not windows:
        raise ValueError("trace holds no bench.window span")
    lo = windows[0]["start"]
    hi = lo + windows[0]["dur"]
    by_device: dict = {}
    op_s: dict = {}
    module_s: dict = {}
    for op, self_ns in _self_times(events["ops"], lo, hi):
        s, e = _clip([(op["start"], op["start"] + op["dur"])], lo, hi)[0]
        by_device.setdefault(op["device"], []).append((s, e))
        key = f"{op['module']}/{op['op']}"
        op_s[key] = op_s.get(key, 0.0) + self_ns * 1e-9
        module_s[op["module"]] = module_s.get(op["module"], 0.0) \
            + self_ns * 1e-9
    window_s = (hi - lo) * 1e-9
    busy, gaps = [], []
    spans = [s for s in events["spans"] if s["name"] != WINDOW_SPAN]
    for dev, ivs in sorted(by_device.items()):
        merged = _union(ivs)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g1 - g0, 0.5 * (g0 + g1)))
    busy_s = sum(busy) / len(busy) if busy else 0.0
    gaps.sort(key=lambda g: -g[0])
    device_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else 1.0,
            "devices": len(busy), "op_s": op_s, "module_s": module_s,
            "device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": [[_span_at(spans, mid), g * 1e-9]
                          for g, mid in gaps[:top]]}


def module_seconds(reduced: dict, name: str, *, exclude: bool = False
                   ) -> float:
    """Self seconds of the modules whose name contains ``name`` (or, with
    ``exclude``, of every other module) in a reduced trace."""
    return sum(t for m, t in reduced["module_s"].items()
               if (name in m) != exclude)
