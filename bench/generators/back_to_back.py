"""Whole calls back to back, one at a time, until the window is spent.

A batch user's traffic: the same job run again and again. The window
runs whole calls, so it ends when the first call that ends at or past
``seconds`` ends."""
from __future__ import annotations

import time


def drive(call, seconds: float, span) -> list:
    """Run ``call()`` until ``seconds`` have passed; returns
    [(start, end, result)]."""
    out = []
    t0 = time.perf_counter()
    while True:
        with span("bench.call"):
            start = time.perf_counter()
            result = call()
            end = time.perf_counter()
        out.append((start, end, result))
        if end - t0 >= seconds:
            return out
