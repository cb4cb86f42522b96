"""Open-loop arrivals of image-tile requests from independent clients.

Reads a traffic mix (``bench/traffic/<mix>.json``): ``rate_rps``, the
offered rate, so that a run of ``seconds`` offers ``round(rate_rps *
seconds)`` requests. What a request is comes from the configuration's
``requests``: one tile of one of its ``images``, cut by the fixed
``tiles`` grid (``generators.images.tiles``).

Every run sends each tile of each image equally often: whole passes over
all tiles, and for the rest of the count a fixed, seed-independent set of
tiles spread evenly over the images and the grid. Gaps between arrivals
are the exponential distribution's quantiles at (i + 1/2) / count,
scaled to the rate. The seed shuffles the order of the requests and,
apart, of the gaps. So every run offers the same count, the same multiset
of gaps and the same multiset of requests, and only their order differs:
how long a tile takes to converge depends on its content, and a seed
that chose the content would change the work. Latency is timed from each
request's due time, not from when the generator got to send it, and how
late the generator ran is kept per request.
"""
from __future__ import annotations

import time

import numpy as np

from generators.images import IMAGES, tiles


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 63), stream])


def request_count(traffic: dict, seconds: float) -> int:
    return max(1, int(round(traffic["rate_rps"] * seconds)))


def gaps(rate: float, count: int, seed: int) -> np.ndarray:
    """Seconds between arrivals: exponential quantiles, seed-shuffled."""
    q = (np.arange(count) + 0.5) / count
    g = -np.log1p(-q) / rate
    return _rng(seed, 1).permutation(g)


def catalog(requests: dict) -> list:
    """Every distinct request, images interleaved tile by tile:
    dicts with ``image``, ``tile`` (ty, tx) and ``points`` ((n, 3)
    float32)."""
    gy, gx = requests["tiles"]
    per_image = [[{"image": name, "tile": t, "points": pts}
                  for t, pts in tiles(IMAGES[name](), gy, gx)]
                 for name in requests["images"]]
    return [img[i] for i in range(gy * gx) for img in per_image]


def multiset(requests: dict, count: int) -> list:
    """The window's requests before shuffling: whole passes over the
    catalog, then ``count mod len(catalog)`` entries at even strides."""
    cat = catalog(requests)
    full, rest = divmod(count, len(cat))
    extra = [cat[(i * len(cat)) // rest] for i in range(rest)]
    return cat * full + extra


def plan(traffic: dict, seed: int, seconds: float, requests: dict) -> list:
    """The window's requests in due order: dicts with ``due`` (seconds
    from the window's start), ``n``, ``image``, ``tile`` and ``points``."""
    count = request_count(traffic, seconds)
    pool = multiset(requests, count)
    order = _rng(seed, 0).permutation(count)
    due = np.cumsum(gaps(traffic["rate_rps"], count, seed))
    return [dict(pool[int(j)], due=float(t),
                 n=int(pool[int(j)]["points"].shape[0]))
            for t, j in zip(due, order)]


def drive(requests: list, submit, t0: float, span) -> list:
    """Send each request at ``t0 + due`` through ``submit(points) ->
    Future``; returns one record per request with ``due``, ``sent`` and,
    once its future resolves, ``done`` (all ``time.perf_counter``
    seconds) and ``future``."""
    records = []
    for req in requests:
        due = t0 + req["due"]
        now = time.perf_counter()
        if due > now:
            with span("bench.wait"):
                time.sleep(due - now)
        rec = {"due": due, "n": req["n"]}
        with span("bench.send"):
            rec["sent"] = time.perf_counter()
            fut = submit(req["points"])
        rec["future"] = fut

        def stamp(_f, r=rec):
            r["done"] = time.perf_counter()

        fut.add_done_callback(stamp)
        records.append(rec)
    return records


def wait_all(records: list, deadline: float) -> None:
    """Wait for every future until ``deadline`` (perf_counter seconds);
    a future's done-callback runs after its waiters wake, so wait for
    the stamps too."""
    for rec in records:
        left = deadline - time.perf_counter()
        if left <= 0:
            break
        try:
            rec["future"].exception(timeout=left)
        except TimeoutError:
            break
    while time.perf_counter() < deadline and any(
            r["future"].done() and "done" not in r for r in records):
        time.sleep(1e-3)
