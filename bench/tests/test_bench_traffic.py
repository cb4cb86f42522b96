"""The open-loop generator: the same requests and gaps for every seed,
tiles that cover each image, and latency timed from the due time."""
from __future__ import annotations

import json
import os
import sys
import time
from collections import Counter
from concurrent.futures import Future

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from generators import open_loop  # noqa: E402
from lib import spans  # noqa: E402

with open(os.path.join(BENCH, "traffic", "serve.json")) as _f:
    SERVE = json.load(_f)
with open(os.path.join(BENCH, "configs", "seg_rgb.json")) as _f:
    REQUESTS = json.load(_f)["requests"]

SEEDS = (7, 2**31 + 12345)


def plan(seed, seconds, rate=None):
    traffic = dict(SERVE, rate_rps=rate or SERVE["rate_rps"])
    return open_loop.plan(traffic, seed, seconds, REQUESTS)


def key(r):
    return (r["image"], r["tile"])


def test_count_follows_the_rate():
    assert open_loop.request_count(SERVE, 40) == \
        round(SERVE["rate_rps"] * 40)
    assert len(plan(3, 40)) == open_loop.request_count(SERVE, 40)


@pytest.mark.parametrize("seconds", [10, 40])
def test_two_seeds_same_sizes_and_gaps(seconds):
    a, b = plan(SEEDS[0], seconds), plan(SEEDS[1], seconds)
    assert len(a) == len(b)
    assert Counter(r["n"] for r in a) == Counter(r["n"] for r in b)
    ga = np.diff([0.0] + [r["due"] for r in a])
    gb = np.diff([0.0] + [r["due"] for r in b])
    np.testing.assert_allclose(np.sort(ga), np.sort(gb), atol=1e-12)
    assert not np.allclose(ga, gb)            # the order differs
    assert [key(r) for r in a] != [key(r) for r in b]


def test_gaps_are_exponential_quantiles():
    g = np.sort(open_loop.gaps(4.0, 1000, 3))
    q = (np.arange(1000) + 0.5) / 1000
    np.testing.assert_allclose(g, -np.log1p(-q) / 4.0)
    assert g.mean() == pytest.approx(0.25, rel=0.01)


def test_same_seed_same_plan():
    a, b = plan(11, 10), plan(11, 10)
    assert [(r["due"], key(r)) for r in a] == [(r["due"], key(r)) for r in b]
    np.testing.assert_array_equal(a[3]["points"], b[3]["points"])


def test_tiles_cover_each_image_whole():
    gy, gx = REQUESTS["tiles"]
    cat = open_loop.catalog(REQUESTS)
    assert len(cat) == gy * gx * len(REQUESTS["images"])
    sizes = {"mandrill": 103 * 103, "buttons": 100 * 120}
    for name, pixels in sizes.items():
        mine = [c for c in cat if c["image"] == name]
        assert {c["tile"] for c in mine} == {
            (y, x) for y in range(gy) for x in range(gx)}
        assert sum(c["points"].shape[0] for c in mine) == pixels


@pytest.mark.parametrize("count", [50, 120, 255])
def test_every_tile_equally_often(count):
    """Whole passes over every tile, then the rest spread evenly: no tile
    is sent more than once beyond any other, on both images and across
    the grid's rows."""
    pool = Counter(map(key, open_loop.multiset(REQUESTS, count)))
    cat = open_loop.catalog(REQUESTS)
    assert sum(pool.values()) == count
    assert max(pool.values()) - min(pool.get(key(c), 0) for c in cat) <= 1
    extra = [k for k, v in pool.items() if v > count // len(cat)]
    gy = REQUESTS["tiles"][0]
    if len(extra) >= 2 * gy:
        assert {img for img, _ in extra} == set(REQUESTS["images"])
        assert {t[0] for _, t in extra} == set(range(gy))


def test_two_seeds_same_requests_in_another_order():
    a, b = plan(SEEDS[0], 40), plan(SEEDS[1], 40)
    assert Counter(map(key, a)) == Counter(map(key, b))
    assert list(map(key, a)) != list(map(key, b))


def test_points_are_rgb_pixels():
    r = plan(5, 5)[0]
    pts = r["points"]
    assert pts.shape == (r["n"], 3) and pts.dtype == np.float32
    assert pts.min() >= 0.0 and pts.max() <= 1.0


def test_stall_is_counted_from_the_due_time():
    """A submit that stalls 60 ms makes the next requests late; their
    lateness and latency count from when they were due."""
    reqs = [{"due": 0.01 * i, "n": 4, "points": None}
            for i in range(4)]

    def submit(_points, calls=[]):
        calls.append(1)
        if len(calls) == 1:
            time.sleep(0.06)
        f = Future()
        f.set_result(None)
        return f

    with spans.span("bench.setup"):     # load the profiler's hooks
        pass
    t0 = time.perf_counter()
    records = open_loop.drive(reqs, submit, t0, spans.span)
    open_loop.wait_all(records, time.perf_counter() + 1.0)
    late = [(r["sent"] - r["due"]) * 1e3 for r in records]
    assert late[0] < 5.0
    assert late[1] > 40.0 and late[2] > 30.0
    lat = [(r["done"] - r["due"]) * 1e3 for r in records]
    assert lat[1] >= late[1]
