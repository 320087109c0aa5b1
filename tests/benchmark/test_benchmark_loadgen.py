"""The generator and the latency arithmetic: seeded schedules, nearest-rank
percentiles, and latency taken from the time a request was due."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, loadgen  # noqa: E402

TRAFFIC = json.loads((REPO / "benchmark" / "traffic" / "served_56rps.json").read_text())
served = harness.load_plugin("drivers", "served")


def test_same_seed_same_schedule_and_sizes():
    a = served.plan_requests(TRAFFIC, 2.0, seed=7)
    b = served.plan_requests(TRAFFIC, 2.0, seed=7)
    assert a == b and len(a) > 0
    assert [t for t, *_ in a] == sorted(t for t, *_ in a)


def test_another_seed_another_schedule():
    a = served.plan_requests(TRAFFIC, 2.0, seed=7)
    b = served.plan_requests(TRAFFIC, 2.0, seed=8)
    assert [p[0] for p in a] != [p[0] for p in b]
    assert [p[2:] for p in a[:50]] != [p[2:] for p in b[:50]]


def test_arrival_count_follows_the_rate():
    n = len(loadgen.arrivals({"rate_rps": 500.0}, 20.0, seed=1))
    assert abs(n - 10000) < 5 * 100  # Poisson: sigma = sqrt(10000) = 100
    assert loadgen.arrivals({"rate_rps": 0.0}, 5.0, seed=1) == []


def test_bursts_add_clumps_on_top_of_the_base_load():
    base = loadgen.arrivals({"rate_rps": 100.0}, 10.0, seed=3)
    spec = {"rate_rps": 100.0, "bursts": {"every_s": 1.0, "width_s": 0.1, "mult": 5.0}}
    burst = loadgen.arrivals(spec, 10.0, seed=3)
    assert burst == sorted(burst) and len(burst) > len(base) + 200
    in_clumps = sum(1 for t in burst if (t % 1.0) < 0.1 and t >= 1.0)
    assert in_clumps > 0.3 * len(burst)


def test_size_mix_is_the_three_class_mix():
    sizes = loadgen.assign_sizes(TRAFFIC["classes"], 20000, seed=5)
    ones = sum(1 for _c, n in sizes if n == 1) / len(sizes)
    bulk = sum(1 for _c, n in sizes if n == 32) / len(sizes)
    mean = sum(n for _c, n in sizes) / len(sizes)
    assert abs(ones - 0.70) < 0.02 and abs(bulk - 0.05) < 0.01
    assert abs(mean - 3.37) < 0.15
    assert {n for _c, n in sizes} == {1, 2, 4, 8, 16, 32}
    assert max(n for _c, n in sizes) <= TRAFFIC["server"]["max_batch"]


@pytest.mark.parametrize(
    "xs,q,want",
    [
        ([], 50, None),
        ([3.0], 99, 3.0),
        ([1, 2, 3, 4], 50, 2),
        ([1, 2, 3, 4], 75, 3),
        ([1, 2, 3, 4], 76, 4),
        ([4, 1, 3, 2], 0, 1),
        (list(range(1, 101)), 99, 99),
        (list(range(1, 1001)), 99, 990),
    ],
)
def test_percentile_is_nearest_rank(xs, q, want):
    assert loadgen.percentile(xs, q) == want


@pytest.mark.parametrize("xs,want", [([], None), ([5], 5), ([1, 9, 3], 3), ([1, 2, 3, 10], 2.5)])
def test_median(xs, want):
    assert loadgen.median(xs) == want


class _Clock:
    """A clock that moves only when told: no wall time in this test."""

    def __init__(self):
        self.now = 100.0

    def monotonic(self):
        return self.now

    def sleep(self, s):
        self.now += s


class _Handle:
    def __init__(self, at):
        self.status, self.result, self.completed_at, self.done = "OK", np.zeros(1), at, True

    def wait(self, _timeout):
        return True


class _StalledServer:
    """Holds the first sender for ``stall`` seconds, then answers at once."""

    def __init__(self, clock, stall):
        self.clock, self.stall, self.seen = clock, stall, 0

    def submit(self, x, cls=""):
        self.seen += 1
        if self.seen == 1:
            self.clock.now += self.stall
        return _Handle(self.clock.now)


class _Ctx:
    def span(self, _name):
        import contextlib

        return contextlib.nullcontext()


def test_latency_runs_from_due_time_not_from_send_time(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(served, "time", clock)
    plan = [(0.00, "interactive", 1, 0), (0.01, "interactive", 1, 1), (0.02, "interactive", 1, 2)]
    res = served.drive(_Ctx(), _StalledServer(clock, 0.05), plan, np.zeros((4, 1)), [], 1.0)
    # the stall delays the two requests behind it; timed from their send
    # they would read 0 ms, timed from when they were due they read the wait
    assert res["latencies_ms"] == pytest.approx([50.0, 40.0, 30.0])
    assert res["late_ms"] == pytest.approx([0.0, 40.0, 30.0])
    assert res["outcome"]["OK"] == 3 and res["outcome"]["unanswered"] == 0


def test_rejected_and_unanswered_requests_count_and_have_no_latency(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(served, "time", clock)

    class Server:
        n = 0

        def submit(self, x, cls=""):
            Server.n += 1
            if Server.n == 2:
                raise RuntimeError("queue full")
            h = _Handle(clock.now)
            if Server.n == 3:
                h.done, h.wait = False, lambda _t: False
            return h

    plan = [(0.0, "a", 1, 0), (0.1, "a", 1, 0), (0.2, "a", 1, 0)]
    res = served.drive(_Ctx(), Server(), plan, np.zeros((4, 1)), [], 0.5)
    assert len(res["latencies_ms"]) == 1
    assert res["outcome"]["OK"] == 1 and res["outcome"]["unanswered"] == 1
    assert res["outcome"]["rejected:RuntimeError"] == 1


def test_sample_fits_its_budget_and_is_seeded():
    plan = served.plan_requests(TRAFFIC, 3.0, seed=2)
    a = served.pick_sample(plan, 48, seed=2)
    assert a == served.pick_sample(plan, 48, seed=2) and a
    assert sum(plan[i][2] for i in a) <= 48


offline = harness.load_plugin("drivers", "offline")


class _PerfClock:
    def __init__(self):
        self.now = 50.0

    def perf_counter(self):
        return self.now


class _OfflineCtx:
    """Spans that cost host time, so that something lies between two chains."""

    def __init__(self, clock):
        self.clock, self.samples = clock, {}

    def span(self, _name):
        import contextlib

        self.clock.now += 0.0005
        return contextlib.nullcontext()

    def log(self, _msg):
        pass


def test_chain_readings_cover_the_window_and_the_window_share_shows_slow_chains(monkeypatch):
    clock = _PerfClock()
    monkeypatch.setattr(offline, "time", clock)
    calls = {"n": 0}

    def fwd(_params, _x):  # 1 ms a call; the third chain of four calls runs at half speed
        clock.now += 0.002 if 8 <= calls["n"] < 12 else 0.001
        calls["n"] += 1
        return 0.0

    ctx, rates = _OfflineCtx(clock), []
    done = offline.run_chains(ctx, fwd, None, [None], batch=2, chain_len=4, seconds=0.05, rates=rates)
    assert done["failed"] == 0 and len(rates) == done["attempted"] == 10
    # nothing between two readings: their times add up to the window
    assert sum(4 * 2 / r for r in rates) == pytest.approx(done["seconds"])
    assert done["images"] == 10 * 8
    # the median does not see the one slow chain; images over the window do
    assert loadgen.median(rates) == pytest.approx(8 / 0.005)
    ctx.samples["offline.rate_img_s"] = rates
    ctx.samples["offline.window_rate_img_s"] = [done["images"] / done["seconds"]]
    share = harness.load_plugin("layer_metrics", "step.window_rate_share").read(ctx)
    assert share == pytest.approx(100 * (80 / 0.054) / (8 / 0.005))
    assert harness.load_plugin("layer_metrics", "step.window_rate_share").read(_OfflineCtx(clock)) is None
