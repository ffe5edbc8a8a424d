"""The traffic generator: determinism, the same pool for every seed, the
open loop's seeded Poisson gaps, and SQL the program's parser accepts."""

import json
import os

import _bench_path
import numpy as np
import pytest

from mbench import traffic

MIXES = sorted(os.path.splitext(f)[0] for f in
               os.listdir(os.path.join(_bench_path.ROOT, "bench", "traffic")))


def _mix(name):
    if name == "open":          # the open-loop kind, over a real mix
        return dict(_mix(MIXES[0]), loop="open", rate=2.0)
    return traffic.load_mix(os.path.join(_bench_path.ROOT, "bench"), name)


@pytest.mark.parametrize("name", MIXES + ["open"])
def test_same_seed_same_requests(name):
    mix = _mix(name)
    if mix["loop"] == "open":
        a = traffic.open_schedule(mix, 2**40 + 3, 30.0)
        b = traffic.open_schedule(mix, 2**40 + 3, 30.0)
        assert [r["sql"] for r in a[0]] == [r["sql"] for r in b[0]]
        assert np.array_equal(a[1], b[1])
    else:
        a = traffic.closed_lists(mix)
        b = traffic.closed_lists(mix)
        assert json.dumps(a) == json.dumps(b)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_sends_the_same_pool(name):
    mix = _mix(name)
    pool = [r for lst in traffic.closed_lists(mix) for r in lst]
    assert pool == traffic.draw_requests(mix, len(pool), "window")
    kinds = [r["spec"]["kind"] for r in pool]
    assert kinds != sorted(kinds, key=[t["kind"] for t in
                                       mix["templates"]].index)
    counts = traffic.template_counts([t["weight"] for t in mix["templates"]],
                                     len(pool))
    for kind in set(kinds):
        assert kinds.count(kind) == sum(
            c for t, c in zip(mix["templates"], counts) if t["kind"] == kind)


def test_open_loop_gaps_are_poisson_quantiles():
    mix = _mix("open")
    r1, d1 = traffic.open_schedule(mix, 1, 30.0)
    r2, d2 = traffic.open_schedule(mix, 2**33 + 1, 30.0)
    assert r1 == r2 and not (d1 == d2).all()
    n = len(r1)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= 30.0 / gaps.sum()
    for d in (d1, d2):
        assert all(np.isclose(gaps, g).any() for g in np.diff(d))
    assert d1[0] == 0.0 and d1[-1] < 30.0
    assert len(r1) == round(mix["rate"] * 30.0)


def test_template_counts_follow_the_weights():
    assert traffic.template_counts([0.3, 0.2, 0.15, 0.1, 0.05, 0.05],
                                   256) == [91, 60, 45, 30, 15, 15]
    assert sum(traffic.template_counts([1, 1, 1], 10)) == 10


@pytest.mark.parametrize("name", MIXES)
def test_sql_parses_to_the_spec(name):
    from repro.core.queries import parse
    mix = _mix(name)
    for req in traffic.draw_requests(mix, 200, "window"):
        plan = parse(req["sql"])
        spec = req["spec"]
        assert plan.k == spec.get("k", plan.k)
        assert getattr(plan, "desc", spec.get("desc")) == spec.get(
            "desc", getattr(plan, "desc", None))


def test_bin_ranges_snap_to_edges():
    rng = np.random.default_rng(0)
    for _ in range(200):
        lv, uv = traffic.draw_range(rng, {"bins": 16})
        assert lv < uv and (lv * 16).is_integer() and (uv * 16).is_integer()
    for _ in range(200):
        lv, uv = traffic.draw_range(rng, {"width": [0.1, 0.3],
                                          "within": [0.0, 1.0],
                                          "round": 0.001})
        assert 0.0 <= lv < uv <= 1.0 + 1e-9
        assert 0.099 <= uv - lv <= 0.301
