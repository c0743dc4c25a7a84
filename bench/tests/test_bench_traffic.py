"""The traffic generators: deterministic from the seed, the stated medians
and clips, the same set of work for every seed, and the fleet trace equal
to the program's own ``flash_crowd_like``."""

import dataclasses
import json

import numpy as np
import pytest

from bench.lib import fleet_trace, traffic
from bench.tests.tiny_cells import ROOT

BIG_SEED = 2 ** 31 + 12345


def _mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("mix", ["chat_steady", "code_burst"])
def test_same_seed_same_requests(mix):
    t = _mix(mix)
    a = traffic.generate(t, BIG_SEED, 50.0, 49152)
    b = traffic.generate(t, BIG_SEED, 50.0, 49152)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)
    c = traffic.generate(t, BIG_SEED + 1, 50.0, 49152)
    assert [r.due_s for r in c] != [r.due_s for r in a]


@pytest.mark.parametrize("mix", ["chat_steady", "code_burst"])
def test_stated_medians_and_clips(mix):
    t = _mix(mix)
    for part in ("prompt", "output"):
        v = traffic.lognormal_set(t[part], 1001)
        assert v.min() >= t[part]["min"] and v.max() <= t[part]["max"]
        assert np.median(v) == pytest.approx(t[part]["median"], abs=1)
    reqs = traffic.generate(t, 7, 50.0, 49152)
    lens = [len(r.prompt) for r in reqs]
    assert min(lens) >= t["prompt"]["min"] and max(lens) <= t["prompt"]["max"]
    assert all(r.prompt.dtype == np.int32 and r.prompt.min() >= 1
               for r in reqs)


@pytest.mark.parametrize("mix", ["chat_steady", "code_burst"])
def test_every_seed_gets_the_same_work(mix):
    t = _mix(mix)
    sets = []
    for seed in (1, 2, BIG_SEED):
        reqs = traffic.generate(t, seed, 50.0, 49152)
        due = [r for r in reqs if r.due_s >= 0]
        assert all(0 <= r.due_s < 50.0 for r in due)
        assert sum(r.due_s < 0 for r in reqs) == t["in_flight"]
        sets.append((len(due), sorted(len(r.prompt) for r in reqs),
                     sorted(r.max_new for r in due)))
    assert sets[0][:2] == sets[1][:2] == sets[2][:2]


def test_on_off_rate_follows_its_phase():
    arr = _mix("code_burst")["arrivals"]
    due = traffic.arrival_times(arr, 1000.0, 3)  # 100 periods
    on = np.mod(due, arr["period"]) < arr["on_seconds"]
    expect_on = arr["on_rate"] * arr["on_seconds"] * 100  # 1125, sd ~34
    assert on.sum() == pytest.approx(expect_on, rel=0.06)
    assert len(due) == round(float(traffic.cumulative_rate(
        arr, np.asarray(1000.0))))


def test_fleet_trace_is_the_programs_at_quick_scale():
    from repro.runtime.serving import build_serving_workload
    from repro.sched.scenarios import get_scenario

    sc = get_scenario("serve_flash_crowd")
    cfg = sc.serving_config(quick=True)
    p = dict(_mix("flash_crowd_point")["trace"], n_servers=400, n_short=8,
             horizon_s=4 * 3600.0)
    ours = fleet_trace.fleet_trace(p, dataclasses.asdict(cfg))
    reqs, _, max_ticks, meta = build_serving_workload(
        sc.trace(quick=True, seed=p["trace_seed"]), cfg)
    assert np.array_equal(ours["arrival"], [r.arrival for r in reqs])
    assert np.array_equal(ours["gen_len"], [r.gen_len for r in reqs])
    assert np.array_equal(ours["pinned"], meta["pinned_per_tick"])
    assert ours["max_ticks"] == max_ticks
    arr, gen = fleet_trace.reorder_sizes(ours, BIG_SEED)
    assert np.array_equal(arr, ours["arrival"])
    assert np.array_equal(np.sort(gen), np.sort(ours["gen_len"]))
    assert not np.array_equal(gen, ours["gen_len"])


def test_per_period_bursts_bring_the_same_work():
    t = _mix("code_burst")
    arr = t["arrivals"]
    P, on = arr["period"], arr["on_seconds"]
    n_on = round(arr["on_rate"] * on)
    bursts = []
    for seed in (1, BIG_SEED):
        reqs = traffic.generate(t, seed, 50.0, 49152)
        for k in range(5):
            b = [r for r in reqs if k * P <= r.due_s < k * P + on]
            assert len(b) == n_on
            bursts.append((sorted(len(r.prompt) for r in b),
                           sorted(r.max_new for r in b)))
    assert all(b == bursts[0] for b in bursts)
