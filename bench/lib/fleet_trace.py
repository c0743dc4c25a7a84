"""The fleet cells' request stream: a Yahoo-calibrated two-class job trace
with flash-crowd spikes, mapped onto a serving fleet.

A copy of the arithmetic of the program's ``workload.builders.
flash_crowd_like`` (MMPP calm/burst base rate, modulated by flash-crowd
spike windows, sampled by thinning; a two-class lognormal job mix) and
``runtime.serving.build_serving_workload`` (short tasks become decode
requests, long tasks the per-tick pinning signal), drawing from one NumPy
stream in the same order, so the yardstick does not move with the program.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import numpy as np


def _lognormal_mean(rng, mean, sigma, size):
    return rng.lognormal(np.log(mean) - 0.5 * sigma ** 2, sigma, size)


def _arrivals(rng, p: Dict, rate_avg: float, horizon: float) -> np.ndarray:
    """MMPP(calm, burst) x flash-crowd envelope, by thinning."""
    bm, cf = p["burst_mult"], p["calm_frac"]
    rc = rate_avg / (cf + (1 - cf) * bm)
    rates = np.array([rc, bm * rc])
    dwells = (p["dwell_calm"], p["dwell_burst"])
    # the calm/burst state path (cyclic two-state chain)
    state = 0 if rng.random() <= cf else 1
    switches, states = [0.0], [state]
    t = rng.exponential(dwells[state])
    while t < horizon:
        state = 1 - state
        switches.append(t)
        states.append(state)
        t += rng.exponential(dwells[state])
    switches = np.asarray(switches)
    path_rates = rates[np.asarray(states)]
    # spike windows, and the envelope's mean so the average stays rate_avg
    dur, n_sp, mult = p["spike_duration"], p["n_spikes"], p["spike_mult"]
    starts = rng.random(n_sp) * max(horizon - dur, 0.0)
    env_mean = 1.0 + (mult - 1.0) * min(n_sp * dur / horizon, 1.0)
    lam_max = rates.max() * max(mult, 1.0) / env_mean

    def lam(tt):
        idx = np.clip(np.searchsorted(switches, tt, side="right") - 1,
                      0, len(path_rates) - 1)
        hot = np.zeros(tt.shape, bool)
        for s in starts:
            hot |= (tt >= s) & (tt < s + dur)
        return path_rates[idx] * np.where(hot, mult, 1.0) / env_mean

    n_cand = rng.poisson(lam_max * horizon)
    cand = np.sort(rng.random(n_cand) * horizon)
    keep = rng.random(n_cand) * lam_max <= lam(cand)
    return cand[keep]


def fleet_trace(p: Dict, fleet: Dict) -> Dict[str, np.ndarray]:
    """Requests and pinning for the trace parameters ``p`` (the traffic
    file's ``trace``) on the fleet ``fleet`` (the configuration's ``fleet``).

    Returns ``arrival`` (ticks, sorted), ``gen_len`` (ticks), the per-tick
    ``pinned`` signal and ``max_ticks`` (25% drain tail past the last
    arrival)."""
    rng = np.random.default_rng(p["trace_seed"])
    n_general = p["n_servers"] - p["n_short"]
    mix = p["mix"]
    work_per_job = (mix["long_frac"] * mix["long_tasks_mean"]
                    * mix["long_mean_s"]
                    + (1 - mix["long_frac"]) * mix["short_tasks_mean"]
                    * mix["short_mean_s"])
    horizon = float(p["horizon_s"])
    rate = ((p["long_util"] * n_general + p["short_util"] * p["n_short"])
            * horizon / work_per_job / horizon)
    times = _arrivals(rng, p, rate, horizon)

    tick_s = fleet["tick_s"]
    horizon_ticks = max(int(math.ceil(horizon / tick_s)), 1)
    req_arr, req_gen, long_s, long_e = [], [], [], []
    for t in times:
        if rng.random() < mix["long_frac"]:
            n = max(1, int(_lognormal_mean(rng, mix["long_tasks_mean"],
                                           mix["tasks_sigma"], 1)[0]))
            d = _lognormal_mean(rng, mix["long_mean_s"],
                                mix["long_dur_sigma"], n)
            long_s += [t] * n
            long_e += list(t + d)
        else:
            n = max(1, int(_lognormal_mean(rng, mix["short_tasks_mean"],
                                           mix["tasks_sigma"], 1)[0]))
            d = _lognormal_mean(rng, mix["short_mean_s"],
                                mix["short_dur_sigma"], n)
            a = min(int(t / tick_s), horizon_ticks - 1)
            req_arr += [a] * n
            req_gen += [max(int(round(x / tick_s)), 1) for x in d]
    # stable order by arrival tick (ties keep trace order), capped
    order = np.argsort(np.asarray(req_arr, np.int64), kind="stable")
    order = order[:fleet["max_requests"]]
    arrival = np.asarray(req_arr, np.int64)[order]
    gen_len = np.asarray(req_gen, np.int64)[order]

    diff = np.zeros(horizon_ticks + 1)
    if long_s:
        s = np.minimum((np.asarray(long_s) / tick_s).astype(int),
                       horizon_ticks)
        e = np.minimum(np.ceil(np.asarray(long_e) / tick_s).astype(int),
                       horizon_ticks)
        np.add.at(diff, s, 1.0)
        np.add.at(diff, e, -1.0)
    conc = np.cumsum(diff)[:horizon_ticks]
    n_rep = fleet["n_replicas"]
    pinned = np.clip(np.rint(conc * (n_rep / fleet["n_general_ref"])
                             * fleet["pin_scale"]), 0, n_rep).astype(int)
    last = int(arrival[-1]) if arrival.size else 0
    max_ticks = int(min(horizon_ticks, last + 1) * 1.25) + 1
    return {"arrival": arrival, "gen_len": gen_len, "pinned": pinned,
            "max_ticks": max_ticks}


def reorder_sizes(trace: Dict[str, np.ndarray], seed: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-seed variation that keeps the work: the same arrivals and the
    same set of request sizes, the sizes dealt to the requests in an order
    drawn from ``seed``."""
    perm = np.random.default_rng([int(seed), 1]).permutation(
        len(trace["gen_len"]))
    return trace["arrival"], trace["gen_len"][perm]
