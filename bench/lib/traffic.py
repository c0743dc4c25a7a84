"""The general open-loop request generator of the replica cells.

A traffic file gives the arrival process and the length distributions; the
seed deals a fixed set of values in another order. Every seed gets the same
set of inter-arrival gaps, prompt lengths and output lengths (stratified
quantiles of the stated distributions), so seeds change the order of the
work, not its amount, and two seeds' runs are comparable.

Arrival processes (times in seconds from the window's opening):

- ``poisson``: rate ``rate`` per second;
- ``on_off``: a fixed-phase square wave of period ``period``, at
  ``on_rate`` for the first ``on_seconds`` of each period and ``off_rate``
  for the rest (the on/off modulation of the program's
  ``workload.arrivals``, with its phase fixed so every seed sees the same
  bursts).

Both are sampled as a time-rescaled Poisson process: unit-mean exponential
gaps (their quantiles, dealt by the seed) mapped through the inverse of the
cumulative rate.

With ``"deal": "per_period"`` (``on_off`` only) the work is fixed period by
period instead of over the run: every on and every off segment holds the
same number of arrivals (its rate times its length, rounded), placed by the
segment's own set of gaps, and every on (off) segment's requests take the
same set of prompt and output lengths; the seed deals each segment's gaps
and lengths in another order. So each burst brings the same work in every
period and for every seed.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List

import numpy as np
from statistics import NormalDist


@dataclass
class Request:
    rid: int
    due_s: float          # seconds after the window opens (< 0: in flight)
    prompt: np.ndarray    # int32 token ids
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_set(spec: Dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a lognormal with median ``median`` and
    log-space deviation ``sigma``, clipped to ``[min, max]``."""
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    v = np.rint(spec["median"] * np.exp(spec["sigma"] * z)).astype(np.int64)
    return np.clip(v, spec["min"], spec["max"])


def cumulative_rate(arr: Dict, t: np.ndarray) -> np.ndarray:
    """Expected arrivals in ``[0, t)``."""
    if arr["kind"] == "poisson":
        return arr["rate"] * t
    if arr["kind"] == "on_off":
        P, on = arr["period"], arr["on_seconds"]
        per = arr["on_rate"] * on + arr["off_rate"] * (P - on)
        k, r = np.floor_divide(t, P), np.mod(t, P)
        part = np.where(r < on, arr["on_rate"] * r,
                        arr["on_rate"] * on + arr["off_rate"] * (r - on))
        return k * per + part
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def _inverse(arr: Dict, lam: np.ndarray, horizon: float) -> np.ndarray:
    grid = np.linspace(0.0, horizon, int(horizon * 1000) + 1)
    return np.interp(lam, cumulative_rate(arr, grid), grid)


def arrival_times(arr: Dict, horizon: float, seed: int) -> np.ndarray:
    """Due times in ``[0, horizon)``: as many as the process expects there,
    from the same set of gaps for every seed."""
    total = float(cumulative_rate(arr, np.asarray(horizon)))
    n = int(round(total))
    if n < 1:
        return np.zeros(0)
    gaps = -np.log1p(-_quantiles(n))
    gaps = gaps * (total / gaps.sum())  # the set spans the whole horizon
    gaps = np.random.default_rng([seed, 0]).permutation(gaps)
    lam = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return _inverse(arr, lam, horizon)


def _segment_counts(arr: Dict):
    P, on = arr["period"], arr["on_seconds"]
    return (int(round(arr["on_rate"] * on)),
            int(round(arr["off_rate"] * (P - on))))


def _segment_times(arr: Dict, horizon: float, seed: int):
    """``per_period`` arrivals: the due times of each on and off segment in
    turn, with the count the segment holds when whole."""
    P, on = arr["period"], arr["on_seconds"]
    n_on, n_off = _segment_counts(arr)
    rng = np.random.default_rng([seed, 0])
    out = []
    for k in range(int(np.ceil(horizon / P))):
        for lo, length, n in ((k * P, on, n_on), (k * P + on, P - on, n_off)):
            gaps = rng.permutation(-np.log1p(-_quantiles(n + 1)))
            t = lo + length * np.cumsum(gaps)[:-1] / gaps.sum()
            out.append((t[t < horizon], n))
    return out


def _per_period(traffic: Dict, seed: int, seconds: float, n_in: int):
    """Due times and lengths of the ``per_period`` deal: the in-flight
    requests' lengths from a set of their own, then each segment's from the
    set of its kind (a segment the window cuts short takes the first of its
    dealt lengths)."""
    segs = _segment_times(traffic["arrivals"], seconds, seed)
    rng = np.random.default_rng([seed, 1])
    sets = [(n_in, n_in)] + [(n, len(t)) for t, n in segs]
    lengths = {part: np.concatenate([
        rng.permutation(lognormal_set(traffic[part], full))[:n]
        for full, n in sets]) for part in ("prompt", "output")}
    due = np.concatenate([t for t, _ in segs])
    return due, lengths["prompt"], lengths["output"]


def generate(traffic: Dict, seed: int, seconds: float, vocab: int
             ) -> List[Request]:
    """The requests of one run: ``in_flight`` requests already admitted
    when the window opens (``due_s`` < 0; their output is what is left of
    theirs), then every request due in ``[0, seconds)``."""
    seed = int(seed)
    n_in = int(traffic["in_flight"])
    rng = np.random.default_rng([seed, 1])
    if traffic.get("deal") == "per_period":
        due, prompts, outputs = _per_period(traffic, seed, seconds, n_in)
        rng = np.random.default_rng([seed, 4])
    else:
        due = arrival_times(traffic["arrivals"], seconds, seed)
        n = n_in + len(due)
        prompts = rng.permutation(lognormal_set(traffic["prompt"], n))
        outputs = rng.permutation(lognormal_set(traffic["output"], n))
    n = n_in + len(due)
    # in flight: the part of each output still to come, stratified too
    left = np.maximum(np.ceil(outputs[:n_in] * _quantiles(n_in)[::-1]), 1)
    outputs[:n_in] = rng.permutation(left.astype(np.int64)) if n_in else left
    tok_rng = np.random.default_rng([seed, 2])
    reqs = []
    for i in range(n):
        prompt = tok_rng.integers(1, vocab, int(prompts[i])).astype(np.int32)
        t = -1.0 if i < n_in else float(due[i - n_in])
        reqs.append(Request(i, t, prompt, int(outputs[i])))
    return reqs
