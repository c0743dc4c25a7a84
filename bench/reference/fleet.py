"""Plain reference of the fleet program: the serving fleet's policy
simulated tick by tick in NumPy, and the guarantees every run has to keep.

Nothing here imports the program. Two comparisons use it:

- :func:`simulate` runs the fleet's policy (CloudCoaster §3: Eagle probing
  of the on-demand pool with a fallback to the active transients, the §3.2
  threshold controller with its provisioning delay and budget K, §3.3
  hedging onto the unpinned on-demand reserve, pinning by long jobs that
  displaces residents and hands queues back to the router) with its own
  random stream. Routing draws cannot be the program's, so a run's answers
  are compared with it as distributions: the wait percentiles, the count
  of completions and the mean number of online transients
  (:func:`policy_gaps`).
- :func:`violations` counts answers that break a guarantee, whatever the
  draws: no request starts before it arrives; a request that finishes has
  started and took at least its decode length (one token per tick; a
  displaced or hedged request takes longer); at no tick do the requests
  that ran without a break (finish - start == decode length, so they held
  a slot throughout) outnumber the fleet's slots, and the program's own
  count of busy slots never exceeds them. :func:`summary` recounts the
  program's reported summary from its answers.

Requests still unfinished at the horizon are counted, not refused: a long
request that starts late in the drain tail (25% past the last arrival) may
not end inside it.
"""

from __future__ import annotations

import heapq
from collections import deque
from typing import Dict

import numpy as np


def _pct(x: np.ndarray, q: float) -> float:
    if x.size == 0:
        return 0.0
    x = np.sort(x.astype(np.float64))
    r = q / 100.0 * (x.size - 1)
    lo = int(np.floor(r))
    hi = min(lo + 1, x.size - 1)
    return float(x[lo] + (x[hi] - x[lo]) * (r - lo))


def summary(arrival, start, finish, tick_s: float) -> Dict[str, float]:
    started = start >= 0
    w = (start[started] - arrival[started]).astype(np.float64) * tick_s
    return {
        "n_done": float(np.sum(finish >= 0)),
        "short_avg_wait_s": float(w.mean()) if w.size else 0.0,
        "short_max_wait_s": float(w.max()) if w.size else 0.0,
        "short_p50_wait_s": _pct(w, 50),
        "short_p90_wait_s": _pct(w, 90),
        "short_p99_wait_s": _pct(w, 99),
    }


def violations(arrival, gen, start, finish, busy, cap) -> Dict[str, int]:
    """Counts of answers that break each guarantee (all 0 when sound)."""
    arrival, gen = np.asarray(arrival), np.asarray(gen)
    start, finish = np.asarray(start), np.asarray(finish)
    busy, cap = np.asarray(busy), np.asarray(cap)
    T = len(cap)
    started, done = start >= 0, finish >= 0
    whole = done & started & (finish - start == gen)
    occ = np.zeros(T + 1, np.int64)
    np.add.at(occ, np.clip(start[whole], 0, T), 1)
    np.add.at(occ, np.clip(finish[whole], 0, T), -1)
    occ = np.cumsum(occ)[:T]
    return {
        "early_start": int(np.sum(started & (start < arrival))),
        "finish_without_start": int(np.sum(done & ~started)),
        "too_fast": int(np.sum(done & started & (finish - start < gen))),
        "over_capacity": int(np.sum(occ > cap) + np.sum(busy > cap)),
        "unfinished": int(np.sum(~done)),
    }


def check_point(arrival, gen, out: Dict, reported: Dict, tick_s: float
                ) -> Dict[str, float]:
    """Violations of one grid point, and the widest gap between the
    program's reported summary and the recount."""
    n = len(arrival)
    start = np.asarray(out["start"])[:n]
    finish = np.asarray(out["finish"])[:n]
    v = violations(arrival, gen, start, finish, out["busy"], out["cap"])
    mine = summary(np.asarray(arrival), start, finish, tick_s)
    gap = max(abs(float(reported[k]) - mine[k]) for k in mine)
    return {"violations": float(sum(v[k] for k in v if k != "unfinished")),
            "unfinished": float(v["unfinished"]), "summary_gap": gap}


def control_answers(arrival, gen, horizon: int) -> Dict[str, np.ndarray]:
    """The control: every request served the tick it arrives, as a fleet
    without a slot limit would, so it rents no transient (it breaks the
    slot guarantee whenever arrivals outrun the fleet)."""
    arrival, gen = np.asarray(arrival), np.asarray(gen)
    return {"start": arrival.copy(), "finish": arrival + gen,
            "active_transients": np.zeros(horizon, np.int64)}


# ----------------------------------------------------------- the policy


class _Draws:
    """Uniform draws in blocks (one generator call per 64k draws)."""

    def __init__(self, seed):
        self.rng = np.random.default_rng(seed)
        self.buf, self.i = self.rng.random(1 << 16), 0

    def index(self, n: int) -> int:
        if self.i == len(self.buf):
            self.buf, self.i = self.rng.random(1 << 16), 0
        u = self.buf[self.i]
        self.i += 1
        return min(int(u * n), n - 1)


def simulate(arrival, gen, pinned, horizon: int, fleet: Dict, *,
             threshold: float, max_transient: int, max_slots: int,
             drain_preference: str, seed) -> Dict[str, np.ndarray]:
    """One trajectory of the fleet's policy over ``horizon`` ticks.

    ``arrival``/``gen`` are the requests (arrival tick, decode length) in
    arrival order, ``pinned`` the number of on-demand replicas long jobs
    hold at each tick, ``fleet`` the configuration's ``fleet`` group.
    Returns each request's first start and finish tick (-1: none) and the
    online transients, busy slots and paid slots of every tick."""
    if fleet["revocation_mttf"]:
        raise NotImplementedError("the reference simulates no revocations")
    arrival = np.asarray(arrival, np.int64)
    gen = np.asarray(gen, np.int64)
    N, S, K = len(arrival), int(max_slots), int(max_transient)
    n_ond = int(fleet["n_replicas"]) + int(fleet["n_reserve"])
    delay = max(int(round(fleet["provisioning_delay"] / fleet["tick_s"])), 1)
    d, retries = int(fleet["probe_d"]), int(fleet["probe_retries"])
    hf = float(fleet["hedge_factor"])
    R = n_ond + 4 * K + 8  # rows; a drained transient's row is reused
    rows = np.arange(R)
    is_tr = rows >= n_ond
    online = ~is_tr
    draining = np.zeros(R, bool)
    pin_row = np.zeros(R, bool)
    online_at = np.zeros(R, np.int64)
    seq = np.zeros(R, np.int64)  # creation order of transients
    pend = np.zeros(R, np.int64)  # decode ticks queued or in slots
    qlen = np.zeros(R, np.int64)
    queues = [deque() for _ in range(R)]
    slot_e = np.full((R, S), -1, np.int64)  # entry: rid, or rid + N (copy)
    slot_rem = np.zeros((R, S), np.int64)
    free_rows = list(range(R - 1, n_ond - 1, -1))
    active = []  # active transients (online, not draining), by creation
    n_seq = n_drain = 0
    pipe = deque()  # ticks at which ordered transients come online
    start = np.full(N, -1, np.int64)
    finish = np.full(N, -1, np.int64)
    hedged = np.zeros(N, bool)
    routed_at = arrival.copy()
    loc = np.full(N, -1, np.int64)  # row holding the original, or -1
    due = []  # (tick it may be hedged, rid, routed_at) of originals
    blocked = []  # due, waiting for an unpinned on-demand replica
    draws = _Draws(seed)
    first = np.searchsorted(arrival, np.arange(horizon + 1), side="left")
    out_tr = np.zeros(horizon, np.int64)
    out_busy = np.zeros(horizon, np.int64)
    out_cap = np.zeros(horizon, np.int64)

    def enqueue(r: int, e: int, t: int) -> None:
        queues[r].append(e)
        qlen[r] += 1
        g = gen[e % N]
        pend[r] += g
        if e < N:
            routed_at[e] = t
            loc[e] = r
            if is_tr[r] and not hedged[e]:
                heapq.heappush(due, (int(np.floor(t + hf * g)) + 1, e, t))

    def route(e: int, t: int) -> None:
        best = -1
        for _ in range(retries):  # probe d on-demand replicas a round
            for _ in range(d):
                i = draws.index(n_ond)
                if not pin_row[i] and (best < 0 or pend[i] < pend[best]):
                    best = i
            if best >= 0:
                break
        if best < 0 and active:  # every probe hit a long job
            for _ in range(min(d, len(active))):
                i = active[draws.index(len(active))]
                if best < 0 or pend[i] < pend[best]:
                    best = i
        if best < 0:  # no transient either: least pending, unpinned first
            key = np.where(pin_row[:n_ond], 1e12, 0.0) + pend[:n_ond] / S
            best = int(np.argmin(key))
        enqueue(best, e, t)

    want_prev = 0
    for t in range(horizon):
        # pinning: the first `want` on-demand replicas go to long jobs
        want = min(int(pinned[t]) if t < len(pinned) else 0, n_ond)
        pin_row[:n_ond] = rows[:n_ond] < want
        for r in range(want_prev, want):
            moved = []
            for k in range(S):
                e = int(slot_e[r, k])
                if e >= 0:
                    if e < N and not hedged[e]:
                        start[e] = -1  # no live copy elsewhere: restart
                    moved.append(e)
            moved += list(queues[r])
            slot_e[r], slot_rem[r] = -1, 0
            queues[r].clear()
            qlen[r] = pend[r] = 0
            for e in moved:
                if e < N:
                    loc[e] = -1
                if finish[e % N] < 0:
                    route(e, t)
        want_prev = want
        # provisioning: transients ordered `delay` ticks ago come online
        while pipe and pipe[0] <= t:
            pipe.popleft()
            r = free_rows.pop()
            online[r], draining[r], online_at[r], seq[r] = True, False, t, n_seq
            n_seq += 1
            active.append(r)
        # this tick's arrivals
        for e in range(first[t], first[t + 1]):
            route(e, t)
        # §3.2 controller: add while the long share of the projected fleet
        # is over the threshold and the budget allows; else drain
        n_act, n_pend = len(active), len(pipe)
        stable = n_ond + n_act
        add = 0
        while (want / max(stable + n_drain + n_pend + add, 1) > threshold
               and n_act + n_pend + add < K):
            add += 1
        if add:
            pipe.extend([t + delay] * add)
        else:
            rem = 0
            while (n_act - rem > 0
                   and want / max(stable - rem - 1, 1) < threshold):
                rem += 1
            for _ in range(rem):
                if drain_preference == "least_loaded":
                    load = [qlen[r] + np.sum(slot_e[r] >= 0) for r in active]
                    r = active[int(np.argmin(load))]
                elif drain_preference == "oldest":
                    r = active[int(np.argmin(online_at[active]))]
                else:
                    r = active[int(np.argmax(online_at[active]))]
                active.remove(r)
                draining[r] = True
                n_drain += 1
        # §3.3 hedging: an original waiting on an active transient longer
        # than hedge_factor x its length is copied onto the least-loaded
        # unpinned on-demand replica; the first copy to finish wins
        while due and due[0][0] <= t:
            blocked.append(heapq.heappop(due))
        reserve = np.flatnonzero(~pin_row[:n_ond])
        if blocked and reserve.size:  # else they wait for a free reserve
            ok = []
            for c in blocked:
                _, e, at = c
                r = loc[e]
                if (routed_at[e] == at and r >= 0 and is_tr[r] and online[r]
                        and not draining[r] and not hedged[e]
                        and finish[e] < 0):
                    ok.append((seq[r], at, e))
            blocked = []
            load = qlen[reserve] + np.sum(slot_e[reserve] >= 0, axis=1)
            for _, _, e in sorted(ok):
                hedged[e] = True
                j = int(np.argmin(load))
                load[j] += 1
                enqueue(int(reserve[j]), e + N, t)
        # every online unpinned replica: cancel the losers of hedged pairs,
        # admit from the queue into free slots, decode one token a slot
        act = online & ~pin_row
        occ = (slot_e >= 0) & act[:, None]
        stale = occ & (finish[np.where(occ, slot_e, 0) % N] >= 0)
        if stale.any():
            pend -= np.sum(np.where(stale, slot_rem, 0), axis=1)
            loc[slot_e[stale & (slot_e < N)]] = -1
            slot_e[stale], slot_rem[stale] = -1, 0
            occ &= ~stale
        n_free = S - occ.sum(axis=1)
        for r in np.flatnonzero(act & (qlen > 0) & (n_free > 0)):
            q = queues[r]
            while q and n_free[r]:
                e = q.popleft()
                qlen[r] -= 1
                p = e % N
                if finish[p] >= 0:  # a duplicate whose pair already won
                    pend[r] -= gen[p]
                    continue
                if start[p] < 0:
                    start[p] = t
                k = int(np.argmax(slot_e[r] < 0))
                slot_e[r, k], slot_rem[r, k] = e, gen[p]
                n_free[r] -= 1
        occ = (slot_e >= 0) & act[:, None]
        busy_r = occ.sum(axis=1)
        slot_rem -= occ
        pend -= busy_r
        fin = occ & (slot_rem <= 0)
        if fin.any():
            fe = slot_e[fin]
            p = fe % N
            finish[p] = np.where(finish[p] < 0, t + 1, finish[p])
            loc[fe[fe < N]] = -1
            slot_e[fin], slot_rem[fin] = -1, 0
        out_busy[t] = busy_r.sum()
        out_cap[t] = act.sum() * S
        gone = (act & draining & (qlen == 0)
                & ~np.any(slot_e >= 0, axis=1))
        for r in np.flatnonzero(gone):
            online[r] = draining[r] = False
            n_drain -= 1
            free_rows.append(int(r))
        out_tr[t] = np.sum(online & is_tr)
    return {"start": start, "finish": finish, "active_transients": out_tr,
            "busy": out_busy, "cap": out_cap}


def _waits(arrival, start) -> np.ndarray:
    s = start >= 0
    return (start[s] - arrival[s]).astype(np.float64)


def policy_gaps(arrival, gen, answers: Dict, ref: Dict) -> Dict[str, float]:
    """How far one run's answers lie from the reference's trajectory.

    ``wait_gap``: the widest gap among the mean, 90th and 99th percentile
    of the waits (in ticks), over the reference's value or the mean decode
    length, whichever is larger; ``p50_wait_gap`` the same for the median,
    which swings more between two trajectories. ``done_gap``: the gap in
    completed requests over all requests. ``transients_gap``: the gap in
    the mean number of online transients, over the reference's (or 1)."""
    arrival = np.asarray(arrival)
    n = len(arrival)
    a_start = np.asarray(answers["start"])[:n]
    a_fin = np.asarray(answers["finish"])[:n]
    wa, wr = _waits(arrival, a_start), _waits(arrival, ref["start"])
    floor = float(np.mean(gen))

    def gap(a, r):
        return abs(a - r) / max(r, floor)

    mean = (float(np.mean(wa)) if wa.size else 0.0,
            float(np.mean(wr)) if wr.size else 0.0)
    tails = [(_pct(wa, q), _pct(wr, q)) for q in (90, 99)]
    done_gap = abs(float(np.sum(a_fin >= 0))
                   - float(np.sum(ref["finish"] >= 0))) / n
    tr_a = float(np.mean(np.asarray(answers["active_transients"])))
    tr_r = float(np.mean(ref["active_transients"]))
    return {"wait_gap": max(gap(a, r) for a, r in [mean] + tails),
            "p50_wait_gap": gap(_pct(wa, 50), _pct(wr, 50)),
            "done_gap": done_gap,
            "transients_gap": abs(tr_a - tr_r) / max(tr_r, 1.0)}
