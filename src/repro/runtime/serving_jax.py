"""JAX-native vectorized serving engine — the whole fleet as fixed-shape
arrays, one ``lax.scan`` over decode ticks, one device program per sweep
cube (``lax.map`` over grid points on CPU, ``vmap`` on parallel backends).

``repro.runtime.serving.ElasticServingFleet`` is the bit-exact oracle: a
~650-line Python tick loop over replica objects. This module re-expresses
the same semantics in the ``core/simjax`` mold (MaxText static-shapes
idiom) so a full (threshold x max_transient x max_slots) sweep cube — and a
seed batch on top — compiles to **one** device program:

  * replica state is ``(n_replicas,)`` / ``(n_replicas, slot_cap)`` arrays
    (occupancy, pending ticks, drain/pin/online flags);
  * every replica owns a bounded ring buffer of queued request ids;
  * the request stream is padded to a fixed length, per-tick arrivals are
    consumed through a bounded window, and displaced / revoked requests
    recycle through a global reroute ring;
  * the §3.2 controller's unit loops run as exact vectorized predicates
    (leading-true counts over a ``[0, K]`` candidate vector, same float
    comparisons as the Python loop);
  * §3.3 hedging duplicates a request id onto the on-demand reserve —
    first completion wins, the stale copy is cancelled at its next
    slot/queue touch — with at most ``hedge_cap`` new hedges per tick.

**No dynamic shapes anywhere**: queue capacity, the routing window, the
hedge scan, the per-tick flush of displaced queues and the lifetime buffer
are all bucketed in :class:`FleetSpec` (a frozen, hashable dataclass that
keys the compiled-program cache, see :func:`cache_info`).

Known, deliberate deviations from the Python oracle (the equivalence tests
in ``tests/test_serving_jax.py`` bound their effect at quick scale):

  * routing draws come from the JAX PRNG, not NumPy's — distributions
    match, individual draws don't (routing itself is sequential within a
    tick, same waterfilling as the oracle);
  * a newly pinned / revoked replica's *queue* is recycled through the
    reroute ring over a few ticks (``flush_cap`` entries per tick) instead
    of instantaneously — slot residents are displaced immediately;
  * ``BurstGuardProbing``'s per-class admission is projected onto plain
    Eagle probing (the guard only redirects fallback traffic when a free
    general replica exists — exactly when probing usually finds one);
  * queue-position hedging only scans the first ``hedge_scan`` queue
    entries per transient.

The deterministic pinned-occupancy path (single on-demand replica, at most
one active transient — no random routing choice anywhere) reproduces the
oracle exactly; ``tests/test_serving_jax.py`` pins that bit-for-bit.
"""

from __future__ import annotations

import math
import time
from collections import namedtuple
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.runtime.serving import Request, ServingFleetConfig

INT = "int32"

DRAIN_CODES = {"least_loaded": 0, "oldest": 1, "youngest": 2}


def _pow2(n: int, lo: int = 1) -> int:
    n = max(int(n), lo)
    return 1 << (n - 1).bit_length()


# --------------------------------------------------------------- static spec

@dataclass(frozen=True)
class FleetSpec:
    """Static-shape bundle: every field is a Python scalar, so a spec is
    hashable and keys the compiled-program cache. Anything that must stay
    sweepable (threshold, budget, max_slots, hedge factor, revocation rate)
    is a *traced* parameter instead — see :func:`make_params`."""

    n_ondemand: int      # on-demand replicas (base fleet + reserve)
    transient_cap: int   # transient replica slots (>= any swept budget K)
    slot_cap: int        # decode slots per replica (>= any swept max_slots)
    queue_cap: int       # per-replica request ring capacity
    route_cap: int       # reroute-ring pops AND arrivals consumed per tick
    horizon: int         # scan length in ticks
    n_requests: int      # padded request-stream length
    pipe_len: int        # provisioning delay in ticks (shift register)
    probe_d: int
    probe_retries: int
    flush_cap: int       # displaced queue entries recycled per replica/tick
    admit_window: int    # queue-head entries considered per admit pass
    hedge_scan: int      # queue-head entries scanned for hedge eligibility
    hedge_cap: int       # max new hedge duplicates per tick
    lifetime_cap: int    # recorded transient lifetimes (sum/count exact)
    drain_code: int      # DRAIN_CODES[drain_preference]
    spot_pricing: bool   # SpotAwareProbing's rework term in the fallback key
    n_tenants: int = 1   # tenant count is SHAPE (credit vectors, per-tenant
    #                      accumulators); credit rates/bursts stay traced so
    #                      a credit-budget sweep reuses one program

    @property
    def n_replicas(self) -> int:
        return self.n_ondemand + self.transient_cap


def make_spec(cfg: ServingFleetConfig, *, n_requests: int, max_ticks: int,
              max_arrivals_per_tick: int,
              transient_cap: Optional[int] = None,
              slot_cap: Optional[int] = None,
              queue_cap: Optional[int] = None,
              drain_preference: str = "least_loaded",
              spot_pricing: bool = False,
              n_tenants: int = 1) -> FleetSpec:
    """Derive the static spec from a resolved config + workload size.

    ``transient_cap`` / ``slot_cap`` must cover the *largest* swept budget /
    ``max_slots`` so one compiled program serves the whole cube (masked
    columns cost flops, not a retrace)."""
    k_cap = int(transient_cap if transient_cap is not None
                else cfg.max_transient)
    s_cap = int(slot_cap if slot_cap is not None else cfg.max_slots)
    if queue_cap is None:
        queue_cap = _pow2(int(np.clip(n_requests // 2 + 1, 64, 1 << 16)))
    route_cap = _pow2(max_arrivals_per_tick, lo=8)
    return FleetSpec(
        n_ondemand=cfg.n_replicas + cfg.n_reserve,
        transient_cap=max(k_cap, 1),
        slot_cap=max(s_cap, 1),
        queue_cap=int(queue_cap),
        route_cap=route_cap,
        horizon=int(max_ticks),
        n_requests=_pow2(n_requests, lo=16),
        pipe_len=max(cfg.ticks(cfg.provisioning_delay), 1),
        probe_d=cfg.probe_d,
        probe_retries=cfg.probe_retries,
        flush_cap=max(route_cap // 2, 8),
        admit_window=max(s_cap, 1) + 4,
        hedge_scan=8,
        hedge_cap=16,
        lifetime_cap=4096,
        drain_code=DRAIN_CODES[drain_preference],
        spot_pricing=bool(spot_pricing),
        n_tenants=max(int(n_tenants), 1))


def make_params(cfg: ServingFleetConfig, *,
                threshold: Optional[float] = None,
                max_transient: Optional[int] = None,
                max_slots: Optional[int] = None,
                n_tenants: int = 1,
                credit_rate=None,
                credit_burst=None) -> Dict[str, np.ndarray]:
    """The traced (sweepable) parameter bundle for one grid point.

    ``credit_rate`` / ``credit_burst`` are per-tenant token-bucket vectors
    (work-ticks per tick / work-ticks) — scalars broadcast. The default
    (rate 0, infinite burst) makes the credit gate a no-op: every fallback
    is funded, so single-tenant runs are bit-identical to the pre-tenancy
    program."""
    mttf_ticks = (cfg.revocation_mttf / cfg.tick_s
                  if cfg.revocation_mttf else 0.0)
    n_t = max(int(n_tenants), 1)
    cr = (np.zeros(n_t, np.float32) if credit_rate is None
          else np.broadcast_to(np.asarray(credit_rate, np.float32),
                               (n_t,)).copy())
    cb = (np.full(n_t, np.inf, np.float32) if credit_burst is None
          else np.broadcast_to(np.asarray(credit_burst, np.float32),
                               (n_t,)).copy())
    return {
        "threshold": np.float32(cfg.threshold if threshold is None
                                else threshold),
        "max_transient": np.float32(cfg.max_transient if max_transient is None
                                    else max_transient),
        "max_slots": np.int32(cfg.max_slots if max_slots is None
                              else max_slots),
        "hedge_factor": np.float32(cfg.hedge_factor),
        "revoke_prob": np.float32(1.0 / mttf_ticks if mttf_ticks > 0 else 0.0),
        "spot_mttf": np.float32(mttf_ticks if mttf_ticks > 0 else np.inf),
        "credit_rate": cr,
        "credit_burst": cb,
    }


def build_consts(spec: FleetSpec, requests: Sequence[Request],
                 pinned_per_tick: np.ndarray) -> Dict[str, np.ndarray]:
    """Pad the (arrival-sorted) request stream and the pinning signal into
    the spec's static shapes. Padding requests carry ``arrival == horizon``
    so they never enter the arrival window."""
    n = len(requests)
    if n > spec.n_requests:
        raise ValueError(f"{n} requests exceed spec.n_requests "
                         f"{spec.n_requests}")
    T, N = spec.horizon, spec.n_requests
    arrival = np.full(N, T, dtype=np.int32)
    gen = np.ones(N, dtype=np.int32)
    tenant = np.zeros(N, dtype=np.int32)
    arrival[:n] = [q.arrival for q in requests]
    gen[:n] = [q.gen_len for q in requests]
    tenant[:n] = [q.tenant_id % spec.n_tenants for q in requests]
    if n and np.any(np.diff(arrival[:n]) < 0):
        raise ValueError("requests must be sorted by arrival")
    # per-tick arrival windows: requests are arrival-sorted, so tick t owns
    # the contiguous index range [arr_start[t], arr_start[t] + arr_count[t])
    arr_start = np.searchsorted(arrival[:n], np.arange(T),
                                side="left").astype(np.int32)
    arr_count = (np.searchsorted(arrival[:n], np.arange(T), side="right")
                 .astype(np.int32) - arr_start)
    if arr_count.size and int(arr_count.max()) > spec.route_cap:
        raise ValueError(f"{int(arr_count.max())} arrivals in one tick "
                         f"exceed route_cap {spec.route_cap}")
    pin = np.zeros(T, dtype=np.int32)
    m = min(T, len(pinned_per_tick))
    pin[:m] = np.asarray(pinned_per_tick[:m], dtype=np.int32)
    return {"arrival": arrival, "gen": gen, "tenant": tenant,
            "arr_start": arr_start, "arr_count": arr_count,
            "pinned_target": pin, "n_real": np.int32(n)}


# ------------------------------------------------------------- the simulator

def _simulate(spec: FleetSpec, params: Dict, consts: Dict, key):
    """One fleet trajectory as a pure JAX program. ``params`` leaves may be
    batched via ``vmap`` (the sweep cube); ``spec`` is static."""
    import jax
    import jax.numpy as jnp

    R, S, Q = spec.n_replicas, spec.slot_cap, spec.queue_cap
    N, T, W = spec.n_requests, spec.horizon, spec.route_cap
    RC = 2 * N  # reroute ring: every rid + its hedge copy at most once
    n_ond = spec.n_ondemand
    K_cap = spec.transient_cap
    idx_r = jnp.arange(R)
    is_ond = idx_r < n_ond
    is_tr = ~is_ond

    arrival = jnp.asarray(consts["arrival"])
    gen = jnp.asarray(consts["gen"])
    tenant_c = jnp.asarray(consts["tenant"])
    arr_start = jnp.asarray(consts["arr_start"])
    arr_count = jnp.asarray(consts["arr_count"])
    pin_tgt = jnp.asarray(consts["pinned_target"])
    NT = spec.n_tenants
    home_tid = idx_r % NT  # replica rid -> owning tenant's home slice

    thr = params["threshold"]
    k_max = params["max_transient"]
    m_slots = params["max_slots"]
    hf = params["hedge_factor"]
    rev_p = params["revoke_prob"]
    spot_mttf = params["spot_mttf"]
    cred_rate = params["credit_rate"]    # (NT,) refill per tick
    cred_burst = params["credit_burst"]  # (NT,) bucket depth
    m_slots_f = m_slots.astype(jnp.float32)
    slot_open = jnp.arange(S)[None, :] < m_slots  # (1,S): usable slots

    def q_window(q_rid, q_head, q_len, width):
        """First ``width`` queued rids per replica (rid, valid)."""
        offs = jnp.arange(width)[None, :]
        pos = (q_head[:, None] + offs) % Q
        rid = jnp.take_along_axis(q_rid, pos, axis=1)
        return rid, offs < q_len[:, None]

    def ring_push(ring, r_head, r_len, rid, mask):
        """Append masked rids (compacted, order-preserving) to the ring."""
        slot = (r_head + r_len + jnp.cumsum(mask) - 1) % RC
        ring = ring.at[jnp.where(mask, slot, RC)].set(rid, mode="drop")
        return ring, r_len + mask.sum()

    def push_entries(st, tgt, rid, mask, t):
        """Enqueue routed entries: intra-tick arrival order becomes queue
        order via same-target ranks; overflow beyond queue_cap is dropped
        (counted — never silent)."""
        q_rid, q_head, q_len, pend, routed_at, n_over = st
        Wn = tgt.shape[0]
        order = jnp.arange(Wn)
        same = ((tgt[None, :] == tgt[:, None])
                & mask[None, :] & mask[:, None])
        rank = jnp.sum(same & (order[None, :] < order[:, None]), axis=1)
        tgt_c = jnp.where(mask, tgt, 0)
        pos = q_len[tgt_c] + rank
        ok = mask & (pos < Q)
        col = (q_head[tgt_c] + pos) % Q
        row = jnp.where(ok, tgt_c, R)
        q_rid = q_rid.at[row, col].set(rid, mode="drop")
        q_len = q_len + jnp.zeros(R, jnp.int32).at[row].add(1, mode="drop")
        g = jnp.where(ok, gen[jnp.where(ok, rid, 0)], 0)
        pend = pend + jnp.zeros(R, jnp.int32).at[row].add(g, mode="drop")
        routed_at = routed_at.at[jnp.where(ok, rid, N)].set(t, mode="drop")
        n_over = n_over + jnp.sum(mask & ~ok)
        return q_rid, q_head, q_len, pend, routed_at, n_over

    def step(carry, t):
        (online, draining, online_at, flushing, q_rid, q_head, q_len, pend,
         slot_rid, slot_rem, start, finish, hedged, routed_at, pipe,
         ring, rr_head, rr_len, want_prev, n_hedges, n_hcancel, n_revoke,
         n_rentals, n_over, lt_buf, lt_count, lt_sum, credits,
         n_throttle) = carry
        tk = jax.random.fold_in(key, t)
        # token-bucket refill, one tick's worth, clipped at the bucket
        # depth — per-tick refill with clip is exactly the Python oracle's
        # lazy refill (both linear in elapsed time, same ceiling)
        credits = jnp.minimum(credits + cred_rate, cred_burst)
        n_thr_pre = n_throttle  # obs: THROTTLE column is the per-tick delta

        with jax.named_scope("tick.pin"):
            # ---- 1 · pinning: first `want` on-demand replicas go to long jobs;
            # newly pinned replicas displace slot residents now, queues flush
            # through the reroute ring over the next few ticks
            want = jnp.minimum(pin_tgt[t], n_ond)
            pinned = is_ond & (idx_r < want)
            newly = pinned & (idx_r >= want_prev)
            disp = newly[:, None] & (slot_rid >= 0)
            d_rid = jnp.where(disp, slot_rid, 0)
            d_live = disp & (finish[d_rid] < 0)
            # obs: slot residents evicted by a pin transition (DISPLACE column)
            ev_disp_pin = jnp.sum(d_live)
            # no live copy elsewhere -> full restart (start resets)
            reset = d_live & ~hedged[d_rid]
            start = start.at[jnp.where(reset, d_rid, N)].set(-1, mode="drop")
            ring, rr_len = ring_push(ring, rr_head, rr_len, d_rid.ravel(),
                                     d_live.ravel())
            pend = pend - jnp.sum(jnp.where(disp, slot_rem, 0), axis=1)
            slot_rid = jnp.where(disp, -1, slot_rid)
            slot_rem = jnp.where(disp, 0, slot_rem)
            flushing = flushing | (newly & (q_len > 0))

        with jax.named_scope("tick.flush"):
            # ---- 2 · flush displaced/revoked queues into the reroute ring.
            # Flushes only happen for a few ticks after a pin transition or a
            # revocation — lax.cond skips the scatter kernels on the common tick
            fl = flushing & (pinned | ~online)

            def do_flush(op):
                start, ring, rr_len, pend, q_head, q_len, flushing = op
                f_rid, f_val = q_window(q_rid, q_head, q_len, spec.flush_cap)
                f_val = f_val & fl[:, None]
                f_pop = jnp.sum(f_val, axis=1)
                f_rid_c = jnp.where(f_val, f_rid, 0)
                # revoked transients drop hedged originals (the copy carries
                # them); finished entries are stale hedge losers either way
                f_route = f_val & (finish[f_rid_c] < 0) & ~(is_tr[:, None]
                                                            & hedged[f_rid_c])
                reset = f_route & ~hedged[f_rid_c]
                start = start.at[jnp.where(reset, f_rid_c, N)].set(-1,
                                                                   mode="drop")
                ring, rr_len = ring_push(ring, rr_head, rr_len, f_rid_c.ravel(),
                                         f_route.ravel())
                pend = pend - jnp.sum(jnp.where(f_val, gen[f_rid_c], 0), axis=1)
                q_head = (q_head + f_pop) % Q
                q_len = q_len - f_pop
                return start, ring, rr_len, pend, q_head, q_len, (flushing
                                                                  & (q_len > 0))

            (start, ring, rr_len, pend, q_head, q_len, flushing) = jax.lax.cond(
                jnp.any(fl), do_flush, lambda op: op,
                (start, ring, rr_len, pend, q_head, q_len, flushing))

        with jax.named_scope("tick.provision"):
            # ---- 3 · provisioning pipeline: transients ordered `pipe_len` ticks
            # ago come online, reusing free transient rows (queue fully flushed)
            due = pipe[0]
            pipe = jnp.roll(pipe, -1).at[-1].set(0)
            avail = is_tr & ~online & (q_len == 0)
            pick = avail & (jnp.cumsum(avail) <= due)
            n_on = jnp.sum(pick)
            pipe = pipe.at[0].add(due - n_on)  # no free row: retry next tick
            online = online | pick
            draining = jnp.where(pick, False, draining)
            online_at = jnp.where(pick, t, online_at)
            n_rentals = n_rentals + n_on

        with jax.named_scope("tick.route"):
            # ---- 4 · routing: reroute-ring pops first (the oracle re-routes
            # displaced work before fresh arrivals), then this tick's arrivals.
            # The whole phase sits behind lax.cond — most ticks route nothing
            act_tr = online & is_tr & ~draining
            n_act = jnp.sum(act_tr)
            W2 = 2 * W

            def do_route(op):
                (q_rid, q_head, q_len, pend, routed_at, n_over, ring, rr_head,
                 rr_len, ev_rr, credits, n_throttle) = op
                offs = jnp.arange(W)
                rr_val = offs < jnp.minimum(rr_len, W)
                rr_rid = ring[(rr_head + offs) % RC]
                n_popped = jnp.minimum(rr_len, W)
                rr_head = (rr_head + n_popped) % RC
                rr_len = rr_len - n_popped
                a_val = offs < arr_count[t]
                a_rid = jnp.clip(arr_start[t] + offs, 0, N - 1)
                # compact into one contiguous entry list so the sequential
                # router below only walks entries that actually exist this tick
                e_rid = jnp.zeros(W2, jnp.int32)
                e_rid = e_rid.at[jnp.where(rr_val, offs, W2)].set(rr_rid,
                                                                  mode="drop")
                e_rid = e_rid.at[jnp.where(a_val, n_popped + offs, W2)].set(
                    a_rid, mode="drop")
                n_e = n_popped + arr_count[t]
                # ring entries whose rid already finished are stale hedge losers
                e_val = (jnp.arange(W2) < n_e) & (finish[e_rid] < 0)
                # obs: live ring pops are re-routes of displaced/revoked work
                # (fresh arrivals — entries past n_popped — are not REROUTEs)
                ev_rr = ev_rr + jnp.sum((jnp.arange(W2) < n_popped) & e_val)
                act_rank = jnp.cumsum(act_tr) - 1
                act_list = jnp.zeros(K_cap, jnp.int32).at[
                    jnp.where(act_tr, act_rank, K_cap)].set(idx_r, mode="drop")
                route_key = jax.random.fold_in(tk, 1)

                # the oracle routes one request at a time and every enqueue
                # bumps the target's pending_ticks, so later same-tick requests
                # see the updated loads (least-loaded fallback waterfills a
                # crunch across replicas). A tick-start snapshot piles the whole
                # window on one argmin replica and fattens the wait tail badly
                # under full pinning — thread the intra-tick load delta through
                # a sequential while_loop bounded by the *actual* entry count
                def choose(state):
                    i, pend_add, chosen, credits, n_thr = state
                    pend_now = (pend + pend_add).astype(jnp.float32) / m_slots_f
                    ek = jax.random.fold_in(route_key, i)
                    # probing: `probe_retries` rounds of `probe_d` uniform draws
                    # over the on-demand pool; first round with an unpinned
                    # candidate wins, lowest pending among them (first tie wins)
                    ci = jnp.floor(
                        jax.random.uniform(jax.random.fold_in(ek, 0),
                                           (spec.probe_retries, spec.probe_d))
                        * n_ond).astype(jnp.int32)
                    c_ok = ~pinned[ci]
                    round_ok = jnp.any(c_ok, axis=1)
                    has_round = jnp.any(round_ok)
                    rd_cand = ci[jnp.argmax(round_ok)]
                    rd_score = jnp.where(~pinned[rd_cand], pend_now[rd_cand],
                                         jnp.inf)
                    probe_sid = rd_cand[jnp.argmin(rd_score)]
                    # fallback: d uniform draws over the active-transient pool
                    fb_draw = jnp.floor(
                        jax.random.uniform(jax.random.fold_in(ek, 1),
                                           (spec.probe_d,))
                        * jnp.maximum(n_act, 1)).astype(jnp.int32)
                    fci = act_list[jnp.clip(fb_draw, 0, K_cap - 1)]
                    fb_score = pend_now[fci]
                    if spec.spot_pricing:
                        # SpotAwareProbing: price expected revocation rework in
                        dur = gen[e_rid[i]].astype(jnp.float32)
                        fb_score = fb_score + dur * (fb_score + dur) / spot_mttf
                    fb_sid = fci[jnp.argmin(fb_score)]
                    # empty short pool: least-loaded *general* replica. The
                    # oracle's 1e12 pin penalty is float64-lexicographic (pinned
                    # last, then least pending); float32 would swallow the
                    # pending term, so encode the two-level key explicitly
                    any_unpin = jnp.any(is_ond & ~pinned)
                    ll_unpin = jnp.argmin(jnp.where(is_ond & ~pinned, pend_now,
                                                    jnp.inf))
                    ll_pin = jnp.argmin(jnp.where(is_ond & pinned, pend_now,
                                                  jnp.inf))
                    ll_sid = jnp.where(any_unpin, ll_unpin, ll_pin)
                    # TenantGuard credit gate: *every* placement must be
                    # funded by its tenant's bucket (cost = service demand),
                    # so the bucket level tracks offered load against the
                    # tenant's paid rate. Over-credit -> throttle to the
                    # least-loaded unpinned replica of the tenant's *home
                    # slice* of the general partition (rid % n_tenants ==
                    # tenant), confining the spike to the owner's fair
                    # share; no free home replica -> route normally without
                    # a debit (work conservation). The default params
                    # (infinite burst) make `funded` always true, so
                    # single-tenant programs route identically
                    live = e_val[i]
                    te = tenant_c[e_rid[i]]
                    cost = gen[e_rid[i]].astype(jnp.float32)
                    home = is_ond & ~pinned & (home_tid == te)
                    any_home = jnp.any(home)
                    ll_home = jnp.argmin(jnp.where(home, pend_now, jnp.inf))
                    funded = credits[te] >= cost
                    throttled = live & ~funded & any_home
                    normal = jnp.where(has_round, probe_sid,
                                       jnp.where(n_act > 0, fb_sid, ll_sid))
                    sid = jnp.where(throttled, ll_home, normal)
                    credits = credits.at[te].add(
                        -jnp.where(live & funded, cost, 0.0))
                    n_thr = n_thr + throttled.astype(jnp.int32)
                    bump = jnp.where(live, gen[e_rid[i]], 0)
                    pend_add = pend_add + jnp.zeros(R, jnp.int32).at[sid].add(
                        bump)
                    return i + 1, pend_add, chosen.at[i].set(sid), credits, n_thr

                _, _, chosen, credits, n_throttle = jax.lax.while_loop(
                    lambda st: st[0] < n_e, choose,
                    (jnp.int32(0), jnp.zeros(R, jnp.int32),
                     jnp.zeros(W2, jnp.int32), credits, n_throttle))
                st = push_entries((q_rid, q_head, q_len, pend, routed_at,
                                   n_over), chosen, e_rid, e_val, t)
                q_rid, q_head, q_len, pend, routed_at, n_over = st
                return (q_rid, q_head, q_len, pend, routed_at, n_over, ring,
                        rr_head, rr_len, ev_rr, credits, n_throttle)

            (q_rid, q_head, q_len, pend, routed_at, n_over, ring, rr_head,
             rr_len, ev_reroute, credits, n_throttle) = jax.lax.cond(
                (rr_len > 0) | (arr_count[t] > 0), do_route, lambda op: op,
                (q_rid, q_head, q_len, pend, routed_at, n_over, ring, rr_head,
                 rr_len, jnp.int32(0), credits, n_throttle))

        with jax.named_scope("tick.control"):
            # ---- 5 · §3.2 controller: exact leading-true counts over a [0, K]
            # candidate vector (same float comparisons as the Python unit loop)
            n_drain = jnp.sum(online & draining)
            n_pend_tr = pipe.sum()
            n_stable = n_ond + n_act
            long_busy = want.astype(jnp.float32)
            a_vec = jnp.arange(K_cap + 1, dtype=jnp.float32)
            proj = (n_stable + n_drain + n_pend_tr).astype(jnp.float32) + a_vec
            used = (n_act + n_pend_tr).astype(jnp.float32) + a_vec
            cond_a = (long_busy > thr * jnp.maximum(proj, 1.0)) & (used < k_max)
            add = jnp.sum(jnp.cumprod(cond_a.astype(jnp.int32)))
            cond_r = ((n_act.astype(jnp.float32) - a_vec > 0)
                      & (long_busy < thr * jnp.maximum(
                          n_stable.astype(jnp.float32) - a_vec - 1.0, 1.0)))
            rem = jnp.sum(jnp.cumprod(cond_r.astype(jnp.int32)))
            rem = jnp.where(add > 0, 0, rem)
            pipe = pipe.at[spec.pipe_len - 1].add(add)
            load = q_len + jnp.sum(slot_rid >= 0, axis=1)
            drain_key = {0: load.astype(jnp.float32),
                         1: online_at.astype(jnp.float32),
                         2: -online_at.astype(jnp.float32)}[spec.drain_code]
            score = jnp.where(act_tr, drain_key, jnp.inf)
            drank = jnp.argsort(jnp.argsort(score))
            draining = draining | (act_tr & (drank < rem))

        with jax.named_scope("tick.revoke"):
            # ---- 6 · revocations: each active transient dies w.p. 1/mttf/tick;
            # slot residents re-route now (hedged originals ride their copy),
            # the queue ghost-flushes through phase 2
            u = jax.random.uniform(jax.random.fold_in(tk, 3), (R,))
            revoked = online & is_tr & ~draining & (u < rev_p)
            # obs: revocation counts from the pre-revoke state (do_revoke only
            # fires on revocation ticks; these reduce to 0 on the common tick).
            # DISPLACE = residents the revocation sends back through routing:
            # still alive and not hedged (the on-demand copy carries those)
            ev_revoke = jnp.sum(revoked)
            v_pre = revoked[:, None] & (slot_rid >= 0)
            v_rid_pre = jnp.where(v_pre, slot_rid, 0)
            ev_disp_rev = jnp.sum(v_pre & (finish[v_rid_pre] < 0)
                                  & ~hedged[v_rid_pre])

            def do_revoke(op):
                (start, ring, rr_len, pend, slot_rid, slot_rem, lt_buf, lt_sum,
                 lt_count, n_revoke, online, flushing) = op
                v = revoked[:, None] & (slot_rid >= 0)
                v_rid = jnp.where(v, slot_rid, 0)
                v_route = v & (finish[v_rid] < 0) & ~hedged[v_rid]
                start = start.at[jnp.where(v_route, v_rid, N)].set(-1,
                                                                   mode="drop")
                ring, rr_len = ring_push(ring, rr_head, rr_len, v_rid.ravel(),
                                         v_route.ravel())
                pend = pend - jnp.sum(jnp.where(v, slot_rem, 0), axis=1)
                slot_rid = jnp.where(v, -1, slot_rid)
                slot_rem = jnp.where(v, 0, slot_rem)
                life = jnp.where(revoked, t - online_at, 0)
                lt_buf = lt_buf.at[jnp.where(
                    revoked, lt_count + jnp.cumsum(revoked) - 1,
                    spec.lifetime_cap)].set(life.astype(jnp.float32),
                                            mode="drop")
                lt_sum = lt_sum + jnp.sum(life)
                lt_count = lt_count + jnp.sum(revoked)
                n_revoke = n_revoke + jnp.sum(revoked)
                online = online & ~revoked
                flushing = flushing | (revoked & (q_len > 0))
                return (start, ring, rr_len, pend, slot_rid, slot_rem, lt_buf,
                        lt_sum, lt_count, n_revoke, online, flushing)

            (start, ring, rr_len, pend, slot_rid, slot_rem, lt_buf, lt_sum,
             lt_count, n_revoke, online, flushing) = jax.lax.cond(
                jnp.any(revoked), do_revoke, lambda op: op,
                (start, ring, rr_len, pend, slot_rid, slot_rem, lt_buf, lt_sum,
                 lt_count, n_revoke, online, flushing))

        with jax.named_scope("tick.hedge"):
            # ---- 7 · §3.3 hedging: originals stuck on an active transient past
            # hedge_factor x gen_len duplicate onto the least-loaded reserve
            act_tr = online & is_tr & ~draining
            reserve = is_ond & ~pinned
            n_res = jnp.sum(reserve)
            n_hedges_pre = n_hedges  # obs: HEDGE column is the per-tick delta

            def do_hedge(op):
                (q_rid, q_head, q_len, pend, routed_at, n_over, hedged,
                 n_hedges) = op
                hq_rid, hq_val = q_window(q_rid, q_head, q_len, spec.hedge_scan)
                h_rid = jnp.concatenate([hq_rid, jnp.where(slot_rid >= 0,
                                                           slot_rid, 0)], axis=1)
                h_val = jnp.concatenate([hq_val, slot_rid >= 0], axis=1)
                h_rid = jnp.where(h_val, h_rid, 0)
                elig = (h_val & act_tr[:, None] & ~hedged[h_rid]
                        & (finish[h_rid] < 0)
                        & ((t - routed_at[h_rid]).astype(jnp.float32)
                           > hf * gen[h_rid].astype(jnp.float32)))
                e_flat = elig.ravel()
                h_cum = jnp.cumsum(e_flat)
                sel = e_flat & (h_cum <= spec.hedge_cap)
                h_pos = jnp.where(sel, h_cum - 1, spec.hedge_cap)
                hedge_rid = jnp.full(spec.hedge_cap, 0, jnp.int32).at[h_pos].set(
                    h_rid.ravel(), mode="drop")
                hedge_ok = (jnp.arange(spec.hedge_cap)
                            < jnp.minimum(jnp.sum(sel), spec.hedge_cap))
                hedged = hedged.at[jnp.where(hedge_ok, hedge_rid, N)].set(
                    True, mode="drop")
                n_hedges = n_hedges + jnp.sum(hedge_ok)
                res_order = jnp.argsort(jnp.where(reserve,
                                                  load.astype(jnp.float32),
                                                  jnp.inf))
                h_tgt = res_order[jnp.arange(spec.hedge_cap)
                                  % jnp.maximum(n_res, 1)]
                st = push_entries((q_rid, q_head, q_len, pend, routed_at,
                                   n_over), h_tgt, hedge_rid, hedge_ok, t)
                q_rid, q_head, q_len, pend, routed_at, n_over = st
                return (q_rid, q_head, q_len, pend, routed_at, n_over, hedged,
                        n_hedges)

            # cheap superset pre-check: an eligible entry implies work pending
            # on an active transient (and a reserve replica to copy onto)
            (q_rid, q_head, q_len, pend, routed_at, n_over, hedged,
             n_hedges) = jax.lax.cond(
                jnp.any(act_tr & (pend > 0)) & (n_res > 0), do_hedge,
                lambda op: op,
                (q_rid, q_head, q_len, pend, routed_at, n_over, hedged,
                 n_hedges))

        with jax.named_scope("tick.advance"):
            # ---- 8 · advance every unpinned online replica one decode tick:
            # cancel slots whose hedge pair already won, admit from the queue
            # into free slots, decode one token per occupied slot
            act = online & ~pinned
            occ = (slot_rid >= 0) & act[:, None]
            stale = occ & (finish[jnp.where(occ, slot_rid, 0)] >= 0)
            n_hcancel = n_hcancel + jnp.sum(stale)
            pend = pend - jnp.sum(jnp.where(stale, slot_rem, 0), axis=1)
            slot_rid = jnp.where(stale, -1, slot_rid)
            slot_rem = jnp.where(stale, 0, slot_rem)

            P = spec.admit_window

            def do_admit(op):
                (q_rid, q_head, q_len, pend, slot_rid, slot_rem, start,
                 n_hcancel, ev_ad, tn_ad, tn_wt) = op
                w_rid, w_val = q_window(q_rid, q_head, q_len, P)
                w_val = w_val & act[:, None]
                w_rid = jnp.where(w_val, w_rid, 0)
                alive = w_val & (finish[w_rid] < 0)
                free_mask = (slot_rid < 0) & slot_open & act[:, None]
                free = jnp.sum(free_mask, axis=1)
                live_cum = jnp.cumsum(alive, axis=1)
                admit = alive & (live_cum <= free[:, None])
                stop = jnp.argmax(alive & (live_cum == free[:, None]), axis=1)
                live_tot = live_cum[:, -1]
                n_valid = jnp.sum(w_val, axis=1)
                # the oracle's pop loop checks free slots *before* each pop: once
                # the free-th live entry is admitted, trailing entries stay
                consumed = jnp.where(
                    free <= 0, 0,
                    jnp.where(live_tot >= free, stop + 1, n_valid))
                dead = (w_val & ~alive
                        & (jnp.arange(P)[None, :] < consumed[:, None]))
                n_hcancel = n_hcancel + jnp.sum(dead)
                pend = pend - jnp.sum(jnp.where(dead, gen[w_rid], 0), axis=1)
                # k-th admitted entry -> k-th free slot (one-hot on the window)
                free_rank = jnp.cumsum(free_mask, axis=1)
                hit = (admit[:, None, :] & free_mask[:, :, None]
                       & (live_cum[:, None, :] == free_rank[:, :, None]))
                has = jnp.any(hit, axis=2)
                ev_ad = ev_ad + jnp.sum(has)  # obs: slot admissions this tick
                eidx = jnp.argmax(hit, axis=2)
                a_rid = jnp.take_along_axis(w_rid, eidx, axis=1)
                slot_rid = jnp.where(has, a_rid, slot_rid)
                slot_rem = jnp.where(has, gen[a_rid], slot_rem)
                srid = jnp.where(has, a_rid, N)
                sg = start[jnp.where(has, a_rid, 0)]
                start = start.at[srid].set(jnp.where(sg < 0, t, sg), mode="drop")
                # per-tenant first-start accounting: admits + wait-ticks this
                # tick, scattered by the owning tenant (hedge-copy re-admits
                # keep their original start, so they don't double count)
                news = has & (sg < 0)
                a_safe = jnp.where(has, a_rid, 0)
                te_a = jnp.where(news, tenant_c[a_safe], NT)
                tn_ad = tn_ad + jnp.zeros(NT, jnp.int32).at[te_a].add(
                    1, mode="drop")
                tn_wt = tn_wt + jnp.zeros(NT, jnp.int32).at[te_a].add(
                    jnp.where(news, t - arrival[a_safe], 0), mode="drop")
                q_head = (q_head + consumed) % Q
                q_len = q_len - consumed
                return (q_rid, q_head, q_len, pend, slot_rid, slot_rem, start,
                        n_hcancel, ev_ad, tn_ad, tn_wt)

            (q_rid, q_head, q_len, pend, slot_rid, slot_rem, start,
             n_hcancel, ev_admit, tn_admit, tn_wait) = jax.lax.cond(
                jnp.any(act & (q_len > 0)), do_admit, lambda op: op,
                (q_rid, q_head, q_len, pend, slot_rid, slot_rem, start,
                 n_hcancel, jnp.int32(0), jnp.zeros(NT, jnp.int32),
                 jnp.zeros(NT, jnp.int32)))

            occ = (slot_rid >= 0) & act[:, None]
            busy_r = jnp.sum(occ, axis=1)
            slot_rem = jnp.where(occ, slot_rem - 1, slot_rem)
            pend = pend - busy_r
            fin = occ & (slot_rem <= 0)
            f_rid2 = jnp.where(fin, slot_rid, 0)
            fg = finish[f_rid2]
            # obs: first completion of a hedged pair (hedged is post-phase-7,
            # matching the oracle's check at the moment finish is stamped)
            ev_hedge_win = jnp.sum(fin & (fg < 0) & hedged[f_rid2])
            finish = finish.at[jnp.where(fin, f_rid2, N)].set(
                jnp.where(fg < 0, t + 1, fg), mode="drop")
            slot_rid = jnp.where(fin, -1, slot_rid)
            slot_rem = jnp.where(fin, 0, slot_rem)

            # paid slot capacity counts every unpinned online replica this tick,
            # including draining replicas going offline inside the advance
            cap_mask = online & ~pinned
            cap = jnp.sum(cap_mask) * m_slots
            busy = jnp.sum(busy_r)
            tr_cap = jnp.sum(cap_mask & is_tr) * m_slots
            tr_busy = jnp.sum(jnp.where(is_tr, busy_r, 0))

            done_drain = (act & draining & (q_len == 0)
                          & ~jnp.any(slot_rid >= 0, axis=1))
            life = jnp.where(done_drain, t - online_at, 0)
            lt_buf = lt_buf.at[jnp.where(
                done_drain, lt_count + jnp.cumsum(done_drain) - 1,
                spec.lifetime_cap)].set(life.astype(jnp.float32), mode="drop")
            lt_sum = lt_sum + jnp.sum(life)
            lt_count = lt_count + jnp.sum(done_drain)
            online = online & ~done_drain
            draining = draining & ~done_drain

        with jax.named_scope("tick.record"):
            online_tr = jnp.sum(online & is_tr)
            # per-tick event-count vector, columns in obs.events.EVENT_TYPES
            # order — the post-hoc event log events_from_counts decodes
            ev_counts = jnp.stack([
                add,                          # RENT
                n_on,                         # PROVISION
                jnp.sum(done_drain),          # DRAIN
                ev_revoke,                    # REVOKE
                n_hedges - n_hedges_pre,      # HEDGE
                ev_hedge_win,                 # HEDGE_WIN
                ev_admit,                     # ADMIT
                ev_disp_pin + ev_disp_rev,    # DISPLACE
                ev_reroute,                   # REROUTE
                n_throttle - n_thr_pre,       # THROTTLE
            ]).astype(jnp.int32)
            # fleet queue depth at end of tick (online replicas only — matches
            # the oracle's tracer counter over replicas with offline_at None)
            qdepth = jnp.sum(jnp.where(online, q_len, 0))
        import os
        if os.environ.get("SJX_DEBUG"):  # pragma: no cover
            jax.debug.print(
                "t={t} want={w} add={a} pipe={p} due={d} n_on={n} online={o} "
                "qlen={q} rrlen={r}", t=t, w=want, a=add, p=pipe, d=due,
                n=n_on, o=online, q=q_len, r=rr_len)
        carry = (online, draining, online_at, flushing, q_rid, q_head, q_len,
                 pend, slot_rid, slot_rem, start, finish, hedged, routed_at,
                 pipe, ring, rr_head, rr_len, want, n_hedges, n_hcancel,
                 n_revoke, n_rentals, n_over, lt_buf, lt_count, lt_sum,
                 credits, n_throttle)
        ys = (online_tr, busy, cap, tr_busy, tr_cap, ev_counts, qdepth,
              tn_admit, tn_wait)
        return carry, ys

    i32 = jnp.int32
    carry0 = (
        is_ond,                                # online: on-demand always
        jnp.zeros(R, bool),                    # draining
        jnp.zeros(R, i32),                     # online_at
        jnp.zeros(R, bool),                    # flushing
        jnp.full((R, Q), -1, i32),             # q_rid
        jnp.zeros(R, i32), jnp.zeros(R, i32),  # q_head, q_len
        jnp.zeros(R, i32),                     # pend
        jnp.full((R, S), -1, i32),             # slot_rid
        jnp.zeros((R, S), i32),                # slot_rem
        jnp.full(N, -1, i32),                  # start
        jnp.full(N, -1, i32),                  # finish
        jnp.zeros(N, bool),                    # hedged
        arrival.astype(i32),                   # routed_at (hedge clock)
        jnp.zeros(spec.pipe_len, i32),         # provisioning pipe
        jnp.full(RC, -1, i32),                 # reroute ring
        jnp.asarray(0, i32), jnp.asarray(0, i32),   # rr_head, rr_len
        jnp.asarray(0, i32),                   # want_prev
        jnp.asarray(0, i32), jnp.asarray(0, i32),   # n_hedges, n_hcancel
        jnp.asarray(0, i32), jnp.asarray(0, i32),   # n_revoke, n_rentals
        jnp.asarray(0, i32),                   # n_overflow
        jnp.zeros(spec.lifetime_cap, jnp.float32),  # lt_buf
        jnp.asarray(0, i32), jnp.asarray(0, i32),   # lt_count, lt_sum
        jnp.asarray(cred_burst, jnp.float32),       # credits (buckets full)
        jnp.asarray(0, i32),                        # n_throttle
    )
    carry, ys = jax.lax.scan(step, carry0, jnp.arange(T))
    (online, draining, online_at, flushing, q_rid, q_head, q_len, pend,
     slot_rid, slot_rem, start, finish, hedged, routed_at, pipe, ring,
     rr_head, rr_len, want_prev, n_hedges, n_hcancel, n_revoke, n_rentals,
     n_over, lt_buf, lt_count, lt_sum, credits, n_throttle) = carry
    (online_tr, busy, cap, tr_busy, tr_cap, ev_counts, qdepth, tn_admit,
     tn_wait) = ys
    return {
        "start": start, "finish": finish, "hedged": hedged,
        "active_transients": online_tr, "busy": busy, "cap": cap,
        "tr_busy": tr_busy, "tr_cap": tr_cap,
        "event_counts": ev_counts, "queue_depth": qdepth,
        "n_hedges": n_hedges, "n_hedge_cancelled": n_hcancel,
        "n_revocations": n_revoke, "n_rentals": n_rentals,
        "n_overflow": n_over, "lifetimes": lt_buf,
        "n_lifetimes": lt_count, "lifetime_sum": lt_sum,
        "final_online_transients": jnp.sum(online & is_tr),
        "final_tr_online": online & is_tr,
        "final_online_at": online_at,
        "tenant_admits": tn_admit, "tenant_wait_sums": tn_wait,
        "n_throttled": n_throttle, "final_credits": credits,
    }


# ----------------------------------------------------- compiled-program cache

CacheInfo = namedtuple("CacheInfo", "hits misses size")
_PROGRAMS: Dict[Tuple, object] = {}
_CACHE_STATS = {"hits": 0, "misses": 0}


def get_program(spec: FleetSpec, *, batch: Optional[str] = None):
    """The jitted simulator for one static spec. Keyed by ``(spec, batch)``,
    so repeated ``exp.run`` / ``exp.sweep`` calls over the same shapes never
    re-trace.

    ``batch=None`` takes one ``(params, consts, key)`` point. Both batched
    modes take stacked params/keys (leading grid axis) and run the whole
    cube as **one** device program; they differ in how XLA executes it:

      * ``"map"`` — ``lax.map`` over grid points. Points run sequentially
        on device, so the simulator's rare-event gating (``lax.cond``
        around routing / flush / revocation / hedging) stays a real branch.
        The right default on CPU.
      * ``"vmap"`` — lanewise vectorization. Gates become ``select``s that
        pay for both branches every tick, which on a single CPU core costs
        ~10x per point; the right choice only on SIMD/parallel backends.
    """
    import jax

    if batch not in (None, "map", "vmap"):
        raise ValueError(f"batch must be None, 'map' or 'vmap': {batch!r}")
    cache_key = (spec, batch)
    fn = _PROGRAMS.get(cache_key)
    if fn is None:
        _CACHE_STATS["misses"] += 1
        # each entry point has a name of its own: a profile names the
        # program after it (``jit_fleet_point``, ...)
        base = partial(_simulate, spec)
        if batch == "vmap":
            lanes = jax.vmap(base, in_axes=(0, None, 0))

            def fleet_cube_vmap(params, consts, keys):
                return lanes(params, consts, keys)
            fn = jax.jit(fleet_cube_vmap)
        elif batch == "map":
            def fleet_cube_map(params, consts, keys):
                return jax.lax.map(
                    lambda pk: base(pk[0], consts, pk[1]), (params, keys))
            fn = jax.jit(fleet_cube_map)
        else:
            def fleet_point(params, consts, key):
                return base(params, consts, key)
            fn = jax.jit(fleet_point)
        _PROGRAMS[cache_key] = fn
    else:
        _CACHE_STATS["hits"] += 1
    return fn


def cache_info() -> CacheInfo:
    return CacheInfo(_CACHE_STATS["hits"], _CACHE_STATS["misses"],
                     len(_PROGRAMS))


def cache_clear() -> None:
    _PROGRAMS.clear()
    _CACHE_STATS.update(hits=0, misses=0)


# ------------------------------------------------ run-level observability

#: facts about the most recent run_workload / sweep_cube execution
_LAST_OBS: Dict[str, object] = {}


def _record_exec(phase: str, exec_s: float, **extra) -> None:
    from repro.obs.metrics import REGISTRY

    REGISTRY.counter("serving_jax.jit_cache_"
                     + ("miss" if phase == "compile" else "hit")).inc()
    REGISTRY.histogram(f"serving_jax.{phase}_exec_s").observe(exec_s)
    _LAST_OBS.clear()
    _LAST_OBS.update(phase=phase, exec_s=exec_s,
                     program_cache_hit=phase != "compile", **extra)


def last_run_obs() -> Dict[str, object]:
    """Observability snapshot for ``RunResult.meta["obs"]``: the most
    recent execution's phase (``compile`` when :func:`get_program` missed
    the program cache and the call paid tracing+XLA, ``steady`` on a cache
    hit) and wall time, plus process-cumulative jit-cache counters and
    compile/steady wall-time histograms — the ``serving_scale`` split, as
    a free by-product of every serving_jax run."""
    from repro.obs.metrics import REGISTRY, Histogram

    hists = REGISTRY.snapshot()["histograms"]
    empty = Histogram("").snapshot()
    return {
        **_LAST_OBS,
        "jit_cache": cache_info()._asdict(),
        "compile": hists.get("serving_jax.compile_exec_s", empty),
        "steady": hists.get("serving_jax.steady_exec_s", empty),
    }


# ------------------------------------------------------------- host wrappers

def _seed_key(seed: int):
    import jax

    return jax.random.PRNGKey(seed)


def summarize(spec: FleetSpec, out: Dict, consts: Dict, tick_s: float
              ) -> Tuple[Dict[str, float], Dict[str, np.ndarray]]:
    """Device output -> the oracle's summary metrics + series (host side).

    Wait metrics follow ``ElasticServingFleet.summary`` / the
    ``from_serving_fleet`` mapping: waits over started requests, finite
    zeros when nothing completed (the shared ``_pctl`` convention)."""
    from repro.core.metrics import _pctl

    n = int(consts["n_real"])
    start = np.asarray(out["start"])[:n]
    finish = np.asarray(out["finish"])[:n]
    arrival = np.asarray(consts["arrival"])[:n]
    waits = (start[start >= 0] - arrival[start >= 0]).astype(float) * tick_s
    online_tr = np.asarray(out["active_transients"], float)
    busy = np.asarray(out["busy"], float)
    cap = np.asarray(out["cap"], float)
    tr_busy = np.asarray(out["tr_busy"], float)
    tr_cap = np.asarray(out["tr_cap"], float)
    n_life = int(out["n_lifetimes"])
    lifetimes = np.asarray(out["lifetimes"])[:min(n_life,
                                                  spec.lifetime_cap)]
    n_done = int(np.sum(finish >= 0))
    metrics = {
        "short_avg_wait_s": float(np.mean(waits)) if waits.size else 0.0,
        "short_max_wait_s": float(np.max(waits)) if waits.size else 0.0,
        "short_p50_wait_s": _pctl(waits, 50),
        "short_p90_wait_s": _pctl(waits, 90),
        "short_p99_wait_s": _pctl(waits, 99),
        "avg_active_transients": float(online_tr.mean()) if online_tr.size
        else 0.0,
        "peak_active_transients": float(online_tr.max()) if online_tr.size
        else 0.0,
        "n_requests": float(n),
        "n_done": float(n_done),
        "n_unfinished": float(n - n_done),
        "n_hedges": float(out["n_hedges"]),
        "n_hedge_cancelled": float(out["n_hedge_cancelled"]),
        "n_revocations": float(out["n_revocations"]),
        "n_transients_used": float(out["n_rentals"]),
        "avg_transient_lifetime_s": (float(out["lifetime_sum"])
                                     / n_life * tick_s if n_life else 0.0),
        "avg_slot_occupancy": float(busy.sum() / max(cap.sum(), 1.0)),
        "transient_slot_occupancy": float(tr_busy.sum()
                                          / max(tr_cap.sum(), 1.0)),
        "n_queue_overflow": float(out["n_overflow"]),
        "n_throttled": float(out.get("n_throttled", 0)),
    }
    series = {
        "short_waits": waits,
        "active_transients": online_tr,
        "transient_lifetimes": lifetimes.astype(float) * tick_s,
        "batch_occupancy": np.divide(busy, cap, out=np.zeros_like(busy),
                                     where=cap > 0),
        # per-tick scheduler event counts (obs.events.EVENT_TYPES columns)
        # and end-of-tick fleet queue depth — the flight-recorder series
        "event_counts": np.asarray(out["event_counts"], np.int64),
        "queue_depth": np.asarray(out["queue_depth"], float),
    }
    if spec.n_tenants > 1:
        # exact per-request (tenant, wait) pairs for the canonical
        # tenant_waits series — `exp.results` turns them into named
        # per-tenant metrics with the trace meta's names/SLOs
        tenant = np.asarray(consts["tenant"])[:n]
        started = start >= 0
        series["tenant_waits"] = np.stack(
            [tenant[started].astype(float),
             (start[started] - arrival[started]).astype(float) * tick_s],
            axis=1) if started.any() else np.zeros((0, 2))
        series["tenant_admits"] = np.asarray(out["tenant_admits"], np.int64)
    return metrics, series


def run_workload(cfg: ServingFleetConfig, requests: Sequence[Request],
                 pinned_per_tick: np.ndarray, max_ticks: int, *,
                 drain_preference: str = "least_loaded",
                 spot_pricing: bool = False, sim_seed: int = 0,
                 spec: Optional[FleetSpec] = None,
                 queue_cap: Optional[int] = None,
                 n_tenants: int = 1,
                 credit_rate=None, credit_burst=None
                 ) -> Tuple[Dict[str, float], Dict[str, np.ndarray],
                            FleetSpec]:
    """One grid point: the ``ElasticServingFleet.run`` analog on device.

    ``n_tenants`` is static (shape of the credit vector and the per-tenant
    accumulators); ``credit_rate`` / ``credit_burst`` are the traced
    token-bucket vectors in tick units (see :func:`make_params`).

    Returns ``(metrics, series, spec)`` — metrics/series exactly match the
    ``from_serving_fleet`` canonical mapping."""
    from repro.obs.metrics import span

    with span("serving_jax.build"):
        if spec is None:
            arr = np.asarray([q.arrival for q in requests], dtype=np.int64)
            max_arr = int(np.bincount(arr).max()) if arr.size else 0
            spec = make_spec(cfg, n_requests=len(requests),
                             max_ticks=max_ticks,
                             max_arrivals_per_tick=max_arr,
                             queue_cap=queue_cap,
                             drain_preference=drain_preference,
                             spot_pricing=spot_pricing, n_tenants=n_tenants)
        consts = build_consts(spec, requests, pinned_per_tick)
        params = make_params(cfg, n_tenants=spec.n_tenants,
                             credit_rate=credit_rate,
                             credit_burst=credit_burst)
        info0 = cache_info()
        fn = get_program(spec)
        fresh = cache_info().misses > info0.misses
    with span("serving_jax.exec"):
        t0 = time.perf_counter()
        out = fn(params, consts, _seed_key(sim_seed))
        out = {k: np.asarray(v) for k, v in out.items()}  # forces device work
        exec_s = time.perf_counter() - t0
    _record_exec("compile" if fresh else "steady", exec_s)
    with span("serving_jax.summarize"):
        metrics, series = summarize(spec, out, consts, cfg.tick_s)
    return metrics, series, spec


#: sweep-cube axes, in array-dimension order (mirrors ``_FLUID_AXES``)
SWEEP_AXES = ("threshold", "max_transient", "max_slots")


def sweep_cube(cfg: ServingFleetConfig, requests: Sequence[Request],
               pinned_per_tick: np.ndarray, max_ticks: int, *,
               thresholds: Sequence[float], max_transients: Sequence[int],
               max_slots_values: Sequence[int], sim_seeds: Sequence[int] = (0,),
               drain_preference: str = "least_loaded",
               spot_pricing: bool = False,
               queue_cap: Optional[int] = None,
               batch: str = "map"
               ) -> Tuple[Dict[str, np.ndarray], FleetSpec]:
    """The whole (threshold x max_transient x max_slots) cube — batched over
    ``sim_seeds`` on top — as **one** device program (``lax.map`` over grid
    points by default; ``batch="vmap"`` for lanewise execution on parallel
    backends — see :func:`get_program`).

    Returns ``(grids, spec)``: metric grids of shape ``(len(thresholds),
    len(max_transients), len(max_slots_values))``, seed-averaged
    (percentile metrics are computed per point on host)."""
    import jax

    from repro.obs.metrics import span

    with span("serving_jax.build"):
        thr = np.asarray(thresholds, np.float32)
        ks = np.asarray(max_transients, np.int32)
        ms = np.asarray(max_slots_values, np.int32)
        seeds = list(sim_seeds)
        arr = np.asarray([q.arrival for q in requests], dtype=np.int64)
        max_arr = int(np.bincount(arr).max()) if arr.size else 0
        spec = make_spec(cfg, n_requests=len(requests), max_ticks=max_ticks,
                         max_arrivals_per_tick=max_arr,
                         transient_cap=max(int(ks.max()), cfg.max_transient,
                                           1),
                         slot_cap=max(int(ms.max()), cfg.max_slots, 1),
                         queue_cap=queue_cap,
                         drain_preference=drain_preference,
                         spot_pricing=spot_pricing)
        consts = build_consts(spec, requests, pinned_per_tick)
        grid = [(s, t, k, m)
                for s in seeds for t in thr for k in ks for m in ms]
        g_seed, g_thr, g_k, g_m = (np.asarray(x) for x in zip(*grid))
        base = make_params(cfg)
        params = dict(base)
        params["threshold"] = g_thr.astype(np.float32)
        params["max_transient"] = g_k.astype(np.float32)
        params["max_slots"] = g_m.astype(np.int32)
        for name in ("hedge_factor", "revoke_prob", "spot_mttf"):
            params[name] = np.full(len(grid), base[name], np.float32)
        for name in ("credit_rate", "credit_burst"):  # (n_points, n_tenants)
            params[name] = np.tile(base[name][None, :], (len(grid), 1))
        keys = jax.vmap(_seed_key)(g_seed.astype(np.uint32))
        info0 = cache_info()
        fn = get_program(spec, batch=batch)
        fresh = cache_info().misses > info0.misses
    with span("serving_jax.exec"):
        t0 = time.perf_counter()
        out = fn(params, consts, keys)
        out = {k: np.asarray(v) for k, v in out.items()}
        exec_s = time.perf_counter() - t0
    _record_exec("compile" if fresh else "steady", exec_s, batch=batch,
                 n_points=len(grid))
    shape = (len(seeds), len(thr), len(ks), len(ms))
    with span("serving_jax.summarize"):
        per_point: List[Dict[str, float]] = []
        for i in range(len(grid)):
            m, _ = summarize(spec, {k: v[i] for k, v in out.items()},
                             consts, cfg.tick_s)
            per_point.append(m)
        grids: Dict[str, np.ndarray] = {}
        for name in per_point[0]:
            flat = np.asarray([p[name] for p in per_point], float)
            grids[name] = flat.reshape(shape).mean(axis=0)  # seed-averaged
    return grids, spec
