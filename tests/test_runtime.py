"""Elastic runtime: rescale mid-run, resume, serving fleet semantics
(hedge duplication, pin-strand reroute, drain-area accounting), straggler
watchdog, data-pipeline determinism."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import Checkpointer
from repro.configs import smoke_config
from repro.data import SyntheticBatches
from repro.models import build_model
from repro.optim import AdamW
from repro.optim.schedule import constant_schedule
from repro.runtime import ElasticServingFleet, ElasticTrainer, Request
from repro.runtime.straggler import StragglerWatchdog
from repro.sched import ControllerSpec


def test_elastic_trainer_rescale_and_resume(tmp_path):
    cfg = smoke_config("starcoder2-3b").replace(num_microbatches=2)
    model = build_model(cfg)
    opt = AdamW(lr=constant_schedule(3e-3))
    data = SyntheticBatches(cfg, global_batch=8, seq_len=32, seed=0)
    ck = Checkpointer(tmp_path, keep=2)
    tr = ElasticTrainer(model, opt, data, ck, model_par=2,
                        devices=jax.devices()[:8])
    tr.run(16, preempt_at={8: 4}, checkpoint_every=5)
    assert tr.rescales == 1
    losses = [h[1] for h in tr.history]
    assert losses[-1] < losses[0]
    assert all(np.isfinite(losses))
    # resume continues from the stored step
    tr2 = ElasticTrainer(model, opt, data, ck, model_par=2,
                         devices=jax.devices()[:4])
    tr2.run(18, checkpoint_every=0)
    assert [h[0] for h in tr2.history] == [16, 17]
    # cold-restore into a differently-sized mesh: the checkpoint written
    # under the 4-device mesh reshards into an 8-device trainer whose
    # abstract state comes from the same opt.init_state constructor
    tr3 = ElasticTrainer(model, opt, data, ck, model_par=2,
                         devices=jax.devices()[:8])
    tr3.run(20, checkpoint_every=0)
    assert [h[0] for h in tr3.history] == [18, 19]
    assert all(np.isfinite(h[1]) for h in tr3.history)


def test_elastic_rescale_picks_survivors_from_own_devices(tmp_path):
    """A trainer given devices 4..7 shrinks onto 4..5, never onto the
    process's first devices, and refuses to grow past what it was given."""
    cfg = smoke_config("starcoder2-3b").replace(num_microbatches=2)
    model = build_model(cfg)
    data = SyntheticBatches(cfg, global_batch=8, seq_len=32, seed=0)
    own = jax.devices()[4:8]
    tr = ElasticTrainer(model, AdamW(lr=constant_schedule(3e-3)), data,
                        Checkpointer(tmp_path, keep=2), model_par=2,
                        devices=own)
    tr.run(4, preempt_at={2: 2}, checkpoint_every=0)
    assert tr.rescales == 1 and tr.devices == own[:2]
    spanned = {d for s in jax.tree.leaves(tr.state_shardings)
               for d in s.device_set}
    assert spanned == set(own[:2])
    with pytest.raises(ValueError):
        tr._survivors(5)


def test_abstract_state_matches_live_constructor():
    """ElasticTrainer cold-restore regression: the abstract TrainState must
    be eval-shaped from the same ``opt.init_state`` the live path calls —
    for every moments layout (the int8 slot tree is where a hand-rolled
    abstract dict drifted)."""
    params = {"w": jnp.zeros((4, 8)), "scale": jnp.zeros((8,))}
    for dtype in ("float32", "int8"):
        for ef in (False, True):
            opt = AdamW(lr=constant_schedule(1e-3), moments_dtype=dtype,
                        error_feedback=ef)
            live = opt.init_state(params)
            abstract = jax.eval_shape(opt.init_state, params)
            assert (jax.tree.structure(live)
                    == jax.tree.structure(abstract)), (dtype, ef)
            for l, a in zip(jax.tree.leaves(live),
                            jax.tree.leaves(abstract)):
                assert l.shape == a.shape and l.dtype == a.dtype, (dtype, ef)


def _reqs(rng, n, horizon, gen=8):
    return [Request(i, int(rng.uniform(0, horizon)), gen_len=gen)
            for i in range(n)]


def test_serving_elastic_beats_static():
    rng = np.random.default_rng(0)
    reqs = _reqs(rng, 600, 1500)
    pinned = lambda t: 6 + (2 if 400 < t < 900 else 0)
    s_static = ElasticServingFleet(8, max_transient=0).run(
        [Request(q.rid, q.arrival, q.gen_len) for q in reqs], pinned, 4000)
    s_el = ElasticServingFleet(8, threshold=0.6, max_transient=8,
                               provisioning_delay=20).run(
        [Request(q.rid, q.arrival, q.gen_len) for q in reqs], pinned, 4000)
    assert s_el["avg_wait"] <= s_static["avg_wait"]
    assert s_el["n_done"] >= s_static["n_done"]


def test_serving_drain_completes_queue():
    """Draining replicas finish queued requests before going offline."""
    fleet = ElasticServingFleet(2, threshold=0.95, max_transient=4,
                                provisioning_delay=1)
    reqs = [Request(i, 0, gen_len=4) for i in range(40)]
    out = fleet.run(reqs, lambda t: 2 if t < 50 else 0, 500)
    assert out["n_done"] == 40
    for r in fleet.replicas:
        if r.kind == "transient" and r.offline_at is not None:
            assert not r.queue and r.active is None


def test_serving_revocation_rerouted():
    rng = np.random.default_rng(1)
    fleet = ElasticServingFleet(4, threshold=0.5, max_transient=8,
                                provisioning_delay=5,
                                revocation_mttf_ticks=100, seed=1)
    reqs = _reqs(rng, 300, 800, gen=6)
    out = fleet.run(reqs, lambda t: 3, 3000)
    assert out["n_done"] == 300  # nothing lost despite revocations
    assert out["n_revocations"] > 0


def test_hedge_duplicates_first_completion_wins():
    """§3.3 transient-safety: a hedged request is *duplicated* onto the
    on-demand reserve (not moved); here the transient copy finishes first
    and the reserve copy is cancelled."""
    # threshold=0 holds the controller (no adds, no drains) so the
    # hand-built transient survives the run
    fleet = ElasticServingFleet(1, threshold=0.0, max_transient=0,
                                hedge_factor=0.5)
    tr = fleet._bring_online(0)
    req = Request(0, 0, gen_len=10)
    for t in range(30):
        # on-demand pinned for the first ticks so the request routes to the
        # transient; unpinned after, so the reserve can take the hedge copy
        fleet._tick(t, [req] if t == 0 else (), pinned=1 if t < 3 else 0)
    assert req.hedged and fleet.n_hedges == 1
    # the original stayed on the transient the whole time: started at t=0,
    # 10 tokens -> finished at t=10 (a *move* would have restarted it on the
    # reserve at the hedge tick and finished later)
    assert req.start == 0 and req.finish == 10
    # the duplicate the reserve picked up lost the race and was cancelled
    assert fleet.n_hedge_cancelled == 1
    ond = fleet.replicas[0]
    assert ond.active is None and not ond.queue
    assert fleet.summary([req])["n_done"] == 1


def test_hedge_covers_revoked_transient():
    """The on-demand copy carries a hedged request whose transient is
    revoked: nothing is lost and nothing restarts from scratch."""
    fleet = ElasticServingFleet(1, threshold=0.0, max_transient=0,
                                hedge_factor=0.5)
    tr = fleet._bring_online(0)
    req = Request(0, 0, gen_len=8)
    for t in range(6):
        fleet._tick(t, [req] if t == 0 else (), pinned=1 if t < 3 else 0)
    assert req.hedged and req.finish is None
    # force a revocation: the primary is dropped (not re-routed) because
    # its reserve copy is already live
    class _AlwaysRevoke:
        def random(self):
            return 0.0

    fleet.revocation_mttf = 1.0
    fleet.rng = _AlwaysRevoke()
    fleet._maybe_revoke(6)
    assert fleet.n_revocations == 1 and tr.offline_at == 6
    fleet.revocation_mttf = 0.0
    for t in range(7, 30):
        fleet._tick(t, (), pinned=0)
    assert req.finish is not None
    assert fleet.summary([req])["n_done"] == 1


def test_pinned_replica_reroutes_queue_and_active():
    """A replica transitioning to pinned hands queued requests back to the
    router and requeues its active request (start reset) — nothing strands
    until unpin."""
    fleet = ElasticServingFleet(2, max_transient=0)
    reqs = [Request(i, 0, gen_len=4) for i in range(4)]
    fleet._tick(0, reqs, pinned=0)
    r0, r1 = fleet.replicas
    assert r0.load + r1.load == 4  # all placed (load = queued + active)
    fleet._tick(1, (), pinned=1)  # r0 newly pinned mid-service
    assert r0.pinned and r0.active is None and not r0.queue
    for t in range(2, 40):
        fleet._tick(t, (), pinned=1)
    # every request finished on the one unpinned replica
    assert fleet.summary(reqs)["n_done"] == 4
    assert all(q.finish is not None for q in reqs)


def test_pending_ticks_counter_invariant():
    """The cached pending_ticks the policy view reads (O(1) per probe) must
    track queued + active decode ticks through routing, hedging, pinning
    displacement and revocations."""
    rng = np.random.default_rng(2)
    fleet = ElasticServingFleet(4, threshold=0.5, max_transient=6,
                                provisioning_delay=5, hedge_factor=1.0,
                                revocation_mttf_ticks=150, seed=2)
    reqs = _reqs(rng, 200, 500, gen=6)
    by_arrival = {}
    for q in reqs:
        by_arrival.setdefault(q.arrival, []).append(q)
    for t in range(900):
        fleet._tick(t, by_arrival.get(t, ()),
                    pinned=3 if (t // 100) % 2 else 1)
        if t % 97 == 0:
            for r in fleet.replicas:
                want = sum(q.gen_len for q in r.queue) + \
                    (r.tokens_left if r.active is not None else 0)
                assert r.pending_ticks == want, (t, r.rid)
    assert fleet.summary(reqs)["n_done"] == 200


def test_pin_want_clamped_to_ondemand():
    """pinned_fn beyond the on-demand fleet is clamped; transients are
    never pinned."""
    fleet = ElasticServingFleet(2, threshold=0.5, max_transient=3,
                                provisioning_delay=1)
    for t in range(10):
        fleet._tick(t, (), pinned=99)
    transients = [r for r in fleet.replicas if r.kind == "transient"]
    assert transients, "controller should have rented transients"
    assert all(not r.pinned for r in transients)
    assert sum(1 for r in fleet.replicas if r.pinned) == 2


def test_drain_counts_in_active_area():
    """Draining-but-still-serving transients are paid capacity: the area
    integral behind avg_active_transients must count them."""
    fleet = ElasticServingFleet(1, max_transient=0)
    tr = fleet._bring_online(0)
    tr.draining = True
    tr.enqueue(Request(0, 0, gen_len=3))
    for t in range(3):
        fleet._tick(t, (), pinned=1)  # pin the on-demand: only tr serves
    # online at t=0 and t=1; finishes + goes offline inside t=2's advance
    assert fleet._active_area == 2.0
    assert fleet.summary([])["avg_active_transients"] == pytest.approx(2 / 3)
    assert tr.offline_at == 2 and not tr.queue


def test_controller_drain_guard():
    """An over-eager negative delta must not crash once no transient
    remains to drain."""
    class _OverDrain(ControllerSpec):
        def desired_delta(self, view):
            return -5

    fleet = ElasticServingFleet(2, spec=_OverDrain(0.95, 4, 1))
    fleet._bring_online(0)
    fleet._controller_tick(0)  # must not raise on the empty candidate pool
    assert [r.draining for r in fleet.replicas if r.kind == "transient"] \
        == [True]


def test_straggler_watchdog_flags_slow_worker():
    wd = StragglerWatchdog(factor=2.0, window=8, min_samples=4)
    for i in range(8):
        for w in range(4):
            wd.observe(w, 1.0 if w != 2 else 5.0)
    assert wd.flagged() == [2]


def test_data_pipeline_determinism_and_sharding():
    cfg = smoke_config("deepseek-coder-33b")
    a = SyntheticBatches(cfg, 8, 32, seed=3).batch(5)
    b = SyntheticBatches(cfg, 8, 32, seed=3).batch(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # host slicing: different hosts get different data, same host stable
    h0 = SyntheticBatches(cfg, 8, 32, seed=3, host_id=0, host_count=2).batch(5)
    h1 = SyntheticBatches(cfg, 8, 32, seed=3, host_id=1, host_count=2).batch(5)
    assert h0["tokens"].shape == (4, 32)
    assert not np.array_equal(h0["tokens"], h1["tokens"])
    # prefetch iterator yields the same stream
    it = SyntheticBatches(cfg, 8, 32, seed=3).iterate(start=5)
    np.testing.assert_array_equal(next(it)["tokens"], a["tokens"])
