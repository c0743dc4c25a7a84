"""The plain float32 reference against the replica's own prefill and paged
decode (kernels on, interpreted), at a tiny width; and its control, the
same reference in float8, which the comparison has to tell apart."""

import jax
import numpy as np
import pytest

from bench.reference import dense_decoder as ref
from bench.reference import fleet as fref

M = dict(ref.TINY, rope_theta=1e5, norm_eps=1e-5, dtype="bfloat16")


def _program(max_slots=2, max_len=64):
    from repro.configs import get_config
    from repro.models import build_model
    from repro.runtime.batching import ContinuousBatcher

    cfg = get_config("starcoder2-3b").replace(
        **{k: M[k] for k in ref.TINY}, use_pallas=True)
    model = build_model(cfg)
    params = ref.program_params(11, M, "bfloat16")
    return model, params, ContinuousBatcher(
        model, params, max_slots=max_slots, max_len=max_len,
        kv_layout="paged", kv_block_size=16)


def test_weights_take_the_programs_layout():
    model, params, _ = _program()
    want = model.init_shape()
    assert jax.tree.structure(want) == jax.tree.structure(params)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(want), jax.tree.leaves(params)))
    one = ref.layer_params(ref.base_key(11), 1, M, np.dtype("bfloat16"))
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(params["blocks"])):
        assert np.array_equal(np.asarray(a), np.asarray(b[1]))


def test_seed_beyond_32_bits_gives_its_own_weights():
    a = ref.base_key(2 ** 31 + 5)
    b = ref.base_key(2 ** 32 + 2 ** 31 + 5)
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
    with pytest.raises(ValueError):
        ref.base_key(-1)


def _served():
    from repro.runtime.batching import GenRequest

    _, _, bk = _program()
    rng = np.random.default_rng(0)
    reqs = [GenRequest(0, rng.integers(1, 128, 21).astype(np.int32), 12),
            GenRequest(1, rng.integers(1, 128, 34).astype(np.int32), 7),
            GenRequest(2, rng.integers(1, 128, 9).astype(np.int32), 9)]
    for r in reqs:
        bk.submit(r)
    while bk.queue or bk.slots.n_active:
        bk.step()
    seqs = [np.concatenate([r.prompt, r.tokens[:-1]]) for r in reqs]
    wanted = [np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + len(r.tokens))
              for r in reqs]
    return reqs, seqs, wanted


def test_replica_prefill_and_decode_agree_with_the_reference():
    reqs, seqs, wanted = _served()
    logits = ref.reference_logits(11, M, seqs, wanted, lengths=(16, 32, 64), n_out=16)
    gaps = np.concatenate([ref.served_gaps(lg, np.asarray(r.tokens))
                           for lg, r in zip(logits, reqs)])
    # bf16 weights and activations over 2 layers: a few 1e-3 of a logit
    # whose spread is ~1; every token the engine served is the reference's
    # best or within that of it
    assert gaps.max() < 0.02
    # logits at the served positions reproduce the engine's last step
    assert all(lg.shape == (len(r.tokens), M["vocab_size"])
               for lg, r in zip(logits, reqs))


def test_fp8_control_is_told_apart():
    reqs, seqs, wanted = _served()
    logits = ref.reference_logits(11, M, seqs, wanted, lengths=(16, 32, 64), n_out=16)
    ctl = ref.reference_logits(11, M, seqs, wanted, lengths=(16, 32, 64), n_out=16,
                               precision="fp8")
    prog = max(ref.served_gaps(lg, np.asarray(r.tokens)).max()
               for lg, r in zip(logits, reqs))
    control = max(ref.served_gaps(lg, c.argmax(-1)).max()
                  for lg, c in zip(logits, ctl))
    assert control > 3 * max(prog, 1e-3)


def test_padding_leaves_the_valid_rows_alone():
    seq = [np.arange(1, 30, dtype=np.int32)]
    w = [np.arange(29)]
    a = ref.reference_logits(3, M, seq, w, lengths=(32,), n_out=32)[0]
    b = ref.reference_logits(3, M, seq, w, lengths=(64,), n_out=64)[0]
    assert np.all(np.isfinite(a))
    np.testing.assert_allclose(a, b, atol=1e-4)


def test_fleet_reference_counts_broken_guarantees():
    arrival = np.array([0, 0, 1, 2])
    gen = np.array([2, 1, 2, 1])
    start = np.array([0, 2, 1, 3])
    finish = np.array([2, 3, 3, 4])
    busy = np.array([1, 2, 2, 1])
    cap = np.array([2, 2, 2, 2])
    v = fref.violations(arrival, gen, start, finish, busy, cap)
    assert all(x == 0 for x in v.values())
    bad = start.copy()
    bad[2] = 2  # starts a tick later but finishes the same tick
    assert fref.violations(arrival, gen, bad, finish, busy, cap)[
        "too_fast"] == 1
    early = start.copy()
    early[3] = 1
    assert fref.violations(arrival, gen, early, finish, busy, cap)[
        "early_start"] == 1
    ctl = fref.control_answers(arrival, gen, 4)
    assert fref.violations(arrival, gen, ctl["start"], ctl["finish"], busy,
                           np.ones(4, int))["over_capacity"] > 0
    s = fref.summary(arrival, start, finish, 1.0)
    w = (start - arrival).astype(float)
    assert s["short_p90_wait_s"] == pytest.approx(np.percentile(w, 90))
    assert s["n_done"] == 4.0


FLEET = dict(n_replicas=1, n_reserve=0, revocation_mttf=0.0,
             provisioning_delay=2.0, tick_s=1.0, probe_d=2, probe_retries=3,
             hedge_factor=4.0)


def _sim(arrival, gen, pinned, horizon, **kw):
    kw = dict(dict(threshold=0.5, max_transient=0, max_slots=1,
                   drain_preference="least_loaded", seed=[1]), **kw)
    return fref.simulate(np.asarray(arrival), np.asarray(gen),
                         np.asarray(pinned), horizon, FLEET, **kw)


def test_fleet_policy_by_hand():
    # one replica, one slot: first come, first served
    r = _sim([0, 0, 1], [2, 1, 2], [0] * 8, 8)
    assert r["start"].tolist() == [0, 2, 3]
    assert r["finish"].tolist() == [2, 3, 5]
    assert r["busy"][:6].tolist() == [1, 1, 1, 1, 1, 0]
    # pinned from tick 1 with a budget of one transient, online 2 ticks
    # after the tick-1 order: the displaced resident restarts and, with
    # nowhere else to go, queues behind the long job; a later arrival
    # finds every probe pinned and falls back to the transient
    r = _sim([0, 4], [3, 2], [0] + [1] * 9, 10, max_transient=1)
    assert r["active_transients"][:5].tolist() == [0, 0, 0, 1, 1]
    assert r["start"].tolist() == [-1, 4]
    assert r["finish"].tolist() == [-1, 6]


def test_fleet_policy_keeps_the_guarantees():
    rng = np.random.default_rng(4)
    arrival = np.sort(rng.integers(0, 300, 400))
    gen = rng.integers(1, 20, 400)
    pinned = (np.arange(500) // 100) % 2
    fl = dict(FLEET, n_replicas=8)
    kw = dict(threshold=0.1, max_transient=4, max_slots=2,
              drain_preference="least_loaded")
    r = fref.simulate(arrival, gen, pinned * 6, 1500, fl, seed=[2], **kw)
    v = fref.violations(arrival, gen, r["start"], r["finish"], r["busy"],
                        r["cap"])
    assert all(x == 0 for x in v.values()), v
    assert r["active_transients"].max() > 0
    again = fref.simulate(arrival, gen, pinned * 6, 1500, fl, seed=[2],
                          **kw)
    assert np.array_equal(r["start"], again["start"])
    gaps = fref.policy_gaps(arrival, gen, r, again)
    assert all(x == 0 for x in gaps.values())
