"""Continuous-batching engine: real-model correctness + slot reuse."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import smoke_config
from repro.models import build_model
from repro.runtime.batching import ContinuousBatcher, GenRequest


def _setup():
    cfg = smoke_config("starcoder2-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _greedy_reference(model, params, prompt, n_new, max_len):
    logits, cache = model.prefill(params, tokens=jnp.asarray(prompt)[None],
                                  max_len=max_len)
    toks = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    for i in range(n_new - 1):
        logits, cache = model.decode_step(
            params, cache, tokens=jnp.asarray([[toks[-1]]], jnp.int32),
            pos=jnp.int32(pos + i))
        toks.append(int(jnp.argmax(logits[0])))
    return toks


def test_batched_generation_matches_sequential():
    cfg, model, params = _setup()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=p).astype(np.int32)
               for p in (8, 8, 8, 8)]
    eng = ContinuousBatcher(model, params, max_slots=2, max_len=64)
    reqs = [GenRequest(i, p, max_new=6) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r, p in zip(reqs, prompts):
        ref = _greedy_reference(model, params, p, 6, 64)
        assert r.tokens == ref, (r.rid, r.tokens, ref)
        assert r.finish_step is not None


def test_slot_reuse_no_cross_contamination():
    """A request admitted into a freed slot must not see the previous
    occupant's KV entries."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(1)
    a = rng.integers(1, cfg.vocab_size, size=8).astype(np.int32)
    b = rng.integers(1, cfg.vocab_size, size=8).astype(np.int32)
    # run b alone
    eng1 = ContinuousBatcher(model, params, max_slots=1, max_len=64)
    rb1 = GenRequest(0, b, max_new=5)
    eng1.submit(rb1)
    eng1.run()
    # run a then b through the same single slot
    eng2 = ContinuousBatcher(model, params, max_slots=1, max_len=64)
    ra = GenRequest(0, a, max_new=5)
    rb2 = GenRequest(1, b, max_new=5)
    eng2.submit(ra)
    eng2.submit(rb2)
    eng2.run()
    assert rb2.tokens == rb1.tokens
    assert rb2.start_step > ra.start_step  # queued behind a


def test_run_honors_until_empty():
    """``run(until_empty=False)`` steps exactly ``max_steps`` times (idle
    steps included) instead of silently draining to empty — the parameter
    used to be accepted and ignored."""
    cfg, model, params = _setup()
    rng = np.random.default_rng(3)
    eng = ContinuousBatcher(model, params, max_slots=2, max_len=64)
    req = GenRequest(0, rng.integers(1, cfg.vocab_size, 8).astype(np.int32),
                     max_new=6)
    eng.submit(req)
    eng.run(until_empty=False, max_steps=3)
    assert eng.step_count == 3 and req.finish_step is None  # mid-flight
    eng.run(until_empty=False, max_steps=5)
    assert eng.step_count == 8  # idle steps still advance the clock
    assert req.finish_step is not None and len(req.tokens) == 6
    # default drains to empty and stops (no idle spinning)
    eng2 = ContinuousBatcher(model, params, max_slots=2, max_len=64)
    eng2.submit(GenRequest(1, rng.integers(1, cfg.vocab_size, 8)
                           .astype(np.int32), max_new=4))
    eng2.run()
    assert eng2.slots.n_active == 0 and not eng2.queue
    assert eng2.step_count == 3  # prefill emits token 1; 3 decode steps


def test_occupancy_and_waits_reported():
    cfg, model, params = _setup()
    rng = np.random.default_rng(2)
    eng = ContinuousBatcher(model, params, max_slots=2, max_len=64)
    reqs = [GenRequest(i, rng.integers(1, cfg.vocab_size, 8).astype(np.int32),
                       max_new=4) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    assert eng.occupancy == 1.0  # both slots busy, 3 queued
    eng.run()
    waits = [r.wait for r in reqs]
    assert all(w is not None for w in waits)
    assert max(waits) > 0  # someone queued


@pytest.mark.parametrize("kv_layout", ["dense", "paged"])
def test_decode_step_takes_weights_as_arguments(kv_layout):
    """The jitted decode step receives the weights as arguments: a closure
    would embed them in the program as constants (module text growing with
    the weights, argument bytes leaving them out)."""
    cfg = smoke_config("starcoder2-3b")
    texts, param_bytes = [], []
    for d_ff in (cfg.d_ff, 8 * cfg.d_ff):
        model = build_model(cfg.replace(d_ff=d_ff))
        params = model.init(jax.random.PRNGKey(0))
        eng = ContinuousBatcher(model, params, max_slots=2, max_len=64,
                                kv_layout=kv_layout)
        lowered = eng.lower_decode()
        nbytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params))
        mem = lowered.compile().memory_analysis()
        assert mem.argument_size_in_bytes >= nbytes
        texts.append(len(lowered.as_text()))
        param_bytes.append(nbytes)
    assert param_bytes[1] - param_bytes[0] > 1_000_000
    assert abs(texts[1] - texts[0]) < 1_000, texts


def test_paged_page_counters_and_released_position():
    """``batcher.kv_pages_walked`` adds each slot's live pages, a step at a
    time, ``batcher.kv_pages_table`` the whole table; a released slot's
    position returns to 0."""
    from repro.obs.metrics import REGISTRY

    cfg, model, params = _setup()
    eng = ContinuousBatcher(model, params, max_slots=3, max_len=64,
                            kv_layout="paged", kv_block_size=16)
    P = eng.pages_per_slot
    rng = np.random.default_rng(4)
    for rid, (plen, new) in enumerate([(20, 3), (5, 9), (40, 4), (16, 2)]):
        eng.submit(GenRequest(rid, rng.integers(1, cfg.vocab_size, plen)
                              .astype(np.int32), max_new=new))
    walked = REGISTRY.counter("batcher.kv_pages_walked")
    table = REGISTRY.counter("batcher.kv_pages_table")
    w0, t0 = walked.value, table.value
    want, steps, released = 0, 0, set()
    while eng.queue or eng.slots.n_active:
        eng.admit()  # the step's own admission then finds nothing to do
        active = {s for s, _ in eng.slots.items()}
        if active:
            want += sum(min(P, int(p) // 16 + 1) for p in eng.pos)
            steps += 1
        eng.step()
        for s in active - {s for s, _ in eng.slots.items()}:
            assert eng.pos[s] == 0
            released.add(s)
    assert steps > 3 and released == {0, 1, 2}
    assert walked.value - w0 == want
    assert table.value - t0 == steps * eng.max_slots * P


@pytest.mark.parametrize("arch", ["starcoder2-3b", "gemma2-2b"])
def test_paged_kernel_engine_matches_jnp_engine(arch):
    """The paged engine with the kernel (each slot walking its live pages,
    rolling local layers past their window included) gives the jnp path's
    logits, step for step."""
    cfg = smoke_config(arch)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(6)
    engines = []
    for use_pallas in (False, True):
        model = build_model(cfg.replace(use_pallas=use_pallas))
        eng = ContinuousBatcher(model, params, max_slots=2, max_len=64,
                                kv_layout="paged")
        engines.append(eng)
    reqs = []
    for rid, (plen, new) in enumerate([(30, 8), (6, 5), (12, 4)]):
        prompt = rng.integers(1, cfg.vocab_size, plen).astype(np.int32)
        reqs.append([GenRequest(rid, prompt, max_new=new) for _ in engines])
        for eng, req in zip(engines, reqs[-1]):
            eng.submit(req)
    steps = 0
    while engines[0].queue or engines[0].slots.n_active:
        for eng in engines:
            eng.admit()
        active = [s for s, _ in engines[0].slots.items()]
        for eng in engines:
            eng.step()
        jnp_logits, kernel_logits = (np.asarray(e.last_logits)[active]
                                     for e in engines)
        np.testing.assert_allclose(kernel_logits, jnp_logits, atol=1e-4,
                                   rtol=1e-4)
        steps += 1
    assert steps == 7  # positions 30-36: past the window of 32
    for pair in reqs:
        assert pair[0].tokens == pair[1].tokens
