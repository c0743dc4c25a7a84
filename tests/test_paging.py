"""Paged KV data plane: allocator invariants, dense-vs-paged token parity,
bucketed-prefill compile counts, int8 KV error bound."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import smoke_config  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.obs.metrics import REGISTRY  # noqa: E402
from repro.runtime.batching import ContinuousBatcher, GenRequest  # noqa: E402
from repro.runtime.paging import (NULL_BLOCK, TRASH_BLOCK, PageAllocator,  # noqa: E402
                                  PagedCacheOOM, pages_needed)


# ---------------------------------------------------------------------------
# allocator


def test_allocator_conservation_random_walk():
    """Property test: allocated + free == total allocatable after every
    reserve/free, no block duplicated, sentinels never handed out."""
    rng = np.random.default_rng(0)
    alloc = PageAllocator(n_blocks=18, block_size=8, max_slots=6,
                          pages_per_slot=4)
    held = {}
    for _ in range(500):
        if held and rng.random() < 0.45:
            slot = rng.choice(sorted(held))
            alloc.free(slot)
            del held[slot]
        else:
            slot = int(rng.integers(0, 6))
            n = int(rng.integers(1, 5))
            if slot in held:
                with pytest.raises(RuntimeError):
                    alloc.reserve(slot, n)
            elif n > alloc.n_free:
                with pytest.raises(PagedCacheOOM):
                    alloc.reserve(slot, n)
            else:
                row = alloc.reserve(slot, n)
                held[slot] = n
                assert not np.isin(row[:n], (NULL_BLOCK, TRASH_BLOCK)).any()
                assert (row[n:] == NULL_BLOCK).all()
        alloc.check_conservation()
    for slot in sorted(held):
        alloc.free(slot)
        alloc.check_conservation()
    assert alloc.n_free == alloc.n_allocatable
    assert (alloc.table == TRASH_BLOCK).all()


def test_allocator_loud_oom_and_reuse():
    alloc = PageAllocator(n_blocks=6, block_size=4, max_slots=2,
                          pages_per_slot=4)
    alloc.reserve(0, 3)
    with pytest.raises(PagedCacheOOM):
        alloc.reserve(1, 2)  # only 1 free
    assert alloc.can_reserve(1) and not alloc.can_reserve(2)
    with pytest.raises(PagedCacheOOM):
        alloc.reserve(1, 5)  # exceeds pages_per_slot
    alloc.free(0)
    row = alloc.reserve(1, 4)
    assert len(set(row.tolist())) == 4  # all distinct physical blocks


def test_pages_needed_covers_writes():
    # highest written position is min(plen + max_new, max_len) - 1
    assert pages_needed(8, 6, 64, 16) == 1
    assert pages_needed(8, 9, 64, 16) == 2  # position 16 straddles page 1
    assert pages_needed(60, 100, 64, 16) == 4  # clamped by max_len
    assert pages_needed(1, 1, 64, 16) == 1


# ---------------------------------------------------------------------------
# dense vs paged generation parity (the acceptance criterion)


def _workload(vocab, seed=42):
    rng = np.random.default_rng(seed)
    shapes = [(8, 6), (5, 9), (12, 7), (15, 5), (3, 12), (40, 6)]
    return [GenRequest(i, rng.integers(1, vocab, p).astype(np.int32), m)
            for i, (p, m) in enumerate(shapes)]


@pytest.mark.parametrize("arch", ["starcoder2-3b", "gemma2-2b"])
def test_paged_matches_dense_token_for_token(arch):
    """Greedy generation under the paged layout reproduces the dense layout
    exactly — gathering a slot's pages rebuilds its dense cache bit-for-bit
    (sliding-window starcoder2; local+global+softcap gemma2)."""
    cfg = smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    out = {}
    for layout in ("dense", "paged"):
        b = ContinuousBatcher(model, params, max_slots=2, max_len=64,
                              kv_layout=layout)
        reqs = _workload(cfg.vocab_size)
        for r in reqs:
            b.submit(r)
        b.run()
        assert all(r.finish_step is not None for r in reqs)
        out[layout] = [r.tokens for r in reqs]
        if layout == "paged":
            b.allocator.check_conservation()
            assert b.allocator.n_free == b.allocator.n_allocatable  # drained
    assert out["dense"] == out["paged"]


def test_paged_budget_head_of_line_and_submit_oom():
    cfg = smoke_config("starcoder2-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = ContinuousBatcher(model, params, max_slots=4, max_len=64,
                          kv_layout="paged", kv_blocks=2)
    with pytest.raises(PagedCacheOOM):  # needs 4 pages, pool holds 2 ever
        b.submit(GenRequest(9, np.arange(1, 41, dtype=np.int32), 30))
    reqs = [GenRequest(i, np.arange(1, 9, dtype=np.int32), 6) for i in range(5)]
    for r in reqs:
        b.submit(r)  # 1 page each; at most 2 resident at a time
    b.run()
    assert all(r.finish_step is not None for r in reqs)
    b.allocator.check_conservation()


def test_submit_rejects_oversize_prompt():
    cfg = smoke_config("starcoder2-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = ContinuousBatcher(model, params, max_slots=2, max_len=32)
    with pytest.raises(ValueError):
        b.submit(GenRequest(0, np.arange(1, 33, dtype=np.int32), 4))


# ---------------------------------------------------------------------------
# bucketed prefill: one compile per bucket, not per prompt length


def test_bucketed_prefill_compile_count():
    cfg = smoke_config("starcoder2-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = ContinuousBatcher(model, params, max_slots=2, max_len=64,
                          prompt_bucket=16)
    c = REGISTRY.counter("batcher.prefill_compiles")
    before = c.value
    # five distinct lengths in bucket 16, two in bucket 32
    for i, plen in enumerate((3, 5, 8, 11, 15, 17, 25)):
        b.submit(GenRequest(i, np.arange(1, plen + 1, dtype=np.int32), 3))
    b.run()
    assert c.value - before == 2  # buckets {16, 32} — not 7 per-plen compiles
    assert sorted(b._prefills) == [16, 32]


def test_prefill_true_len_matches_exact():
    """Model-level: bucket-padded prefill with true_len reproduces the
    exact-length prefill — logits at the true last token and cache content
    at valid slots (rolling-window gather branch included)."""
    cfg = smoke_config("gemma2-2b")  # local (window 32) + global layers
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(5)
    S = 64
    toks = rng.integers(1, cfg.vocab_size, S).astype(np.int32)
    for t in (5, 8, 16, 20, 40, 63):
        exact_logits, exact_caches = model.prefill(
            params, tokens=jnp.asarray(toks[:t])[None], max_len=S)
        padded = np.zeros(S, np.int32)
        padded[:t] = toks[:t]
        pad_logits, pad_caches = model.prefill(
            params, tokens=jnp.asarray(padded)[None], max_len=S,
            true_len=jnp.asarray(t, jnp.int32))
        np.testing.assert_allclose(np.asarray(exact_logits),
                                   np.asarray(pad_logits), atol=2e-5, rtol=2e-5)
        for ec, pc in zip(exact_caches, pad_caches):
            epos, ppos = np.asarray(ec["pos"]), np.asarray(pc["pos"])
            np.testing.assert_array_equal(epos, ppos)
            valid = epos >= 0  # (n_blocks, L)
            ek, pk = np.asarray(ec["k"]), np.asarray(pc["k"])
            np.testing.assert_allclose(
                ek[:, 0][valid], pk[:, 0][valid], atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# int8 KV quantization on the paged layout


def test_int8_kv_pool_error_bound():
    """Rowwise int8 KV (scale = amax/127 over hd) bounds the elementwise
    cache error by half a quantization step; the end-to-end attention output
    of the paged int8 oracle stays close to f32."""
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    from repro.models.common import NEG_INF
    from repro.optim.compress import dequantize_int8, quantize_int8

    rng = np.random.default_rng(11)
    B, H, KV, hd, bs, P, n_phys = 2, 4, 2, 32, 16, 4, 12
    L = P * bs
    kp = jnp.asarray(rng.standard_normal((n_phys, KV, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_phys, KV, bs, hd)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
    qk, ks = quantize_int8(kp)
    # elementwise bound: |x - deq(x)| <= scale/2 = amax/254
    err = jnp.abs(kp - dequantize_int8(qk, ks))
    bound = jnp.max(jnp.abs(kp), axis=-1, keepdims=True) / 254.0 + 1e-6
    assert bool(jnp.all(err <= bound))
    qv, vs = quantize_int8(vp)
    tbl = jnp.asarray(np.stack([rng.permutation(np.arange(2, n_phys))[:P]
                                for _ in range(B)]).astype(np.int32))
    valid = np.array([33, 17])
    bias = jnp.asarray(np.where(np.arange(L)[None] < valid[:, None],
                                0.0, NEG_INF).astype(np.float32))
    o32 = paged_decode_attention_ref(q, kp, vp, tbl, bias)
    o8 = paged_decode_attention_ref(q, qk, qv, tbl, bias, k_scale=ks, v_scale=vs)
    assert float(jnp.max(jnp.abs(o32 - o8))) < 0.05


def test_paged_int8_generation_runs():
    cfg = smoke_config("starcoder2-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = ContinuousBatcher(model, params, max_slots=2, max_len=64,
                          kv_layout="paged", kv_quant="int8")
    reqs = _workload(cfg.vocab_size)[:3]
    for r in reqs:
        b.submit(r)
    b.run()
    assert all(r.finish_step is not None and len(r.tokens) > 0 for r in reqs)
    # int8 pool (k,v int8 + f32 scales over hd=32) ~3.6x smaller than f32
    b32 = ContinuousBatcher(model, params, max_slots=2, max_len=64,
                            kv_layout="paged")
    assert b.kv_cache_bytes() < 0.35 * b32.kv_cache_bytes()
