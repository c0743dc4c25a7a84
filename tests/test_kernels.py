"""Per-kernel validation: shape/dtype sweeps asserting allclose against the
pure-jnp ref oracles (kernels run interpret=True on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rwkv6_scan.ops import rwkv6_scan
from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref
from repro.kernels.ssm_scan.ops import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref

NEG_INF = -2.3819763e38


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------- flash attn

@pytest.mark.parametrize("B,H,KV,S,hd", [
    (1, 4, 4, 128, 64),   # MHA
    (2, 4, 2, 256, 64),   # GQA
    (1, 8, 1, 128, 128),  # MQA, MXU-width head
    (1, 2, 2, 192, 32),   # non-pow2 seq (divisible by block 64)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(B, H, KV, S, hd, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, S, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, KV, S, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, KV, S, hd)), dtype)
    o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    o_ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("kwargs", [
    dict(window=32), dict(window=64), dict(softcap=30.0),
    dict(prefix_len=24), dict(window=48, softcap=20.0),
])
def test_flash_attention_variants(kwargs):
    rng = np.random.default_rng(1)
    B, H, KV, S, hd = 2, 4, 2, 256, 64
    q = jnp.asarray(rng.normal(size=(B, H, S, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, KV, S, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, KV, S, hd)), jnp.float32)
    o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64, **kwargs)
    o_ref = attention_ref(q, k, v, causal=True, **kwargs)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)


def test_flash_attention_grad_matches_ref():
    rng = np.random.default_rng(2)
    B, H, KV, S, hd = 1, 2, 2, 128, 32
    q = jnp.asarray(rng.normal(size=(B, H, S, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, KV, S, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, KV, S, hd)), jnp.float32)
    g = jax.grad(lambda *a: flash_attention(*a, block_q=64, block_k=64).sum(),
                 argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: attention_ref(*a).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# -------------------------------------------------------------- decode attn

@pytest.mark.parametrize("B,H,KV,L,hd,valid", [
    (2, 4, 2, 512, 64, 300),
    (1, 8, 8, 256, 128, 256),
    (4, 4, 1, 1024, 64, 7),  # nearly-empty cache
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, H, KV, L, hd, valid, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, KV, L, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, KV, L, hd)), dtype)
    bias = jnp.where(jnp.arange(L) < valid, 0.0, NEG_INF).astype(jnp.float32)
    o = decode_attention(q, k, v, bias, block_l=128)
    o_ref = decode_attention_ref(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


# ------------------------------------------------------- paged decode attn

def _paged_setup(B, KV, L, hd, bs, seed=0):
    """Dense (B, L, KV, hd) K/V scattered into a (n_phys, KV, bs, hd) paged
    pool with a distinct physical block per (batch, logical page); blocks
    0/1 are the NULL/TRASH sentinels and stay zero."""
    rng = np.random.default_rng(seed)
    P = L // bs
    k = rng.normal(size=(B, L, KV, hd)).astype(np.float32)
    v = rng.normal(size=(B, L, KV, hd)).astype(np.float32)
    n_phys = 2 + B * P
    kp = np.zeros((n_phys, KV, bs, hd), np.float32)  # kv-head-major pool
    vp = np.zeros((n_phys, KV, bs, hd), np.float32)
    # shuffled assignment: physical order != logical order
    phys = rng.permutation(np.arange(2, n_phys)).reshape(B, P)
    for b in range(B):
        for j in range(P):
            kp[phys[b, j]] = k[b, j * bs:(j + 1) * bs].swapaxes(0, 1)
            vp[phys[b, j]] = v[b, j * bs:(j + 1) * bs].swapaxes(0, 1)
    return (jnp.asarray(k), jnp.asarray(v), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(phys.astype(np.int32)), rng)


@pytest.mark.parametrize("valid", [16, 17, 33, 64])  # page-boundary straddles
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_paged_decode_attention(valid, softcap):
    from repro.kernels.decode_attention.ops import paged_decode_attention
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref

    B, H, KV, L, hd, bs = 3, 4, 2, 64, 32, 16
    k, v, kp, vp, tbl, rng = _paged_setup(B, KV, L, hd, bs)
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    bias = jnp.broadcast_to(
        jnp.where(jnp.arange(L) < valid, 0.0, NEG_INF), (B, L)
    ).astype(jnp.float32)
    o = paged_decode_attention(q, kp, vp, tbl, bias, softcap=softcap,
                               interpret=True)
    o_ref = paged_decode_attention_ref(q, kp, vp, tbl, bias, softcap=softcap)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)
    # the paged oracle on a gathered pool == the dense oracle, bitwise
    if softcap == 0.0:
        dense = decode_attention_ref(q, jnp.moveaxis(k, 1, 2),
                                     jnp.moveaxis(v, 1, 2), bias[0])
        np.testing.assert_array_equal(np.asarray(o_ref), np.asarray(dense))


def test_paged_decode_attention_int8():
    from repro.kernels.decode_attention.ops import paged_decode_attention
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    from repro.optim.compress import quantize_int8

    B, H, KV, L, hd, bs = 2, 4, 2, 64, 32, 16
    _, _, kp, vp, tbl, rng = _paged_setup(B, KV, L, hd, bs, seed=7)
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    bias = jnp.broadcast_to(
        jnp.where(jnp.arange(L) < 41, 0.0, NEG_INF), (B, L)).astype(jnp.float32)
    qk, ks = quantize_int8(kp)
    qv, vs = quantize_int8(vp)
    o = paged_decode_attention(q, qk, qv, tbl, bias, k_scale=ks, v_scale=vs,
                               interpret=True)
    o_ref = paged_decode_attention_ref(q, qk, qv, tbl, bias, k_scale=ks,
                                       v_scale=vs)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)
    # quantization error vs the f32 oracle stays bounded
    o32 = paged_decode_attention_ref(q, kp, vp, tbl, bias)
    assert float(jnp.max(jnp.abs(o32 - o_ref))) < 0.05


def _live_batch(pool, softcap):
    """One batch of every kind of slot the engine hands the kernel, over a
    table long enough for four compute blocks (L=1024 in 16-token pages):
    one page, a page-boundary straddle, a block-boundary straddle, the full
    table, a rolling local layer that has wrapped (pos >= L, every position
    in the window), and a free slot (pos 0, its row all TRASH, whose pages
    each hold the free slot's position 0 at offset 0)."""
    from repro.kernels.decode_attention.ops import (live_pages,
                                                    paged_decode_attention)
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    from repro.optim.compress import quantize_int8
    from repro.runtime.paging import TRASH_BLOCK

    B, H, KV, L, hd, bs = 6, 4, 2, 1024, 32, 16
    P = L // bs
    _, _, kp, vp, tbl, rng = _paged_setup(B, KV, L, hd, bs, seed=5)
    kp = kp.at[TRASH_BLOCK].set(rng.normal(size=kp.shape[1:]))
    vp = vp.at[TRASH_BLOCK].set(rng.normal(size=vp.shape[1:]))
    pos = np.array([3, 16, 256, L - 1, L + 100, 0])
    tbl = tbl.at[5].set(TRASH_BLOCK)
    allowed = np.arange(L)[None] <= pos[:, None]
    allowed[5] = np.arange(L) % bs == 0
    bias = jnp.asarray(np.where(allowed, 0.0, NEG_INF).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kw = dict(softcap=softcap)
    if pool == "int8":
        kp, ks = quantize_int8(kp)
        vp, vs = quantize_int8(vp)
        kw.update(k_scale=ks, v_scale=vs)
    elif pool == "bf16":
        q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    n_pages = live_pages(jnp.asarray(pos, jnp.int32), bs, P)
    np.testing.assert_array_equal(np.asarray(n_pages), [1, 2, 17, P, P, 1])
    o = paged_decode_attention(q, kp, vp, tbl, bias, n_pages=n_pages,
                               interpret=True, **kw)
    return o, paged_decode_attention_ref(q, kp, vp, tbl, bias, **kw), (
        q, kp, vp, tbl, bias, kw, P)


@pytest.mark.parametrize("pool,softcap", [
    ("f32", 0.0), ("f32", 30.0), ("bf16", 0.0), ("int8", 0.0),
    ("int8", 30.0),
])
def test_paged_decode_attention_live_pages(pool, softcap):
    """The kernel walks only each slot's live pages and still matches the
    oracle, which reads the whole table."""
    o, o_ref, _ = _live_batch(pool, softcap)
    tol = 2e-2 if pool == "bf16" else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol,
                               rtol=tol)


def test_paged_decode_attention_all_pages_by_default():
    """``n_pages=None`` is the whole table, bit for bit."""
    from repro.kernels.decode_attention.ops import paged_decode_attention

    _, _, (q, kp, vp, tbl, bias, kw, P) = _live_batch("f32", 0.0)
    full = jnp.full((q.shape[0],), P, jnp.int32)
    o_none = paged_decode_attention(q, kp, vp, tbl, bias, interpret=True,
                                    **kw)
    o_full = paged_decode_attention(q, kp, vp, tbl, bias, n_pages=full,
                                    interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(o_none), np.asarray(o_full))


def test_paged_decode_attention_skips_dead_blocks():
    """Blocks past a slot's live pages are never read: NaN there leaves the
    output as it was."""
    from repro.kernels.decode_attention.kernel import pages_per_block
    from repro.kernels.decode_attention.ops import (live_pages,
                                                    paged_decode_attention)

    o, _, (q, kp, vp, tbl, bias, kw, P) = _live_batch("f32", 0.0)
    bs = kp.shape[2]
    ppb = pages_per_block(bs, P)
    pos = jnp.asarray([3, 16, 256, 1023, 1123, 0], jnp.int32)
    n_pages = live_pages(pos, bs, P)
    dead = [int(tbl[b, j]) for b in range(4)  # slots with their own pages
            for j in range(-(-int(n_pages[b]) // ppb) * ppb, P)]
    assert len(dead) == 3 * P - 16 - 16 - 32
    kp, vp = (x.at[jnp.asarray(dead)].set(jnp.nan) for x in (kp, vp))
    o_dead = paged_decode_attention(q, kp, vp, tbl, bias, n_pages=n_pages,
                                    interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(o_dead), np.asarray(o))


def test_pages_per_block_rule():
    """Compute blocks: the largest divisor of P within 256 keys."""
    from repro.kernels.decode_attention.kernel import pages_per_block

    assert pages_per_block(16, 256) == 16  # starcoder2: 256 keys a block
    assert pages_per_block(16, 64) == 16
    assert pages_per_block(32, 2) == 2  # a short table is one block
    assert pages_per_block(16, 24) == 12  # 16 does not divide 24
    assert pages_per_block(16, 17) == 1
    assert pages_per_block(512, 8) == 1  # a page wider than the budget


# ------------------------------------------------------------------- rwkv6

@pytest.mark.parametrize("B,H,S,hd,chunk", [
    (2, 3, 128, 32, 32), (1, 2, 96, 64, 16), (2, 1, 64, 64, 64),
])
def test_rwkv6_scan(B, H, S, hd, chunk):
    rng = np.random.default_rng(0)
    r = jnp.asarray(rng.normal(size=(B, H, S, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, hd)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.2, 0.999, size=(B, H, S, hd)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(H, hd)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(B, H, hd, hd)), jnp.float32)
    y, sT = rwkv6_scan(r, k, v, w, u, s0, chunk=chunk)
    y_ref, sT_ref = rwkv6_scan_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_ref),
                               atol=1e-3, rtol=1e-3)


def test_rwkv6_state_chaining():
    """Running two half-sequences with state carry == one full run."""
    rng = np.random.default_rng(1)
    B, H, S, hd = 1, 2, 64, 32
    mk = lambda: jnp.asarray(rng.normal(size=(B, H, S, hd)), jnp.float32)
    r, k, v = mk(), mk(), mk()
    w = jnp.asarray(rng.uniform(0.5, 0.99, size=(B, H, S, hd)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(H, hd)), jnp.float32)
    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    y_full, sT_full = rwkv6_scan(r, k, v, w, u, s0, chunk=16)
    h = S // 2
    y1, s1 = rwkv6_scan(r[:, :, :h], k[:, :, :h], v[:, :, :h], w[:, :, :h], u, s0, chunk=16)
    y2, s2 = rwkv6_scan(r[:, :, h:], k[:, :, h:], v[:, :, h:], w[:, :, h:], u, s1, chunk=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=2)),
                               np.asarray(y_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(sT_full), atol=1e-4)


# --------------------------------------------------------------------- ssm

@pytest.mark.parametrize("B,S,Di,N,chunk,bd", [
    (2, 128, 64, 8, 32, 32), (1, 64, 128, 16, 64, 64), (2, 96, 32, 4, 16, 32),
])
def test_ssm_scan(B, S, Di, N, chunk, bd):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, S, Di)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(B, S, Di)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2, size=(Di, N)), jnp.float32)
    Bc = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cc = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(Di,)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(B, Di, N)), jnp.float32)
    y, hT = ssm_scan(x, dt, A, Bc, Cc, D, h0, chunk=chunk, block_d=bd)
    y_ref, hT_ref = ssm_scan_ref(x, dt, A, Bc, Cc, D, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_ref), atol=1e-4, rtol=1e-4)


def test_ssm_grads_flow():
    rng = np.random.default_rng(3)
    B, S, Di, N = 1, 32, 16, 4
    x = jnp.asarray(rng.normal(size=(B, S, Di)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(B, S, Di)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2, size=(Di, N)), jnp.float32)
    Bc = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cc = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(Di,)), jnp.float32)
    h0 = jnp.zeros((B, Di, N), jnp.float32)

    def loss_k(x):
        return ssm_scan(x, dt, A, Bc, Cc, D, h0, chunk=16, block_d=16)[0].sum()

    def loss_r(x):
        return ssm_scan_ref(x, dt, A, Bc, Cc, D, h0)[0].sum()

    np.testing.assert_allclose(np.asarray(jax.grad(loss_k)(x)),
                               np.asarray(jax.grad(loss_r)(x)), atol=1e-4)


# --------------------------------------------------- training backward kernels

def test_rwkv6_backward_kernel_matches_ref():
    rng = np.random.default_rng(4)
    B, H, S, hd = 2, 3, 96, 32
    r, k, v = (jnp.asarray(rng.normal(size=(B, H, S, hd)), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(rng.uniform(0.3, 0.99, size=(B, H, S, hd)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(H, hd)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(B, H, hd, hd)), jnp.float32)

    def loss(fn):
        def f(*a):
            y, sT = fn(*a)
            return (y**2).sum() + (sT * 1.3).sum()
        return f

    gk = jax.grad(loss(lambda *a: rwkv6_scan(*a, chunk=32, bwd_impl="kernel")),
                  argnums=tuple(range(6)))(r, k, v, w, u, s0)
    gr = jax.grad(loss(rwkv6_scan_ref), argnums=tuple(range(6)))(r, k, v, w, u, s0)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ssm_backward_kernel_matches_ref():
    rng = np.random.default_rng(5)
    B, S, Di, N = 2, 96, 64, 8
    x = jnp.asarray(rng.normal(size=(B, S, Di)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(B, S, Di)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2, size=(Di, N)), jnp.float32)
    Bc = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cc = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(Di,)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(B, Di, N)), jnp.float32)

    def loss(fn):
        def f(*a):
            y, hT = fn(*a)
            return (y**2).sum() + (hT * 1.3).sum()
        return f

    # block_d=32 < Di exercises the multi-d-block partial accumulation
    gk = jax.grad(loss(lambda *a: ssm_scan(*a, chunk=32, block_d=32,
                                           bwd_impl="kernel")),
                  argnums=tuple(range(7)))(x, dt, A, Bc, Cc, D, h0)
    gr = jax.grad(loss(ssm_scan_ref), argnums=tuple(range(7)))(x, dt, A, Bc, Cc, D, h0)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
