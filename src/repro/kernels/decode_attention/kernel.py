"""Flash-decode — Pallas TPU kernel.

GPU flash-decode splits the KV cache across SMs and combines partial
softmaxes in a second pass. The TPU-native shape of the same idea: the cache
length is the innermost *sequential* grid dimension, so the partial-softmax
state (m, l, acc) lives in VMEM scratch across cache blocks and no combine
pass exists. Cross-chip cache splits (cache_len sharded over "model") are
handled one level up by XLA SPMD inserting the max/sum all-reduces — see
repro.parallel.layouts decode rules.

Grid: (B, KV, n_L_blocks). All G=H/KV query heads of a kv-head ride in one
block (G x hd fits VMEM), so the MXU sees (G, hd) x (hd, bL) matmuls.

Paged variant (``paged_decode_attention_fwd``): the cache is a shared pool of
fixed-size blocks (``k_pages``/``v_pages``: (n_phys_blocks, KV, block_size,
hd)) and each sequence's logical page ``j`` resolves to a physical block
through a per-sequence ``page_table`` row. The table rides in as a
*scalar-prefetch* operand (``pltpu.PrefetchScalarGridSpec``), so the
K/V BlockSpec index maps read ``table[b, j]`` and the gather happens in the
kernel's own DMA pipeline — no (B, L) dense cache is ever materialized in
HBM. Online-softmax state is identical to the dense kernel.

Deviations / assumptions (inventory, serving_jax docstring convention):
  * page_table entries must be valid physical block ids in
    [0, n_phys_blocks); unreserved logical pages point at the shared NULL
    block (see repro.runtime.paging) whose positions are -1 — masking is
    carried entirely by ``bias`` (per-sequence here, shared in the dense
    kernel), so the kernel itself never inspects positions.
  * block_size is the innermost-grid tile: best TPU utilisation wants it a
    multiple of the lane count (128); the reference engine runs block_size
    16-32 under interpret mode on CPU, where this only costs grid steps.
  * int8 KV: when ``k_scale``/``v_scale`` are passed, K/V pools are int8
    with per-(block, kv-head, slot) f32 scales over the hd axis
    (optim.compress.quantize_int8 rowwise layout); dequantization happens
    in-kernel after the gather, so HBM traffic stays int8. The f32 path
    and the int8 path intentionally share the softmax accumulator math.
  * one new-token query per sequence (Sq == 1), inference only — no VJP.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
    _VMEM = pltpu.VMEM
except Exception:  # pragma: no cover
    _VMEM = None

NEG_INF = -2.3819763e38


def _kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, softcap, n_l):
    il = pl.program_id(2)

    @pl.when(il == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, hd)
    k = k_ref[0, 0]  # (bL, hd)
    s = jax.lax.dot_general(q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bL)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = s + bias_ref[...]  # (1, bL) broadcast over the G query heads

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0, 0],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new

    @pl.when(il == n_l - 1)
    def _out():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-37)).astype(o_ref.dtype)


def decode_attention_fwd(q, k, v, bias, *, softcap=0.0, block_l=256,
                         interpret=False):
    """q: (B,H,hd); k,v: (B,KV,L,hd); bias: (L,) f32. Returns (B,H,hd).

    The bias rides in as a (1, L) row: a 1-D f32 block would need a tile
    the TPU's 1-D layout (T(1024)) does not give below 1024 elements."""
    B, H, hd = q.shape
    KV, L = k.shape[1], k.shape[2]
    G = H // KV
    bl = min(block_l, L)
    assert L % bl == 0, (L, bl)
    n_l = L // bl
    qg = q.reshape(B, KV, G, hd)
    bias = bias.reshape(1, L)

    kern = functools.partial(_kernel, scale=hd**-0.5, softcap=softcap, n_l=n_l)
    out = pl.pallas_call(
        kern,
        grid=(B, KV, n_l),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, g, j: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, bl, hd), lambda b, g, j: (b, g, j, 0)),
            pl.BlockSpec((1, 1, bl, hd), lambda b, g, j: (b, g, j, 0)),
            pl.BlockSpec((1, bl), lambda b, g, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, g, j: (b, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        scratch_shapes=[
            _VMEM((G, 1), jnp.float32),
            _VMEM((G, 1), jnp.float32),
            _VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qg, k, v, bias)
    return out.reshape(B, H, hd)


# ---------------------------------------------------------------------------
# paged variant: gather K/V blocks through the page table inside the kernel


def _paged_kernel(tbl_ref, q_ref, k_ref, v_ref, bias_ref, *rest, scale,
                  softcap, n_p, quantized):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    del tbl_ref  # consumed by the BlockSpec index maps, not the body
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, hd)
    k = k_ref[0, 0]  # (bs, hd) — one physical block of this kv-head
    if quantized:
        k = k.astype(jnp.float32) * ks_ref[0, 0]
    s = jax.lax.dot_general(q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bs)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = s + bias_ref[0, 0]  # (1, bs) broadcast over the G query heads

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    if quantized:
        v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    else:
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0, 0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = m_new

    @pl.when(j == n_p - 1)
    def _out():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-37)).astype(o_ref.dtype)


def paged_decode_attention_fwd(q, k_pages, v_pages, page_table, bias, *,
                               k_scale=None, v_scale=None, softcap=0.0,
                               interpret=False):
    """q: (B,H,hd); k_pages,v_pages: (n_phys,KV,bs,hd); page_table: (B,P)
    int32; bias: (B, P*bs) f32 (NEG_INF = blocked — covers causality,
    sliding windows, unwritten/NULL slots). Optional k_scale/v_scale:
    (n_phys,KV,bs,1) f32 for int8 pools. Returns (B,H,hd).

    The pool is kv-head-major so one grid step's K/V block ends in the
    dense tile ``(bs, hd)``; on the chip ``bs`` must be a multiple of 8
    (the f32 sublane count). The bias rides in as (B, P, 1, bs) so its
    block's last two dimensions equal the array's."""
    B, H, hd = q.shape
    n_phys, KV, bs, _ = k_pages.shape
    P = page_table.shape[1]
    assert bias.shape == (B, P * bs), (bias.shape, B, P, bs)
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    bias = bias.reshape(B, P, 1, bs)
    quantized = k_scale is not None

    kern = functools.partial(_paged_kernel, scale=hd**-0.5, softcap=softcap,
                             n_p=P, quantized=quantized)
    # index maps receive the prefetched table ref after the grid indices
    in_specs = [
        pl.BlockSpec((1, 1, G, hd), lambda b, g, j, t: (b, g, 0, 0)),
        pl.BlockSpec((1, 1, bs, hd), lambda b, g, j, t: (t[b, j], g, 0, 0)),
        pl.BlockSpec((1, 1, bs, hd), lambda b, g, j, t: (t[b, j], g, 0, 0)),
        pl.BlockSpec((1, 1, 1, bs), lambda b, g, j, t: (b, j, 0, 0)),
    ]
    inputs = [qg, k_pages, v_pages, bias]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, bs, 1), lambda b, g, j, t: (t[b, j], g, 0, 0)),
            pl.BlockSpec((1, 1, bs, 1), lambda b, g, j, t: (t[b, j], g, 0, 0)),
        ]
        inputs += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, g, j, t: (b, g, 0, 0)),
        scratch_shapes=[
            _VMEM((G, 1), jnp.float32),
            _VMEM((G, 1), jnp.float32),
            _VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(page_table, *inputs)
    return out.reshape(B, H, hd)
