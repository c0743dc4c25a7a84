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
fixed-size pages (``k_pages``/``v_pages``: (n_phys, KV, bs, hd)) and each
sequence's logical page ``j`` resolves to a physical page through its
``page_table`` row. The table and each sequence's live-page count ride in
as *scalar-prefetch* operands; the pools stay in HBM (``pl.ANY``) and the
kernel gathers pages itself, so no (B, L) dense cache is ever materialized.

Grid: (B,). One grid step is one sequence, both of its KV heads included:
one physical page is the contiguous (KV, bs, hd) slab, and a single DMA
moves it. Inside the step a ``fori_loop`` walks compute blocks of ``ppb``
pages (``pages_per_block``: the largest divisor of P with ``ppb * bs <=
256`` tokens — starcoder2's 16-token pages give 16 pages, 256 keys, 8 KB
of K and 8 KB of V a page). A grid step or loop iteration costs a fixed
fraction of a microsecond whatever it moves, so the block has to be many
pages, and 256 keys keep the (G, 256) f32 scores and the double-buffered
K/V (512 KB at starcoder2's widths) small beside VMEM. The DMAs of block
i+1 start before block i is computed (two VMEM buffers), so only each
sequence's first block waits on HBM.

Live pages: ``n_pages[b]`` bounds the walk to ``cdiv(n_pages[b], ppb)``
blocks. The caller derives it from the sequence's position
(``ops.live_pages``), so the bound only skips pages whose every position
the bias would block; the bias alone still decides what is attended, and
the pages past ``n_pages[b]`` inside the last block are gathered and
masked as before. A free slot (position 0) walks one block.
``n_pages=None`` walks all P pages of every sequence.

Deviations / assumptions (inventory, serving_jax docstring convention):
  * page_table entries must be valid physical page ids in [0, n_phys);
    unreserved logical pages point at the shared NULL page (see
    repro.runtime.paging) whose positions are -1 — masking is carried
    entirely by ``bias`` (per-sequence here, shared in the dense kernel),
    so the kernel itself never inspects positions.
  * int8 KV: when ``k_scale``/``v_scale`` are passed, K/V pools are int8
    with per-(block, kv-head, slot) f32 scales over the hd axis
    (optim.compress.quantize_int8 rowwise layout). The K/V pages are
    gathered int8 and dequantized in-kernel; each key's scale multiplies
    its score and each value row's scale its softmax weight (the same
    products as scaling the rows, which Mosaic cannot lay out from a
    (KV, bs, 1) page). The scales reach the kernel as lane rows gathered
    through the whole table by XLA: 4 bytes a key and head, 1/32 of the
    int8 K/V bytes. The f32 path and the int8 path share the softmax
    accumulator math.
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
# paged variant: gather K/V pages through the page table inside the kernel

#: keys per compute block of the paged kernel (see the module docstring)
PAGED_BLOCK_TOKENS = 256


def pages_per_block(block_size: int, n_pages: int) -> int:
    """Pages per compute block: the largest divisor of ``n_pages`` whose
    block holds at most ``PAGED_BLOCK_TOKENS`` keys (at least one page)."""
    ppb = max(1, min(n_pages, PAGED_BLOCK_TOKENS // block_size))
    while n_pages % ppb:
        ppb -= 1
    return ppb


def _paged_kernel(tbl_ref, live_ref, q_ref, bias_ref, k_hbm, v_hbm, *rest,
                  scale, softcap, ppb, quantized):
    if quantized:
        ks_ref, vs_ref, o_ref, k_buf, v_buf, sem = rest
    else:
        o_ref, k_buf, v_buf, sem = rest
    b = pl.program_id(0)
    _, _, KV, bs, hd = k_buf.shape
    G = q_ref.shape[2]
    n_blocks = bias_ref.shape[1]
    # live blocks of this sequence, kept inside the table
    n_live = jnp.clip((live_ref[b] + ppb - 1) // ppb, 1, n_blocks)

    def copies(i, slot):  # one DMA per page and pool: the (KV, bs, hd) slab
        return [pltpu.make_async_copy(src.at[tbl_ref[b, i * ppb + j]],
                                      buf.at[slot, j], sem.at[slot])
                for j in range(ppb)
                for src, buf in ((k_hbm, k_buf), (v_hbm, v_buf))]

    for c in copies(0, 0):
        c.start()

    def block(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_live)
        def _prefetch():
            for c in copies(i + 1, 1 - slot):
                c.start()

        for c in copies(i, slot):
            c.wait()
        bias = bias_ref[0, pl.ds(i, 1), :]  # (1, ppb*bs) over the G heads
        out = []
        for g, (m_prev, l_prev, acc) in enumerate(carry):
            q = q_ref[0, g].astype(jnp.float32) * scale  # (G, hd)
            k = k_buf[slot, :, g].astype(jnp.float32).reshape(ppb * bs, hd)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if quantized:  # each key's scale, on its score
                s = s * ks_ref[0, g, pl.ds(i, 1), :]
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            s = s + bias
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
            if quantized:  # each value row's scale, on its weight
                v = v_buf[slot, :, g].astype(jnp.float32).reshape(ppb * bs, hd)
                p = p * vs_ref[0, g, pl.ds(i, 1), :]
            else:
                v = v_buf[slot, :, g].reshape(ppb * bs, hd)
                p = p.astype(v.dtype)
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            out.append((m_new, l_new, acc * alpha + pv))
        return tuple(out)

    init = tuple((jnp.full((G, 1), NEG_INF, jnp.float32),
                  jnp.zeros((G, 1), jnp.float32),
                  jnp.zeros((G, hd), jnp.float32)) for _ in range(KV))
    carry = jax.lax.fori_loop(0, n_live, block, init)
    for g, (_, l, acc) in enumerate(carry):
        o_ref[0, g] = (acc / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


def paged_decode_attention_fwd(q, k_pages, v_pages, page_table, bias, *,
                               n_pages=None, k_scale=None, v_scale=None,
                               softcap=0.0, interpret=False):
    """q: (B,H,hd); k_pages,v_pages: (n_phys,KV,bs,hd); page_table: (B,P)
    int32; bias: (B, P*bs) f32 (NEG_INF = blocked — covers causality,
    sliding windows, unwritten/NULL slots). n_pages: (B,) int32 logical
    pages each sequence walks, or None for all P. Optional
    k_scale/v_scale: (n_phys,KV,bs,1) f32 for int8 pools. Returns
    (B,H,hd).

    On the chip ``bs`` must be a multiple of 8 (the f32 sublane count),
    so a block's pages stack into (ppb*bs, hd) keys. The bias rides in as
    (B, P/ppb, ppb*bs): one row per compute block."""
    B, H, hd = q.shape
    n_phys, KV, bs, _ = k_pages.shape
    P = page_table.shape[1]
    assert bias.shape == (B, P * bs), (bias.shape, B, P, bs)
    G = H // KV
    ppb = pages_per_block(bs, P)
    qg = q.reshape(B, KV, G, hd)
    bias = bias.reshape(B, P // ppb, ppb * bs)
    if n_pages is None:
        n_pages = jnp.full((B,), P, jnp.int32)
    quantized = k_scale is not None

    kern = functools.partial(_paged_kernel, scale=hd**-0.5, softcap=softcap,
                             ppb=ppb, quantized=quantized)
    # index maps receive the prefetched table and live counts after b
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        pl.BlockSpec((1, KV, G, hd), lambda b, t, n: (b, 0, 0, 0)),
        pl.BlockSpec((1, P // ppb, ppb * bs), lambda b, t, n: (b, 0, 0)),
        hbm, hbm,
    ]
    inputs = [qg, bias, k_pages, v_pages]
    scratch = [_VMEM((2, ppb, KV, bs, hd), k_pages.dtype),
               _VMEM((2, ppb, KV, bs, hd), v_pages.dtype)]
    if quantized:
        # the table's scales as lane rows, one per KV head and block: a
        # page's (KV, bs, 1) scales are too narrow for a DMA or an in-kernel
        # reshape, and are 1/32 of its int8 K/V bytes
        def rows(scale):
            r = scale[page_table][..., 0].transpose(0, 2, 1, 3)
            return r.reshape(B, KV, P // ppb, ppb * bs)

        in_specs += [pl.BlockSpec((1, KV, P // ppb, ppb * bs),
                                  lambda b, t, n: (b, 0, 0, 0))] * 2
        inputs += [rows(k_scale), rows(v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, G, hd), lambda b, t, n: (b, 0, 0, 0)),
        scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(page_table, n_pages.astype(jnp.int32), *inputs)
    return out.reshape(B, H, hd)
