"""Public decode-attention op (inference only — no VJP needed)."""

from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.decode_attention.kernel import (decode_attention_fwd,
                                                   paged_decode_attention_fwd)


def decode_attention(q, k, v, bias, *, softcap=0.0, block_l=256,
                     interpret=None):
    return decode_attention_fwd(q, k, v, bias, softcap=softcap,
                                block_l=block_l,
                                interpret=interpret_mode(interpret))


def live_pages(pos, block_size: int, n_pages: int):
    """Logical pages holding positions a sequence whose new token sits at
    ``pos`` can attend to: ``min(P, pos // block_size + 1)``. A rolling
    local layer that has wrapped (``pos >= P * block_size``) gives all P.
    Works on NumPy and JAX arrays alike (the engine's host-side counter and
    the decode step use the same rule)."""
    return (pos // block_size + 1).clip(max=n_pages)


def paged_decode_attention(q, k_pages, v_pages, page_table, bias, *,
                           n_pages=None, k_scale=None, v_scale=None,
                           softcap=0.0, interpret=None):
    """Decode attention against a paged KV pool — the gather through
    ``page_table`` happens inside the kernel (scalar-prefetched table,
    manual page DMAs).

    q: (B,H,hd); k_pages/v_pages: (n_phys_blocks, KV, block_size, hd);
    page_table: (B,P) int32; bias: (B, P*block_size) f32 additive mask.
    n_pages: (B,) int32 pages each sequence walks (``live_pages``), or None
    for all P. k_scale/v_scale: (n_phys_blocks, KV, block_size, 1) f32 when
    the pools are int8 (in-kernel dequantization). Returns (B,H,hd)."""
    return paged_decode_attention_fwd(q, k_pages, v_pages, page_table, bias,
                                      n_pages=n_pages, k_scale=k_scale,
                                      v_scale=v_scale, softcap=softcap,
                                      interpret=interpret_mode(interpret))
