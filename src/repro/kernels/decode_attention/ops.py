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


def paged_decode_attention(q, k_pages, v_pages, page_table, bias, *,
                           k_scale=None, v_scale=None, softcap=0.0,
                           interpret=None):
    """Decode attention against a paged KV pool — the gather through
    ``page_table`` happens inside the kernel (scalar-prefetch BlockSpecs).

    q: (B,H,hd); k_pages/v_pages: (n_phys_blocks, KV, block_size, hd);
    page_table: (B,P) int32; bias: (B, P*block_size) f32 additive mask.
    k_scale/v_scale: (n_phys_blocks, KV, block_size, 1) f32 when the pools
    are int8 (in-kernel dequantization). Returns (B,H,hd)."""
    return paged_decode_attention_fwd(q, k_pages, v_pages, page_table, bias,
                                      k_scale=k_scale, v_scale=v_scale,
                                      softcap=softcap,
                                      interpret=interpret_mode(interpret))
