"""Pure-jnp oracle for single-token decode attention against a KV cache.

q: (B, H, hd) — one new token per sequence.
k, v: (B, KV, L, hd) — cache (RoPE'd keys at absolute slots).
bias: (L,) additive f32 mask (0 = attend, NEG_INF = blocked) — precomputed by
the caller from cache slot positions (covers rolling-window staleness,
unwritten slots and sliding windows uniformly).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def decode_attention_ref(q, k, v, bias, *, softcap=0.0):
    B, H, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    scale = hd**-0.5
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bklh->bkgl", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = s + bias[None, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgl,bklh->bkgh", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, hd).astype(q.dtype)


def paged_decode_attention_ref(q, k_pages, v_pages, page_table, bias, *,
                               k_scale=None, v_scale=None, softcap=0.0):
    """Pure-jnp oracle for the paged kernel: gather the per-sequence cache
    through the page table, (optionally) dequantize int8 pools, then run the
    same masked softmax-attention as ``decode_attention_ref`` with a
    per-sequence bias.

    q: (B,H,hd); k_pages/v_pages: (n_phys, KV, bs, hd); page_table: (B,P)
    int32; bias: (B, P*bs) f32; k_scale/v_scale: (n_phys, KV, bs, 1) f32.
    """
    B, H, hd = q.shape
    n_phys, KV, bs, _ = k_pages.shape
    P = page_table.shape[1]
    L = P * bs
    k = k_pages[page_table]  # (B, P, KV, bs, hd)
    v = v_pages[page_table]
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[page_table]
        v = v.astype(jnp.float32) * v_scale[page_table]
    k = k.swapaxes(2, 3).reshape(B, L, KV, hd)
    v = v.swapaxes(2, 3).reshape(B, L, KV, hd)
    G = H // KV
    scale = hd**-0.5
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,blkh->bkgl", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = s + bias[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgl,blkh->bkgh", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, hd).astype(q.dtype)
