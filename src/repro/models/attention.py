"""Attention: GQA/MQA/MHA with RoPE/sinusoidal/none positions, global or
sliding-window masks, gemma2 soft-capping, prefix-LM, and KV caches.

Three entry modes:
  * train   — full self-attention over the sequence.
  * prefill — same math, additionally returns a KV cache (rolling buffer for
              local layers, dense buffer for global layers).
  * decode  — one new token against the cache; rolling writes for local
              layers use slot = pos % window, absolute slot positions are
              stored so masking is position-exact (stale slots masked out).

The O(S^2) materialization is avoided for long sequences with a doubly
chunked online-softmax ("flash in jnp") — ``lax.scan`` over query chunks with
an inner scan over key chunks. This is also the reference semantics for the
Pallas flash kernel in ``repro.kernels.flash_attention``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import NEG_INF, allow_mask, apply_rope, dense_init, softcap
from repro.models.config import LayerSpec, ModelConfig
from repro.parallel import logical


# ---------------------------------------------------------------------------
# params


def init_attention(key, cfg: ModelConfig, dtype):
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(kq, (d, H * hd), dtype=dtype),
        "wk": dense_init(kk, (d, KV * hd), dtype=dtype),
        "wv": dense_init(kv, (d, KV * hd), dtype=dtype),
        "wo": dense_init(ko, (H * hd, d), dtype=dtype),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
        p["bo"] = jnp.zeros((d,), dtype)
    return p


# ---------------------------------------------------------------------------
# core attention math (grouped GQA form)


def _direct_attention(q, k, v, q_pos, k_pos, *, window, prefix_len, cap, scale):
    """q: (B,Sq,KV,G,hd); k,v: (B,Sk,KV,hd); positions 1-D. Returns (B,Sq,KV,G,hd)."""
    logits = jnp.einsum("bqkgh,bskh->bkgqs", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if cap:
        logits = cap * jnp.tanh(logits / cap)
    ok = allow_mask(q_pos, k_pos, window=window, prefix_len=prefix_len)  # (Sq,Sk)
    logits = jnp.where(ok[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def _chunked_attention(q, k, v, q_pos, k_pos, *, window, prefix_len, cap, scale,
                       chunk_q, chunk_k, with_stats=False):
    """Online-softmax doubly-chunked attention. Shapes as _direct_attention.
    with_stats=True additionally returns the per-row (m, logsumexp-free l)
    needed by the recompute backward."""
    B, Sq, KV, G, hd = q.shape
    Sk = k.shape[1]
    cq = min(chunk_q, Sq)
    ck = min(chunk_k, Sk)
    assert Sq % cq == 0 and Sk % ck == 0, (Sq, cq, Sk, ck)
    nq, nk = Sq // cq, Sk // ck

    qc = q.reshape(B, nq, cq, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    qp = q_pos.reshape(nq, cq)
    kc = k.reshape(B, nk, ck, KV, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, ck, KV, hd).transpose(1, 0, 2, 3, 4)
    kp = k_pos.reshape(nk, ck)

    def q_body(_, qin):
        qi, qpi = qin  # (B,cq,KV,G,hd), (cq,)

        def k_body(carry, kin):
            m, l, acc = carry
            kj, vj, kpj = kin
            logits = jnp.einsum("bqkgh,bskh->bkgqs", qi, kj,
                                preferred_element_type=jnp.float32) * scale
            if cap:
                logits = cap * jnp.tanh(logits / cap)
            ok = allow_mask(qpi, kpj, window=window, prefix_len=prefix_len)
            logits = jnp.where(ok[None, None, None], logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(vj.dtype), vj,
                            preferred_element_type=jnp.float32)
            acc_new = acc * alpha[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, cq), jnp.float32)
        a0 = jnp.zeros((B, KV, G, cq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(k_body, (m0, l0, a0), (kc, vc, kp))
        l = jnp.maximum(l, 1e-37)  # fully-masked rows (can't happen causally) stay finite
        out = (acc / l[..., None]).astype(v.dtype)  # (B,KV,G,cq,hd)
        return None, (out.transpose(0, 3, 1, 2, 4), m, l)  # (B,cq,KV,G,hd)

    _, (out, m, l) = jax.lax.scan(q_body, None, (qc, qp))  # (nq,B,cq,KV,G,hd)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, KV, G, hd)
    if with_stats:
        # m,l: (nq,B,KV,G,cq) -> (B,KV,G,Sq)
        m = m.transpose(1, 2, 3, 0, 4).reshape(B, KV, G, Sq)
        l = l.transpose(1, 2, 3, 0, 4).reshape(B, KV, G, Sq)
        return out, m, l
    return out


# ---------------------------------------------------------------------------
# flash-style custom VJP (pure jnp): recompute backward, no O(S^2) residuals.
# This is the XLA-portable twin of repro.kernels.flash_attention — the
# backward re-derives per-block probabilities from (q,k,v,m,l) instead of
# saving them, removing the f32 probability tensors that dominate the
# baseline train/prefill memory and collective terms (EXPERIMENTS.md §Perf).


def _recompute_block(qi, kj, qpi, kpj, m_i, l_i, *, window, prefix_len, cap,
                     scale):
    """Recompute p_ij and the softcap jacobian factor for one block pair."""
    s_pre = jnp.einsum("bqkgh,bskh->bkgqs", qi, kj,
                       preferred_element_type=jnp.float32) * scale
    if cap:
        t = jnp.tanh(s_pre / cap)
        s = cap * t
        jac = 1.0 - t * t  # d softcap / d s_pre
    else:
        s = s_pre
        jac = None
    ok = allow_mask(qpi, kpj, window=window, prefix_len=prefix_len)
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    p = jnp.exp(s - m_i[..., None]) / l_i[..., None]
    return p, jac


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_jnp(q, k, v, q_pos, k_pos, window, prefix_len, cap, scale, cq, ck):
    return _chunked_attention(q, k, v, q_pos, k_pos, window=window,
                              prefix_len=prefix_len, cap=cap, scale=scale,
                              chunk_q=cq, chunk_k=ck)


def _flash_jnp_fwd(q, k, v, q_pos, k_pos, window, prefix_len, cap, scale,
                   cq, ck):
    out, m, l = _chunked_attention(q, k, v, q_pos, k_pos, window=window,
                                   prefix_len=prefix_len, cap=cap, scale=scale,
                                   chunk_q=cq, chunk_k=ck, with_stats=True)
    return out, (q, k, v, q_pos, k_pos, out, m, l)


def _flash_jnp_bwd(window, prefix_len, cap, scale, cq, ck, res, do):
    q, k, v, q_pos, k_pos, out, m, l = res
    B, Sq, KV, G, hd = q.shape
    Sk = k.shape[1]
    nq, nk = Sq // cq, Sk // ck
    qc = q.reshape(B, nq, cq, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    qp = q_pos.reshape(nq, cq)
    kc = k.reshape(B, nk, ck, KV, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, ck, KV, hd).transpose(1, 0, 2, 3, 4)
    kp = k_pos.reshape(nk, ck)
    doc = do.reshape(B, nq, cq, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    mc = m.reshape(B, KV, G, nq, cq).transpose(3, 0, 1, 2, 4)  # (nq,B,KV,G,cq)
    lc = l.reshape(B, KV, G, nq, cq).transpose(3, 0, 1, 2, 4)
    # D_i = rowsum(do_i * o_i): (B,Sq,KV,G) -> (nq,B,KV,G,cq)
    Df = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    Dc = Df.reshape(B, nq, cq, KV, G).transpose(1, 0, 3, 4, 2)

    def j_body(dq_acc, kin):
        kj, vj, kpj = kin

        def i_body(carry, iin):
            dk_j, dv_j = carry
            qi, qpi, doi, m_i, l_i, D_i = iin
            p, jac = _recompute_block(qi, kj, qpi, kpj, m_i, l_i,
                                      window=window, prefix_len=prefix_len,
                                      cap=cap, scale=scale)
            dp = jnp.einsum("bqkgh,bskh->bkgqs", doi.astype(jnp.float32),
                            vj.astype(jnp.float32))
            ds = p * (dp - D_i[..., None])
            if jac is not None:
                ds = ds * jac
            dq_i = jnp.einsum("bkgqs,bskh->bqkgh", ds, kj.astype(jnp.float32)) * scale
            dk_j = dk_j + jnp.einsum("bkgqs,bqkgh->bskh", ds,
                                     qi.astype(jnp.float32)) * scale
            dv_j = dv_j + jnp.einsum("bkgqs,bqkgh->bskh", p,
                                     doi.astype(jnp.float32))
            return (dk_j, dv_j), dq_i

        dk0 = jnp.zeros((B, ck, KV, hd), jnp.float32)
        dv0 = jnp.zeros((B, ck, KV, hd), jnp.float32)
        (dk_j, dv_j), dq_parts = jax.lax.scan(
            i_body, (dk0, dv0), (qc, qp, doc, mc, lc, Dc))
        dq_acc = dq_acc + dq_parts  # (nq,B,cq,KV,G,hd)
        return dq_acc, (dk_j, dv_j)

    dq0 = jnp.zeros((nq, B, cq, KV, G, hd), jnp.float32)
    dq, (dk, dv) = jax.lax.scan(j_body, dq0, (kc, vc, kp))
    dq = dq.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, KV, G, hd).astype(q.dtype)
    dk = dk.transpose(1, 0, 2, 3, 4).reshape(B, Sk, KV, hd).astype(k.dtype)
    dv = dv.transpose(1, 0, 2, 3, 4).reshape(B, Sk, KV, hd).astype(v.dtype)
    return dq, dk, dv, None, None


_flash_jnp.defvjp(_flash_jnp_fwd, _flash_jnp_bwd)


def _untileable(what: str):
    """Off the TPU a shape the kernels cannot tile takes the jnp path
    (returns None); on the TPU it is an error, so a run that asked for the
    kernels never measures the jnp path instead."""
    if jax.default_backend() == "tpu":
        raise ValueError(f"use_pallas: {what} does not tile the Pallas kernels")
    return None


def _pallas_attention(q, k, v, q_pos, k_pos, cfg, window):
    """Route through the Pallas kernels (repro.kernels). Shapes that don't
    tile return None off the TPU (caller takes the jnp path) and raise on
    it (see ``_untileable``)."""
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.flash_attention.ops import flash_attention

    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    if Sq == 1:  # decode against a cache
        bias = jnp.where(
            allow_mask(q_pos, k_pos, window=window, prefix_len=cfg.prefix_len)[0],
            0.0, NEG_INF).astype(jnp.float32)
        block_l = min(256, Sk)
        if Sk % block_l != 0:
            return _untileable(f"decode cache length {Sk}")
        o = decode_attention(q[:, 0].transpose(0, 1, 2), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), bias,
                             softcap=cfg.attn_softcap, block_l=block_l)
        return o[:, None]
    # full/prefill self-attention with positions 0..S-1
    bq = min(128, Sq)
    bk = min(128, Sk)
    if Sq % bq or Sk % bk or Sq != Sk:
        return _untileable(f"self-attention over Sq={Sq}, Sk={Sk}")
    o = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True, window=window, softcap=cfg.attn_softcap,
        prefix_len=cfg.prefix_len, block_q=bq, block_k=bk)
    return o.transpose(0, 2, 1, 3)


def grouped_attention(q, k, v, q_pos, k_pos, cfg: ModelConfig, spec: LayerSpec):
    """Dispatch direct vs chunked. q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    window = cfg.window_size if spec.attn_type == "local" else 0
    if cfg.use_pallas:
        out = _pallas_attention(q, k, v, q_pos, k_pos, cfg, window)
        if out is not None:
            return out
    cap = cfg.attn_softcap
    scale = hd**-0.5
    qg = q.reshape(B, Sq, KV, G, hd)
    kwargs = dict(window=window, prefix_len=cfg.prefix_len, cap=cap, scale=scale)
    Sk = k.shape[1]
    chunkable = Sq % min(cfg.attn_chunk_q, Sq) == 0 and Sk % min(cfg.attn_chunk_k, Sk) == 0
    if Sq <= cfg.attn_chunk_q and Sk <= cfg.attn_chunk_k:
        out = _direct_attention(qg, k, v, q_pos, k_pos, **kwargs)
    elif Sq == 1 or not chunkable:
        out = _direct_attention(qg, k, v, q_pos, k_pos, **kwargs)
    elif cfg.flash_vjp:
        out = _flash_jnp(qg, k, v, q_pos, k_pos, window, cfg.prefix_len, cap,
                         scale, min(cfg.attn_chunk_q, Sq), min(cfg.attn_chunk_k, Sk))
    else:
        out = _chunked_attention(qg, k, v, q_pos, k_pos, **kwargs,
                                 chunk_q=cfg.attn_chunk_q, chunk_k=cfg.attn_chunk_k)
    return out.reshape(B, Sq, H, hd)


# ---------------------------------------------------------------------------
# qkv projection / output


def _project(p, x, cfg: ModelConfig):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.use_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    return q, k, v


def _out(p, o, cfg: ModelConfig):
    B, S = o.shape[:2]
    y = o.reshape(B, S, -1) @ p["wo"]
    if cfg.use_bias:
        y = y + p["bo"]
    return y


# ---------------------------------------------------------------------------
# caches


def cache_len_for(cfg: ModelConfig, spec: LayerSpec, max_len: int) -> int:
    if spec.attn_type == "local" and cfg.window_size and cfg.window_size < max_len:
        return cfg.window_size
    return max_len


def init_cache_entry(cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int):
    L = cache_len_for(cfg, spec, max_len)
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    return {
        "k": jnp.zeros((batch, L, KV, hd), dt),
        "v": jnp.zeros((batch, L, KV, hd), dt),
        "pos": jnp.full((L,), -1, jnp.int32),
    }


def init_paged_entry(cfg: ModelConfig, spec: LayerSpec, n_phys_blocks: int,
                     block_size: int, quant: Optional[str] = None):
    """One layer's paged KV pool: a shared pool of ``n_phys_blocks`` blocks of
    ``block_size`` positions each (repro.runtime.paging owns the block ids).

    Logical cache slot ``s`` of a sequence lives at physical block
    ``page_table[s // block_size]``, offset ``s % block_size`` — the same
    ``slot = pos % L`` rolling invariant as the dense cache, just indirected
    through the table. K/V are kv-head-major, ``(n_phys_blocks, KV,
    block_size, hd)``, so one head's block is the dense ``(block_size, hd)``
    tile the paged kernel loads. ``pos`` is stored per (block, offset) so
    gathering a table row reproduces a dense cache entry bit-for-bit
    (NULL-block tail included: zeros with pos=-1). ``quant="int8"`` stores
    K/V int8 with rowwise (over hd) f32 scales
    (optim.compress.quantize_int8 layout).
    """
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    dt = jnp.int8 if quant == "int8" else jnp.dtype(cfg.dtype)
    entry = {
        "k": jnp.zeros((n_phys_blocks, KV, block_size, hd), dt),
        "v": jnp.zeros((n_phys_blocks, KV, block_size, hd), dt),
        "pos": jnp.full((n_phys_blocks, block_size), -1, jnp.int32),
    }
    if quant == "int8":
        entry["k_scale"] = jnp.zeros((n_phys_blocks, KV, block_size, 1), jnp.float32)
        entry["v_scale"] = jnp.zeros((n_phys_blocks, KV, block_size, 1), jnp.float32)
    return entry


# ---------------------------------------------------------------------------
# layer entry points (x is already normed; residual handled by caller)


def attn_train(p, x, cfg: ModelConfig, spec: LayerSpec, positions):
    q, k, v = _project(p, x, cfg)
    if cfg.pos_type == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = logical(q, "batch", "act_seq", "heads", None)
    k = logical(k, "batch", "act_kv_seq", "kv_heads", None)
    v = logical(v, "batch", "act_kv_seq", "kv_heads", None)
    o = grouped_attention(q, k, v, positions, positions, cfg, spec)
    o = logical(o, "batch", "act_seq", "heads", None)
    return _out(p, o, cfg)


def attn_prefill(p, x, cfg: ModelConfig, spec: LayerSpec, positions, max_len=None,
                 true_len=None):
    """Returns (y, cache_entry). Cache stores RoPE'd keys at absolute slots.

    ``max_len`` sizes the cache for subsequent decoding (>= S); global layers
    pad to max_len (empty slots carry pos=-1 and are masked), local layers
    keep a rolling window.

    ``true_len`` (traced scalar) marks a right-padded prompt: the sequence is
    a length-``S`` bucket whose tokens beyond ``true_len`` are padding. Keys
    are position-local (projection + RoPE of the token's own embedding), so
    the cache at real positions is bit-identical to an exact-length prefill;
    pad positions get pos=-1 and are masked out of every later decode step.
    Requires ``cfg.prefix_len == 0`` (a bidirectional prefix would let pad
    keys leak into real queries — the batcher guards this)."""
    B, S, _ = x.shape
    max_len = max_len or S
    q, k, v = _project(p, x, cfg)
    if cfg.pos_type == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = logical(q, "batch", "act_seq", "heads", None)
    k = logical(k, "batch", "act_kv_seq", "kv_heads", None)
    v = logical(v, "batch", "act_kv_seq", "kv_heads", None)
    o = grouped_attention(q, k, v, positions, positions, cfg, spec)
    o = logical(o, "batch", "act_seq", "heads", None)
    y = _out(p, o, cfg)

    L = cache_len_for(cfg, spec, max_len)
    if true_len is not None:
        ck, cv, cpos = _padded_prefill_cache(k, v, positions, L, true_len)
    elif L == S:
        ck, cv, cpos = k, v, positions.astype(jnp.int32)
    elif L > S:
        pad = [(0, 0), (0, L - S), (0, 0), (0, 0)]
        ck = jnp.pad(k, pad)
        cv = jnp.pad(v, pad)
        cpos = jnp.pad(positions.astype(jnp.int32), (0, L - S), constant_values=-1)
    else:
        # rolling buffer invariant: slot = pos % L; roll so last-L keys land
        # on their slots.
        shift = (S - L) % L
        ck = jnp.roll(k[:, S - L:], shift, axis=1)
        cv = jnp.roll(v[:, S - L:], shift, axis=1)
        cpos = jnp.roll(positions[S - L:].astype(jnp.int32), shift, axis=0)
    cache = {
        "k": logical(ck, "batch", "cache_len", "kv_heads", None),
        "v": logical(cv, "batch", "cache_len", "kv_heads", None),
        "pos": cpos,
    }
    return y, cache


def _padded_prefill_cache(k, v, positions, L, true_len):
    """Cache entry from a right-padded (bucketed) prefill of true length
    ``true_len``: reproduce what the exact-length prefill would have stored.

    Valid positions keep their keys; everything else carries pos=-1. For a
    rolling window (L < S) slot ``c`` holds the last real position ``p <
    true_len`` with ``p % L == c`` — gathered from the padded sequence rather
    than rolled, so pad tokens never evict real keys."""
    B, S = k.shape[:2]
    pos32 = positions.astype(jnp.int32)
    if L >= S:
        idx = jnp.arange(S, dtype=jnp.int32)
        cpos = jnp.where(idx < true_len, pos32, -1)
        if L > S:
            pad = [(0, 0), (0, L - S), (0, 0), (0, 0)]
            k = jnp.pad(k, pad)
            v = jnp.pad(v, pad)
            cpos = jnp.pad(cpos, (0, L - S), constant_values=-1)
        return k, v, cpos
    c = jnp.arange(L, dtype=jnp.int32)
    src = true_len - L + jnp.mod(c - true_len, L)  # last p < true_len, p%L==c
    valid = src >= 0
    safe = jnp.clip(src, 0, S - 1)
    ck = jnp.take(k, safe, axis=1)
    cv = jnp.take(v, safe, axis=1)
    cpos = jnp.where(valid, src, -1)
    return ck, cv, cpos


def attn_decode(p, x, cache, cfg: ModelConfig, spec: LayerSpec, pos):
    """x: (B,1,d); pos: scalar int32 absolute position. Returns (y, cache')."""
    B = x.shape[0]
    q, k, v = _project(p, x, cfg)  # (B,1,H,hd), (B,1,KV,hd)
    qpos = pos[None] if pos.ndim == 0 else pos
    if cfg.pos_type == "rope":
        q = apply_rope(q, qpos, cfg.rope_theta)
        k = apply_rope(k, qpos, cfg.rope_theta)
    L = cache["k"].shape[1]
    slot = jnp.mod(pos, L)
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    cpos = jax.lax.dynamic_update_slice(cache["pos"], qpos.astype(jnp.int32), (slot,))
    ck = logical(ck, "batch", "cache_len", "kv_heads", None)
    cv = logical(cv, "batch", "cache_len", "kv_heads", None)
    o = grouped_attention(q, ck, cv, qpos, cpos, cfg, spec)
    y = _out(p, o, cfg)
    return y, {"k": ck, "v": cv, "pos": cpos}


# ---------------------------------------------------------------------------
# paged decode: slot-batched decode against a shared block pool


def _paged_attention_jnp(qg, k, v, q_pos, k_pos, *, window, prefix_len, cap,
                         scale):
    """Batched-positions twin of ``_direct_attention`` for paged decode.

    qg: (B,1,KV,G,hd); k,v: (B,L,KV,hd); q_pos: (B,1); k_pos: (B,L). The
    einsum/softmax structure is identical to ``_direct_attention`` (same
    contraction order over hd and L), so a slot-batched paged step matches
    the dense engine's per-slot vmapped step bit-for-bit — only the mask is
    per-sequence instead of shared."""
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                        preferred_element_type=jnp.float32)
    logits = logits * scale
    if cap:
        logits = cap * jnp.tanh(logits / cap)
    ok = allow_mask(q_pos, k_pos, window=window, prefix_len=prefix_len)  # (B,1,L)
    logits = jnp.where(ok[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def attn_decode_paged(p, x, pool, cfg: ModelConfig, spec: LayerSpec, pos_vec,
                      pages):
    """Slot-batched decode step against this layer's paged KV pool.

    x: (B,1,d) — one new token per slot; pos_vec: (B,) int32 per-slot
    absolute positions; pages: (B, P_global) int32 page-table rows (shared
    across layers — a local layer uses only its first ``window//block_size``
    logical pages, because its rolling slot ``pos % window`` never leaves
    them). Returns (y, pool').

    The new K/V land at logical slot ``s = pos % L`` → physical
    ``(pages[s // bs], s % bs)``. Every slot writes unconditionally (static
    shapes — same as the dense engine); the runtime points inactive slots'
    rows at the shared TRASH block so their garbage writes are never read.
    With ``cfg.use_pallas`` the attention runs in the paged Pallas kernel
    (gather inside the kernel, each slot walking only the pages its
    position reaches: ``live_pages``); otherwise the pool is gathered to a
    dense (B,L) cache and fed through the jnp path (the oracle semantics).
    """
    from repro.kernels.decode_attention.ops import (live_pages,
                                                    paged_decode_attention)
    from repro.optim.compress import dequantize_int8, quantize_int8

    B = x.shape[0]
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    H = cfg.num_heads
    G = H // KV
    bs = pool["k"].shape[2]
    max_len = pages.shape[1] * bs
    L = cache_len_for(cfg, spec, max_len)
    P = L // bs
    window = cfg.window_size if spec.attn_type == "local" else 0
    quantized = "k_scale" in pool

    q, k, v = _project(p, x, cfg)  # (B,1,H,hd), (B,1,KV,hd)
    qpos = pos_vec[:, None]  # (B,1)
    if cfg.pos_type == "rope":
        q = apply_rope(q, qpos, cfg.rope_theta)
        k = apply_rope(k, qpos, cfg.rope_theta)

    with jax.named_scope("kv_write"):
        s = jnp.mod(pos_vec, L)
        blk = jnp.take_along_axis(pages, (s // bs)[:, None], axis=1)[:, 0]  # (B,)
        off = s % bs
        newk, newv = k[:, 0], v[:, 0]  # (B,KV,hd)
        pool = dict(pool)
        # [blk, :, off] addresses (B, KV, hd) in the kv-head-major pool
        if quantized:
            qk, ksc = quantize_int8(newk)
            qv, vsc = quantize_int8(newv)
            pool["k"] = pool["k"].at[blk, :, off].set(qk)
            pool["v"] = pool["v"].at[blk, :, off].set(qv)
            pool["k_scale"] = pool["k_scale"].at[blk, :, off].set(ksc)
            pool["v_scale"] = pool["v_scale"].at[blk, :, off].set(vsc)
        else:
            pool["k"] = pool["k"].at[blk, :, off].set(
                newk.astype(pool["k"].dtype))
            pool["v"] = pool["v"].at[blk, :, off].set(
                newv.astype(pool["v"].dtype))
        pool["pos"] = pool["pos"].at[blk, off].set(pos_vec.astype(jnp.int32))

    tbl = pages[:, :P]  # (B,P)
    with jax.named_scope("kv_mask"):
        cpos = pool["pos"][tbl].reshape(B, L)
        if cfg.use_pallas:
            bias = jnp.where(
                allow_mask(qpos, cpos, window=window,
                           prefix_len=cfg.prefix_len),
                0.0, NEG_INF).astype(jnp.float32)[:, 0]  # (B,L)
    with jax.named_scope("paged_attention"):
        if cfg.use_pallas:
            o = paged_decode_attention(
                q[:, 0], pool["k"], pool["v"], tbl, bias,
                n_pages=live_pages(pos_vec, bs, P),
                k_scale=pool.get("k_scale"), v_scale=pool.get("v_scale"),
                softcap=cfg.attn_softcap)
            o = o[:, None]  # (B,1,H,hd)
        else:
            def gather(leaf):  # (B,P,KV,bs,x) -> dense (B,L,KV,x)
                g = leaf[tbl].swapaxes(2, 3)
                return g.reshape(B, L, KV, g.shape[-1])

            ck, cv = gather(pool["k"]), gather(pool["v"])
            if quantized:
                ck = dequantize_int8(ck, gather(pool["k_scale"]))
                cv = dequantize_int8(cv, gather(pool["v_scale"]))
            o = _paged_attention_jnp(
                q.reshape(B, 1, KV, G, hd), ck, cv, qpos, cpos,
                window=window, prefix_len=cfg.prefix_len,
                cap=cfg.attn_softcap, scale=hd**-0.5)
            o = o.reshape(B, 1, H, hd)
    y = _out(p, o, cfg)
    return y, pool
