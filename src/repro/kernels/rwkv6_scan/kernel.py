"""RWKV-6 WKV recurrence — Pallas TPU kernel, chunked over time.

Why a kernel: the jnp ``lax.scan`` path round-trips the (hd x hd) f32 state
through HBM on *every timestep* (the dry-run shows rwkv6-3b train at ~1.4e16
HBM bytes/device — 3 orders above the compute roofline). GPU implementations
parallelize with log-depth inter-chunk scans; the TPU-native adaptation keeps
the state **resident in VMEM scratch across the sequential chunk grid** — one
HBM read of r/k/v/w per element, one HBM write of y, state traffic zero.

Grid: (B, H, n_chunks) — innermost sequential over time chunks; the chunk's
timesteps run in a ``fori_loop`` of VPU outer-product updates (the
data-dependent per-channel decay prevents an MXU matmul form without
numerically-unstable pairwise exp rescaling; see DESIGN.md §4).
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


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref, s_scr,
            *, chunk, n_chunks, sstart_ref=None):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    if sstart_ref is not None:  # chunk-start state checkpoint (training)
        sstart_ref[0, 0, 0] = s_scr[...]

    def step(t, s):
        r_t = r_ref[0, 0, t, :].astype(jnp.float32)  # (hd,)
        k_t = k_ref[0, 0, t, :].astype(jnp.float32)
        v_t = v_ref[0, 0, t, :].astype(jnp.float32)
        w_t = w_ref[0, 0, t, :].astype(jnp.float32)
        u = u_ref[0, 0].astype(jnp.float32)
        kv = k_t[:, None] * v_t[None, :]  # (hd_k, hd_v)
        y_t = jnp.sum(r_t[:, None] * (s + u[:, None] * kv), axis=0)
        y_ref[0, 0, t, :] = y_t.astype(y_ref.dtype)
        return w_t[:, None] * s + kv

    s = jax.lax.fori_loop(0, chunk, step, s_scr[...])
    s_scr[...] = s

    @pl.when(ic == n_chunks - 1)
    def _out():
        sT_ref[0, 0] = s


def rwkv6_scan_fwd(r, k, v, w, u, s0, *, chunk=64, interpret=False,
                   save_states=False):
    """r,k,v,w: (B,H,S,hd); u: (H,hd); s0: (B,H,hd,hd) f32.

    save_states=True additionally returns the per-chunk start states
    (B,H,n_chunks,hd,hd) — the checkpoints the backward kernel rewinds from.
    ``u`` rides in as (H, 1, hd) so its block's last two dimensions equal
    the array's (the TPU tiling refuses a (1, hd) block of (H, hd)).
    """
    B, H, S, hd = r.shape
    u = u.reshape(H, 1, hd)
    c = min(chunk, S)
    assert S % c == 0, (S, c)
    n_chunks = S // c
    seq_spec = pl.BlockSpec((1, 1, c, hd), lambda b, h, i: (b, h, i, 0))
    state_spec = pl.BlockSpec((1, 1, hd, hd), lambda b, h, i: (b, h, 0, 0))
    out_specs = [seq_spec, state_spec]
    out_shape = [
        jax.ShapeDtypeStruct((B, H, S, hd), jnp.float32),
        jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
    ]
    if save_states:
        def kern(r_, k_, v_, w_, u_, s0_, y_, sT_, sst_, s_scr):
            _kernel(r_, k_, v_, w_, u_, s0_, y_, sT_, s_scr,
                    chunk=c, n_chunks=n_chunks, sstart_ref=sst_)

        out_specs = out_specs + [
            pl.BlockSpec((1, 1, 1, hd, hd), lambda b, h, i: (b, h, i, 0, 0))]
        out_shape = out_shape + [
            jax.ShapeDtypeStruct((B, H, n_chunks, hd, hd), jnp.float32)]
    else:
        kern = functools.partial(_kernel, chunk=c, n_chunks=n_chunks)
    outs = pl.pallas_call(
        kern,
        grid=(B, H, n_chunks),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, 1, hd), lambda b, h, i: (h, 0, 0)),
                  state_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[_VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u, s0)
    return outs


def _bwd_kernel(r_ref, k_ref, v_ref, w_ref, dy_ref, u_ref, sstart_ref,
                dsT_ref, dr_ref, dk_ref, dv_ref, dw_ref, du_ref, ds0_ref,
                g_scr, hist_scr, *, chunk, n_chunks):
    """Reverse-chunk backward pass.

    Grid iterates chunks in REVERSE (index maps flip the chunk index). Per
    chunk: rewind the forward from the saved chunk-start state into VMEM
    history (hist[t] = S_{t-1}), then run the reverse recurrence
        G_{t-1} = w_t o G_t + r_t (x) dy_t
    emitting dr/dk/dv/dw rows and accumulating du.
    """
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        g_scr[...] = dsT_ref[0, 0].astype(jnp.float32)

    u = u_ref[0, 0].astype(jnp.float32)

    def fstep(t, s):
        hist_scr[t] = s
        k_t = k_ref[0, 0, t, :].astype(jnp.float32)
        v_t = v_ref[0, 0, t, :].astype(jnp.float32)
        w_t = w_ref[0, 0, t, :].astype(jnp.float32)
        return w_t[:, None] * s + k_t[:, None] * v_t[None, :]

    jax.lax.fori_loop(0, chunk, fstep, sstart_ref[0, 0, 0].astype(jnp.float32))

    hd = g_scr.shape[-1]

    def bstep(tt, carry):
        g, du = carry
        t = chunk - 1 - tt
        r_t = r_ref[0, 0, t, :].astype(jnp.float32)
        k_t = k_ref[0, 0, t, :].astype(jnp.float32)
        v_t = v_ref[0, 0, t, :].astype(jnp.float32)
        w_t = w_ref[0, 0, t, :].astype(jnp.float32)
        dy_t = dy_ref[0, 0, t, :].astype(jnp.float32)
        s_pre = hist_scr[t]  # S_{t-1}
        dyv = jnp.sum(dy_t * v_t)
        dr = jnp.sum(s_pre * dy_t[None, :], axis=1) + u * k_t * dyv
        dk = jnp.sum(g * v_t[None, :], axis=1) + u * r_t * dyv
        dv = jnp.sum(g * k_t[:, None], axis=0) + jnp.sum(r_t * u * k_t) * dy_t
        dw = jnp.sum(g * s_pre, axis=1)
        du_new = du + r_t * k_t * dyv
        dr_ref[0, 0, t, :] = dr.astype(dr_ref.dtype)
        dk_ref[0, 0, t, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0, t, :] = dv.astype(dv_ref.dtype)
        dw_ref[0, 0, t, :] = dw.astype(dw_ref.dtype)
        g = w_t[:, None] * g + r_t[:, None] * dy_t[None, :]
        return g, du_new

    g, du = jax.lax.fori_loop(0, chunk, bstep,
                              (g_scr[...], jnp.zeros((hd,), jnp.float32)))
    g_scr[...] = g
    du_ref[0, 0, 0, 0, :] = du

    @pl.when(ic == n_chunks - 1)
    def _ds0():
        ds0_ref[0, 0] = g


def rwkv6_scan_bwd(r, k, v, w, dy, u, s_starts, dsT, *, chunk=64,
                   interpret=False):
    """Returns (dr, dk, dv, dw, du_chunks (B,H,nc,hd), ds0)."""
    B, H, S, hd = r.shape
    u = u.reshape(H, 1, hd)
    c = min(chunk, S)
    n_chunks = S // c
    rev = lambda b, h, i: (b, h, n_chunks - 1 - i, 0)
    seq_spec = pl.BlockSpec((1, 1, c, hd), rev)
    state_spec = pl.BlockSpec((1, 1, hd, hd), lambda b, h, i: (b, h, 0, 0))
    kern = functools.partial(_bwd_kernel, chunk=c, n_chunks=n_chunks)
    outs = pl.pallas_call(
        kern,
        grid=(B, H, n_chunks),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, 1, hd), lambda b, h, i: (h, 0, 0)),
                  pl.BlockSpec((1, 1, 1, hd, hd),
                               lambda b, h, i: (b, h, n_chunks - 1 - i, 0, 0)),
                  state_spec],
        out_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                   pl.BlockSpec((1, 1, 1, 1, hd),
                                lambda b, h, i: (b, h, n_chunks - 1 - i, 0, 0)),
                   state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), r.dtype),
            jax.ShapeDtypeStruct((B, H, S, hd), k.dtype),
            jax.ShapeDtypeStruct((B, H, S, hd), v.dtype),
            jax.ShapeDtypeStruct((B, H, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, n_chunks, 1, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[_VMEM((hd, hd), jnp.float32),
                        _VMEM((c, hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, dy, u, s_starts, dsT)
    dr, dk, dv, dw, du_chunks, ds0 = outs
    return dr, dk, dv, dw, du_chunks[:, :, :, 0], ds0
