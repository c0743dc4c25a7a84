"""Mamba selective scan — Pallas TPU kernel, chunked over time and blocked
over channels.

Same TPU adaptation as rwkv6_scan: the (d_block, N) f32 state stays resident
in VMEM scratch across the sequential time-chunk grid dimension instead of
round-tripping HBM per step (the jnp path's dominant cost — see the jamba
dry-run cells). Channels are embarrassingly parallel (d_inner is TP-sharded
one level up), so the channel-block grid dim is parallel and the kernel
vectorizes each timestep over (d_block, N) VPU lanes.

Grid: (B, n_d_blocks, n_chunks) — innermost sequential over time.
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


_BWD_HIST_BYTES = 8 << 20


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref, y_ref, hT_ref,
            h_scr, *, chunk, n_chunks, hstart_ref=None):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    if hstart_ref is not None:  # chunk-start checkpoint (training)
        hstart_ref[0, 0] = h_scr[...]

    A = a_ref[...].astype(jnp.float32)  # (bd, N)
    Dk = d_ref[0].astype(jnp.float32)  # (bd,)

    def step(t, h):
        x_t = x_ref[0, t, :].astype(jnp.float32)  # (bd,)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)  # (N,)
        c_t = c_ref[0, t, :].astype(jnp.float32)
        da = jnp.exp(dt_t[:, None] * A)  # (bd, N)
        h = da * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_t = jnp.sum(h * c_t[None, :], axis=1) + Dk * x_t
        y_ref[0, t, :] = y_t.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h

    @pl.when(ic == n_chunks - 1)
    def _out():
        hT_ref[0] = h


def ssm_scan_fwd(x, dt, A, Bc, Cc, D, h0, *, chunk=64, block_d=512,
                 interpret=False, save_states=False):
    """x, dt: (B,S,Di); Bc,Cc: (B,S,N); A: (Di,N); D: (Di,); h0: (B,Di,N).

    save_states=True also returns per-chunk start states
    (B, n_chunks, Di, N) for the backward kernel. ``D`` rides in as a
    (1, Di) row: a 1-D f32 block would need a tile the TPU's 1-D layout
    (T(1024)) does not give below 1024 elements."""
    B, S, Di = x.shape
    D = D.reshape(1, Di)
    N = A.shape[1]
    c = min(chunk, S)
    bd = min(block_d, Di)
    assert S % c == 0 and Di % bd == 0, (S, c, Di, bd)
    n_chunks = S // c
    n_d = Di // bd

    xd_spec = pl.BlockSpec((1, c, bd), lambda b, d, i: (b, i, d))
    bn_spec = pl.BlockSpec((1, c, N), lambda b, d, i: (b, i, 0))
    out_specs = [xd_spec, pl.BlockSpec((1, bd, N), lambda b, d, i: (b, d, 0))]
    out_shape = [
        jax.ShapeDtypeStruct((B, S, Di), jnp.float32),
        jax.ShapeDtypeStruct((B, Di, N), jnp.float32),
    ]
    if save_states:
        def kern(x_, dt_, a_, b_, c_, d_, h0_, y_, hT_, hst_, h_scr):
            _kernel(x_, dt_, a_, b_, c_, d_, h0_, y_, hT_, h_scr,
                    chunk=c, n_chunks=n_chunks, hstart_ref=hst_)

        out_specs = out_specs + [
            pl.BlockSpec((1, 1, bd, N), lambda b, d, i: (b, i, d, 0))]
        out_shape = out_shape + [
            jax.ShapeDtypeStruct((B, n_chunks, Di, N), jnp.float32)]
    else:
        kern = functools.partial(_kernel, chunk=c, n_chunks=n_chunks)
    outs = pl.pallas_call(
        kern,
        grid=(B, n_d, n_chunks),
        in_specs=[
            xd_spec,  # x
            xd_spec,  # dt
            pl.BlockSpec((bd, N), lambda b, d, i: (d, 0)),  # A
            bn_spec,  # B
            bn_spec,  # C
            pl.BlockSpec((1, bd), lambda b, d, i: (0, d)),  # D
            pl.BlockSpec((1, bd, N), lambda b, d, i: (b, d, 0)),  # h0
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[_VMEM((bd, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, Bc, Cc, D, h0)
    return outs


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, dy_ref, hstart_ref,
                dhT_ref, dx_ref, ddt_ref, da_ref, db_ref, dc_ref, dd_ref,
                dh0_ref, g_scr, hist_scr, *, chunk, n_chunks):
    """Reverse-chunk backward: rewind h history from the chunk checkpoint,
    then run g_{t-1} = da_t o g_t with per-step grads (see ops.py docstring
    for the derivation)."""
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        g_scr[...] = dhT_ref[0].astype(jnp.float32)

    A = a_ref[...].astype(jnp.float32)  # (bd, N)
    Dk = d_ref[0].astype(jnp.float32)  # (bd,)

    def fstep(t, h):
        hist_scr[t] = h  # h_{t-1}
        x_t = x_ref[0, t, :].astype(jnp.float32)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)
        da = jnp.exp(dt_t[:, None] * A)
        return da * h + (dt_t * x_t)[:, None] * b_t[None, :]

    jax.lax.fori_loop(0, chunk, fstep, hstart_ref[0, 0].astype(jnp.float32))

    bd, N = g_scr.shape

    def bstep(tt, carry):
        g, dA_acc, dD_acc = carry
        t = chunk - 1 - tt
        x_t = x_ref[0, t, :].astype(jnp.float32)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)
        c_t = c_ref[0, t, :].astype(jnp.float32)
        dy_t = dy_ref[0, t, :].astype(jnp.float32)
        h_pre = hist_scr[t]  # h_{t-1}
        da = jnp.exp(dt_t[:, None] * A)
        h_t = da * h_pre + (dt_t * x_t)[:, None] * b_t[None, :]
        g = g + dy_t[:, None] * c_t[None, :]  # y_t uses h_t
        gh = g * h_pre * da
        ddt = jnp.sum(gh * A, axis=1) + x_t * jnp.sum(g * b_t[None, :], axis=1)
        dx = dt_t * jnp.sum(g * b_t[None, :], axis=1) + Dk * dy_t
        db = jnp.sum(g * (dt_t * x_t)[:, None], axis=0)
        dc = jnp.sum(dy_t[:, None] * h_t, axis=0)
        dA_acc = dA_acc + gh * dt_t[:, None]
        dD_acc = dD_acc + dy_t * x_t
        dx_ref[0, t, :] = dx.astype(dx_ref.dtype)
        ddt_ref[0, t, :] = ddt.astype(ddt_ref.dtype)
        db_ref[0, 0, t, :] = db.astype(db_ref.dtype)
        dc_ref[0, 0, t, :] = dc.astype(dc_ref.dtype)
        g = da * g  # propagate to h_{t-1}
        return g, dA_acc, dD_acc

    g, dA_acc, dD_acc = jax.lax.fori_loop(
        0, chunk, bstep,
        (g_scr[...], jnp.zeros((bd, N), jnp.float32),
         jnp.zeros((bd,), jnp.float32)))
    g_scr[...] = g
    da_ref[0, 0] = dA_acc
    dd_ref[0, 0, 0] = dD_acc

    @pl.when(ic == n_chunks - 1)
    def _dh0():
        dh0_ref[0] = g


def ssm_scan_bwd(x, dt, A, Bc, Cc, D, dy, h_starts, dhT, *, chunk=64,
                 block_d=512, interpret=False):
    """Returns (dx, ddt, dA_chunks, dB, dC, dD_chunks, dh0)."""
    B, S, Di = x.shape
    D = D.reshape(1, Di)
    N = A.shape[1]
    c = min(chunk, S)
    bd = min(block_d, Di)
    # The rewind history (c, bd, N) f32 sits in VMEM with N padded to the
    # 128 lanes; halve the channel block until it fits half the TPU's
    # 16 MiB scoped VMEM, keeping it a multiple of the 128 lanes (the
    # forward's block_d may be larger: h_starts are blocked over channels
    # independently).
    while c * bd * max(N, 128) * 4 > _BWD_HIST_BYTES and bd % 256 == 0:
        bd //= 2
    n_chunks = S // c
    n_d = Di // bd
    rev_i = lambda i: n_chunks - 1 - i
    xd_spec = pl.BlockSpec((1, c, bd), lambda b, d, i: (b, rev_i(i), d))
    bn_spec = pl.BlockSpec((1, c, N), lambda b, d, i: (b, rev_i(i), 0))
    kern = functools.partial(_bwd_kernel, chunk=c, n_chunks=n_chunks)
    outs = pl.pallas_call(
        kern,
        grid=(B, n_d, n_chunks),
        in_specs=[
            xd_spec, xd_spec,
            pl.BlockSpec((bd, N), lambda b, d, i: (d, 0)),  # A
            bn_spec, bn_spec,
            pl.BlockSpec((1, bd), lambda b, d, i: (0, d)),  # D
            xd_spec,  # dy
            pl.BlockSpec((1, 1, bd, N), lambda b, d, i: (b, rev_i(i), d, 0)),
            pl.BlockSpec((1, bd, N), lambda b, d, i: (b, d, 0)),  # dhT
        ],
        out_specs=[
            xd_spec,  # dx
            xd_spec,  # ddt
            pl.BlockSpec((1, 1, bd, N), lambda b, d, i: (b, rev_i(i), d, 0)),
            # dB/dC are per-d-block partials (summed over axis 1 in ops)
            pl.BlockSpec((1, 1, c, N), lambda b, d, i: (b, d, rev_i(i), 0)),
            pl.BlockSpec((1, 1, c, N), lambda b, d, i: (b, d, rev_i(i), 0)),
            pl.BlockSpec((1, 1, 1, bd), lambda b, d, i: (b, rev_i(i), 0, d)),
            pl.BlockSpec((1, bd, N), lambda b, d, i: (b, d, 0)),  # dh0
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, Di), x.dtype),
            jax.ShapeDtypeStruct((B, S, Di), jnp.float32),
            jax.ShapeDtypeStruct((B, n_chunks, Di, N), jnp.float32),
            jax.ShapeDtypeStruct((B, n_d, S, N), jnp.float32),
            jax.ShapeDtypeStruct((B, n_d, S, N), jnp.float32),
            jax.ShapeDtypeStruct((B, n_chunks, 1, Di), jnp.float32),
            jax.ShapeDtypeStruct((B, Di, N), jnp.float32),
        ],
        scratch_shapes=[_VMEM((bd, N), jnp.float32),
                        _VMEM((c, bd, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, Bc, Cc, D, dy, h_starts, dhT)
    dx, ddt, dA_chunks, dB_p, dC_p, dD_chunks, dh0 = outs
    return dx, ddt, dA_chunks, dB_p, dC_p, dD_chunks[:, :, 0], dh0
