"""Public RWKV-6 WKV op.

Training-complete kernel pair: the forward kernel checkpoints chunk-start
states; the backward kernel rewinds each chunk from its checkpoint inside
VMEM and runs the reverse recurrence
    G_{t-1} = w_t o G_t + r_t (x) dy_t
so neither pass materializes per-step states in HBM. ``bwd_impl="ref"``
falls back to differentiating the jnp oracle (used by tests to cross-check
the kernel gradients).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.rwkv6_scan.kernel import (rwkv6_scan_bwd, rwkv6_scan_fwd)
from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _wkv(r, k, v, w, u, s0, chunk, interpret, bwd_impl):
    interpret = interpret_mode(interpret)
    return rwkv6_scan_fwd(r, k, v, w, u, s0, chunk=chunk, interpret=interpret)


def _fwd(r, k, v, w, u, s0, chunk, interpret, bwd_impl):
    interpret = interpret_mode(interpret)
    if bwd_impl == "ref":
        y, sT = rwkv6_scan_fwd(r, k, v, w, u, s0, chunk=chunk,
                               interpret=interpret)
        return (y, sT), (r, k, v, w, u, s0, None)
    y, sT, s_starts = rwkv6_scan_fwd(r, k, v, w, u, s0, chunk=chunk,
                                     interpret=interpret, save_states=True)
    return (y, sT), (r, k, v, w, u, s0, s_starts)


def _bwd(chunk, interpret, bwd_impl, res, cts):
    r, k, v, w, u, s0, s_starts = res
    dy, dsT = cts
    if bwd_impl == "ref" or s_starts is None:
        _, vjp = jax.vjp(rwkv6_scan_ref, r, k, v, w, u, s0)
        return vjp((dy, dsT))
    interpret = interpret_mode(interpret)
    dr, dk, dv, dw, du_chunks, ds0 = rwkv6_scan_bwd(
        r, k, v, w, dy.astype(jnp.float32), u, s_starts,
        dsT.astype(jnp.float32), chunk=chunk, interpret=interpret)
    du = du_chunks.sum(axis=(0, 2)).astype(u.dtype)  # (H, hd)
    return dr, dk, dv, dw.astype(w.dtype), du, ds0.astype(s0.dtype)


_wkv.defvjp(_fwd, _bwd)


def rwkv6_scan(r, k, v, w, u, s0, *, chunk=64, interpret=None,
               bwd_impl="kernel"):
    """Chunked WKV recurrence. Returns (y, sT); see kernel.py for layout."""
    return _wkv(r, k, v, w, u, s0, chunk, interpret, bwd_impl)
