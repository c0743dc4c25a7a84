"""Public Mamba selective-scan op.

Training-complete kernel pair (mirrors rwkv6_scan): the forward checkpoints
chunk-start states, the backward rewinds each chunk in VMEM and runs

    g_t += dy_t (x) C_t ;  (ddt, dx, dB, dC, dA, dD from h_{t-1}, h_t)
    g_{t-1} = exp(dt_t A) o g_t

``bwd_impl="ref"`` differentiates the jnp oracle instead (test cross-check).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.ssm_scan.kernel import ssm_scan_bwd, ssm_scan_fwd
from repro.kernels.ssm_scan.ref import ssm_scan_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _ssm(x, dt, A, Bc, Cc, D, h0, chunk, block_d, interpret, bwd_impl):
    interpret = interpret_mode(interpret)
    return ssm_scan_fwd(x, dt, A, Bc, Cc, D, h0, chunk=chunk,
                        block_d=block_d, interpret=interpret)


def _fwd(x, dt, A, Bc, Cc, D, h0, chunk, block_d, interpret, bwd_impl):
    interpret = interpret_mode(interpret)
    if bwd_impl == "ref":
        y, hT = ssm_scan_fwd(x, dt, A, Bc, Cc, D, h0, chunk=chunk,
                             block_d=block_d, interpret=interpret)
        return (y, hT), (x, dt, A, Bc, Cc, D, h0, None)
    y, hT, h_starts = ssm_scan_fwd(x, dt, A, Bc, Cc, D, h0, chunk=chunk,
                                   block_d=block_d, interpret=interpret,
                                   save_states=True)
    return (y, hT), (x, dt, A, Bc, Cc, D, h0, h_starts)


def _bwd(chunk, block_d, interpret, bwd_impl, res, cts):
    x, dt, A, Bc, Cc, D, h0, h_starts = res
    dy, dhT = cts
    if bwd_impl == "ref" or h_starts is None:
        _, vjp = jax.vjp(ssm_scan_ref, x, dt, A, Bc, Cc, D, h0)
        return vjp((dy, dhT))
    interpret = interpret_mode(interpret)
    dx, ddt, dA_chunks, dB_p, dC_p, dD_chunks, dh0 = ssm_scan_bwd(
        x, dt, A, Bc, Cc, D, dy.astype(jnp.float32), h_starts,
        dhT.astype(jnp.float32), chunk=chunk, block_d=block_d,
        interpret=interpret)
    dA = dA_chunks.sum(axis=(0, 1)).astype(A.dtype)
    dB = dB_p.sum(axis=1).astype(Bc.dtype)  # sum d-block partials
    dC = dC_p.sum(axis=1).astype(Cc.dtype)
    dD = dD_chunks.sum(axis=(0, 1)).astype(D.dtype)
    return (dx, ddt.astype(dt.dtype), dA, dB, dC, dD, dh0.astype(h0.dtype))


_ssm.defvjp(_fwd, _bwd)


def ssm_scan(x, dt, A, Bc, Cc, D, h0, *, chunk=64, block_d=512,
             interpret=None, bwd_impl="kernel"):
    """Chunked selective scan. Returns (y, hT); see kernel.py for layout."""
    return _ssm(x, dt, A, Bc, Cc, D, h0, chunk, block_d, interpret, bwd_impl)
