"""Public flash-attention op: jit'd wrapper + memory-frugal custom VJP.

Forward runs the Pallas kernel (interpret mode on the CPU backend). Backward
recomputes attention from (q, k, v) via the reference implementation — no
O(S^2) probability residuals are saved, which is the kernel's training-memory win
over the autodiff'd jnp path (see EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, window, softcap, prefix_len, q_offset,
           block_q, block_k, interpret):
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window, softcap=softcap,
        prefix_len=prefix_len, q_offset=q_offset, block_q=block_q,
        block_k=block_k, interpret=interpret_mode(interpret))


def _fwd(q, k, v, causal, window, softcap, prefix_len, q_offset,
         block_q, block_k, interpret):
    o = _flash(q, k, v, causal, window, softcap, prefix_len, q_offset,
               block_q, block_k, interpret)
    return o, (q, k, v)


def _bwd(causal, window, softcap, prefix_len, q_offset, block_q, block_k,
         interpret, res, do):
    q, k, v = res
    ref = functools.partial(
        attention_ref, causal=causal, window=window, softcap=softcap,
        prefix_len=prefix_len, q_offset=q_offset)
    _, vjp = jax.vjp(ref, q, k, v)
    return vjp(do)


_flash.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    prefix_len=0, q_offset=0, block_q=128, block_k=128,
                    interpret=None):
    """GQA flash attention. q: (B,H,Sq,hd); k,v: (B,KV,Sk,hd)."""
    return _flash(q, k, v, causal, window, softcap, prefix_len, q_offset,
                  block_q, block_k, interpret)
