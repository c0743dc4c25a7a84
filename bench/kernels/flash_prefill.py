"""Work of the flash-attention kernel in prefill (``kernels/flash_attention``):
one call per layer per prompt.

Counted from the prompt's true length, not its padded bucket: the causal,
windowed QK^T and PV, and the query, key, value and output of the true
tokens (bf16)."""

from __future__ import annotations

from typing import Dict, Tuple

from bench.lib.flops import attn_flops, causal_keys

PROGRAM = "jit_prefill"
OP = r"^custom-call:"


def work(m: Dict, T: int) -> Tuple[int, int]:
    """(flops, bytes) of one call over a prompt of true length ``T``."""
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    flops = attn_flops(m, 1) * causal_keys(T, m["window_size"])
    nbytes = (2 * H + 2 * KV) * hd * T * 2
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: Dict) -> float:
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
