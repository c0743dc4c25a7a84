"""Work of the paged decode-attention kernel (``kernels/decode_attention``,
``paged_decode_attention``): one call per layer per decode step.

Counted from the tokens each active slot holds, not from the pages the
kernel walks, so the number reads the same work whatever implements it:
QK^T and PV over the held keys; the held K and V (bf16), the query and the
output."""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

#: the program that holds the kernel, and its device events' stable name
#: (the Pallas call is the program's one ``custom-call``)
PROGRAM = "jit_decode_paged"
OP = r"^custom-call:"


def work(m: Dict, held: Iterable[int]) -> Tuple[int, int]:
    """(flops, bytes) of one call over slots holding ``held`` tokens."""
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    held = list(held)
    flops = sum(4 * H * hd * n for n in held)
    nbytes = sum(2 * KV * hd * n * 2 for n in held) + len(held) * 2 * H * hd * 2
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: Dict) -> float:
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
