"""Model FLOPs of a dense decoder from its configuration's widths: the
matrix products a token needs (2 per weight), and attention's two products
over the keys it attends to."""

from __future__ import annotations

from typing import Dict, Iterable


def layer_weights(m: Dict) -> int:
    d, H, KV, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                        m["head_dim"], m["d_ff"])
    return 2 * d * H * hd + 2 * d * KV * hd + 2 * d * ff


def attn_flops(m: Dict, keys: int) -> int:
    """One query's QK^T and PV over ``keys`` keys, all heads, one layer."""
    return 4 * m["num_heads"] * m["head_dim"] * keys


def causal_keys(T: int, window: int) -> int:
    """Keys the T queries of a causal windowed prompt attend to in all."""
    w = min(window, T)
    return w * (w + 1) // 2 + (T - w) * w


def decode_step_flops(m: Dict, held: Iterable[int]) -> int:
    """One decode step: each active slot's token through every layer (its
    attention over the tokens it holds) and the output head."""
    per_tok = m["num_layers"] * 2 * layer_weights(m) \
        + 2 * m["d_model"] * m["vocab_size"]
    return sum(per_tok + m["num_layers"] * attn_flops(m, n) for n in held)


def prefill_flops(m: Dict, T: int) -> int:
    """A prompt of true length T; logits of its last position only."""
    return (m["num_layers"] * (2 * layer_weights(m) * T
                               + attn_flops(m, 1) * causal_keys(
                                   T, m["window_size"]))
            + 2 * m["d_model"] * m["vocab_size"])
