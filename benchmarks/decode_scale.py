"""Real-model decode data plane: dense vs paged KV cache on the smoke model.

Four phases, all on the ``starcoder2-3b`` smoke config (d_model=128, window
32 — small enough that a CPU container runs it, structured like the real
thing):

  * parity — the acceptance criterion: greedy generation under the paged
    layout must reproduce the dense layout token-for-token on a mixed-length
    workload (``tokens_match`` is gated at exactly 1.0);
  * throughput — steady-state decode tokens/s for each layout on the same
    (already-compiled) batcher instance. Wall-clock on whatever machine runs
    the benchmark; the committed baseline gates the machine-independent
    paged/dense *ratio* only loosely — on a single CPU core the page-table
    gather adds overhead and there is no parallel memory system to win back,
    so the ratio is informational (~1x here, the win shows up in capacity);
  * capacity — the headline: at a **fixed physical block budget** (8 pages
    of 16 tokens = the memory of 2 dense max_len=64 slots), a short-request
    burst (1 page per request) sustains 8 resident paged slots vs 2 dense —
    ``max_slots_ratio`` >= 4x is gated. This is the transient-aware serving
    claim at the KV level: burst capacity scales with *actual* sequence
    footprint, not worst-case.
  * int8 — paged pool with ``kv_quant="int8"``: oracle attention error vs
    f32 (gated upper bound) and the measured pool bytes ratio (~3.4x at
    head_dim=32, gated both ways).

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] --only decode_scale
"""

from __future__ import annotations

import time

import numpy as np

ARCH = "starcoder2-3b"


def _workload(vocab, shapes, seed, rid0=0):
    from repro.runtime.batching import GenRequest

    rng = np.random.default_rng(seed)
    return [GenRequest(rid0 + i, rng.integers(1, vocab, p).astype(np.int32), m)
            for i, (p, m) in enumerate(shapes)]


def _timed_run(batcher, reqs):
    for r in reqs:
        batcher.submit(r)
    t0 = time.perf_counter()
    batcher.run()
    dt = time.perf_counter() - t0
    return dt, sum(len(r.tokens) for r in reqs)


def run(quick: bool = False) -> dict:
    import jax

    from repro.configs import smoke_config
    from repro.models import build_model
    from repro.runtime.batching import ContinuousBatcher

    cfg = smoke_config(ARCH)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    parity_shapes = [(8, 6), (5, 9), (12, 7), (15, 5), (3, 12), (40, 6)]
    n_rep = 2 if quick else 8
    tput_shapes = [(9, 12), (6, 10), (14, 8), (11, 12)] * n_rep

    # parity + throughput: same instance so the timed run hits the jit cache
    tokens = {}
    seconds = {}
    n_tok = {}
    for layout in ("dense", "paged"):
        b = ContinuousBatcher(model, params, max_slots=4, max_len=64,
                              kv_layout=layout)
        warm = _workload(cfg.vocab_size, parity_shapes, seed=42)
        for r in warm:
            b.submit(r)
        b.run()
        tokens[layout] = [r.tokens for r in warm]
        seconds[layout], n_tok[layout] = _timed_run(
            b, _workload(cfg.vocab_size, tput_shapes, seed=7, rid0=100))
    tokens_match = float(tokens["dense"] == tokens["paged"])

    # capacity at a fixed physical budget: 8 blocks of 16 = two dense slots'
    # worth of KV memory; 1-page requests pack 8 resident paged slots into it
    pool_pages, pages_per_slot = 8, 4
    dense_max_slots = pool_pages // pages_per_slot
    burst = [(8, 8)] * (12 if quick else 24)
    bp = ContinuousBatcher(model, params, max_slots=pool_pages, max_len=64,
                           kv_layout="paged", kv_blocks=pool_pages)
    reqs = _workload(cfg.vocab_size, burst, seed=3, rid0=200)
    for r in reqs:
        bp.submit(r)
    peak = 0
    while bp.queue or bp.slots.n_active:
        peak = max(peak, bp.step())
    bp.allocator.check_conservation()
    all_finished = float(all(r.finish_step is not None for r in reqs))

    # int8 paged pool: oracle error vs f32 + measured bytes ratio
    b8 = ContinuousBatcher(model, params, max_slots=2, max_len=64,
                           kv_layout="paged", kv_quant="int8")
    b32 = ContinuousBatcher(model, params, max_slots=2, max_len=64,
                            kv_layout="paged")
    bytes_ratio = b32.kv_cache_bytes() / b8.kv_cache_bytes()
    r8 = _workload(cfg.vocab_size, parity_shapes[:3], seed=42, rid0=300)
    for r in r8:
        b8.submit(r)
    b8.run()
    int8_finished = float(all(r.finish_step is not None for r in r8))

    import jax.numpy as jnp

    from repro.kernels.decode_attention.ref import paged_decode_attention_ref
    from repro.models.common import NEG_INF
    from repro.optim.compress import quantize_int8

    rng = np.random.default_rng(11)
    bs, P, n_phys, KV, hd = 16, 4, 12, cfg.num_kv_heads, cfg.head_dim
    # drawn (block, offset, head)-ordered, stored kv-head-major
    kp = jnp.asarray(rng.standard_normal((n_phys, bs, KV, hd)).swapaxes(1, 2),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_phys, bs, KV, hd)).swapaxes(1, 2),
                     jnp.float32)
    q = jnp.asarray(rng.standard_normal((2, cfg.num_heads, hd)), jnp.float32)
    tbl = jnp.asarray(np.stack([rng.permutation(np.arange(2, n_phys))[:P]
                                for _ in range(2)]).astype(np.int32))
    bias = jnp.asarray(np.where(np.arange(P * bs)[None]
                                < np.array([[33], [17]]), 0.0,
                                NEG_INF).astype(np.float32))
    qk, ks = quantize_int8(kp)
    qv, vs = quantize_int8(vp)
    o32 = paged_decode_attention_ref(q, kp, vp, tbl, bias)
    o8 = paged_decode_attention_ref(q, qk, qv, tbl, bias,
                                    k_scale=ks, v_scale=vs)
    max_abs_err = float(jnp.max(jnp.abs(o32 - o8)))

    return {
        "arch": ARCH,
        "quick": bool(quick),
        "parity": {
            "tokens_match": tokens_match,
            "n_requests": len(parity_shapes),
        },
        "throughput": {
            "dense_tok_s": n_tok["dense"] / seconds["dense"],
            "paged_tok_s": n_tok["paged"] / seconds["paged"],
            "paged_over_dense": (n_tok["paged"] / seconds["paged"])
            / (n_tok["dense"] / seconds["dense"]),
            "dense_seconds": seconds["dense"],
            "paged_seconds": seconds["paged"],
            "n_tokens": n_tok["paged"],
        },
        "capacity": {
            "pool_pages": pool_pages,
            "block_size": 16,
            "pages_per_slot": pages_per_slot,
            "dense_max_slots": dense_max_slots,
            "paged_peak_resident": peak,
            "max_slots_ratio": peak / dense_max_slots,
            "all_finished": all_finished,
            "n_requests": len(burst),
        },
        "int8": {
            "max_abs_err": max_abs_err,
            "bytes_ratio": bytes_ratio,
            "all_finished": int8_finished,
        },
    }


if __name__ == "__main__":
    import json

    print(json.dumps(run(quick=True), indent=1, default=float))
