"""Runs the replica cells: one continuous-batching replica
(``runtime.batching.ContinuousBatcher`` over the paged KV pool, Pallas
kernels on) serving an open loop of requests.

The harness holds the arrival queue and hands a request to the engine when a
slot is free (``submit`` + ``admit``, under the ``bench.admit`` span); then
one ``step`` decodes a token for every active slot (``bench.step``). Every
request is timed from when it was due, not from when it was handed over.

All that depends on the model's architecture (the widths handed to the
program, the structure it must have, the weights and the float32 reference)
comes from the reference module that the configuration names
(``bench/reference/<reference>.py``, loaded by path; its docstring lists
the names every reference has).
"""

from __future__ import annotations

import gc
import math
import time
from collections import deque
from typing import Dict, List

import numpy as np

from bench.lib import harness, traffic
from bench.lib import trace as tr

#: requests due in the window are followed this long past its close, for
#: their first token
DRAIN_S = 60.0


def program_config(conf: Dict, ref):
    """The program's model configuration, checked against the file: the
    widths (``ref.WIDTHS``) come from the file; the structure the reference
    implements (``ref.STRUCTURE``) has to be the program's."""
    from repro.configs import get_config

    m = conf["model"]
    cfg = get_config(conf["program"]["config"]).replace(
        **{k: m[k] for k in ref.WIDTHS}, dtype=m["dtype"],
        param_dtype=m["dtype"],
        use_pallas=bool(conf["program"]["use_pallas"]))
    bad = {k: getattr(cfg, k) for k, v in ref.STRUCTURE.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"program config departs from the reference's "
                         f"layer equations: {bad}")
    return cfg


class Served:
    """What the harness saw of one request."""

    __slots__ = ("req", "gen", "t_first", "t_tokens")

    def __init__(self, req, gen):
        self.req, self.gen = req, gen
        self.t_first = None
        self.t_tokens: List[float] = []


def build(ctx, ref):
    """Weights, engine and requests; nothing compiled yet."""
    import jax

    from repro.models import build_model
    from repro.runtime.batching import ContinuousBatcher

    conf, m, eng = ctx.config, ctx.config["model"], ctx.config["engine"]
    cfg = program_config(conf, ref)
    model = build_model(cfg)
    params = jax.block_until_ready(
        ref.program_params(ctx.seed, m, m["dtype"]))
    bk = ContinuousBatcher(model, params, max_slots=eng["max_slots"],
                           max_len=eng["max_len"],
                           prompt_bucket=eng["prompt_bucket"],
                           kv_layout="paged",
                           kv_block_size=eng["kv_block_size"])
    reqs = traffic.generate(ctx.traffic, ctx.seed, ctx.seconds,
                            m["vocab_size"])
    if int(ctx.traffic["in_flight"]) > eng["max_slots"]:
        raise ValueError("more requests in flight than slots")
    return model, params, bk, reqs


def hand_over(bk, item: Served, clock) -> None:
    """Give the engine one request; its first token exists once ``admit``
    returns (the prefill's sample is read back to the host there)."""
    from repro.runtime.batching import GenRequest

    r = item.req
    item.gen = GenRequest(r.rid, r.prompt, r.max_new)
    item.gen._bench = item  # the engine's occupant leads back to the item
    bk.submit(item.gen)
    bk.admit()
    if item.gen.start_step is None:
        raise RuntimeError(f"request {r.rid} was not admitted to a free slot")
    now = clock()
    item.t_first = now
    item.t_tokens.append(now)


def step(bk, clock) -> None:
    active = [s for _, s in bk.slots.items()]
    bk.step()
    now = clock()
    for g in active:
        g._bench.t_tokens.append(now)


def run(ctx) -> Dict:
    import jax

    ref = harness.load_module(ctx.reference)
    model, params, bk, reqs = build(ctx, ref)
    spans = ctx.spans
    items = [Served(r, None) for r in reqs]
    in_flight = [it for it in items if it.req.due_s < 0]
    due = deque(it for it in items if it.req.due_s >= 0)

    # warm: every prefill bucket this run's prompts use, the admission
    # scatter and the decode step (the in-flight requests' own prefill and
    # first step)
    buckets = {}
    for it in items:
        buckets.setdefault(bk._bucket_for(len(it.req.prompt)), it.req.prompt)
    for b, p in sorted(buckets.items()):
        jax.block_until_ready(bk.prefill(p))
    t_setup = time.perf_counter()
    for it in in_flight:
        hand_over(bk, it, lambda: -1.0)
    if bk.slots.n_active:
        step(bk, lambda: -1.0)
    jax.block_until_ready(bk.pools)
    ctx.log(f"warmed {len(buckets)} prefill buckets, {len(in_flight)} "
            f"requests in flight ({time.perf_counter() - t_setup:.2f}s)")

    trace_s = float(ctx.traffic["trace_seconds"]) if ctx.trace else 0.0
    if ctx.trace:
        ctx.tracer.start()
    prefills_c0 = _counter("batcher.prefill_compiles")
    queue: deque = deque()
    steps_traced, prefills_traced = [], []
    spans.reset()
    ctx.compiles.active = True
    t0 = time.perf_counter()
    setup_s = time.time() - ctx.t_start
    clock = lambda: time.perf_counter() - t0
    window_cm = jax.profiler.TraceAnnotation("bench.window")
    window_cm.__enter__()
    tracing = ctx.trace
    while True:
        now = clock()
        if tracing and now >= trace_s:
            window_cm.__exit__(None, None, None)
            tracing = False
            traced_spans = dict(spans.total), dict(spans.count)
            ctx.tracer.stop()
            ctx.log("trace written")
        if now >= ctx.seconds:
            break
        while due and due[0].req.due_s <= now:
            queue.append(due.popleft())
        if queue and bk.slots.n_free:
            with spans("admit"):
                while queue and bk.slots.n_free:
                    it = queue.popleft()
                    hand_over(bk, it, clock)
                    if tracing:
                        prefills_traced.append(len(it.req.prompt))
        if bk.slots.n_active:
            if tracing:
                steps_traced.append([int(bk.pos[s]) + 1
                                     for s, _ in bk.slots.items()])
            with spans("step"):
                step(bk, clock)
        else:
            with spans("idle"):
                wait = (due[0].req.due_s if due else ctx.seconds) - clock()
                time.sleep(max(0.0, min(wait, ctx.seconds - clock())))
    window_s = clock()
    if tracing:  # the window ended before the trace did
        window_cm.__exit__(None, None, None)
        traced_spans = dict(spans.total), dict(spans.count)
        ctx.tracer.stop()
    ctx.compiles.active = False
    window_compiles = ctx.compiles.n
    window_prefill_jits = _counter("batcher.prefill_compiles") - prefills_c0
    queue.extend(due)  # all of them were due inside the window
    due.clear()
    queue_at_close = len(queue)

    # requests due in the window are followed to their first token
    t_drain = clock()
    pending = queue
    while pending and clock() - t_drain < DRAIN_S:
        while pending and bk.slots.n_free:
            it = pending.popleft()
            hand_over(bk, it, clock)
        if bk.slots.n_active:
            step(bk, clock)
    mem_peak = harness.memory_peak(ctx.devices)

    # end to end
    window_items = [it for it in items if 0 <= it.req.due_s < ctx.seconds]
    ttft = [(it.t_first - it.req.due_s) * 1e3 if it.t_first is not None
            else math.inf for it in window_items]
    gaps = []
    for it in items:
        t = it.t_tokens
        gaps += [(b - a) * 1e3 for a, b in zip(t, t[1:])
                 if a >= 0 and b <= window_s]
    e2e = {"ttft_p90_ms": harness.nearest_rank(ttft, 0.90),
           "itl_p95_ms": harness.nearest_rank(gaps, 0.95),
           "setup_s": setup_s}
    failed = sum(1 for it in window_items if it.t_first is None)
    ctx.log(f"window {window_s:.2f}s: {len(window_items)} due, "
            f"{failed} without a first token after {DRAIN_S:.0f}s, "
            f"{len(gaps)} token gaps, {bk.step_count} steps, "
            f"{window_compiles} backend compiles and {window_prefill_jits} "
            f"new prefill programs in the window; e2e {e2e}")

    finished = [it for it in items if it.gen is not None
                and it.gen.finish_step is not None]
    layer = None
    if ctx.trace:
        red = tr.reduce_trace(ctx.tracer.file())
        ctx.tracer.save(red)
        layer = {"trace": red, "steps": steps_traced,
                 "prefills": prefills_traced,
                 "span_total_s": traced_spans[0],
                 "span_count": traced_spans[1], "model": ctx.config["model"]}

    # free the program's state before the reference runs
    del model, params, bk
    gc.collect()
    checks, extra = check(ctx, ref, finished)
    extra.update(window_compiles=window_compiles,
                 window_prefill_programs=window_prefill_jits)
    if ctx.sweep:
        extra.update(queue_at_close=queue_at_close,
                     served_in_window=sum(1 for it in window_items
                                          if it.t_first is not None),
                     e2e=e2e)
    out = {"e2e": e2e, "checks": checks, "attempted": len(window_items),
           "failed": failed, "memory_peak_bytes": mem_peak, "extra": extra}
    if layer is not None:
        out.update(layer_data=layer, busy_s=layer["trace"]["busy_s"],
                   window_s=layer["trace"]["window_s"],
                   breakdown=tr.breakdown(layer["trace"]))
    return out


def _counter(name: str) -> int:
    from repro.obs.metrics import REGISTRY

    return REGISTRY.counter(name).value


def sample(items: List[Served], seed: int, want_tokens: int, most: int
           ) -> List[Served]:
    """The longest finished request, then others drawn from the seed, until
    ``want_tokens`` served tokens or ``most`` requests."""
    if not items:
        return []
    longest = max(items, key=lambda it: len(it.req.prompt)
                  + len(it.gen.tokens))
    rest = [it for it in items if it is not longest]
    order = np.random.default_rng([seed, 3]).permutation(len(rest))
    out, n = [longest], len(longest.gen.tokens)
    for i in order:
        if n >= want_tokens or len(out) >= most:
            break
        out.append(rest[i])
        n += len(rest[i].gen.tokens)
    return out


def check(ctx, ref, finished: List[Served]):
    """Every served token of a sample of finished requests against the
    float32 reference's logits at the same position: the widest gap by
    which a served token's logit lies below the reference's best. With
    ``ctx.control`` the control (the reference in float8, greedy at each
    of the same positions) is judged in the served tokens' place."""
    t0 = time.perf_counter()
    lim = ctx.config["limits"]
    picked = sample(finished, ctx.seed, int(ctx.traffic["sample_tokens"]),
                    int(ctx.traffic["sample_most"]))
    seqs, wanted, toks = [], [], []
    for it in picked:
        p, t = it.req.prompt, np.asarray(it.gen.tokens)
        seqs.append(np.concatenate([p, t[:-1]]).astype(np.int32))
        wanted.append(np.arange(len(p) - 1, len(p) - 1 + len(t)))
        toks.append(t)
    m = ctx.config["model"]
    n_tok = int(sum(len(t) for t in toks))
    checks = {"sampled_tokens_short": (
        float(max(0, int(lim["min_sampled_tokens"]) - n_tok)), 0.0)}
    extra = {"sampled_requests": len(picked), "sampled_tokens": n_tok}
    if not picked:
        return checks, extra
    max_len = ctx.config["engine"]["max_len"]
    shapes = dict(lengths=[L for L in (1024, 2048) if L < max_len] + [max_len],
                  n_out=int(ctx.traffic["output"]["max"]))
    logits = ref.reference_logits(ctx.seed, m, seqs, wanted, **shapes)
    gaps = np.concatenate([ref.served_gaps(lg, t)
                           for lg, t in zip(logits, toks)])
    extra["reference_s"] = time.perf_counter() - t0
    if ctx.control:  # the control's tokens take the served tokens' place
        extra["program_max_logit_gap"] = float(gaps.max())
        ctl = ref.reference_logits(ctx.seed, m, seqs, wanted,
                                   precision="fp8", **shapes)
        gaps = np.concatenate([ref.served_gaps(lg, c.argmax(-1))
                               for lg, c in zip(logits, ctl)])
    checks["max_logit_gap"] = (float(gaps.max()), float(lim["max_logit_gap"]))
    ctx.log(f"reference over {len(picked)} requests, {n_tok} served tokens: "
            f"widest gap {gaps.max()} ({time.perf_counter() - t0:.2f}s)")
    return checks, extra
