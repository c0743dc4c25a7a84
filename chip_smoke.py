#!/usr/bin/env python3
"""On-chip smoke test of the system's main path. Needs a TPU.

    python chip_smoke.py            # one chip: data plane + fleet program
    python chip_smoke.py --chips 4  # four chips: elastic trainer only

One chip, two phases:

  data plane -- starcoder2-3b at its published widths (bf16, random weights
      from ``--seed``) behind ``ContinuousBatcher`` with the paged KV pool,
      8 slots, ``max_len`` 4096 and the Pallas kernels on, serving 16
      requests (prompts of 100-1500 tokens, 32-64 new tokens each) until
      empty. Checks: every request gets exactly its token count, the
      compiled decode step holds the Mosaic kernel (``tpu_custom_call``),
      the paged kernel alone agrees with its oracle within ``KERNEL_TOL``,
      and on the first 8 requests the kernel path's prefill logits, and
      its first decode step's logits on the same pools, tokens and
      positions, agree with the pure-jnp path's (``use_pallas`` off)
      within ``LOGIT_TOL``.
  fleet -- ``exp.run("serve_flash_crowd", engine="serving_jax")`` at full
      scale: a clean ``validate_run_result``, conserved requests, and the
      same metrics from a second run of the cached program.

``--chips 4`` runs only the elastic trainer (``100m`` preset of
``examples/train_elastic.py``, ``model_par=2``) on a 2x2 mesh with a 4->2
revocation, against the same seeds and steps on one chip.

Every line but the last names the device. The last line is one JSON object,
``{"ok": true, "device": {...}}``. The script exits non-zero and prints no
result when JAX finds no TPU or any phase fails. One process throughout: a
child process could not reach the chip this one holds.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time

REPO = pathlib.Path(__file__).resolve().parent

# Gaps are max |diff| over max |reference|. The paged kernel against its
# oracle on bf16 inputs: both round the output to bf16 (2^-9 of its scale)
# and the kernel also rounds the probabilities to bf16 before the PV
# product; the interpreted kernel gives 0.004 at these shapes. A wrong page
# or mask moves the output by O(1) of its scale.
KERNEL_TOL = 0.02
# Kernel vs jnp path logits on the same inputs: the two paths round bf16
# activations differently (the kernels scale q before the dot and
# accumulate the softmax online), about one bf16 ulp (2^-8) of relative
# error per layer; over 30 layers that adds up in quadrature to ~0.02.
LOGIT_TOL = 0.05
# 4 chips (model_par=2) vs 1 chip, f32 training: same math, different
# reduction order across shards; relative loss gap allowed per step.
LOSS_RTOL = 1e-3

ARCH, N_REQUESTS, N_SLOTS, MAX_LEN, KV_BLOCK = "starcoder2-3b", 16, 8, 4096, 16
PROMPT_LENS, NEW_TOKENS = (100, 1500), (32, 64)  # inclusive ranges
ELASTIC_PRESET = "100m"


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def say(dev: dict, msg: str) -> None:
    print(f"[{dev['platform']} {dev['kind']} x{dev['count']}] {msg}",
          flush=True)


def _rel_gap(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-6))


# ------------------------------------------------------------------ phases


def _check_paged_kernel(dev: dict, cfg, seed: int) -> None:
    """The paged decode kernel alone at the data plane's shapes against its
    pure-jnp oracle, on random pages in a shuffled table."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.decode_attention.kernel import NEG_INF
    from repro.kernels.decode_attention.ops import paged_decode_attention
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref

    B, bs, L = N_SLOTS, KV_BLOCK, MAX_LEN
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    P = L // bs
    rng = np.random.default_rng(seed)
    kp, vp = (jnp.asarray(rng.standard_normal((2 + B * P, KV, bs, hd)),
                          jnp.bfloat16) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.bfloat16)
    tbl = jnp.asarray(2 + rng.permutation(B * P).reshape(B, P), jnp.int32)
    held = rng.integers(1, L + 1, B)
    bias = jnp.asarray(np.where(np.arange(L) < held[:, None], 0.0, NEG_INF),
                       jnp.float32)
    out = jax.jit(paged_decode_attention)(q, kp, vp, tbl, bias)
    gap = _rel_gap(out, paged_decode_attention_ref(q, kp, vp, tbl, bias))
    say(dev, f"paged decode kernel vs oracle (B={B} H={H} KV={KV} hd={hd} "
             f"block {bs}): rel gap {gap} (tol {KERNEL_TOL})")
    if not gap <= KERNEL_TOL:
        raise AssertionError("paged decode kernel disagrees with its oracle")


def phase_data_plane(dev: dict, seed: int) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import build_model
    from repro.runtime.batching import ContinuousBatcher, GenRequest

    cfg = get_config(ARCH)
    _check_paged_kernel(dev, cfg, seed)
    model_k = build_model(cfg.replace(use_pallas=True))
    model_j = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(model_k.init)(jax.random.PRNGKey(seed)))
    n_params = sum(l.size for l in jax.tree.leaves(params))
    say(dev, f"data plane: {cfg.name} {n_params / 1e9:.3f}B params "
             f"{cfg.dtype}, init {time.perf_counter() - t0:.3f}s")

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, int(n)).astype(np.int32)
               for n in rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1,
                                     N_REQUESTS)]
    max_new = [int(m) for m in rng.integers(NEW_TOKENS[0], NEW_TOKENS[1] + 1,
                                            N_REQUESTS)]
    bk = ContinuousBatcher(model_k, params, max_slots=N_SLOTS,
                           max_len=MAX_LEN, kv_layout="paged",
                           kv_block_size=KV_BLOCK)
    # the jnp path's prefill (one slot: its pool is never decoded into)
    bj = ContinuousBatcher(model_j, params, max_slots=1, max_len=MAX_LEN,
                           kv_layout="paged", kv_block_size=KV_BLOCK)
    reqs = [GenRequest(i, p, m) for i, (p, m) in enumerate(zip(prompts, max_new))]
    for r in reqs:
        bk.submit(r)

    t0 = time.perf_counter()
    compiled = bk.lower_decode().compile()
    say(dev, f"decode step compile {time.perf_counter() - t0:.3f}s")
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("compiled decode step holds no Pallas kernel")
    mem = compiled.memory_analysis()
    say(dev, f"decode step memory: arguments {mem.argument_size_in_bytes} B, "
             f"output {mem.output_size_in_bytes} B, "
             f"temp {mem.temp_size_in_bytes} B, "
             f"KV pool {bk.kv_cache_bytes()} B")

    t0 = time.perf_counter()
    bk.admit()
    jax.block_until_ready(bk.pools)
    say(dev, f"admission of {N_SLOTS} requests (prefills, bucket compiles "
             f"included) {time.perf_counter() - t0:.3f}s")
    # both paths decode the same state: the pools the kernel path's
    # prefills wrote, the same last tokens, positions and page table
    jnp_decode = jax.jit(lambda *a: model_j.decode_step_paged(
        a[0], a[1], tokens=a[2], pos_vec=a[3], pages=a[4])[0])
    ref_logits = jnp_decode(*bk.decode_args())
    t0 = time.perf_counter()
    bk.step()  # no free slot: decode only
    say(dev, f"first decode step (its first jit dispatch, which finds the "
             f"program compiled above in the compile cache) "
             f"{(time.perf_counter() - t0) * 1e3} ms")
    gap = _rel_gap(bk.last_logits, ref_logits)
    agree = float(np.mean(np.argmax(np.asarray(bk.last_logits), -1)
                          == np.argmax(np.asarray(ref_logits), -1)))
    say(dev, f"decode step logits kernel vs jnp path, same inputs: rel gap "
             f"{gap} (tol {LOGIT_TOL}), argmax agreement {agree}")
    prefill_gaps = []
    for p in prompts[:N_SLOTS]:
        prefill_gaps.append(_rel_gap(bk.prefill(p)[0], bj.prefill(p)[0]))
        t0 = time.perf_counter()
        jax.block_until_ready(bk.prefill(p))  # compiled bucket: warm
        say(dev, f"prefill {len(p)} tokens (kernel path): "
                 f"{(time.perf_counter() - t0) * 1e3} ms")
    say(dev, f"prefill logits kernel vs jnp path: max rel gap "
             f"{max(prefill_gaps)} (tol {LOGIT_TOL})")
    if not (gap <= LOGIT_TOL and max(prefill_gaps) <= LOGIT_TOL):
        raise AssertionError("kernel path logits disagree with the jnp path")
    del bj

    decode_s, admit_s = [], []
    t_run = time.perf_counter()
    while bk.queue or bk.slots.n_active:
        queued = len(bk.queue)
        t0 = time.perf_counter()
        bk.step()  # ends in a host read of the sampled tokens
        (admit_s if len(bk.queue) != queued else decode_s).append(
            time.perf_counter() - t0)
    run_s = time.perf_counter() - t_run
    n_tok = sum(len(r.tokens) for r in reqs)
    bad = [r.rid for r in reqs
           if r.finish_step is None or len(r.tokens) != r.max_new]
    if bad:
        raise AssertionError(f"requests without their token count: {bad}")
    say(dev, f"served {len(reqs)} requests, {n_tok} tokens in "
             f"{bk.step_count} steps, {run_s:.3f}s after the first step")
    say(dev, f"decode step ({N_SLOTS} slots, no admission): median "
             f"{np.median(decode_s) * 1e3} ms, p90 "
             f"{np.percentile(decode_s, 90) * 1e3} ms over {len(decode_s)} "
             f"steps; steps with admissions: {len(admit_s)}, median "
             f"{np.median(admit_s) * 1e3} ms")


def phase_fleet(dev: dict, seed: int) -> None:
    """The scenario's own trace seed; ``seed`` only feeds the data plane."""
    import jax

    from repro import exp

    runs = []
    for _ in range(2):  # compile, then the cached program
        runs.append(exp.run("serve_flash_crowd", engine="serving_jax",
                            quick=False))
    if jax.default_backend() != "tpu":
        raise AssertionError(f"fleet program ran on {jax.default_backend()}")
    for rr in runs:
        problems = exp.validate_run_result(rr)
        if problems:
            raise AssertionError(f"invalid RunResult: {problems}")
        m = rr.metrics
        submitted = rr.meta["workload"]["n_requests"]
        if not (m["n_done"] + m["n_unfinished"] == m["n_requests"] == submitted
                and len(rr.series["short_waits"]) >= m["n_done"]
                and rr.series["queue_depth"][-1] <= m["n_unfinished"]):
            raise AssertionError(f"requests not conserved: {m}")
    if runs[0].metrics != runs[1].metrics:
        raise AssertionError("cached fleet program changed the metrics")
    m, obs = runs[1].metrics, runs[1].meta["obs"]
    say(dev, f"fleet serve_flash_crowd: {int(m['n_requests'])} requests, "
             f"{int(m['n_done'])} done, p99 wait {m['short_p99_wait_s']} s, "
             f"{runs[1].meta['workload']['max_ticks']} ticks")
    say(dev, f"fleet program: compile run {runs[0].meta['obs']['exec_s']} s, "
             f"cached run {obs['exec_s']} s, "
             f"{m['n_requests'] / obs['exec_s']} simulated req/s")


def phase_elastic(dev: dict, seed: int, steps: int = 12,
                  revoke_at: int = 6) -> None:
    import jax
    import numpy as np

    sys.path.insert(0, str(REPO))
    from examples.train_elastic import build_trainer

    devs = jax.devices()
    if len(devs) < 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found {len(devs)}")
    histories = {}
    for n_chips, model_par in ((4, 2), (1, 1)):
        with tempfile.TemporaryDirectory() as ckpt:
            tr = build_trainer(ELASTIC_PRESET, ckpt, devices=devs[:n_chips],
                               model_par=model_par, steps=steps,
                               log=lambda s: say(dev, s))
            spanned = {d for s in jax.tree.leaves(tr.state_shardings)
                       for d in s.device_set}
            if spanned != set(devs[:n_chips]):
                raise AssertionError(f"state spans {len(spanned)} devices, "
                                     f"not {n_chips}")
            revoke = {revoke_at: 2} if n_chips == 4 else {}
            t0 = time.perf_counter()
            tr.run(steps, seed=seed, preempt_at=revoke, checkpoint_every=0)
            say(dev, f"{n_chips} chip(s), model_par={model_par}: {steps} steps "
                     f"in {time.perf_counter() - t0:.3f}s, "
                     f"{tr.rescales} rescale(s), ends on "
                     f"{len(tr.devices)} devices")
            if n_chips == 4 and (tr.rescales != 1 or tr.devices != devs[:2]):
                raise AssertionError("revocation 4->2 did not happen")
            histories[n_chips] = tr.history
    l4 = np.array([h[1] for h in histories[4]])
    l1 = np.array([h[1] for h in histories[1]])
    rel = np.abs(l4 - l1) / np.abs(l1)
    say(dev, f"losses 4 chips: {l4.tolist()}")
    say(dev, f"losses 1 chip:  {l1.tolist()}")
    say(dev, f"relative loss gap before revocation max {rel[:revoke_at].max()}"
             f" (tol {LOSS_RTOL}), after {rel[revoke_at:].max()}")
    if not np.all(np.isfinite(l4)) or rel[:revoke_at].max() > LOSS_RTOL:
        raise AssertionError("4-chip losses depart from the 1-chip run")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev['platform']!r} ({dev['kind']})", file=sys.stderr)
        return 1
    if not (REPO / "src" / "repro").is_dir():
        print(f"chip_smoke: the repo's sources are not beside it "
              f"({REPO / 'src' / 'repro'} is missing)", file=sys.stderr)
        return 1
    sys.path.insert(0, str(REPO / "src"))
    from repro.launch.cache import enable_compile_cache

    say(dev, f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        phases = [("elastic trainer 2x2", phase_elastic)]
    else:
        phases = [("data plane", phase_data_plane), ("fleet", phase_fleet)]
    for name, phase in phases:
        t0 = time.perf_counter()
        phase(dev, args.seed)
        say(dev, f"phase {name}: pass ({time.perf_counter() - t0:.3f}s)")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
