"""Serving launcher: batched prefill + decode for any ``--arch``, or a
scenario-driven elastic serving fleet through the unified experiment API.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x22b --smoke \
      --batch 4 --prompt 32 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --scenario serve_yahoo --quick \
      --out artifacts/serve_yahoo.runresult.npz

``--scenario`` runs ``repro.exp.run(scenario, engine="serving")`` — the
scenario's trace becomes the request stream + pinning signal and the fleet
metrics print like ``repro.launch.sim`` — while ``--arch`` keeps the raw
model decode path.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _run_fleet(args) -> None:
    from repro.exp import run as exp_run

    res = exp_run(args.scenario, engine="serving", quick=args.quick,
                  seed=args.seed, sim_seed=args.seed)
    print(f"scenario: {args.scenario} | engine: serving | "
          f"workload: {res.meta['workload']}")
    print(json.dumps(res.metrics, indent=1, default=float))
    if args.out:
        path = res.save(args.out)
        print(f"RunResult saved to {path}", file=sys.stderr)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="raw decode benchmark for one model config")
    ap.add_argument("--scenario", default=None,
                    help="serving-fleet scenario (repro.sched registry) run "
                         "through repro.exp with engine='serving'")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized scenario scale (with --scenario)")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="persist the RunResult (with --scenario)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    if args.scenario:
        _run_fleet(args)
        return
    if not args.arch:
        ap.error("one of --arch or --scenario is required")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, smoke_config
    from repro.models import build_model

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    B, P, G = args.batch, args.prompt, args.gen
    max_len = P + G + (cfg.prefix_len or 0)

    kw = {}
    if cfg.family == "vlm":
        kw["prefix_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.prefix_len, cfg.d_model)), jnp.float32)
    if cfg.family == "audio":
        prompt = jnp.asarray(rng.normal(size=(B, P, cfg.d_model)), jnp.float32)
        logits, cache = model.prefill(params, embeds=prompt, max_len=max_len, **kw)
    else:
        prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, P)), jnp.int32)
        logits, cache = model.prefill(params, tokens=prompt, max_len=max_len, **kw)

    # weights as an argument, not a closure (a closure bakes them into the
    # program as constants)
    step = jax.jit(lambda p, c, t, pos: model.decode_step(p, c, tokens=t, pos=pos))
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    pos0 = P + (cfg.prefix_len if cfg.family == "vlm" else 0)
    t0 = time.perf_counter()
    outs = []
    for i in range(G):
        if cfg.family == "audio":
            emb = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
            logits, cache = model.decode_step(params, cache, embeds=emb,
                                              pos=jnp.int32(pos0 + i))
        else:
            logits, cache = step(params, cache, tok, jnp.int32(pos0 + i))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        outs.append(np.asarray(tok[:, 0]))
    dt = time.perf_counter() - t0
    print(f"arch={args.arch} smoke={args.smoke} batch={B} prompt={P} gen={G}")
    print(f"decode throughput: {B * G / dt:.1f} tok/s ({dt/G*1e3:.1f} ms/step)")
    print("sample continuation (seq 0):", [int(o[0]) for o in outs[:16]])


if __name__ == "__main__":
    main()
