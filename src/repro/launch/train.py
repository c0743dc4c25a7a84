"""Production training launcher: ``--arch <id>`` selects an assigned
architecture; the elastic runtime handles revocations and checkpoints.

On accelerator fleets this runs the full config; on this CPU container use
``--smoke`` (reduced config of the same family) — the full configs are
exercised via the dry-run (launch/dryrun.py).

  PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --smoke \
      --steps 50 --batch 8 --seq 64 --model-par 2 --preempt 20:4
"""

from __future__ import annotations

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--devices", type=int, default=0, help="0 = all")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N CPU host devices (testing)")
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--preempt", default="",
                    help="step:n_devices[,step:n] simulated revocations")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}")

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from repro.checkpoint import Checkpointer
    from repro.configs import get_config, smoke_config
    from repro.data import SyntheticBatches
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.optim.schedule import cosine_schedule
    from repro.runtime import ElasticTrainer

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    print(f"arch={args.arch} params={model.param_count()/1e6:.1f}M "
          f"active={model.active_param_count()/1e6:.1f}M smoke={args.smoke}")
    opt = AdamW(lr=cosine_schedule(args.lr, 20, args.steps),
                moments_dtype=cfg.opt_moments_dtype)
    data = SyntheticBatches(cfg, args.batch, args.seq, seed=args.seed)
    devices = jax.devices()[: args.devices or len(jax.devices())]
    preempt = {}
    for part in filter(None, args.preempt.split(",")):
        s, n = part.split(":")
        preempt[int(s)] = int(n)
    trainer = ElasticTrainer(model, opt, data, Checkpointer(args.ckpt_dir),
                             model_par=args.model_par, devices=devices,
                             log=print)
    trainer.run(args.steps, seed=args.seed, preempt_at=preempt,
                checkpoint_every=args.ckpt_every)
    for s, l, d in trainer.history[:: max(1, len(trainer.history) // 10)]:
        print(f"step {s:5d} loss {l:.4f} devices {d}")


if __name__ == "__main__":
    main()
