"""End-to-end elastic training driver (deliverable b).

Trains a llama-style decoder with the full production stack — sharded mesh,
grad-accum AdamW, async checkpointing — and exercises the CloudCoaster
fault-tolerance path: a simulated transient-pod revocation mid-run triggers
drain -> checkpoint -> mesh rebuild on the survivors -> resharded resume.

Presets:
  tiny  (default) — ~3M params, 120 steps, finishes in ~2 min on this CPU box.
  100m            — ~100M-param model, 300 steps (the deliverable shape; run
                    it on real accelerators, or be patient on CPU).

Run:  PYTHONPATH=src python examples/train_elastic.py [--preset 100m]
"""

import argparse
import os
import tempfile

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

from repro.checkpoint import Checkpointer  # noqa: E402
from repro.data import SyntheticBatches  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.optim import AdamW  # noqa: E402
from repro.optim.schedule import cosine_schedule  # noqa: E402
from repro.runtime import ElasticTrainer  # noqa: E402

PRESETS = {
    "tiny": dict(num_layers=4, d_model=192, num_heads=4, num_kv_heads=2,
                 head_dim=48, d_ff=512, vocab_size=2048, steps=120,
                 batch=8, seq=128, preempt_step=50),
    "100m": dict(num_layers=12, d_model=768, num_heads=12, num_kv_heads=4,
                 head_dim=64, d_ff=2048, vocab_size=32768, steps=300,
                 batch=16, seq=512, preempt_step=120),
}


def build_trainer(preset, ckpt_dir, *, devices, model_par=2, steps=None,
                  log=print):
    """The preset's model, optimizer, synthetic data and checkpointer behind
    an ElasticTrainer on ``devices``. ``steps`` sets the length of the
    learning-rate schedule (default: the preset's)."""
    p = PRESETS[preset]
    cfg = ModelConfig(
        name=f"llama-{preset}", family="dense",
        num_layers=p["num_layers"], d_model=p["d_model"],
        num_heads=p["num_heads"], num_kv_heads=p["num_kv_heads"],
        head_dim=p["head_dim"], d_ff=p["d_ff"], vocab_size=p["vocab_size"],
        dtype="float32", param_dtype="float32", remat="none",
        num_microbatches=2, attn_chunk_q=128, attn_chunk_k=128)
    model = build_model(cfg)
    opt = AdamW(lr=cosine_schedule(3e-3, 20, steps or p["steps"]))
    data = SyntheticBatches(cfg, global_batch=p["batch"], seq_len=p["seq"])
    return ElasticTrainer(model, opt, data, Checkpointer(ckpt_dir, keep=3),
                          model_par=model_par, devices=devices, log=log)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    p = PRESETS[args.preset]

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="coaster_ckpt_")
    trainer = build_trainer(args.preset, ckpt_dir, devices=jax.devices()[:8])
    print(f"model: {trainer.model.param_count()/1e6:.1f}M params; "
          f"devices: {len(jax.devices())}")
    print(f"training {p['steps']} steps; simulated revocation of one pod "
          f"(8 -> 4 devices) at step {p['preempt_step']}")
    trainer.run(p["steps"], preempt_at={p["preempt_step"]: 4},
                checkpoint_every=40)

    hist = trainer.history
    print("\nstep  loss    devices")
    for s, l, d in hist[:: max(1, len(hist) // 12)]:
        print(f"{s:5d}  {l:.4f}  {d}")
    first, last = hist[0][1], hist[-1][1]
    print(f"\nloss {first:.3f} -> {last:.3f} across {trainer.rescales} "
          f"rescale(s); checkpoints in {ckpt_dir}")
    assert last < first


if __name__ == "__main__":
    main()
