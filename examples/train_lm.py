"""End-to-end driver: train a ~100M-parameter dense LM for a few hundred
steps on synthetic data, with checkpoint/resume and NaN guards active.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.manager import CheckpointManager
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.ft.resilience import TrainLoop
from repro.launch.mesh import make_mesh
from repro.models.common import ModelConfig
from repro.models.transformer import Model
from repro.train.step import make_train_state, make_train_step, state_specs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    # ~100M params: 12L x 768d (GPT-2-small-ish, with GQA + SwiGLU)
    cfg = ModelConfig(name="lm-100m", family="dense", n_layers=12,
                      d_model=768, n_heads=12, n_kv_heads=4, d_ff=2048,
                      vocab=32000, dtype="float32", remat=False,
                      max_seq=args.seq)
    model = Model(cfg)
    print(f"params: {cfg.param_count()/1e6:.1f}M")

    mesh = make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    state = make_train_state(model, jax.random.PRNGKey(0))
    st_spec = state_specs(state, mesh, cfg)
    _, jit_with, _ = make_train_step(model, mesh, base_lr=6e-4,
                                     warmup=50, total_steps=args.steps)
    train_step = jit_with(st_spec)

    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch))
    ckpt = CheckpointManager(args.ckpt_dir, keep=2)

    losses = []

    def on_metrics(step, m):
        losses.append(float(m["loss"]))
        if step % 20 == 0:
            print(f"step {step:4d}  loss {m['loss']:.4f}  "
                  f"gnorm {m['grad_norm']:.3f}")

    def wrapped(state, batch):
        return train_step(state,
                          {k: jnp.asarray(v) for k, v in batch.items()})

    t0 = time.time()
    loop = TrainLoop(wrapped, ckpt, data, ckpt_every=100)
    loop.run(state, num_steps=args.steps, on_metrics=on_metrics)
    dt = time.time() - t0
    first = np.mean(losses[:20]) if len(losses) >= 20 else losses[0]
    last = np.mean(losses[-20:])
    print(f"\n{args.steps} steps in {dt:.0f}s; "
          f"loss {first:.3f} -> {last:.3f} "
          f"({'LEARNING OK' if last < first - 0.1 else 'no movement?'})")


if __name__ == "__main__":
    main()
