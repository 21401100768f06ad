"""Training launcher: ``python -m repro.launch.train --arch <id> [--smoke]``.

Wires together config -> model -> mesh -> sharded train step -> data pipeline
-> fault-tolerant loop (checkpoint/restart, NaN guard, watchdog).  On this
CPU container use --smoke (reduced config, 1 device); on a real cluster the
same file launches at any mesh size.
"""
from __future__ import annotations

import argparse
import logging
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config for CPU")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--data-axis", type=int, default=0,
                    help="mesh data-axis size; 0 = all devices")
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.enable()

    import jax
    import jax.numpy as jnp

    from repro.ckpt.manager import CheckpointManager
    from repro.configs import config, smoke_config
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.ft.resilience import TrainLoop
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import Model
    from repro.train.step import (make_train_state, make_train_step,
                                  state_specs)

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    log = logging.getLogger("repro.train")

    cfg = smoke_config(args.arch) if args.smoke else config(args.arch)
    model = Model(cfg)

    n_dev = len(jax.devices())
    nd = args.data_axis or n_dev
    mesh = make_mesh((nd, 1), ("data", "model"), jax.devices()[:nd])
    log.info("arch=%s params=%.2fM mesh=%s", cfg.name,
             cfg.param_count() / 1e6 if args.smoke else
             cfg.param_count() / 1e6, dict(mesh.shape))

    key = jax.random.PRNGKey(0)
    state = make_train_state(model, key, use_8bit=cfg.opt_8bit)
    st_spec = state_specs(state, mesh, cfg)
    step_fn, jit_with, batch_spec = make_train_step(
        model, mesh, microbatches=args.microbatches, base_lr=args.lr,
        total_steps=args.steps)
    train_step = jit_with(st_spec)

    data = SyntheticLM(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        n_codebooks=cfg.n_codebooks))

    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    t_hist = []

    def on_metrics(step, m):
        t_hist.append(time.time())
        if step % args.log_every == 0:
            dt = (t_hist[-1] - t_hist[-min(len(t_hist), args.log_every)]) / \
                max(min(len(t_hist), args.log_every) - 1, 1)
            log.info("step=%d loss=%.4f gnorm=%.3f lr=%.2e %.0fms/step",
                     step, float(m["loss"]), float(m["grad_norm"]),
                     float(m["lr"]), dt * 1000)

    def wrapped_step(state, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        return train_step(state, batch)

    loop = TrainLoop(wrapped_step, ckpt, data, ckpt_every=args.ckpt_every)
    state = loop.run(state, num_steps=args.steps, on_metrics=on_metrics)
    log.info("done: %d steps (skipped=%d)", args.steps, loop.skipped_steps)


if __name__ == "__main__":
    main()
