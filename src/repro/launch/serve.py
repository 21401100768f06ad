"""Serving launcher: ``python -m repro.launch.serve --arch <id> [--smoke]``.

Initialises a model, submits a batch of prompts, and decodes with the
continuous-batching (default) or static engine, greedy or sampled, over the
fused on-device decode chunks.  :func:`load_model`, :func:`make_requests`,
:func:`make_engine` and :func:`serve` are the path ``main`` runs, shared
with ``chip_smoke.py``."""
from __future__ import annotations

import argparse
import time
from typing import List, Optional, Sequence


def load_model(arch: str, *, smoke: bool = False, seed: int = 0,
               sharding=None):
    """(model, params) for ``arch`` with random weights from ``seed``,
    placed by ``sharding`` (default: the first device).  The init is jitted
    so each weight is made in its dtype on the device: eager init would
    hold a full-width model's float32 stacks first."""
    import jax

    from repro.configs import config, smoke_config
    from repro.models.transformer import Model

    model = Model(smoke_config(arch) if smoke else config(arch))
    init = jax.jit(model.init_params, out_shardings=sharding)
    return model, init(jax.random.PRNGKey(seed))


def make_requests(cfg, prompt_lens: Sequence[int], max_new: int, *,
                  temperature: float = 0.0, seed: int = 0):
    """One request per prompt length, token ids drawn from ``seed``."""
    import jax

    from repro.serve.engine import Request

    key = jax.random.PRNGKey(seed)
    reqs = []
    for i, n in enumerate(prompt_lens):
        shape = (int(n), cfg.n_codebooks) if cfg.n_codebooks else (int(n),)
        prompt = jax.random.randint(jax.random.fold_in(key, i), shape, 0,
                                    cfg.vocab)
        reqs.append(Request(prompt=prompt, max_new_tokens=max_new,
                            temperature=temperature))
    return reqs


def make_engine(model, params, engine: str = "continuous", *, max_seq: int,
                slots: int = 4, chunk: int = 8, kv_layout: str = "dense",
                kv_blocks: Optional[int] = None):
    from repro.serve.engine import BatchedEngine, ContinuousEngine

    if engine == "continuous":
        return ContinuousEngine(model, params, max_seq=max_seq, slots=slots,
                                chunk=chunk, kv_layout=kv_layout,
                                kv_blocks=kv_blocks)
    if engine == "static":
        return BatchedEngine(model, params, max_seq=max_seq, chunk=chunk)
    raise ValueError(f"unknown engine {engine!r}")


def serve(engine, requests, *, seed: int = 0):
    """Run ``requests`` through ``engine``: (tokens per request, seconds)."""
    import jax

    t0 = time.perf_counter()
    outs = engine.run(requests, key=jax.random.PRNGKey(seed))
    return outs, time.perf_counter() - t0


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", choices=("static", "continuous"),
                    default="continuous")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4,
                    help="device decode lanes (continuous engine)")
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode tokens per fused on-device chunk")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable()

    model, params = load_model(args.arch, smoke=args.smoke)
    reqs = make_requests(model.cfg, [args.prompt_len] * args.batch,
                         args.max_new, temperature=args.temperature)
    max_seq = args.prompt_len + args.max_new + 8
    engine = make_engine(model, params, args.engine, max_seq=max_seq,
                         slots=args.slots, chunk=args.chunk)
    outs, dt = serve(engine, reqs)
    total_new = sum(len(o) for o in outs)
    print(f"arch={model.cfg.name} engine={args.engine} batch={args.batch} "
          f"generated {total_new} tokens in {dt:.2f}s "
          f"({total_new / dt:.1f} tok/s, compilation included)")
    for i, o in enumerate(outs):
        print(f"  request[{i}]: {o[:12]}{'...' if len(o) > 12 else ''}")


if __name__ == "__main__":
    main()
