import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell with ShapeDtypeStruct stand-ins (no allocation), print
memory/cost analysis, and record roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both --out experiments/dryrun.json

Results are cached incrementally in the JSON (safe to re-run / resume).
"""

import argparse
import json
import time
import traceback
from typing import Dict

import numpy as np


def _mesh(multi_pod: bool):
    import jax
    from repro.launch.mesh import make_mesh
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"),
                         jax.devices()[:512])
    return make_mesh((16, 16), ("data", "model"), jax.devices()[:256])


def lower_cell(arch: str, shape: str, multi_pod: bool) -> Dict:
    """Lower + compile one cell; returns the roofline/record dict."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from repro.analysis import hlo as hlo_mod
    from repro.configs import config
    from repro.launch import specs as S
    from repro.sharding import rules
    from repro.train.step import make_train_step, state_specs

    t0 = time.time()
    cfg = config(arch)
    ok, why = S.shape_applicable(cfg, shape)
    if not ok:
        return {"status": "skipped", "reason": why}

    mesh = _mesh(multi_pod)
    from repro.sharding import ctx
    ctx.set_mesh(mesh)
    chips = int(np.prod(list(mesh.shape.values())))
    model = S.model_for(cfg, shape)
    cfg = model.cfg
    info = S.SHAPES[shape]
    kind = info["kind"]
    named = lambda spec: jax.tree_util.tree_map(  # noqa: E731
        lambda s: NamedSharding(mesh, s), spec,
        is_leaf=lambda x: isinstance(x, PS))

    if kind == "train":
        state_sds = S.train_state_sds(model)
        st_spec = state_specs(state_sds, mesh, cfg)
        step_fn, _, _ = make_train_step(model, mesh)
        batch_sds, batch_spec = S.input_specs(cfg, shape, mesh)
        fn = jax.jit(step_fn,
                     in_shardings=(named(st_spec), named(batch_spec)),
                     out_shardings=(named(st_spec), None),
                     donate_argnums=(0,))
        lowered = fn.lower(state_sds, batch_sds)
        tokens_per_step = info["batch"] * info["seq"]
        model_flops = 6.0 * cfg.active_param_count() * tokens_per_step
    else:
        params = S.params_sds(model)
        p_spec = rules.params_specs(params, mesh, cfg)
        cache = S.cache_sds(model, shape)
        c_spec = rules.cache_specs(cfg, mesh, cache)
        data_sds, data_spec = S.input_specs(cfg, shape, mesh)
        if kind == "prefill":
            def prefill_step(params, tokens, cache):
                return model.prefill(params, tokens, cache)
            fn = jax.jit(prefill_step,
                         in_shardings=(named(p_spec),
                                       named(data_spec["tokens"]),
                                       named(c_spec)),
                         out_shardings=(None, named(c_spec)),
                         donate_argnums=(2,))
            lowered = fn.lower(params, data_sds["tokens"], cache)
            tokens_per_step = info["batch"] * info["seq"]
            model_flops = 2.0 * cfg.active_param_count() * tokens_per_step
        else:
            def serve_step(params, token, cache, pos):
                return model.decode_step(params, token, cache, pos)
            fn = jax.jit(serve_step,
                         in_shardings=(named(p_spec),
                                       named(data_spec["token"]),
                                       named(c_spec), None),
                         out_shardings=(None, named(c_spec)),
                         donate_argnums=(2,))
            lowered = fn.lower(params, data_sds["token"], cache,
                               data_sds["pos"])
            tokens_per_step = info["batch"]
            model_flops = 2.0 * cfg.active_param_count() * tokens_per_step

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    rec: Dict = {"status": "ok", "chips": chips,
                 "lower_s": round(t_lower, 1),
                 "compile_s": round(t_compile, 1)}
    try:
        mem = compiled.memory_analysis()
        rec["memory"] = {
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        rec["memory"] = {"error": str(e)}

    roof = hlo_mod.analyze(compiled, chips=chips, model_flops=model_flops)
    rec["roofline"] = roof.row()
    rec["tokens_per_step"] = tokens_per_step
    rec["params"] = cfg.param_count()
    rec["active_params"] = cfg.active_param_count()
    return rec


def main() -> None:
    from repro.configs import ARCH_IDS

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun.json")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    from repro.launch.specs import SHAPES
    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results: Dict = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = f"{arch}|{shape}|{'multi' if mp else 'single'}"
                if key in results and results[key].get("status") in (
                        "ok", "skipped") and not args.force:
                    print(f"[cached] {key}")
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                try:
                    rec = lower_cell(arch, shape, mp)
                except Exception as e:
                    rec = {"status": "error", "error": repr(e),
                           "trace": traceback.format_exc()[-2000:]}
                results[key] = rec
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
                status = rec.get("status")
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" flops={r['flops']:.3g}"
                             f" coll={r['coll_bytes']:.3g}B"
                             f" bottleneck={r['bottleneck']}"
                             f" compile={rec['compile_s']}s")
                elif status == "error":
                    extra = " " + rec["error"][:200]
                print(f"[done]   {key}: {status}{extra}", flush=True)


if __name__ == "__main__":
    main()
