"""Per-cell diagnostic for the perf hillclimb: lower one (arch x shape) cell
on a reduced mesh, break down FLOPs/bytes/collectives by kind, and report
the roofline terms — the 'profile' of the dry-run world.

    PYTHONPATH=src python -m benchmarks.cell_diag --arch dbrx_132b \
        --shape train_4k [--devices 16 --mesh 4x4]
"""
import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--devices", type=int, default=256)
    ap.add_argument("--mesh", default="16x16")
    args = ap.parse_args()

    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices}")
    import numpy as np
    import jax

    from repro.launch.mesh import make_mesh

    dims = [int(x) for x in args.mesh.split("x")]
    names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    mesh = make_mesh(dims, names, jax.devices()[:int(np.prod(dims))])

    from repro.launch import dryrun
    dryrun._mesh = lambda mp: mesh
    import time
    t0 = time.time()
    rec = dryrun.lower_cell(args.arch, args.shape, False)
    print(f"[{args.arch} x {args.shape} on {args.mesh}] "
          f"{rec['status']} in {time.time()-t0:.0f}s")
    if rec["status"] != "ok":
        print(rec.get("reason") or rec.get("trace", "")[-2000:])
        return
    r = rec["roofline"]
    for k in ("flops", "bytes", "bytes_min", "coll_bytes", "compute_s",
              "memory_s", "memory_floor_s", "collective_s", "bottleneck",
              "useful_frac"):
        print(f"  {k:16s} {r.get(k)}")

    # detailed breakdown requires re-lowering with text capture
    print("\n-- re-lowering for kind breakdown --")
    from repro.analysis import hlo_counter as H
    from repro.configs import config
    from repro.launch import specs as S
    from repro.sharding import rules
    from repro.train.step import make_train_step, state_specs
    from jax.sharding import NamedSharding, PartitionSpec as PS
    import jax

    cfg = config(args.arch)
    model = S.model_for(cfg, args.shape)
    cfg = model.cfg
    named = lambda s: jax.tree_util.tree_map(  # noqa: E731
        lambda x: NamedSharding(mesh, x), s,
        is_leaf=lambda x: isinstance(x, PS))
    kind = S.SHAPES[args.shape]["kind"]
    if kind == "train":
        state_sds = S.train_state_sds(model)
        st_spec = state_specs(state_sds, mesh, cfg)
        step_fn, _, _ = make_train_step(model, mesh)
        batch_sds, batch_spec = S.input_specs(cfg, args.shape, mesh)
        fn = jax.jit(step_fn, in_shardings=(named(st_spec), named(batch_spec)),
                     out_shardings=(named(st_spec), None), donate_argnums=(0,))
        txt = fn.lower(state_sds, batch_sds).compile().as_text()
    elif kind == "prefill":
        params = S.params_sds(model)
        p_spec = rules.params_specs(params, mesh, cfg)
        cache = S.cache_sds(model, args.shape)
        c_spec = rules.cache_specs(cfg, mesh, cache)
        data_sds, data_spec = S.input_specs(cfg, args.shape, mesh)
        fn = jax.jit(lambda p, t, c: model.prefill(p, t, c),
                     in_shardings=(named(p_spec), named(data_spec["tokens"]),
                                   named(c_spec)),
                     out_shardings=(None, named(c_spec)), donate_argnums=(2,))
        txt = fn.lower(params, data_sds["tokens"], cache).compile().as_text()
    else:
        params = S.params_sds(model)
        p_spec = rules.params_specs(params, mesh, cfg)
        cache = S.cache_sds(model, args.shape)
        c_spec = rules.cache_specs(cfg, mesh, cache)
        data_sds, data_spec = S.input_specs(cfg, args.shape, mesh)
        fn = jax.jit(lambda p, t, c, pos: model.decode_step(p, t, c, pos),
                     in_shardings=(named(p_spec), named(data_spec["token"]),
                                   named(c_spec), None),
                     out_shardings=(None, named(c_spec)), donate_argnums=(2,))
        txt = fn.lower(params, data_sds["token"], cache,
                       data_sds["pos"]).compile().as_text()

    m = H.HloModule(txt)
    from collections import Counter
    coll = Counter()
    fus = Counter()

    def walk(name, scale):
        comp = m.computations.get(name)
        if comp is None:
            return
        for ins in comp.instrs:
            if ins.op == "while":
                walk(ins.attr("body"),
                     scale * m._trip_count(ins.attr("condition") or ""))
                continue
            if ins.op.replace("-start", "") in (
                    "all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute"):
                coll[ins.op.replace("-start", "")] += \
                    H._bytes_of(ins.type_str) * scale
            if ins.op == "fusion":
                b, _ = m._fusion_bytes(comp, ins)
                fus[(ins.name.split(".")[0], ins.type_str[:44])] += b * scale

    walk(m.entry, 1.0)
    print("collective bytes by kind (per partition):")
    for k, b in coll.most_common():
        print(f"  {k:22s} {b/1e9:10.2f} GB")
    print("top fusion traffic (per partition):")
    for k, b in fus.most_common(10):
        print(f"  {b/1e9:8.1f} GB  {k[0][:36]:38s} {k[1]}")


if __name__ == "__main__":
    main()
