"""Does a configuration's engine fit one chip?  Compiles the decode chunk
and the largest prefill of ``bench/configs/<config>.json`` for a described
TPU v5e (no chip needed) and prints each program's memory analysis:

    JAX_PLATFORMS=cpu python3 bench/fit.py qwen3-4b [--kv-blocks N]

The figure recorded in a configuration's ``engine`` is the decode chunk's
arguments + outputs - aliased + temporaries, in bytes.
"""
import argparse
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--kv-blocks", type=int)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import json

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import system
    from bench import weights as W
    from repro.serve.engine import ContinuousEngine
    from repro.models.transformer import Model

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(ROOT, "bench", "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    e = cfg["engine"]
    blocks = args.kv_blocks or e["kv_blocks"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    model = Model(system.model_config(cfg))
    dtype = jnp.dtype(cfg["torch_dtype"])
    params = sds(jax.eval_shape(lambda k: system.to_program(
        W.all_weights(k, cfg, dtype), cfg), jax.random.PRNGKey(0)))
    slots, max_seq = e["slots"], e["max_seq"]
    cache = sds(jax.eval_shape(lambda: model.init_paged_cache(
        slots, max_seq, n_blocks=blocks, block_size=e["block_size"])))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)  # noqa
    f32 = jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=chip)
    keys = jax.ShapeDtypeStruct((slots, 2), jnp.uint32, sharding=chip)
    chunk = ContinuousEngine._make_chunk_fn(types.SimpleNamespace(
        model=model, max_seq=max_seq, chunk=e["chunk"]))
    bt = i32(slots, max_seq // e["block_size"])

    def report(name, compiled):
        m = compiled.memory_analysis()
        used = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        print(f"{name}: arguments {m.argument_size_in_bytes} outputs "
              f"{m.output_size_in_bytes} aliased {m.alias_size_in_bytes} "
              f"temporaries {m.temp_size_in_bytes} -> {used} bytes",
              flush=True)

    report(f"decode chunk ({slots} slots x {max_seq}, {blocks} pages)",
           chunk.lower(params, cache, i32(slots), i32(slots), keys, f32,
                       i32(slots), bt).compile())
    kv, _ = model.split_paged_cache(cache)
    prefill = jax.jit(lambda p, t, kv, row, s, l: model.prefill_paged(
        p, t, kv, row, None, s, l, first=True), donate_argnums=(2,))
    report(f"prefill ({max_seq} bucket)",
           prefill.lower(params, i32(1, max_seq), kv,
                         i32(max_seq // e["block_size"]), i32(), i32(1))
           .compile())


if __name__ == "__main__":
    main()
