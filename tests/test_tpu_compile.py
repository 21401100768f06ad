"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses here what interpret mode accepts: unaligned
slices, more VMEM than a kernel may use, a program that does not fit HBM.
So the kernels of the serving path, the DPIA->Pallas translation of the
paper's ops and one qwen3-4b decode step are compiled at real widths with
``interpret=False``.  The topology is described inside a fixture, never at
import, so test workers that do not run this file never load libtpu."""
import dataclasses
import os
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro import compiler
from repro.configs import config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul
from repro.kernels.paged_decode import paged_decode_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.models import attention
from repro.models.transformer import Model
from repro.serve.engine import ContinuousEngine

V5E_HBM_BYTES = 16e9
N = 1 << 22           # a 16 MiB float32 operand: too big to sit whole in VMEM
TOKENS, D, FF = 4096, 2560, 9728
HEADS, KV_HEADS, HEAD_DIM = 32, 8, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_hlo(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the compiled HLO"
    return text


@pytest.mark.parametrize("kernel", ["matmul", "rmsnorm", "flash_attention"])
def test_hand_written_kernel_compiles(one_chip, kernel):
    bf = jnp.bfloat16
    if kernel == "matmul":
        _kernel_hlo(lambda a, b: matmul(a, b, interpret=False),
                    _sds(one_chip, (TOKENS, D), bf),
                    _sds(one_chip, (D, FF), bf))
    elif kernel == "rmsnorm":
        _kernel_hlo(lambda x, w: rmsnorm(x, w, interpret=False),
                    _sds(one_chip, (TOKENS, D), bf),
                    _sds(one_chip, (D,), bf))
    else:
        _kernel_hlo(
            lambda q, k, v: flash_attention(q, k, v, interpret=False),
            _sds(one_chip, (HEADS, TOKENS, HEAD_DIM), bf),
            _sds(one_chip, (KV_HEADS, TOKENS, HEAD_DIM), bf),
            _sds(one_chip, (KV_HEADS, TOKENS, HEAD_DIM), bf))


def test_flash_attention_refuses_kv_beyond_vmem(one_chip):
    bf = jnp.bfloat16
    with pytest.raises(ValueError, match="VMEM"):
        jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=False)
                ).lower(_sds(one_chip, (HEADS, 128, HEAD_DIM), bf),
                        _sds(one_chip, (KV_HEADS, 32768, HEAD_DIM), bf),
                        _sds(one_chip, (KV_HEADS, 32768, HEAD_DIM), bf))


_DPIA_OPS = [
    ("dot", dict(n=N), [(N,), (N,)]),
    ("asum", dict(n=N), [(N,)]),
    ("scal", dict(n=N), [(), (N,)]),
    ("matmul", dict(m=1024, k=D, n=FF), [(1024, D), (D, FF)]),
    ("rmsnorm", dict(rows=TOKENS, d=D), [(TOKENS, D), (D,)]),
    ("softmax", dict(rows=TOKENS, d=D), [(TOKENS, D)]),
]


@pytest.mark.parametrize("kernel,shape,args", _DPIA_OPS,
                         ids=[k for k, _, _ in _DPIA_OPS])
def test_dpia_pallas_op_compiles(one_chip, kernel, shape, args):
    """The paper's path: the default strategy through Stage I -> II -> the
    Pallas translation, compiled by Mosaic, not interpreted."""
    fn = compiler.Program.from_kernel(kernel, **shape).check().lower() \
        .compile("pallas", interpret=False, jit=False)
    _kernel_hlo(fn, *[_sds(one_chip, s) for s in args])


def test_qwen3_4b_decode_step_fits_v5e(one_chip):
    model = Model(config("qwen3_4b"))
    slots, max_seq = 8, 2048

    def place(tree):
        return jax.tree_util.tree_map(
            lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    params = place(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_cache(slots, max_seq)))
    token = _sds(one_chip, (slots, 1), jnp.int32)
    pos = _sds(one_chip, (slots,), jnp.int32)
    compiled = jax.jit(model.decode_step).lower(params, token, cache,
                                                pos).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"decode step needs {used} bytes of HBM"


# the cells' pools: qwen3-4b (36 layers, 528 pages, 8 kv heads) and
# yi-9b-l24 (24 layers, 2048 pages, 4 kv heads), 16-position pages
_POOLS = [("qwen3-4b", 36, 528, 8, 2048), ("yi-9b-l24", 24, 2048, 4, 4096)]


@pytest.mark.parametrize("layers,n_blocks,nkv,max_seq",
                         [p[1:] for p in _POOLS], ids=[p[0] for p in _POOLS])
def test_paged_decode_kernel_compiles(one_chip, layers, n_blocks, nkv,
                                      max_seq):
    bf, i32 = jnp.bfloat16, jnp.int32
    pool = _sds(one_chip, (layers, n_blocks, 16, nkv, HEAD_DIM), bf)
    text = _kernel_hlo(
        lambda q, k, v, li, n, bt: paged_decode_attention(q, k, v, li, n, bt),
        _sds(one_chip, (8, HEADS, HEAD_DIM), bf), pool, pool,
        _sds(one_chip, (), i32), _sds(one_chip, (8,), i32),
        _sds(one_chip, (8, max_seq // 16), i32))
    # the pools go to the kernel as they are: no copy of a pool
    assert "copy(%k" not in text and "copy(%v" not in text


def test_paged_kernel_engages_only_for_a_pool_on_one_tpu(topo, one_chip):
    from repro.launch.mesh import make_mesh
    shape = (36, 528, 16, KV_HEADS, HEAD_DIM)
    assert attention.paged_kernel_engages(_sds(one_chip, shape, jnp.bfloat16))
    # the CPU, a shape with no placement, a pool sharded over a mesh
    assert not attention.paged_kernel_engages(jnp.zeros((1, 2, 16, 2, 128)))
    assert not attention.paged_kernel_engages(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    assert not attention.paged_kernel_engages(
        _sds(NamedSharding(mesh, PartitionSpec()), shape, jnp.bfloat16))
    # one TPU, but a layout the kernel does not read
    assert not attention.paged_kernel_engages(
        _sds(one_chip, (36, 528, 16, 3, HEAD_DIM), jnp.bfloat16))
    assert not attention.paged_kernel_engages(
        _sds(one_chip, (36, 528, 16, KV_HEADS, 64), jnp.bfloat16))


def test_decode_chunk_runs_the_paged_kernel_for_v5e(one_chip):
    """Two layers at qwen3-4b's widths: the paged decode chunk compiled
    for a described v5e runs the Mosaic kernel and gathers no view."""
    model = Model(dataclasses.replace(config("qwen3_4b"), n_layers=2))

    def place(tree):
        return jax.tree_util.tree_map(
            lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    params = place(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_paged_cache(
        8, 2048, n_blocks=528, block_size=16)))
    i32 = lambda *s: _sds(one_chip, s, jnp.int32)  # noqa: E731
    chunk = ContinuousEngine._make_chunk_fn(types.SimpleNamespace(
        model=model, max_seq=2048, chunk=8))
    text = chunk.lower(params, cache, i32(8), i32(8),
                       _sds(one_chip, (8, 2), jnp.uint32),
                       _sds(one_chip, (8,)), i32(8),
                       i32(8, 2048 // 16)).compile().as_text()
    assert "tpu_custom_call" in text
    # no (layers, slots, max_seq, kv heads, head_dim) view in the program
    assert "bf16[2,8,2048,8,128]" not in text
