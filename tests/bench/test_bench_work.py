"""FLOP and byte counters of the architectures against counts made by hand
from the published widths, and against the counts pinned before they moved
into ``bench/archs/``."""
import pytest

from bench import spec
from bench_cells import CELLS


def cfg(name):
    return spec.cell(name)["config"]


def test_qwen3_4b_counts():
    c = cfg("qwen3-4b.decode")
    work = spec.arch(c)
    # 2560x4096 q, 2x 2560x1024 k/v, 4096x2560 o, 3x 2560x9728 MLP
    assert work.layer_matmul_params(c) == 100_925_440
    assert work.head_params(c) == 388_956_160
    # 36 layers with 2 norms of 2560 and 2 qk-norms of 128, head, ln_f
    assert work.weight_bytes(c) == 8_044_936_192
    # plus the embedding: the 8822848512 bytes the bring-up measured
    assert work.weight_bytes(c) + 2 * work.head_params(c) == 8_822_848_512
    assert work.kv_bytes_per_position(c) == 36 * 2 * 8 * 128 * 2
    assert work.decode_step_flops(c, [1000]) == 8_044_544_000 + 589_824 * 1000
    assert work.prefill_flops(c, 100) == (7_266_631_680 * 100 + 777_912_320
                                          + 294_912 * 100 * 101)


def test_yi_9b_l24_counts():
    c = cfg("yi-9b-l24.rag")
    work = spec.arch(c)
    # 4096x4096 q, 2x 4096x512 k/v, 4096x4096 o, 3x 4096x11008 MLP
    assert work.layer_matmul_params(c) == 173_015_040
    assert work.head_params(c) == 262_144_000
    assert work.weight_bytes(c) == 8_829_411_328
    assert work.weight_bytes(c) + 2 * work.head_params(c) == 9_353_699_328
    assert work.kv_bytes_per_position(c) == 49_152
    assert work.decode_step_flops(c, [10, 20]) == (
        2 * 8_829_009_920 + 393_216 * 30)


@pytest.mark.parametrize("name", CELLS)
def test_decode_bytes_count_valid_kv_not_capacity(name):
    c = cfg(name)
    work = spec.arch(c)
    kv, d = work.kv_bytes_per_position(c), c["hidden_size"]
    got = work.decode_step_bytes(c, [100, 300])
    assert got == work.weight_bytes(c) + kv * (99 + 299) + 2 * kv + 2 * 2 * d
    assert work.decode_step_bytes(c, []) == 0.0
    assert work.decode_step_flops(c, []) == 0.0
    # attention work grows with the context, never with max_seq
    assert work.decode_step_flops(c, [200]) > work.decode_step_flops(c, [100])


# the counts of bench/work.py at commit e0ca39b, before they moved into
# bench/archs/: decode at contexts (1, 129, 2048), prefill of 512 and 3968
PINNED = {
    "qwen3-4b": dict(decode_step_flops=25418268672.0,
                     decode_step_bytes=8366110720.0,
                     prefill_512=3798753738752.0,
                     prefill_3968=33479339147264.0),
    "yi-9b-l24": dict(decode_step_flops=27343454208.0,
                      decode_step_bytes=8936488960.0,
                      prefill_512=4304182181888.0,
                      prefill_3968=36050038685696.0),
}


@pytest.mark.parametrize("config", sorted(PINNED))
def test_counts_are_the_pinned_counts(config):
    c = next(spec.cell(w)["config"] for w in CELLS
             if spec.cell(w)["config"]["name"] == config)
    work, want = spec.arch(c), PINNED[config]
    assert work.decode_step_flops(c, (1, 129, 2048)) == want[
        "decode_step_flops"]
    assert work.decode_step_bytes(c, (1, 129, 2048)) == want[
        "decode_step_bytes"]
    assert work.prefill_flops(c, 512) == want["prefill_512"]
    assert work.prefill_flops(c, 3968) == want["prefill_3968"]
