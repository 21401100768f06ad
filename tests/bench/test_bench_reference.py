"""The benchmark's float32 reference against the program's own forward
pass, at smoke widths on the CPU, on the same seeded weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, reference, spec, system
from bench import weights as W
from bench_cells import smoke_cell

# big enough for the fp8 control to show (0.61-1.03 on these seeds), small
# enough for the CPU
CONTROL_WIDTHS = dict(hidden_size=256, intermediate_size=512,
                      num_hidden_layers=4, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=64, vocab_size=4096)


@pytest.mark.parametrize("name", ["qwen3-4b.decode", "yi-9b-l24.rag"])
def test_reference_matches_model_forward(name):
    from repro.models.transformer import Model
    cfg = smoke_cell(name)["config"]
    seed = 2**33 + 5
    params = jax.jit(lambda k: system.to_program(
        W.all_weights(k, cfg, jnp.float32), cfg))(W.seed_key(seed))
    model = Model(system.model_config(cfg))
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"],
                                             (2, 512)).astype(np.int32)
    want = np.asarray(model.forward(params, jnp.asarray(toks)))
    rows = np.array([[0, 7, 200, 511], [3, 64, 300, 510]], np.int32)
    h, head = reference.hidden(seed, cfg, toks, rows)
    got = np.einsum("krd,dv->krv", np.asarray(h), np.asarray(head))
    ref = np.take_along_axis(want, rows[..., None], axis=1)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
    # and the gaps of the forward's own argmax tokens are all 0
    gap, top = reference.gaps(h, head, ref.argmax(-1).astype(np.int32))
    assert np.abs(gap).max() < 1e-5 and (top == ref.argmax(-1)).all()


def test_stacked_weights_are_the_per_layer_weights_bit_for_bit():
    cfg = smoke_cell("qwen3-4b.decode")["config"]
    key = W.seed_key(123)
    stacked = jax.jit(lambda k: W.all_weights(k, cfg))(key)["layers"]
    for layer in range(cfg["num_hidden_layers"]):
        one = W.layer_weights(key, cfg, layer)
        for n, a in one.items():
            assert a.dtype == jnp.bfloat16
            np.testing.assert_array_equal(np.asarray(stacked[n][layer]),
                                          np.asarray(a))


def test_seed_key_keeps_all_64_bits():
    k = [np.asarray(W.seed_key(s)) for s in (1, 2**32 + 1, 2**40 + 1)]
    assert len({tuple(x) for x in k}) == 3
    with pytest.raises(ValueError):
        W.seed_key(-1)


def test_fp8_control_rounds_below_bf16():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 64)),
                    jnp.float32)
    q = reference._quant(x, -1)
    rel = float(jnp.max(jnp.abs(q - x)) / jnp.max(jnp.abs(x)))
    assert 1e-3 < rel < 0.07    # e4m3 keeps 3 mantissa bits


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", ["qwen3-4b.decode", "yi-9b-l24.rag"])
def test_fp8_control_is_not_correct(name, seed):
    """The control (the reference with fp8 matmul operands put in the
    program's place) fails the cell's own limit at every position."""
    c = spec.cell(name)
    cfg = dict(c["config"], **CONTROL_WIDTHS)
    cfg["engine"] = dict(cfg["engine"], max_seq=512)
    rng = np.random.default_rng(seed)
    v = cfg["vocab_size"]
    pairs = [(rng.integers(0, v, 64).astype(np.int32),
              list(rng.integers(0, v, 256))) for _ in range(3)]
    out = check.compare(cfg, 3, 256, seed, pairs, control=True)
    limit = c["check"]["limits"]["max_gap"]["limit"]
    assert out["control_max_gap"] > limit
