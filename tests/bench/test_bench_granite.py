"""granite-4.0-h-micro's architecture module against the program, on the
CPU in float32 at smoke widths: the served path (paged prefill, in one
call and in continuation chunks, then decode through the cache) agrees in
logits with the plain reference, whose Mamba-2 is the sequential
recurrence; the state-space parameters land in Mamba-2's initial ranges;
the work counts by hand at the published widths."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, spec
from bench import weights as W
from bench_cells import smoke_cell

CELL = "granite-4.0-h-micro.long-agent"
SEED = 2**33 + 17


@pytest.fixture(scope="module")
def served():
    from repro.models.transformer import Model
    cfg = smoke_cell(CELL)["config"]
    a = spec.arch(cfg)
    params = jax.jit(lambda k: a.to_program(
        W.all_weights(k, cfg, jnp.float32), cfg))(W.seed_key(SEED))
    return cfg, a, Model(a.model_config(cfg)), params


def _reference_logits(a, cfg, seq, rows):
    t = 512
    seqs = np.zeros((1, t), np.int32)
    seqs[0, :len(seq)] = seq
    h, head = a.hidden(SEED, cfg, seqs, np.asarray([rows], np.int32))
    return np.asarray(h[0] @ head)


@pytest.mark.parametrize("split", [None, 40], ids=["one_call", "chunks"])
def test_paged_prefill_then_decode_matches_the_reference(served, split):
    """Logits at every prompt end and decode step, the model driven as
    the engine drives it: page pools, block tables, the slot's staged
    recurrent state, then decode steps through the cache."""
    cfg, a, model, params = served
    rng = np.random.default_rng(0)
    plen, steps, bs = 75, 24, 16
    seq = rng.integers(0, cfg["vocab_size"], plen + steps).astype(np.int32)
    max_seq = 128
    cache = model.init_paged_cache(1, max_seq, n_blocks=max_seq // bs,
                                   block_size=bs)
    kv, state = model.split_paged_cache(cache)
    bt = jnp.arange(max_seq // bs, dtype=jnp.int32)
    st = model.init_prefill_state(1)
    cuts = [0, plen] if split is None else [0, split, plen]
    for c0, c1 in zip(cuts, cuts[1:]):
        bucket = 64 if c1 - c0 <= 64 else 128
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :c1 - c0] = seq[c0:c1]
        logits, kv, st = model.prefill_paged(
            params, jnp.asarray(toks), kv, bt, st, c0,
            jnp.asarray([c1 - c0]), first=c0 == 0)
    state = jax.tree_util.tree_map(lambda s, n: n.astype(s.dtype), state, st)
    cache = model.merge_paged_cache(kv, state)
    got = [np.asarray(logits[0])]
    for t in range(plen, plen + steps - 1):
        lg, cache = model.decode_step(params, jnp.asarray([[seq[t]]]), cache,
                                      jnp.asarray([t]),
                                      block_tables=bt[None])
        got.append(np.asarray(lg[0]))
    ref = _reference_logits(a, cfg, seq, list(range(plen - 1,
                                                    plen + steps - 1)))
    # float32 both sides, the SSD's chunked sums against one position at a
    # time: agreement to a few parts in 1e5 of the logits' scale
    scale = np.abs(ref).max()
    np.testing.assert_allclose(np.stack(got), ref, atol=1e-4 * scale,
                               rtol=1e-4)


def test_continuous_engine_serves_the_reference_greedy(served):
    """The whole timed path: ContinuousEngine with the paged pool, greedy;
    the check's gaps of its tokens against the reference are ~0."""
    from repro.launch.serve import make_engine
    from repro.serve.engine import Request
    cfg, a, model, params = served
    eng = make_engine(model, params, "continuous", max_seq=256, slots=2,
                      chunk=4, kv_layout="paged", kv_blocks=32)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (20, 90, 45)]
    outs = eng.run([Request(prompt=p, max_new_tokens=n)
                    for p, n in zip(prompts, (30, 12, 40))])
    c = dict(cfg, engine=dict(cfg["engine"], max_seq=256))
    got = check.compare(c, 3, 64, SEED, list(zip(prompts, outs)))
    assert got["tokens"] == 82 and got["max_gap"] < 1e-4, got


def test_state_space_parameters_land_in_mamba2_ranges():
    a = spec.arch({"architecture": "GraniteMoeHybridForCausalLM"})
    c = np.sqrt(3.0 / 64)
    bits = jnp.asarray(np.linspace(-c, c, 64), jnp.float32)
    a_log, dt_bias = a.ssm_params(bits, bits)
    A = np.exp(np.asarray(a_log))
    dt = np.asarray(jax.nn.softplus(dt_bias))
    np.testing.assert_allclose([A.min(), A.max()], [1.0, 16.0], rtol=1e-5)
    np.testing.assert_allclose([dt.min(), dt.max()], [1e-3, 0.1], rtol=1e-4)
    assert (np.diff(A) > 0).all() and (np.diff(dt) > 0).all()


def test_counts_at_the_published_widths():
    cfg = spec.cell(CELL)["config"]
    a = spec.arch(cfg)
    mamba = 2048 * 8512 + 4096 * 2048                    # in-, out-proj
    attn = 2048 * 2048 * 2 + 2048 * 512 * 2
    mlp = 3 * 2048 * 8192
    matmul = 36 * mamba + 4 * attn + 40 * mlp
    head = 2048 * 100352
    small = 36 * (2 * (4 * 4352 + 4352 + 4096) + 4 * 3 * 64)
    assert a.weight_bytes(cfg) == (2 * (matmul + head) + small
                                   + 2 * (80 * 2048 + 2048))
    assert a.kv_bytes_per_position(cfg) == 4 * 2 * 8 * 64 * 2 == 8192
    # 36 layers of conv tail (3 x 4352 bf16) and SSM state (64x64x128 f32)
    assert a.state_bytes_per_slot(cfg) == 36 * (3 * 4352 * 2
                                                + 64 * 64 * 128 * 4)
    base = a.decode_step_bytes(cfg, [100, 300])
    live = types.SimpleNamespace(name="serve.ssm_state",
                                 args={"slots": 2, "layers": 36,
                                       "bytes_per_slot":
                                       a.state_bytes_per_slot(cfg)})
    assert a.decode_step_bytes(cfg, [100, 300], None, [live]) == (
        base + 2 * 2 * a.state_bytes_per_slot(cfg))
    ssd_step = 5 * 64 * 64 * 128 + 2 * 4 * 4352
    assert a.decode_step_flops(cfg, [10]) == (2 * (matmul + head)
                                              + 36 * ssd_step
                                              + 4 * 4 * 32 * 64 * 10)
    # one full SSD chunk of 256 and a partial one of 44
    pairs = lambda q: q * (q + 1) // 2  # noqa: E731
    ssd = sum(2 * pairs(q) * 128 + 2 * pairs(q) * 64 * 64
              + 4 * q * 128 * 64 * 64 for q in (256, 44))
    assert a.prefill_flops(cfg, 300) == (2 * matmul * 300 + 2 * head
                                         + 4 * 2 * 32 * 64 * 300 * 301
                                         + 36 * (ssd + 300 * 2 * 4 * 4352))
