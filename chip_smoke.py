"""Smoke run of the system on a TPU, through the entry points a user calls.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip path only

One chip: serves qwen3-4b at full width (random bf16 weights from a fixed
seed) through ``repro.launch.serve`` with the paged ``ContinuousEngine``,
holds ``Model.prefill``'s last-position logits to ``Model.forward``'s, and
teacher-forces every emitted token back through ``Model.forward``: each
must be within ``LOGIT_RTOL`` of the top logit at its position.  The dense
``BatchedEngine`` serves the same requests and is held to the same check;
where the two engines part, the top-2 logit margin there is printed.
Then the six ``dpia-pallas`` ops and the hand-written matmul, rmsnorm and
flash-attention kernels run compiled for the chip (``interpret=False``)
against ``repro.kernels.ref``, and the paged decode kernel against the jnp
attention over the gathered view it replaces, at the page pools of the
benchmark's two cells; a decode step's attention over every layer is then
timed at the kernel's block of pages and at twice and four times it.

``--chips 4``: ``ShardedEngine`` over a ``data=4`` mesh serves the same
traffic greedily, with its decode state on all four chips, next to
``ContinuousEngine`` on the first chip at the same 8 slots and at the 2
slots each chip decodes in the mesh.  Every engine's tokens are
teacher-forced as above, so a token routed to the wrong slot or read from
the wrong cache fails the run.  At 2 slots the sharded engine's tokens
must be identical; where it parts from the 8-slot engine, the first
divergence and its top-2 margin are printed.  ``dpia-shardmap`` dot and
matmul run on that mesh against the reference.

Every phase fails the run if the kernel layer degraded or fell back
(``kernels.degradations`` / ``kernels.fallbacks``).  The times printed are
smoke timings with compilation included, not benchmark numbers.  The last
line of standard output is ``{"ok": true, "device": {...}}``; any failure
exits non-zero without it, and so does a host where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
ARCH = "qwen3_4b"
PROMPT_LENS = (64, 80, 96, 128, 160, 200, 256, 320,
               384, 448, 512, 640, 768, 896, 960, 1024)
MAX_NEW = 32
MAX_SEQ = 2048
SLOTS = 8
# prefill's last-position logits vs forward's, bf16 weights and activations:
# max |difference| <= LOGIT_RTOL * max |forward logit|; and each greedy
# token's forward logit is at most LOGIT_RTOL * max |logit| below the top
LOGIT_RTOL = 0.05
# dpia-pallas op sizes: an n = 2^22 float32 operand (16 MiB) cannot sit
# whole in VMEM; matmul at a qwen3-4b MLP width
OP_SIZES = {"n": 1 << 22, "mm": (1024, 2560, 9728), "rows": 4096, "d": 2560}
# hand-written kernels at qwen3-4b serving shapes (a 1024-token prefill)
KERNEL_SIZES = {"tokens": 1024, "d": 2560, "ff": 9728, "heads": 32,
                "kv_heads": 8, "head_dim": 128}
# paged decode at the pools of the benchmark's cells (bench/configs): 8
# slots, 32 query heads of 128, 16-position pages; qwen3-4b's 36 layers of
# 528 pages and 8 kv heads over 2048 positions, yi-9b-l24's 24 of 2048 and
# 4 over 4096
PAGED_SIZES = ({"name": "qwen3-4b", "layers": 36, "pages": 528,
                "kv_heads": 8, "max_seq": 2048},
               {"name": "yi-9b-l24", "layers": 24, "pages": 2048,
                "kv_heads": 4, "max_seq": 4096})
PAGED_SLOTS, PAGED_HEADS, PAGED_HEAD_DIM, PAGED_BLOCK = 8, 32, 128, 16
# a decode step's slot lengths, as shares of max_seq: six busy slots at
# 22-40% of it, two parked
STEP_LEN_SHARES = (0.28, 0.34, 0.22, 0.4, 0.3, 0.34, 0.0, 0.0)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileClock:
    """Seconds JAX spends in backend compilation, from its monitoring
    events."""

    def __init__(self):
        import jax.monitoring
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, *args, **kwargs):
        if event.endswith("backend_compile_duration"):
            self.total += secs

    def phase(self, name: str, since: float, t0: float) -> None:
        log(f"{name}: compile {self.total - since:.3f} s, wall "
            f"{time.perf_counter() - t0:.3f} s (smoke timings)")


def check_kernel_counters(phase: str) -> None:
    from repro import obs
    bad = {n: obs.counter(n).value
           for n in ("kernels.degradations", "kernels.fallbacks")}
    log(f"{phase}: " + ", ".join(f"{k}={v}" for k, v in bad.items()))
    if any(bad.values()):
        fail(f"{phase}: the kernel layer degraded or fell back: {bad}")


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        fail(f"shape {got.shape} != reference {want.shape}")
    if not np.all(np.isfinite(got)):
        fail("non-finite output")
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def kv_blocks(lens) -> int:
    """A page pool for what the traffic can reserve: every slot at the
    longest prompt plus its new tokens, in 16-position pages."""
    return SLOTS * -(-(max(lens) + MAX_NEW) // 16)


def check_tokens(outs, reqs, vocab: int, what: str) -> None:
    if len(outs) != len(reqs):
        fail(f"{what}: {len(outs)} results for {len(reqs)} requests")
    for i, o in enumerate(outs):
        if len(o) != MAX_NEW or not all(0 <= t < vocab for t in o):
            fail(f"{what}: request {i} gave {len(o)} tokens {o[:8]}...")


@functools.lru_cache(maxsize=None)
def _scorer(model):
    """Jitted: for the ``MAX_NEW`` positions from ``start``, each emitted
    token's gap to the top forward logit, the top-2 margin and max |logit|."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def score(params, toks, start, emitted):
        logits = jax.lax.dynamic_slice_in_dim(
            model.forward(params, toks)[0], start, MAX_NEW
        ).astype(jnp.float32)
        top2 = jax.lax.top_k(logits, 2)[0]
        got = jnp.take_along_axis(logits, emitted[:, None], 1)[:, 0]
        return (top2[:, 0] - got, top2[:, 0] - top2[:, 1],
                jnp.max(jnp.abs(logits), axis=1))
    return score


def teacher_force(model, params, reqs, outs, what: str):
    """Run each request's prompt and emitted tokens through ``Model.forward``
    and fail unless every emitted token's logit is within ``LOGIT_RTOL`` x
    max |logit| of the top logit at the position that emitted it.  Returns,
    per request, the gaps to the top logit and the top-2 margins."""
    import jax.numpy as jnp
    import numpy as np

    score = _scorer(model)
    scored, argmax, worst = [], 0, 0.0
    for i, (r, o) in enumerate(zip(reqs, outs)):
        n = r.prompt.shape[0]
        seq = np.concatenate([np.asarray(r.prompt), np.asarray(o[:-1])])
        # right-pad to a power of two (causal: padding changes no logit)
        toks = np.zeros((1, 1 << (len(seq) - 1).bit_length()), np.int32)
        toks[0, :len(seq)] = seq
        gap, margin, scale = map(np.asarray, score(
            params, jnp.asarray(toks), n - 1, jnp.asarray(o, jnp.int32)))
        scored.append((gap, margin))
        argmax += int(np.sum(gap == 0))
        worst = max(worst, float(np.max(gap / scale)))
        j = int(np.argmax(gap / scale))
        if gap[j] > LOGIT_RTOL * scale[j]:
            fail(f"{what}: request {i} new token {j} is {gap[j]:.6g} below "
                 f"the top forward logit (max |logit| {scale[j]:.6g})")
    log(f"{what}: teacher-forced through Model.forward, {argmax} of "
        f"{len(reqs) * MAX_NEW} tokens are the forward argmax; largest gap "
        f"to the top logit {worst:.6g} of max |logit| (tolerance "
        f"{LOGIT_RTOL})")
    return scored


def report_divergences(what: str, outs, ref, scored, scored_ref,
                       require: bool = False) -> int:
    """Print where each request of ``outs`` first parts from ``ref``, with
    the forward top-2 margin there; returns how many are identical.  With
    ``require``, any divergence fails the run."""
    same = 0
    for i, (a, b) in enumerate(zip(outs, ref)):
        if a == b:
            same += 1
            continue
        j = next(k for k, (u, v) in enumerate(zip(a, b)) if u != v)
        log(f"{what}: request {i} parts at new token {j}: top-2 forward "
            f"logit margin {scored_ref[i][1][j]:.6g}; its token is "
            f"{scored[i][0][j]:.6g} below the top, the reference's "
            f"{scored_ref[i][0][j]:.6g}")
    log(f"{what}: same tokens for {same} of {len(ref)} requests")
    if require and same != len(ref):
        fail(f"{what}: tokens differ for {len(ref) - same} requests")
    return same


def phase_serve(clock: CompileClock, *, smoke: bool = False,
                lens=PROMPT_LENS) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve as serve_mod

    c0, t0 = clock.total, time.perf_counter()
    model, params = serve_mod.load_model(ARCH, smoke=smoke, seed=SEED)
    cfg = model.cfg
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}, vocab {cfg.vocab}, "
        f"{nbytes} parameter bytes ({cfg.dtype})")
    reqs = serve_mod.make_requests(cfg, lens, MAX_NEW, seed=SEED)
    engine = serve_mod.make_engine(model, params, "continuous",
                                   max_seq=MAX_SEQ, slots=SLOTS,
                                   kv_layout="paged",
                                   kv_blocks=kv_blocks(lens))
    outs, dt = serve_mod.serve(engine, reqs, seed=SEED)
    check_tokens(outs, reqs, cfg.vocab, "ContinuousEngine(paged)")
    st = engine.stats()
    log(f"ContinuousEngine(kv_layout=paged, slots={SLOTS}, max_seq="
        f"{MAX_SEQ}, kv_blocks={kv_blocks(lens)}): {len(outs)} requests, "
        f"prompts {min(lens)}-{max(lens)}, {sum(map(len, outs))} tokens in "
        f"{dt:.3f} s (compilation included); decode compiles "
        f"{st['decode_compiles']}, prefill entries {st['prefill_entries']}")
    del engine
    gc.collect()
    clock.phase("serve", c0, t0)

    # prefill's last-position logits vs the full forward pass
    c0, t0 = clock.total, time.perf_counter()
    k = len(lens) // 2
    toks = reqs[k].prompt[None]
    last, _ = jax.jit(model.prefill)(
        params, toks, model.init_cache(1, toks.shape[1]))
    full = jax.jit(model.forward)(params, toks)[:, -1]
    last = np.asarray(last.astype(jnp.float32))
    full = np.asarray(full.astype(jnp.float32))
    err = float(np.max(np.abs(last - full)))
    scale = float(np.max(np.abs(full)))
    log(f"prefill vs forward logits ({toks.shape[1]} tokens): max |diff| "
        f"{err:.6g}, max |logit| {scale:.6g}, tolerance "
        f"{LOGIT_RTOL} x max |logit|")
    if not np.all(np.isfinite(last)) or err > LOGIT_RTOL * scale:
        fail("prefill logits disagree with forward logits")
    clock.phase("prefill-vs-forward", c0, t0)

    # every greedy token against the forward pass, then the dense static
    # engine on the same requests, four at a time
    c0, t0 = clock.total, time.perf_counter()
    scored = teacher_force(model, params, reqs, outs,
                           "ContinuousEngine(paged)")
    order = sorted(range(len(reqs)), key=lambda i: lens[i])
    static = [None] * len(reqs)
    for g in range(0, len(order), 4):
        idx = order[g:g + 4]
        engine = serve_mod.make_engine(model, params, "static",
                                       max_seq=MAX_SEQ)
        got, _ = serve_mod.serve(engine, [reqs[i] for i in idx], seed=SEED)
        for j, i in enumerate(idx):
            static[i] = got[j]
        del engine
    check_tokens(static, reqs, cfg.vocab, "BatchedEngine(dense)")
    report_divergences(
        "BatchedEngine(dense) vs ContinuousEngine(paged)", static, outs,
        teacher_force(model, params, reqs, static, "BatchedEngine(dense)"),
        scored)
    clock.phase("greedy checks and static engine", c0, t0)
    check_kernel_counters("serve")


def paged_decode_check(shape: dict, close, interpret: bool) -> None:
    """The paged decode kernel against the jnp decode attention over the
    gathered view (``models.attention._attend_token``'s math) at one pool:
    lengths 1, 15, 16, 17, mid and full, a sentinel-padded tail and a
    parked lane, at two layers other than 0; then a decode step's
    attention over every layer at ``STEP_LEN_SHARES``, timed at the
    kernel's pages per block and at two and four times as many."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import paged_decode
    from repro.models.attention import gather_paged_view

    L, nb, nkv = shape["layers"], shape["pages"], shape["kv_heads"]
    b, nh, hd, bs = PAGED_SLOTS, PAGED_HEADS, PAGED_HEAD_DIM, PAGED_BLOCK
    mb = shape["max_seq"] // bs
    ks = jax.random.split(jax.random.PRNGKey(SEED + 1), 4)
    pool = (L, nb, bs, nkv, hd)
    kp = jax.random.normal(ks[0], pool, jnp.bfloat16)
    vp = jax.random.normal(ks[1], pool, jnp.bfloat16)
    q = jax.random.normal(ks[2], (b, nh, hd), jnp.bfloat16)
    qg = q.reshape(b, nkv, nh // nkv, hd)
    tail = mb * bs // 4 + 3
    bt = jax.random.randint(ks[3], (b, mb), 0, nb, jnp.int32)
    bt = bt.at[6, -(-tail // bs):].set(nb).at[7].set(nb)
    lengths = jnp.asarray([1, 15, 16, 17, mb * bs // 2 + 5, mb * bs, tail,
                           1], jnp.int32)

    def view_attention(layer):
        vk, vv = gather_paged_view(kp[layer][None], vp[layer][None], bt)
        s = jnp.einsum("bngh,btnh->bngt", qg, vk[0],
                       preferred_element_type=jnp.float32) / hd ** 0.5
        valid = jnp.arange(mb * bs) < lengths[:, None, None, None]
        p = jax.nn.softmax(jnp.where(valid, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bngt,btnh->bngh", p.astype(vv.dtype), vv[0],
                       preferred_element_type=jnp.float32)
        return o.reshape(b, nh, hd)

    for layer in (1, L - 1):
        close(f"pallas paged_decode_attention ({shape['name']}, layer "
              f"{layer})",
              paged_decode.paged_decode_attention(q, kp, vp, jnp.int32(layer),
                                                  lengths, bt),
              lambda: view_attention(layer), 2e-2)

    lens = jnp.asarray([max(1, round(f * shape["max_seq"]))
                        for f in STEP_LEN_SHARES], jnp.int32)
    valid_bytes = int(lens.sum()) * L * 2 * nkv * hd * kp.dtype.itemsize
    reps = 1 if interpret else 20
    ppb0 = paged_decode._pages_per_block(bs)
    for ppb in (ppb0, 2 * ppb0, 4 * ppb0):
        call = functools.partial(paged_decode._call, ppb=ppb,
                                 interpret=interpret)

        @jax.jit
        def step(qg, kp, vp, lens, bt):
            def layer(c, li):
                return c + call(qg, kp, vp, li, lens, bt).sum(), None
            return jax.lax.scan(layer, jnp.float32(0), jnp.arange(L))[0]

        out = step(qg, kp, vp, lens, bt)
        if not np.isfinite(float(out)):
            fail(f"paged decode step at {ppb} pages per block: non-finite")
        t = time.perf_counter()
        for _ in range(reps):
            out = step(qg, kp, vp, lens, bt)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t) / reps * 1e3
        log(f"pallas paged_decode_attention ({shape['name']}): a decode "
            f"step over {L} layers at lengths {[int(n) for n in lens]}, "
            f"{ppb} pages per block: {ms:.3f} ms, "
            f"{valid_bytes / ms / 1e6:.1f} GB/s of {valid_bytes} valid KV "
            f"bytes")


def phase_kernels(clock: CompileClock, sizes=OP_SIZES,
                  ksizes=KERNEL_SIZES, psizes=PAGED_SIZES,
                  interpret: bool = False) -> list:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import compiler
    from repro.kernels import ops, ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.matmul import matmul
    from repro.kernels.rmsnorm import rmsnorm

    c0, t0 = clock.total, time.perf_counter()
    key = jax.random.PRNGKey(SEED)
    ks = jax.random.split(key, 8)
    n = sizes["n"]
    m, kk, nn = sizes["mm"]
    rows, d = sizes["rows"], sizes["d"]
    x = jax.random.normal(ks[0], (n,), jnp.float32)
    y = jax.random.normal(ks[1], (n,), jnp.float32)
    a = jax.random.normal(ks[2], (m, kk), jnp.float32)
    b = jax.random.normal(ks[3], (kk, nn), jnp.float32)
    xr = jax.random.normal(ks[4], (rows, d), jnp.float32)
    w = jax.random.normal(ks[5], (d,), jnp.float32)
    alpha = jnp.float32(1.5)

    def close(name, got, want, tol):
        with jax.default_matmul_precision("highest"):
            want = want()
        e = rel_err(got, want)
        log(f"{name}: max error {e:.3g} of max |reference| "
            f"(tolerance {tol:g})")
        if e > tol:
            fail(f"{name} disagrees with the reference")

    ran = []
    with compiler.options(autotune=False, interpret=interpret):
        for name, got, want, tol in (
                ("dot", lambda: ops.dot(x, y, impl="dpia-pallas"),
                 lambda: ref.dot(x, y), 1e-4),
                ("asum", lambda: ops.asum(x, impl="dpia-pallas"),
                 lambda: ref.asum(x), 1e-5),
                ("scal", lambda: ops.scal(alpha, x, impl="dpia-pallas"),
                 lambda: ref.scal(alpha, x), 1e-6),
                ("matmul", lambda: ops.matmul(a, b, impl="dpia-pallas"),
                 lambda: ref.matmul(a, b), 1e-2),
                ("rmsnorm", lambda: ops.rmsnorm(xr, w, impl="dpia-pallas"),
                 lambda: ref.rmsnorm(xr, w), 1e-5),
                ("softmax", lambda: ops.softmax(xr, impl="dpia-pallas"),
                 lambda: ref.softmax(xr), 1e-5)):
            close(f"dpia-pallas {name}", got(), want, tol)
            ran.append(name)
    check_kernel_counters("dpia-pallas ops")

    s, dm, ff = ksizes["tokens"], ksizes["d"], ksizes["ff"]
    h, kvh, hd = ksizes["heads"], ksizes["kv_heads"], ksizes["head_dim"]
    bf = jnp.bfloat16
    act = jax.random.normal(ks[6], (s, dm), bf)
    wmm = (jax.random.normal(ks[7], (dm, ff), jnp.float32) / dm ** 0.5
           ).astype(bf)
    wn = (1 + 0.1 * jax.random.normal(ks[2], (dm,), jnp.float32)).astype(bf)
    q = jax.random.normal(ks[0], (h, s, hd), bf)
    kv = jax.random.normal(ks[1], (2, kvh, s, hd), bf)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    close("pallas matmul (bf16)", matmul(act, wmm, interpret=interpret),
          lambda: ref.matmul(f32(act), f32(wmm)), 2e-2)
    close("pallas rmsnorm (bf16)", rmsnorm(act, wn, interpret=interpret),
          lambda: ref.rmsnorm(f32(act), f32(wn)), 2e-2)
    close("pallas flash_attention (bf16, causal)",
          flash_attention(q, kv[0], kv[1], interpret=interpret),
          lambda: ref.flash_attention(f32(q), f32(kv[0]), f32(kv[1])), 2e-2)
    for shape in psizes:
        paged_decode_check(shape, close, interpret)
        gc.collect()
    ran += ["pallas matmul", "pallas rmsnorm", "pallas flash_attention",
            "pallas paged_decode_attention"]
    check_kernel_counters("pallas kernels")
    clock.phase("kernels", c0, t0)
    return ran


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_four_chips(clock: CompileClock, *, smoke: bool = False,
                     lens=PROMPT_LENS, sizes=OP_SIZES) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from repro import compiler
    from repro.kernels import ops, ref
    from repro.launch import serve as serve_mod
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import ContinuousEngine, ShardedEngine

    c0, t0 = clock.total, time.perf_counter()
    devs = jax.devices()
    if len(devs) < 4:
        fail(f"--chips 4 needs four devices, JAX has {len(devs)}")
    mesh = make_mesh((4,), ("data",), devs[:4])
    rep = NamedSharding(mesh, PS())
    # one replicated copy of the weights; the single-chip engines read
    # chip 0's shard of it, so no chip holds the weights twice
    model, params = serve_mod.load_model(ARCH, smoke=smoke, seed=SEED,
                                         sharding=rep)
    params0 = jax.tree_util.tree_map(
        lambda p: next(s.data for s in p.addressable_shards
                       if s.device == devs[0]), params)
    cfg = model.cfg
    reqs = serve_mod.make_requests(cfg, lens, MAX_NEW, seed=SEED)
    sh = ShardedEngine(model, params, max_seq=MAX_SEQ, slots=SLOTS,
                       mesh=mesh)
    got, dt = serve_mod.serve(sh, reqs, seed=SEED)
    check_tokens(got, reqs, cfg.vocab, "ShardedEngine")
    n_dev = len(sh.tokens.sharding.device_set)
    log(f"ShardedEngine(data=4, slots={SLOTS}): {dt:.3f} s (compilation "
        f"included); decode state on {n_dev} devices")
    if n_dev != 4:
        fail(f"ShardedEngine decode state is on {n_dev} devices, not 4")
    del sh
    gc.collect()
    # the same traffic on chip 0 alone: at the engine's 8 slots, and at the
    # 2 slots each chip of the mesh decodes.  At 2 slots the per-chip decode
    # batch is the same, so the tokens must be identical; at 8 the bf16
    # rounding of the batch differs and greedy decoding may part at a
    # near-tie, which is reported
    single = {}
    for slots in (SLOTS, SLOTS // 4):
        engine = ContinuousEngine(model, params0, max_seq=MAX_SEQ,
                                  slots=slots)
        single[slots], dt = serve_mod.serve(engine, reqs, seed=SEED)
        check_tokens(single[slots], reqs, cfg.vocab,
                     f"ContinuousEngine(slots={slots})")
        log(f"ContinuousEngine(slots={slots}) on {devs[0]}: {dt:.3f} s "
            f"(compilation included)")
        del engine
        gc.collect()
    scored = teacher_force(model, params0, reqs, got,
                           "ShardedEngine(data=4)")
    for slots, want in single.items():
        report_divergences(
            f"ShardedEngine(data=4) vs ContinuousEngine(slots={slots})",
            got, want, scored,
            teacher_force(model, params0, reqs, want,
                          f"ContinuousEngine(slots={slots})"),
            require=slots == SLOTS // 4)
    clock.phase("sharded serve", c0, t0)
    check_kernel_counters("sharded serve")

    c0, t0 = clock.total, time.perf_counter()
    del params, params0
    gc.collect()
    key = jax.random.split(jax.random.PRNGKey(SEED), 4)
    n = sizes["n"]
    m, kk, nn = sizes["mm"]
    x = jax.random.normal(key[0], (n,), jnp.float32)
    y = jax.random.normal(key[1], (n,), jnp.float32)
    a = jax.random.normal(key[2], (m, kk), jnp.float32)
    b = jax.random.normal(key[3], (kk, nn), jnp.float32)
    with compiler.options(backend="dpia-shardmap", mesh=mesh,
                          autotune=False):
        got_v = {"dot": ops.dot(x, y), "matmul": ops.matmul(a, b)}
    with jax.default_matmul_precision("highest"):
        want_v = {"dot": ref.dot(x, y), "matmul": ref.matmul(a, b)}
    for name, tol in (("dot", 1e-4), ("matmul", 1e-2)):
        e = rel_err(got_v[name], want_v[name])
        log(f"dpia-shardmap {name} on data=4: max error {e:.3g} of max "
            f"|reference| (tolerance {tol:g})")
        if e > tol:
            fail(f"dpia-shardmap {name} disagrees with the reference")
    check_kernel_counters("dpia-shardmap ops")
    clock.phase("dpia-shardmap", c0, t0)


# ---------------------------------------------------------------------------

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no repro package under {src}: run from a checkout")
    sys.path.insert(0, src)
    # a fresh tuning cache under the checkout: nothing tuned or staged on
    # another machine or platform is read
    smoke_dir = os.path.join(ROOT, ".smoke")
    os.makedirs(smoke_dir, exist_ok=True)
    cache = os.path.join(smoke_dir, "autotune.json")
    if os.path.exists(cache):
        os.remove(cache)
    os.environ["REPRO_AUTOTUNE_CACHE"] = cache

    import jax
    from repro.launch import compile_cache

    try:
        dev = jax.devices()[0]
    except RuntimeError as e:
        fail(f"JAX found no device: {e}")
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's first device is {dev.platform!r}")
    log(f"compile cache: {compile_cache.enable()}")
    log(f"device: platform {dev.platform}, device_kind {dev.device_kind}, "
        f"count {len(jax.devices())}")
    clock = CompileClock()

    if args.chips == 4:
        phase_four_chips(clock)
    else:
        phase_serve(clock)
        ran = phase_kernels(clock)
        log(f"compiled for the chip and checked: {', '.join(ran)}")
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} on {dev}; "
        f"compile {clock.total:.3f} s; wall "
        f"{time.perf_counter() - t_start:.3f} s (smoke timings, not "
        f"benchmark numbers)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
