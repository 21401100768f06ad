"""Serving engines: the decode fast path.

The decode hot loop runs entirely on device: a jitted ``lax.scan`` advances
``chunk`` tokens per call with sampling (per-request temperature + top-k)
fused into the step, the KV cache and the token/position/key buffers donated
(``donate_argnums``) so decode is copy-free, and the host syncs exactly once
per chunk — it reads the ``(batch, chunk)`` token block after the scan, never
an individual token.

Two engines share that core:

  * :class:`BatchedEngine` — static batch: prefill all requests together,
    decode lock-step until every request has its tokens (the oracle the
    continuous engine is tested against).
  * :class:`ContinuousEngine` — continuous batching over a fixed number of
    device slots: requests are admitted into free slots and retired at chunk
    boundaries (:mod:`repro.serve.scheduler`), prompts are right-padded to
    power-of-two buckets and the decode batch is always ``slots`` wide, so
    jit sees a small closed set of shapes — zero recompiles after one pass
    over the buckets.  KV memory is a strategy dimension
    (``kv_layout="dense"|"paged"|"auto"``, :mod:`repro.serve.paged`) and
    long prompts prefill in chunks across boundaries (``prefill_chunk=``),
    capping the bucket set.
  * :class:`ShardedEngine` — the same continuous engine with the slot axis
    sharded over a named mesh axis (``data``): device state carries
    ``NamedSharding`` placements and GSPMD partitions the identical jitted
    chunk, so decode runs data-parallel and stays token-identical.  With
    ``hosts=`` the mesh's devices partition into failure domains
    (:mod:`repro.serve.domains`): a host lost or straggling at a chunk
    boundary evacuates its slots back to the queue, shrinks the mesh onto
    the survivors, and records the shrink as a ``degraded(mesh(a)->mesh(b))``
    provenance origin — survivors and evacuees alike stay token-identical.

Every engine can keep a scheduler-state **journal** (``journal=`` path,
:class:`repro.serve.domains.SchedulerJournal`): submissions, per-boundary
emitted-token snapshots, and terminal states, append-only and per-record
checksummed, so a crashed/killed engine's surviving requests
``domains.replay`` to token identity in a fresh process.

Sampling determinism: each request's PRNG stream is
``fold_in(run_key, request_index)`` advanced once per sampled token, so the
tokens a request receives are a function of the request alone — independent
of which other requests share the batch, of slot assignment, and of chunk
size.  That is what makes continuous-batching output token-identical to the
static oracle.

Engines with a ``tuning_cache`` pre-tune the strategy autotuner for the
model's kernel shapes at build time, stage the corresponding executors, and
persist them ahead-of-time next to the tuning cache
(``repro.compiler.executor_cache().save_aot``) — a restarted engine loads
the lowered programs and skips Stage I->II entirely.  ``run`` scopes the
``repro.kernels.ops`` dispatch to that cache thread-locally via
``repro.compiler.options(tuning_cache=...)``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.ft.resilience import Watchdog
from repro.models import attention as attn_mod
from repro.models.transformer import Model
from repro.serve.resilience import (RequestResult, ResilienceConfig,
                                    record_degradation)
from repro.serve.scheduler import Scheduler, pick_bucket, seq_buckets
from repro.testing import faults

log = logging.getLogger("repro.serve.engine")

__all__ = ["Request", "BatchedEngine", "ContinuousEngine", "ShardedEngine",
           "sample", "sample_tokens"]


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def sample(logits, key, *, temperature: float = 0.0, top_k: int = 0):
    """Single-temperature sampling (whole batch shares the knobs).

    ``temperature <= 0`` is greedy argmax.  ``top_k > 0`` keeps the k
    largest logits per row; values tied with the k-th largest are all kept
    (the cutoff is a >=-threshold, not a count), and ``top_k >= vocab`` is a
    no-op."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


def sample_tokens(logits, keys, temps, top_ks):
    """Per-request sampling, vectorised over the batch — the form fused into
    the decode chunk.

    logits (b, vocab) f32; keys (b, 2) per-slot PRNG keys; temps (b,) f32
    (``<= 0`` means greedy for that row); top_ks (b,) int32 (``0`` means no
    top-k filter).  Same per-row semantics as :func:`sample`.

    The expensive paths are gated on runtime predicates (``lax.cond``), so
    an all-greedy batch pays an argmax and nothing else — no full-vocab
    sort, no gumbel draw — even though the same compiled chunk serves every
    temperature mix."""
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1)

    def with_topk(scaled):
        desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        k = jnp.clip(jnp.where(top_ks > 0, top_ks, vocab), 1, vocab)
        kth = jnp.take_along_axis(desc, (k - 1)[:, None], axis=-1)
        return jnp.where(scaled < kth, -jnp.inf, scaled)

    def sampled(_):
        t = jnp.maximum(temps, 1e-6)[:, None]
        scaled = logits / t
        masked = jax.lax.cond(jnp.any((top_ks > 0) & (temps > 0.0)),
                              with_topk, lambda s: s, scaled)
        return jax.vmap(lambda kk, row: jax.random.categorical(kk, row))(
            keys, masked)

    toks = jax.lax.cond(jnp.any(temps > 0.0), sampled,
                        lambda _: greedy, None)
    return jnp.where(temps <= 0.0, greedy, toks).astype(jnp.int32)


def _split_keys(keys):
    """Advance a (b, 2) batch of PRNG keys one step: (carry, subkeys)."""
    pairs = jax.vmap(lambda k: jax.random.split(k))(keys)
    return pairs[:, 0], pairs[:, 1]


def _slot_axis(big, small) -> Optional[int]:
    """The slot/batch axis of a cache leaf: the unique axis where the
    1-slot shape differs from the engine shape (None when slots == 1, i.e.
    the slot IS the cache).  Works on every cache pytree leaf (dense
    KVCache, rwkv states, the hybrid mamba+kv dict) — shared by slot
    insertion and by ShardedEngine's sharding specs so the two can never
    disagree on which axis is the batch."""
    return next((i for i, (a, c) in enumerate(zip(big.shape, small.shape))
                 if a != c), None)


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    prompt: jnp.ndarray          # (s,) or (s, K)
    max_new_tokens: int = 32
    temperature: float = 0.0
    top_k: int = 0
    out_tokens: Optional[List[int]] = None
    # per-request deadlines, both measured from submission: ``deadline_s``
    # is end-to-end, ``ttft_deadline_s`` applies until the first token.
    # Enforced at chunk boundaries (the host never sees mid-chunk time);
    # an expired request ends in terminal state "timeout" with its partial
    # tokens intact (repro.serve.resilience.STATES).
    deadline_s: Optional[float] = None
    ttft_deadline_s: Optional[float] = None


def decode_attention(model: Model, cache, block_tables) -> str:
    """How the decode chunk attends: ``"paged_kernel"`` where the cache's
    KV pool lives on one TPU device
    (:func:`repro.models.attention.paged_kernel_engages`), ``"view"`` for
    other paged pools (the chunk gathers the per-slot view), ``"dense"``
    without block tables or KV pools."""
    kv = None if block_tables is None else model.split_paged_cache(cache)[0]
    if kv is None:
        return "dense"
    return ("paged_kernel" if attn_mod.paged_kernel_engages(kv.k)
            else "view")


def prefill_attention(model: Model, params, bucket: int,
                      first: bool) -> Optional[str]:
    """How a prefill chunk of ``bucket`` positions attends
    (:func:`repro.models.attention.prefill_attention`): a first chunk
    attends within itself, through ``"flash_kernel"`` where the weights
    live on one TPU device and the bucket tiles, else ``"chunked"`` or
    ``"dense"``; a continuation chunk attends over the slot's cached
    positions, ``"dense"``.  None for a model without attention."""
    if model.cfg.family == "ssm":
        return None
    if not first:
        return "dense"
    sharding = getattr(jax.tree_util.tree_leaves(params)[0], "sharding",
                       None)
    devices = (sharding.device_set if sharding is not None
               else {jax.devices()[0]})
    return attn_mod.prefill_attention(model.cfg, bucket,
                                      next(iter(devices)).platform,
                                      len(devices))


class _DecodeChunk:
    """The jitted decode chunk, one program per attention path.  A call
    takes the path as the static ``paged_kernel`` (False: the view or dense
    program), chosen by its caller once per chunk; ``lower`` chooses it
    from the arguments' placement (:func:`decode_attention`), so a compile
    for a described chip gets the program the chip would run."""

    def __init__(self, fn, model: Model):
        self.__wrapped__ = fn
        self._model = model
        # cache + token/pos/key buffers are donated: decode is copy-free
        # and the engine rebinds the returned buffers each chunk.  ``bt``
        # (the block tables; None for dense layouts) is tiny and read-only
        self._jit = jax.jit(fn, donate_argnums=(1, 2, 3, 4),
                            static_argnames=("paged_kernel",))

    def __call__(self, *args, paged_kernel: bool = False):
        return self._jit(*args, paged_kernel=paged_kernel)

    def lower(self, *args):
        attn = decode_attention(self._model, args[1], args[7])
        return self._jit.lower(*args, paged_kernel=attn == "paged_kernel")

    def _cache_size(self) -> int:
        return self._jit._cache_size()


# ---------------------------------------------------------------------------
# shared engine core
# ---------------------------------------------------------------------------

class _EngineBase:
    """Model/params + the jitted fast-path functions + tuner/AOT warm-up."""

    def __init__(self, model: Model, params, *, max_seq: int, chunk: int,
                 tuning_cache=None, batch_sizes=(1, 8), aot="auto",
                 kv_layout: str = "dense",
                 resilience: Optional[ResilienceConfig] = None):
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be 'dense' or 'paged', got "
                             f"{kv_layout!r}")
        self.model = model
        self.params = params
        self.max_seq = max_seq
        self.chunk = chunk
        self.kv_layout = kv_layout
        self.tuning_cache = tuning_cache
        self.tuned: Dict[str, dict] = {}
        self.resilience = resilience or ResilienceConfig()
        # chunk-level straggler detection reuses the hardened train-loop
        # Watchdog (its disarm race is fixed — a chunk finishing just under
        # the deadline can no longer record a spurious straggler)
        self._watchdog: Optional[Watchdog] = None
        if self.resilience.chunk_deadline_s is not None:
            self._watchdog = Watchdog(self.resilience.chunk_deadline_s,
                                      on_straggler=self._on_straggler)
        self._n_chunk_calls = 0
        self._n_chunk_retries = 0
        self._n_chunk_quarantines = 0
        self._n_nan_quarantines = 0
        self._n_degradations = 0
        # recompile detector: (decode compiles, prefill entries) at the last
        # ``mark_warm()``; None until the engine declares itself warm
        self._jit_baseline = None
        self._recompiles_after_warm = 0
        self._drift_audited = False
        if tuning_cache is not None:
            self._warm(batch_sizes, aot)
        self._prefill = jax.jit(
            lambda params, tokens, cache, lengths:
            model.prefill(params, tokens, cache, lengths=lengths))
        # fresh-cache prefill: the zero cache is materialised INSIDE the
        # program, so XLA fuses the zero-init with the cache writes — no
        # host-side init_cache allocation, no input-cache copy per call
        self._prefill_fresh = jax.jit(
            lambda params, tokens, lengths:
            model.prefill(params, tokens,
                          model.init_cache(tokens.shape[0], max_seq),
                          lengths=lengths))
        self._prefill_exes: Dict[tuple, object] = {}
        self._warned_prefill_fallback = False
        self._sample0 = jax.jit(sample_tokens)
        self._chunk_fn = self._make_chunk_fn()

    # -- fused decode chunk --------------------------------------------------

    def _make_chunk_fn(self):
        model, cfg, max_seq = self.model, self.model.cfg, self.max_seq

        def chunk_fn(params, cache, tokens, pos, keys, temps, top_ks, bt,
                     paged_kernel=False):
            # paged on one TPU (``paged_kernel``): each step's attention
            # reads only each slot's valid pages, straight from the pool.
            # Other paged pools gather each slot's pages into a
            # dense-shaped view ONCE per chunk; steps attend/update the
            # view and mirror the token write into the pool
            view = (None if bt is None or paged_kernel
                    else model.gather_paged_view(cache, bt))
            bad0 = jnp.zeros(tokens.shape[:1], bool)

            def step(carry, _):
                tokens, cache, view, pos, keys, bad = carry
                tok = tokens[:, None]
                if cfg.n_codebooks:
                    tok = jnp.broadcast_to(
                        tok[..., None],
                        (tok.shape[0], 1, cfg.n_codebooks))
                if view is None:
                    logits, cache = model.decode_step(
                        params, tok, cache, pos, block_tables=bt,
                        paged_kernel=paged_kernel)
                else:
                    logits, cache, view = model.decode_step(
                        params, tok, cache, pos, block_tables=bt,
                        kv_view=view)
                # NaN guard: a per-slot poison flag, sticky across the scan.
                # Pure observation — the token dataflow is untouched, so
                # clean rows stay bitwise-identical with the guard on
                bad = bad | ~jnp.isfinite(
                    logits.reshape(logits.shape[0], -1)).all(axis=1)
                with jax.named_scope("sample"):
                    keys, sub = _split_keys(keys)
                    nxt = sample_tokens(logits, sub, temps, top_ks)
                # clamp: a retired slot keeps decoding until the boundary;
                # past max_seq its (per-slot-path) cache writes are dropped
                # (the paged path drops through the block-table sentinel)
                pos = jnp.minimum(pos + 1, max_seq)
                return (nxt, cache, view, pos, keys, bad), nxt

            (tokens, cache, view, pos, keys, bad), toks = jax.lax.scan(
                step, (tokens, cache, view, pos, keys, bad0), None,
                length=self.chunk)
            return cache, tokens, pos, keys, toks.T, bad  # toks: (b, chunk)

        return _DecodeChunk(chunk_fn, model)

    def _on_straggler(self, chunk_i: int, dt: float) -> None:
        obs.counter("serve.stragglers").inc()
        obs.event("serve.straggler", chunk=chunk_i, elapsed_s=round(dt, 4))
        log.warning("decode chunk %d exceeded the chunk deadline "
                    "(%.3fs > %.3fs) — straggler suspected", chunk_i, dt,
                    self.resilience.chunk_deadline_s)

    @staticmethod
    def _args_consumed(args) -> bool:
        """True when any donated buffer in ``args`` was consumed by a
        failed dispatch — re-invoking would read deleted buffers, so the
        retry loop must stop and the caller rebuild device state."""
        for leaf in jax.tree_util.tree_leaves(args):
            if getattr(leaf, "is_deleted", None) and leaf.is_deleted():
                return True
        return False

    def _call_chunk(self, args, req_ids: str = "",
                    paged_kernel: bool = False):
        """Invoke the fused decode chunk (on the Pallas paged-kernel path
        where ``paged_kernel``) with the resilience wrapping: the
        ``serve.slow_chunk`` / ``serve.chunk_error`` fault sites, the
        chunk-level straggler watchdog, and bounded retry-with-backoff for
        transient failures.

        ``req_ids`` (comma-joined active request ids) attributes the fault
        sites and failure events to the requests riding the chunk, so a
        drill's trace/flight-dump names who was affected.

        Retry is only safe while the donated buffers are intact — faults
        injected here fire *before* dispatch, and a dispatch that died
        after consuming its donation (:meth:`_args_consumed`) is not
        retried: the exception propagates and the continuous engine
        quarantines in-flight work + rebuilds device state."""
        rc = self.resilience
        self._n_chunk_calls += 1
        attempt = 0
        while True:
            try:
                if self._watchdog is not None:
                    self._watchdog.arm(self._n_chunk_calls)
                try:
                    f = faults.should_fire("serve.slow_chunk",
                                           req_ids=req_ids)
                    if f is not None:
                        time.sleep(float(f.value or 0.05))
                    faults.raise_if("serve.chunk_error", req_ids=req_ids)
                    if paged_kernel:   # else the plain view/dense call
                        return self._chunk_fn(*args, paged_kernel=True)
                    return self._chunk_fn(*args)
                finally:
                    if self._watchdog is not None:
                        self._watchdog.disarm()
            except Exception as e:
                attempt += 1
                obs.counter("serve.chunk_failures").inc()
                obs.event("serve.chunk_failure", attempt=attempt,
                          req_ids=req_ids,
                          error=f"{type(e).__name__}: {e}")
                if attempt > rc.max_chunk_retries or self._args_consumed(args):
                    raise
                self._n_chunk_retries += 1
                log.warning("decode chunk failed (%s: %s); retry %d/%d",
                            type(e).__name__, e, attempt,
                            rc.max_chunk_retries)
                time.sleep(rc.retry_backoff_s * attempt)

    # -- prefill: per-bucket AOT executables ---------------------------------

    def _prefill_call(self, tokens, lengths):
        """Run the fresh-cache, length-aware prefill through a PER-SHAPE
        ahead-of-time compiled executable.

        This is the admission path's fix for the PR 3 prefill regression
        (BENCH_serve.json showed fused prefill LOSING to the legacy loop):
        ``jax.jit`` dispatch re-hashed the call signature every admission,
        and every call re-padded + copied a host-initialised zero cache
        through an undonated argument.  The engine instead lowers +
        compiles once per padded-bucket shape, calls the executable
        directly, and lets the program build its own zero cache.  Falls
        back to the jitted path if the executable rejects the arguments
        (e.g. sharding drift)."""
        key = (tokens.shape, str(tokens.dtype))
        exe = self._prefill_exes.get(key)
        if exe is None:
            with obs.span("serve.prefill_compile", shape=str(tokens.shape)):
                exe = self._prefill_fresh.lower(self.params, tokens,
                                                lengths).compile()
            self._prefill_exes[key] = exe
        try:
            return exe(self.params, tokens, lengths)
        except Exception as e:
            # safe only because nothing is donated here; warn so a
            # persistent mismatch (every admission paying jit dispatch)
            # is a diagnosable regression, not an invisible one
            obs.counter("serve.prefill_fallbacks").inc()
            obs.event("serve.prefill_fallback",
                      error=f"{type(e).__name__}: {e}")
            if not self._warned_prefill_fallback:
                self._warned_prefill_fallback = True
                import warnings
                msg = (f"prefill executable rejected its arguments "
                       f"({type(e).__name__}: {e}); falling back to jit "
                       f"dispatch for this engine")
                log.warning("%s", msg)
                warnings.warn(msg, RuntimeWarning)
            return self._prefill_fresh(self.params, tokens, lengths)

    def prefill_cache_size(self) -> int:
        """Number of compiled prefill entries (AOT executables + any jitted
        continuation/paged variants) — the serving benchmark's prefill
        recompile accounting."""
        n = len(self._prefill_exes) + int(self._prefill._cache_size())
        for name in ("_prefill_cont", "_prefill_paged0", "_prefill_pagedC"):
            fn = getattr(self, name, None)
            if fn is not None:
                n += int(fn._cache_size())
        return n

    def decode_cache_misses(self) -> int:
        """Number of XLA compilations of the fused decode chunk so far (the
        'recompile count' the serving benchmark and tests watch)."""
        return int(self._chunk_fn._cache_size())

    # -- unified stats + recompile detector ----------------------------------

    def stats(self) -> dict:
        """Every number the engine exposes, in one dict.

        Supersedes poking ``decode_cache_misses()`` / ``prefill_cache_size()``
        / the executor cache / the scheduler one at a time (those accessors
        all remain).  Subclasses extend the dict; they never replace keys."""
        from repro import compiler
        return {
            "decode_compiles": self.decode_cache_misses(),
            "prefill_entries": self.prefill_cache_size(),
            "recompiles_after_warm": self._recompiles_after_warm,
            "executor_cache": compiler.executor_cache().stats(),
            "latency": self._latency_stats(),
            "resilience": {
                "chunk_retries": self._n_chunk_retries,
                "chunk_quarantines": self._n_chunk_quarantines,
                "nan_quarantines": self._n_nan_quarantines,
                "degradations": self._n_degradations,
                "stragglers": (len(self._watchdog.events)
                               if self._watchdog is not None else 0),
            },
        }

    @staticmethod
    def _latency_stats() -> dict:
        """Percentile summaries of the serving latency histograms.

        Reads the process-wide metrics registry (histograms are global, so
        numbers cover every engine in the process); only histograms with
        observations appear."""
        reg = obs.registry()
        out = {}
        for name in ("serve.queue_wait_s", "serve.ttft_s", "serve.e2e_s",
                     "serve.decode_tok_s", "serve.chunk_s"):
            h = reg.histogram(name)
            if h.count:
                out[name.split(".", 1)[1]] = {
                    "count": h.count, "mean": h.mean,
                    "p50": h.percentile(0.50),
                    "p95": h.percentile(0.95),
                    "p99": h.percentile(0.99),
                }
        return out

    def _jit_sizes(self):
        return (self.decode_cache_misses(), self.prefill_cache_size())

    def mark_warm(self) -> None:
        """Declare the jit caches warm: any growth past this point is a
        *recompile* — flagged by the detector, counted in ``stats()``.
        ``run`` calls this automatically when its first batch completes."""
        self._jit_baseline = self._jit_sizes()
        self._audit_drift()

    def _audit_drift(self) -> None:
        """Serve-boundary roofline audit: re-rank every *measured* tuning
        record for this engine's cache under the current HwModel and fire
        ``tune.drift`` on predicted-vs-measured ranking disagreement.

        Runs once per engine at the warm boundary (analytic only — builds
        exprs, compiles nothing); records without per-candidate timings
        are skipped, so analytic-only caches cost ~nothing."""
        if self.tuning_cache is None or self._drift_audited:
            return
        self._drift_audited = True
        try:
            from repro.autotune.api import _resolve_cache
            obs.audit_cache(_resolve_cache(self.tuning_cache))
        except Exception:
            log.debug("drift audit skipped", exc_info=True)

    def _check_recompiles(self) -> None:
        """Compare jit-cache sizes against the warm baseline; flag growth.

        Fires a structured obs event + a ``logging`` warning (NOT
        ``warnings.warn`` — a recompile is a performance regression, never
        an error) and advances the baseline so each growth is reported
        once."""
        if self._jit_baseline is None:
            return
        cur = self._jit_sizes()
        base = self._jit_baseline
        grew = sum(max(0, c - b) for c, b in zip(cur, base))
        if not grew:
            return
        self._recompiles_after_warm += grew
        self._jit_baseline = cur
        obs.counter("serve.recompiles_after_warm").inc(grew)
        obs.event("serve.recompile_after_warm",
                  decode_compiles=cur[0], prefill_entries=cur[1],
                  baseline_decode=base[0], baseline_prefill=base[1])
        log.warning(
            "jit cache grew after warm-up: decode compiles %d -> %d, "
            "prefill entries %d -> %d (a new shape/bucket reached the "
            "engine; warm traffic should never recompile)",
            base[0], cur[0], base[1], cur[1])

    # -- autotune + AOT warm-up ----------------------------------------------

    def _aot_dir(self, aot) -> Optional[str]:
        if aot is None or aot is False:
            return None
        if isinstance(aot, str) and aot != "auto":
            return aot
        path = getattr(self.tuning_cache, "path", None) or (
            self.tuning_cache if isinstance(self.tuning_cache, str) else None)
        return (str(path) + ".aot") if path else None

    def _warm(self, batch_sizes, aot) -> None:
        from repro import autotune, compiler
        from repro.kernels import ops
        cfg = self.model.cfg
        with obs.span("engine.warm", max_seq=self.max_seq,
                      batch_sizes=str(tuple(batch_sizes))):
            self.tuned = autotune.warm_for_model(
                cfg, max_seq=self.max_seq, cache=self.tuning_cache,
                batch_sizes=batch_sizes)
            aot_dir = self._aot_dir(aot)
            if aot_dir is None:
                return
            store = compiler.executor_cache()
            store.load_aot(aot_dir)  # a prior engine's programs: skip staging
            before = set(store.keys())
            with self._options_scope():
                for kernel, shape in autotune.model_kernel_shapes(
                        cfg, max_seq=self.max_seq, batch_sizes=batch_sizes):
                    try:
                        ops.warm_kernel(kernel, **shape)
                    except (ValueError, AssertionError):
                        continue  # shape with no valid strategy space
            # export only the keys THIS engine staged — a shared process
            # cache must not leak another model's programs into this AOT
            # directory
            store.save_aot(aot_dir, keys=set(store.keys()) - before)

    def _options_scope(self):
        """The compile-options scope this engine's kernels run under."""
        from repro import compiler
        if self.tuning_cache is None:
            return contextlib.nullcontext()
        # kv_layout is a strategy dimension: executors staged under this
        # scope carry it in their cache keys, like the mesh descriptor
        return compiler.options(tuning_cache=self.tuning_cache,
                                kv_layout=self.kv_layout)

    # -- shared pieces -------------------------------------------------------

    def _pad_prompt(self, prompt, to: int):
        """RIGHT-pad a (s[, K]) prompt with token 0 to length ``to``."""
        pad_n = to - prompt.shape[0]
        return jnp.pad(prompt, [(0, pad_n)] + [(0, 0)] * (prompt.ndim - 1))

    def _check_request(self, r: Request) -> None:
        need = int(r.prompt.shape[0]) + max(int(r.max_new_tokens), 0)
        if need > self.max_seq:
            raise ValueError(
                f"request needs {need} cache positions (prompt "
                f"{int(r.prompt.shape[0])} + {r.max_new_tokens} new) but "
                f"max_seq is {self.max_seq}")


# ---------------------------------------------------------------------------
# static batch (the oracle)
# ---------------------------------------------------------------------------

class BatchedEngine(_EngineBase):
    """Static-batch serving engine: prefill a batch of requests together,
    then decode lock-step in fused on-device chunks until every request has
    its ``max_new_tokens``.

    Each request is sampled with its *own* temperature/top-k (fixing the
    seed bug where the whole batch ran at ``requests[0].temperature``).
    Prompts are right-padded to the batch max; ``prefill(lengths=...)``
    gathers each row's real next-token logits, so padding never distorts
    positions or outputs.

    ``tuning_cache`` (a path or repro.autotune.TuningCache) pre-tunes the
    strategy autotuner for this model's kernel shapes at engine build time,
    stages the matching executors, and persists them AOT next to the cache;
    ``run`` scopes the ``repro.kernels.ops`` DPIA dispatch to that cache via
    ``repro.compiler.options(tuning_cache=...)`` — thread-local, per-engine.
    """

    def __init__(self, model: Model, params, max_seq: int = 512,
                 tuning_cache=None, batch_sizes=(1, 8), chunk: int = 8,
                 aot="auto", resilience: Optional[ResilienceConfig] = None):
        super().__init__(model, params, max_seq=max_seq, chunk=chunk,
                         tuning_cache=tuning_cache, batch_sizes=batch_sizes,
                         aot=aot, resilience=resilience)

    def run(self, requests: List[Request], key=None) -> List[List[int]]:
        with self._options_scope():
            return self._run(requests, key)

    def _run(self, requests: List[Request], key=None) -> List[List[int]]:
        cfg = self.model.cfg
        key = key if key is not None else jax.random.PRNGKey(0)
        for r in requests:
            self._check_request(r)
        b = len(requests)
        lengths = [int(r.prompt.shape[0]) for r in requests]
        s = max(lengths)
        tokens = jnp.stack([self._pad_prompt(r.prompt, s) for r in requests])
        logits, cache = self._prefill_call(tokens,
                                           jnp.asarray(lengths, jnp.int32))

        temps = jnp.asarray([r.temperature for r in requests], jnp.float32)
        top_ks = jnp.asarray([getattr(r, "top_k", 0) or 0 for r in requests],
                             jnp.int32)
        keys = jnp.stack([jax.random.fold_in(key, i) for i in range(b)])
        keys, sub = _split_keys(keys)
        first = self._sample0(logits, sub, temps, top_ks)

        outs: List[List[int]] = [[] for _ in requests]
        remaining = [max(int(r.max_new_tokens), 0) for r in requests]
        first_host = np.asarray(first)
        for i in range(b):
            if remaining[i] > 0:
                outs[i].append(int(first_host[i]))
                remaining[i] -= 1

        pos = jnp.asarray(lengths, jnp.int32)
        tokens = first
        while any(n > 0 for n in remaining):
            live = ",".join(str(i) for i, n in enumerate(remaining) if n > 0)
            cache, tokens, pos, keys, toks, _bad = self._call_chunk(
                (self.params, cache, tokens, pos, keys, temps, top_ks, None),
                req_ids=live)
            block = np.asarray(toks)          # the chunk's one host sync
            for i in range(b):
                take = min(remaining[i], block.shape[1])
                outs[i].extend(int(t) for t in block[i, :take])
                remaining[i] -= take
        return outs


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------

class ContinuousEngine(_EngineBase):
    """Continuous-batching engine over ``slots`` fixed device decode lanes.

    Requests are admitted into free slots and retired at chunk boundaries;
    prompts prefill right-padded to a power-of-two bucket
    (:func:`repro.serve.scheduler.seq_buckets`), each admission inserting its
    slot's cache into the donated engine cache.  The decode batch is always
    ``slots`` wide — free lanes decode padding that is simply discarded — so
    the jitted shape set is ``{(slots, chunk)} x {prefill buckets}`` and
    warm traffic never recompiles.

    Output is token-identical to :class:`BatchedEngine` on the same
    requests/key for every model family where numerics do not depend on
    batch shape (float32 on the CPU): per-request PRNG streams and
    padding-invariant prefill (attention by causal masking, ssm/hybrid by
    masked recurrent-state updates) make the tokens a function of the
    request alone.

    ``kv_layout`` makes KV memory a strategy dimension:

      * ``"dense"`` — one ``(slots, max_seq)`` cache (the PR 3 layout);
      * ``"paged"`` — KV lives in a pool of ``kv_blocks`` pages of
        ``block_size`` positions (:mod:`repro.serve.paged`); each slot maps
        into the pool through a ``(max_blocks,)`` block-table row, pages
        are reserved at admission and freed at retirement, and peak KV
        memory is the *pool* size — a policy, not ``slots * max_seq``;
      * ``"auto"`` — let the tuner's HBM roofline pick
        (:func:`repro.autotune.pick_kv_layout`).

    ``prefill_chunk`` caps the admission bucket set: prompts longer than it
    are CHUNKED — split across successive chunk boundaries, one prefill
    chunk each — so long prompts neither stall the other lanes for a whole
    prompt-length prefill nor add the largest power-of-two buckets to the
    jit shape set.
    """

    def __init__(self, model: Model, params, max_seq: int = 512,
                 slots: int = 4, chunk: int = 8, min_bucket: int = 16,
                 tuning_cache=None, batch_sizes=None, aot="auto",
                 kv_layout: str = "dense", block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 resilience: Optional[ResilienceConfig] = None,
                 journal=None):
        if kv_layout == "auto":
            from repro import autotune
            kv_layout = autotune.pick_kv_layout(
                model.cfg, slots=slots, max_seq=max_seq,
                block_size=block_size, cache=tuning_cache)["layout"]
        if kv_layout == "paged":
            if max_seq % block_size != 0:
                raise ValueError(
                    f"paged layout needs block_size ({block_size}) to "
                    f"divide max_seq ({max_seq}) so the gathered view is "
                    f"shape-identical to the dense cache")
            self.block_size = block_size
            self.max_blocks = max_seq // block_size
            self.kv_blocks = int(kv_blocks or slots * self.max_blocks)
        self.prefill_chunk = prefill_chunk
        super().__init__(model, params, max_seq=max_seq, chunk=chunk,
                         tuning_cache=tuning_cache,
                         batch_sizes=batch_sizes or (1, slots), aot=aot,
                         kv_layout=kv_layout, resilience=resilience)
        self.slots = slots
        limit = (max_seq if prefill_chunk is None
                 else max(min(prefill_chunk, max_seq), min_bucket))
        self.buckets = seq_buckets(limit, min_bucket)
        self._insert = jax.jit(self._insert_slot, donate_argnums=(0,))
        model_ = self.model
        if kv_layout == "paged":
            def paged_prefill(first):
                def fn(params, tokens, kv, bt_row, state, start, lengths):
                    return model_.prefill_paged(params, tokens, kv, bt_row,
                                                state, start, lengths,
                                                first=first)
                return jax.jit(fn, donate_argnums=(2,))
            self._prefill_paged0 = paged_prefill(True)
            self._prefill_pagedC = paged_prefill(False)
        else:
            self._prefill_cont = jax.jit(
                lambda params, tokens, cache, start, lengths:
                model_.prefill(params, tokens, cache, start=start,
                               lengths=lengths, attend_cache=True),
                donate_argnums=(2,))
        # scheduler-state journal (a path, or a SchedulerJournal): every
        # submit/boundary-snapshot/terminal is appended checksummed, so a
        # killed engine's surviving requests replay to token identity
        # (repro.serve.domains.replay)
        if journal is None:
            self.journal = None
        elif isinstance(journal, str):
            from repro.serve.domains import SchedulerJournal
            self.journal = SchedulerJournal(journal)
        else:
            self.journal = journal
        self._reset_state()

    # -- device state --------------------------------------------------------

    def _init_device_state(self, park: bool = False) -> None:
        """(Re)build every device-resident buffer for the CURRENT
        ``kv_layout`` — factored out of :meth:`_reset_state` so the
        resilience paths (chunk-failure quarantine, paged->dense
        degradation) can rebuild device state without discarding the
        scheduler's pending queue or terminal records.  ``park=True``
        starts every lane at ``pos == max_seq`` (writes drop) — the safe
        posture when the rebuild happens mid-traffic."""
        b = self.slots
        if self.kv_layout == "paged":
            from repro.serve.paged import BlockPool
            self.cache = self.model.init_paged_cache(
                b, self.max_seq, n_blocks=self.kv_blocks,
                block_size=self.block_size)
            # all-sentinel tables: every lane's writes drop until admission
            self.block_tables = jnp.full((b, self.max_blocks),
                                         self.kv_blocks, jnp.int32)
            self.pool = BlockPool(self.kv_blocks, self.block_size)
        else:
            self.cache = self.model.init_cache(b, self.max_seq)
            self.block_tables = None
            self.pool = None
        self.tokens = jnp.zeros((b,), jnp.int32)
        self.pos = (jnp.full((b,), self.max_seq, jnp.int32) if park
                    else jnp.zeros((b,), jnp.int32))
        self.keys = jnp.stack(
            [jax.random.PRNGKey(i) for i in range(b)])
        self.temps = jnp.zeros((b,), jnp.float32)
        self.top_ks = jnp.zeros((b,), jnp.int32)
        # immutable zero staging template, reused by every paged admission
        # (never donated): no per-admission init dispatch; dense admissions
        # need no template at all — the fresh-cache prefill executable
        # builds its own zero cache
        self._zero_staging = (self.model.init_prefill_state(1)
                              if self.kv_layout == "paged" else None)
        self._staging: Dict[int, object] = {}
        self._admit_logits: Dict[int, jax.Array] = {}

    def _reset_state(self) -> None:
        self._init_device_state()
        self.sched = Scheduler(self.slots, pool=self.pool)
        self._requests: Dict[int, Request] = {}
        self._stream_keys: Dict[int, jax.Array] = {}
        self._next_id = 0
        self._run_key = jax.random.PRNGKey(0)

    @staticmethod
    def _insert_slot(big, small, slot):
        """Insert a batch=1 cache into the engine cache at ``slot``.

        Works on every cache pytree; per leaf the batch axis comes from
        :func:`_slot_axis`."""
        def ins(bl, sl):
            axis = _slot_axis(bl, sl)
            if axis is None:          # slots == 1: the slot IS the cache
                return sl.astype(bl.dtype)
            start = [jnp.int32(0)] * bl.ndim
            start[axis] = jnp.asarray(slot, jnp.int32)
            return jax.lax.dynamic_update_slice(
                bl, sl.astype(bl.dtype), tuple(start))
        return jax.tree_util.tree_map(ins, big, small)

    # -- API -----------------------------------------------------------------

    def submit(self, request: Request, stream: Optional[int] = None) -> int:
        """Queue a request; returns its id.

        ``stream`` is the request's PRNG stream index: its tokens are
        sampled from ``fold_in(run_key, stream)`` advanced once per token.
        ``run`` passes each request's position in its batch — the same
        stream the static oracle uses — so outputs stay token-identical
        across engine reuse and resubmission.  Streaming callers that omit
        it get the (unique, monotonically increasing) request id."""
        self._check_request(request)
        rid = self._next_id
        self._next_id += 1
        self._requests[rid] = request
        self._stream_keys[rid] = jax.random.fold_in(
            self._run_key, rid if stream is None else stream)
        self.sched.submit(rid, int(request.prompt.shape[0]),
                          max(int(request.max_new_tokens), 0),
                          deadline_s=request.deadline_s,
                          ttft_deadline_s=request.ttft_deadline_s)
        if self.journal is not None:
            self.journal.record_submit(
                rid, request.prompt,
                max_new=max(int(request.max_new_tokens), 0),
                temperature=request.temperature,
                top_k=getattr(request, "top_k", 0) or 0,
                stream=rid if stream is None else stream,
                deadline_s=request.deadline_s,
                ttft_deadline_s=request.ttft_deadline_s)
        return rid

    def take_output(self, rid: int) -> List[int]:
        """Collect (and release) a finished request's tokens.

        Completed requests hold their outputs until collected; collecting
        prunes every per-request record, so a long-running engine's memory
        is bounded by in-flight + uncollected work, not by total traffic."""
        return self.sched.pop_output(rid)

    def take_result(self, rid: int) -> RequestResult:
        """Collect (and release) a terminal request's full outcome —
        tokens + terminal state (``ok|timeout|cancelled|failed``) + reason
        (:class:`repro.serve.resilience.RequestResult`)."""
        return self.sched.pop_result(rid)

    def cancel(self, rid: int, reason: str = "cancelled by caller") -> None:
        """Cancel a pending or in-flight request at the current boundary.

        Partial tokens survive into the terminal result (state
        ``cancelled``); the device lane is parked and — paged — its pages
        return to the pool immediately.  Idempotent once terminal;
        KeyError for ids never submitted."""
        slot = self.sched.cancel(rid, reason)
        if slot is not None:
            self._evict_slot(slot)
        if self.journal is not None and rid in self.sched.done:
            # cancellation happens between boundaries: journal the final
            # snapshot + terminal now, not at the next step_chunk (there
            # may never be one)
            toks = self.sched.outputs.get(rid)
            if toks:
                self.journal.record_progress(rid, toks)
            state, why = self.sched.done[rid]
            self.journal.record_terminal(rid, state, why)
        self._requests.pop(rid, None)
        self._stream_keys.pop(rid, None)

    def run(self, requests: List[Request], key=None) -> List[List[int]]:
        """Serve a closed set of requests to completion (convenience driver
        for the streaming ``submit`` + ``step_chunk`` API); returns outputs
        in submission order."""
        with self._options_scope():
            self._run_key = key if key is not None else jax.random.PRNGKey(0)
            rids = [self.submit(r, stream=i)
                    for i, r in enumerate(requests)]
            while not self.sched.idle:
                self.step_chunk()
            if self._jit_baseline is None:
                # first completed batch = warm: later jit-cache growth is a
                # recompile the detector flags
                self.mark_warm()
            return [self.take_output(rid) for rid in rids]

    def _check_request(self, r: Request) -> None:
        super()._check_request(r)
        if self.kv_layout == "paged":
            need = self.pool.blocks_for(
                int(r.prompt.shape[0]) + max(int(r.max_new_tokens), 0))
            if need > self.pool.n_blocks:
                raise ValueError(
                    f"request needs {need} KV blocks but the pool only has "
                    f"{self.pool.n_blocks} (block_size "
                    f"{self.pool.block_size}); raise kv_blocks")

    # -- the chunk-boundary loop --------------------------------------------

    def step_chunk(self) -> List[int]:
        """Admit pending requests, advance in-flight prompt prefills by one
        chunk each, then decode one fused chunk.

        Returns the request ids retired at this boundary.

        While recording (``repro.obs``), ``serve.step_chunk`` spans the
        whole call and its children name every stretch of host time:
        ``serve.boundary_checks``, ``serve.admissions``,
        ``serve.prefill_chunk`` (holding ``serve.finish_admit``),
        ``serve.decode_chunk`` (the dispatch), ``serve.device_wait`` (each
        blocking host sync, ``on`` naming which), ``serve.record`` and
        ``serve.bookkeeping``."""
        with obs.span("serve.step_chunk"):
            try:
                finished = self._step_chunk_inner()
            except Exception as e:
                # the resilience ladder is exhausted (or disabled) and the
                # exception is about to leave the engine: capture the box
                obs.flight_dump("unhandled_exception",
                                error=f"{type(e).__name__}: {e}")
                raise
            with obs.span("serve.bookkeeping"):
                if self.journal is not None:
                    self._journal_sync(finished)
                self._check_recompiles()
        return finished

    def _journal_sync(self, finished: List[int]) -> None:
        """Journal this boundary: an emitted-token snapshot per request
        with new tokens, then a terminal record per retirement.  Chunk
        boundaries are the journal's granularity — inside a chunk the host
        observes nothing, so there is nothing finer to record."""
        for rid, toks in self.sched.outputs.items():
            self.journal.record_progress(rid, toks)
        for rid in finished:
            state_reason = self.sched.done.get(rid)
            if state_reason is not None:
                self.journal.record_terminal(rid, *state_reason)

    def _domain_sweep(self) -> None:
        """Failure-domain hook, run first at every chunk boundary —
        :class:`ShardedEngine` polls its host groups here; the unsharded
        engines have no domains to lose."""

    def _step_chunk_inner(self) -> List[int]:
        finished: List[int] = []
        with obs.span("serve.boundary_checks"):
            # failure domains first: a lost host must be evacuated + the
            # mesh shrunk before this boundary admits into (or decodes on)
            self._domain_sweep()
            # deadline sweep next: an expired request must not consume the
            # boundary's admission/prefill/decode work
            for slot, rid in self.sched.check_deadlines():
                if slot is not None:
                    self._evict_slot(slot)
                finished.append(rid)
            # pool integrity: a corrupt block pool means tables may alias
            # pages across requests — degrade paged -> dense instead of
            # decoding through a damaged mapping
            if self.pool is not None and self.resilience.pool_check:
                if faults.should_fire("serve.pool_corrupt") is not None:
                    faults.corrupt_pool(self.pool)
                problems = self.pool.validate()
                if problems:
                    finished.extend(
                        self._degrade_to_dense("; ".join(problems)))
        with obs.span("serve.admissions"):
            self.sched.admissions()           # reserve slots (and KV blocks)
            if self.pool is not None:
                obs.gauge("serve.kv_pool.used_blocks").set(
                    self.pool.used_blocks)
                obs.gauge("serve.kv_pool.free_blocks").set(
                    self.pool.free_blocks)
                if obs.recording():
                    # pages reserved against pages holding a written
                    # position: the reservation the decode chunk runs with
                    obs.instant("serve.kv_pages",
                                reserved=self.pool.used_blocks,
                                written=self.sched.written_blocks(),
                                pool=self.pool.n_blocks)
        for slot, rid in self.sched.prefilling():
            if self._prefill_advance(slot, rid):      # one chunk per boundary
                finished.append(rid)
        if self.sched.busy_slots():
            self._before_chunk()              # hook: ShardedEngine pins here
            req_ids = ",".join(str(s.req_id) for s in self.sched.slots
                               if not s.free)
            attn = decode_attention(self.model, self.cache,
                                    self.block_tables)
            ssm = {"ssm": "step"} if self.model.ssm_layers() else {}
            if ssm and obs.recording():
                # the lanes whose recurrent state the chunk carries on
                obs.instant("serve.ssm_state",
                            slots=sum(1 for s in self.sched.slots
                                      if not s.free and not s.prefilling),
                            bytes_per_slot=self.model.ssm_state_bytes(),
                            layers=self.model.ssm_layers())
            t0 = time.perf_counter()
            try:
                with obs.span("serve.decode_chunk", chunk=self.chunk,
                              req_ids=req_ids, attn=attn, **ssm):
                    (self.cache, self.tokens, self.pos, self.keys, toks,
                     bad) = self._call_chunk(
                        (self.params, self.cache, self.tokens, self.pos,
                         self.keys, self.temps, self.top_ks,
                         self.block_tables), req_ids=req_ids,
                        paged_kernel=attn == "paged_kernel")
                    if attn == "paged_kernel":
                        obs.counter("serve.decode_attn.paged_kernel").inc()
                with obs.span("serve.device_wait", on="decode"):
                    block = np.asarray(toks)  # the chunk's one host sync
                    bad_host = np.asarray(bad)
            except Exception as e:
                if not self.resilience.quarantine_on_chunk_failure:
                    raise
                finished.extend(self._quarantine_chunk_failure(e))
            else:
                with obs.span("serve.record"):
                    # per-chunk wall time, measured at the boundary the
                    # host already pays: the latency histogram + the drift
                    # auditor's baseline-relative watch on this shape
                    dt = time.perf_counter() - t0
                    obs.histogram("serve.chunk_s").observe(dt)
                    obs.drift_observe(
                        f"serve|decode_chunk|slots={self.slots}"
                        f"|chunk={self.chunk}", dt)
                    slot_of = {s.req_id: i
                               for i, s in enumerate(self.sched.slots)
                               if not s.free}
                    if self.resilience.nan_guard and bad_host.any():
                        finished.extend(self._quarantine_nan_rows(bad_host))
                    retired = self.sched.record_chunk(block)
                    for rid in retired:
                        self._park_lane(slot_of[rid])
                    finished.extend(retired)
        for rid in finished:                  # release prompts/keys at retire
            self._requests.pop(rid, None)
            self._stream_keys.pop(rid, None)
        return finished

    # -- quarantine / degradation paths --------------------------------------

    def _evict_slot(self, slot: int) -> None:
        """Neutralise a lane whose request terminated outside the normal
        retire path (cancel/timeout/failure): park it and drop any
        admission scratch it was holding."""
        self._park_lane(slot)
        self._staging.pop(slot, None)
        self._admit_logits.pop(slot, None)

    def _quarantine_nan_rows(self, bad_host) -> List[int]:
        """Quarantine slots whose decode chunk produced non-finite logits:
        the request fails terminally, the lane is parked, and — paged —
        its pages are scrubbed before returning to the pool (a reissued
        page must never leak NaNs into the next occupant).  Rows the
        guard flagged while free/prefilling are stale lanes decoding
        padding; they are ignored."""
        out: List[int] = []
        for i, s in enumerate(self.sched.slots):
            if not bad_host[i] or s.free or s.prefilling:
                continue
            rid = s.req_id
            self._n_nan_quarantines += 1
            obs.counter("serve.nan_quarantines").inc()
            obs.event("serve.nan_quarantine", req_id=rid, slot=i)
            log.warning("request %d produced non-finite logits in slot %d "
                        "— quarantined (co-batched requests unaffected)",
                        rid, i)
            if self.kv_layout == "paged":
                self._scrub_pages(self.pool.owned(i))
            self.sched.fail(rid, "non-finite logits in decode chunk")
            self._evict_slot(i)
            out.append(rid)
        return out

    def _quarantine_chunk_failure(self, e: Exception) -> List[int]:
        """The decode chunk failed past the retry budget (or consumed its
        donated buffers): fail every in-flight request and rebuild the
        device state for the current layout.  Pending requests survive in
        the queue and admit into the rebuilt state."""
        self._n_chunk_quarantines += 1
        obs.counter("serve.chunk_quarantines").inc()
        obs.event("serve.chunk_quarantine",
                  error=f"{type(e).__name__}: {e}")
        log.warning("decode chunk failed past the retry budget (%s: %s); "
                    "failing in-flight requests and rebuilding device "
                    "state", type(e).__name__, e)
        failed = self._fail_in_flight(
            f"decode chunk failed: {type(e).__name__}: {e}")
        self._init_device_state(park=True)
        self.sched.pool = self.pool
        self._jit_baseline = None   # rebuilt buffers may re-lower; re-warm
        return failed

    def _fail_in_flight(self, reason: str) -> List[int]:
        """Fail every admitted request (used when shared device state is
        suspect); returns their ids.  Queued requests are untouched."""
        failed: List[int] = []
        for i, s in enumerate(self.sched.slots):
            if s.free:
                continue
            rid = s.req_id
            self.sched.fail(rid, reason)
            self._evict_slot(i)
            self._requests.pop(rid, None)
            self._stream_keys.pop(rid, None)
            failed.append(rid)
        return failed

    def _degrade_to_dense(self, reason: str) -> List[int]:
        """The paged->dense rung of the degradation ladder: the block pool
        failed validation, so the engine abandons the paged layout rather
        than write through a damaged page mapping.  In-flight requests
        fail (their pages are suspect); pending requests admit into the
        rebuilt dense cache; the switch is recorded as an obs provenance
        Decision with origin ``degraded(paged->dense)``."""
        self._n_degradations += 1
        log.warning("KV block pool failed validation (%s); degrading "
                    "kv_layout paged -> dense", reason)
        failed = self._fail_in_flight(f"kv pool corrupt: {reason}")
        record_degradation(
            "kv_layout", "serve.engine",
            key=f"serve|kv_layout|slots={self.slots}|max_seq={self.max_seq}",
            frm="paged", to="dense", layout="dense", note=reason)
        self.kv_layout = "dense"
        if not hasattr(self, "_prefill_cont"):
            # the dense continuation prefill only exists on engines built
            # dense; a degraded engine needs it from here on
            model_ = self.model
            self._prefill_cont = jax.jit(
                lambda params, tokens, cache, start, lengths:
                model_.prefill(params, tokens, cache, start=start,
                               lengths=lengths, attend_cache=True),
                donate_argnums=(2,))
        self._init_device_state(park=True)
        self.sched.pool = None
        self._jit_baseline = None   # dense chunk/prefill signatures are new
        return failed

    def _scrub_pages(self, blocks: List[int]) -> None:
        """Zero the KV pool contents of ``blocks`` before they return to
        the free list.  Needed because attention's validity masking keeps
        *weights* at zero but ``0 * NaN`` is NaN — a poisoned page handed
        to the next request would re-poison it."""
        if not blocks:
            return
        idx = jnp.asarray(sorted(blocks), jnp.int32)
        kv, state = self.model.split_paged_cache(self.cache)
        if kv is None:
            return

        def scrub(leaf):
            # pool leaves are (layers/groups, n_blocks, block_size, ...)
            if (leaf.ndim >= 3 and leaf.shape[1] == self.kv_blocks
                    and jnp.issubdtype(leaf.dtype, jnp.floating)):
                return leaf.at[:, idx].set(0)
            return leaf
        kv = jax.tree_util.tree_map(scrub, kv)
        self.cache = self.model.merge_paged_cache(kv, state)

    def _poison_slot_cache(self, slot: int) -> None:
        """Deterministic damage for the ``serve.nan_decode`` drill: fill
        the slot's cached state with NaN so its next decode chunk trips
        the in-chunk NaN guard — exactly the flaky-HBM poison model."""
        if self.kv_layout == "paged":
            blocks = self.pool.owned(slot)
            if blocks:
                idx = jnp.asarray(sorted(blocks), jnp.int32)
                kv, state = self.model.split_paged_cache(self.cache)
                if kv is not None:
                    def poison(leaf):
                        if (leaf.ndim >= 3
                                and leaf.shape[1] == self.kv_blocks
                                and jnp.issubdtype(leaf.dtype,
                                                   jnp.floating)):
                            return leaf.at[:, idx].set(jnp.nan)
                        return leaf
                    kv = jax.tree_util.tree_map(poison, kv)
                    self.cache = self.model.merge_paged_cache(kv, state)
            return
        small = self.model.init_cache(1, self.max_seq)

        def poison(bl, sl):
            if not jnp.issubdtype(bl.dtype, jnp.floating):
                return bl
            axis = _slot_axis(bl, sl)
            if axis is None:
                return jnp.full_like(bl, jnp.nan)
            idx = [slice(None)] * bl.ndim
            idx[axis] = slot
            return bl.at[tuple(idx)].set(jnp.nan)
        self.cache = jax.tree_util.tree_map(poison, self.cache, small)

    def _before_chunk(self) -> None:
        """Hook between boundary admissions and the fused decode chunk —
        :class:`ShardedEngine` re-pins shardings here so admission-time
        host updates can never hand the chunk a new jit signature."""

    def stats(self) -> dict:
        out = super().stats()
        out["scheduler"] = self.sched.stats()
        if self.pool is not None:
            out["kv_pool"] = self.pool.stats()
        return out

    def _park_lane(self, slot: int) -> None:
        """Neutralise a freed lane: position past max_seq so its decode
        writes drop.  Load-bearing for the paged layout — the slot's pages
        go back to the pool at retirement and may be re-issued, so the
        lane must never write through its stale block table."""
        self.pos = self.pos.at[slot].set(self.max_seq)

    def _prefill_advance(self, slot: int, rid: int) -> bool:
        """Prefill the next prompt chunk of ``rid`` into ``slot``; once the
        whole prompt is in the cache, finish the admission (first token).
        True when the request retired at once.

        Chunks are ``buckets[-1]`` tokens (the prefill-chunk cap); the tail
        is padded to the smallest bucket that fits, so the executable set
        stays one-per-bucket whatever the prompt length."""
        r = self._requests[rid]
        plen = int(r.prompt.shape[0])
        start = self.sched.slots[slot].prefill_pos
        take = min(plen - start, self.buckets[-1])
        bucket = pick_bucket(take, self.buckets)
        attn = prefill_attention(self.model, self.params, bucket,
                                 start == 0)
        with obs.span("serve.prefill_chunk", slot=slot, req_id=rid,
                      bucket=bucket, start=start,
                      **({"attn": attn} if attn else {}),
                      **({"ssm": "chunked"} if self.model.ssm_layers()
                         else {})):
            if start == 0:
                self._begin_admit(slot)
            advanced = self._prefill_advance_inner(slot, r, plen, start,
                                                   take, bucket)
            if attn == "flash_kernel":
                obs.counter("serve.prefill_attn.flash_kernel").inc()
            if not advanced:
                return False
            with obs.span("serve.finish_admit", slot=slot, req_id=rid):
                return self._finish_admit(slot, rid)

    def _prefill_advance_inner(self, slot, r, plen, start, take,
                               bucket) -> bool:
        tokens = self._pad_prompt(r.prompt[start:start + take], bucket)[None]
        lengths = jnp.asarray([take], jnp.int32)
        if self.kv_layout == "paged":
            kv, _ = self.model.split_paged_cache(self.cache)
            args = (self.params, tokens, kv, self.block_tables[slot],
                    self._staging[slot], jnp.int32(start), lengths)
            fn = self._prefill_paged0 if start == 0 else self._prefill_pagedC
            # same AOT-executable discipline as the dense admission path:
            # one compiled program per (bucket, first-chunk) signature.
            # No jit fallback here: the pools are DONATED, so re-running
            # after a partial failure would read deleted buffers — a
            # mismatch must surface, not silently slow-path
            exe_key = (tokens.shape, start == 0)
            exe = self._prefill_exes.get(exe_key)
            if exe is None:
                with obs.span("serve.prefill_compile",
                              shape=str(tokens.shape), first=start == 0):
                    exe = fn.lower(*args).compile()
                self._prefill_exes[exe_key] = exe
            logits, kv, staging = exe(*args)
            _, slot_state = self.model.split_paged_cache(self.cache)
            self.cache = self.model.merge_paged_cache(kv, slot_state)
            self._staging[slot] = staging
        else:
            if start == 0:
                logits, cache1 = self._prefill_call(tokens, lengths)
            else:
                logits, cache1 = self._prefill_cont(
                    self.params, tokens, self._staging[slot],
                    jnp.int32(start), lengths)
            self._staging[slot] = cache1
        rid = self.sched.slots[slot].req_id
        if (start + take >= plen
                and faults.should_fire("serve.nan_prefill",
                                       req_id=rid) is not None):
            # poison drill: the request's admission logits read as NaN
            logits = jnp.full_like(logits, jnp.nan)
        self._admit_logits[slot] = logits
        self.sched.prefill_advance(slot, take)
        return start + take >= plen

    def _begin_admit(self, slot: int) -> None:
        """Set up the slot for its (possibly multi-chunk) prompt prefill."""
        if self.kv_layout == "paged":
            from repro.serve.paged import table_row
            row = table_row(self.pool.owned(slot), self.max_blocks,
                            self.kv_blocks)
            self.block_tables = self.block_tables.at[slot].set(
                jnp.asarray(row, jnp.int32))
            self._staging[slot] = self._zero_staging
        self._park_lane(slot)  # mid-prefill decode writes must drop

    def _finish_admit(self, slot: int, rid: int) -> bool:
        """The prompt is fully cached: install the slot's decode state and
        sample the first token; True if it retired immediately."""
        r = self._requests[rid]
        length = int(r.prompt.shape[0])
        logits = self._admit_logits.pop(slot)
        staging = self._staging.pop(slot)
        finite = True
        if self.resilience.nan_guard:
            with obs.span("serve.device_wait", on="prefill_logits"):
                logits_host = np.asarray(logits)
            finite = np.isfinite(logits_host).all()
        if not finite:
            # poisoned prompt: quarantine at admission, before the slot's
            # state ever joins the shared decode batch
            self._n_nan_quarantines += 1
            obs.counter("serve.nan_quarantines").inc()
            obs.event("serve.nan_quarantine", req_id=rid, slot=slot,
                      where="prefill")
            log.warning("request %d produced non-finite prefill logits — "
                        "quarantined at admission", rid)
            if self.kv_layout == "paged":
                self._scrub_pages(self.pool.owned(slot))
            self.sched.fail(rid, "non-finite prefill logits")
            self._evict_slot(slot)
            return True
        if self.kv_layout == "paged":
            if staging is not None:           # recurrent state -> its slot
                # the prefilled state, begun from zeros at admission,
                # replaces the lane's whole state in every recurrent layer
                with obs.span("serve.state_install", slot=slot,
                              req_id=rid):
                    kv, slot_state = self.model.split_paged_cache(
                        self.cache)
                    slot_state = self._insert(slot_state, staging, slot)
                    self.cache = self.model.merge_paged_cache(kv,
                                                              slot_state)
        else:
            self.cache = self._insert(self.cache, staging, slot)

        rkey = self._stream_keys[rid]
        carry, sub = _split_keys(rkey[None])
        temp = jnp.asarray([r.temperature], jnp.float32)
        top_k = jnp.asarray([getattr(r, "top_k", 0) or 0], jnp.int32)
        first = self._sample0(logits, sub, temp, top_k)

        self.tokens = self.tokens.at[slot].set(first[0])
        self.pos = self.pos.at[slot].set(length)
        self.keys = self.keys.at[slot].set(carry[0])
        self.temps = self.temps.at[slot].set(temp[0])
        self.top_ks = self.top_ks.at[slot].set(top_k[0])
        # one tiny host sync per ADMISSION (not per token): the first token
        with obs.span("serve.device_wait", on="first_token"):
            token = int(np.asarray(first)[0])
        done = self.sched.record_first(slot, token)
        if done:
            self._park_lane(slot)
        elif faults.should_fire("serve.nan_decode", req_id=rid) is not None:
            # poison drill: NaN the slot's cached state so the next decode
            # chunk trips the in-chunk NaN guard for this row
            self._poison_slot_cache(slot)
        return done


# ---------------------------------------------------------------------------
# sharded continuous batching (data-parallel slots over a mesh axis)
# ---------------------------------------------------------------------------

class ShardedEngine(ContinuousEngine):
    """Continuous batching with the slot axis sharded over a named mesh axis
    (``data`` by default) — the multi-host serving driver from the ROADMAP.

    The decode state (KV cache, token/pos/key/temp buffers) lives sharded
    over the mesh via ``NamedSharding``; params are replicated once at
    build time.  The fused decode chunk is the *same* jitted function as
    :class:`ContinuousEngine` — GSPMD partitions it over the batch axis, so
    each device decodes ``slots / mesh.shape[axis]`` lanes and no collective
    appears in the hot loop (per-request work never crosses shards).  That
    also makes the engine token-identical to the unsharded
    :class:`ContinuousEngine` wherever the per-row computation is bitwise
    the same — float32 on the CPU: only its placement changes, strategy
    preservation at the serving level.  In bf16 on a TPU it is
    token-identical to an unsharded engine with the same per-device batch
    (``slots / mesh.shape[axis]`` slots); against a wider one, greedy
    tokens can part where the top two logits are about a bf16 step apart.
    ``chip_smoke.py --chips 4`` checks both.

    Admission prefill still runs batch=1 (replicated) and inserts the slot
    cache into the sharded engine cache; shapes and shardings are closed
    after one pass over the prompt buckets, so warm traffic never
    recompiles (``decode_cache_misses()`` stays at 1).

    ``hosts`` turns on the failure-domain layer
    (:class:`repro.serve.domains.FailureDomains`): the mesh's devices
    partition into that many contiguous host groups (``hosts="auto"``
    groups by ``device.process_index`` on a real multi-host mesh), and at
    every chunk boundary the engine polls for a lost or straggling host
    (the ``mesh.host_lost`` / ``mesh.host_slow`` / ``collective.timeout``
    fault sites stand in for heartbeats in drills).  On a loss the dead
    host's slots are **evacuated** back to the queue front, the engine
    re-places its state on the shrunk mesh (lost rows zeroed — their HBM
    is gone), the autotuner re-ranks mesh candidates for the new
    descriptor, and the shrink is recorded as provenance origin
    ``degraded(mesh(data=8)->mesh(data=4))`` plus one flight dump with
    reason ``host_lost``.  Survivors keep their tokens; evacuees re-decode
    from their prompts bit-identically.  The shrink recompiles the chunk
    once (new shardings) — the warm baseline resets, so the recompile
    detector stays meaningful afterwards.
    """

    def __init__(self, model: Model, params, max_seq: int = 512,
                 slots: int = 8, chunk: int = 8, min_bucket: int = 16,
                 tuning_cache=None, batch_sizes=None, aot="auto",
                 mesh=None, mesh_axis: str = "data",
                 kv_layout: str = "dense", block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 resilience: Optional[ResilienceConfig] = None,
                 journal=None, hosts=None, host_slow_threshold: int = 3):
        from repro.sharding import ctx
        mesh = mesh if mesh is not None else ctx.get_mesh()
        if mesh is None:
            raise ValueError(
                "ShardedEngine needs a mesh: pass mesh=... or set the "
                "process mesh context (repro.sharding.ctx.set_mesh)")
        from repro.launch.mesh import has_explicit_axes
        if has_explicit_axes(mesh):
            raise ValueError(
                f"ShardedEngine needs a mesh with Auto axes, got axis types "
                f"{tuple(str(t) for t in mesh.axis_types)} (jax.make_mesh "
                f"makes Explicit axes by default); build it with "
                f"repro.launch.mesh.make_mesh")
        if mesh_axis not in mesh.shape:
            raise ValueError(f"mesh axis {mesh_axis!r} not in mesh axes "
                             f"{list(mesh.shape)}")
        n_shards = int(mesh.shape[mesh_axis])
        if slots % n_shards != 0:
            raise ValueError(f"slots ({slots}) must be divisible by mesh "
                             f"axis {mesh_axis!r} of size {n_shards}")
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        self.domains = None
        if hosts is not None:
            from repro.serve.domains import FailureDomains
            self.domains = FailureDomains(
                mesh, axis=mesh_axis,
                hosts=None if hosts == "auto" else int(hosts),
                slow_threshold=host_slow_threshold)
        self._n_host_losses = 0
        super().__init__(model, params, max_seq=max_seq, slots=slots,
                         chunk=chunk, min_bucket=min_bucket,
                         tuning_cache=tuning_cache, batch_sizes=batch_sizes,
                         aot=aot, kv_layout=kv_layout, block_size=block_size,
                         kv_blocks=kv_blocks, prefill_chunk=prefill_chunk,
                         resilience=resilience, journal=journal)

    # -- sharded device state ------------------------------------------------

    def _shardings(self):
        from jax.sharding import NamedSharding, PartitionSpec as PS
        rep = NamedSharding(self.mesh, PS())
        row = NamedSharding(self.mesh, PS(self.mesh_axis))
        return rep, row

    def _cache_sharding(self, big, small):
        """Per-leaf NamedSharding: the slot axis (:func:`_slot_axis`, the
        same detection ``_insert_slot`` uses) sharded over the mesh axis,
        all else replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as PS
        axis = _slot_axis(big, small)
        if axis is None:
            return NamedSharding(self.mesh, PS())
        return NamedSharding(
            self.mesh, PS(*([None] * axis + [self.mesh_axis])))

    def _install_shardings(self) -> None:
        """(Re)compute ``_cache_shardings`` against the CURRENT mesh and
        replicate the params onto it — shared by the initial build and by
        the failure-domain re-placement after a mesh shrink."""
        rep, row = self._shardings()
        self.params = jax.device_put(self.params, rep)   # replicate weights
        if self.kv_layout == "paged":
            # page pools have no slot axis: they live REPLICATED (every
            # device holds the pool; slots map into it via their tables),
            # only the recurrent slot state shards over the mesh axis
            kv, st = self.model.split_paged_cache(self.cache)
            kv_sh = (None if kv is None
                     else jax.tree_util.tree_map(lambda _: rep, kv))
            st_sh = None
            if st is not None:
                small = self.model.init_prefill_state(1)
                st_sh = jax.tree_util.tree_map(
                    lambda bl, sl: self._cache_sharding(bl, sl), st, small)
            self._cache_shardings = self.model.merge_paged_cache(kv_sh,
                                                                 st_sh)
        else:
            small = self.model.init_cache(1, self.max_seq)
            self._cache_shardings = jax.tree_util.tree_map(
                lambda bl, sl: self._cache_sharding(bl, sl),
                self.cache, small)

    def _init_device_state(self, park: bool = False) -> None:
        # the resilience rebuild paths call this too (chunk-failure
        # quarantine, paged->dense degradation): the rebuilt state must
        # come back SHARDED, or the next chunk would recompile unsharded
        super()._init_device_state(park)
        self._install_shardings()
        self.cache = jax.tree_util.tree_map(
            jax.device_put, self.cache, self._cache_shardings)
        self._pin_slot_state()

    def _pin_slot_state(self) -> None:
        """Keep the per-slot vectors on their canonical sharding.  A no-op
        (no transfer) when already placed — called at chunk boundaries so
        host-side ``.at[slot].set`` admissions can never drift the decode
        chunk onto a new sharding signature (which would recompile)."""
        rep, row = self._shardings()
        self.tokens = jax.device_put(self.tokens, row)
        self.pos = jax.device_put(self.pos, row)
        self.keys = jax.device_put(self.keys, row)
        self.temps = jax.device_put(self.temps, row)
        self.top_ks = jax.device_put(self.top_ks, row)
        # the cache too: admission inserts (whose staging came from the
        # AOT prefill executable) can leave GSPMD free to re-place the
        # merged cache; re-pinning keeps the decode chunk on one signature
        self.cache = jax.tree_util.tree_map(
            jax.device_put, self.cache, self._cache_shardings)
        if self.block_tables is not None:
            # tables index a replicated pool: keep them replicated too
            self.block_tables = jax.device_put(self.block_tables, rep)

    def _before_chunk(self) -> None:
        self._pin_slot_state()

    def step_chunk(self):
        out = super().step_chunk()
        self._pin_slot_state()
        return out

    # -- failure domains: detection -> evacuation -> shrink ------------------

    def _domain_sweep(self) -> None:
        if self.domains is None:
            return
        ev = self.domains.poll()
        if ev is None:
            return
        if ev.kind == "slow":
            obs.counter("serve.host_slow").inc()
            obs.event("serve.host_slow", host=ev.host,
                      strikes=self.domains.slow_count(ev.host),
                      cause=ev.cause)
            log.warning("%s", ev.cause)
            if ev.delay_s:
                time.sleep(ev.delay_s)   # the drill's injected stall
            return
        self._handle_host_loss(ev.host, ev.cause)

    def _handle_host_loss(self, host: int, cause: str) -> None:
        """Survive the loss of ``host``: evacuate its slots back to the
        queue front, shrink the mesh onto the survivors, re-place device
        state, re-tune for the new descriptor, and record the shrink as a
        degradation (one provenance origin + one ``host_lost`` flight
        dump per event)."""
        from repro.mesh.strategy import descriptor
        dom = self.domains
        frm = descriptor(self.mesh)
        lost_slots = set(dom.slots_of_host(host, self.slots))
        # the slot axis must divide the surviving positions; when it would
        # not (uneven host sizes), drop further hosts from the tail until
        # it does — a smaller servable mesh beats an unshardable one.
        # With the usual hosts-divides-slots layouts this never iterates.
        drop = [host]

        def _size_after() -> int:
            return sum(len(g) for h, g in enumerate(dom.groups)
                       if dom.alive[h] and h not in drop)

        while _size_after() and self.slots % _size_after() != 0:
            extra = max(h for h in dom.alive_hosts() if h not in drop)
            drop.append(extra)
            lost_slots |= set(dom.slots_of_host(extra, self.slots))
        self._n_host_losses += 1
        obs.counter("serve.host_losses").inc()
        log.warning("host %d lost (%s): evacuating slots %s and shrinking "
                    "the mesh", host, cause, sorted(lost_slots))
        evacuated: List[int] = []
        # descending slot order + appendleft => evacuees rejoin the queue
        # front in ascending slot order (FIFO among themselves, ahead of
        # never-admitted requests)
        for slot in sorted(lost_slots, reverse=True):
            rid = self.sched.evacuate(slot, reason=cause)
            if rid is None:
                continue
            self._evict_slot(slot)
            evacuated.append(rid)
            if self.journal is not None:
                self.journal.record_evacuate(rid, host)
        for h in drop:
            dom.mark_lost(h)    # raises when no host survives: unservable
        new_mesh = dom.shrunk_mesh()
        to = descriptor(new_mesh)
        obs.event("serve.host_lost", host=host, cause=cause, frm=frm,
                  to=to, evacuated=",".join(str(r) for r in evacuated),
                  dropped_hosts=",".join(str(h) for h in drop))
        self._remesh(new_mesh, sorted(lost_slots))
        record_degradation(
            "mesh", "serve.engine",
            key=f"serve|mesh|slots={self.slots}|axis={self.mesh_axis}",
            frm=f"mesh({frm})", to=f"mesh({to})", note=cause,
            params={"mesh_axis": self.mesh_axis, "hosts": dom.n_hosts,
                    "alive": len(dom.alive_hosts())},
            dump=False)
        # exactly ONE flight dump per host-loss event, reason host_lost
        # (record_degradation's generic dump is suppressed above)
        obs.flight_dump("host_lost", host=host, cause=cause, frm=frm,
                        to=to, evacuated=",".join(str(r) for r in evacuated))
        if self.journal is not None:
            self.journal.record_shrink(frm, to, host, cause)
        self._retune_mesh(to)

    def _remesh(self, new_mesh, lost_slots: List[int]) -> None:
        """Re-place every device buffer onto ``new_mesh``, preserving the
        surviving slots' rows and zeroing the lost ones (the dead host's
        HBM is gone — nothing may depend on it, and survivors provably do
        not: their rows round-trip through the host copy bit-identical)."""
        with obs.span("serve.remesh", frm=str(self.mesh.shape),
                      to=str(new_mesh.shape)):
            cache_host = jax.device_get(self.cache)
            cache_host = self._zero_slot_rows(cache_host, lost_slots)
            (self.tokens, self.pos, self.keys, self.temps,
             self.top_ks) = jax.device_get(
                (self.tokens, self.pos, self.keys, self.temps, self.top_ks))
            if self.block_tables is not None:
                self.block_tables = jax.device_get(self.block_tables)
            self.mesh = new_mesh
            self._install_shardings()
            self.cache = jax.tree_util.tree_map(
                jax.device_put, cache_host, self._cache_shardings)
            self._pin_slot_state()
        # stale strategy artefacts: the old-mesh AOT prefill executables
        # would reject the re-placed params (jit re-lowers once, fine);
        # the chunk recompiles once for the new shardings — reset the warm
        # baseline so that expected compile is not flagged as drift
        self._prefill_exes.clear()
        self._jit_baseline = None

    def _zero_slot_rows(self, cache_host, lost_slots: List[int]):
        """Zero the lost slots' rows of a HOST-side cache pytree (numpy):
        the simulation of their HBM dying with the host."""
        if not lost_slots:
            return cache_host
        idx = np.asarray(sorted(lost_slots), dtype=np.int64)

        def zero(tree, small):
            def z(bl, sl):
                axis = _slot_axis(bl, sl)
                if axis is None:
                    return bl
                bl = np.array(bl)
                sli = [slice(None)] * bl.ndim
                sli[axis] = idx
                bl[tuple(sli)] = 0
                return bl
            return jax.tree_util.tree_map(z, tree, small)

        if self.kv_layout == "paged":
            kv, st = self.model.split_paged_cache(cache_host)
            if st is not None:
                st = zero(st, self.model.init_prefill_state(1))
            return self.model.merge_paged_cache(kv, st)
        return zero(cache_host, self.model.init_cache(1, self.max_seq))

    def _retune_mesh(self, desc: str) -> None:
        """Re-rank mesh-axis candidates for the shrunk descriptor (cache
        keys carry it, so this fills the cold rows the new mesh would
        otherwise tune one by one at dispatch)."""
        if self.tuning_cache is None:
            return
        from repro.serve.domains import retune_for_mesh
        try:
            retune_for_mesh(self.model.cfg, desc, max_seq=self.max_seq,
                            batch_sizes=(1, self.slots),
                            cache=self.tuning_cache)
        except Exception:
            log.debug("mesh retune for %s skipped", desc, exc_info=True)

    def stats(self) -> dict:
        out = super().stats()
        from repro.mesh.strategy import descriptor
        out["mesh"] = {"axis": self.mesh_axis,
                       "shards": int(self.mesh.shape[self.mesh_axis]),
                       "devices": int(self.mesh.devices.size),
                       "descriptor": descriptor(self.mesh)}
        if self.domains is not None:
            out["mesh"]["hosts"] = self.domains.describe()
        out["resilience"]["host_losses"] = self._n_host_losses
        return out
