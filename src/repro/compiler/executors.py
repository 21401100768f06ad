"""Process-wide executor cache: compiled Stage III callables, reused across
calls and persistable ahead-of-time.

The op layer (``repro.kernels.ops``) used to keep a private dict of compiled
Programs; this module promotes that dict to a compiler-level service with

  * canonical keys — ``(kernel, shape, dtype, backend, params, options bits)``
    rendered as one stable string, so the same executor is found no matter
    which layer asks for it;
  * hit/build statistics — ``benchmarks/serve_bench.py`` and the serving
    tests read these to assert "zero recompiles after warm-up";
  * an AOT store — ``save_aot(dir)`` exports every cached entry's *lowered*
    program (via ``Program.export``) next to the tuning cache, and
    ``load_aot(dir)`` rebuilds the executors in a fresh process without
    redoing Stage I->II translation or the SCIR check.

Stage III code generation itself stays lazy: a rebuilt executor is a
``jax.jit``-wrapped closure whose XLA compilation happens on first call,
exactly as for a freshly staged Program.
"""
from __future__ import annotations

import hashlib
import os
import threading
from typing import Callable, Dict, Optional, Tuple

from repro import obs
from repro.ft import artefacts
from repro.testing import faults

__all__ = ["ExecutorCache", "make_key", "default_cache"]

# v2: make_key gained the mesh-descriptor component; v3: the kv_layout
# component — older artefacts' keys can never hit again, so they must not
# be parsed/compiled on load
AOT_VERSION = 3


def _fmt_params(params: Optional[Dict[str, object]]) -> str:
    if params is None:
        return "default"
    return ",".join(f"{k}={params[k]}" for k in sorted(params)) or "default"


def make_key(kernel: str, shape: Dict[str, object], backend: str, *,
             params: Optional[Dict[str, object]] = None,
             dtype: str = "float32", mesh: str = "single",
             layout: str = "dense",
             interpret: bool = True, jit: bool = True) -> str:
    """Canonical executor key.  Every component the compiled artefact depends
    on is in the key (same discipline as the tuning cache) — including the
    mesh descriptor (``repro.mesh.descriptor``) and the serving KV layout
    (``CompileOptions.kv_layout``), so an executor compiled for one mesh or
    memory strategy can never serve another — and a hit is always safe to
    reuse."""
    shape_s = ",".join(f"{k}={shape[k]}" for k in sorted(shape))
    return (f"{kernel}|{shape_s}|{dtype}|{backend}|{mesh or 'single'}"
            f"|{layout or 'dense'}|{_fmt_params(params)}"
            f"|interpret={int(bool(interpret))}|jit={int(bool(jit))}")


class ExecutorCache:
    """Memoised compiled kernels + AOT persistence.

    ``get_or_compile`` is the one dispatch entry: steady state is a dict
    lookup; a cold key runs the supplied builder (typically
    ``Program.check().lower().compile(backend)``) exactly once per process
    (two racing threads may both build; ``setdefault`` keeps one result).
    """

    def __init__(self):
        self._mem: Dict[str, object] = {}
        self._meta: Dict[str, dict] = {}
        self._lock = threading.Lock()
        self._hits = 0
        self._builds = 0
        self._aot_loads = 0

    # -- dispatch -----------------------------------------------------------

    def get(self, key: str):
        return self._mem.get(key)

    def get_or_compile(self, key: str, build: Callable[[], object], *,
                       meta: Optional[dict] = None):
        fn = self._mem.get(key)
        if fn is not None:
            with self._lock:
                self._hits += 1
            obs.event("executor_cache.hit", key=key)
            return fn
        # deterministic build-failure drill (``executor.build``, ctx: key):
        # raises here so the op layer's degradation ladder handles it the
        # same way as a real staging/compile failure
        faults.raise_if("executor.build", key=key)
        with obs.span("executor_cache.build", key=key):
            fn = build()
        with self._lock:
            self._builds += 1
            if meta:
                self._meta.setdefault(key, dict(meta))
        obs.counter("executor_cache.builds").inc()
        return self._mem.setdefault(key, fn)

    def put(self, key: str, fn, *, meta: Optional[dict] = None) -> None:
        with self._lock:
            self._mem[key] = fn
            if meta:
                self._meta[key] = dict(meta)

    def __contains__(self, key: str) -> bool:
        return key in self._mem

    def __len__(self) -> int:
        return len(self._mem)

    def keys(self):
        return list(self._mem)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"entries": len(self._mem), "hits": self._hits,
                    "builds": self._builds, "aot_loads": self._aot_loads}

    def clear(self) -> None:
        with self._lock:
            self._mem.clear()
            self._meta.clear()
            self._hits = self._builds = self._aot_loads = 0

    # -- AOT store ----------------------------------------------------------

    @staticmethod
    def _aot_path(directory: str, key: str) -> str:
        h = hashlib.sha1(key.encode()).hexdigest()[:16]
        return os.path.join(directory, f"prog-{h}.json")

    def save_aot(self, directory: str, keys=None) -> int:
        """Export cached executors whose provenance is a Program.

        ``keys`` restricts the export to those cache keys — callers that
        warmed a specific set (a serving engine) pass it so a shared
        process cache never leaks another model's programs into their AOT
        directory.  Files already present are left alone (the export is
        content-addressed by key), so repeated warm-ups are cheap.  The
        directory is append-only: a key retired by e.g. new tuned params
        leaves its file behind, costing one JSON parse on later loads.
        Returns the number of programs written."""
        from .backends import get_backend
        from .program import CompiledKernel
        os.makedirs(directory, exist_ok=True)
        keyset = None if keys is None else set(keys)
        written = 0
        for key, fn in list(self._mem.items()):
            if not isinstance(fn, CompiledKernel):
                continue
            if keyset is not None and key not in keyset:
                continue
            try:
                if get_backend(fn.backend).requires:
                    # backends with compile-time requirements (shardmap's
                    # mesh) cannot be rebuilt from a doc in a later process
                    # — those executors re-stage on restart, never export
                    continue
            except ValueError:
                continue  # backend no longer registered
            path = self._aot_path(directory, key)
            if os.path.exists(path):
                continue
            meta = self._meta.get(key, {})
            if "interpret" not in meta:
                continue  # unknown interpret mode: a load could not honour it
            try:
                prog_doc = fn.program.to_doc()
            except Exception:
                continue  # no persistable lowering: skip, don't crash
            doc = {
                "version": AOT_VERSION,
                "key": key,
                "backend": fn.backend,
                "interpret": bool(meta["interpret"]),
                "jit": bool(meta.get("jit", True)),
                "program": prog_doc,
            }
            # checksummed + atomic (repro.ft.artefacts): a torn or
            # bit-flipped program file is detected and quarantined at load
            # instead of silently skipped
            artefacts.save_json(path, doc)
            written += 1
        return written

    def load_aot(self, directory: str) -> int:
        """Populate the cache from an AOT directory (idempotent).

        Each artefact is rebuilt as an imperative-only Program and compiled
        through the backend registry with its persisted options bits —
        Stage I->II and the SCIR check are skipped entirely.  Version skew
        is a silent skip (expected after an upgrade); a CORRUPT file —
        unparseable, or failing its embedded checksum — is quarantined to
        ``<directory>/.quarantine/`` and reported through the always-on
        ``artefact.load_failed`` counter (repro.ft.artefacts), never
        silently dropped.  A file whose program fails to REBUILD (e.g. its
        backend grew unmet requirements) is reported but left in place —
        the file is intact; the environment changed.  Returns the number
        of executors loaded."""
        from .backends import get_backend
        from .program import Program
        if not os.path.isdir(directory):
            return 0
        qdir = os.path.join(directory, ".quarantine")
        loaded = 0
        for name in sorted(os.listdir(directory)):
            if not name.endswith(".json") or name.startswith("."):
                continue
            path = os.path.join(directory, name)
            doc = artefacts.load_json(path, what="AOT program", qdir=qdir)
            if doc is None:
                continue  # corrupt (quarantined + reported) or vanished
            try:
                if doc.get("version") != AOT_VERSION:
                    continue
                key = doc["key"]
                if key in self._mem:
                    continue
                prog = Program.from_doc(doc["program"])
                b = get_backend(doc["backend"])
                kw = {}
                if "interpret" in b.accepts:
                    if "interpret" not in doc:
                        raise ValueError(f"AOT artefact {name} does not "
                                         f"record its interpret mode")
                    kw["interpret"] = bool(doc["interpret"])
                with obs.span("executor_cache.aot_load", key=key,
                              backend=doc["backend"]):
                    fn = prog.compile(b, jit=bool(doc.get("jit", True)),
                                      **kw)
                self.put(key, fn, meta={"interpret": doc.get("interpret"),
                                        "jit": doc.get("jit")})
                with self._lock:
                    self._aot_loads += 1
                obs.counter("executor_cache.aot_loads").inc()
                # the staged strategy arrived via the AOT store: record it
                # (the params component is the 7th field of the canonical
                # key — see make_key)
                parts = key.split("|")
                obs.record("executor", prog.kernel or prog.name, key,
                           {"params": parts[6] if len(parts) > 6 else "?"},
                           "aot-loaded", shape=dict(prog.shape),
                           backend=doc["backend"],
                           strategy_trace=prog.strategy_trace,
                           note=f"program {prog.name!r} rebuilt from "
                                f"{directory}")
                loaded += 1
            except (OSError, ValueError, KeyError, TypeError) as e:
                # a well-formed file that cannot be rebuilt here (e.g.
                # TypeError: its backend now has unmet compile
                # requirements) — report, skip, never poison the whole
                # load; the file stays for a process that CAN rebuild it
                artefacts.report_load_failure(path, "AOT program", e)
                continue
        return loaded


_default: Optional[ExecutorCache] = None
_default_lock = threading.Lock()


def default_cache() -> ExecutorCache:
    """The process-wide executor cache (what ``kernels.ops`` dispatches on)."""
    global _default
    with _default_lock:
        if _default is None:
            _default = ExecutorCache()
        return _default
