"""Compilation options — explicit, immutable, thread-locally scoped.

:class:`CompileOptions` is the record the seed hid in process-wide globals
(``kernels.ops._DEFAULT_IMPL`` / ``_AUTOTUNE``): which kernel impl to use,
whether the strategy autotuner may pick params, which tuning cache it reads,
and Pallas interpret mode.  It is threaded *explicitly* — every op takes an
``options=`` argument — with a thread-local context-manager stack for
scoping:

    with compiler.options(backend="dpia-pallas", autotune=False):
        y = ops.matmul(a, b)          # sees the scoped options

Scopes nest (inner scopes inherit unset fields from the enclosing scope) and
are per-thread, so concurrent serving threads can run different backends
without racing on a global.  The process-wide *default* (what
``current_options()`` returns outside any scope) exists for the deprecated
``set_default_impl``/``set_autotune`` shims and for program start-up
configuration via :func:`set_default_options`.
"""
from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from dataclasses import dataclass, field, fields, replace as _dc_replace
from typing import Optional

from .backends import ops_impls

__all__ = ["CompileOptions", "options", "current_options",
           "set_default_options", "default_options", "default_interpret"]


def _env_autotune() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "1") != "0"


def default_interpret() -> bool:
    """Whether Pallas kernels should default to interpret mode here: True
    only when the host platform is CPU (no Mosaic compiler), False on real
    accelerators.  ``REPRO_INTERPRET=0|1`` overrides the probe on the CPU;
    on an accelerator ``REPRO_INTERPRET=1`` is an error, so a kernel never
    runs in the interpreter where the chip could run it."""
    import jax
    cpu = jax.default_backend() == "cpu"
    env = os.environ.get("REPRO_INTERPRET")
    if env is not None and env != "0" and not cpu:
        raise RuntimeError(
            f"REPRO_INTERPRET={env} on platform {jax.default_backend()!r}: "
            f"interpret mode is for CPU hosts only; unset it to compile the "
            f"Pallas kernels for the device")
    if env is not None:
        return env != "0"
    return cpu


@dataclass(frozen=True)
class CompileOptions:
    """Everything a kernel compilation depends on besides the term itself.

    backend       kernel-layer impl name: 'xla' | 'pallas' | 'dpia-<stage3>'
                  (validated against the backend registry)
    autotune      let repro.autotune choose strategy params (default: the
                  REPRO_AUTOTUNE env var, read at import)
    tuning_cache  None (process default cache), a path, or a TuningCache
    interpret     run Pallas kernels in interpret mode (default: auto from
                  the platform — True only on CPU; see default_interpret)
    jit           wrap compiled programs in jax.jit
    mesh          jax.sharding.Mesh for mesh-level backends (dpia-shardmap)
                  and mesh-keyed tuning; None defers to the process mesh
                  context (repro.sharding.ctx.get_mesh()), so single-device
                  runs stay single-device without ever naming a mesh
    kv_layout     the serving KV-memory strategy this compilation scope
                  belongs to ('dense' | 'paged'); a cache-key dimension
                  (executor + tuning caches) like the mesh descriptor, so
                  artefacts staged for one memory layout never serve the
                  other
    """
    backend: str = "xla"
    autotune: bool = field(default_factory=_env_autotune)
    tuning_cache: object = None
    interpret: bool = field(default_factory=default_interpret)
    jit: bool = True
    mesh: object = None
    kv_layout: str = "dense"

    def __post_init__(self):
        valid = ops_impls()
        if self.backend not in valid:
            raise ValueError(
                f"unknown backend {self.backend!r}; valid backends: "
                f"{list(valid)}")
        if self.kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be 'dense' or 'paged', got "
                             f"{self.kv_layout!r}")

    def replace(self, **kw) -> "CompileOptions":
        """A copy with the given fields replaced (validates like __init__)."""
        return _dc_replace(self, **kw)

    @property
    def dpia_backend(self) -> str:
        """The Stage III backend name this impl choice maps to."""
        if self.backend.startswith("dpia-"):
            return self.backend[len("dpia-"):]
        # native impls validate DPIA programs on the reference backend
        return "jnp"

    def resolved_mesh(self):
        """The concrete Mesh mesh-level compilation runs against: the
        explicit ``mesh`` field, else the process mesh context
        (``repro.sharding.ctx``).  None means single-device."""
        if self.mesh is not None:
            return self.mesh
        from repro.sharding import ctx
        return ctx.get_mesh()

    def mesh_descriptor(self) -> str:
        """Canonical descriptor of :meth:`resolved_mesh` — the mesh
        component every tuning/executor cache key carries (``"single"``
        when no mesh is in scope)."""
        from repro.mesh import descriptor
        return descriptor(self.resolved_mesh())


class _Scope(threading.local):
    def __init__(self):
        self.stack = []


_SCOPE = _Scope()
_DEFAULT_LOCK = threading.Lock()
_DEFAULT: Optional[CompileOptions] = None


def default_options() -> CompileOptions:
    """The process-wide default options (outside any ``options()`` scope)."""
    global _DEFAULT
    if _DEFAULT is None:
        with _DEFAULT_LOCK:
            if _DEFAULT is None:
                _DEFAULT = CompileOptions()
    return _DEFAULT


def set_default_options(**kw) -> CompileOptions:
    """Replace fields of the process-wide default options.

    This is start-up configuration (and the target the deprecated
    ``ops.set_default_impl``/``set_autotune`` shims delegate to) — inside an
    active ``with options(...)`` scope the scoped options still win."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        base = _DEFAULT if _DEFAULT is not None else CompileOptions()
        _DEFAULT = base.replace(**kw) if kw else base
    return _DEFAULT


def current_options() -> CompileOptions:
    """The innermost active options scope on this thread, else the default."""
    stack = _SCOPE.stack
    return stack[-1] if stack else default_options()


@contextmanager
def options(opts: Optional[CompileOptions] = None, **kw):
    """Scope compile options for the current thread.

    Either pass a full :class:`CompileOptions`, or keyword overrides which
    are applied on top of the *current* options (so scopes nest/inherit)::

        with compiler.options(backend="dpia-jnp"):
            with compiler.options(autotune=False):   # backend still dpia-jnp
                ...
    """
    if opts is not None and kw:
        raise TypeError("options(): pass either a CompileOptions or field "
                        "overrides, not both")
    if opts is None:
        opts = current_options().replace(**kw) if kw else current_options()
    elif not isinstance(opts, CompileOptions):
        raise TypeError(f"options() expects CompileOptions, got "
                        f"{type(opts).__name__}")
    _SCOPE.stack.append(opts)
    try:
        yield opts
    finally:
        _SCOPE.stack.pop()


# keep the field list discoverable for docs/tests
OPTION_FIELDS = tuple(f.name for f in fields(CompileOptions))
