"""Fault tolerance: step watchdog (straggler detection), NaN guards with
step retry, auto-resume from the latest checkpoint, and elastic re-meshing.

At 1000+ nodes the failure model is: slow host (straggler), dead host
(restart), corrupted step (NaN/inf from flaky HBM).  The pieces here:

  * Watchdog       — per-step deadline; on breach it records the straggler
                     event (hook point for re-scheduling / pre-emption).
  * guard_update   — reject non-finite losses/grad-norms; the caller skips
                     the update (step retried with the next data batch —
                     deterministic data makes this reproducible).
  * TrainLoop      — checkpoint every N steps (async), restore-latest on
                     entry, bounded retry on exceptions.
  * elastic_remesh — rebuild a mesh from the currently-available device set
                     and re-place a host-resident checkpoint onto it.
"""
from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Dict, Optional

import jax
import numpy as np

log = logging.getLogger("repro.ft")


class Watchdog:
    """Flags steps exceeding ``deadline_s`` (straggler mitigation hook).

    Race-free: ``threading.Timer.cancel()`` does not stop a callback that
    has already started, so ``_fire`` can run concurrently with — or just
    after — ``disarm()`` on the step-completion path, recording a spurious
    straggler for a step that finished in time.  Every ``arm()`` therefore
    issues a generation token; ``_fire`` re-checks under the lock that its
    generation is still the armed one (and fires at most once per arm),
    and ``disarm()`` retires the generation before cancelling the timer.
    Used by ``TrainLoop`` per train step and by the serving engines as the
    chunk-level straggler detector (``repro.serve.engine``).
    """

    def __init__(self, deadline_s: float = 300.0,
                 on_straggler: Optional[Callable[[int, float], None]] = None):
        self.deadline = deadline_s
        self.on_straggler = on_straggler or (
            lambda step, dt: log.warning(
                "step %d exceeded deadline (%.1fs > %.1fs) — straggler "
                "suspected", step, dt, self.deadline))
        self.events = []
        self._armed_at: Optional[float] = None
        self._step = 0
        self._timer: Optional[threading.Timer] = None
        self._lock = threading.Lock()
        self._gen = 0           # incremented on every arm
        self._live_gen = -1     # the generation allowed to fire (-1: none)
        self._fired = False     # current generation already fired

    def arm(self, step: int) -> None:
        with self._lock:
            self._retire_locked()
            self._gen += 1
            self._live_gen = self._gen
            self._fired = False
            self._step = step
            self._armed_at = time.monotonic()
            self._timer = threading.Timer(self.deadline, self._fire,
                                          args=(self._gen,))
            self._timer.daemon = True
            self._timer.start()

    def _fire(self, gen: int) -> None:
        with self._lock:
            if gen != self._live_gen or self._fired:
                return  # disarmed (step completed) or duplicate firing
            self._fired = True
            step = self._step
            dt = time.monotonic() - (self._armed_at or time.monotonic())
            self.events.append((step, dt))
        self.on_straggler(step, dt)

    def disarm(self) -> None:
        with self._lock:
            self._retire_locked()

    def _retire_locked(self) -> None:
        self._live_gen = -1
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None


def guard_update(metrics: Dict) -> bool:
    """True if the step's numerics are sane (update may be applied)."""
    loss = float(metrics.get("loss", 0.0))
    gn = float(metrics.get("grad_norm", 0.0))
    return bool(np.isfinite(loss) and np.isfinite(gn))


def elastic_remesh(preferred, axis_names=None):
    """Build the largest mesh the *currently available* devices support,
    shrinking the leading (data) axis first — elastic scale-down after node
    loss; checkpoints re-place transparently because they are stored
    mesh-agnostically (ckpt/manager.py).

    ``preferred`` is a canonical mesh descriptor string
    (``"data=8"`` / ``"data=8,model=2"`` — ``repro.mesh.strategy``) or a
    legacy shape tuple paired with ``axis_names``.  The shrink itself is
    :func:`repro.mesh.strategy.shrink_descriptor`, so the shape the mesh is
    built from round-trips through ``parse_descriptor`` and is exactly what
    tuning/executor cache keys will carry for it."""
    from repro.mesh import strategy as ms
    if isinstance(preferred, str):
        if axis_names is not None:
            raise TypeError("axis_names only applies to shape-tuple form; "
                            "a descriptor string already names its axes")
        desc = preferred
    else:
        if axis_names is None:
            raise TypeError("shape-tuple form needs axis_names")
        desc = ",".join(f"{a}={int(s)}"
                        for a, s in zip(axis_names, preferred))
    axes = ms.parse_descriptor(ms.shrink_descriptor(desc, len(jax.devices())))
    if not axes:
        raise ValueError(f"elastic_remesh needs at least one axis, got "
                         f"{preferred!r}")
    from repro.launch.mesh import make_mesh
    return make_mesh(tuple(axes.values()), tuple(axes.keys()))


class TrainLoop:
    """Checkpointed, auto-resuming, NaN-guarded train loop."""

    def __init__(self, step_fn, ckpt_mgr, data, *, ckpt_every: int = 100,
                 max_retries: int = 3, deadline_s: float = 600.0):
        self.step_fn = step_fn
        self.ckpt = ckpt_mgr
        self.data = data
        self.ckpt_every = ckpt_every
        self.max_retries = max_retries
        self.watchdog = Watchdog(deadline_s)
        self.skipped_steps = 0

    def run(self, state, *, num_steps: int, on_metrics=None):
        restored = self.ckpt.restore_latest(state)
        start = 0
        data_state = None
        if restored is not None:
            start, state, extra = restored
            data_state = extra.get("data_state")
            log.info("resumed from checkpoint step %d", start)

        from repro.data.pipeline import DataState
        ds = (DataState.from_dict(data_state) if data_state
              else DataState(step=start))
        it = self.data.iterator(ds)

        retries = 0
        step = start
        while step < num_steps:
            batch, ds = next(it)
            try:
                self.watchdog.arm(step)
                new_state, metrics = self.step_fn(state, batch)
                metrics = jax.device_get(metrics)
                self.watchdog.disarm()
            except Exception:
                self.watchdog.disarm()
                retries += 1
                if retries > self.max_retries:
                    raise
                log.exception("step %d failed; restoring last checkpoint "
                              "(retry %d/%d)", step, retries,
                              self.max_retries)
                restored = self.ckpt.restore_latest(state)
                if restored is not None:
                    step, state, extra = restored
                    ds = DataState.from_dict(extra.get(
                        "data_state", {"step": step}))
                    it = self.data.iterator(ds)
                continue

            if not guard_update(metrics):
                # the train step suppressed the update in-graph (train/step.py
                # 'applied' guard); record the event and move on
                log.warning("step %d non-finite (loss=%s) — update was "
                            "suppressed in-graph", step, metrics.get("loss"))
                self.skipped_steps += 1

            state = new_state
            retries = 0
            if on_metrics:
                on_metrics(step, metrics)
            step += 1
            if step % self.ckpt_every == 0:
                self.ckpt.save(step, state,
                               extra={"data_state": ds.to_dict()})
        self.ckpt.wait()
        return state
