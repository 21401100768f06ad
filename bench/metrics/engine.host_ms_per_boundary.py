"""Engine layer: host milliseconds per boundary, the mean over the traced
``serve.step_chunk`` spans of their duration less the ``serve.device_wait``
spans inside them (the host blocked on the device)."""
from bench import spans as S


def read(ctx):
    sp = S.window(ctx)
    steps, waits = S.named(sp, "serve.step_chunk"), S.named(
        sp, "serve.device_wait")
    if not steps:
        return None
    host = [s.dur_ns - sum(w.dur_ns for w in S.within(s, waits))
            for s in steps]
    ctx.note(f"engine.host_ms_per_boundary: {len(steps)} boundaries, "
             f"{len(waits)} device waits")
    return sum(host) / len(host) / 1e6
