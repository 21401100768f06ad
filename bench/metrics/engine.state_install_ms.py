"""Engine layer: the mean duration of the traced ``serve.state_install``
spans, the admission's write of a slot's prefilled recurrent state into
the engine's state for every Mamba layer.  A program without the span (or
a model without recurrent state) gives nothing."""
from bench import spans as S


def read(ctx):
    installs = S.named(S.window(ctx), "serve.state_install")
    if not installs:
        return None
    ctx.note(f"engine.state_install_ms: {len(installs)} installs")
    return sum(s.dur_ns for s in installs) / len(installs) / 1e6
