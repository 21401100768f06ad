"""Engine layer: p95 over the requests whose ``serve.request.prefill`` span
(admission to first token sampled) ends in the traced window: the wait
behind the serial prefills admitted at the same boundary, and its own."""
import numpy as np

from bench import spans as S


def read(ctx):
    waits = [s.dur_ns for s in S.named(S.window(ctx),
                                       "serve.request.prefill")]
    if not waits:
        return None
    ctx.note(f"engine.prefill_wait_p95_ms: {len(waits)} requests")
    return float(np.percentile(waits, 95)) / 1e6
