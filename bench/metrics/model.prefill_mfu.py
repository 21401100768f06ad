"""Model step, prefill: FLOPs of the real prompt tokens (bucket padding
excluded) over the device time of the prefill programs in the traced
window, as a share of the chip's peak bf16 FLOP/s."""
from bench import work
from bench.system import PREFILL_PROGRAM


def read(ctx):
    if ctx.trace is None:
        return None
    _, secs = ctx.trace.programs.get(PREFILL_PROGRAM, (0, 0.0))
    flops = sum(work.prefill_flops(ctx.cfg, p)
                for b in ctx.traced for p in b.prefills)
    if not secs or not flops:
        return None
    return 100.0 * flops / secs / ctx.peaks["flops"]
