"""Model step, prefill: FLOPs of the real prompt tokens (bucket padding
excluded, as the architecture counts them) over the device time of the
prefill programs in the traced window, as a share of the chip's peak bf16
FLOP/s."""
from bench import spans as S
from bench import spec


def read(ctx):
    if ctx.trace is None:
        return None
    _, secs = ctx.trace.programs.get(spec.PREFILL_PROGRAM, (0, 0.0))
    a = spec.arch(ctx.cfg)
    flops = sum(a.prefill_flops(ctx.cfg, p, b, sp)
                for b, sp in S.by_boundary(ctx) for p in b.prefills)
    if not secs or not flops:
        return None
    return 100.0 * flops / secs / ctx.peaks["flops"]
