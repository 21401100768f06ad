"""Model step, decode: FLOPs of the tokens owed to busy slots (matmuls and
attention over each slot's valid context, as the architecture counts them)
over the device time of the decode-chunk program in the traced window, as
a share of the chip's peak bf16 FLOP/s."""
from bench import spans as S
from bench import spec


def read(ctx):
    if ctx.trace is None:
        return None
    _, secs = ctx.trace.programs.get(spec.DECODE_PROGRAM, (0, 0.0))
    a = spec.arch(ctx.cfg)
    flops = sum(a.decode_step_flops(ctx.cfg, step, b, sp)
                for b, sp in S.by_boundary(ctx) for step in b.decode)
    if not secs or not flops:
        return None
    return 100.0 * flops / secs / ctx.peaks["flops"]
