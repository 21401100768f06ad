"""Model step, decode: FLOPs of the tokens owed to busy slots (matmuls and
attention over each slot's valid context) over the device time of the
decode-chunk program in the traced window, as a share of the chip's peak
bf16 FLOP/s."""
from bench import work
from bench.system import DECODE_PROGRAM


def read(ctx):
    if ctx.trace is None:
        return None
    _, secs = ctx.trace.programs.get(DECODE_PROGRAM, (0, 0.0))
    flops = sum(work.decode_step_flops(ctx.cfg, step)
                for b in ctx.traced for step in b.decode)
    if not secs or not flops:
        return None
    return 100.0 * flops / secs / ctx.peaks["flops"]
