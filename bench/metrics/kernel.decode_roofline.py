"""Kernels (the jitted decode-chunk program): the least time the chip
needs for the decode steps of the traced window over the time the program
took.  A step's least time is the larger of its bytes (weights, valid KV
read, KV written) over peak HBM bandwidth and its FLOPs over peak FLOP/s;
steps in which no slot is owed a token need none.  The architecture
counts the bytes and FLOPs."""
from bench import spans as S
from bench import spec


def read(ctx):
    if ctx.trace is None:
        return None
    _, secs = ctx.trace.programs.get(spec.DECODE_PROGRAM, (0, 0.0))
    bw, peak = ctx.peaks["hbm_bytes_per_s"], ctx.peaks["flops"]
    a = spec.arch(ctx.cfg)
    by_bytes = by_flops = least = 0.0
    for b, sp in S.by_boundary(ctx):
        for step in b.decode:
            tb = a.decode_step_bytes(ctx.cfg, step, b, sp) / bw
            tf = a.decode_step_flops(ctx.cfg, step, b, sp) / peak
            by_bytes, by_flops, least = (by_bytes + tb, by_flops + tf,
                                         least + max(tb, tf))
    if not secs or not least:
        return None
    ctx.note(f"decode roofline: bytes bound {by_bytes:.6f} s, flops bound "
             f"{by_flops:.6f} s, decode program {secs:.6f} s")
    return 100.0 * least / secs
