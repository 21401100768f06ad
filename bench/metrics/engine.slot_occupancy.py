"""Engine layer: the share of decode slots holding a request, read from
the scheduler after every boundary of the window and averaged."""


def read(ctx):
    occ = ctx.window.occupancy
    return 100.0 * sum(occ) / len(occ) if occ else None
