"""Device time of the Pallas compression kernels (maxabs, histogram,
sparsify_ef: the trace's Mosaic ops) per round, mean over the chips."""


def read(ctx):
    s = ctx.view.kind_s("mosaic")
    return 1e3 * s / ctx.rounds if s > 0 else None
