"""Device time per round of the sort and top-k ops (the layered sparse
selection's ``lax.top_k`` per channel), mean over the chips."""


def read(ctx):
    s = ctx.view.kind_s("sort")
    return 1e3 * s / ctx.rounds if s > 0 else None
