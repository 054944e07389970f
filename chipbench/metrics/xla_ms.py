"""Device time per round of every op that is neither a Mosaic kernel nor a
collective: local forward and backward, the XLA parts of compression and
selection, the server update.  Mean over the chips."""


def read(ctx):
    return 1e3 * (ctx.view.kind_s("xla") + ctx.view.kind_s("sort")) / ctx.rounds
