"""The compression kernels' share of their HBM roofline: the least bytes
they must move per round (16 B per element routed to them, ``counts.py``)
over the peak bandwidth, divided by their device time per round."""


def read(ctx):
    s = ctx.view.kind_s("mosaic")
    if s <= 0 or ctx.peak is None:
        return None
    least_s = (ctx.compress_bytes_per_round / ctx.chips
               / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (s / ctx.rounds)
