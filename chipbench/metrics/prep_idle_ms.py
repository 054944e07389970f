"""Device-idle time per round while the host prepares the round's inputs:
under the program's host spans ``lgc.mask`` (the delivery-mask program and
its transfer) and ``lgc.batch`` (the next batch and its transfers), mean
over the chips (``chipbench/spans.py``)."""
from chipbench import spans


def read(ctx):
    return spans.idle_ms_per_round(ctx, ("lgc.mask", "lgc.batch"))
