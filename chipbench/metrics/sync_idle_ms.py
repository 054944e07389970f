"""Device-idle time per round while the host dispatches the step and reads
its loss back: under the program's host spans ``lgc.step`` and
``lgc.readback``, mean over the chips (``chipbench/spans.py``)."""
from chipbench import spans


def read(ctx):
    return spans.idle_ms_per_round(ctx, ("lgc.step", "lgc.readback"))
