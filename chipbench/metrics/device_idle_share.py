"""Share of the traced window in which no op ran on the device, mean over
the chips: 1 - union of the device-op intervals / window."""


def read(ctx):
    return 100.0 * (1.0 - ctx.view.busy_s() / ctx.view.window_s())
