"""Device self time per round of the ops whose innermost ``lgc.*`` scope is
``lgc.compress``: the delta, every leaf's layered selection (kernels and
XLA alike) and the wire cast, mean over the chips.  None where the trace
was read without the step's scopes."""


def read(ctx):
    s = ctx.view.innermost_s("lgc.").get("lgc.compress", 0.0)
    return 1e3 * s / ctx.rounds if s > 0 else None
