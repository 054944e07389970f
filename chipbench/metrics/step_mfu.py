"""The whole round's share of the chips' bf16 peak over the traced window:
model FLOPs of the traced rounds (PaLM's count, ``counts.py``) over
window x chips x peak."""


def read(ctx):
    if ctx.peak is None:
        return None
    return 100.0 * ctx.flops_per_round * ctx.rounds / (
        ctx.view.window_s() * ctx.chips * ctx.peak["bf16_flops_per_s"])
