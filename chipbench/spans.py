"""The device's idle time split by the program's own host spans.

``LGCTransformerTask.run`` opens a step span ``lgc.round`` per sync round
and, inside it, the spans ``lgc.mask``, ``lgc.batch``, ``lgc.step`` and
``lgc.readback`` (and one ``lgc.mask`` before the first round); the
profiler records them on the host planes, on the clock of the device's
"XLA Ops" line.  Each idle stretch of a chip (the
traced window less the union of its ops) is credited to the innermost
program span over it: a child span, else ``lgc.round`` itself, else
``NO_SPAN``.  A trace of a program without these spans holds none of them.
"""
from __future__ import annotations

PREFIX = "lgc."
ROUND = "lgc.round"
NO_SPAN = "no span"


def idle_by_span(view) -> dict:
    """Idle seconds of each chip under each innermost program span, mean
    over the chips; {} when the trace holds no ``lgc.round`` span."""
    spans = [(n, s, s + d) for n, s, d in view.host if n.startswith(PREFIX)]
    if not view.devices or not any(n == ROUND for n, _, _ in spans):
        return {}
    t0, t1 = view.window
    out = {}
    for evs in view.devices.values():
        busy = view.busy_intervals(evs)
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            cuts = sorted({a, b} | {x for _, s, e in spans for x in (s, e)
                                    if a < x < b})
            for x, y in zip(cuts, cuts[1:]):
                over = [(e - s, n) for n, s, e in spans if s <= x and y <= e]
                name = min(over)[1] if over else NO_SPAN
                out[name] = out.get(name, 0.0) + (y - x) / 1e9
    return {n: s / len(view.devices) for n, s in out.items()}


def idle_ms_per_round(ctx, names) -> float | None:
    """Idle ms per round under the named spans; None without the spans."""
    idle = idle_by_span(ctx.view)
    if not idle:
        return None
    return 1e3 * sum(idle.get(n, 0.0) for n in names) / ctx.rounds
