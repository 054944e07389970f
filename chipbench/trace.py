"""Reduction of a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps,
for each chip, the events of its "XLA Ops" line (one per executed HLO op;
a ``while`` op's event spans the events of its body, so each op is
credited its self time, its duration less its direct children's), and the
host's events.  The traced window is the host
span ``WINDOW`` that the harness opens around the traced call.  A
``View`` goes to and from JSON, so the reducers are tested on trimmed
traces recorded on the chip.

Op kinds, from the HLO text the chip's trace gives each op
(``%name = type opcode(...), ...``):
  * ``mosaic``: Pallas kernels (``custom_call_target="tpu_custom_call"``);
  * ``collective``: all-reduce, all-gather, reduce-scatter, all-to-all,
    collective-permute (and their async start/done halves), by op name;
  * ``sort``: sort and top-k, by op name;
  * ``xla``: every other op.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "chipbench.window"
DEVICE_LINE = "XLA Ops"

_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|allreduce|allgather")
_SORT = re.compile(r"sort|top-?k", re.IGNORECASE)


@dataclasses.dataclass
class View:
    """Device ops per chip and host events, in ns on one clock."""
    devices: dict   # plane name -> [[name, kind, start_ns, dur_ns, self_ns]]
    host: list             # [[name, start_ns, dur_ns]]
    window: tuple          # (start_ns, end_ns)

    @classmethod
    def from_json(cls, d: dict) -> "View":
        return cls(d["devices"], d["host"], tuple(d["window"]))

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def ops(self, kind: str | None = None):
        """Every device op in the window (all chips), optionally of a kind."""
        t0, t1 = self.window
        for evs in self.devices.values():
            for name, k, start, dur, own in evs:
                if start >= t0 and start + dur <= t1 and (
                        kind is None or k == kind):
                    yield name, k, start, dur, own

    def busy_intervals(self, events) -> list:
        """Union of the events' intervals, clipped to the window."""
        t0, t1 = self.window
        spans = sorted((max(e[2], t0), min(e[2] + e[3], t1)) for e in events
                       if e[2] + e[3] > t0 and e[2] < t1)
        merged = []
        for s, e in spans:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return merged

    def busy_s(self) -> float:
        """Seconds in which an op ran on the device, mean over the chips."""
        per = []
        for evs in self.devices.values():
            ivs = self.busy_intervals(evs)
            per.append(sum(e - s for s, e in ivs) / 1e9)
        return sum(per) / max(len(per), 1)

    def kind_s(self, kind: str) -> float:
        """Summed self seconds of one op kind, mean over the chips."""
        return sum(e[4] for e in self.ops(kind)) / 1e9 / max(
            len(self.devices), 1)

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time, and the longest idle gaps
        of the first chip labelled by the host event that covers most of
        each gap."""
        per_op = {}
        for name, _, _, _, own in self.ops():
            per_op[name] = per_op.get(name, 0) + own
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        first = next(iter(self.devices.values()), [])
        busy = self.busy_intervals(first)
        t0, t1 = self.window
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, d / 1e9] for n, d in ops],
                "idle_gaps": [[self._host_label(s, e), (e - s) / 1e9]
                              for s, e in gaps]}

    def _host_label(self, s: int, e: int) -> str:
        best, key = "no host event", (0, 0)
        for name, hs, hd in self.host:
            cover = min(e, hs + hd) - max(s, hs)
            # most of the gap covered; of equals, the innermost event
            if name != WINDOW and cover > 0 and (cover, -hd) > key:
                best, key = name, (cover, -hd)
        return best


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_kind(hlo: str) -> str:
    if 'custom_call_target="tpu_custom_call"' in hlo:
        return "mosaic"
    name = op_name(hlo)
    if _COLLECTIVE.search(name):
        return "collective"
    if _SORT.search(name):
        return "sort"
    return "xla"


def with_self_time(events) -> list:
    """[name, kind, start, dur] -> [..., self]: the duration less that of
    the events directly nested in it (a loop's body ops)."""
    out = [list(e) + [e[3]] for e in sorted(events,
                                            key=lambda e: (e[2], -e[3]))]
    stack = []
    for e in out:
        while stack and stack[-1][2] + stack[-1][3] <= e[2]:
            stack.pop()
        if stack:
            stack[-1][4] -= e[3]
        stack.append(e)
    return out


def load(trace_dir: str, chips: int) -> View:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[0])
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    devices[plane.name] = with_self_time(
                        [op_name(ev.name), op_kind(ev.name),
                         int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events)
    devices = dict(sorted(devices.items())[:chips])
    spans = [(s, s + d) for n, s, d in host if n == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} host span")
    return View(devices, host, spans[0])


@dataclasses.dataclass
class Context:
    """What a per-layer reducer reads: the trace view and the counts."""
    view: View
    rounds: int
    chips: int
    peak: dict | None
    flops_per_round: float
    compress_bytes_per_round: float
