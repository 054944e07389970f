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

import bisect
import dataclasses
import glob
import os
import re

WINDOW = "chipbench.window"
DEVICE_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"

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
    #: plane name -> the scope path ("a/b/c") or None of each op in
    #: ``devices[plane]``; None: the trace was read without the program
    scopes: dict | None = None

    @classmethod
    def from_json(cls, d: dict) -> "View":
        return cls(d["devices"], d["host"], tuple(d["window"]),
                   d.get("scopes"))

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def ops(self, kind: str | None = None):
        """Every device op in the window (all chips), optionally of a kind."""
        for e, _ in self._scoped_ops():
            if kind is None or e[1] == kind:
                yield tuple(e)

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

    def _scoped_ops(self):
        """(op, scope path or None) of every device op in the window."""
        t0, t1 = self.window
        for plane, evs in self.devices.items():
            paths = (self.scopes or {}).get(plane) or [None] * len(evs)
            for e, path in zip(evs, paths):
                if e[2] >= t0 and e[2] + e[3] <= t1:
                    yield e, path

    def scope_s(self, scope: str) -> float:
        """Self seconds, mean over the chips, of the ops whose scope path
        holds ``scope``."""
        ns = sum(e[4] for e, path in self._scoped_ops()
                 if path is not None and scope in path.split("/"))
        return ns / 1e9 / max(len(self.devices), 1)

    def innermost_s(self, prefix: str) -> dict:
        """Self seconds, mean over the chips, by each op's innermost scope
        that starts with ``prefix`` (``None``: no such scope)."""
        out = {}
        for e, path in self._scoped_ops():
            inner = None if path is None else next(
                (p for p in reversed(path.split("/")) if p.startswith(prefix)),
                None)
            out[inner] = out.get(inner, 0) + e[4]
        return {k: ns / 1e9 / max(len(self.devices), 1)
                for k, ns in out.items()}

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


_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(")
_INSTR = re.compile(r"^\s+(ROOT )?%([\w.\-]+) = (.*)$")
_CALLS = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation"
                    r"|false_computation)=%([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)"
                         r"=\{([^}]*)\}")
_NAME = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_scopes(text: str) -> tuple[str, dict]:
    """(module name, {instruction: scope path or None}) of an HLO module's
    text: an instruction's ``op_name`` metadata; without one, the scope of
    the computations it calls (their root first, then their instructions
    in order, then what those call), else of its nearest operand, else of
    its nearest user (breadth first, within its computation)."""
    module = text.split(None, 2)[1].rstrip(",") if text.startswith(
        "HloModule") else ""
    comps, comp = {}, None          # computation -> [instructions], root first
    direct, callees, operands, home = {}, {}, {}, {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            h = None if line[:1].isspace() else _HEADER.match(line)
            if h is not None and line.rstrip().endswith("{"):
                comp = h.group(1)
                comps.setdefault(comp, [])
            continue
        root, name, rest = m.groups()
        cut = min((i for i in (rest.find("metadata={"),
                               rest.find("backend_config=")) if i >= 0),
                  default=len(rest))
        body = rest[:cut]
        op = _OP_NAME.search(rest[rest.find("metadata={"):]) \
            if "metadata={" in rest else None
        direct[name] = op.group(1) if op else None
        calls = _CALLS.findall(body) + [
            c.strip().lstrip("%") for lst in _CALL_LISTS.findall(body)
            for c in lst.split(",") if c.strip()]
        callees[name] = calls
        operands[name] = [o for o in _NAME.findall(_CALLS.sub("", body))
                          if o != name and o not in calls]
        home[name] = comp
        if comp is not None:
            comps[comp].insert(0 if root else len(comps[comp]), name)

    memo = {}

    def comp_scope(c):
        if c not in memo:
            memo[c] = None                      # a cycle finds nothing
            names = comps.get(c, [])
            memo[c] = next((direct[n] for n in names if direct[n]), None) \
                or next((s for n in names for k in callees[n]
                         if (s := comp_scope(k))), None)
        return memo[c]

    base = {n: direct[n] or next((s for k in callees[n]
                                  if (s := comp_scope(k))), None)
            for n in direct}
    users = {}
    for n, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(n)

    def nearest(n, edges):
        seen, frontier = {n}, [n]
        while frontier:
            nxt = []
            for x in frontier:
                for y in edges.get(x, []):
                    if y in seen or home.get(y) != home[n]:
                        continue
                    if base.get(y):
                        return base[y]
                    seen.add(y)
                    nxt.append(y)
            frontier = nxt
        return None

    return module, {n: base[n] or nearest(n, operands) or nearest(n, users)
                    for n in direct}


def _module_of(modules: list, start: int) -> str | None:
    """Name of the program whose event on the "XLA Modules" line holds
    ``start``; ``modules``: sorted [(start, end, name)]."""
    i = bisect.bisect_right(modules, (start, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= start < modules[i][1]:
        return modules[i][2]
    return None


def op_scopes(ops, modules: list, module: str, table: dict) -> list:
    """The scope path or None of each op ([name, kind, start, ...]): from
    ``table`` for the ops that ran inside an event of the program
    ``module``, None for the others; ``modules``: sorted [(start, end,
    name)] of the plane's "XLA Modules" line."""
    return [table.get(e[0]) if _module_of(modules, e[2]) == module else None
            for e in ops]


def load(trace_dir: str, chips: int, hlo: str | None = None) -> View:
    """The trace under ``trace_dir``; with ``hlo``, the compiled text of the
    program whose ops are given scopes."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[0])
    module, table = hlo_scopes(hlo) if hlo else ("", {})
    devices, scopes, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            modules = []
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    devices[plane.name] = with_self_time(
                        [op_name(ev.name), op_kind(ev.name),
                         int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events)
                elif line.name == MODULE_LINE:
                    modules = sorted(
                        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                         ev.name.split("(", 1)[0]) for ev in line.events)
            scopes[plane.name] = op_scopes(devices.get(plane.name, []),
                                           modules, module, table)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events)
    devices = dict(sorted(devices.items())[:chips])
    spans = [(s, s + d) for n, s, d in host if n == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} host span")
    return View(devices, host, spans[0],
                {p: scopes[p] for p in devices} if hlo else None)


@dataclasses.dataclass
class Context:
    """What a per-layer reducer reads: the trace view and the counts, and
    the cell with the ``(path, shape)`` of each parameter leaf, from which
    a kernel's reader counts its operations and bytes."""
    view: View
    rounds: int
    chips: int
    peak: dict | None
    flops_per_round: float
    compress_bytes_per_round: float
    cell: object = None
    leaves: tuple = ()
