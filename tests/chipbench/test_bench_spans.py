"""The idle split by the program's host spans (``chipbench/spans.py``) and
the two reducers that read it, against values worked out here again with
plain loops; and the existing reducers' numbers on the first recorded
trace, which holds no program span.

The traces recorded with the program's spans also carry, under
``view["scopes"]``, the innermost ``lgc.*`` phase scope of each device op
(aligned with ``view["devices"]``), as the chip's trace gave it.
"""
import importlib
import json
import pathlib

import pytest

from chipbench import counts, spans, trace as T
from chipbench.cell import load_benchmark, load_cell
from chipbench.run import _per_layer

DATA = pathlib.Path(__file__).resolve().parent / "data"
FIRST = DATA / "trace_qwen2-1.5b.dense-h1.json"
PEAK = counts.peaks("TPU v5 lite")
PHASES = {"lgc.local_sgd", "lgc.compress", "lgc.exchange",
          "lgc.server_update"}


def _load(path):
    meta = json.loads(path.read_text())
    return meta, T.View.from_json(meta["view"])


def _ctx(meta, view):
    return T.Context(view=view, rounds=meta["rounds"], chips=1, peak=PEAK,
                     flops_per_round=meta["flops_per_round"],
                     compress_bytes_per_round=meta["compress_bytes_per_round"])


def _read(metric, ctx):
    return importlib.import_module(f"chipbench.metrics.{metric}").read(ctx)


def _with_spans():
    return [p for p in sorted(DATA.glob("*.json"))
            if any(h[0] == spans.ROUND for h in json.loads(
                p.read_text())["view"]["host"])]


SPANNED = _with_spans()


def _idle_by_latest_span(view):
    """Idle ns of the first chip under each innermost span, where the
    innermost of the spans over a stretch is the one that began last."""
    t0, t1 = view.window
    ops = sorted(view.devices[next(iter(view.devices))], key=lambda e: e[2])
    idle, end = [], t0
    for ev in ops:
        s, e = max(ev[2], t0), min(ev[2] + ev[3], t1)
        if e <= s:
            continue
        if s > end:
            idle.append((end, s))
        end = max(end, e)
    if end < t1:
        idle.append((end, t1))
    prog = [(n, s, s + d) for n, s, d in view.host if n.startswith("lgc.")]
    out = {}
    for a, b in idle:
        marks = [a, b]
        for _, s, e in prog:
            for x in (s, e):
                if a < x < b:
                    marks.append(x)
        marks.sort()
        for x, y in zip(marks, marks[1:]):
            if y == x:
                continue
            best, began = "no span", None
            for n, s, e in prog:
                if s <= x and y <= e and (began is None or s > began):
                    best, began = n, s
            out[best] = out.get(best, 0) + (y - x)
    return out


def test_idle_split_on_a_hand_made_view():
    view = T.View(
        devices={"/device:TPU:0": [["a", "xla", 10, 10, 10],
                                   ["b", "xla", 50, 10, 10]]},
        host=[["lgc.round", 5, 90], ["lgc.mask", 5, 25],
              ["lgc.batch", 30, 10], ["lgc.step", 40, 5],
              ["lgc.readback", 45, 45], ["PjitFunction(step)", 40, 3]],
        window=(0, 100))
    got = {n: round(s * 1e9) for n, s in spans.idle_by_span(view).items()}
    assert got == {"no span": 10, "lgc.mask": 15, "lgc.batch": 10,
                   "lgc.step": 5, "lgc.readback": 35, "lgc.round": 5}
    ctx = T.Context(view=view, rounds=1, chips=1, peak=None,
                    flops_per_round=0.0, compress_bytes_per_round=0.0)
    assert _read("prep_idle_ms", ctx) == pytest.approx(25e-6)
    assert _read("sync_idle_ms", ctx) == pytest.approx(40e-6)


def test_recorded_traces_hold_the_program_spans():
    assert len(SPANNED) >= 2, "no recorded trace with the program's spans"


@pytest.mark.parametrize("path", SPANNED, ids=lambda p: p.stem)
def test_prep_idle_ms_matches_plain_loops(path):
    meta, view = _load(path)
    idle = _idle_by_latest_span(view)
    want = (idle.get("lgc.mask", 0) + idle.get("lgc.batch", 0)) / 1e6 / \
        meta["rounds"]
    assert _read("prep_idle_ms", _ctx(meta, view)) == pytest.approx(want)


@pytest.mark.parametrize("path", SPANNED, ids=lambda p: p.stem)
def test_sync_idle_ms_matches_plain_loops(path):
    meta, view = _load(path)
    idle = _idle_by_latest_span(view)
    want = (idle.get("lgc.step", 0) + idle.get("lgc.readback", 0)) / 1e6 / \
        meta["rounds"]
    assert _read("sync_idle_ms", _ctx(meta, view)) == pytest.approx(want)


@pytest.mark.parametrize("path", SPANNED, ids=lambda p: p.stem)
def test_idle_split_adds_up_to_the_idle_time(path):
    meta, view = _load(path)
    idle = spans.idle_by_span(view)
    assert sum(idle.values()) == pytest.approx(
        view.window_s() - view.busy_s(), rel=1e-9)
    # the round's inputs, dispatch and readback cover most of the idle time
    assert idle.get(spans.NO_SPAN, 0) < 0.1 * sum(idle.values())


@pytest.mark.parametrize("path", SPANNED, ids=lambda p: p.stem)
def test_recorded_scopes_cover_the_busy_time(path):
    """The chip's trace credits nearly all device self time to one of the
    step's four phase scopes."""
    meta, view = _load(path)
    t0, t1 = view.window
    by_scope = {}
    for plane, evs in view.devices.items():
        scopes = meta["view"]["scopes"][plane]
        assert len(scopes) == len(evs)
        for ev, scope in zip(evs, scopes):
            if ev[2] >= t0 and ev[2] + ev[3] <= t1:
                by_scope[scope] = by_scope.get(scope, 0) + ev[4]
    assert set(by_scope) - {None} <= PHASES
    busy_ns = view.busy_s() * 1e9
    assert by_scope.get(None, 0) < 0.02 * busy_ns, by_scope
    assert by_scope.get("lgc.local_sgd", 0) > 0
    assert by_scope.get("lgc.compress", 0) > 0


def test_first_trace_has_no_program_spans():
    """A trace of a program without the spans gives no span metric."""
    meta, view = _load(FIRST)
    ctx = _ctx(meta, view)
    assert spans.idle_by_span(view) == {}
    assert _read("prep_idle_ms", ctx) is None
    assert _read("sync_idle_ms", ctx) is None
    out = _per_layer(load_cell(meta["cell"]), load_benchmark(), ctx)
    assert "prep_idle_ms" not in out and "sync_idle_ms" not in out


def test_first_trace_reads_as_it_did():
    """The six earlier metrics read the first recorded trace as before."""
    meta, view = _load(FIRST)
    ctx = _ctx(meta, view)
    want = {"device_idle_share": 9.02939188205848,
            "step_mfu": 16.63807781729671,
            "compress_kernel_ms": 290.035927,
            "compress_roofline": 5.199190900170628,
            "xla_ms": 251.194564,
            "topk_sort_ms": None}
    got = {m: _read(m, ctx) for m in want}
    assert got == pytest.approx(want, rel=1e-12)
