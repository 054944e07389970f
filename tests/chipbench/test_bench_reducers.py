"""Each per-layer reducer on trimmed traces recorded on a v5e chip.

The traces under ``data/`` are ``trace.View`` JSON: the device ops of the
traced window and the host events, cut to the first traced rounds.  Every
expected value is worked out here again from the raw events with plain
loops.
"""
import json
import pathlib

import pytest

from chipbench import counts, trace as T
from chipbench.cell import load_cell
from chipbench.run import _per_layer
from chipbench.cell import load_benchmark

DATA = pathlib.Path(__file__).resolve().parent / "data"
TRACES = sorted(DATA.glob("*.json"))
PEAK = counts.peaks("TPU v5 lite")


def _load(path):
    meta = json.loads(path.read_text())
    return meta, T.View.from_json(meta["view"])


def _ctx(meta, view):
    return T.Context(view=view, rounds=meta["rounds"], chips=1, peak=PEAK,
                     flops_per_round=meta["flops_per_round"],
                     compress_bytes_per_round=meta["compress_bytes_per_round"])


def _in_window(view):
    """[name, kind, start, dur, self] of each op wholly in the window."""
    t0, t1 = view.window
    return [e for evs in view.devices.values() for e in evs
            if e[2] >= t0 and e[2] + e[3] <= t1]


def _union_ns(view):
    t0, t1 = view.window
    busy, end = 0, t0
    for ev in sorted(view.devices[next(iter(view.devices))],
                     key=lambda e: e[2]):
        s, e = max(ev[2], end), min(ev[2] + ev[3], t1)
        if e > s:
            busy += e - s
            end = e
    return busy


def test_traces_are_present():
    assert TRACES, "no recorded trace under tests/chipbench/data"


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.stem)
def test_idle_share_is_one_minus_the_union(path):
    meta, view = _load(path)
    want = 100 * (1 - _union_ns(view) / (view.window[1] - view.window[0]))
    got = __import__("chipbench.metrics.device_idle_share",
                     fromlist=["read"]).read(_ctx(meta, view))
    assert got == pytest.approx(want, rel=1e-9)
    assert 0 <= got < 100


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.stem)
def test_kind_times_add_up(path):
    meta, view = _load(path)
    ctx = _ctx(meta, view)
    by_kind = {}
    for _, kind, _, _, own in _in_window(view):
        by_kind[kind] = by_kind.get(kind, 0) + own
    out = _per_layer(load_cell(meta["cell"]), load_benchmark(), ctx)
    ms = lambda kind: by_kind.get(kind, 0) / 1e6 / meta["rounds"]
    assert out["xla_ms"]["value"] == pytest.approx(ms("xla") + ms("sort"))
    if "mosaic" in by_kind:
        assert out["compress_kernel_ms"]["value"] == pytest.approx(
            ms("mosaic"))
        least_ms = meta["compress_bytes_per_round"] / PEAK[
            "hbm_bytes_per_s"] * 1e3
        roof = out["compress_roofline"]["value"]
        assert roof == pytest.approx(100 * least_ms / ms("mosaic"))
        assert 0 < roof <= 100
    else:
        assert "compress_kernel_ms" not in out
    if "sort" in by_kind:
        assert out["topk_sort_ms"]["value"] == pytest.approx(ms("sort"))
    else:
        assert "topk_sort_ms" not in out
    mfu = out["step_mfu"]["value"]
    assert mfu == pytest.approx(
        100 * meta["flops_per_round"] * meta["rounds"]
        / (view.window_s() * PEAK["bf16_flops_per_s"]))
    assert 0 < mfu <= 100


@pytest.mark.parametrize("path", TRACES, ids=lambda p: p.stem)
def test_breakdown_is_bounded_and_sorted(path):
    _, view = _load(path)
    b = view.breakdown()
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(b[key]) <= 10
        secs = [s for _, s in b[key]]
        assert secs == sorted(secs, reverse=True)
    assert sum(s for _, s in b["idle_gaps"]) <= view.window_s()


def test_op_kinds():
    assert T.op_kind("all-reduce.3") == "collective"
    assert T.op_kind("sort.12") == "sort"
    assert T.op_kind("fusion.7") == "xla"
