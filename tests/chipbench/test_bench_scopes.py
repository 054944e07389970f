"""Device time by the program's named scopes (``chipbench/trace.py``): the
scope of each op from a compiled program's HLO text, the ``View``'s
queries by scope, and the two readers of the step's phases, against
values worked out here again with plain loops."""
import importlib
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from chipbench import counts, trace as T
from chipbench.cell import load_benchmark, load_cell
from chipbench.run import _per_layer, step_hlo

DATA = pathlib.Path(__file__).resolve().parent / "data"
FIRST = DATA / "trace_qwen2-1.5b.dense-h1.json"
SCOPED = sorted(DATA.glob("*.spans.json"))
PEAK = counts.peaks("TPU v5 lite")
READERS = {"local_sgd_ms": "lgc.local_sgd", "compress_ms": "lgc.compress"}

HLO = """HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %exponential.1 = f32[4]{0} exponential(f32[4]{0} %param_0), metadata={op_name="jit(step)/lgc.compress/exp" stack_frame_id=3}
}

%compare (p.0.lhs: f32[], p.0.rhs: f32[]) -> pred[] {
  %p.0.lhs = f32[] parameter(0)
  %p.0.rhs = f32[] parameter(1)
  ROOT %compare.1 = pred[] compare(f32[] %p.0.lhs, f32[] %p.0.rhs), direction=GT
}

ENTRY %main.9 (Arg_0.1: f32[4]) -> (f32[4], f32[4]) {
  %Arg_0.1 = f32[4]{0} parameter(0), metadata={op_name="params"}
  %add.2 = f32[4]{0} add(f32[4]{0} %Arg_0.1, f32[4]{0} %Arg_0.1), metadata={op_name="jit(step)/lgc.local_sgd/add"}
  %fusion.3 = f32[4]{0:T(256)} fusion(f32[4]{0:T(256)} %add.2), kind=kLoop, calls=%fused_computation, backend_config={"outer_dimension_partitions":["1"]}
  %copy.4 = f32[4]{0} copy(f32[4]{0} %fusion.3)
  %sort.5 = f32[4]{0} sort(f32[4]{0} %copy.4), dimensions={0}, to_apply=%compare
  %constant.6 = f32[] constant(2)
  %broadcast.7 = f32[4]{0} broadcast(f32[] %constant.6), dimensions={}
  %multiply.8 = f32[4]{0} multiply(f32[4]{0} %sort.5, f32[4]{0} %broadcast.7), metadata={op_name="jit(step)/lgc.server_update/mul"}
  ROOT %tuple.9 = (f32[4]{0}, f32[4]{0}) tuple(f32[4]{0} %multiply.8, f32[4]{0} %add.2)
}
"""


def test_hlo_scopes_by_op_name_callee_operand_and_user():
    module, scopes = T.hlo_scopes(HLO)
    assert module == "jit_step"
    assert scopes["add.2"] == "jit(step)/lgc.local_sgd/add"      # op_name
    assert scopes["fusion.3"] == "jit(step)/lgc.compress/exp"     # callee
    assert scopes["copy.4"] == "jit(step)/lgc.compress/exp"       # operand
    # the comparator holds no op_name: the sort takes its nearest
    # operand's scope, two steps up
    assert scopes["sort.5"] == "jit(step)/lgc.compress/exp"
    # a constant has no operand: it takes its nearest user's scope
    assert scopes["constant.6"] == "jit(step)/lgc.server_update/mul"
    assert scopes["broadcast.7"] == "jit(step)/lgc.server_update/mul"
    assert scopes["Arg_0.1"] == "params"


def test_module_of_an_op_by_its_start():
    modules = [(10, 20, "jit_step"), (30, 40, "jit_mask")]
    assert T._module_of(modules, 10) == "jit_step"
    assert T._module_of(modules, 19) == "jit_step"
    assert T._module_of(modules, 25) is None
    assert T._module_of(modules, 35) == "jit_mask"
    assert T._module_of(modules, 5) is None
    assert T._module_of([], 5) is None


def test_op_scopes_only_for_the_programs_ops():
    """An op of another program that shares an instruction name with the
    step's takes no scope."""
    modules = [(0, 100, "jit_step"), (200, 300, "jit_mask")]
    ops = [["fusion.1", "xla", 10, 5, 5], ["fusion.1", "xla", 210, 5, 5],
           ["fusion.2", "xla", 20, 5, 5], ["copy.3", "xla", 150, 5, 5]]
    table = {"fusion.1": "jit(step)/lgc.compress/x", "fusion.2": None,
             "copy.3": "jit(step)/lgc.local_sgd/y"}
    assert T.op_scopes(ops, modules, "jit_step", table) == [
        "jit(step)/lgc.compress/x", None, None, None]


def test_step_hlo_carries_the_named_scopes():
    """This JAX writes a named scope into the compiled text's op_name."""
    @jax.jit
    def step(x):
        with jax.named_scope("lgc.local_sgd"):
            y = jnp.sin(x) * 3.0
        with jax.named_scope("lgc.compress"):
            return jnp.sort(y) + 1.0

    x = jax.ShapeDtypeStruct((128,), jnp.float32)
    module, scopes = T.hlo_scopes(step_hlo(step, (x,)))
    assert module == "jit_step"
    inner = {next((p for p in reversed(s.split("/")) if p.startswith("lgc.")),
                  None) for s in scopes.values() if s}
    assert {"lgc.local_sgd", "lgc.compress"} <= inner


def _view():
    return T.View(
        devices={"/device:TPU:0": [["a", "xla", 10, 10, 10],
                                   ["b", "sort", 20, 10, 10],
                                   ["c", "xla", 30, 5, 5],
                                   ["d", "xla", 40, 20, 20],
                                   ["e", "xla", 200, 5, 5]]},
        host=[], window=(0, 100),
        scopes={"/device:TPU:0": [
            "jit(step)/lgc.compress/lgc.exchange/psum",
            "jit(step)/lgc.compress/top_k", "params['embed']", None,
            "jit(step)/lgc.compress/x"]})


def test_scope_queries_on_a_hand_made_view():
    view = _view()
    assert view.scope_s("lgc.compress") == pytest.approx(20e-9)
    assert view.scope_s("lgc.exchange") == pytest.approx(10e-9)
    assert view.scope_s("lgc.local_sgd") == 0
    got = view.innermost_s("lgc.")
    assert got == pytest.approx({"lgc.exchange": 10e-9,
                                 "lgc.compress": 10e-9, None: 25e-9})
    ctx = T.Context(view=view, rounds=2, chips=1, peak=None,
                    flops_per_round=0.0, compress_bytes_per_round=0.0)
    assert _read("compress_ms", ctx) == pytest.approx(5e-6)
    assert _read("local_sgd_ms", ctx) is None


def _read(metric, ctx):
    return importlib.import_module(f"chipbench.metrics.{metric}").read(ctx)


def _load(path):
    meta = json.loads(path.read_text())
    return meta, T.View.from_json(meta["view"])


def _ctx(meta, view):
    return T.Context(view=view, rounds=meta["rounds"], chips=1, peak=PEAK,
                     flops_per_round=meta["flops_per_round"],
                     compress_bytes_per_round=meta["compress_bytes_per_round"])


def _self_ns_by_scope(meta, view):
    """Self ns of the ops wholly in the window, by their recorded scope."""
    t0, t1 = view.window
    out = {}
    for plane, evs in view.devices.items():
        for ev, scope in zip(evs, meta["view"]["scopes"][plane]):
            if ev[2] >= t0 and ev[2] + ev[3] <= t1:
                out[scope] = out.get(scope, 0) + ev[4]
    return out


def test_recorded_traces_hold_scopes():
    assert len(SCOPED) >= 2, "no recorded trace with the step's scopes"


@pytest.mark.parametrize("path", SCOPED, ids=lambda p: p.stem)
def test_scope_times_add_up_to_the_chips_self_time(path):
    meta, view = _load(path)
    inner = view.innermost_s("lgc.")
    total_ns = sum(_self_ns_by_scope(meta, view).values())
    assert sum(inner.values()) == pytest.approx(total_ns / 1e9, rel=1e-12)
    assert inner.get(None, 0) < 0.02 * total_ns / 1e9, inner


@pytest.mark.parametrize("metric", sorted(READERS))
@pytest.mark.parametrize("path", SCOPED, ids=lambda p: p.stem)
def test_phase_readers_match_plain_loops(path, metric):
    meta, view = _load(path)
    want = _self_ns_by_scope(meta, view)[READERS[metric]] / 1e6 / \
        meta["rounds"]
    assert _read(metric, _ctx(meta, view)) == pytest.approx(want, rel=1e-12)
    out = _per_layer(load_cell(meta["cell"]), load_benchmark(),
                     _ctx(meta, view))
    assert out[metric]["value"] == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_first_trace_has_no_scopes(metric):
    """The first recorded trace holds no scopes: its view loads, and the
    phase readers find nothing to read."""
    meta, view = _load(FIRST)
    assert view.scopes is None
    ctx = _ctx(meta, view)
    assert _read(metric, ctx) is None
    assert metric not in _per_layer(load_cell(meta["cell"]),
                                    load_benchmark(), ctx)
