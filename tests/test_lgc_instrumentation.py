"""The LGC step's phase scopes and ``run()``'s per-round host spans.

The step names each phase of Algorithm 1 with a ``jax.named_scope``
(``steps.PHASE_SCOPES``); the scopes write only HLO metadata, so the
compiled program is the same with them and without.  ``run()`` opens a
``lgc.round`` step span per sync round with four child spans, which the
profiler records on the host planes of its trace.
"""
import collections
import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from _hlo_compare import without_debug_info
from repro.launch.steps import PHASE_SCOPES
from repro.models import lgc_transformer as L
from repro.models.paper_models import make_task

STEP_CASES = [("dense_masked", "exact"), ("dense_masked", "pallas"),
              ("sparse_gather", "exact"), ("bucket_sparse", "exact"),
              ("none", "exact")]


def _task(aggregate="dense_masked", backend="exact"):
    kw = dict(pallas_min_elems=1) if backend == "pallas" else {}
    return make_task("qwen2_100m", m_devices=1, preset="smoke", seq=16,
                     aggregate=aggregate, backend=backend, **kw)


def _compiled_text(task) -> str:
    b = task.build()
    x, y = b["pipe"].next_batch()
    batch = {"tokens": jnp.asarray(x), "labels": jnp.asarray(y)}
    recv = jnp.ones((task.m_devices, task.step_cfg.n_channels), jnp.int32)
    return b["step"].lower(b["params"], b["ef"], batch,
                           recv).compile().as_text()


def _innermost_scope(op_name: str):
    scopes = [p for p in op_name.split("/") if p.startswith("lgc.")]
    return scopes[-1] if scopes else None


@pytest.mark.parametrize("aggregate,backend", STEP_CASES,
                         ids=lambda v: str(v))
def test_step_ops_carry_a_phase_scope(aggregate, backend):
    """At least 95% of the compiled step's instructions whose op_name comes
    from the step body carry one of the four phase scopes."""
    names = re.findall(r'op_name="([^"]*)"', _compiled_text(
        _task(aggregate, backend)))
    body = [n for n in names if n.startswith("jit(step)/")]
    assert body
    by_scope = collections.Counter(_innermost_scope(n) for n in body)
    assert set(by_scope) - {None} <= set(PHASE_SCOPES)
    assert by_scope[None] <= 0.05 * len(body), by_scope
    # the scan, the loss's pmean and the server update are always there
    for scope in ("lgc.local_sgd", "lgc.exchange", "lgc.server_update"):
        assert by_scope[scope] > 0, by_scope
    if aggregate != "none":
        assert by_scope["lgc.compress"] > 0, by_scope


@pytest.fixture
def no_compile_cache():
    """The persistent cache's key ignores metadata: it would hand the
    scoped program back for the plain one."""
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.mark.parametrize("aggregate", ["dense_masked", "sparse_gather"])
def test_scopes_leave_the_program_unchanged(aggregate, monkeypatch,
                                            no_compile_cache):
    """With every named scope a no-op, the optimized HLO is the same once
    its metadata is stripped."""
    scoped = without_debug_info(_compiled_text(_task(aggregate)))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_text(_task(aggregate))
    assert "lgc." not in plain
    assert without_debug_info(plain) == scoped


def _host_spans(trace_dir):
    """[(name, start_ns, end_ns, stats)] of the program's ``lgc.*`` host
    spans, by start."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("lgc."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def test_run_writes_a_step_span_per_round(tmp_path):
    task = _task()
    task.run(1)                                  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        task.run(3)
    spans = _host_spans(tmp_path)
    rounds = [s for s in spans if s[0] == L.SPAN_ROUND]
    assert [r[3].get("step_num") for r in rounds] == [0, 1, 2]
    for _, r0, r1, _ in rounds:
        inside = [s[0] for s in spans
                  if s[0] != L.SPAN_ROUND and r0 <= s[1] and s[2] <= r1]
        assert inside == list(L.ROUND_SPANS)
    # the chains' initial state, before the first round
    first = spans[0]
    assert first[0] == L.SPAN_MASK and first[2] <= rounds[0][1]
    assert len(spans) == 1 + 5 * 3


def test_run_returns_no_model_counters(monkeypatch):
    """``run()`` leaves the model's size and wire bytes to the task's own
    methods, so no whole-model ``eval_shape`` runs inside a timed call."""
    task = _task()
    task.build()
    calls = []
    real = jax.eval_shape
    monkeypatch.setattr(jax, "eval_shape",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    out = task.run(2)
    assert calls == []
    assert set(out) == {"losses", "first_round_s", "steady_round_s",
                        "device_steps_per_s"}
    assert task.param_count() > 0 and task.wire_bytes_per_round() > 0
