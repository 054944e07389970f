"""Roofline machinery: trip-count-aware HLO cost model vs analytic ground
truth, collective-byte parsing, and model_flops accounting."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(py: str) -> str:
    """Run a snippet in a subprocess with its own XLA device count (keeps
    this test module independent of the session's device configuration)."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(py)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class TestHloCostModel:
    def test_scan_trip_count_exact(self):
        out = _run("""
            import jax, jax.numpy as jnp
            from repro.analysis.hlo_cost import analyze_hlo
            def f(x, ws):
                c, _ = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)
                return c.sum()
            x = jax.ShapeDtypeStruct((256,128), jnp.float32)
            ws = jax.ShapeDtypeStruct((7,128,128), jnp.float32)
            c = analyze_hlo(jax.jit(f).lower(x, ws).compile().as_text())
            print(c.flops / (2*256*128*128*7))
        """)
        assert abs(float(out.strip()) - 1.0) < 0.02

    def test_nested_scan(self):
        out = _run("""
            import jax, jax.numpy as jnp
            from repro.analysis.hlo_cost import analyze_hlo
            def inner(c, w):
                return jnp.tanh(c @ w), None
            def outer(c, ws):
                c2, _ = jax.lax.scan(inner, c, ws)
                return c2, None
            def f(x, ws):
                c, _ = jax.lax.scan(outer, x, ws)
                return c.sum()
            x = jax.ShapeDtypeStruct((64,64), jnp.float32)
            ws = jax.ShapeDtypeStruct((3,5,64,64), jnp.float32)
            c = analyze_hlo(jax.jit(f).lower(x, ws).compile().as_text())
            print(c.flops / (2*64*64*64*15))
        """)
        assert abs(float(out.strip()) - 1.0) < 0.05

    def test_sharded_flops_per_device_and_collectives(self):
        out = _run("""
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from repro.analysis.hlo_cost import analyze_hlo
            from repro.launch import compat
            mesh = compat.make_mesh((8,), ("data",))
            jax.set_mesh(mesh)
            def f(x, w):
                return jnp.sum(x @ w)
            x = jax.ShapeDtypeStruct((512, 256), jnp.float32)
            w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
            comp = jax.jit(f, in_shardings=compat.shardings(mesh, (P('data', None), P(None, None))),
                           out_shardings=compat.shardings(mesh, P())).lower(x, w).compile()
            c = analyze_hlo(comp.as_text())
            print(c.flops / (2*512*256*256/8), sum(c.coll.values()) >= 4)
        """)
        ratio, has_coll = out.split()
        assert abs(float(ratio) - 1.0) < 0.05
        assert has_coll == "True"

    def test_collective_parse_kinds(self):
        from repro.analysis.hlo_cost import HloCostModel
        hlo = """
HloModule m

ENTRY %main (p: f32[64,4]) -> f32[64,4] {
  %p = f32[64,4]{1,0} parameter(0)
  %ag = f32[512,4]{1,0} all-gather(%p), replica_groups={}, dimensions={0}
  %ar = f32[64,4]{1,0} all-reduce(%p), to_apply=%add
  ROOT %cp = f32[64,4]{1,0} collective-permute(%ar), source_target_pairs={{0,1}}
}
"""
        c = HloCostModel(hlo).total()
        assert c.coll["all-gather"] == 512 * 4 * 4
        assert c.coll["all-reduce"] == 64 * 4 * 4
        assert c.coll["collective-permute"] == 64 * 4 * 4


class TestModelFlops:
    def test_dense_6nd(self):
        from repro.analysis.roofline import model_flops
        from repro.configs import get_config
        cfg = get_config("qwen2-1.5b")
        n = cfg.param_count()
        assert model_flops(cfg, "train", 1000) == pytest.approx(6 * n * 1000)
        assert model_flops(cfg, "decode", 10) == pytest.approx(2 * n * 10)

    def test_moe_uses_active_params(self):
        from repro.analysis.roofline import model_flops
        from repro.configs import get_config
        cfg = get_config("olmoe-1b-7b")
        assert cfg.active_param_count() < 0.25 * cfg.param_count()
        assert model_flops(cfg, "train", 100) == pytest.approx(
            6 * cfg.active_param_count() * 100)

    def test_param_counts_near_nameplate(self):
        from repro.configs import get_config
        expect = {"glm4-9b": 9.4e9, "yi-34b": 34.4e9, "qwen2-1.5b": 1.5e9,
                  "mamba2-370m": 0.42e9, "starcoder2-7b": 7.4e9,
                  "grok-1-314b": 314e9, "olmoe-1b-7b": 6.9e9,
                  "zamba2-1.2b": 1.2e9, "whisper-small": 0.28e9,
                  "phi-3-vision-4.2b": 3.8e9}
        for a, n in expect.items():
            got = get_config(a).param_count()
            assert abs(got - n) / n < 0.12, (a, got, n)
