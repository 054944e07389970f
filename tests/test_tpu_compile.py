"""Compile-only checks of the LGC Pallas kernels for a described TPU v5e.

Nothing runs: each case compiles one kernel with ``interpret=False`` for a
v5e chip that is described, not attached, and checks that the compiled
program holds the Mosaic kernel (``tpu_custom_call``) and fits the chip's
16 GB of HBM.  The sizes are real ``qwen2-100m`` leaves: an attention
matrix (768 x 768), an MLP matrix (768 x 3072) and the tied 32k x 768
embedding.  This is what the interpret-mode parity tests in
tests/test_kernels.py cannot see: scalar stores to VMEM, unaligned blocks
and oversized intermediates are refused here, on the CPU, at no chip time.

The topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU compiler library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from _hlo_compare import without_debug_info
from repro.kernels import histogram, lgc_compress_hist, maxabs, sparsify_ef

V5E_HBM_BYTES = 16 * 10 ** 9
LEAF_SIZES = [768 * 768, 768 * 3072, 32_000 * 768]
N_CHANNELS = 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _kernel_args(name, n, sharding):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    vec, chans = s((n,)), s((N_CHANNELS,), jnp.int32)
    return {
        "maxabs": (lambda x: maxabs(x, interpret=False), (vec,)),
        "histogram": (lambda x, m: histogram(x, m, interpret=False),
                      (vec, s((1, 1)))),
        "sparsify_ef": (lambda e, d, t, r: sparsify_ef(e, d, t, r,
                                                       interpret=False),
                        (vec, vec, s((N_CHANNELS,)), chans)),
        "lgc_compress_hist": (
            lambda e, d, k, r: lgc_compress_hist(e, d, k, r, interpret=False),
            (vec, vec, chans, chans)),
    }[name]


@pytest.mark.parametrize("n", LEAF_SIZES)
@pytest.mark.parametrize("kernel", ["maxabs", "histogram", "sparsify_ef",
                                    "lgc_compress_hist"])
def test_kernel_compiles_for_v5e(one_chip, kernel, n):
    fn, args = _kernel_args(kernel, n, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{kernel}@{n}: {used} bytes"


# --- the LGC step's phase scopes write metadata only --------------------

def _smoke_step_hlo(device, aggregate: str) -> str:
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_smoke_config
    from repro.launch import sharding_rules as rules
    from repro.launch.steps import (LGCStepConfig, init_ef_tree,
                                    make_lgc_train_step)
    from repro.models import transformer as tf

    cfg = get_smoke_config("qwen2-100m")
    mesh = Mesh(np.array([device]).reshape(1, 1), ("data", "model"))
    params = jax.eval_shape(lambda k: tf.init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    batch = {k: jax.ShapeDtypeStruct((2, 16), jnp.int32)
             for k in ("tokens", "labels")}
    bspecs = rules.batch_specs(cfg, batch, mesh)
    pspecs = rules.param_specs(cfg, params, mesh)

    def placed(tree, specs):
        return jax.tree_util.tree_map(
            lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
            tree, specs)

    ef = jax.eval_shape(lambda p: init_ef_tree(p, 1), params)
    step_cfg = LGCStepConfig(local_steps=1, aggregate=aggregate,
                             backend="pallas", pallas_min_elems=1000)
    step = jax.jit(make_lgc_train_step(cfg, mesh, step_cfg, bspecs,
                                       param_spec_tree=pspecs),
                   donate_argnums=(0, 1))
    recv = jax.ShapeDtypeStruct((1, 3), jnp.int32,
                                sharding=NamedSharding(mesh, P("data")))
    return step.lower(placed(params, pspecs),
                      placed(ef, rules.ef_specs(pspecs, "data")),
                      placed(batch, bspecs), recv).compile().as_text()


@pytest.mark.parametrize("aggregate", ["dense_masked", "sparse_gather"])
def test_step_scopes_leave_the_v5e_program_unchanged(one_chip, aggregate,
                                                     monkeypatch):
    """The step's named scopes (``steps.PHASE_SCOPES``) change no op of
    the program the chip's compiler makes, Mosaic kernels included."""
    import contextlib
    # the kernels take their compiled branch for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        device, = one_chip.device_set
        scoped = _smoke_step_hlo(device, aggregate)
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
            plain = _smoke_step_hlo(device, aggregate)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert "lgc.compress" in scoped and "lgc." not in plain
    assert (aggregate == "sparse_gather") != ("tpu_custom_call" in scoped)
    assert without_debug_info(scoped) == without_debug_info(plain)
