"""The 100M-parameter federated transformer task (`qwen2_100m`).

This wires the dormant big-model stack -- ``configs.qwen2_100m``, the
shard_map LGC train step in :mod:`repro.launch.steps`, the Pallas
layered-sparsify / maxabs-histogram kernels, and the synthetic token
pipeline -- into the same ``TASKS`` registry surface as the MNIST /
Shakespeare zoo: ``make_task("qwen2_100m", m_devices, scenario=...)``.

Unlike the FLTask workloads (which the loop/batched/sharded *engines*
stack into (M, d) trees -- infeasible at 1.28e8 parameters), this task IS
the sharded engine: one mesh with a data-parallel FL axis x a tensor-model
axis, ``make_lgc_train_step`` exchanging the layered channels as real
collectives, and the stacked (n_fl, .) error-feedback tree sharded over
the FL axis.  The equivalence rungs that apply at this scale are
documented in docs/ARCHITECTURE.md §12; tests/test_lgc_step.py enforces
them (sparse/bucket uplinks vs the dense server sum, mesh {1, 8}, static
and gilbert_flaky).

The scenario drives the paper's multi-channel availability: per round a
(m_devices, C) delivery mask is sampled from the scenario's
Gilbert-Elliott chains (channel c of device m up/down) plus the whole-
uplink dropout rule, and fed to the step's ``received`` argument --
undelivered mass stays in the device's error memory.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ArchConfig
from repro.core.scenario import (Scenario, dropout_mask, get_scenario,
                                 init_carry, step_carry)
from repro.data.tokens import TokenPipeline
from repro.launch import compat
from repro.launch import sharding_rules as rules
from repro.launch.mesh import fl_axis_name, make_host_mesh
from repro.launch.steps import (LGCStepConfig, init_ef_tree,
                                lgc_wire_bytes_per_round,
                                make_lgc_train_step)
from repro.models import transformer as tf

Array = jax.Array

# host spans of one sync round in ``LGCTransformerTask.run``
SPAN_ROUND = "lgc.round"        # the round; step_num is its index
SPAN_MASK = "lgc.mask"          # the mask program (and its initial state)
SPAN_BATCH = "lgc.batch"        # next_batch and its two transfers
SPAN_STEP = "lgc.step"          # the step's dispatch
SPAN_READBACK = "lgc.readback"  # float(loss): waits for the step
ROUND_SPANS = (SPAN_MASK, SPAN_BATCH, SPAN_STEP, SPAN_READBACK)


@dataclasses.dataclass
class LGCTransformerTask:
    """A registry task backed by the shard_map LGC train step.

    ``build()`` constructs the mesh/params/step once; ``run(steps)``
    drives training and returns the loss trajectory and throughput;
    ``param_count()`` and ``wire_bytes_per_round()`` give the model size
    and the per-device uplink bytes.
    """
    arch: ArchConfig
    m_devices: int
    scenario: Scenario
    step_cfg: LGCStepConfig
    batch_per_device: int = 2
    seq: int = 64
    seed: int = 0
    model_axis: int = 1
    name: str = "qwen2-100m"

    _built: dict | None = dataclasses.field(default=None, repr=False)

    @property
    def n_devices(self) -> int:
        return self.m_devices * self.model_axis

    def param_count(self) -> int:
        p = jax.eval_shape(lambda k: tf.init_params(self.arch, k),
                           jax.ShapeDtypeStruct((2,), jnp.uint32))
        return sum(int(x.size) for x in jax.tree_util.tree_leaves(p))

    def wire_bytes_per_round(self) -> int:
        """Per-device uplink bytes under the configured aggregate mode."""
        p = jax.eval_shape(lambda k: tf.init_params(self.arch, k),
                           jax.ShapeDtypeStruct((2,), jnp.uint32))
        return lgc_wire_bytes_per_round(p, self.step_cfg)[
            self.step_cfg.aggregate]

    # -- construction -------------------------------------------------------

    def build(self) -> dict:
        if self._built is not None:
            return self._built
        cfg = self.arch
        present = len(jax.devices())
        if self.n_devices > present:
            raise ValueError(
                f"{self.name}: m_devices={self.m_devices} x model_axis="
                f"{self.model_axis} needs {self.n_devices} devices, but "
                f"{present} are present")
        mesh = make_host_mesh(self.n_devices, model=self.model_axis)
        fl_ax = fl_axis_name(mesh)
        params = tf.init_params(cfg, jax.random.PRNGKey(self.seed))
        pipe = TokenPipeline(cfg.vocab_size, self.seq,
                             self.batch_per_device * self.m_devices,
                             seed=self.seed)
        x0, y0 = pipe.next_batch()
        batch0 = {"tokens": jnp.asarray(x0), "labels": jnp.asarray(y0)}
        bspecs = rules.batch_specs(cfg, batch0, mesh)
        pspecs = rules.param_specs(cfg, params, mesh)
        especs = rules.ef_specs(pspecs, fl_ax)
        params = rules.place(params, pspecs, mesh)
        ef = rules.place(init_ef_tree(params, self.m_devices,
                                      jnp.dtype(self.step_cfg.ef_dtype)),
                         especs, mesh)
        recv_sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(fl_ax))
        step = jax.jit(
            make_lgc_train_step(cfg, mesh, self.step_cfg, bspecs,
                                param_spec_tree=pspecs),
            in_shardings=compat.shardings(mesh, (pspecs, especs, bspecs))
            + (recv_sharding,),
            donate_argnums=(0, 1))
        self._built = dict(mesh=mesh, fl_ax=fl_ax, params=params, ef=ef,
                           step=step, pipe=pipe, pspecs=pspecs,
                           especs=especs, bspecs=bspecs,
                           recv_sharding=recv_sharding)
        return self._built

    # -- scenario-driven channel availability -------------------------------

    def _mask_state(self):
        base = jax.random.PRNGKey(self.seed)
        dev_ids = jnp.arange(self.m_devices)
        n_ch = self.step_cfg.n_channels
        carry = jax.vmap(lambda i: init_carry(self.scenario, base, i, n_ch)
                         )(dev_ids)
        return base, dev_ids, carry

    def _round_mask(self, base, dev_ids, carry, t: int):
        """Advance the per-device chains and realise the (m, C) delivery
        mask for sync round ``t`` -- Gilbert-Elliott channel availability
        AND whole-uplink dropout, both keyed on the shared TAG streams so
        any engine observing the same scenario agrees."""
        tt = jnp.int32(t)
        carry = jax.vmap(lambda c, i: step_carry(
            self.scenario, base, c, tt, i, jnp.bool_(True)))(carry, dev_ids)
        up = carry.good.astype(jnp.int32)                    # (m, C)
        drop = dropout_mask(self.scenario, base, tt, dev_ids)  # (m,)
        received = up * (~drop).astype(jnp.int32)[:, None]
        return carry, received

    # -- training -----------------------------------------------------------

    def run(self, steps: int, log_every: int = 0) -> dict:
        """Train for ``steps`` sync rounds; returns the losses and the
        throughput (the bench consumes this directly).

        Each round is a profiler step span ``lgc.round`` (its step number
        is the round's index) holding four host spans in order:
        ``lgc.mask`` (the delivery mask), ``lgc.batch`` (the next batch),
        ``lgc.step`` (the step's dispatch) and ``lgc.readback`` (the loss,
        which syncs the round); the channel chains' initial state, made
        once per call before the first round, is a ``lgc.mask`` span of
        its own.  They cost a few microseconds a round when no profiler
        runs."""
        b = self.build()
        params, ef, step, pipe = b["params"], b["ef"], b["step"], b["pipe"]
        with jax.profiler.TraceAnnotation(SPAN_MASK):
            base, dev_ids, carry = self._mask_state()
        losses, t_steady, first_round_s = [], None, 0.0
        t0 = time.perf_counter()
        for i in range(steps):
            with jax.profiler.StepTraceAnnotation(SPAN_ROUND, step_num=i):
                with jax.profiler.TraceAnnotation(SPAN_MASK):
                    carry, received = self._round_mask(base, dev_ids, carry,
                                                       i)
                    # the mask program's output is committed replicated;
                    # the step takes it split over the FL axis
                    received = jax.device_put(received, b["recv_sharding"])
                with jax.profiler.TraceAnnotation(SPAN_BATCH):
                    x, y = pipe.next_batch()
                    batch = {"tokens": jnp.asarray(x),
                             "labels": jnp.asarray(y)}
                with jax.profiler.TraceAnnotation(SPAN_STEP):
                    params, ef, loss = step(params, ef, batch, received)
                with jax.profiler.TraceAnnotation(SPAN_READBACK):
                    losses.append(float(loss))   # float() syncs the step
            if i == 0:
                t_steady = time.perf_counter()   # exclude compile
                first_round_s = t_steady - t0
            if log_every and (i % log_every == 0 or i == steps - 1):
                print(f"[{self.name}] round {i:4d} loss {losses[-1]:.4f} "
                      f"({time.perf_counter() - t0:.0f}s)")
        steady_s = (time.perf_counter() - t_steady) if steps > 1 else 0.0
        # device-steps/s: every sync round advances each of the m devices
        # by H local steps
        dev_steps = (steps - 1) * self.m_devices * self.step_cfg.local_steps
        self._built["params"], self._built["ef"] = params, ef
        return {
            "losses": losses,
            # compile + first round; mean round after it
            "first_round_s": first_round_s,
            "steady_round_s": steady_s / (steps - 1) if steps > 1 else 0.0,
            "device_steps_per_s": (dev_steps / steady_s) if steady_s else 0.0,
        }


def make_qwen2_100m_task(m_devices: int = 8, seed: int = 0,
                         scenario: str | Scenario | None = None,
                         preset: str = "full",
                         sparsity: tuple = (0.01, 0.02, 0.02),
                         aggregate: str = "sparse_gather",
                         local_steps: int = 2, local_lr: float = 3e-3,
                         batch_per_device: int = 2, seq: int = 64,
                         backend: str = "pallas",
                         pallas_min_elems: int | None = None,
                         model_axis: int = 1,
                         arch: ArchConfig | None = None
                         ) -> LGCTransformerTask:
    """Factory behind ``make_task("qwen2_100m", ...)``.

    ``preset="full"`` is the real ~128M-parameter config (1.28e8-element
    flattened gradients -- every matmul leaf above ``PALLAS_MIN_ELEMS``);
    ``preset="smoke"`` is the tiny same-shape variant for tests and CI.
    ``backend="pallas"`` routes the dense-path compression of the big
    leaves through the fused Pallas pipeline (compiled on TPU, interpreted
    on CPU).
    """
    if arch is None:
        arch = (get_config("qwen2-100m") if preset == "full"
                else get_smoke_config("qwen2-100m"))
    scn = get_scenario(scenario)
    kw = {} if pallas_min_elems is None else {
        "pallas_min_elems": pallas_min_elems}
    step_cfg = LGCStepConfig(local_steps=local_steps, local_lr=local_lr,
                             sparsity=tuple(sparsity), aggregate=aggregate,
                             backend=backend, **kw)
    return LGCTransformerTask(arch=arch, m_devices=m_devices, scenario=scn,
                              step_cfg=step_cfg, seed=seed,
                              batch_per_device=batch_per_device, seq=seq,
                              model_axis=model_axis, name=arch.name)
