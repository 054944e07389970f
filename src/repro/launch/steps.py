"""Step functions: sync train, LGC train (the paper's technique), prefill,
serve -- all pjit/shard_map-ready.

The LGC step is the paper's Algorithm 1 mapped onto the mesh (DESIGN.md §3):
the FL-device axis is the slow axis ("pod" on the multi-pod mesh, "data" on
the single-pod mesh).  ``jax.shard_map`` is *manual* over that axis only --
inside, each FL device runs H local SGD steps on its own microbatches,
compresses its net progress with histogram-LGC + error feedback (per-tensor,
preserving every tensor's sharding over the auto axes), and the layers are
exchanged explicitly:

  * aggregate="dense_masked":  psum of the masked dense gradient -- the
    functional equivalent of the paper's server sum (full wire bytes).
  * aggregate="sparse_gather": per layer c an all_gather of fixed-k
    (values, indices) + scatter-add -- the layered multi-channel
    transmission, cutting collective bytes by ~D/(2 sum k_c).
  * aggregate="none":          FedAvg baseline (dense delta, no compression).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.compressor import PALLAS_MIN_ELEMS
from repro.kernels import lgc_compress_hist
from repro.kernels import ref as kref
from repro.models import transformer as tf
from repro.optim.optimizers import (OptimizerConfig, apply_updates,
                                    get_optimizer)
from . import compat
from .mesh import fl_axis_name

Array = jax.Array

# per-arch gradient-accumulation defaults for train_4k on the 256-chip pod
# (keeps the scan-carry activation stash under ~8 GB/chip; DESIGN.md §5)
ACCUM_STEPS = {
    "glm4-9b": 4, "yi-34b": 8, "grok-1-314b": 8, "starcoder2-7b": 4,
    "phi-3-vision-4.2b": 4, "olmoe-1b-7b": 2, "qwen2-1.5b": 2,
    "mamba2-370m": 2, "zamba2-1.2b": 2, "whisper-small": 2,
}


@dataclasses.dataclass(frozen=True)
class LGCStepConfig:
    local_steps: int = 4                   # H: local SGD steps per sync
    local_lr: float = 1e-3
    sparsity: tuple = (0.01, 0.02, 0.02)   # per-channel k_c / D fractions
    # dense_masked | sparse_gather | bucket_sparse | none
    aggregate: str = "dense_masked"
    ef_dtype: str = "float32"
    # I-C7: exchange the masked update in bf16 (EF keeps the f32 residual,
    # including the rounding error -- error feedback absorbs quantisation
    # exactly like sparsification).  Halves cross-pod bytes for the
    # dense_masked mode on TPU.  Default f32 because XLA:CPU's
    # AllReducePromotion pass aborts on bf16 all-reduce ("Invalid binary
    # instruction opcode copy") -- flip to "bfloat16" on real TPU.
    psum_dtype: str = "float32"
    # "pallas" routes dense-path leaves of >= pallas_min_elems elements
    # through the fused kernels.lgc_compress_hist pipeline (bit-identical
    # to the kref oracle -- tests/test_kernels.py); smaller leaves stay on
    # the oracle either way.  "exact" keeps everything on the oracle.
    # The kernels run compiled on TPU and interpreted on CPU
    # (kernels.platform.resolve_interpret).
    backend: str = "exact"
    pallas_min_elems: int = PALLAS_MIN_ELEMS

    @property
    def n_channels(self) -> int:
        return len(self.sparsity)


# ---------------------------------------------------------------------------
# sync (standard data+tensor-parallel) training -- the framework baseline
# ---------------------------------------------------------------------------

def make_sync_train_step(cfg: ArchConfig, *, accum_steps: int = 1,
                         opt_cfg: OptimizerConfig | None = None):
    _, opt_update = get_optimizer(cfg.optimizer, opt_cfg)

    def loss_fn(p, mb):
        return tf.lm_loss(p, cfg, mb)

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps,
                                    *x.shape[1:]), batch)

            def acc(carry, mb):
                loss_sum, g_sum = carry
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                g_sum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), g_sum, g)
                return (loss_sum + l, g_sum), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(acc, (jnp.float32(0.0), g0), mbs)
            loss = loss / accum_steps
            grads = jax.tree_util.tree_map(
                lambda g, p: (g / accum_steps).astype(p.dtype), grads, params)
        updates, opt_state = opt_update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


# ---------------------------------------------------------------------------
# LGC training step (Algorithm 1 on the mesh)
# ---------------------------------------------------------------------------

# Named scopes of the step's phases.  They only write HLO metadata (each
# op's ``op_name`` path), so a profiler trace can credit device time to a
# phase; the innermost ``lgc.*`` component of the path decides.
SCOPE_LOCAL_SGD = "lgc.local_sgd"          # the H-step scan
SCOPE_COMPRESS = "lgc.compress"            # delta, per-leaf LGC, wire cast
SCOPE_EXCHANGE = "lgc.exchange"            # every cross-device reduction
SCOPE_SERVER_UPDATE = "lgc.server_update"  # new weights, new error memory
PHASE_SCOPES = (SCOPE_LOCAL_SGD, SCOPE_COMPRESS, SCOPE_EXCHANGE,
                SCOPE_SERVER_UPDATE)


def _leaf_ks(size: int, sparsity: Sequence[float]) -> list[int]:
    """Per-channel k budgets, cumulatively clamped to the leaf size.

    The naive ``max(1, int(size * f))`` floor lets the *cumulative* budget
    exceed the leaf for small leaves (a 64-element bias at sparsity
    (0.01, 0.02, 0.02) requests 3 coords; a 2-element leaf requests 3):
    the overflow channels then get degenerate (zero) thresholds and their
    bands either truncate or double-cover coordinates.  Clamping the
    cumulative sum keeps the channels disjoint by construction: channel c
    owns ranks [cum[c-1], cum[c]) and trailing channels degrade to k=0
    (empty band, no collective payload) once the leaf is exhausted.
    Pinned by tests/test_lgc_step.py::TestSmallLeafBudgets.
    """
    ks = [max(1, int(size * f)) for f in sparsity]
    cum = np.minimum(np.cumsum(ks), size)
    return np.diff(np.concatenate([[0], cum])).tolist()


def _leaf_cum_ks(size: int, sparsity: Sequence[float]) -> jnp.ndarray:
    return jnp.asarray(np.cumsum(_leaf_ks(size, sparsity)), jnp.int32)


def _compress_leaf_dense(e: Array, delta: Array, sparsity, recv: Array,
                         *, backend: str = "exact",
                         pallas_min_elems: int = PALLAS_MIN_ELEMS
                         ) -> tuple[Array, Array]:
    """Histogram-LGC on one tensor; returns (g, e_new) with leaf's shape.

    ``recv`` is this FL device's (C,) per-channel delivery mask: masked
    channels contribute nothing to the wire sum and their mass stays in
    the error memory.  Leaves of >= ``pallas_min_elems`` elements route
    through the fused Pallas pipeline when ``backend == "pallas"`` -- at
    qwen2_100m scale that is every matmul leaf (ARCHITECTURE.md §12).
    """
    shape = delta.shape
    e_flat = e.reshape(-1).astype(jnp.float32)
    d_flat = delta.reshape(-1).astype(jnp.float32)
    cum_ks = _leaf_cum_ks(d_flat.shape[0], sparsity)
    if backend == "pallas" and d_flat.shape[0] >= pallas_min_elems:
        g, e_new = lgc_compress_hist(e_flat, d_flat, cum_ks, recv)
    else:
        g, e_new = kref.hist_lgc_compress(e_flat, d_flat, cum_ks, recv)
    return g.reshape(shape), e_new.reshape(shape)


def _model_axis_of(spec) -> int | None:
    """Index of the dimension a PartitionSpec shards over 'model'."""
    if spec is None:
        return None
    for i, ax in enumerate(spec):
        if ax == "model" or (isinstance(ax, tuple) and "model" in ax):
            return i
    return None


def _row_thresholds(u: Array, mx: Array, cum: Array) -> Array:
    """Per-row layer thresholds of the 256-bin magnitude histogram.

    Bit-identical to ``vmap(kref.hist_thresholds)(vmap(kref.hist_counts)(u,
    mx), mx)`` without building the histogram: vmapped, its scatter-add
    lowers on a TPU to a sort of every bin index plus a scatter.  Per row
    and cumulative budget K, the threshold's bin is the largest b with
    #{bin >= b} >= K.  That count never grows with b, and b = 0 holds
    (K <= cols, the clamp of ``_leaf_ks``), so an 8-step bisection over the
    bins finds b exactly.  Each step is one fused reduce over a uint8 copy
    of the bins, one count per channel.

    u: (rows, cols) f32; mx: (rows,) per-row max |u|; cum: (C,) int32.
    Returns (rows, C) f32 thresholds.
    """
    scale = jnp.where(mx > 0, kref.N_BINS / mx, 0.0)       # == hist_counts
    bins = jnp.clip((jnp.abs(u) * scale[:, None]).astype(jnp.int32),
                    0, kref.N_BINS - 1).astype(jnp.uint8)
    lo = jnp.zeros((u.shape[0], cum.shape[0]), jnp.uint8)
    step = kref.N_BINS // 2
    while step:
        mid = lo + step                                    # <= 255
        # one reduce per channel, fused by XLA into one pass over bins;
        # counting down the columns of bins.T keeps the leaf's TPU layout
        # rows-minor, as top_k wants (a transposed copy made it 2.5x slower)
        cnt = jnp.stack([jnp.sum(bins.T >= mid[:, c], axis=0,
                                 dtype=jnp.int32)
                         for c in range(cum.shape[0])], 1)
        lo = jnp.where(cnt >= cum, mid, lo)
        step //= 2
    return lo.astype(jnp.float32) * (mx / kref.N_BINS)[:, None]


def _compress_leaf_sparse(e: Array, delta: Array, sparsity, recv: Array,
                          fl_ax: str, n_fl: int, spec=None
                          ) -> tuple[Array, Array]:
    """Layered sparse exchange: per channel, all_gather fixed-k (val, idx).

    Each LGC layer is an independent collective -- the multi-channel
    transmission.  Returns (g_mean_global, e_new_local).

    SHARD-ALIGNED selection (perf iterations I-C2/I-C3, EXPERIMENTS.md
    §Perf): a global top-k over a model-sharded leaf forces GSPMD to
    all-gather the whole tensor (measured: cross-pod bytes UP 4x -- the
    original hypothesis refuted), and a naive (rows, cols) reshape is not
    shard-aligned either (involuntary-full-remat warnings, no improvement).
    The fix moves the leaf's OWN model-sharded axis to the front, so the
    (rows, cols) view is a local relabeling; every shard then selects its
    own k/rows coordinates, the pod-axis all_gather moves only sharded
    (val, idx) pairs, and the rank bias of shard-local selection is
    absorbed by the error-feedback memory.
    """
    from repro.models.layers import maybe_constrain
    shape = delta.shape
    u0 = e + delta.astype(jnp.float32)
    ax = _model_axis_of(spec) if delta.ndim else None
    if ax is not None:
        u = jnp.moveaxis(u0, ax, 0).reshape(shape[ax], -1)
        u = maybe_constrain(u, "model", None)
    else:
        u = u0.reshape(1, -1)
    rows, cols = u.shape

    # per-row layer thresholds of the magnitude histogram (all local)
    mx = jax.vmap(kref.hist_maxabs)(u)                     # (rows,)
    ks = _leaf_ks(cols, sparsity)            # cumulative clamp: see _leaf_ks
    cum = jnp.asarray(np.cumsum(ks), jnp.int32)
    thr = _row_thresholds(u, mx, cum)                      # (rows, C)
    a = jnp.abs(u)
    hi = jnp.concatenate([jnp.full((rows, 1), jnp.inf), thr[:, :-1]], 1)

    g_own = jnp.zeros_like(u)
    g_sum = jnp.zeros_like(u)
    for c, k_c in enumerate(ks):
        if k_c == 0:
            # channel budget exhausted by the clamp: empty band on every
            # device (ks is host-side, so all shards skip the collective)
            continue
        band = jnp.where((a <= hi[:, c:c + 1]) & (a > thr[:, c:c + 1]), a, 0.0)
        k_eff = min(k_c + max(1, cols // kref.N_BINS), cols)
        bvals, idx = jax.lax.top_k(band, k_eff)            # (rows, k_eff)
        # bvals==0 slots are top_k ties on empty band positions: masking
        # their values dedupes the (arbitrary) repeated indices, and the
        # recv mask drops undelivered channels (their mass stays in EF)
        vals = (jnp.take_along_axis(u, idx, 1) * (bvals > 0)
                * recv[c].astype(jnp.float32))
        if ax is not None:
            vals = maybe_constrain(vals, "model", None)
            idx = maybe_constrain(idx, "model", None)
        g_own = jax.vmap(lambda g, i, v: g.at[i].add(v))(g_own, idx, vals)
        # ---- one collective per LGC layer (the "channel") ----
        # (I-C5: re-pin the gathered buffers to the model axis -- the
        # all_gather result otherwise materialises replicated per chip,
        # which is what kept xpod at the unsharded size in I-C4)
        with jax.named_scope(SCOPE_EXCHANGE):
            vals_all = jax.lax.all_gather(vals, fl_ax)     # (n_fl, rows, k)
            idx_all = jax.lax.all_gather(idx, fl_ax)
        if ax is not None:
            vals_all = maybe_constrain(vals_all, None, "model", None)
            idx_all = maybe_constrain(idx_all, None, "model", None)
        for fl in range(n_fl):
            g_sum = jax.vmap(lambda g, i, v: g.at[i].add(v)
                             )(g_sum, idx_all[fl], vals_all[fl])
    e_new = u - g_own
    g_mean = g_sum / n_fl
    if ax is not None:
        back = lambda t: jnp.moveaxis(
            t.reshape((shape[ax],) + shape[:ax] + shape[ax + 1:]), 0, ax)
        return back(g_mean), back(e_new)
    return g_mean.reshape(shape), e_new.reshape(shape)


def _compress_leaf_bucket(e: Array, delta: Array, sparsity, recv: Array,
                          fl_ax: str, n_fl: int, spec=None
                          ) -> tuple[Array, Array]:
    """Bucketed layered selection (perf iteration I-C6, beyond-paper).

    ``lax.top_k`` lowers to a sort, and XLA's sort partitioning replicates a
    model-sharded operand (measured: the sparse exchange stayed at the
    unsharded byte count through I-C4/C5).  Bucket-argmax sidesteps sort
    entirely: split each shard-local row into K strided buckets and keep
    each bucket's max-|.| element -- a pure reduction that partitions
    cleanly.  Selection is a randomized top-K approximation (bucket maxima
    ~ top-K for heavy-tailed gradients); the un-sent mass stays in the
    error-feedback memory exactly as for exact top-K, so Lemma 1 applies
    with a (slightly smaller) per-shard gamma.  Channel c owns k_c of the
    K buckets -- the layers stay disjoint by construction.
    """
    from repro.models.layers import maybe_constrain
    shape = delta.shape
    u0 = e + delta.astype(jnp.float32)
    ax = _model_axis_of(spec) if delta.ndim else None
    if ax is not None:
        u = jnp.moveaxis(u0, ax, 0).reshape(shape[ax], -1)
        u = maybe_constrain(u, "model", None)
    else:
        u = u0.reshape(1, -1)
    rows, cols = u.shape
    ks = _leaf_ks(cols, sparsity)            # cumulative clamp: see _leaf_ks
    k_total = sum(ks)
    bucket = max(cols // k_total, 1)
    k_eff = cols // bucket
    used = k_eff * bucket
    ub = u[:, :used].reshape(rows, k_eff, bucket)
    pos_in = jnp.argmax(jnp.abs(ub), -1)                   # (rows, k_eff)
    vals = jnp.take_along_axis(ub, pos_in[..., None], -1)[..., 0]
    idx = (jnp.arange(k_eff)[None, :] * bucket + pos_in).astype(jnp.int32)
    if ax is not None:
        vals = maybe_constrain(vals, "model", None)
        idx = maybe_constrain(idx, "model", None)

    # one all_gather per channel-layer: channel c carries buckets
    # [sum(ks[:c]), sum(ks[:c+1])) -- disjoint layers, separate collectives.
    # g_own accumulates ONLY the delivered slices: buckets past the channel
    # budget (k_eff > k_total) or on a masked channel are never transmitted,
    # so their mass must stay in the error memory (the seed code credited
    # every bucket to g_own, silently leaking the untransmitted tail).
    g_own = jnp.zeros_like(u)
    g_sum = jnp.zeros_like(u)
    lo = 0
    for c, k_c in enumerate(ks):
        hi = min(lo + k_c, k_eff)
        if hi <= lo:
            break
        v_c = vals[:, lo:hi] * recv[c].astype(jnp.float32)
        i_c = idx[:, lo:hi]
        g_own = jax.vmap(lambda g, i, v: g.at[i].add(v))(g_own, i_c, v_c)
        with jax.named_scope(SCOPE_EXCHANGE):
            v_all = jax.lax.all_gather(v_c, fl_ax)         # (n_fl, rows, k_c)
            i_all = jax.lax.all_gather(i_c, fl_ax)
        for fl in range(n_fl):
            g_sum = jax.vmap(lambda g, i, v: g.at[i].add(v)
                             )(g_sum, i_all[fl], v_all[fl])
        lo = hi
    e_new = u - g_own
    g_mean = g_sum / n_fl
    if ax is not None:
        back = lambda t: jnp.moveaxis(
            t.reshape((shape[ax],) + shape[:ax] + shape[ax + 1:]), 0, ax)
        return back(g_mean), back(e_new)
    return g_mean.reshape(shape), e_new.reshape(shape)


def make_lgc_train_step(cfg: ArchConfig, mesh, step_cfg: LGCStepConfig,
                        batch_spec_tree, param_spec_tree=None):
    """Algorithm 1: returns f(params, ef, batch, received=None)
    -> (params, ef, metrics).

    Server update is plain subtraction (Alg. 1 line 21); the optimizer lives
    on the devices as plain SGD (line 6), exactly as in the paper.
    ``param_spec_tree`` (optional) enables shard-aligned sparse selection
    in the sparse_gather mode (see _compress_leaf_sparse).

    The error-feedback tree uses the stacked ``(n_fl, *leaf)`` convention
    (:func:`init_ef_tree`), sharded ``P(fl_ax)``: each FL device owns its
    own residual row.  The seed code kept per-device EF under a replicated
    ``P()`` spec -- undefined with ``check_rep=False``, and ``device_get``
    (and therefore every checkpoint) silently collapsed it to shard 0's
    residual (tests/test_checkpoint.py pins the round-trip).

    ``received`` (optional, (n_fl, C) int) is the per-device per-channel
    delivery mask for the sync round -- the multi-channel availability the
    paper's scenarios drive (gilbert_flaky etc.).  Masked channels are
    never transmitted; their mass stays in the device's error memory (the
    same dropout+EF rule the engines use).  ``None`` means all delivered.
    The FedAvg baseline (aggregate="none") has no channels and ignores it.
    """
    fl_ax = fl_axis_name(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_fl = sizes[fl_ax]
    # manual over the FL axis and every size-1 axis: a size-1 axis
    # partitions nothing, and Mosaic kernels lower only where no axis is
    # left to automatic partitioning
    manual = {fl_ax} | {a for a, n in sizes.items() if n == 1}
    if step_cfg.backend == "pallas" and manual != set(mesh.axis_names):
        raise ValueError(
            f"backend='pallas' needs every mesh axis but the FL axis "
            f"{fl_ax!r} to have size 1 (Pallas kernels cannot be "
            f"partitioned automatically); mesh axes {sizes}")
    h = step_cfg.local_steps
    n_ch = step_cfg.n_channels

    def loss_fn(p, mb):
        return tf.lm_loss(p, cfg, mb)

    # manual specs: slice only the FL axis; auto axes flow through
    def manual_batch_spec(spec):
        # keep the leading-axis entry only if it names the fl axis
        lead = spec[0] if len(spec) else None
        has_fl = lead == fl_ax or (isinstance(lead, tuple) and fl_ax in lead)
        return P(fl_ax) if has_fl else P()

    batch_in_specs = jax.tree_util.tree_map(
        manual_batch_spec, batch_spec_tree,
        is_leaf=lambda x: isinstance(x, P))

    dense_kw = dict(backend=step_cfg.backend,
                    pallas_min_elems=step_cfg.pallas_min_elems)

    def compress(ef, delta, recv):
        """(g_mean, ef_new): the server's mean update and this device's new
        error memory, by aggregate mode."""
        def split(pairs):
            pick = lambda i: jax.tree_util.tree_map(
                lambda t: t[i], pairs, is_leaf=lambda t: isinstance(t, tuple))
            return pick(0), pick(1)

        if step_cfg.aggregate == "none":              # FedAvg baseline
            with jax.named_scope(SCOPE_EXCHANGE):
                return jax.tree_util.tree_map(
                    lambda dl: jax.lax.pmean(dl, fl_ax), delta), ef
        if step_cfg.aggregate in ("bucket_sparse", "sparse_gather"):
            leaf_fn = (_compress_leaf_bucket
                       if step_cfg.aggregate == "bucket_sparse"
                       else _compress_leaf_sparse)
            if param_spec_tree is not None:
                return split(jax.tree_util.tree_map(
                    lambda e, dl, sp: leaf_fn(
                        e, dl, step_cfg.sparsity, recv, fl_ax, n_fl, sp),
                    ef, delta, param_spec_tree))
            return split(jax.tree_util.tree_map(
                lambda e, dl: leaf_fn(
                    e, dl, step_cfg.sparsity, recv, fl_ax, n_fl),
                ef, delta))
        # dense_masked
        g, ef_new = split(jax.tree_util.tree_map(
            lambda e, dl: _compress_leaf_dense(
                e, dl, step_cfg.sparsity, recv, **dense_kw),
            ef, delta))
        wire_dt = jnp.dtype(step_cfg.psum_dtype)
        g_wire = jax.tree_util.tree_map(lambda gl: gl.astype(wire_dt), g)
        # quantisation residue joins the error memory (I-C7)
        ef_new = jax.tree_util.tree_map(
            lambda en, gl, gw: en + (gl - gw.astype(jnp.float32)),
            ef_new, g, g_wire)
        with jax.named_scope(SCOPE_EXCHANGE):
            g_mean = jax.tree_util.tree_map(
                lambda gw: jax.lax.pmean(gw, fl_ax).astype(jnp.float32),
                g_wire)
        return g_mean, ef_new

    def step(params, ef, batch, received=None):
        if received is None:
            received = jnp.ones((n_fl, n_ch), jnp.int32)

        @functools.partial(
            compat.shard_map, mesh=mesh,
            in_specs=(P(), P(fl_ax), batch_in_specs, P(fl_ax)),
            out_specs=(P(), P(fl_ax), P()),
            axis_names=manual)
        def inner(params, ef_stack, batch, received):
            with jax.named_scope(SCOPE_COMPRESS):
                ef = jax.tree_util.tree_map(lambda x: x[0], ef_stack)
                recv = received[0].astype(jnp.int32)  # (C,) own channels
            # ---- H local SGD steps (Alg. 1 line 6) -----------------------
            b_local = jax.tree_util.tree_leaves(batch)[0].shape[0]
            assert b_local % h == 0 and b_local >= h, (
                f"per-FL-device batch {b_local} must be divisible by "
                f"local_steps H={h}")

            def local_sgd(carry, mb):
                p, loss_sum = carry
                l, g = jax.value_and_grad(loss_fn)(p, mb)
                p = jax.tree_util.tree_map(
                    lambda w, gi: (w.astype(jnp.float32)
                                   - step_cfg.local_lr
                                   * gi.astype(jnp.float32)).astype(w.dtype),
                    p, g)
                return (p, loss_sum + l), None

            with jax.named_scope(SCOPE_LOCAL_SGD):
                mbs = jax.tree_util.tree_map(
                    lambda x: x.reshape(h, x.shape[0] // h, *x.shape[1:]),
                    batch)
                (p_end, loss_sum), _ = jax.lax.scan(
                    local_sgd, (params, jnp.float32(0.0)), mbs)
            with jax.named_scope(SCOPE_EXCHANGE):
                loss = jax.lax.pmean(loss_sum / h, fl_ax)

            # ---- net progress + error feedback + LGC (lines 8-11) -------
            with jax.named_scope(SCOPE_COMPRESS):
                delta = jax.tree_util.tree_map(
                    lambda w0, w1: (w0.astype(jnp.float32)
                                    - w1.astype(jnp.float32)), params, p_end)
                g_mean, ef_new = compress(ef, delta, recv)

            # ---- server update + broadcast (lines 20-21, 12) -------------
            with jax.named_scope(SCOPE_SERVER_UPDATE):
                params_new = jax.tree_util.tree_map(
                    lambda w, gm: (w.astype(jnp.float32) - gm).astype(w.dtype),
                    params, g_mean)
                ef_new = jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.dtype(step_cfg.ef_dtype))[None],
                    ef_new)
            return params_new, ef_new, loss

        return inner(params, ef, batch, received)

    return step


def init_ef_tree(params, n_fl: int = 1, dtype=jnp.float32):
    """Stacked per-FL-device error-feedback tree: leaves are
    ``(n_fl, *param_shape)`` -- row m is device m's residual (the same
    stacked (M, .) convention the batched engines use).  Shard the leading
    axis ``P(fl_axis)`` via :func:`repro.launch.sharding_rules.ef_specs`.
    """
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros((n_fl,) + p.shape, dtype), params)


def lgc_wire_bytes_per_round(params, step_cfg: LGCStepConfig,
                             value_bytes: int = 4, index_bytes: int = 4
                             ) -> dict[str, int]:
    """Per-device uplink bytes for one sync round, by aggregate mode.

    Uses the clamped per-leaf channel budgets (:func:`_leaf_ks`), so small
    leaves never over-report.  ``dense_masked`` moves the full dense tensor
    through the psum (the masking saves nothing on the wire -- that is the
    point of the sparse/bucket modes); ``none`` is the FedAvg baseline.
    """
    leaves = [int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)]
    k_total = sum(sum(_leaf_ks(n, step_cfg.sparsity)) for n in leaves)
    d_total = sum(leaves)
    psum_bytes = jnp.dtype(step_cfg.psum_dtype).itemsize
    return {
        "none": d_total * value_bytes,
        "dense_masked": d_total * psum_bytes,
        "sparse_gather": k_total * (value_bytes + index_bytes),
        "bucket_sparse": k_total * (value_bytes + index_bytes),
    }


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ArchConfig, cache_len: int | None = None):
    def prefill_step(params, batch):
        return tf.prefill(params, cfg, batch, cache_len)
    return prefill_step


def make_serve_step(cfg: ArchConfig, window: int = 0):
    def serve_step(params, token, cache):
        logits, cache = tf.decode_step(params, cfg, token, cache,
                                       window=window)
        next_token = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        return next_token, cache
    return serve_step
