"""LGC compressors (paper §2.1).

Implements, in pure JAX:

* ``top_k(x, k)``               -- classic Top_k sparsifier (Eq. before (1)).
* ``top_alpha_beta(x, a, b)``   -- Top_{alpha,beta}: keep coordinates whose
                                   |x_i| rank lies in (alpha, beta]  (Eq. (1)).
* ``lgc_layers(x, ks)``         -- the C disjoint layers
                                   {Top_{K_{c-1}, K_c}(x)}_{c=1..C}  (Eq. (2)).
* ``lgc_compress(x, ks, mask)`` -- LGC_k(x) = sum of the *received* layers.

Rank semantics follow the paper: thr_alpha is the alpha-th largest absolute
value, and Top_{alpha,beta} keeps thr_alpha >= |x_i| > thr_beta.  We resolve
ties by strict rank (jnp.argsort of -|x|), which makes layers exactly disjoint
and sum(layers) == top_{K_C}(x) -- the property the server decode relies on.

Histogram-threshold selection (the TPU-native approximation used by the
Pallas kernels) lives in ``repro.kernels``; this module is the exact oracle.

Invariants: layer disjointness / rank semantics are pinned by
tests/test_compressor.py, and ``lgc_compress_topk`` (the argsort-free
selection the batched engine uses) must stay exactly rank-equivalent to
``lgc_compress`` (tests/test_compressor.py::TestTracedSelection) -- it
feeds the engine-equivalence ladder (docs/ARCHITECTURE.md §1).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

Array = jax.Array


# ---------------------------------------------------------------------------
# pytree <-> flat vector helpers
# ---------------------------------------------------------------------------

def tree_size(tree) -> int:
    """Total number of scalar parameters in a pytree."""
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))


def flatten_tree(tree) -> Array:
    """Concatenate all leaves into one flat f32 vector (stable leaf order)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])


def unflatten_like(flat: Array, tree):
    """Inverse of :func:`flatten_tree` against a reference pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out, off = [], 0
    for l in leaves:
        n = int(l.size)
        out.append(jnp.reshape(flat[off:off + n], l.shape).astype(l.dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# rank-exact compressors (paper semantics)
# ---------------------------------------------------------------------------

def _rank_of(x: Array) -> Array:
    """rank[i] = 0-based rank of |x_i| among all coordinates (0 = largest).

    Strict total order (argsort tie-break) so that rank-range selections are
    exactly disjoint.
    """
    order = jnp.argsort(-jnp.abs(x))          # indices sorted by |x| desc
    rank = jnp.zeros_like(order).at[order].set(jnp.arange(x.shape[0]))
    return rank


def top_k(x: Array, k: int) -> Array:
    """Keep the k largest-|.| coordinates of x, zero the rest."""
    if k <= 0:
        return jnp.zeros_like(x)
    if k >= x.shape[0]:
        return x
    rank = _rank_of(x)
    return jnp.where(rank < k, x, 0.0)


def top_alpha_beta(x: Array, alpha: int, beta: int) -> Array:
    """Top_{alpha,beta}: keep coordinates ranked in (alpha, beta] by |.|.

    Paper Eq. (1) keeps thr_alpha >= |x_i| > thr_beta where thr_j is the j-th
    largest absolute value; in strict-rank terms that is
    ``alpha - 1 <= rank < beta`` with 1-based (alpha, beta].  We expose the
    0-based half-open rank interval [alpha, beta) which matches
    Top_{alpha+1..beta} of the paper and composes cleanly into layers.
    """
    rank = _rank_of(x)
    return jnp.where((rank >= alpha) & (rank < beta), x, 0.0)


def lgc_layers(x: Array, ks: Sequence[int]) -> list[Array]:
    """Split x into C disjoint layers; layer c keeps ranks [K_{c-1}, K_c).

    ks are the per-channel coordinate budgets k_c (paper's traffic
    allocation vector k).  sum(layers) == top_k(x, sum(ks)).
    """
    rank = _rank_of(x)
    layers, lo = [], 0
    for k in ks:
        hi = lo + int(k)
        layers.append(jnp.where((rank >= lo) & (rank < hi), x, 0.0))
        lo = hi
    return layers


def lgc_compress(x: Array, ks: Sequence[int],
                 received: Sequence[bool] | None = None) -> Array:
    """LGC_k(x) (paper Eq. (2)): sum of layers that actually arrived.

    ``received[c]`` models channel c delivering its layer; default all True
    (ideal channels), in which case LGC_k(x) == Top_{sum(ks)}(x).
    """
    layers = lgc_layers(x, ks)
    if received is None:
        received = [True] * len(layers)
    out = jnp.zeros_like(x)
    for layer, ok in zip(layers, received):
        out = out + (layer if ok else jnp.zeros_like(layer))
    return out


def lgc_compress_topk(u: Array, ks: Array, received: Array,
                      k_cap: int) -> Array:
    """:func:`lgc_compress_traced` without the full argsort.

    A (M=64, D=7850) argsort costs ~190 ms on XLA:CPU while ``lax.top_k``
    with k=400 costs ~12 ms, so the batched engine's sync block selects
    layers by *threshold*: the b-th largest |u| plus an index-order cumsum
    to split ties, which reproduces the stable-argsort rank semantics
    exactly on every coordinate that matters (ties among |u| values are
    broken by ascending index in both formulations; coordinates with
    u == 0 may differ in mask membership but contribute 0 either way).

    ``k_cap`` is a static bound with k_cap >= min(max(cumsum(ks)), D);
    callers round it up to a power of two so DDPG budget changes do not
    recompile.
    """
    a = jnp.abs(u)
    d = u.shape[0]
    vals = jax.lax.top_k(a, min(k_cap, d))[0]          # descending |u|
    cum = jnp.cumsum(ks.astype(jnp.int32))

    def rank_below(b):
        """Boolean mask of {i : rank(|u_i|) < b} (b traced)."""
        bc = jnp.clip(b, 1, vals.shape[0])
        thr = vals[bc - 1]                             # b-th largest value
        gt = a > thr
        eq = a == thr
        tied_take = bc - jnp.sum(gt)                   # ties to include
        pos = jnp.cumsum(eq)                           # 1-based index order
        sel = gt | (eq & (pos <= tied_take))
        sel = jnp.where(b > 0, sel, jnp.zeros_like(sel))
        return jnp.where(b >= d, jnp.ones_like(sel), sel)

    g = jnp.zeros_like(u)
    prev = jnp.zeros(a.shape, bool)
    for c in range(ks.shape[0]):       # static unroll over C channels
        cur = rank_below(cum[c])
        g = g + jnp.where(cur & ~prev & received[c], u, 0.0)
        prev = cur
    return g


def lgc_compress_traced(u: Array, ks: Array, received: Array) -> Array:
    """LGC_k(u) with *traced* layer budgets and delivery mask.

    Same rank semantics as :func:`lgc_compress` but with ``ks`` ((C,) int32)
    and ``received`` ((C,) bool) as traced values; only the layer *count* C
    is static.  This is the readable rank-based reference that
    :func:`lgc_compress_topk` (the argsort-free variant the batched engine
    actually runs) must match bit-for-bit --
    tests/test_compressor.py::TestTracedSelection pins all three against
    each other.
    """
    rank = _rank_of(u)
    cum = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(ks.astype(jnp.int32))])
    g = jnp.zeros_like(u)
    for c in range(ks.shape[0]):       # static unroll over C channels
        sel = (rank >= cum[c]) & (rank < cum[c + 1])
        g = g + jnp.where(sel & received[c], u, 0.0)
    return g


# ---------------------------------------------------------------------------
# per-model-layer budgets (structure-aware compression)
# ---------------------------------------------------------------------------
#
# The LGC channel layers above rank coordinates *globally*: a conv kernel
# competes with the fc matrix for the same top-k slots, and whole model
# layers can go silent for rounds.  The per-layer path first allocates the
# round's budget k_total across MODEL layers (the pytree leaves) under a
# registered policy, selects the top-b_l coordinates inside each layer, and
# only then splits the selected candidates across channels with the
# unchanged magnitude layering -- following layer-divergence feedback
# aggregation (arXiv:2404.08324) and FedGreen's fine-grained per-layer
# compression (arXiv:2111.06146).
#
# Contract (tests/test_compressor.py::TestPerLayer):
# * candidate masks of distinct layers are disjoint (they live in disjoint
#   slices) and sum(budgets) == k_total for "uniform" always and for
#   "size_prop" whenever k_total <= D;
# * the "uniform" policy (uniform magnitude threshold across layers ==
#   per-layer budgets set to the global top-k's per-layer hit counts) is
#   BIT-equivalent to the global path: per_layer_compress(u, ...) equals
#   lgc_compress_topk(u, ...) exactly, which is what lets
#   FLConfig.layer_policy ride the engine-equivalence ladder.

#: flat segments at least this large route through the Pallas kernels when
#: ``backend="pallas"`` -- below it the (rows, 128) marshalling costs more
#: than the kernel saves (ROADMAP item 2 measures the 10^8 regime)
PALLAS_MIN_ELEMS = 100_000


def tree_layer_slices(tree, skip_leading_axes: int = 0
                      ) -> list[tuple[str, int, int]]:
    """``(name, lo, hi)`` half-open slices of each pytree leaf inside the
    :func:`flatten_tree` vector, in leaf order.

    ``skip_leading_axes=1`` treats the leaves as stacked per-device state
    ((M, ...) arrays) and describes the per-device flat vector -- the shape
    the engines' compression rows actually have."""
    leaves, _ = jax.tree_util.tree_flatten(tree)
    paths = jax.tree_util.tree_leaves_with_path(tree)
    out, lo = [], 0
    for (path, leaf) in paths:
        shape = leaf.shape[skip_leading_axes:]
        n = 1
        for s in shape:
            n *= int(s)
        name = jax.tree_util.keystr(path)
        out.append((name, lo, lo + n))
        lo += n
    assert len(out) == len(leaves)
    return out


def _topb_mask(a: Array, b: Array, k_cap: int) -> Array:
    """Boolean mask of the ``b`` largest entries of ``a`` (absolute values
    already taken), ties split by ascending index -- the same stable-rank
    semantics as :func:`lgc_compress_topk`'s ``rank_below``.  ``b`` is
    traced, ``k_cap`` static with b <= k_cap."""
    n = a.shape[0]
    vals = jax.lax.top_k(a, min(k_cap, n))[0]
    bc = jnp.clip(b, 1, vals.shape[0])
    thr = vals[bc - 1]
    gt = a > thr
    eq = a == thr
    tied_take = bc - jnp.sum(gt)
    pos = jnp.cumsum(eq)
    sel = gt | (eq & (pos <= tied_take))
    sel = jnp.where(b > 0, sel, jnp.zeros_like(sel))
    return jnp.where(b >= n, jnp.ones_like(sel), sel)


def _largest_remainder(weights: Array, sizes: Array, k_total: Array) -> Array:
    """Apportion ``k_total`` coordinates over layers proportionally to
    ``weights``, by largest-remainder rounding, capped at layer sizes.

    Exact (sum == k_total) whenever no layer's quota exceeds its size --
    always true for size-proportional weights with k_total <= D; heavily
    skewed divergence weights may undershoot after the cap (the remainder
    pass hands out at most one extra coordinate per layer)."""
    w = jnp.maximum(weights.astype(jnp.float32), 0.0)
    tot = jnp.sum(w)
    quota = jnp.where(tot > 0, k_total * w / jnp.where(tot > 0, tot, 1.0),
                      k_total * sizes.astype(jnp.float32)
                      / jnp.sum(sizes.astype(jnp.float32)))
    base = jnp.minimum(jnp.floor(quota).astype(jnp.int32), sizes)
    rem = k_total - jnp.sum(base)
    frac = quota - jnp.floor(quota)
    headroom = (sizes - base) > 0
    # one extra coordinate to the `rem` layers with the largest remainders
    # (index-ascending tie-break via argsort stability), headroom permitting
    order = jnp.argsort(-jnp.where(headroom, frac, -1.0))
    rank = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    extra = (rank < rem) & headroom
    return base + extra.astype(jnp.int32)


def layer_budgets(policy: str, u: Array,
                  slices: Sequence[tuple[str, int, int]],
                  k_total: Array, k_cap: int) -> Array:
    """Per-model-layer coordinate budgets ``(L,) int32`` under ``policy``.

    Policies (:data:`LAYER_POLICIES`):

    * ``"uniform"``    -- one magnitude threshold across all layers: budgets
      are the per-layer hit counts of the global top-``k_total`` selection,
      so the induced compression is bit-equal to the global path.
    * ``"size_prop"``  -- b_l proportional to layer size (every layer keeps
      the same fraction of itself).
    * ``"divergence"`` -- b_l proportional to the layer's update mass
      ||u_l||_2 (layer-divergence feedback, arXiv:2404.08324): layers whose
      accumulated update diverges most from the global model get the budget.
    """
    sizes = jnp.asarray([hi - lo for _, lo, hi in slices], jnp.int32)
    if policy == "uniform":
        mask = _topb_mask(jnp.abs(u), k_total, k_cap)
        return jnp.asarray([jnp.sum(mask[lo:hi], dtype=jnp.int32)
                            for _, lo, hi in slices])
    if policy == "size_prop":
        return _largest_remainder(sizes.astype(jnp.float32), sizes, k_total)
    if policy == "divergence":
        norms = jnp.asarray([jnp.sqrt(jnp.sum(u[lo:hi] ** 2))
                             for _, lo, hi in slices])
        return _largest_remainder(norms, sizes, k_total)
    raise ValueError(f"unknown layer policy {policy!r}; registered: "
                     f"{sorted(LAYER_POLICIES)}")


#: registry of per-model-layer budget policies (see :func:`layer_budgets`)
LAYER_POLICIES: dict[str, str] = {
    "uniform": "global magnitude threshold (bit-equal to global top-k)",
    "size_prop": "budgets proportional to layer size",
    "divergence": "budgets proportional to layer update mass ||u_l||_2",
}


def per_layer_candidates(u: Array, slices: Sequence[tuple[str, int, int]],
                         budgets: Array, k_cap: int) -> Array:
    """Boolean candidate mask: top-``budgets[l]`` by |u| inside each layer
    slice, stable-rank tie split per layer.  Masks of different layers are
    disjoint by construction."""
    a = jnp.abs(u)
    parts = [_topb_mask(a[lo:hi], budgets[i], min(k_cap, hi - lo))
             for i, (_, lo, hi) in enumerate(slices)]
    return jnp.concatenate(parts)


def per_layer_candidates_hist(u: Array,
                              slices: Sequence[tuple[str, int, int]],
                              budgets: Array,
                              pallas_min_elems: int = PALLAS_MIN_ELEMS
                              ) -> Array:
    """Histogram-threshold candidate mask (the Pallas backend's selection).

    Each layer's threshold comes from the 256-bin magnitude histogram --
    the same 2-pass approximation :func:`repro.kernels.lgc_compress_hist`
    uses for channel layers -- so selected counts are bin-granular, not
    exact.  Layers with at least ``pallas_min_elems`` coordinates route
    through the Pallas ``maxabs``/``histogram`` kernels (where the fused
    row-blocked passes pay off); smaller layers use the bit-identical
    :mod:`repro.kernels.ref` oracles, so the routing threshold never
    changes the result (tests/test_kernels.py::TestPerLayerHistParity)."""
    from repro.kernels import histogram, maxabs
    from repro.kernels.ref import (hist_counts, hist_maxabs,
                                   hist_thresholds)
    parts = []
    for i, (_, lo, hi) in enumerate(slices):
        seg = u[lo:hi]
        cum = budgets[i].reshape((1,)).astype(jnp.int32)
        if hi - lo >= pallas_min_elems:
            mx = maxabs(seg)
            counts = histogram(seg, mx)
            mx = mx.reshape(())
        else:
            mx = hist_maxabs(seg)
            counts = hist_counts(seg, mx)
        thr = hist_thresholds(counts, mx, cum)[0]
        # strict > thr: same keep rule as ref.hist_layered_sparsify
        parts.append((jnp.abs(seg) > thr) & (budgets[i] > 0))
    return jnp.concatenate(parts)


def per_layer_compress(u: Array, ks: Array, received: Array,
                       slices: Sequence[tuple[str, int, int]],
                       policy: str, k_cap: int) -> Array:
    """Structure-aware LGC: per-layer budgets -> per-layer top-b_l candidate
    mask -> the unchanged channel layering over the masked vector.

    Under ``policy="uniform"`` this is bit-equal to
    ``lgc_compress_topk(u, ks, received, k_cap)`` -- the candidate set is
    exactly the global top-k_total, and every channel layer lives inside it
    (tests/test_compressor.py::TestPerLayer).  Other policies reshape WHICH
    coordinates compete, not how many: sum(ks) coordinates still cross the
    channels, so the engines' byte accounting is policy-independent."""
    k_total = jnp.sum(ks.astype(jnp.int32))
    if policy == "uniform":
        # shortcut: the global mask IS the union of the per-layer masks
        mask = _topb_mask(jnp.abs(u), k_total, k_cap)
    else:
        budgets = layer_budgets(policy, u, slices, k_total, k_cap)
        mask = per_layer_candidates(u, slices, budgets, k_cap)
    return lgc_compress_topk(jnp.where(mask, u, 0.0), ks, received, k_cap)


def per_layer_wire_bytes(budgets: Sequence[int],
                         slices: Sequence[tuple[str, int, int]],
                         value_bytes: int = 4) -> int:
    """Bytes on the wire for the per-layer sparse format.

    Per-layer indices are *layer-local*, so each costs
    ceil(log2(layer_size)) bits instead of the flat format's 4 bytes --
    the honest bytes-on-wire win structure-aware compression buys at equal
    k (reported per policy by benchmarks/bench_tasks.py)."""
    total = 0
    for b, (_, lo, hi) in zip(budgets, slices):
        idx_bytes = max(1, -(-max(hi - lo, 2).bit_length() // 8))
        total += int(b) * (value_bytes + idx_bytes)
    return total


# ---------------------------------------------------------------------------
# sparse wire format -- what actually crosses a channel
# ---------------------------------------------------------------------------

def layer_to_sparse(layer_dense: Array, k: int, x: Array,
                    lo: int) -> tuple[Array, Array]:
    """Extract fixed-size (values, indices) for a layer from the full vector.

    Used for wire-byte accounting and for the sparse_gather collective mode:
    the k coordinates ranked [lo, lo+k) of |x|.
    """
    rank = _rank_of(x)
    # position p gets the index whose rank == lo + p
    order = jnp.argsort(rank)            # order[r] = index with rank r
    idx = jax.lax.dynamic_slice_in_dim(order, lo, k)
    vals = x[idx]
    del layer_dense
    return vals, idx


def sparse_to_dense(vals: Array, idx: Array, d: int) -> Array:
    """Scatter (values, indices) back to a dense D-vector (server decode)."""
    return jnp.zeros((d,), vals.dtype).at[idx].set(vals)


def wire_bytes(ks: Sequence[int], value_bytes: int = 4,
               index_bytes: int = 4) -> list[int]:
    """Bytes on the wire per channel for the sparse format."""
    return [int(k) * (value_bytes + index_bytes) for k in ks]


# ---------------------------------------------------------------------------
# compressor objects (used by the FL loop and the distributed step)
# ---------------------------------------------------------------------------

class LGCCompressor:
    """Stateless layered compressor bound to layer budgets ``ks``.

    gamma (paper's contraction coefficient) for Top_K satisfies
    E||u - C(u)||^2 <= (1 - K/D)||u||^2, i.e. gamma = K/D in the worst case.
    """

    def __init__(self, ks: Sequence[int]):
        self.ks = [int(k) for k in ks]
        self.k_total = sum(self.ks)

    def gamma(self, d: int) -> float:
        return min(1.0, self.k_total / max(d, 1))

    def __call__(self, u: Array, received: Sequence[bool] | None = None) -> Array:
        return lgc_compress(u, self.ks, received)

    def layers(self, u: Array) -> list[Array]:
        return lgc_layers(u, self.ks)

    def sparse_layers(self, u: Array) -> list[tuple[Array, Array]]:
        out, lo = [], 0
        for k in self.ks:
            out.append(layer_to_sparse(None, k, u, lo))
            lo += k
        return out

    def wire_bytes(self) -> list[int]:
        return wire_bytes(self.ks)


@functools.partial(jax.jit, static_argnums=(1,))
def topk_jit(x: Array, k: int) -> Array:
    return top_k(x, k)


# ---------------------------------------------------------------------------
# QSGD quantization (Alistarh et al. 2017, cited by the paper §5.1) --
# composes with LGC: the selected layer values are quantized to s levels
# with unbiased stochastic rounding before transmission; the quantization
# residual joins the error-feedback memory like any other compression error.
# ---------------------------------------------------------------------------

def qsgd_quantize(x: Array, key: Array, levels: int = 255
                  ) -> tuple[Array, Array]:
    """Unbiased stochastic quantization: returns (q int8/int16 codes, scale).

    q_i in [-levels/2, levels/2], E[dequantize(q)] == x elementwise.
    """
    scale = jnp.max(jnp.abs(x)) + 1e-30
    half = levels // 2
    y = x / scale * half                       # in [-half, half]
    lo = jnp.floor(y)
    p = y - lo                                 # P(round up)
    up = jax.random.uniform(key, x.shape) < p
    q = (lo + up.astype(jnp.float32)).astype(jnp.int32)
    q = jnp.clip(q, -half, half)
    return q, scale


def qsgd_dequantize(q: Array, scale: Array, levels: int = 255) -> Array:
    half = levels // 2
    return q.astype(jnp.float32) * (scale / half)
