"""Plain reference of one LGC sync round, and the comparison that decides
``correct``.  It imports nothing of the program.

The round (the paper's Algorithm 1 as the configuration and traffic state
it): every FL device takes H local SGD steps from the server weights, each
on its own slice of the batch; its net progress plus its error memory is
split into C layers by magnitude; the layers whose channel delivered are
sent, the rest stay in the error memory; the server subtracts the mean of
what was sent.  Weights are stored in the dtype the configuration states
(bfloat16); each local step computes the configuration's model family's
plain forward and loss (``families/``) in float32 at ``highest`` matmul
precision and stores the stepped weights back in that dtype.

Layer selection is the histogram rule the configuration's uplink names:
the thresholds are edges of a 256-bin histogram of ``|u|`` over
``[0, max|u|]``, channel c keeping ``thr[c-1] >= |u| > thr[c]``, where
``thr[c]`` is the highest edge with at least ``k_1 + ... + k_c`` elements
above it (``k_c = max(1, int(n * f_c))``, clamped so the budgets never
exceed ``n``).  ``dense_masked`` selects over each whole tensor;
``sparse_gather`` selects per row along the tensor-parallel axis (the
family's ``row_axis``) and keeps at most ``k_c + max(1, n // 256)`` of a
band's largest elements per row.

``precision="fp8"`` casts every matmul operand to float8_e4m3fn
(``families.mm``; the control), and ``half_batch`` trains each local step
on half its sequences (a planted fault); neither is used by a benchmark
run.
"""
from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import families, weights as W
from chipbench.feed import for_cell as feed_for_cell

N_BINS = 256


# ---------------------------------------------------------------------------
# local steps
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("config", "precision", "lr"))
def local_step(p, tokens, labels, *, config: str, precision: str, lr: float):
    """One local SGD step: (loss, stepped weights, per-leaf |grad|).
    ``config``: the configuration file's JSON text (hashable, so static)."""
    c = json.loads(config)
    pf = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
    loss, g = jax.value_and_grad(families.load(c).loss_fn)(
        pf, tokens, labels, c, precision)
    stepped = jax.tree_util.tree_map(
        lambda w, wf, gi: (wf - lr * gi).astype(w.dtype), p, pf, g)
    return loss, stepped, W.leaf_norms(g)


# ---------------------------------------------------------------------------
# layered selection with error feedback
# ---------------------------------------------------------------------------

def leaf_ks(n: int, sparsity) -> tuple[int, ...]:
    """Per-channel budgets, the cumulative sum clamped to ``n``."""
    ks = [max(1, int(n * f)) for f in sparsity]
    cum = np.minimum(np.cumsum(ks), n)
    return tuple(int(k) for k in np.diff(np.concatenate([[0], cum])))


def _thresholds(a, ks):
    """Channel thresholds of ``a`` = |u| (..., n) over its last axis:
    (..., C).

    ``thr[c]`` is the edge of the highest bin b with at least
    ``k_1 + ... + k_c`` elements in bins b and above.  That count falls as
    b rises, so b is found by bisection over the 256 bins, one counting
    pass per step.
    """
    mx = jnp.max(a, axis=-1)
    scale = jnp.where(mx > 0, N_BINS / mx, 0.0)
    bins = jnp.clip((a * scale[..., None]).astype(jnp.int32), 0, N_BINS - 1)
    lead = a.shape[:-1]
    out = []
    for k in np.cumsum(ks):
        def halve(_, lohi, k=int(k)):
            lo, hi = lohi                   # the answer lies in [lo, hi]
            mid = (lo + hi + 1) // 2
            ok = jnp.sum(bins >= mid[..., None], axis=-1) >= k
            return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)
        lo, _ = jax.lax.fori_loop(
            0, 8, halve, (jnp.zeros(lead, jnp.int32),
                          jnp.full(lead, N_BINS - 1, jnp.int32)))
        out.append(lo)
    return jnp.stack(out, -1).astype(jnp.float32) * (mx / N_BINS)[..., None]


@functools.partial(jax.jit, static_argnames=("ks", "uplink"))
def select(u, recv, *, ks: tuple, uplink: str):
    """Sent part of ``u`` under delivery mask ``recv`` (C,): over the whole
    of a flat ``u`` (n,), or per row of ``u`` (rows, n)."""
    a = jnp.abs(u)
    thr = _thresholds(a, ks)
    n = u.shape[-1]
    sent = jnp.zeros_like(u)
    hi = jnp.full(u.shape[:-1], jnp.inf)
    for c, k in enumerate(ks):
        lo = thr[..., c]
        band = (a <= hi[..., None]) & (a > lo[..., None])
        hi = lo
        if k == 0:
            continue
        deliver = recv[c] > 0
        if uplink == "dense_masked":
            sent = sent + jnp.where(band & deliver, u, 0.0)
            continue
        top_vals, idx = jax.lax.top_k(jnp.where(band, a, 0.0),
                                      min(k + max(1, n // N_BINS), n))
        vals = jnp.take_along_axis(u, idx, -1) * (top_vals > 0) * deliver
        sent = sent.at[jnp.arange(u.shape[0])[:, None], idx].add(vals)
    return sent


def compress_leaf(w0, w_end, e, recv, sparsity, uplink: str, ax):
    """(sent, new error memory) of one leaf, float32, shaped like ``w0``;
    ``ax``: the leaf's row axis (``None``: one row), read by sparse_gather
    alone."""
    u = (w0.astype(jnp.float32) - w_end.astype(jnp.float32)
         + (0.0 if e is None else e))
    if uplink == "dense_masked":
        flat = u.reshape(-1)
        sent = select(flat, recv, ks=leaf_ks(flat.shape[0], sparsity),
                      uplink=uplink).reshape(u.shape)
    elif ax is None or u.ndim == 0:
        rows = u.reshape(1, -1)
        sent = select(rows, recv, ks=leaf_ks(rows.shape[1], sparsity),
                      uplink=uplink).reshape(u.shape)
    else:
        ax = ax % u.ndim
        moved = jnp.moveaxis(u, ax, 0)
        rows = moved.reshape(u.shape[ax], -1)
        sent = select(rows, recv, ks=leaf_ks(rows.shape[1], sparsity),
                      uplink=uplink)
        sent = jnp.moveaxis(sent.reshape(moved.shape), 0, ax)
    return sent, u - sent


# ---------------------------------------------------------------------------
# rounds
# ---------------------------------------------------------------------------

def _round(params, names, ef, tokens, labels, recv, *, cell, config: str,
           precision: str, half_batch: bool):
    """One sync round. ``ef``: per device a list of host leaves (None: 0)."""
    t = cell.traffic
    family = families.load(cell.config)
    n_dev, b, h = t["fl_devices"], t["sequences_per_device"], t["local_steps"]
    mb = b // h
    w0 = jax.tree_util.tree_leaves(params)
    treedef = jax.tree_util.tree_structure(params)
    losses, grad_norms, new_ef = [], None, []
    sent_sum = [None] * len(names)
    for i in range(n_dev):
        p = params
        for j in range(h):
            lo = i * b + j * mb
            hi = lo + (mb // 2 if half_batch else mb)
            loss, p, gn = local_step(p, jnp.asarray(tokens[lo:hi]),
                                     jnp.asarray(labels[lo:hi]),
                                     config=config,
                                     precision=precision, lr=t["local_lr"])
            losses.append(float(loss))
            grad_norms = gn if grad_norms is None else grad_norms
        ends = jax.tree_util.tree_leaves(p)
        del p
        t_steps = time.perf_counter()
        r = jnp.asarray(recv[i], jnp.int32)
        ef_i = []
        for k, name in enumerate(names):
            e = None if ef is None else jnp.asarray(ef[i][k])
            sent, e_new = compress_leaf(w0[k], ends[k], e, r,
                                        tuple(t["channel_sparsity"]),
                                        t["uplink"], family.row_axis(
                                            name, w0[k].ndim))
            sent_sum[k] = sent if sent_sum[k] is None else sent_sum[k] + sent
            ef_i.append(np.asarray(e_new))    # host: frees the chip for steps
            ends[k] = None
        new_ef.append(ef_i)
        print(f"reference device {i}: compress "
              f"{time.perf_counter() - t_steps:.2f} s", file=sys.stderr)
    new = [(w.astype(jnp.float32) - s / n_dev).astype(w.dtype)
           for w, s in zip(w0, sent_sum)]
    return (float(np.mean(losses)), jax.tree_util.tree_unflatten(treedef, new),
            new_ef, np.asarray(grad_norms))


def run_reference(cell, seed: int, shapes, masks, rounds: int = 3, *,
                  precision: str = "f32", half_batch: bool = False) -> dict:
    """The reference's readings over ``rounds`` rounds from the seed's
    weights and batches, with the program's delivery masks per round."""
    config = json.dumps(cell.config, sort_keys=True)
    sigma = cell.config["initializer_range"]
    names = W.leaf_names(shapes)
    params = W.make(shapes, seed, sigma)
    feed = feed_for_cell(cell, seed)
    out = {"losses": []}
    ef = None
    for r in range(rounds):
        t0 = time.perf_counter()
        x, y = feed.next_batch()
        loss, params, ef, gn = _round(
            params, names, ef, x, y, masks[r], cell=cell, config=config,
            precision=precision, half_batch=half_batch)
        out["losses"].append(loss)
        if r == 0:
            out["grad"] = gn
            out["update"] = np.asarray(W.norms_from_init(params, seed, sigma))
            rows = [np.stack([dev[k] for dev in ef]) for k in range(len(names))]
            out["progress"] = np.asarray(W.norms_from_init(
                params, seed, sigma,
                plus=jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(params), rows)))
            del rows
        print(f"reference round {r}: {time.perf_counter() - t0:.2f} s",
              file=sys.stderr, flush=True)
    out["change"] = np.asarray(W.norms_from_init(params, seed, sigma))
    return out


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

#: leaves whose reference gradient is under this share of the median
#: leaf's move by round-off alone (a key bias under softmax) and are left
#: out of the norm comparisons
ROUNDOFF_SHARE = 1e-3


def worst_leaf_gap(prog, ref, keep) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    den = np.maximum(ref, np.median(ref[keep]))
    diff = np.abs(prog - ref)
    # a leaf that neither side moved (every channel undelivered) agrees
    gaps = np.where(den > 0, diff / np.where(den > 0, den, 1.0),
                    np.where(diff > 0, np.inf, 0.0))
    return float(np.max(gaps[keep]))


def readings(prog: dict, ref: dict) -> dict:
    """The numbers: the first round's relative loss gap; the worst leaf's
    gap of norms of the first round's update (what the server applied), of
    the device's whole progress in it (the update plus the error memory it
    left) and of the change after the last round; and, shown but not
    compared, the loss gap of the worst round."""
    grad = np.asarray(ref["grad"], np.float64)
    keep = grad >= ROUNDOFF_SHARE * np.median(grad)
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss = np.abs(lp - lr) / np.abs(lr)
    return {
        "loss_gap": float(loss[0]),
        "update_gap": worst_leaf_gap(prog["update"], ref["update"], keep),
        "progress_gap": worst_leaf_gap(prog["progress"], ref["progress"],
                                       keep),
        "change_gap": worst_leaf_gap(prog["change"], ref["change"], keep),
        "loss_gap_any_round": float(np.max(loss)),
    }


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct iff all are within
    and finite.  A number the limits file does not name is shown, not
    compared."""
    checks, ok = {}, True
    for name, v in values.items():
        lim = limits.get(name)
        checks[name] = {"value": v, "limit": lim}
        if lim is not None and not (np.isfinite(v) and v <= lim):
            ok = False
    return ok, checks
