"""Plain reference of one LGC sync round, and the comparison that decides
``correct``.  It imports nothing of the program.

The round (the paper's Algorithm 1 as the configuration and traffic state
it): every FL device takes H local SGD steps from the server weights, each
on its own slice of the batch; its net progress plus its error memory is
split into C layers by magnitude; the layers whose channel delivered are
sent, the rest stay in the error memory; the server subtracts the mean of
what was sent.  Weights are stored in the dtype the configuration states
(bfloat16); each local step computes in float32 at ``highest`` matmul
precision and stores the stepped weights back in that dtype.

Layer selection is the histogram rule the configuration's uplink names:
the thresholds are edges of a 256-bin histogram of ``|u|`` over
``[0, max|u|]``, channel c keeping ``thr[c-1] >= |u| > thr[c]``, where
``thr[c]`` is the highest edge with at least ``k_1 + ... + k_c`` elements
above it (``k_c = max(1, int(n * f_c))``, clamped so the budgets never
exceed ``n``).  ``dense_masked`` selects over each whole tensor;
``sparse_gather`` selects per row along the tensor-parallel axis and keeps
at most ``k_c + max(1, n // 256)`` of a band's largest elements per row.

``precision="fp8"`` casts every matmul operand to float8_e4m3fn (the
control), and ``half_batch`` trains each local step on half its
sequences (a planted fault); neither is used by a benchmark run.
"""
from __future__ import annotations

import dataclasses
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.feed import for_cell as feed_for_cell

HIGHEST = jax.lax.Precision.HIGHEST
N_BINS = 256
LOSS_CHUNK = 512

#: the tensor-parallel axis of each weight, by leaf name: sparse_gather
#: selects per row along it; a leaf not named here is one row
ROW_AXIS = {"wq": -1, "wk": -1, "wv": -1, "bq": -1, "bk": -1, "bv": -1,
            "w_gate": -1, "w_up": -1, "b_up": -1, "lm_head": -1,
            "wo": -2, "w_down": -2, "embed": 0}


@dataclasses.dataclass(frozen=True)
class Model:
    n_heads: int
    n_kv: int
    head_dim: int
    rope_theta: float
    gated: bool          # silu-gated MLP (else a plain tanh-GELU MLP)
    rms: bool            # RMSNorm (else LayerNorm)
    eps: float
    tied: bool

    @classmethod
    def from_config(cls, c: dict) -> "Model":
        rms = "rms_norm_eps" in c
        return cls(n_heads=c["num_attention_heads"],
                   n_kv=c["num_key_value_heads"],
                   head_dim=c["hidden_size"] // c["num_attention_heads"],
                   rope_theta=float(c["rope_theta"]),
                   gated=c["hidden_act"] == "silu", rms=rms,
                   eps=c["rms_norm_eps"] if rms else c["norm_epsilon"],
                   tied=bool(c["tie_word_embeddings"]))


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def _q(x, precision: str):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(a, b, precision):
    return jnp.matmul(_q(a, precision), _q(b, precision), precision=HIGHEST)


def _ein(spec, a, b, precision):
    return jnp.einsum(spec, _q(a, precision), _q(b, precision),
                      precision=HIGHEST)


def _norm(x, p, m: Model):
    if m.rms:
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + m.eps) * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + m.eps) * p["scale"] + p["bias"]


def _rope(x, theta):
    """Rotate-half RoPE at positions 0..S-1; x: (B, S, heads, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, bp, m: Model, precision):
    b, s, _ = x.shape
    a = bp["attn"]
    h = _norm(x, bp["norm1"], m)
    q, k, v = (_mm(h, a[w], precision) + a.get(bias, 0.0)
               for w, bias in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
    q = _rope(q.reshape(b, s, m.n_heads, m.head_dim), m.rope_theta)
    k = _rope(k.reshape(b, s, m.n_kv, m.head_dim), m.rope_theta)
    v = v.reshape(b, s, m.n_kv, m.head_dim)
    rep = m.n_heads // m.n_kv          # query head j reads kv head j // rep
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = _ein("bqhd,bkhd->bhqk", q, k, precision) / np.sqrt(m.head_dim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    o = _ein("bhqk,bkhd->bqhd", probs, v, precision).reshape(b, s, -1)
    x = x + _mm(o, a["wo"], precision)
    h = _norm(x, bp["norm2"], m)
    f = bp["mlp"]
    if m.gated:
        y = _mm(jax.nn.silu(_mm(h, f["w_gate"], precision))
                * _mm(h, f["w_up"], precision), f["w_down"], precision)
    else:
        y = _mm(jax.nn.gelu(_mm(h, f["w_up"], precision) + f.get("b_up", 0.0),
                            approximate=True),
                f["w_down"], precision) + f.get("b_down", 0.0)
    return x + y


def loss_fn(pf, tokens, labels, m: Model, precision: str = "f32"):
    """Mean next-token cross-entropy over every position, float32."""
    x = pf["embed"][tokens]
    layer = jax.checkpoint(functools.partial(_layer, m=m,
                                             precision=precision))
    x, _ = jax.lax.scan(lambda c, bp: (layer(c, bp), None), x, pf["blocks"])
    x = _norm(x, pf["final_norm"], m)
    head = pf["embed"].T if m.tied else pf["lm_head"]
    b, s, d = x.shape
    c = min(LOSS_CHUNK, s)

    @jax.checkpoint
    def chunk_nll(xc, yc):
        logits = _mm(xc, head, precision)
        gold = jnp.take_along_axis(logits, yc[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    xs = jnp.swapaxes(x.reshape(b, s // c, c, d), 0, 1)
    ys = jnp.swapaxes(labels.reshape(b, s // c, c), 0, 1)
    total, _ = jax.lax.scan(lambda t, xy: (t + chunk_nll(*xy), None),
                            jnp.float32(0.0), (xs, ys))
    return total / (b * s)


@functools.partial(jax.jit, static_argnames=("m", "precision", "lr"))
def local_step(p, tokens, labels, *, m: Model, precision: str, lr: float):
    """One local SGD step: (loss, stepped weights, per-leaf |grad|)."""
    pf = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)
    loss, g = jax.value_and_grad(loss_fn)(pf, tokens, labels, m, precision)
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


def compress_leaf(name: str, w0, w_end, e, recv, sparsity, uplink: str):
    """(sent, new error memory) of one leaf, float32, shaped like ``w0``."""
    u = (w0.astype(jnp.float32) - w_end.astype(jnp.float32)
         + (0.0 if e is None else e))
    ax = ROW_AXIS.get(name.split("/")[-1]) if uplink == "sparse_gather" \
        else None
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

def _round(params, names, ef, tokens, labels, recv, *, cell, m: Model,
           precision: str, half_batch: bool):
    """One sync round. ``ef``: per device a list of host leaves (None: 0)."""
    t = cell.traffic
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
                                     jnp.asarray(labels[lo:hi]), m=m,
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
            sent, e_new = compress_leaf(name, w0[k], ends[k], e, r,
                                        tuple(t["channel_sparsity"]),
                                        t["uplink"])
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
    m = Model.from_config(cell.config)
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
            params, names, ef, x, y, masks[r], cell=cell, m=m,
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
