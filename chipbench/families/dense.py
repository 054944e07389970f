"""The dense decoder: pre-norm blocks of grouped-query attention with
rotate-half RoPE and an MLP (silu-gated, else plain tanh-GELU), RMSNorm or
LayerNorm, a tied or untied head.  Keys are the published config.json's.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.counts import padded_vocab
from chipbench.families import ein, mm

#: keys of a configuration file that the program's ArchConfig takes, by the
#: name the published config.json gives them
_ARCH_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
}

#: the tensor-parallel axis of each weight, by leaf name: sparse_gather
#: selects per row along it; a leaf not named here is one row
ROW_AXIS = {"wq": -1, "wk": -1, "wv": -1, "bq": -1, "bk": -1, "bv": -1,
            "w_gate": -1, "w_up": -1, "b_up": -1, "lm_head": -1,
            "wo": -2, "w_down": -2, "embed": 0}

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, vocab_size=512)

LOSS_CHUNK = 512


def arch_kwargs(config: dict) -> dict:
    return {"arch_type": "dense"} | {
        ours: config[theirs] for theirs, ours in _ARCH_KEYS.items()}


def row_axis(path: str, ndim: int) -> int | None:
    return ROW_AXIS.get(path.split("/")[-1])


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def matmul_params(config: dict) -> int:
    """N of PaLM's count: every weight an activation multiplies."""
    d, f = config["hidden_size"], config["intermediate_size"]
    nh, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // nh
    attn = 2 * d * nh * hd + 2 * d * nkv * hd
    mlp = (3 if config["hidden_act"] == "silu" else 2) * d * f
    head = d * padded_vocab(config["vocab_size"])
    return config["num_hidden_layers"] * (attn + mlp) + head


def model_flops_per_token(config: dict, seq: int) -> int:
    n_l, nh = config["num_hidden_layers"], config["num_attention_heads"]
    hd = config["hidden_size"] // nh
    return 6 * matmul_params(config) + 12 * n_l * nh * hd * seq


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

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
    q, k, v = (mm(h, a[w], precision) + a.get(bias, 0.0)
               for w, bias in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
    q = _rope(q.reshape(b, s, m.n_heads, m.head_dim), m.rope_theta)
    k = _rope(k.reshape(b, s, m.n_kv, m.head_dim), m.rope_theta)
    v = v.reshape(b, s, m.n_kv, m.head_dim)
    rep = m.n_heads // m.n_kv          # query head j reads kv head j // rep
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = ein("bqhd,bkhd->bhqk", q, k, precision) / np.sqrt(m.head_dim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    o = ein("bhqk,bkhd->bqhd", probs, v, precision).reshape(b, s, -1)
    x = x + mm(o, a["wo"], precision)
    h = _norm(x, bp["norm2"], m)
    f = bp["mlp"]
    if m.gated:
        y = mm(jax.nn.silu(mm(h, f["w_gate"], precision))
               * mm(h, f["w_up"], precision), f["w_down"], precision)
    else:
        y = mm(jax.nn.gelu(mm(h, f["w_up"], precision) + f.get("b_up", 0.0),
                           approximate=True),
               f["w_down"], precision) + f.get("b_down", 0.0)
    return x + y


def loss_fn(pf, tokens, labels, config: dict, precision: str = "f32"):
    """Mean next-token cross-entropy over every position, float32."""
    m = Model.from_config(config)
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
        logits = mm(xc, head, precision)
        gold = jnp.take_along_axis(logits, yc[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    xs = jnp.swapaxes(x.reshape(b, s // c, c, d), 0, 1)
    ys = jnp.swapaxes(labels.reshape(b, s // c, c), 0, 1)
    total, _ = jax.lax.scan(lambda t, xy: (t + chunk_nll(*xy), None),
                            jnp.float32(0.0), (xs, ys))
    return total / (b * s)
