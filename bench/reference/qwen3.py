"""Plain reference of the Qwen3 decoder, in float32, for the benchmark.

Follows the published Qwen3 description (hf:Qwen/Qwen3-0.6B): token
embedding; per layer pre-RMSNorm, grouped-query attention with per-head
RMSNorm of q and k (the learned scales are all ones at initialisation, so
none are stored), rotary embedding on the two halves of each head, causal
softmax; pre-RMSNorm SwiGLU MLP; final RMSNorm; logits from the tied
embedding. It imports nothing of the program under test. It reads the
weights the benchmark made (``bench/weights.py``), in the benchmark's
layout, and computes everything itself.

The HQP configuration is the same function after two transforms that the
configuration states, and that this module applies itself:

* structural pruning: whole KV-head groups (their q heads, k, v and o
  slices) and whole MLP channels (gate and up columns, down row) are
  zeroed, chosen by ``prune_units`` from the diagonal Fisher information
  on a calibration batch (HQP's Algorithm 1: units ranked by ascending
  sensitivity across all layers, dropped ``step_frac`` of all units at a
  time while the accuracy drop stays within ``delta_ax``);
* symmetric integer quantisation: each linear weight per output channel,
  each linear's input per row (token), and keys and values per (position,
  head), at the bit widths of ``Precision``.

Every matrix product runs at ``jax.default_matmul_precision("highest")``.
Departures from the published model: none in the equations; the
vocabulary rows past ``vocab_size`` that the served embedding table pads
with are never read.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 512          # queries per attention block (bounds score memory)


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    eps: float
    rope_theta: float

    @classmethod
    def from_hf(cls, m: dict) -> "Dims":
        return cls(m["num_hidden_layers"], m["hidden_size"],
                   m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"], m["intermediate_size"], m["vocab_size"],
                   m["rms_norm_eps"], float(m["rope_theta"]))


@dataclasses.dataclass(frozen=True)
class Precision:
    """Bit widths of the integer paths; None keeps float32."""
    weights: Optional[int] = None
    acts: Optional[int] = None
    kv: Optional[int] = None


FLOAT = Precision()


# ------------------------------------------------------------ quantisation
def quantize(x, bits: int, axis):
    """Symmetric quantise-dequantise: scale = max|x| / (2^(bits-1) - 1)
    over ``axis``, round to nearest, clip."""
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def linear(x, w, prec: Precision):
    """x (T, K) @ w (K, N) with the precision's weight and input paths."""
    if prec.weights is not None:
        w = quantize(w, prec.weights, axis=0)
    if prec.acts is not None:
        x = quantize(x, prec.acts, axis=-1)
    return x @ w


# ------------------------------------------------------------ the model
def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def rope(x, pos, theta):
    """x (T, H, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v):
    """Causal GQA: q (T, Hq, hd), k/v (T, Hkv, hd); q head h reads KV
    head h // (Hq / Hkv). Computed in blocks of Q_BLOCK queries."""
    t, hq, hd = q.shape
    g = hq // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    bq = min(Q_BLOCK, t)
    n_blk = -(-t // bq)
    qp = jnp.pad(q, ((0, n_blk * bq - t), (0, 0), (0, 0)))

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * bq, bq) * hd ** -0.5
        s = jnp.einsum("qhd,khd->hqk", qb, k)
        qpos = i * bq + jnp.arange(bq)
        s = jnp.where(jnp.arange(t)[None, None, :] <= qpos[None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(n_blk))
    return out.reshape(n_blk * bq, hq, hd)[:t]


def layer_weights(params):
    """The stacked per-layer weights of the benchmark's layout, float32."""
    b = params["blocks"][0]
    a, m = b["attn"], b["mlp"]
    return {"g1": b["norm1"]["g"], "g2": b["norm2"]["g"],
            "wq": a["wq"]["w"], "wk": a["wk"]["w"], "wv": a["wv"]["w"],
            "wo": a["wo"]["w"], "gate": m["gate"]["w"], "up": m["up"]["w"],
            "down": m["down"]["w"]}


def apply_masks(w: dict, keep_heads, keep_ff, dims: Dims) -> dict:
    """Zero pruned units in one layer's weights. keep_heads (Hkv,) and
    keep_ff (d_ff,) are 0/1."""
    g, hd = dims.heads // dims.kv_heads, dims.head_dim
    qcol = jnp.repeat(keep_heads, g * hd)
    kvcol = jnp.repeat(keep_heads, hd)
    return dict(w, wq=w["wq"] * qcol, wk=w["wk"] * kvcol,
                wv=w["wv"] * kvcol, wo=w["wo"] * qcol[:, None],
                gate=w["gate"] * keep_ff, up=w["up"] * keep_ff,
                down=w["down"] * keep_ff[:, None])


def hidden(params, dims: Dims, tokens, prec: Precision, keep=None):
    """Final normed hidden states (T, d) of one sequence."""
    x = params["embed"]["table"][tokens].astype(F32)
    pos = jnp.arange(tokens.shape[0])
    ws = {k: v.astype(F32) for k, v in layer_weights(params).items()}
    if keep is None:
        keep = (jnp.ones((dims.layers, dims.kv_heads), F32),
                jnp.ones((dims.layers, dims.d_ff), F32))

    def body(x, xs):
        w, kh, kf = xs
        w = apply_masks(w, kh, kf, dims)
        h = rms_norm(x, w["g1"], dims.eps)
        q = linear(h, w["wq"], prec).reshape(-1, dims.heads, dims.head_dim)
        k = linear(h, w["wk"], prec).reshape(-1, dims.kv_heads,
                                             dims.head_dim)
        v = linear(h, w["wv"], prec).reshape(-1, dims.kv_heads,
                                             dims.head_dim)
        q = rope(rms_norm(q, 1.0, dims.eps), pos, dims.rope_theta)
        k = rope(rms_norm(k, 1.0, dims.eps), pos, dims.rope_theta)
        if prec.kv is not None:
            k, v = quantize(k, prec.kv, -1), quantize(v, prec.kv, -1)
        o = attention(q, k, v).reshape(-1, dims.heads * dims.head_dim)
        x = x + linear(o, w["wo"], prec)
        h = rms_norm(x, w["g2"], dims.eps)
        a = jax.nn.silu(linear(h, w["gate"], prec)) * linear(h, w["up"],
                                                             prec)
        return x + linear(a, w["down"], prec), None

    x, _ = jax.lax.scan(body, x, (ws, keep[0], keep[1]))
    return rms_norm(x, params["final_norm"]["g"].astype(F32), dims.eps)


def logits(params, dims: Dims, h):
    return h @ params["embed"]["table"][:dims.vocab].astype(F32).T


@functools.partial(jax.jit, static_argnames=("dims", "prec"))
def token_gaps(params, dims: Dims, tokens, at, served, prec: Precision,
               keep=None):
    """For each compared position ``at[i]``: how far the logit of
    ``served[i]`` lies below the best logit there, and the token this
    precision puts first. tokens (T,) is the prompt and the served
    tokens; the logits at position p predict token p + 1."""
    with jax.default_matmul_precision("highest"):
        lg = logits(params, dims, hidden(params, dims, tokens, prec,
                                         keep)[at])
    best = jnp.max(lg, axis=-1)
    own = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
    return best - own, jnp.argmax(lg, axis=-1)


# ------------------------------------------------------------ HQP pruning
@functools.partial(jax.jit, static_argnames=("dims",))
def fisher_diag(params, dims: Dims, tokens):
    """E[g^2] of the mean next-token cross-entropy over a calibration
    batch (B, S), for every float leaf of ``params``."""
    def loss(p):
        def one(seq):
            lg = logits(p, dims, hidden(p, dims, seq, FLOAT)[:-1])
            lse = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, seq[1:, None], axis=-1)[:, 0]
            return jnp.sum(lse - gold)
        return sum(one(s) for s in tokens) / (tokens.shape[0]
                                             * (tokens.shape[1] - 1))

    with jax.default_matmul_precision("highest"):
        grads = jax.grad(loss)(jax.tree.map(lambda t: t.astype(F32), params))
    return jax.tree.map(jnp.square, grads)


@functools.partial(jax.jit, static_argnames=("dims",))
def accuracy(params, dims: Dims, tokens, keep):
    """Next-token top-1 accuracy on a calibration batch (B, S)."""
    with jax.default_matmul_precision("highest"):
        hits = [jnp.argmax(logits(params, dims, hidden(
            params, dims, s, FLOAT, keep)[:-1]), -1) == s[1:]
            for s in tokens]
    return jnp.mean(jnp.stack(hits).astype(F32))


def unit_sensitivity(sq, dims: Dims):
    """Sum of E[g^2] over each unit's weights: (L, Hkv) KV-head groups,
    (L, d_ff) MLP channels. Float64 on the host."""
    w = {k: np.asarray(v, np.float64) for k, v in layer_weights(sq).items()}
    n, g, hd = dims.layers, dims.heads // dims.kv_heads, dims.head_dim
    heads = (w["wq"].reshape(n, dims.d_model, dims.kv_heads, g * hd)
             .sum(axis=(1, 3))
             + w["wk"].reshape(n, dims.d_model, dims.kv_heads, hd)
             .sum(axis=(1, 3))
             + w["wv"].reshape(n, dims.d_model, dims.kv_heads, hd)
             .sum(axis=(1, 3))
             + w["wo"].reshape(n, dims.kv_heads, g * hd, dims.d_model)
             .sum(axis=(2, 3)))
    ff = (w["gate"].sum(axis=1) + w["up"].sum(axis=1)
          + w["down"].sum(axis=2))
    return heads, ff


def prune_units(params, dims: Dims, sq, calib, step_frac: float,
                max_steps: int, delta_ax: float):
    """HQP's conditional prune. Returns (keep_heads (L, Hkv), keep_ff
    (L, d_ff)) as 0/1 float arrays and the number of units dropped."""
    heads, ff = unit_sensitivity(sq, dims)
    s = np.concatenate([heads.ravel(), ff.ravel()])
    order = np.argsort(s, kind="stable")
    total = s.size
    delta = max(1, int(step_frac * total))

    def keep_for(n_drop):
        k = np.ones(total, np.float32)
        k[order[:n_drop]] = 0.0
        return (jnp.asarray(k[:heads.size].reshape(heads.shape)),
                jnp.asarray(k[heads.size:].reshape(ff.shape)))

    base = float(accuracy(params, dims, calib, keep_for(0)))
    best = 0
    for t in range(1, max_steps + 1):
        n_drop = min(t * delta, total)
        if base - float(accuracy(params, dims, calib,
                                 keep_for(n_drop))) > delta_ax:
            break
        best = n_drop
        if n_drop >= total:
            break
    return keep_for(best), best
