"""Shared building blocks: norms, rotary embeddings, (possibly quantized) dense.

Parameter convention
--------------------
A linear layer's params are either
  full precision : {"w": (in, out) bf16/f32}
  HQP-quantized  : a ``repro.compress.QuantizedLinear`` pytree node
``dense()`` dispatches on *type*, so the same model code runs both the FP
baseline and the HQP INT8 artifact — quantization is a parameter transform,
not a model rewrite. This mirrors the paper's "output is a standard model"
property; see DESIGN.md §Compression-artifact.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.compress.qtypes import (QuantizedLinear, linear_bytes,
                                   linear_kernel, out_features)
from repro.kernels import ops as kops

# re-exported for model code that types against the layers namespace
__all__ = ["QuantizedLinear", "linear_bytes", "linear_kernel",
           "out_features"]

COMPUTE_DTYPE = jnp.bfloat16


# ---------------------------------------------------------------- init utils
def he_init(key, shape, dtype=jnp.float32, fan_in: Optional[int] = None):
    fan = fan_in if fan_in is not None else shape[0]
    return (jax.random.normal(key, shape) * (2.0 / fan) ** 0.5).astype(dtype)


def linear_init(key, d_in, d_out, dtype=COMPUTE_DTYPE):
    return {"w": he_init(key, (d_in, d_out), dtype)}


# ---------------------------------------------------------------- dense
def dense(x: jax.Array, p, out_dtype=None) -> jax.Array:
    """Matmul dispatch: FP weight dict, or a typed ``QuantizedLinear``.

    ``out_dtype``: the FP path's result type (None: the compute dtype); the
    INT8 kernel returns its own.

    The INT8 path intentionally keeps the weight int8 in HLO (bytes halve in
    the roofline memory term); dequant is folded into the matmul epilogue by
    scaling the int32/f32 accumulator — never materializing an FP weight.
    Which kernel runs is the execution backend's choice
    (``kernels.backend``): fused Pallas on TPU, XLA-fused jnp elsewhere.
    """
    if isinstance(p, QuantizedLinear):
        return kops.int8_matmul(x, p.w_q, p.scale)
    w = p["w"]
    return jnp.dot(x.astype(COMPUTE_DTYPE), w.astype(COMPUTE_DTYPE),
                   preferred_element_type=out_dtype)


def dense_param_bytes(p) -> int:
    return linear_bytes(p)


# ---------------------------------------------------------------- norms
def rmsnorm_init(d: int):
    return {"g": jnp.ones((d,), jnp.float32)}


def rmsnorm(x: jax.Array, p: dict, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["g"]
    return y.astype(COMPUTE_DTYPE)


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head qk-norm (qwen3 style), no learned scale on the head axis."""
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
            ).astype(COMPUTE_DTYPE)


# ---------------------------------------------------------------- rotary
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                        # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                  # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(COMPUTE_DTYPE)


# ---------------------------------------------------------------- embedding
def embed_init(key, vocab: int, d: int, dtype=COMPUTE_DTYPE):
    return {"table": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


def embed_lookup(p: dict, tokens: jax.Array) -> jax.Array:
    return p["table"][tokens].astype(COMPUTE_DTYPE)


def unembed(p: dict, x: jax.Array) -> jax.Array:
    """Logits in f32 for a stable softmax/loss."""
    return jnp.dot(x.astype(COMPUTE_DTYPE), p["table"].T.astype(COMPUTE_DTYPE)
                   ).astype(jnp.float32)


# ---------------------------------------------------------------- MLP (SwiGLU)
def mlp_init(key, d_model: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": linear_init(k1, d_model, d_ff),
        "up": linear_init(k2, d_model, d_ff),
        "down": linear_init(k3, d_ff, d_model),
    }


def mlp(x: jax.Array, p: dict) -> jax.Array:
    return dense(jax.nn.silu(dense(x, p["gate"])) * dense(x, p["up"]), p["down"])
