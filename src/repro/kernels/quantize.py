"""Pallas TPU kernel: rowwise symmetric INT8 quantization (activation quant).

One pass: read a (bm, K) bf16 tile, compute the row absmax in VMEM, write the
int8 tile + f32 row scales. Fusing quantization this way keeps activation
quant a single HBM round-trip (read 2B/elt, write 1B/elt) in front of the
W8A8 matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0       # (bm, 1)
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def quantize_rowwise_pallas(x: jax.Array, *, bm: int = 256,
                            interpret: bool = False):
    """x: (M, K) float -> ((M, K) int8, (M,) f32 scales). The kernel
    writes the scales as an (M, 1) column (see ``int8_matmul_pallas`` on
    1-D blocks)."""
    m, k = x.shape
    bm = min(bm, m)
    pm = (-m) % bm
    if pm:
        x = jnp.pad(x, ((0, pm), (0, 0)))
    mp = m + pm
    q, s = pl.pallas_call(
        _kernel,
        grid=(mp // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((mp, k), jnp.int8),
                   jax.ShapeDtypeStruct((mp, 1), jnp.float32)],
        interpret=interpret,
        # the kernel's name, and the name of its operation in a profile
        name="quantize_rowwise_pallas",
    )(x)
    return q[:m], s[:m, 0]
