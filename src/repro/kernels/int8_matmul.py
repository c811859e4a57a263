"""Pallas TPU kernel: fused W8A8 matmul with per-channel dequant epilogue.

HBM traffic: activations int8 (pre-quantized or quantized on the fly by the
caller via ``kernels.quantize``), weights int8, output bf16 — the weight
stream halves vs bf16 and the MXU runs in its int8 mode (v5e: 394 TOPS vs 197
TFLOPS). Accumulation is int32 in a VMEM scratch tile; the f32 dequant
(row-scale x col-scale) happens once per output tile in the epilogue — the
dequantized weight matrix is never materialized anywhere.

Grid: (M/bm, N/bn, K/bk), K innermost so the accumulator tile lives in VMEM
across the K loop. Block sizes default to MXU-aligned (128) multiples.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        scale = xs_ref[...] * ws_ref[...]           # (bm, 1) x (1, bn)
        o_ref[...] = (acc * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_matmul_pallas(x_q: jax.Array, w_q: jax.Array, x_scale: jax.Array,
                       w_scale: jax.Array, *, bm: int = 256, bn: int = 256,
                       bk: int = 512, interpret: bool = False) -> jax.Array:
    """x_q: (M, K) int8; w_q: (K, N) int8; x_scale: (M,); w_scale: (N,).

    The scales enter the kernel as (M, 1) / (1, N) columns and rows: a 1-D
    block has a different tiled layout in Mosaic than XLA gives the array,
    and the TPU compiler refuses the mismatch."""
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        x_q = jnp.pad(x_q, ((0, pm), (0, pk)))
        x_scale = jnp.pad(x_scale, (0, pm))
    if pk or pn:
        w_q = jnp.pad(w_q, ((0, pk), (0, pn)))
        w_scale = jnp.pad(w_scale, (0, pn))
    mp, kp, np_ = m + pm, k + pk, n + pn
    x_scale = x_scale.reshape(mp, 1)
    w_scale = w_scale.reshape(1, np_)
    n_k = kp // bk

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(mp // bm, np_ // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.bfloat16),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        # the kernel's name, and the name of its operation in a profile
        name="int8_matmul_pallas",
    )(x_q, w_q, x_scale, w_scale)
    return out[:m, :n]
