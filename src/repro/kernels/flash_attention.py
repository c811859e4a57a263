"""Pallas TPU kernel: causal flash attention (online softmax, block-skipping).

Grid (B*H, Sq/bq, Skv/bk) with the KV dimension innermost; the (m, l, acc)
accumulators live in VMEM scratch across the KV loop. Causality is exploited
structurally: KV blocks strictly above the diagonal contribute nothing and are
skipped via ``pl.when`` — on TPU the grid still visits them, but no MXU work
or HBM traffic for the block is issued (unlike the XLA path, which multiplies
the masked half anyway). GQA is handled by the caller (q heads grouped).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, n_kv: int, scale: float):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki * bk <= qi * bq + bq - 1)          # block intersects causal tri
    def _compute():
        q = q_ref[0]                                # (bq, hd)
        k = k_ref[0]                                # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p.astype(v_ref.dtype), v_ref[0],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           bq: int = 512, bk: int = 512,
                           interpret: bool = False) -> jax.Array:
    """q, k, v: (BH, S, hd) — heads already folded into batch. Causal only."""
    bh, s, hd = q.shape
    bq, bk = min(bq, s), min(bk, s)
    assert s % bq == 0 and s % bk == 0
    scale = hd ** -0.5
    n_kv = s // bk
    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, n_kv=n_kv, scale=scale),
        grid=(bh, s // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out
