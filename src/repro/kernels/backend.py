"""Execution-backend registry for the quantized/attention hot paths.

Three registered backends (DESIGN.md §Backend-registry):

  pallas — the TPU Pallas kernels (fused W8A8 epilogue, flash attention)
  xla    — portable jnp implementations (``kernels.ref``) that XLA fuses;
           the default off-TPU and the correctness oracle everywhere
  ref    — the Pallas kernels in interpret mode: exercises the real kernel
           logic (grids, padding, epilogues) on any platform, for tests

Selection order: explicit ``set_backend()`` > ``REPRO_BACKEND`` env var >
platform default (pallas on TPU, xla elsewhere). Backends expose a uniform
primitive surface; ``kernels.ops`` owns the shape plumbing (flattening
leading axes, dynamic activation quant) and dispatches here — model code
never imports a kernel module directly.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Backend:
    """Primitive surface each backend implements.

    quantize_rowwise: (M, K) float -> ((M, K) int8, (M,) f32 scales)
    int8_matmul:      (M, K) int8, (K, N) int8, (M,) f32, (N,) f32 -> (M, N)
    flash_attention:  (B, S, H, hd) q/k/v -> (B, S, H, hd), causal
    decode_attention: (B, Hq, hd) q vs a (B, W, Hkv, hd) slotted KV window
                      (float, or int8 + (B, W, Hkv) f32 scales), (B,) int32
                      per-slot ``start`` -> (B, Hq, hd); the serving decode
                      hot path (split-KV flash decoding on pallas)
    prefill_attention: (B, Sq, Hq, hd) q at absolute positions
                      start..start+Sq-1 vs the same slotted KV window
                      -> (B, Sq, Hq, hd); the serving chunked-prefill hot
                      path (cache-continuation online-softmax kernel on
                      pallas; on xla it IS ``cached_attention_ref`` — the
                      token-identity hinge, exactly how ``decode_attention``
                      landed)
    decode_attention_paged / prefill_attention_paged:
                      the same two primitives against a PAGED KV arena —
                      k/v (n_pages, page_size, Hkv, hd) (scales
                      (n_pages, Hkv*page_size)) plus a (B, n_blk) int32
                      ``pages`` window prefix of each row's page table. On
                      xla: gather-to-contiguous + the contiguous einsum
                      (bit-identity with the contiguous layout by
                      construction); on pallas/ref: the block index maps
                      walk the table via scalar prefetch, no gather ever
                      materializes (DESIGN.md §12)
    """
    name: str
    quantize_rowwise: Callable
    int8_matmul: Callable
    flash_attention: Callable
    decode_attention: Callable
    prefill_attention: Callable
    decode_attention_paged: Callable
    prefill_attention_paged: Callable


_REGISTRY: Dict[str, Backend] = {}
_ACTIVE: Optional[str] = None


def register(backend: Backend) -> Backend:
    _REGISTRY[backend.name] = backend
    return backend


def available() -> tuple:
    return tuple(sorted(_REGISTRY))


def set_backend(name: Optional[str]) -> Optional[str]:
    """Force a backend globally (None = back to auto). Returns the previous
    forced value so tests can restore it.

    Trace-time contract: the backend is resolved when a function is TRACED,
    so functions already jit-compiled keep the backend they were traced
    with — switching here affects new traces only. Flip the backend before
    building jitted steps (or clear jax caches) when A/B-ing backends."""
    global _ACTIVE
    if name is not None and name not in _REGISTRY:
        raise KeyError(f"unknown backend {name!r}; available: {available()}")
    prev, _ACTIVE = _ACTIVE, name
    return prev


def get_backend(name: Optional[str] = None) -> Backend:
    name = (name or _ACTIVE or os.environ.get("REPRO_BACKEND")
            or ("pallas" if jax.default_backend() == "tpu" else "xla"))
    if name not in _REGISTRY:
        raise KeyError(f"unknown backend {name!r}; available: {available()}")
    return _REGISTRY[name]


# ------------------------------------------------------------------ xla
def _xla_backend() -> Backend:
    from repro.kernels import ref
    return Backend(
        name="xla",
        quantize_rowwise=lambda x: ref.quantize_ref(x, axis=-1),
        int8_matmul=lambda x_q, w_q, x_s, w_s: ref.int8_matmul_ref(
            x_q, w_q, w_s, x_s),
        flash_attention=lambda q, k, v: ref.flash_attention_ref(
            q, k, v, causal=True),
        decode_attention=ref.decode_attention_ref,
        # verbatim the masked einsum: serial prefill, chunked engine prefill,
        # and the Sq=1 decode slice all share one set of numerics bit-for-bit
        prefill_attention=ref.cached_attention_ref,
        decode_attention_paged=ref.paged_decode_attention_ref,
        prefill_attention_paged=ref.paged_prefill_attention_ref,
    )


# ------------------------------------------------------------------ pallas
def _fold_heads(fn):
    """(B, S, H, hd) <-> (B*H, S, hd) adapter around the Pallas flash kernel
    (equal q/kv heads; GQA folded by the caller)."""
    def wrapped(q, k, v):
        b, s, h, hd = q.shape
        fold = lambda t: jnp.moveaxis(t, 2, 1).reshape(b * h, s, hd)
        o = fn(fold(q), fold(k), fold(v))
        return jnp.moveaxis(o.reshape(b, h, s, hd), 1, 2)
    return wrapped


def _pallas_backend(interpret: bool) -> Backend:
    from repro.kernels.decode_attention import (decode_attention_pallas,
                                                paged_decode_attention_pallas)
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.int8_matmul import int8_matmul_pallas
    from repro.kernels.prefill_attention import (
        paged_prefill_attention_pallas, prefill_attention_pallas)
    from repro.kernels.quantize import quantize_rowwise_pallas
    return Backend(
        name="ref" if interpret else "pallas",
        quantize_rowwise=lambda x: quantize_rowwise_pallas(
            x, interpret=interpret),
        int8_matmul=lambda x_q, w_q, x_s, w_s: int8_matmul_pallas(
            x_q, w_q, x_s, w_s, interpret=interpret),
        flash_attention=_fold_heads(lambda q, k, v: flash_attention_pallas(
            q, k, v, interpret=interpret)),
        decode_attention=lambda q, k, v, k_s, v_s, start:
            decode_attention_pallas(q, k, v, k_s, v_s, start,
                                    interpret=interpret),
        prefill_attention=lambda q, k, v, k_s, v_s, start:
            prefill_attention_pallas(q, k, v, k_s, v_s, start,
                                     interpret=interpret),
        decode_attention_paged=lambda q, k, v, k_s, v_s, start, pages:
            paged_decode_attention_pallas(q, k, v, k_s, v_s, start, pages,
                                          interpret=interpret),
        prefill_attention_paged=lambda q, k, v, k_s, v_s, start, pages:
            paged_prefill_attention_pallas(q, k, v, k_s, v_s, start, pages,
                                           interpret=interpret),
    )


register(_xla_backend())
register(_pallas_backend(interpret=False))
register(_pallas_backend(interpret=True))
