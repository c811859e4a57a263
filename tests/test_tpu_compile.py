"""Every serving-path kernel, and one whole engine prefill and decode step,
compiled for a described TPU v5e at qwen3-0.6b's published widths.

Nothing runs: the TPU compiler installed with jax compiles for a chip that
is described, not attached, and refuses what the chip would refuse (a block
shape that breaks the (8, 128) tiling rule, a 1-D block whose layout Mosaic
and XLA disagree on, a program that does not fit HBM). Interpret-mode tests
cannot see any of that. The topology is described inside a fixture, never
at import, so every pytest-xdist worker collects the same tests and only
the worker given this file loads the TPU library."""
import dataclasses
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs

HBM_BYTES = 16e9                         # one v5e chip
CFG = configs.get_config("qwen3-0.6b")
HQ, HKV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.resolved_head_dim
B, W, PS, SQ = 4, 1024, 16, 64           # slots, window, page size, chunk
N_PAGES = 1 + B * W // PS                # full provisioning + trash page


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _check(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text()   # compiled Pallas, not
    ma = compiled.memory_analysis()                  # interpret mode
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes) < HBM_BYTES


def _compile(fn, args) -> None:
    _check(jax.jit(fn).lower(*args).compile())


def _kv(spec, lead, length, kind, paged=False):
    """k, v, k_s, v_s shape specs: bf16 (contiguous), uint16 (a paged
    arena's raw bf16 words) or int8 with f32 per-(pos, head) scales, which
    a paged arena keeps as one (Hkv*page_size) row per page."""
    shape = (lead, length, HKV, HD)
    if kind == "int8":
        s = (lead, HKV * length) if paged else shape[:3]
        return (spec(shape, jnp.int8), spec(shape, jnp.int8),
                spec(s, jnp.float32), spec(s, jnp.float32))
    dt = jnp.uint16 if kind == "uint16" else jnp.bfloat16
    return spec(shape, dt), spec(shape, dt), None, None


@pytest.mark.parametrize("sq", [1, SQ], ids=["decode", "prefill"])
@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_cache_attention_compiles(one_chip, sq, paged, int8):
    from repro.kernels.decode_attention import (
        decode_attention_pallas, paged_decode_attention_pallas)
    from repro.kernels.prefill_attention import (
        paged_prefill_attention_pallas, prefill_attention_pallas)
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    q = spec((B, HQ, HD) if sq == 1 else (B, sq, HQ, HD), jnp.bfloat16)
    start = spec((B,), jnp.int32)
    if paged:
        kv = _kv(spec, N_PAGES, PS, "int8" if int8 else "uint16", paged)
        fn = (paged_decode_attention_pallas if sq == 1
              else paged_prefill_attention_pallas)
        _compile(fn, (q, *kv, start, spec((B, W // PS), jnp.int32)))
    else:
        kv = _kv(spec, B, W, "int8" if int8 else "bf16")
        fn = decode_attention_pallas if sq == 1 else prefill_attention_pallas
        _compile(fn, (q, *kv, start))


@pytest.mark.parametrize("m", [4, 64])
def test_int8_matmul_compiles(one_chip, m):
    from repro.kernels.int8_matmul import int8_matmul_pallas
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)
    d, f = CFG.d_model, CFG.d_ff
    _compile(int8_matmul_pallas,
             (spec((m, d), jnp.int8), spec((d, f), jnp.int8),
              spec((m,), jnp.float32), spec((f,), jnp.float32)))


@pytest.mark.parametrize("m", [4, 512])
def test_quantize_rowwise_compiles(one_chip, m):
    from repro.kernels.quantize import quantize_rowwise_pallas
    _compile(quantize_rowwise_pallas,
             (jax.ShapeDtypeStruct((m, CFG.d_model), jnp.bfloat16,
                                   sharding=one_chip),))


def test_kernel_operations_keep_their_names(one_chip):
    """Inside a step, each Pallas kernel's operation is named after the
    kernel (its ``pallas_call`` ``name=``), which is how a profile of the
    chip tells the kernels apart."""
    import re
    from repro.kernels.int8_matmul import int8_matmul_pallas
    from repro.kernels.prefill_attention import paged_prefill_attention_pallas
    from repro.kernels.quantize import quantize_rowwise_pallas
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)

    def step(x, w, ws, q, k, v, ks, vs, start, pages):
        xq, xs = quantize_rowwise_pallas(x)
        y = int8_matmul_pallas(xq, w, xs, ws)
        return y, paged_prefill_attention_pallas(q, k, v, ks, vs, start,
                                                 pages)

    d, f = CFG.d_model, CFG.d_ff
    args = (spec((B, d), jnp.bfloat16), spec((d, f), jnp.int8),
            spec((f,), jnp.float32), spec((B, 1, HQ, HD), jnp.bfloat16),
            *_kv(spec, N_PAGES, PS, "int8", paged=True),
            spec((B,), jnp.int32),
            spec((B, W // PS), jnp.int32))
    text = jax.jit(step).lower(*args).compile().as_text()
    calls = {m.group(1).split(".")[0] for m in re.finditer(
        r"%?([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)}
    assert calls == {"paged_prefill_attention_pallas", "int8_matmul_pallas",
                     "quantize_rowwise_pallas"}, calls


_ENGINE_STEPS: dict = {}


def _engine_step(one_chip, step, hqp):
    """(engine, compiled step): one whole paged engine step (28-layer scan)
    on the ``pallas`` backend, compiled once for every test that reads it.
    The backend is resolved when a function is traced, and off the chip it
    would resolve to ``xla`` — so this steers it itself."""
    if (step, hqp) in _ENGINE_STEPS:
        return _ENGINE_STEPS[step, hqp]
    from repro.analysis.hlo_checks import engine_hot_paths
    from repro.compress.quantize import quantize_lm_params
    from repro.kernels.backend import set_backend
    from repro.models import lm
    from repro.serving import Engine
    from repro.sharding.ctx import default_ctx

    params = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                   CFG))
    if hqp:
        params = jax.eval_shape(quantize_lm_params, params)
    ctx = dataclasses.replace(default_ctx(), quantized_kv=hqp)
    prev = set_backend("pallas")
    try:
        eng = Engine(params, CFG, ctx=ctx, n_slots=B, max_seq=256,
                     page_size=PS)
        fn, args = engine_hot_paths(eng)[step]
        on_chip = lambda t: jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), t)
        compiled = fn.lower(*[a if isinstance(a, int) else on_chip(a)
                              for a in args]).compile()
    finally:
        set_backend(prev)
    _ENGINE_STEPS[step, hqp] = eng, compiled
    return eng, compiled


@pytest.mark.parametrize("hqp", [False, True], ids=["bf16", "hqp_int8"])
@pytest.mark.parametrize("step", ["engine.prefill", "engine.decode"])
def test_engine_step_compiles(one_chip, step, hqp):
    _check(_engine_step(one_chip, step, hqp)[1])


# one HLO instruction: `[ROOT] %name = dtype[dims]{layout} opcode(operands`
_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s+=\s+(\w+)\[([\d,]*)\]\S*"
                        r"\s+([\w\-]+)\((.*)$")
_HLO_OPERAND = re.compile(r"(\w+)\[([\d,]*)\]\S*\s+%")


def _arena_moves(hlo_text, pool):
    """Copies, slices and write-backs of a paged KV arena in optimized HLO
    (fusion bodies included): a ``copy`` or ``dynamic-slice`` whose result
    is a stacked KV leaf, one layer of it, or the stack's merged
    (groups*n_pages, ...) view, and a ``dynamic-update-slice`` that writes
    such a block back; shapes compare with unit dims dropped (a layer sliced
    out of the stack is (1, n_pages, ...)). The in-place per-token write is
    a ``scatter``."""
    from repro.serving import state_pool as sp
    dt = {"int8": "s8", "uint16": "u16", "float32": "f32", "bfloat16": "bf16"}
    arena = set()
    for entry in pool["caches"]:
        if sp.is_kv_entry(entry):
            for leaf in jax.tree.leaves(entry):
                d, s = dt[leaf.dtype.name], tuple(leaf.shape)
                arena |= {(d, tuple(x for x in t if x != 1))
                          for t in (s, s[1:], (s[0] * s[1],) + s[2:])}
    dims = lambda text: tuple(int(x) for x in text.split(",")
                              if x and x != "1")
    moves = []
    for line in hlo_text.splitlines():
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        d, shape, op, rest = m.group(1), dims(m.group(2)), m.group(3), m.group(4)
        if op in ("copy", "dynamic-slice") and (d, shape) in arena:
            moves.append(line.strip()[:160])
        elif op == "dynamic-update-slice":
            upd = _HLO_OPERAND.findall(rest)[1:2]
            if upd and (upd[0][0], dims(upd[0][1])) in arena:
                moves.append(line.strip()[:160])
    return moves


@pytest.mark.parametrize("hqp", [False, True], ids=["bf16", "hqp_int8"])
@pytest.mark.parametrize("step", ["engine.prefill", "engine.decode"])
def test_engine_step_moves_no_arena(one_chip, step, hqp):
    """The KV arena rides in the layer scan's carry and is written in place
    by layer index: the compiled step holds no copy, slice or write-back of
    any stacked KV leaf (uint16 K/V, or INT8 K/V and their f32 scales) nor
    of one layer's arena."""
    eng, compiled = _engine_step(one_chip, step, hqp)
    text = compiled.as_text()
    assert "scatter(" in text                  # the in-place write is there
    assert _arena_moves(text, eng.pool) == []


def test_chip_smoke_refuses_a_host_without_tpu():
    """``chip_smoke.py`` is the chip run: on a CPU-only JAX it exits
    non-zero, names the platform it found, and prints no result line."""
    root = pathlib.Path(__file__).resolve().parents[1]
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout
