"""Compiled-plane invariant checks (analysis plane 1).

Each check lowers a declared hot path with representative abstract shapes
and walks the optimized HLO — the artifact that actually runs — instead
of trusting the source graph (HALP's argument, applied to our own stack):

  f32-roundtrip   no bf16 cache write lowered through an f32
                  ``dynamic-update-slice``/``scatter`` sandwich. This is
                  DESIGN.md §12 as a detector: XLA CPU float-normalization
                  rewrites bf16 stores through f32 converts, which
                  materializes a copy of the WHOLE arena on every write
                  (~4.8µs/page before PR 6/8 fixed it by storing raw
                  uint16 words). Matching is by result element count of
                  the protected cache leaves, not exact dims — the write
                  paths reshape the arena (``merge_layers`` merges the
                  layer stack's leading dims) but never change its size,
                  while every non-pathological f32 tensor in these
                  programs is activation-sized, orders of magnitude
                  smaller than an arena.
  donation        every leaf of a declared-donated argument appears in
                  the executable's ``input_output_alias`` map. A donation
                  silently dropped (dtype drift, an accidental copy)
                  doubles peak KV memory without failing any test.
  host-syncs      the count of host boundary ops (infeed/outfeed/send/
                  recv/host-callback custom-calls) inside the compiled
                  body is ``declared - 1``: fetching the dispatch result
                  is always one sync, and the body must not hide more.
  retrace-budget  after a scripted workload, the number of distinct
                  compiled variants of each hot path stays within the
                  declared window-bucketing bound (``max_seq /
                  SchedulerConfig.window_block``) — the guard against a
                  dynamic shape sneaking into a static argument.

Scenarios cover the KV matrix the engine actually serves: {bf16, INT8 KV}
x {contiguous, paged}, plus the speculative dual-pool path.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import hlo_core
from .invariants import REGISTRY, InvariantSpec, spec_of
from .report import Violation

# opcodes that cross the host boundary; host callbacks lower to
# custom-calls whose target names the python callback trampoline
HOST_BOUNDARY_OPCODES = ("infeed", "outfeed", "send", "recv")
HOST_CALLBACK_MARKERS = ("python_cpu_callback", "python_gpu_callback",
                         "callback_custom_call", "xla_ffi_python")

CACHE_WRITE_OPCODES = ("dynamic-update-slice", "scatter")


def _elem_count(dims: Tuple[int, ...]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


# --------------------------------------------------------- low-level checks
def f32_roundtrip_violations(hlo_text: str,
                             protected_counts: Sequence[int]) -> List[str]:
    """f32 cache-write instructions whose result is exactly the size of a
    protected (non-f32) cache leaf — the §12 float-normalization sandwich.
    ``protected_counts``: element counts of every cache leaf that must
    never round-trip through f32 (bf16/uint16/int8 storage)."""
    protected = set(protected_counts)
    out = []
    for ins in hlo_core.parse_instructions(hlo_text):
        if (ins.opcode in CACHE_WRITE_OPCODES and ins.dtype == "f32"
                and _elem_count(ins.dims) in protected and ins.dims):
            out.append(
                f"f32 {ins.opcode} over a protected cache buffer "
                f"(shape f32[{','.join(map(str, ins.dims))}] in "
                f"%{ins.computation}): bf16 storage is round-tripping "
                f"through float-normalization — store raw uint16 words "
                f"instead (kernels.kv_layout.to_store)")
    return out


def donation_violations(hlo_text: str,
                        expected_shapes: Sequence[str]) -> List[str]:
    """Donated leaves with no matching entry in ``input_output_alias``.

    ``expected_shapes``: one canonical ``dtype[dims]`` string per donated
    leaf (multiplicity matters — a pool with two u16[...] KV leaves needs
    two aliased u16[...] params). Matching is by shape rather than param
    number because jit's ``keep_unused=False`` default prunes unused
    arguments from the executable, shifting every later param number."""
    params = hlo_core.parse_entry_params(hlo_text)
    aliased: Dict[str, int] = {}
    for p in hlo_core.aliased_param_numbers(hlo_text):
        if p < len(params):
            aliased[params[p]] = aliased.get(params[p], 0) + 1
    out = []
    for shape in expected_shapes:
        if aliased.get(shape, 0) > 0:
            aliased[shape] -= 1
        else:
            out.append(
                f"donated leaf {shape} absent from input_output_alias — "
                f"the executable copies instead of updating in place")
    return out


def host_sync_violations(hlo_text: str, host_syncs: int) -> List[str]:
    """Host boundary ops in the body vs the declared budget (the result
    fetch itself is the one sync a budget of 1 allows)."""
    hits = []
    for ins in hlo_core.parse_instructions(hlo_text):
        if ins.opcode in HOST_BOUNDARY_OPCODES:
            hits.append(ins.opcode)
        elif ins.opcode == "custom-call" and any(
                m in ins.raw for m in HOST_CALLBACK_MARKERS):
            hits.append("host-callback")
    allowed = host_syncs - 1
    if len(hits) > allowed:
        return [
            f"{len(hits)} host boundary op(s) in the compiled body "
            f"({', '.join(hits)}) but the declared budget of "
            f"host_syncs={host_syncs} allows {allowed} beyond the result "
            f"fetch"]
    return []


# ------------------------------------------------------- lowering machinery
def abstractify(tree):
    """Concrete pytree -> ShapeDtypeStructs (lowering needs shapes only,
    not a second live copy of an engine pool)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        tree)


# numpy dtype name -> HLO element-type token (parse_entry_params canon)
_HLO_DTYPE = {
    "float32": "f32", "float64": "f64", "float16": "f16",
    "bfloat16": "bf16", "int8": "s8", "int16": "s16", "int32": "s32",
    "int64": "s64", "uint8": "u8", "uint16": "u16", "uint32": "u32",
    "uint64": "u64", "bool": "pred",
}


def _hlo_shape(x) -> str:
    dt = _HLO_DTYPE.get(jnp.result_type(x).name, jnp.result_type(x).name)
    return f"{dt}[{','.join(str(d) for d in jnp.shape(x))}]"


def donated_leaf_shapes(args: Sequence, spec: InvariantSpec) -> List[str]:
    """Canonical ``dtype[dims]`` string per leaf of the spec's donated
    arguments (one entry per leaf — multiplicity carries through to the
    alias-map multiset check)."""
    out: List[str] = []
    for pos in spec.donated_positions():
        out += [_hlo_shape(l) for l in jax.tree.leaves(args[pos])]
    return out


def lower_hlo(fn, args: Sequence, spec: InvariantSpec) -> str:
    """Optimized HLO text for ``fn(*args)`` — static args stay concrete,
    dynamic args are abstracted to shapes."""
    lowered_args = [a if i in set(spec.static_argnums) else abstractify(a)
                    for i, a in enumerate(args)]
    return fn.lower(*lowered_args).compile().as_text()


def check_callable(fn, args: Sequence, *, where: str,
                   protected_counts: Sequence[int] = (),
                   spec: Optional[InvariantSpec] = None) -> List[Violation]:
    """Run every HLO-plane check the callable's spec declares."""
    spec = spec or spec_of(fn)
    if spec is None:
        return [Violation("hlo", "no-spec", where,
                          "callable has no declared invariants")]
    text = lower_hlo(fn, args, spec)
    out: List[Violation] = []
    if spec.forbid_f32_roundtrip_on:
        out += [Violation("hlo", "f32-roundtrip", where, m)
                for m in f32_roundtrip_violations(text, protected_counts)]
    if spec.donated:
        expected = donated_leaf_shapes(args, spec)
        out += [Violation("hlo", "donation", where, m)
                for m in donation_violations(text, expected)]
    if spec.host_syncs is not None:
        out += [Violation("hlo", "host-syncs", where, m)
                for m in host_sync_violations(text, spec.host_syncs)]
    return out


# ------------------------------------------------------- engine scenarios
def kv_leaf_counts(pool: dict) -> List[int]:
    """Element counts of every non-f32 KV-cache leaf (f32 leaves are the
    INT8 path's dequant scales — those legitimately update in f32)."""
    from ..serving import state_pool as sp
    counts = []
    for entry in pool["caches"]:
        if sp.is_kv_entry(entry):
            counts += [_elem_count(tuple(l.shape))
                       for l in jax.tree.leaves(entry)
                       if l.dtype != jnp.float32]
    return counts


def _i32(shape=()):
    return jnp.zeros(shape, jnp.int32)


def engine_hot_paths(eng) -> Dict[str, Tuple[Callable, tuple]]:
    """name -> (jitted fn, representative concrete args). Args mirror the
    engine's own dispatch sites; the checker abstracts the dynamic ones."""
    table = jnp.asarray(eng.table)
    chunk = _i32((1, eng.scheduler.cfg.prefill_chunk))
    win_pre = eng._window(eng.scheduler.cfg.prefill_chunk)
    win_dec = eng._window(
        eng.scheduler.cfg.prefill_chunk + eng.scheduler.cfg.decode_steps)
    b = eng.n_slots
    tokens, active = _i32((b, 1)), jnp.zeros((b,), bool)
    eos, budget = _i32((b,)), _i32((b,))
    paths = {
        "engine.reset": (eng._reset_fn,
                         (eng.pool, _i32(), eng._template, _i32())),
        "engine.prefill": (eng._prefill_fn,
                           (eng.params, eng.pool, table, _i32(), chunk,
                            win_pre)),
        "engine.decode": (eng._decode_fn,
                          (eng.params, eng.pool, table, tokens, active,
                           eos, budget, win_dec)),
    }
    if eng.paged:
        dpool = eng.draft_pool if eng.spec is not None else None
        paths["engine.copy_page"] = (
            eng._copy_page_fn, (eng.pool, dpool, _i32(), _i32()))
    if eng.spec is not None:
        paths["engine.spec_prefill"] = (
            eng._spec_prefill_fn,
            (eng.spec.draft_params, eng.params, eng.draft_pool, eng.pool,
             table, _i32(), chunk, win_pre))
        paths["engine.spec"] = (
            eng.spec.spec_fn,
            (eng.spec.draft_params, eng.params, eng.draft_pool, eng.pool,
             table, tokens, tokens, active, eos, budget, eng.spec.k,
             eng.spec.cycles, win_dec))
    return paths


def check_engine(eng, scenario: str) -> List[Violation]:
    protected = kv_leaf_counts(eng.pool)
    if eng.spec is not None:
        protected = protected + kv_leaf_counts(eng.draft_pool)
    out: List[Violation] = []
    for name, (fn, args) in engine_hot_paths(eng).items():
        out += check_callable(fn, args, where=f"{name}[{scenario}]",
                              protected_counts=protected)
    return out


def check_retrace(eng, scenario: str, *,
                  prompt_lens: Sequence[int] = (5, 9, 17, 23, 31),
                  max_new: int = 8, seed: int = 0) -> List[Violation]:
    """Drive a scripted workload spanning several window buckets, then
    compare each hot path's distinct-lowering count to its declared
    ``max_lowerings`` (the ``max_seq / window_block`` bound)."""
    from ..serving import Request
    rng = np.random.RandomState(seed)
    vocab = eng.cfg.vocab_size
    reqs = [Request(prompt=rng.randint(0, vocab, n).tolist(),
                    max_new_tokens=max_new) for n in prompt_lens]
    eng.run(reqs, arrival_ticks=list(range(0, 3 * len(reqs), 3)))
    out: List[Violation] = []
    for name, (fn, _) in engine_hot_paths(eng).items():
        spec = spec_of(fn)
        if spec is None or spec.max_lowerings is None:
            continue
        size = getattr(fn, "_cache_size", lambda: None)()
        if size is None:
            continue    # older jax without the introspection hook
        if size > spec.max_lowerings:
            out.append(Violation(
                "hlo", "retrace-budget", f"{name}[{scenario}]",
                f"{size} distinct lowerings after the scripted workload, "
                f"declared max_lowerings={spec.max_lowerings} "
                f"(max_seq/window_block bucketing bound) — a dynamic "
                f"shape is leaking into a static argument"))
    return out


# --------------------------------------------------------------- driver API
def build_scenario(quantized_kv: bool, paged: bool, *, speculative=False,
                   arch: str = "qwen3-0.6b", n_slots: int = 2,
                   max_seq: int = 64, page_size: int = 8):
    """A small live engine for one cell of the KV matrix."""
    from .. import configs
    from ..models import lm
    from ..serving import Engine
    from ..sharding.ctx import default_ctx
    cfg = configs.get_smoke_config(arch)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    ctx = dataclasses.replace(default_ctx(), quantized_kv=quantized_kv)
    kw = dict(ctx=ctx, n_slots=n_slots, max_seq=max_seq)
    if paged:
        kw["page_size"] = page_size
    if speculative:
        from ..compress import compress
        art = compress(params, cfg, log=lambda s: None)
        kw.update(draft_params=art.params, draft_ctx=ctx,
                  draft_manifest=art.manifest)
    return Engine(params, cfg, **kw)


def scenario_name(quantized_kv: bool, paged: bool, speculative=False) -> str:
    return "+".join(["int8" if quantized_kv else "bf16",
                     "paged" if paged else "contig"]
                    + (["spec"] if speculative else []))


def run_hlo_plane(log=print) -> List[Violation]:
    """The full compiled-plane sweep ``scripts/check_static.py`` runs."""
    out: List[Violation] = []
    for quantized_kv in (False, True):
        for paged in (False, True):
            name = scenario_name(quantized_kv, paged)
            log(f"[hlo] scenario {name}: lowering declared hot paths")
            eng = build_scenario(quantized_kv, paged)
            out += check_engine(eng, name)
    # speculative dual-pool cell (spec_fn + fused spec prefill)
    name = scenario_name(True, False, speculative=True)
    log(f"[hlo] scenario {name}: lowering declared hot paths")
    eng = build_scenario(True, False, speculative=True)
    out += check_engine(eng, name)
    # retrace budget: one paged + one contiguous workload
    for paged in (False, True):
        name = scenario_name(False, paged)
        log(f"[hlo] scenario {name}: scripted retrace-budget workload")
        eng = build_scenario(False, paged)
        out += check_retrace(eng, name)
    return out
