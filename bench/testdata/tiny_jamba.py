"""Test-only reference of a Jamba-style hybrid, for the harness's tests of
a configuration with two kinds of layer: its program sizes and the work
its served model does. It has no forward pass; ``bench/test_configs.py``
uses it with ``tiny-jamba.json``.

Layer ``i`` attends where ``i % attn_layer_period == attn_layer_offset``
and is a Mamba layer otherwise; every layer has a dense SwiGLU MLP. A
Mamba layer's linears are in_proj (d, 2 d_in), x_proj (d_in, dt_rank +
2 d_state), dt_proj (dt_rank, d_in) and out_proj (d_in, d); its depthwise
causal convolution and selective scan are one ``selective_scan`` call,
whose state (float32, d_in x d_state, and the last d_conv - 1 inputs in
bf16) each request reads and writes once a call.
"""
from __future__ import annotations

import dataclasses

from bench import work


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    vocab: int
    attn_period: int
    attn_offset: int
    d_state: int
    d_conv: int

    @classmethod
    def from_hf(cls, m: dict) -> "Dims":
        return cls(m["num_hidden_layers"], m["hidden_size"],
                   m["num_attention_heads"], m["num_key_value_heads"],
                   m["vocab_size"], m["attn_layer_period"],
                   m["attn_layer_offset"], m["mamba_d_state"],
                   m["mamba_d_conv"])

    def kind(self, i: int) -> str:
        return ("attn" if i % self.attn_period == self.attn_offset
                else "mamba")


def program_sizes(m: dict) -> dict:
    """``mamba_dt_rank`` is not among them: the program works it out from
    d_model (its ``ssm.dt_rank`` 0), and ``work_model`` reads it from the
    served x_proj. ``moe`` None: every layer's MLP is dense."""
    dims = Dims.from_hf(m)
    return {"n_layers": dims.layers, "d_model": dims.d_model,
            "n_heads": dims.heads, "n_kv_heads": dims.kv_heads,
            "resolved_head_dim": dims.d_model // dims.heads,
            "d_ff": m["intermediate_size"], "vocab_size": dims.vocab,
            "norm_eps": m["rms_norm_eps"],
            "tie_embeddings": m["tie_word_embeddings"],
            "pattern": tuple(dims.kind(i) for i in range(dims.layers)),
            "moe": None, "ssm.d_state": dims.d_state,
            "ssm.d_conv": dims.d_conv, "ssm.expand": m["mamba_expand"]}


def _width(p) -> tuple:
    """(K, N) of a served linear, float or INT8."""
    return (p.w_q if hasattr(p, "w_q") else p["w"]).shape[-2:]


def work_model(dims: Dims, served, int8: bool) -> work.Model:
    blocks = served["blocks"]
    hd = dims.d_model // dims.heads
    layers = []
    for i in range(dims.layers):
        b = blocks[i % len(blocks)]
        if dims.kind(i) == "attn":
            mixer = [work.Linear(*_width(b["attn"][w]))
                     for w in ("wq", "wk", "wv", "wo")]
            attn = work.Attention(dims.heads, dims.kv_heads, hd,
                                  1 if int8 else 2, 4 if int8 else 0)
            extra = ()
        else:
            mb = b["mamba"]
            mixer = [work.Linear(*_width(mb[w]))
                     for w in ("in_proj", "x_proj", "dt_proj", "out_proj")]
            d_in = mixer[0].n // 2
            attn = None
            extra = (work.Extra(
                "selective_scan", "bf16",
                ops_per_token=2 * dims.d_conv * d_in
                + 7 * d_in * dims.d_state,
                bytes_per_token=3 * 2 * d_in,          # x, z in; y out
                bytes_per_row=2 * (4 * d_in * dims.d_state
                                   + 2 * (dims.d_conv - 1) * d_in)),)
        ffn = [work.Linear(*_width(b["mlp"][w]))
               for w in ("gate", "up", "down")]
        layers.append(work.Layer(attn, tuple(mixer + ffn), extra))
    return work.Model(tuple(layers), dims.d_model, dims.vocab,
                      8 if int8 else None)
