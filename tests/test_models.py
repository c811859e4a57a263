"""Model-component tests: Mamba chunked-vs-sequential, mLSTM chunk invariance,
MoE routing properties, CNN behaviours, data pipeline."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:      # bare container: skip property tests
    from _hypothesis_stub import given, settings, st

from repro import configs
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig, XLSTMConfig
from repro.data.synthetic import SyntheticImages, SyntheticTokens
from repro.models import cnn, moe, ssm, xlstm
from repro.sharding.ctx import default_ctx


# ------------------------------------------------------------------ mamba
def _mamba_cfg(chunk):
    return ModelConfig(name="t", family="ssm", n_layers=1, d_model=32,
                       n_heads=2, n_kv_heads=2, d_ff=0, vocab_size=64,
                       ssm=SSMConfig(d_state=8, d_conv=4, expand=2,
                                     chunk=chunk))


def test_mamba_chunk_size_invariance():
    """Chunked parallel scan must not depend on the chunk size."""
    p = ssm.mamba_init(jax.random.PRNGKey(0), _mamba_cfg(64))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 32), jnp.float32)
    y1, _ = ssm.mamba_forward(p, _mamba_cfg(64), x)
    y2, _ = ssm.mamba_forward(p, _mamba_cfg(8), x)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), rtol=2e-2,
                               atol=2e-2)


def test_mamba_decode_matches_parallel():
    cfg = _mamba_cfg(16)
    p = ssm.mamba_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 32), jnp.float32)
    y_par, _ = ssm.mamba_forward(p, cfg, x)
    state = ssm.init_mamba_state(1, cfg)
    outs = []
    for t in range(16):
        y, state = ssm.mamba_forward(p, cfg, x[:, t:t + 1], state)
        outs.append(y)
    y_seq = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_par, np.float32),
                               np.asarray(y_seq, np.float32),
                               rtol=3e-2, atol=3e-2)


# ------------------------------------------------------------------ xlstm
def _xlstm_cfg(chunk):
    return ModelConfig(name="t", family="ssm", n_layers=1, d_model=32,
                       n_heads=2, n_kv_heads=2, d_ff=0, vocab_size=64,
                       block_pattern=("mlstm",),
                       xlstm=XLSTMConfig(chunk=chunk))


def test_mlstm_chunk_size_invariance():
    p = xlstm.mlstm_init(jax.random.PRNGKey(0), _xlstm_cfg(64))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 32), jnp.bfloat16)
    y1, _ = xlstm.mlstm_forward(p, _xlstm_cfg(64), x)
    y2, _ = xlstm.mlstm_forward(p, _xlstm_cfg(8), x)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), rtol=6e-2,
                               atol=6e-2)


def test_slstm_state_carries():
    cfg = _xlstm_cfg(8)
    p = xlstm.slstm_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 32), jnp.bfloat16)
    y_full, _ = xlstm.slstm_forward(p, cfg, x)
    st = xlstm.init_slstm_state(1, cfg)
    y1, st = xlstm.slstm_forward(p, cfg, x[:, :6], st)
    y2, st = xlstm.slstm_forward(p, cfg, x[:, 6:], st)
    y_cat = jnp.concatenate([y1, y2], axis=1)
    np.testing.assert_allclose(np.asarray(y_full, np.float32),
                               np.asarray(y_cat, np.float32),
                               rtol=3e-2, atol=3e-2)


# ------------------------------------------------------------------ moe
def _moe_cfg(e=4, k=2):
    return ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                       n_heads=2, n_kv_heads=2, d_ff=48, vocab_size=64,
                       moe=MoEConfig(n_experts=e, experts_per_token=k,
                                     capacity_factor=2.0))


def test_moe_routes_and_mixes():
    cfg = _moe_cfg()
    p = moe.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32), jnp.bfloat16)
    ctx = default_ctx()
    out, aux = moe.moe_forward(p, cfg, x, ctx, with_aux=True)
    assert out.shape == x.shape
    assert float(aux["load_balance"]) > 0
    # a token's output depends on its own expert mix: different inputs differ
    x2 = x.at[0, 0].add(1.0)
    out2, _ = moe.moe_forward(p, cfg, x2, ctx, with_aux=False)
    assert float(jnp.max(jnp.abs(out2[0, 0] - out[0, 0]))) > 1e-4


def test_moe_capacity_drops_gracefully():
    cfg = dataclasses.replace(
        _moe_cfg(), moe=MoEConfig(n_experts=4, experts_per_token=2,
                                  capacity_factor=0.1))
    p = moe.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32), jnp.bfloat16)
    out, _ = moe.moe_forward(p, cfg, x, default_ctx())
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))


def test_moe_gate_weights_normalized():
    """Scaling every expert by c scales output by ~c (gates sum to 1)."""
    cfg = _moe_cfg()
    p = moe.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32), jnp.bfloat16)
    out1, _ = moe.moe_forward(p, cfg, x, default_ctx())
    p2 = dict(p, down={"w": p["down"]["w"] * 2})
    out2, _ = moe.moe_forward(p2, cfg, x, default_ctx())
    ratio = (np.asarray(out2, np.float32)
             / (np.asarray(out1, np.float32) + 1e-9))
    assert np.nanmedian(np.abs(ratio)) == pytest.approx(2.0, rel=0.2)


# ------------------------------------------------------------------ cnn
def test_cnn_shapes_and_train_mode():
    for arch in ("resnet18", "mobilenetv3s"):
        cfg = dataclasses.replace(configs.get_cnn_config(arch),
                                  width_mult=0.25)
        v = cnn.cnn_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        logits, new_st = cnn.cnn_apply(cfg, v, x, train=True)
        assert logits.shape == (2, 10)
        # train mode must update running stats
        diff = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                            v["stats"], new_st)
        assert max(jax.tree.leaves(diff)) > 0


# ------------------------------------------------------------------ data
def test_synthetic_images_learnable_structure():
    d = SyntheticImages(200, seed=0)
    assert d.images.shape == (200, 32, 32, 3)
    # same-class images correlate more than cross-class
    same = cross = n_same = n_cross = 0.0
    for i in range(0, 50):
        for j in range(i + 1, 50):
            c = float(np.mean(d.images[i] * d.images[j]))
            if d.labels[i] == d.labels[j]:
                same += c
                n_same += 1
            else:
                cross += c
                n_cross += 1
    assert same / n_same > cross / n_cross


def test_synthetic_tokens_markov():
    d = SyntheticTokens(vocab=64, seq_len=33, n_seqs=16, seed=0)
    assert d.seqs.shape == (16, 33)
    assert d.seqs.max() < 64
    b = next(d.batches(4))
    assert b["tokens"].shape == (4, 33)


@given(vocab=st.sampled_from([16, 64]), det=st.floats(0.5, 0.95))
@settings(max_examples=5, deadline=None)
def test_markov_determinism_ceiling(vocab, det):
    d = SyntheticTokens(vocab=vocab, seq_len=200, n_seqs=4, seed=1,
                        determinism=det)
    # empirical top-transition frequency approaches `det`
    from collections import Counter, defaultdict
    trans = defaultdict(Counter)
    for row in d.seqs:
        for a, b in zip(row[:-1], row[1:]):
            trans[int(a)][int(b)] += 1
    tops = [max(c.values()) / sum(c.values()) for c in trans.values()
            if sum(c.values()) >= 20]
    if tops:
        assert abs(np.mean(tops) - det) < 0.2


# -------------------------------------------------------------- attention
@pytest.mark.parametrize("qk_norm", [True, False], ids=["qk_norm", "plain"])
def test_qk_norm_projections_reach_the_norm_unrounded(qk_norm):
    """With qk-norm, a bf16 model's q and k projections leave the matmul as
    f32 and reach the norm with no bf16 rounding between: XLA on a TPU skips
    such a rounding where it fuses the projection with the norm and keeps
    it where it does not, so a rounded projection would make one layer's K
    depend on the program around it (the paged engine's against serial
    decode's). v, and q/k without qk-norm, stay bf16."""
    from repro.models import attention as A
    from repro.models import layers as L
    cfg = dataclasses.replace(configs.get_smoke_config("qwen3-0.6b"),
                              qk_norm=qk_norm)
    p = A.attention_init(jax.random.PRNGKey(0), cfg)
    x = jnp.ones((1, 4, cfg.d_model), L.COMPUTE_DTYPE)
    positions = jnp.arange(4, dtype=jnp.int32)[None]
    closed = jax.make_jaxpr(
        lambda p, x: A.attention_forward(p, cfg, x, positions))(p, x)
    leaves = jax.tree_util.tree_flatten_with_path((p, x))[0]
    weight = {v: jax.tree_util.keystr(path)
              for (path, _), v in zip(leaves, closed.jaxpr.invars)}
    source = {}                 # a weight's dtype conversions lead back to it
    out = {}
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            src = eqn.invars[0]
            if src in weight or src in source:
                source[eqn.outvars[0]] = source.get(src, src)
        elif eqn.primitive.name == "dot_general":
            w = eqn.invars[1]
            w = source.get(w, w)
            if w in weight:
                out[weight[w].split("'")[1]] = eqn.outvars[0].aval.dtype
    want = jnp.float32 if qk_norm else L.COMPUTE_DTYPE
    assert out == {"wq": want, "wk": want, "wv": L.COMPUTE_DTYPE,
                   "wo": L.COMPUTE_DTYPE}, out
