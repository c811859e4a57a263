"""A configuration brings its own program-config check, work counts and
kernel classes, in its file and its reference module.

    python -m pytest -q bench/test_configs.py

``EARLIER`` holds the counts that the harness's fixed-shape, Qwen3-like
work model gave on ``SPANS`` before the counts moved into the reference
module; the qwen3 reference's ``work_model`` has to give them to the last
bit. A configuration with two kinds of layer, ``testdata/tiny-jamba.json``
with its test-only reference ``testdata/tiny_jamba.py``, is checked and
counted by the harness as it stands. It is the program's
jamba-1.5-large-398b smoke preset with its routed experts taken out (a
Mamba layer, then an attention layer, each with a dense MLP); ``dense``
takes them out where the tests ask the program for its preset.
"""
import collections
import dataclasses
import hashlib
import json
import pathlib
import sys
import types

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

from bench import run, weights, work  # noqa: E402

TESTDATA = BENCH / "testdata"
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


def span(name, uid, t0, t1, **args):
    return {"type": "span", "name": name, "uid": uid, "t0": t0, "t1": t1,
            "args": args}


# window [0.9, 2.0): uid 3 lies before it, the last decode dispatch after
SPANS = [
    span("request", 1, 0.9, 2.0, prompt_len=300),
    span("request", 2, 1.2, 2.0, prompt_len=40),
    span("request", 3, 0.1, 0.8, prompt_len=20),
    span("prefill", 3, 0.4, 0.5, lo=0, hi=20, tokens=1),
    span("decode", 3, 0.5, 0.6, tokens=4),
    span("prefill", 1, 0.95, 1.0, lo=0, hi=128),
    span("prefill", 1, 1.0, 1.1, lo=128, hi=256),
    span("prefill", 1, 1.1, 1.2, lo=256, hi=300, tokens=1),
    span("prefill", 2, 1.2, 1.25, lo=0, hi=40, tokens=1),
    span("decode", 1, 1.3, 1.4, tokens=4),
    span("decode", 2, 1.3, 1.4, tokens=4),
    span("decode", 1, 1.4, 1.5, tokens=4),
    span("decode", 2, 1.4, 1.5, tokens=2),
    span("decode", 1, 1.5, 1.6, tokens=3),
    span("decode", 1, 2.1, 2.2, tokens=4),
]
PROMPT_LEN = {s["uid"]: s["args"]["prompt_len"] for s in SPANS
              if s["name"] == "request"}
WINDOW = (0.9, 2.0)


EARLIER = {'bf16': {'attention': [(1893728256.0, 44040192.0),
                        (5651824640.0, 58720256.0),
                        (2810773504.0, 44498944.0),
                        (188088320.0, 13762560.0),
                        (78446592.0, 39682048.0),
                        (78905344.0, 39911424.0),
                        (79364096.0, 40140800.0),
                        (79822848.0, 40370176.0),
                        (80281600.0, 40599552.0),
                        (80740352.0, 40828928.0),
                        (70418432.0, 35438592.0),
                        (70647808.0, 35553280.0),
                        (70877184.0, 35667968.0),
                        (71106560.0, 35782656.0),
                        (71335936.0, 35897344.0)],
          'least_attention': 0.0007092731623931624,
          'least_w8a8': 0.0,
          'matmul': [],
          'model_ops': {'bf16': 331735465984.0},
          'quantize': [],
          'tokens': 357},
 'int8': {'attention': [(1893728256.0, 36929536.0),
                        (5651824640.0, 44498944.0),
                        (2810773504.0, 27833344.0),
                        (188088320.0, 11540480.0),
                        (78446592.0, 20683264.0),
                        (78905344.0, 20801536.0),
                        (79364096.0, 20919808.0),
                        (79822848.0, 21038080.0),
                        (80281600.0, 21156352.0),
                        (80740352.0, 21274624.0),
                        (70418432.0, 18384128.0),
                        (70647808.0, 18443264.0),
                        (70877184.0, 18502400.0),
                        (71106560.0, 18561536.0),
                        (71335936.0, 18620672.0)],
          'least_attention': 0.0004141489230769231,
          'least_w8a8': 0.008376855794871319,
          'matmul': [((2097152.0, 1055748.0), 280),
                     ((4194304.0, 1058824.0), 336),
                     ((4194304.0, 2105348.0), 140),
                     ((4194304.0, 2110468.0), 140),
                     ((5662720.0, 2840273.0), 140),
                     ((5662720.0, 2848978.0), 280),
                     ((8388608.0, 2109448.0), 168),
                     ((8388608.0, 2115592.0), 168),
                     ((11325440.0, 2845090.0), 168),
                     ((11325440.0, 2855536.0), 336),
                     ((83886080.0, 1175712.0), 56),
                     ((92274688.0, 1188016.0), 56),
                     ((167772160.0, 2265248.0), 28),
                     ((167772160.0, 2310304.0), 28),
                     ((184549376.0, 2281648.0), 28),
                     ((184549376.0, 2330800.0), 28),
                     ((226508800.0, 3028136.0), 28),
                     ((226508800.0, 3104740.0), 56),
                     ((249159680.0, 3047404.0), 28),
                     ((249159680.0, 3130972.0), 56),
                     ((268435456.0, 1446400.0), 112),
                     ((536870912.0, 2626048.0), 56),
                     ((536870912.0, 2761216.0), 56),
                     ((724828160.0, 3452032.0), 56),
                     ((724828160.0, 3681844.0), 112)],
          'model_ops': {'bf16': 17288495104.0, 'int8': 295592435712.0},
          'quantize': [((0.0, 3076.0), 700), ((0.0, 6148.0), 140),
                       ((0.0, 6152.0), 840), ((0.0, 8299.0), 140),
                       ((0.0, 12296.0), 168), ((0.0, 16598.0), 168),
                       ((0.0, 123040.0), 140), ((0.0, 135344.0), 140),
                       ((0.0, 245920.0), 28), ((0.0, 270512.0), 28),
                       ((0.0, 331960.0), 28), ((0.0, 365156.0), 28),
                       ((0.0, 393728.0), 280), ((0.0, 786944.0), 56),
                       ((0.0, 1062272.0), 56)],
          'tokens': 357}}


def config(name):
    return json.loads((TESTDATA / f"{name}.json").read_text())


def reference_of(cfg):
    """A configuration's reference: ``bench/reference`` for the benchmark's
    own, ``testdata`` for the test-only one."""
    for d in (BENCH / "reference", TESTDATA):
        path = d / f"{cfg['reference']}.py"
        if path.exists():
            return run.load_module(path)
    raise FileNotFoundError(cfg["reference"])


def dense_jamba(real):
    """The program's preset getter, with the jamba preset's routed experts
    taken out."""
    def get(arch):
        pc = real(arch)
        return (dataclasses.replace(pc, moe=None)
                if arch == "jamba-1.5-large-398b" else pc)
    return get


@pytest.fixture
def dense(monkeypatch):
    from repro import configs
    monkeypatch.setattr(configs, "get_smoke_config",
                        dense_jamba(configs.get_smoke_config))


def least(calls, prec):
    return work.least_time(calls, PEAKS["flops"][prec],
                           PEAKS["hbm_bytes_per_s"])[0]


# ------------------------------------------------------------ qwen3
@pytest.mark.parametrize("precision", ["int8", "bf16"])
def test_qwen3_counts_are_the_earlier_counts(precision):
    """qwen3-0.6b's published widths; as INT8, an MLP width of 2765, any
    width the artifact could compact to, chosen to pin the arithmetic (not
    the width a served artifact has); as bf16, the float width 3072."""
    cfg = json.loads((BENCH / "configs" / "qwen3-0.6b-hqp.json")
                     .read_text())
    ref = reference_of(cfg)
    int8 = precision == "int8"
    w = types.SimpleNamespace(shape=(28, 1024, 2765 if int8 else 3072))
    gate = types.SimpleNamespace(w_q=w) if int8 else {"w": w}
    m = ref.work_model(ref.Dims.from_hf(cfg["model"]),
                       {"blocks": ({"mlp": {"gate": gate}},)}, int8)
    c = work.count(m, SPANS, PROMPT_LEN, *WINDOW)
    want = EARLIER[precision]
    assert c.of("attention") == want["attention"]
    for cls, key in (("w8a8_matmul", "matmul"),
                     ("w8a8_quantize", "quantize")):
        assert sorted(collections.Counter(c.of(cls)).items()) == want[key]
    assert c.model_ops == want["model_ops"]
    assert c.tokens == want["tokens"]
    assert least(c.of("attention"), "bf16") == want["least_attention"]
    assert least(c.of("w8a8_matmul", "w8a8_quantize"),
                 "int8") == want["least_w8a8"]


def test_qwen3_weights_are_the_earlier_weights():
    """``weights.make`` draws what it drew before the reference modules
    took the work counts (sha256 of every leaf's bytes, on the CPU,
    tiny-hqp's layout)."""
    import jax
    from repro import configs
    from repro.models import lm
    pc = configs.get_smoke_config("qwen3-0.6b")
    layout = jax.eval_shape(lambda k: lm.init_params(k, pc),
                            jax.random.PRNGKey(0))
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(weights.make(layout, 2 ** 31 + 77, 0.3)):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == ("74d66937e119d683ca39048181703608"
                             "a21e6806f2fe0789c1176ac1437b9158")


# ------------------------------------------------------------ program sizes
@pytest.mark.parametrize("name", ["tiny-hqp", "tiny-jamba"])
def test_program_config_accepts_the_file(name, dense):
    cfg = config(name)
    pc = run.program_config(cfg, reference_of(cfg))
    assert pc.n_layers == cfg["model"]["num_hidden_layers"]


@pytest.mark.parametrize("key,value", [
    ("mamba_d_state", 16), ("intermediate_size", 128),
    ("attn_layer_offset", 0), ("num_key_value_heads", 4),
    ("mamba_expand", 4)])
def test_program_config_refuses_a_changed_size(key, value, dense):
    cfg = config("tiny-jamba")
    cfg["model"][key] = value
    with pytest.raises(SystemExit, match="is not the file's"):
        run.program_config(cfg, reference_of(cfg))


def test_program_config_refuses_a_program_with_routed_experts():
    """The preset as the program has it routes layer 1's MLP over 4
    experts; the dense file refuses it."""
    cfg = config("tiny-jamba")
    with pytest.raises(SystemExit, match="'moe': None"):
        run.program_config(cfg, reference_of(cfg))


def test_program_config_refuses_a_qwen3_program_without_qk_norm(
        monkeypatch):
    from repro import configs
    real = configs.get_smoke_config
    monkeypatch.setattr(configs, "get_smoke_config", lambda arch:
                        dataclasses.replace(real(arch), qk_norm=False))
    cfg = config("tiny-hqp")
    with pytest.raises(SystemExit, match="qk_norm"):
        run.program_config(cfg, reference_of(cfg))


def test_program_config_refuses_an_empty_map():
    cfg = config("tiny-hqp")
    with pytest.raises(SystemExit, match="no size"):
        run.program_config(cfg, types.SimpleNamespace(
            program_sizes=lambda m: {}))


# ------------------------------------------------------------ two kinds
@pytest.fixture(scope="module")
def jamba():
    """(file, reference, dims, served layout) of the two-kind file."""
    import jax
    from repro import configs
    from repro.models import lm
    cfg = config("tiny-jamba")
    ref = reference_of(cfg)
    pc = dense_jamba(configs.get_smoke_config)(cfg["arch"])
    layout = jax.eval_shape(lambda k: lm.init_params(k, pc),
                            jax.random.PRNGKey(0))
    return cfg, ref, ref.Dims.from_hf(cfg["model"]), layout


def test_attention_is_counted_in_the_attention_layer_only(jamba):
    cfg, ref, dims, served = jamba
    m = ref.work_model(dims, served, True)
    attn = [layer.attention for layer in m.layers]
    assert attn[0] is None and attn[1] is not None
    every = dataclasses.replace(m, layers=tuple(
        dataclasses.replace(layer, attention=attn[1]) for layer in m.layers))
    one = work.count(m, SPANS, PROMPT_LEN, *WINDOW).of("attention")
    both = work.count(every, SPANS, PROMPT_LEN, *WINDOW).of("attention")
    assert len(one) == len(both) == 15
    assert one == [(o / 2, b / 2) for o, b in both]


def test_mamba_layer_linears_have_their_own_shapes(jamba):
    """One decode step of one row: a W8A8 call of 2 K N operations for
    each linear, from the file's sizes."""
    cfg, ref, dims, served = jamba
    m = cfg["model"]
    d, f = m["hidden_size"], m["intermediate_size"]
    d_in, r, s = m["mamba_expand"] * d, m["mamba_dt_rank"], m["mamba_d_state"]
    mlp = [(d, f), (d, f), (f, d)]
    mamba = [(d, 2 * d_in), (d_in, r + 2 * s), (r, d_in), (d_in, d)] + mlp
    attn = [(d, d), (d, d // 2), (d, d // 2), (d, d)] + mlp
    want = sorted(2.0 * kk * n for kk, n in mamba + attn)
    spans = [span("request", 9, 0.0, 1.0, prompt_len=10),
             span("decode", 9, 0.1, 0.2, tokens=1)]
    c = work.count(ref.work_model(dims, served, True), spans, {9: 10},
                   0.0, 1.0)
    assert sorted(o for o, _ in c.of("w8a8_matmul")) == want
    assert c.model_ops["int8"] == sum(want)
    assert c.tokens == 1


def test_extra_class_is_one_call_a_step(jamba):
    """The Mamba layer's scan: one ``selective_scan`` call each step, at
    the precision the reference names, its ops in the model's."""
    cfg, ref, dims, served = jamba
    c = work.count(ref.work_model(dims, served, False), SPANS, PROMPT_LEN,
                   *WINDOW)
    scan = c.of("selective_scan")
    assert len(scan) == len(c.of("attention")) == 15
    assert c.precision["selective_scan"] == "bf16"
    d_in = cfg["model"]["mamba_expand"] * cfg["model"]["hidden_size"]
    per_token = 2 * 4 * d_in + 7 * d_in * 8
    assert sum(o for o, _ in scan) == per_token * c.tokens
    assert "w8a8_matmul" not in c.calls
