"""The comparison that decides ``correct``, shown to fail, at a test size.

    python -m pytest -q bench/test_correctness.py

Each test drives a whole run of the harness on the program's qwen3-0.6b
smoke preset (``bench/testdata``) on the CPU, skipping the harness's look
for a chip: set-up, the HTTP front door, the load generator child, the
window, the reference. A sound run is correct; the control (the reference
in the next lower precision, put in the program's place) is not; and a run
with the timed path broken underneath is not, for each fault a serving
cell can have: a decode step that leaves its state unchanged (the KV
cache write dropped), half of the batch left out of the decode dispatch,
and a token altered where it is produced. The fourth fault of the list,
the exchange between chips, has no place in a one-chip cell.
"""
import json
import pathlib
import sys
import time

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent))

from bench import run  # noqa: E402

SECONDS = 3.0
SEED = 2 ** 31 + 77


def cell(name):
    td = BENCH / "testdata"
    return {"name": f"tiny-{name}", "chips": 1,
            "config": json.loads((td / f"tiny-{name}.json").read_text()),
            "mix": json.loads((td / "tiny-chat.json").read_text()),
            "end_to_end": [{"name": "tpot_p95_ms", "unit": "ms"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def one_run(name, control=False):
    return run.run_cell(cell(name), SEED, SECONDS, 0, control=control,
                        check_chip=False, t_start=time.monotonic())


def test_sound_run_is_correct_and_control_is_not():
    res = one_run("hqp", control=True)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0
    assert res["compared"]["pruned_units_diff"]["value"] == 0
    assert res["control"]["correct"] is False, res["control"]
    assert res["control"]["max_gap"] > res["compared"]["max_gap"]["limit"]


def _drop_kv_write(monkeypatch):
    from repro.models import attention
    monkeypatch.setattr(attention, "update_kv_cache",
                        lambda cache, k, v, pos, pages=None: cache)


def _half_batch(monkeypatch):
    from repro.serving import engine

    real = engine.Engine.__init__

    def init(self, *a, **kw):
        real(self, *a, **kw)
        fn = self._decode_fn

        def half(params, pool, table, tokens, active, *rest):
            import jax.numpy as jnp
            keep = jnp.arange(active.shape[0]) % 2 == 0
            return fn(params, pool, table, tokens, active & keep, *rest)
        self._decode_fn = half

    monkeypatch.setattr(engine.Engine, "__init__", init)


def _alter_token(monkeypatch):
    from repro.serving import engine
    real = engine.Engine._emit

    def emit(self, slot, tok, finished):
        return real(self, slot, (tok + 1) % self.cfg.vocab_size, finished)

    monkeypatch.setattr(engine.Engine, "_emit", emit)


@pytest.mark.parametrize("fault", [_drop_kv_write, _half_batch,
                                   _alter_token])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    monkeypatch.setattr(run, "DRAIN_S", 8.0)
    fault(monkeypatch)
    res = one_run("hqp")
    assert res["correct"] is False, res["compared"]
