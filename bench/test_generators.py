"""Self-tests of the traffic generators and the metric arithmetic.

    python -m pytest -q bench/test_generators.py
"""
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent))

from bench import correctness, stats  # noqa: E402
from bench.run import end_to_end, load_module  # noqa: E402

MIXES = ["chat"]


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def gen(m):
    return load_module(BENCH / "traffic" / f"{m['kind']}.py")


def sizes(plan):
    return (sorted(len(r["prompt"]) for r in plan["requests"]),
            sorted(r["max_new_tokens"] for r in plan["requests"]))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_trace(name):
    m = mix(name)
    assert gen(m).generate(m, 2 ** 33 + 5, 12, 151936) == \
        gen(m).generate(m, 2 ** 33 + 5, 12, 151936)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_other_trace_same_sizes(name):
    m = mix(name)
    a = gen(m).generate(m, 1, 12, 151936)
    b = gen(m).generate(m, 2, 12, 151936)
    assert a != b
    assert sizes(a) == sizes(b)


@pytest.mark.parametrize("name", ["chat"])
def test_open_loop_rate_and_window(name):
    m = mix(name)
    plan = gen(m).generate(m, 9, 40, 151936)
    due = [r["due"] for r in plan["requests"]]
    assert len(due) == round(m["rate_per_s"] * 40)
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 40
    lens = [len(r["prompt"]) for r in plan["requests"]]
    assert min(lens) >= m["prompt_len"]["min"]
    assert max(lens) <= m["prompt_len"]["max"]


def test_shorter_window_draws_a_prefix_of_sizes():
    m = mix("chat")
    short = gen(m).generate(m, 3, 10, 151936)
    full = gen(m).generate(m, 3, 40, 151936)
    assert {len(r["prompt"]) for r in short["requests"]} <= \
        {len(r["prompt"]) for r in full["requests"]}


@pytest.mark.parametrize("name", MIXES)
def test_longest_request_fits_max_seq(name):
    m = mix(name)
    plan = gen(m).generate(m, 4, 40, 151936)
    top = max(len(r["prompt"]) + r["max_new_tokens"]
              for r in plan["requests"])
    assert top <= m["engine"]["max_seq"]
    assert m["prompt_len"]["max"] + m["output_len"]["max"] <= \
        m["engine"]["max_seq"]


@pytest.mark.parametrize("q,want", [(50, 3), (95, 5), (100, 5), (1, 1),
                                    (20, 1), (21, 2)])
def test_nearest_rank_over_all_values(q, want):
    assert stats.nearest_rank([5, 1, 4, 2, 3], q) == want


def test_nearest_rank_empty():
    assert stats.nearest_rank([], 95) is None


def record(due, first=None, tokens=2, reason="length", status=200):
    toks = list(range(tokens)) if first is not None else []
    return {"id": due, "due": due, "status": status, "t_first": first,
            "t_last": None if first is None else first + 0.01 * (tokens - 1),
            "finish_reason": reason if first is not None else None,
            "tokens": toks, "token_times": [first] * len(toks)
            if first is not None else [], "max_new_tokens": tokens,
            "prompt_len": 4}


def test_request_without_first_token_counts_failed_and_late():
    recs = [record(0.0, 0.1), record(0.5, 0.7), record(1.0)]
    e2e, attempted, failed = end_to_end(recs, (0.0, 2.0), drain_end=62.0)
    assert (attempted, failed) == (3, 1)
    assert e2e["ttft_p95_ms"] == pytest.approx(61.0e3)
    assert e2e["ttft_p50_ms"] == pytest.approx(200.0)
    assert correctness.counts(recs, (0.0, 2.0)) == (1, 0)


def test_requests_due_after_window_are_not_counted():
    recs = [record(0.0, 0.1), record(2.5, 2.6)]
    _, attempted, _ = end_to_end(recs, (0.0, 2.0), drain_end=62.0)
    assert attempted == 1


def test_shed_request_is_failed_not_lost():
    recs = [record(0.0, 0.1), dict(record(0.2), status=429)]
    _, _, failed = end_to_end(recs, (0.0, 2.0), drain_end=62.0)
    assert failed == 1
    assert correctness.counts(recs, (0.0, 2.0)) == (0, 0)


def test_short_answer_is_wrong_length():
    r = record(0.0, 0.1, tokens=3)
    r["tokens"] = r["tokens"][:2]
    assert correctness.counts([r], (0.0, 1.0)) == (0, 1)


def test_sample_holds_the_longest():
    recs = [dict(record(i * 0.1, i * 0.1 + 0.05, tokens=5 + i),
                 prompt_len=10) for i in range(20)]
    s = correctness.sample(recs, seed=11)
    assert s[0]["id"] == recs[-1]["id"]
    assert s == correctness.sample(recs, seed=11)


@pytest.mark.parametrize("name", MIXES)
def test_setup_warms_every_length_of_the_plan(name):
    import numpy as np
    m = mix(name)
    plan = gen(m).generate(m, 2 ** 31 + 9, 14, 151936)
    lens = {len(r["prompt"]) for r in plan["requests"]}
    assert len(lens) > len(plan["requests"]) // 2     # token by token
    (_, [pre]), (_, [[(first, n_dec)]]) = gen(m).setup_stages(
        plan, m, 151936, np.random.default_rng(0))
    assert {len(p) for p, _ in pre} == lens
    assert len(first) + n_dec == max(lens) + max(
        r["max_new_tokens"] for r in plan["requests"])
    other = gen(m).generate(m, 7, 14, 151936)
    assert gen(m).setup_stages(other, m, 151936, np.random.default_rng(0)) \
        == gen(m).setup_stages(plan, m, 151936, np.random.default_rng(0))
