"""Open-loop traffic: independent users, each request sent when it is due.

Parameters (the mix's JSON file): ``rate_per_s``; ``interarrival`` with a
``cv`` (gamma gaps, coefficient of variation); ``prompt_len`` and
``output_len``, each a lognormal with ``median`` and ``sigma`` truncated to
[``min``, ``max``], drawn token by token; ``base_seed``.

The arrival schedule and the multiset of sizes are fixed, drawn from
``base_seed``; the run's ``--seed`` deals the sizes to the arrivals in its
own order and draws every prompt token. So every seed sends the same work,
``rate_per_s * seconds`` requests due in the window in the same bursts,
while the requests in each burst and every prompt are new. (With the gaps
in another order per seed as well, the 95th percentile of TTFT on a v5e
ranged from 165 to 993 ms over six seeds: where the long bursts fall
decides the tail.) Gaps, prompt and output sizes come from streams of
their own, so a shorter window draws a prefix of each.

The engine compiles a prefill for each distinct prompt length and a decode
for each 16-position window, so set-up (``setup_stages``) warms exactly
the lengths and windows of the run's own plan, and that cost shows in
``setup_s``.
"""
from __future__ import annotations

import math

import numpy as np


def _ppf(spec: dict, u):
    """Truncated-lognormal quantiles for u in [0, 1], rounded to tokens."""
    from statistics import NormalDist
    nd = NormalDist()
    mu, s = math.log(spec["median"]), spec["sigma"]
    lo = nd.cdf((math.log(spec["min"]) - mu) / s)
    hi = nd.cdf((math.log(spec["max"]) - mu) / s)
    x = [math.exp(mu + s * nd.inv_cdf(lo + (hi - lo) * float(v)))
         for v in np.atleast_1d(u)]
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> dict:
    n = max(1, round(mix["rate_per_s"] * seconds))
    base = [np.random.default_rng([mix["base_seed"], i]) for i in range(3)]
    k = 1.0 / mix["interarrival"]["cv"] ** 2
    gaps = base[0].gamma(k, 1.0, n)
    prompt_len = _ppf(mix["prompt_len"], base[1].random(n))
    output_len = _ppf(mix["output_len"], base[2].random(n))
    rng = np.random.default_rng(seed % 2 ** 64)
    gaps *= seconds / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    prompt_len = rng.permutation(prompt_len)
    output_len = rng.permutation(output_len)
    requests = [{"id": i, "due": float(due[i]),
                 "prompt": rng.integers(0, vocab, int(prompt_len[i])).tolist(),
                 "max_new_tokens": int(output_len[i])} for i in range(n)]
    return {"mode": "open", "requests": requests}


def setup_stages(plan: dict, mix: dict, vocab: int, rng) -> list:
    """What set-up sends through the engine before the window, so that
    every shape the window needs is compiled: one prefill of each distinct
    prompt length of the plan (random tokens, so the prefix cache never
    serves the window), then one request whose decode passes every
    position from the shortest prompt to the longest prompt plus the
    longest output, which meets every decode window of any dealing of
    these sizes. So set-up does the same work for every seed."""
    reqs = plan["requests"]
    sizes = sorted({len(r["prompt"]) for r in reqs})
    top = min(sizes[-1] + max(r["max_new_tokens"] for r in reqs),
              mix["engine"]["max_seq"])
    return [("warm_prefill",
             [[(rng.integers(0, vocab, n).tolist(), 1) for n in sizes]]),
            ("warm_decode",
             [[(rng.integers(0, vocab, sizes[0]).tolist(), top - sizes[0])]])]
