"""Whether the timed path served correct tokens.

After the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and holding the
longest of them, is run through the plain reference (``bench/reference``)
over each prompt and its served tokens. The number compared is the widest
gap by which a served token's reference logit lies below the reference's
best logit at that position: 0 where the server picked the reference's
greedy token, small where the two round a near tie differently, large
where the server computed something else. Beside it, two counts with the
limit 0: requests accepted in the window that never finished, and
finished requests whose answer is not exactly as long as asked (the
traffic sends no end-of-sequence id).

The control (``--control 1``) reads, at the same positions, the gap of the
token that the reference in the next lower precision puts first.
"""
from __future__ import annotations

import numpy as np

MIN_SAMPLE_TOKENS = 400
MAX_SAMPLE = 6


def finished(records):
    return [r for r in records if r["finish_reason"] in ("length", "eos")
            and r["tokens"]]


def sample(records, seed: int):
    """The longest finished request, then others in an order drawn from
    the seed, until MIN_SAMPLE_TOKENS served tokens or MAX_SAMPLE."""
    done = finished(records)
    if not done:
        return []
    done.sort(key=lambda r: (r["prompt_len"] + len(r["tokens"]),
                             str(r["id"])))
    out = [done.pop()]
    rng = np.random.default_rng([seed % 2 ** 64, 7])
    for i in rng.permutation(len(done)):
        if (len(out) >= MAX_SAMPLE or sum(len(r["tokens"]) for r in out)
                >= MIN_SAMPLE_TOKENS):
            break
        out.append(done[i])
    return out


def gaps(ref, params, dims, keep, prec, rec, length, served=None):
    """Per-position gaps of ``served`` (default: the served tokens) under
    ``prec``, and the tokens ``prec`` puts first, over one request. Every
    request is padded to ``length`` (the engine's ``max_seq``), so the
    reference compiles once; padding after a sequence cannot change its
    causal logits."""
    import jax.numpy as jnp
    prompt, toks = rec["prompt"], rec["tokens"]
    served = toks if served is None else served
    seq = np.asarray(prompt + toks[:-1], np.int32)
    n = len(toks)
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    seq = np.pad(seq, (0, length - len(seq)))
    at = np.pad(at, (0, length - n))
    sv = np.pad(np.asarray(served, np.int32), (0, length - n))
    g, first = ref.token_gaps(params, dims, jnp.asarray(seq),
                              jnp.asarray(at), jnp.asarray(sv), prec, keep)
    return np.asarray(g)[:n], np.asarray(first)[:n].tolist()


def counts(records, due_window):
    """(lost, wrong_length) over the requests due in the window."""
    t0, t1 = due_window
    due = [r for r in records if t0 <= r["due"] < t1]
    lost = sum(r["status"] == 200 and r["finish_reason"] not in
               ("length", "eos") for r in due)
    wrong = sum(r["finish_reason"] == "eos"
                or (r["finish_reason"] == "length"
                    and len(r["tokens"]) != r["max_new_tokens"])
                for r in due)
    return lost, wrong
